"""The one place ``shard_map`` is imported from (52 modules do), kept so
those imports stay as they are. There is one supported JAX (``jax>=0.9``,
``pyproject.toml``): the top-level ``jax.shard_map`` with ``check_vma``.
"""

from jax import shard_map

__all__ = ["shard_map"]
