"""Compiled-program cache for the serving path.

One executable per ``(callable, bucket shape, dtype, mesh)`` — the serving
analogue of the resharding plan cache (``core/resharding.py``): a bounded
key space (the bucket ladder is finite), explicit hit/miss/compile
counters, and a hard observable for the steady-state guarantee that
traffic triggers **zero recompiles** after warmup (asserted in
``tests/test_serve.py``, same spirit as ``RESPLIT_AUDIT.json``).

The implementation was generalized into
:mod:`heat_tpu.utils.program_cache` when the op-chain fusion engine
(:mod:`heat_tpu.core.fusion`) needed the same contract; this module keeps
every historical ``heat_tpu.serve.program_cache`` import path working AND
pins the mirrored-counter namespace to ``serve.program_hits`` /
``_misses`` / ``_compiles`` regardless of the cache's display name — the
adapters build executors with per-model cache names ("transformer", the
estimator class), and the ladder's per-test ``serve_program_compiles``
log line (the per-process executable budget correlation) must keep counting all of them under
one family, as it always has.
"""

from __future__ import annotations

from ..utils.program_cache import ProgramCache as _ProgramCache

__all__ = ["ProgramCache"]


class ProgramCache(_ProgramCache):
    """Serving-path program cache: display name is per-model, counters
    always aggregate under ``serve.program_*``."""

    def __init__(self, name: str = "serve", aot: bool = True):
        super().__init__(name=name, aot=aot, counter_prefix="serve")
