"""The main path's kernels, compiled for the chip without the chip.

The TPU's compiler is installed in the CPU sandbox and compiles for a
DESCRIBED v5e (``jax.experimental.topologies``): what it refuses here
(unaligned slices, too much scoped VMEM, a kernel that cannot be
partitioned) the chip would refuse too, at no chip time. Nothing runs, so
these say nothing about results or speed — ``chip_smoke.py`` does that.

The topology is described inside a module fixture, never at import: only one
process at a time may load libtpu, and under pytest-xdist every worker
imports this file but only one runs it. All such tests stay in THIS file.

The kernels ask ``jax.default_backend()`` (the CPU, during a described
compile) whether to interpret; the ``on_chip`` fixture steers that here, in
the test — the program has no option for it.
"""

import math
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from heat_tpu.core import pallas_kernels as pk
from heat_tpu.core._compat import shard_map


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        t = topologies.get_topology_desc(platform="tpu",
                                         topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no libtpu / lock held elsewhere
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described compile is written to the persistent cache but cannot be
    # read back without a chip: keep the cache out of these compiles
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield t
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def on_chip(monkeypatch):
    """Take the branch the chip takes: Mosaic, not the interpreter."""
    monkeypatch.setattr(pk, "_interpret", lambda: False)
    pk.set_pallas(True)
    yield
    pk.set_pallas(None)


def _compiled_text(fn, *shapes):
    text = jax.jit(fn).lower(*shapes).compile().as_text()
    assert "tpu_custom_call" in text, "no Mosaic kernel in the program"
    return text


def test_topology_is_the_v5e(topo):
    assert len(topo.devices) == 4
    assert topo.devices[0].platform == "tpu"
    assert topo.devices[0].device_kind == "TPU v5 lite"


@pytest.mark.parametrize("dtype,n,d", [(jnp.float32, 8192, 18),
                                       (jnp.bfloat16, 8192, 64)])
def test_cdist_tile_compiles(on_chip, one_chip, dtype, n, d):
    x = jax.ShapeDtypeStruct((n, d), dtype, sharding=one_chip)
    _compiled_text(lambda a, b: pk.cdist_tile(a, b, sqrt=True), x, x)


# (8, 16, 1024, 64): chip_smoke.py's train phase; (2, 16, 2048, 128): the
# benchmark's train cell (pythia-1.4b-d8.train-s2048), heads of 128
_FLASH_SHAPES = [(8, 16, 1024, 64), (2, 16, 2048, 128)]


def _qkv(sharding, shape=_FLASH_SHAPES[0]):
    return [jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=sharding)] * 3


@pytest.mark.parametrize("shape", _FLASH_SHAPES, ids=str)
def test_flash_forward_compiles(on_chip, one_chip, shape):
    _compiled_text(lambda q, k, v: pk.flash_attention(q, k, v, causal=True),
                   *_qkv(one_chip, shape))


@pytest.mark.parametrize("shape", _FLASH_SHAPES, ids=str)
def test_flash_backward_compiles(on_chip, one_chip, shape):
    """``jax.grad`` through the kernel takes the hand-written blockwise
    backward (dK/dV and dQ kernels) — not the dense jnp branch the
    interpreter's vma hazard routes to off-TPU. bfloat16: the block shapes
    and the MXU operand forms (one-pass exact GEMMs, two-term tiles) are what
    the chip would be asked to compile."""
    def loss(q, k, v):
        out = pk.flash_attention(q, k, v, causal=True)
        return jnp.sum(out.astype(jnp.float32) ** 2)

    text = _compiled_text(jax.grad(loss, argnums=(0, 1, 2)),
                          *_qkv(one_chip, shape))
    assert text.count("tpu_custom_call") >= 3  # forward + dkv + dq


def test_kmeans_step_tile_compiles_at_its_defaults(on_chip, one_chip,
                                                   monkeypatch):
    """The opt-in Lloyd kernel at the defaults the program ships
    (``loop`` sums, 128-row tiles): 1,048,576 x 64, k=8."""
    monkeypatch.delenv("HEAT_TPU_KMEANS_SUMS", raising=False)
    monkeypatch.delenv("HEAT_TPU_KMEANS_BLOCK_ROWS", raising=False)
    assert (pk._kmeans_sums_mode(), pk._kmeans_block_rows()) == ("loop", 128)
    n = 1 << 20
    x = jax.ShapeDtypeStruct((n, 64), jnp.float32, sharding=one_chip)
    c = jax.ShapeDtypeStruct((8, 64), jnp.float32, sharding=one_chip)
    m = jax.ShapeDtypeStruct((n, 1), jnp.float32, sharding=one_chip)
    _compiled_text(pk.kmeans_step_tile, x, c, m)


def test_flash_inside_check_vma_shard_map_over_four_chips(on_chip, topo):
    """The position that has only ever been routed around on the CPU
    (``interpret_vma_hazard``): the kernel under a ``check_vma=True``
    ``shard_map`` on a 2x2 mesh of described chips, batch split four ways —
    its outputs must carry the operands' varying type, forward and
    backward."""
    mesh = Mesh(np.array(topo.devices).reshape(4), ("dp",))
    sh = NamedSharding(mesh, P("dp"))
    scale = 1.0 / math.sqrt(64)

    def local(q, k, v):
        def loss(q, k, v):
            out = pk.flash_attention(q, k, v, scale=scale, causal=True)
            return jnp.sum(out.astype(jnp.float32) ** 2)

        l, g = jax.value_and_grad(loss, argnums=(0, 1, 2))(q, k, v)
        return jax.lax.psum(l, "dp"), g

    fn = shard_map(local, mesh=mesh, in_specs=(P("dp"),) * 3,
                   out_specs=(P(), (P("dp"),) * 3), check_vma=True)
    text = _compiled_text(fn, *_qkv(sh))
    assert "all-reduce" in text
