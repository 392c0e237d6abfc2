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
import re
import types

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from heat_tpu.core import pallas_kernels as pk
from heat_tpu.core._compat import shard_map
from heat_tpu.core.communication import TPUCommunication
from heat_tpu.spatial import distance


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


# (float32, 40000, 18): the benchmark's cdist cell (heat-cdist-40k.cdist),
# rows no multiple of the 256 tile; (bfloat16, 5000, 64): ragged too, rows no
# multiple of bfloat16's 16-row packing either
@pytest.mark.parametrize("dtype,n,d", [(jnp.float32, 8192, 18),
                                       (jnp.bfloat16, 8192, 64),
                                       (jnp.float32, 40000, 18),
                                       (jnp.bfloat16, 5000, 64)])
def test_cdist_tile_compiles(on_chip, one_chip, dtype, n, d):
    x = jax.ShapeDtypeStruct((n, d), dtype, sharding=one_chip)
    _compiled_text(lambda a, b: pk.cdist_tile(a, b, sqrt=True), x, x)


def test_cdist_job_writes_its_result_once(on_chip, topo):
    """The cdist cell's own program (`spatial.distance._ring_kernel` on one
    chip, 40,000 x 18 float32): the Mosaic kernel's output IS the result.
    A `slice`, `copy` or `dynamic-update-slice` of the n x n matrix after it
    is a second pass over 6.4 GB that costs more than the kernel did (PR 32's
    trace: 19.85 ms against 16.78), and a padded result is 6.46 GB of
    temporaries beside it."""
    n = 40000
    comm = TPUCommunication(devices=topo.devices[:1])
    xs = jax.ShapeDtypeStruct((n, 18), jnp.float32,
                              sharding=comm.sharding(2, 0))
    x = types.SimpleNamespace(larray=xs, shape=xs.shape)  # what it reads of a DNDarray
    fn = distance._ring_kernel(x, x, distance._euclidean_tile, True,
                               jnp.dtype(jnp.float32), comm, ("euclidean",))
    compiled = fn.lower(xs, xs).compile()
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    second_pass = [line.strip()[:120] for line in text.splitlines()
                   if re.search(rf"= f32\[{n},{n}\]\S* "
                                r"(slice|copy|dynamic-update-slice)\(", line)]
    assert not second_pass, second_pass
    assert re.search(rf"ROOT %cdist_tile\S* = f32\[{n},{n}\]", text)
    assert compiled.memory_analysis().temp_size_in_bytes < 64 * 2 ** 20


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


def _copy_sizes(compiled):

    return [int(np.prod([int(d) for d in dims.split(",")]))
            for dims in re.findall(r"= \w+\[([\d,]+)\][^ ]* copy\(",
                                   compiled.as_text())]


def _passes(compiled, op):
    """The ENTRY computation's passes over memory that are an ``op``: the
    bare instructions and the fusions XLA names after an ``op`` they hold
    (``convert.164``, ``convert_bitcast_fusion.1``, ``copy_bitcast_fusion``:
    the names a trace of the chip shows): (name, elements of the largest
    array it writes). An ``op`` in the body of a fusion named for something
    else (a product that widens its operand in registers) is not one, nor
    is a ``dynamic-update-slice`` fusion, which returns the array it updates
    in place (``_assert_touches_no_lane`` holds those to a slot's rows)."""
    text = compiled.as_text()
    out = []
    for name, types, opcode in re.findall(
            r"\n\s+(?:ROOT )?(%[\w.\-]+) = (.*?) (fusion|copy|convert)\(",
            text[text.index("\nENTRY"):]):
        if opcode == op or (opcode == "fusion" and op in name
                            and "update-slice" not in name):
            out.append((name, max(
                int(np.prod([int(d) for d in dims.split(",") if d]))
                for dims in re.findall(r"\w+\[([\d,]*)\]", types))))
    return out


def _slice_fusions(compiled):
    """The ENTRY computation's fusions with ``slice`` in their name (XLA
    names a fusion after what it holds: ``slice_bitcast_fusion``,
    ``bitcast_dynamic-update-slice_fusion``, ...): (name, elements of the
    output, elements of each operand, largest first)."""

    text = compiled.as_text()
    entry = text[text.index("\nENTRY"):]
    elems = {}
    for name, dims in re.findall(r"(%[\w.\-]+) = \w+\[([\d,]*)\]", entry):
        elems[name] = int(np.prod([int(d) for d in dims.split(",") if d]))
    out = []
    for name, args in re.findall(
            r"(%[\w.\-]*slice[\w.\-]*) = \S+ fusion\(([^)]*)\)", entry):
        ops = sorted((elems[a] for a in re.findall(r"%[\w.\-]+", args)),
                     reverse=True)
        out.append((name, elems[name], ops))
    return out


def _lane_touches(compiled, *lanes):
    """The ENTRY computation's instructions that take or give an array of one
    of the ``lanes``' shapes (tuples of dims), but for parameters, bitcasts
    and tuples: (name, op_name of their metadata). Where the step reads each
    slot's lane only up to its position, they are ``lane_attention``'s calls
    (under ``attn.core``) and the row writes (under ``cache.write``): no
    copy, convert or fusion of a whole lane besides."""
    text = compiled.as_text()
    entry = text[text.index("\nENTRY"):]
    want = {",".join(str(d) for d in lane) for lane in lanes}
    shape_of = dict(re.findall(r"\n\s+(?:ROOT )?(%[\w.\-]+) = \w+\[([\d,]*)\]",
                               entry))
    out = []
    for name, dims, op, args, rest in re.findall(
            r"\n\s+(?:ROOT )?(%[\w.\-]+) = \w+\[([\d,]*)\]\S* "
            r"([\w\-]+)\(([^)]*)\)([^\n]*)", entry):
        if op in ("parameter", "bitcast", "tuple", "get-tuple-element"):
            continue
        if dims in want or want & {shape_of.get(a) for a in
                                   re.findall(r"%[\w.\-]+", args)}:
            meta = re.search(r'op_name="([^"]*)"', rest)
            out.append((name, meta.group(1) if meta else ""))
    return out


def _assert_lanes_read_by_the_kernel(compiled, kernels, *lanes,
                                     kernel="lane_attention"):
    """``kernels`` calls of ``kernel``, each under ``attn.core``; every
    other instruction that touches a lane writes rows under
    ``cache.write``."""
    touches = _lane_touches(compiled, *lanes)
    calls = [m for n, m in touches if n.startswith("%" + kernel)]
    assert len(calls) == kernels, calls
    assert all("/attn.core/" in m and kernel in m for m in calls)
    rest = [(n, m) for n, m in touches if not n.startswith("%" + kernel)]
    assert rest and all("/cache.write" in m for _n, m in rest), \
        [t for t in rest if "/cache.write" not in t[1]]


def _described_engine(model, slots, s_cap):
    """A never-started decode engine of ``model`` on one described v5e (the
    constructor places its lanes; a described device holds nothing) and
    ``on``, which puts a ``ShapeDtypeStruct`` on the model's mesh."""
    from heat_tpu.serve.decode import DecodeConfig, DecodeEngine
    from heat_tpu.serve.program_cache import ProgramCache

    eng = DecodeEngine.__new__(DecodeEngine)
    eng.model, eng.slots, eng.S_cap = model, slots, s_cap
    eng.config = DecodeConfig(slots=slots, max_seq_len=s_cap)
    eng._dp_axes, eng._vec_spec = "dp", P("dp")
    eng._one_device = model.mesh_size == 1
    eng._cache_shapes, eng._cache_specs, eng._cache_bytes = \
        model.cache_layout(slots, s_cap, "dp")
    eng.program_cache = ProgramCache(name="described")

    def on(sd, spec=P()):
        return jax.ShapeDtypeStruct(
            sd.shape, sd.dtype, sharding=NamedSharding(model.grid.mesh, spec))

    return eng, on


def _described_pattern_engine(topo):
    """A never-started decode engine of the served pattern at
    Phi-4-mini-flash's widths (32 layers, 64 slots of 4,096 positions,
    bfloat16) on one described v5e, and its programs' arguments as
    ``ShapeDtypeStruct``s: (engine, params, cache, slot vector, live mask,
    ``on``)."""
    import heat_tpu as ht
    from heat_tpu.nn.transformer import (TransformerLM, TransformerLMConfig,
                                         sambay_pattern)

    grid = ht.MeshGrid((1, 1, 1, 1), ("dp", "pp", "tp", "sp"),
                       devices=topo.devices[:1])
    model = TransformerLM(grid, TransformerLMConfig(
        vocab=200064, d_model=2560, n_heads=40, n_kv_heads=20, n_layers=32,
        d_ff=10240, rope=False, pattern=sambay_pattern(32), window=512,
        d_inner=5120, d_state=16, d_conv=4, dt_rank=160,
        compute_dtype=jnp.bfloat16, param_dtype=jnp.bfloat16))
    slots, s_cap = 64, 4096
    eng, on = _described_engine(model, slots, s_cap)
    nbytes = eng._cache_bytes
    assert sum(nbytes.values()) == 2890792960           # 2.89 GB, per kind
    assert nbytes["lane"] == nbytes["ring"] == 2 * 64 * 4096 * 1280 * 2

    params = jax.tree.map(on, model.pattern_param_shapes())
    cache = jax.tree.map(on, eng._cache_shapes, eng._cache_specs)
    vec = on(jax.ShapeDtypeStruct((slots,), jnp.int32), P("dp"))
    live = on(jax.ShapeDtypeStruct((slots,), jnp.bool_), P("dp"))
    return eng, params, cache, vec, live, on


def test_pattern_decode_step_at_published_widths_copies_no_lane(topo,
                                                                on_chip):
    """The served pattern's step program at Phi-4-mini-flash's widths
    compiles for one v5e, fits beside its 10.6 GB of parameters and cache,
    and its optimised HLO holds no copy as large as a ring: a scatter on a
    lane's minor axis, a product batched over heads, or a `shard_map`
    boundary would each make XLA re-lay or copy the whole cache every step
    (PERF.md section 6, PR 33). Nor one as large as the smallest weight
    matrix: the step reads static slices of the runs' stacked parameters
    where they lie (seen together with the split into heads, XLA re-laid
    every QKV weight twice a step). The 8 rings, the full lane and its 7
    readers are read by 16 calls of `lane_attention`, to each slot's
    position; nothing else but the row writes touches a lane, and the
    float32 score maps of the whole-lane form (64 x 40 x 4,096 x 4 B, 42 MB)
    are gone from the temporaries (59.2 MB in the whole-lane form)."""
    eng, params, cache, vec, live, _on = _described_pattern_engine(topo)
    compiled = eng._step_prog().lower(
        params, *cache, vec, live, vec,
        jax.eval_shape(lambda: jax.random.key(0))).compile()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes < 10.7e9          # 7.71 GB + 2.89 GB
    assert mem.alias_size_in_bytes > 2.89e9             # the cache, in place
    assert mem.temp_size_in_bytes < 40e6
    copies = _copy_sizes(compiled)
    assert copies and max(copies) < min(64 * 512 * 1280, 2560 * 2560), \
        max(copies)
    _assert_lanes_read_by_the_kernel(compiled, 16, (64, 512, 1280),
                                     (64, 4096, 1280))


def test_pattern_prefill_at_published_widths_scans_its_runs(topo, on_chip):
    """The 512-token prefill program at the same widths: the pattern's two
    runs are `while` loops over the stacked parameters (the flash kernel of
    the cross layers inside one), the cache is updated in place, and nothing
    as large as a lane is copied: a prefill is O(prompt), not O(cache)."""
    eng, params, cache, vec, _live, on = _described_pattern_engine(topo)
    assert eng.model.segments == ((0, 2, 8), (16, 1, 1), (17, 1, 1),
                                  (18, 2, 7))
    i32 = on(jax.ShapeDtypeStruct((), jnp.int32))
    compiled = eng._prefill_prog(512).lower(
        params, *cache, vec, vec, on(jax.ShapeDtypeStruct((512,), jnp.int32)),
        i32, i32, jax.eval_shape(lambda: jax.random.key(0))).compile()
    text = compiled.as_text()
    assert text.count(" while(") >= 2 and "tpu_custom_call" in text
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes > 2.89e9
    assert mem.temp_size_in_bytes < 1.0e9
    assert max(_copy_sizes(compiled)) < 64 * 4096 * 1280


# the benchmark's expert cell (granite-4.0-h-small-d10e36.decode-rag-closed96):
# one chip's share of granite-4.0-h-small at its published widths: layers 0-9,
# experts 0-35 of 72 a layer, 64 slots of 4,096 positions, bfloat16
_GRANITE_STATE = 128 * 64 * 128                 # one slot's state of a layer
_GRANITE_LANE = 4096 * 8 * 128                  # one slot's K (or V) lane


def _described_granite_engine(topo):
    """(engine, params, cache, slot vector, live mask, ``on``) as
    :func:`_described_pattern_engine`."""
    import heat_tpu as ht
    from heat_tpu.nn.transformer import TransformerLM, TransformerLMConfig

    grid = ht.MeshGrid((1, 1, 1, 1), ("dp", "pp", "tp", "sp"),
                       devices=topo.devices[:1])
    model = TransformerLM(grid, TransformerLMConfig(
        vocab=100352, d_model=4096, n_heads=32, n_kv_heads=8, n_layers=10,
        rope=False, pattern=("mamba2",) * 5 + ("gqa",) + ("mamba2",) * 4,
        ffn=("moe",) * 10, norm_kind="rmsnorm", d_inner=8192, d_state=128,
        d_conv=4, ssm_heads=128, ssm_chunk=256, n_experts=72,
        experts_per_token=10, d_expert=768, d_shared=1536,
        experts_held=(0, 36), embedding_multiplier=12.0,
        residual_multiplier=0.22, attention_multiplier=0.0078125,
        logits_scaling=16.0, compute_dtype=jnp.bfloat16,
        param_dtype=jnp.bfloat16))
    slots, s_cap = 64, 4096
    eng, on = _described_engine(model, slots, s_cap)
    eng.config.logprobs = True      # as the cell serves: its judge reads them
    assert eng._cache_bytes == {
        "state": 9 * slots * (_GRANITE_STATE * 4 + 3 * 8448 * 2),
        "lane": 2 * slots * _GRANITE_LANE * 2}          # 3.52 GB together
    params = jax.tree.map(on, model.pattern_param_shapes())
    assert sum(math.prod(a.shape) for a in jax.tree.leaves(params)) \
        == 4962732672                                   # 4.96 B, 9.93 GB
    cache = jax.tree.map(on, eng._cache_shapes, eng._cache_specs)
    vec = on(jax.ShapeDtypeStruct((slots,), jnp.int32), P("dp"))
    live = on(jax.ShapeDtypeStruct((slots,), jnp.bool_), P("dp"))
    return eng, params, cache, vec, live, on


def test_expert_decode_step_at_published_widths_reads_its_experts_in_place(
        topo, on_chip):
    """The share's step program compiles for one v5e beside its 13.44 GB of
    parameters and cache with next to no temporaries: the 20 grouped products
    (`lax.ragged_dot`, two a layer) are the TPU's own kernel
    (``tpu_custom_call``), not a masked product over every group, and read a
    run's stacked experts where they lie (a slice of the stack handed to them
    was copied out first: 0.45 GB a layer, 2.3 GB of temporaries); no state
    and no lane is copied whole, and the attention layer's lane is read by
    `lane_attention` alone."""
    eng, params, cache, vec, live, _on = _described_granite_engine(topo)
    compiled = eng._step_prog().lower(
        params, *cache, vec, live, vec,
        jax.eval_shape(lambda: jax.random.key(0))).compile()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes < 13.5e9          # 9.93 GB + 3.52 GB
    assert mem.alias_size_in_bytes > 3.51e9             # the cache, in place
    assert mem.temp_size_in_bytes < 0.2e9
    assert compiled.as_text().count("tpu_custom_call") >= 20
    copies = _copy_sizes(compiled)
    assert copies and max(copies) < min(_GRANITE_STATE, _GRANITE_LANE), \
        max(copies)
    # the one grouped-query layer reads its lane through `lane_attention`
    _assert_lanes_read_by_the_kernel(compiled, 1, (64, 4096, 8 * 128))


def test_expert_prefill_at_published_widths_fits_beside_the_cache(topo,
                                                                  on_chip):
    """The 2,048-token prefill program, the cell's largest bucket: arguments
    and temporaries together stay under the chip's 17.18 GB (so 64 slots of
    4,096 positions stay), the two runs of Mamba-2 layers are `while` loops
    whose bodies take the stacked experts whole, the cache is updated in
    place and nothing as large as a layer's lanes or states is copied."""
    eng, params, cache, vec, _live, on = _described_granite_engine(topo)
    assert eng.model.segments == ((0, 1, 5), (5, 1, 1), (6, 1, 4))
    i32 = on(jax.ShapeDtypeStruct((), jnp.int32))
    compiled = eng._prefill_prog(2048).lower(
        params, *cache, vec, vec,
        on(jax.ShapeDtypeStruct((2048,), jnp.int32)), i32, i32,
        jax.eval_shape(lambda: jax.random.key(0))).compile()
    text = compiled.as_text()
    assert text.count(" while(") >= 2 and "tpu_custom_call" in text
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes > 3.51e9
    assert mem.temp_size_in_bytes < 1.5e9
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 16.0e9
    assert max(_copy_sizes(compiled)) < 64 * min(_GRANITE_STATE,
                                                 _GRANITE_LANE)
    # one program a bucket, one step program
    eng._step_prog()
    assert eng.program_cache.stats()["compiles"] == 2


# the benchmark's dense decode cell (pythia-1.4b-d8.decode-conv-closed48):
# Pythia-1.4b's widths, 8 layers, 32 slots of 2,048 positions, a bfloat16
# cache beside the bfloat16 copy the engine makes of float32 parameters
_DENSE = dict(vocab=50304, d_model=2048, n_heads=16, n_layers=8, d_ff=8192)
_DENSE_SLOTS, _DENSE_S_CAP = 32, 2048
_SLOT_LANE = _DENSE_S_CAP * 16 * 128            # one slot's rows of a layer
_LANE = _DENSE_SLOTS * _SLOT_LANE               # one layer's K (or V) lane
# a layer's lane as the cache holds it: a position a row, its heads side by
# side
_DENSE_LANE_SHAPE = (_DENSE_SLOTS, _DENSE_S_CAP, 16 * 128)
_DENSE_COMPILED: dict = {}                      # program -> its compile


def _dense_given():
    """The float32 tree a caller of the dense model hands in, as shapes:
    the dense `init`'s (it draws on the host: too slow to trace)."""
    D, F, V, H, L = 2048, 8192, 50304, 16, 8
    shapes = {"embed": (V, D), "final_ln": (D,), "unembed": (D, V),
              "stages": {"ln1": (1, L, D), "wqkv": (1, L, D, 3, H, D // H),
                         "wproj": (1, L, H, D // H, D), "ln2": (1, L, D),
                         "w_up": (1, L, D, F), "w_down": (1, L, F, D)}}
    return jax.tree.map(
        lambda shape: jax.ShapeDtypeStruct(shape, jnp.float32), shapes,
        is_leaf=lambda s: isinstance(s, tuple))


def _described_dense_engine(topo):
    """The dense engine at the decode cell's own size on one described v5e:
    (engine, params, cache, slot vector, live mask, ``on``). ``params`` is
    the tree the engine HOLDS: ``serving_params`` of the float32 shapes a
    caller hands in."""
    import heat_tpu as ht
    from heat_tpu.nn.transformer import TransformerLM, TransformerLMConfig

    grid = ht.MeshGrid((1, 1, 1, 1), ("dp", "pp", "tp", "sp"),
                       devices=topo.devices[:1])
    model = TransformerLM(grid, TransformerLMConfig(
        compute_dtype=jnp.bfloat16, **_DENSE))
    eng, on = _described_engine(model, _DENSE_SLOTS, _DENSE_S_CAP)
    assert eng._one_device
    assert eng._cache_bytes == {"arena": 4294967296}    # 16 lanes of 268 MB
    params = jax.tree.map(on, jax.eval_shape(model.serving_params,
                                             _dense_given()),
                          model.serving_param_specs())
    D, H, L = 2048, 16, 8
    assert params["stages"][model.HELD_QKV].shape == (1, L, 3, H, D, D // H)
    assert {sd.dtype for sd in jax.tree.leaves(params)} == {
        jnp.dtype(jnp.bfloat16)}
    cache = jax.tree.map(on, eng._cache_shapes, eng._cache_specs)
    vec = on(jax.ShapeDtypeStruct((_DENSE_SLOTS,), jnp.int32), P("dp"))
    live = on(jax.ShapeDtypeStruct((_DENSE_SLOTS,), jnp.bool_), P("dp"))
    return eng, params, cache, vec, live, on


def _dense_compiled(topo, program):
    """The engine's step (``program`` "step") or the prefill of a bucket,
    compiled ONCE a module for one described v5e, over the held tree."""
    if program not in _DENSE_COMPILED:
        eng, params, cache, vec, live, on = _described_dense_engine(topo)
        key = jax.eval_shape(lambda: jax.random.key(0))
        if program == "step":
            lowered = eng._step_prog().lower(
                params, *cache, vec, live, vec, key)
        else:
            i32 = on(jax.ShapeDtypeStruct((), jnp.int32))
            lowered = eng._prefill_prog(program).lower(
                params, *cache, vec, vec,
                on(jax.ShapeDtypeStruct((program,), jnp.int32)), i32, i32,
                key)
        _DENSE_COMPILED[program] = lowered.compile()
    return _DENSE_COMPILED[program]


def _assert_touches_no_lane(compiled):
    """What "written and read where it lies" means in the optimised HLO:
    the 16 donated lanes come back in place, nothing as large as a lane is
    copied, no fusion slices a lane out, and a ``dynamic-update-slice``
    fusion that returns a lane takes that lane and, besides it, nothing
    larger than a slot's rows (it updates in place)."""
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= 4.29e9
    assert mem.temp_size_in_bytes < 1.0e9
    copies = _copy_sizes(compiled)
    assert copies and max(copies) < _LANE, max(copies)
    for name, out, ops in _slice_fusions(compiled):
        if "update-slice" in name and ops[:1] == [out]:
            assert max(ops[1:], default=0) <= _SLOT_LANE, (name, ops)
        else:
            assert out < _LANE, (name, out)


def test_dense_decode_step_at_the_cell_size_touches_no_lane(topo, on_chip):
    """The dense step at the decode cell's size compiles for one v5e and
    writes ``slots`` rows a layer where they lie: before a layer's lanes were
    leaves of their own the step sliced every layer's lane out of a
    ``(layers, slots, S_cap, H, Dh)`` arena and wrote it back whole (32
    fusions of 0.8 ms, half the step: PERF.md section 6, PR 34)."""
    compiled = _dense_compiled(topo, "step")
    _assert_touches_no_lane(compiled)
    # the row scatter is the only thing that returns a lane
    assert not [name for name, out, _ops in _slice_fusions(compiled)
                if out >= _LANE]
    # and `lane_attention` the only thing besides that reads one: the 16
    # whole-lane fusions of 0.36 ms each (80.8% of the step, PR 38) are gone,
    # with their float32 scores (2.1 MB of temporaries in that form)
    _assert_lanes_read_by_the_kernel(compiled, 8, _DENSE_LANE_SHAPE)
    assert compiled.memory_analysis().temp_size_in_bytes < 2.0e6


def test_dense_generate_with_lanes_no_multiple_of_128_compiles(topo,
                                                                on_chip):
    """``generate()`` sizes its lanes to the prompt's bucket plus the tokens
    asked for: a prompt of 1,000 tokens and 1,000 new ones make lanes of
    1,024 + 1,000 = 2,024 rows, which no multiple of 128 divides. Its one
    program (the prefill and the scan of steps) compiles for one v5e at the
    dense cell's widths, each step reading the lanes through
    `lane_attention` in 256-row blocks, the last partial: a single block of
    the whole lane (2,024 x 2,048 bfloat16 for K and for V, double-buffered)
    asks for 24.75 MB of the 16 MB of scoped VMEM, and the chip refuses it."""
    import heat_tpu as ht
    from heat_tpu.nn.transformer import TransformerLM, TransformerLMConfig

    grid = ht.MeshGrid((1, 1, 1, 1), ("dp", "pp", "tp", "sp"),
                       devices=topo.devices[:1])
    model = TransformerLM(grid, TransformerLMConfig(
        compute_dtype=jnp.bfloat16, **_DENSE))
    Sb = model.prompt_bucket(1000)
    assert Sb == 1024
    lanes = model.cache_layout(1, Sb + 1000)[0][0][0]
    assert lanes["k"].shape == (1, 2024, 16 * 128)

    def on(sd, spec=P()):
        return jax.ShapeDtypeStruct(
            sd.shape, sd.dtype, sharding=NamedSharding(grid.mesh, spec))

    params = jax.tree.map(on, _dense_given(), model.param_specs())
    compiled = model._generate_prog(1, Sb, 1000, 0.0).lower(
        params, on(jax.ShapeDtypeStruct((1, Sb), jnp.int32), P("dp", None)),
        on(jax.ShapeDtypeStruct((), jnp.int32)),
        jax.eval_shape(lambda: jax.random.key(0))).compile()
    calls = re.findall(r"%lane_attention\S* = f32\[1,16,128\]\S* custom-call\("
                       r"[^\n]*bf16\[1,2024,2048\]", compiled.as_text())
    assert len(calls) == 8, calls                       # one a layer
    # the float32 weights handed in and what the program makes of them (a
    # bfloat16 copy, the lanes) fit one chip's 16 GB
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 12e9


_WQKV = 8 * 2048 * 3 * 16 * 128                 # the stacked QKV weights


@pytest.mark.parametrize("bucket", [1024, 512, 256, 128, 64, 32])
def test_dense_prefill_at_the_cell_size_is_o_prompt(topo, on_chip, bucket):
    """The dense prefill programs of every bucket of the cell: one slot's
    ``bucket`` rows a layer are written in place (``cache_store``, rows of
    the lane's own flat form), and but for the QKV weights (a prompt of 128
    tokens or more re-lays them for its wide product: not the cache's) no
    copy is larger than the prompt's own rows: a prefill is O(prompt), not
    O(cache) (it took 36.9 ms whatever the prompt while each of the two
    arenas was copied twice: PERF.md section 5, PR 32). Nothing but the
    slot writes touches a lane."""
    compiled = _dense_compiled(topo, bucket)
    _assert_touches_no_lane(compiled)
    rest = [n for n in _copy_sizes(compiled) if n != _WQKV]
    assert max(rest) <= 2 * bucket * 16 * 128 <= 2 * _SLOT_LANE, max(rest)
    _assert_lanes_read_by_the_kernel(compiled, 0, _DENSE_LANE_SHAPE)


@pytest.mark.parametrize("program", ["step", 1024, 32])
def test_dense_programs_at_the_cell_size_cast_no_weight(topo, on_chip,
                                                        program):
    """The engine holds its weights as its step reads them
    (``serving_params``: cast once when it is built, each head's QKV matrix
    contiguous), so no program converts a matrix and the step re-lays none:
    no pass of the optimised HLO named for a ``convert`` or a ``copy`` is as
    large as the smallest stacked matrix, the step's temporaries are a few
    megabytes, and it takes the held 1.22 GB, not 2.43 GB of float32 (of a
    15 ms step 7.1 ms was that cast and re-lay, done again every step:
    PERF.md section 6, PR 38). What stays: a prompt of 128 tokens or more
    wants the QKV weights transposed for its wide product and copies them,
    once a prefill."""
    compiled = _dense_compiled(topo, program)
    wproj = 8 * 2048 * 2048
    assert _passes(compiled, "convert")
    assert not [p for p in _passes(compiled, "convert") if p[1] >= wproj]
    relaid = [n for n in _copy_sizes(compiled) if n >= wproj]
    assert relaid == ([_WQKV] if program == 1024 else []), relaid
    assert not [p for p in _passes(compiled, "copy")
                if p[1] >= wproj and p[1] != _WQKV]
    if program == "step":
        mem = compiled.memory_analysis()
        assert mem.argument_size_in_bytes < 5.6e9
        assert mem.temp_size_in_bytes < 16 * 2 ** 20


# the benchmark's latent-attention cell (openPangu-Ultra-MoE-718B-d5e16.
# decode-long-closed192): one chip's share of openPangu-Ultra-MoE-718B at its
# published widths: a dense layer and four expert layers, experts 0-15 of 256,
# vocabulary rows 0-19,199, 128 slots of 4,096 positions, bfloat16
_PANGU_LANE = 128 * 4096 * 640                  # one layer's latent lane
_PANGU_W_DQ = 7680 * 1536                       # its smallest weight but two
_PANGU_COMPILED: dict = {}


def _argument_copies(compiled):
    """The ENTRY computation's copies (bare, or fusions named for one) of
    an ARGUMENT of the program: (name, elements). A weight re-laid every
    step is one; a stacked weight's slice copied may be a fusion of it."""
    text = compiled.as_text()
    entry = text[text.index("\nENTRY"):]
    args = set(re.findall(r"(%[\w.\-]+) = \S+ parameter\(", entry))
    out = []
    for name, dims, op, ops in re.findall(
            r"\n\s+(?:ROOT )?(%[\w.\-]+) = \w+\[([\d,]*)\]\S* "
            r"(copy|fusion)\(([^)]*)\)", entry):
        if (op == "copy" or "copy" in name) and args & set(
                re.findall(r"%[\w.\-]+", ops)):
            out.append((name, int(np.prod(
                [int(d) for d in dims.split(",") if d]))))
    return out


def _pangu_compiled(topo, program):
    """The share's step (``program`` "step") or the prefill of a bucket,
    compiled ONCE a module for one described v5e."""
    if program not in _PANGU_COMPILED:
        import heat_tpu as ht
        from heat_tpu.nn.transformer import TransformerLM, TransformerLMConfig

        grid = ht.MeshGrid((1, 1, 1, 1), ("dp", "pp", "tp", "sp"),
                           devices=topo.devices[:1])
        model = TransformerLM(grid, TransformerLMConfig(
            vocab=19200, d_model=7680, n_heads=128, n_layers=5, d_ff=18432,
            rope=True, rope_theta=25600000.0, pattern=("mla",) * 5,
            ffn=("mlp",) + ("moe",) * 4, norm_kind="rmsnorm",
            q_lora_rank=1536, kv_lora_rank=512, qk_nope_head_dim=128,
            qk_rope_head_dim=64, v_head_dim=128, n_experts=256,
            experts_per_token=8, d_expert=2048, d_shared=2048,
            experts_held=(0, 16), expert_score="sigmoid", expert_scale=2.5,
            sandwich_norm=True, tie_head=False, compute_dtype=jnp.bfloat16,
            param_dtype=jnp.bfloat16))
        slots, s_cap = 128, 4096
        eng, on = _described_engine(model, slots, s_cap)
        eng.config.logprobs = True      # as the cell serves: its judge reads
        assert eng._cache_bytes == {"latent": 5 * _PANGU_LANE * 2}  # 3.36 GB
        params = jax.tree.map(on, model.pattern_param_shapes())
        assert sum(math.prod(a.shape) for a in jax.tree.leaves(params)) \
            == 4919139840                               # 4.919 B, 9.84 GB
        cache = jax.tree.map(on, eng._cache_shapes, eng._cache_specs)
        vec = on(jax.ShapeDtypeStruct((slots,), jnp.int32), P("dp"))
        key = jax.eval_shape(lambda: jax.random.key(0))
        if program == "step":
            live = on(jax.ShapeDtypeStruct((slots,), jnp.bool_), P("dp"))
            lowered = eng._step_prog().lower(params, *cache, vec, live, vec,
                                             key)
        else:
            i32 = on(jax.ShapeDtypeStruct((), jnp.int32))
            lowered = eng._prefill_prog(program).lower(
                params, *cache, vec, vec,
                on(jax.ShapeDtypeStruct((program,), jnp.int32)), i32, i32,
                key)
        _PANGU_COMPILED[program] = lowered.compile()
    return _PANGU_COMPILED[program]


def test_latent_decode_step_at_published_widths_reads_its_lanes_in_place(
        topo, on_chip):
    """The share's step program compiles for one v5e beside its 13.19 GB of
    parameters and latent lanes, with the lanes updated and read in place and
    no weight re-laid: a row of 576 held 576 wide made the chip's default
    layout put the POSITIONS minor, and the step copied every lane three
    times (12 GB a step); W_UQ seen together with the split into heads was
    re-laid every layer (PERF.md section 6, PR 39). The 8 grouped products
    are the TPU's own kernel. The five latent lanes are read by five calls
    of `latent_attention`, to each slot's position; nothing else but the
    row writes touches a lane, and the float32 score maps of the whole-lane
    form (128 x 128 x 4,096 x 4 B, 268 MB) are gone from the temporaries
    (14.1 MB; 282 MB in the whole-lane form)."""
    compiled = _pangu_compiled(topo, "step")
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes < 13.25e9         # 9.84 + 3.36 GB
    assert mem.alias_size_in_bytes >= 5 * _PANGU_LANE * 2
    assert mem.temp_size_in_bytes < 40e6
    assert compiled.as_text().count("tpu_custom_call") >= 8 + 5
    copies = _copy_sizes(compiled)
    assert copies and max(copies) < _PANGU_W_DQ, max(copies)
    assert not _argument_copies(compiled)
    _assert_lanes_read_by_the_kernel(compiled, 5, (128, 4096, 640),
                                     kernel="latent_attention")


def test_latent_prefill_at_published_widths_fits_beside_the_cache(topo,
                                                                  on_chip):
    """The 2,048-token prefill, the cell's largest bucket: arguments and
    temporaries together stay under the chip's memory (so 128 slots of 4,096
    positions stay), the four expert layers are one `while` loop over their
    stacks, the decompressed attention is the flash kernel (no 128 x 2,048 x
    2,048 score map), the lanes are written in place and no lane and no
    weight is copied."""
    compiled = _pangu_compiled(topo, 2048)
    text = compiled.as_text()
    assert text.count(" while(") >= 1 and "tpu_custom_call" in text
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= 5 * _PANGU_LANE * 2
    assert mem.temp_size_in_bytes < 1.0e9
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 14.5e9
    assert max(_copy_sizes(compiled)) < _PANGU_LANE // 4
    assert not [c for c in _argument_copies(compiled) if c[1] > 1]
