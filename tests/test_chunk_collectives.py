"""Chunked, double-buffered packed collectives + async train-step
dispatch (``HEAT_TPU_FUSION_CHUNKS``, ISSUE 11).

The contract under test (doc/fusion.md "Chunked packed collectives"):

* the ``CHUNKS=1`` leg is BITWISE (and program-identical to) today's
  emission; the N-chunk leg is value-bitwise the unchunked plan for the
  exact, bf16 AND int8 codecs (block-aligned chunk boundaries — ints
  bitwise, floats within the engine's existing few-ulp flush contract
  because only the surrounding program may re-fuse);
* an N-chunked program carries N communicating collective groups per
  wire leg and moves EXACTLY the unchunked plan's wire bytes — the
  per-chunk ``hlo_audit.collective_bytes`` ring model sums to the
  whole-payload figure per codec, and the tail chunk is never
  double-charged for block-alignment padding;
* the chunk configuration keys the program caches next to
  ``quant_key()``: toggling compiles sibling programs, toggling back
  re-hits (steady state per chunk count = 0 misses);
* ``trace_step(..., block=False)`` queues steps asynchronously: results
  are bitwise the synchronous steps, donated inputs are still
  invalidated, and ``fusion.sync()`` is the explicit barrier;
* counters (``op_engine.chunk_collectives`` / ``chunk_fallbacks``) tick
  per dispatch and surface in ``runtime_stats()``.
"""

import gc

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import heat_tpu as ht
from heat_tpu.core import fusion
from heat_tpu.core._compat import shard_map
from heat_tpu.utils import hlo_audit, metrics

from jax.sharding import PartitionSpec as P


def _multi_device():
    if ht.MESH_WORLD.size < 2:
        pytest.skip("needs a multi-device mesh for a communicating psum")


@pytest.fixture
def _legs_as_emitted(monkeypatch):
    """Audit the collectives the library EMITS. XLA:CPU of jax 0.9 runs
    an all-reduce combiner that merges the N small chunk legs back into
    one instruction after the fact (the leg counts below were written
    before that pass existed); it is switched off for the compiles these
    audits read, per compile, not for the process."""
    orig = jax.stages.Lowered.compile

    def compile_(self, compiler_options=None, **kw):
        opts = dict(compiler_options or {})
        opts["xla_disable_hlo_passes"] = "cpu-all-reduce-combiner"
        return orig(self, compiler_options=opts, **kw)

    monkeypatch.setattr(jax.stages.Lowered, "compile", compile_)


def _counters(*keys):
    c = metrics.counters()
    return tuple(int(c.get(k, 0)) for k in keys)


def _ulp_equal(a, b, ulps=8):
    """The engine's documented float flush contract: different programs
    over the same chain may differ by a few ulps (FMA/fusion freedom);
    chunking itself is value-exact, but the surrounding program is
    recompiled."""
    a, b = np.asarray(a), np.asarray(b)
    if a.dtype.kind in "iub":
        np.testing.assert_array_equal(a, b)
        return
    ai = a.view({2: np.int16, 4: np.int32, 8: np.int64}[a.dtype.itemsize])
    bi = b.view(ai.dtype)
    assert np.all(np.abs(ai.astype(np.int64) - bi.astype(np.int64))
                  <= ulps), float(np.abs(a - b).max())


# --------------------------------------------------------------------- #
# pure-model unit tests: chunk geometry + per-codec ring-byte lemma      #
# (satellite: hlo_audit chunk-awareness — no compiles)                   #
# --------------------------------------------------------------------- #
class TestChunkModel:
    def test_chunk_bounds_alignment_coverage_and_tail(self):
        for total, n, align in ((400, 4, 4), (1000, 3, 8), (4097, 4, 32),
                                (52800, 7, 512)):
            b = fusion._chunk_bounds(total, n, align)
            assert b is not None
            assert len(b) <= n and len(b) >= 2
            assert b[0][0] == 0 and b[-1][1] == total
            for (lo, hi), (lo2, _hi2) in zip(b, b[1:]):
                assert hi == lo2          # contiguous
                assert hi % align == 0    # aligned interior boundary
            assert all(hi > lo for lo, hi in b)

    def test_chunk_bounds_declines_small_payloads(self):
        assert fusion._chunk_bounds(100, 4, 128) is None   # < 2 units
        assert fusion._chunk_bounds(100, 1, 4) is None     # n == 1
        assert fusion._chunk_bounds(7, 4, 4) is None

    def test_exact_ring_bytes_sum_per_chunk(self):
        # group-aligned boundaries make the integer-division ring model
        # split exactly: floor((M*g + t)*c/g) == M*c + floor(t*c/g)
        for total in (400, 4097, 52800):
            for g in (2, 4, 8):
                b = fusion._chunk_bounds(total, 4, g)
                if b is None:
                    continue
                whole = 2 * total * 4 * (g - 1) // g
                parts = sum(2 * (hi - lo) * 4 * (g - 1) // g
                            for lo, hi in b)
                assert parts == whole

    def test_bf16_ring_bytes_sum_per_chunk(self):
        for total, g in ((4096, 4), (52800, 8)):
            b = fusion._chunk_bounds(total, 4, g)
            whole = 2 * total * 2 * (g - 1) // g
            parts = sum(2 * (hi - lo) * 2 * (g - 1) // g for lo, hi in b)
            assert parts == whole

    def test_int8_ring_bytes_sum_per_chunk_no_tail_double_charge(self):
        # primary×block-aligned boundaries: every chunk of the (already
        # block-aligned) payload re-pads to NOTHING, so the per-chunk
        # modeled legs sum to exactly the whole-payload figure — the
        # tail chunk pays only the padding the unchunked exchange would
        block = fusion._QUANT_BLOCK
        for nparts in ([1500, 700], [4096], [300, 300, 300]):
            for p in (2, 4, 8):
                bounds = fusion._quant_chunk_bounds(
                    nparts, (p,), "int8", block, 4)
                if bounds is None:
                    continue
                _, whole = fusion._quant_wire_bytes(
                    nparts, 4, "int8", (p,), block)
                parts = 0
                for lo, hi in bounds:
                    _, q = fusion._quant_wire_bytes(
                        [hi - lo], 4, "int8", (p,), block)
                    parts += q
                assert parts == whole, (nparts, p, parts, whole)

    def test_quant_chunk_bounds_block_alignment(self):
        block = fusion._QUANT_BLOCK
        bounds = fusion._quant_chunk_bounds([4096], (4,), "int8", block, 4)
        assert bounds is not None
        for lo, hi in bounds[:-1]:
            assert hi % (4 * block) == 0


# --------------------------------------------------------------------- #
# flush path: property sweep, HLO audits, cache keys, counters           #
# --------------------------------------------------------------------- #
def _chain(split, dtype, m=96):
    """Op chain into a split-axis reduction: the packed-psum flush shape.
    Uneven gshape (13 rows over any mesh) keeps the padding discipline in
    the picture; the kept axis is wide enough to clear the (lowered)
    chunk floor. The int8 audits pass a wider ``m`` — that codec's chunk
    alignment is ``mesh_size × block`` elements, so 4 chunks need a
    payload of at least ``4 × size × 128``."""
    n = 13
    x = ht.arange(n * m, dtype=dtype, split=None).reshape((n, m))
    if split is not None:
        x = x.resplit(split)
    if dtype is ht.int32:
        y = x * 3 + 1
        y = y * y - x
    else:
        y = ht.exp(x * 1e-5) + x * 1e-4 - 1.25
        y = y * y + 0.25
    return y.sum(axis=0)  # crosses the split axis when split == 0


class TestChunkedFlush:
    @pytest.fixture(autouse=True)
    def _force_fused(self):
        with fusion.override(True):
            yield

    @pytest.mark.parametrize("codec", [None, "bf16", "int8"])
    @pytest.mark.parametrize("split", [None, 0, 1])
    def test_property_sweep_chunked_equals_unchunked(self, codec, split):
        with fusion.quant_override(codec, min_numel=8):
            with fusion.chunk_override(1):
                ref = _chain(split, ht.float32).numpy()
            for n in (2, 4):
                with fusion.chunk_override(n, min_numel=8):
                    _ulp_equal(_chain(split, ht.float32).numpy(), ref)

    @pytest.mark.parametrize("split", [0, 1])
    def test_property_sweep_ints_bitwise(self, split):
        # integers never quantize and never round: bitwise across N
        with fusion.quant_override(None):
            with fusion.chunk_override(1):
                ref = _chain(split, ht.int32).numpy()
            for n in (2, 4):
                with fusion.chunk_override(n, min_numel=8):
                    np.testing.assert_array_equal(
                        _chain(split, ht.int32).numpy(), ref)

    def _flush_hlo(self, codec, chunks, m=96):
        with fusion.quant_override(codec, min_numel=8), \
                fusion.chunk_override(chunks, min_numel=8):
            fusion.reset()
            fusion.capture_hlo(True)
            try:
                out = _chain(0, ht.float32, m=m).numpy()
                hlo = fusion.last_hlo()
            finally:
                fusion.capture_hlo(False)
        assert hlo is not None
        return out, hlo

    @pytest.mark.parametrize("codec", [None, "bf16", "int8"])
    def test_hlo_audit_n_legs_and_equal_wire_bytes(self, codec,
                                                   _legs_as_emitted):
        """THE acceptance audit at the flush level: the N-chunked program
        carries N communicating collective groups per wire leg and moves
        exactly the unchunked plan's wire bytes, per codec."""
        _multi_device()
        # int8 chunk boundaries align to size×block: 4 chunks need a
        # payload of 4 aligned units (the exact path aligns to size only)
        m = 4 * ht.MESH_WORLD.size * 128 if codec == "int8" else 96
        out1, hlo1 = self._flush_hlo(codec, 1, m=m)
        out4, hlo4 = self._flush_hlo(codec, 4, m=m)
        _ulp_equal(out4, out1)
        b1 = hlo_audit.collective_bytes(hlo1, world=ht.MESH_WORLD.size)
        b4 = hlo_audit.collective_bytes(hlo4, world=ht.MESH_WORLD.size)
        assert b4["total_wire_bytes"] == b1["total_wire_bytes"]
        s1 = hlo_audit.communicating_collective_stats(hlo1)
        s4 = hlo_audit.communicating_collective_stats(hlo4)
        if codec == "int8":
            # RS leg = payload + scales a2a pairs, return leg = gather:
            # every leg shows 4x the unchunked instruction count
            assert s4["all-to-all"]["count"] == \
                4 * s1["all-to-all"]["count"]
            assert s4["all-gather"]["count"] == \
                4 * s1["all-gather"]["count"]
        else:
            assert s1.get("all-reduce", {}).get("count") == 1
            assert s4.get("all-reduce", {}).get("count") == 4

    def test_steady_state_zero_recompiles_including_toggling(self):
        _multi_device()
        with fusion.quant_override(None):
            for n in (4, 1, 2):
                with fusion.chunk_override(n, min_numel=8):
                    _chain(0, ht.float32).numpy()  # compile sibling
            before = fusion.program_cache().stats()
            for n in (4, 1, 2, 4, 1):
                with fusion.chunk_override(n, min_numel=8):
                    _chain(0, ht.float32).numpy()
            after = fusion.program_cache().stats()
        assert after["misses"] - before["misses"] == 0
        assert after["compiles"] - before["compiles"] == 0

    def test_chunk_collectives_ticks_per_dispatch(self):
        _multi_device()
        with fusion.quant_override(None), \
                fusion.chunk_override(4, min_numel=8):
            _chain(0, ht.float32).numpy()  # compile + first dispatch
            before = _counters("op_engine.chunk_collectives")
            _chain(0, ht.float32).numpy()  # pure cache-hit dispatch
            after = _counters("op_engine.chunk_collectives")
        assert after[0] - before[0] == 1

    def test_below_floor_payloads_stay_unchunked(self):
        _multi_device()
        with fusion.quant_override(None), \
                fusion.chunk_override(4, min_numel=10 ** 9):
            fusion.reset()
            fusion.capture_hlo(True)
            try:
                _chain(0, ht.float32).numpy()
                hlo = fusion.last_hlo()
            finally:
                fusion.capture_hlo(False)
        s = hlo_audit.communicating_collective_stats(hlo)
        assert s.get("all-reduce", {}).get("count") == 1


# --------------------------------------------------------------------- #
# packed_psum (the train-step form): parity, qinfo accounting            #
# --------------------------------------------------------------------- #
class TestChunkedPackedPsum:
    def _run(self, codec, chunks, v1, v2):
        comm = ht.get_comm()
        with fusion.quant_override(codec, min_numel=8), \
                fusion.chunk_override(chunks, min_numel=8):
            qk, ck = fusion.quant_key(), fusion.chunk_key()
            qinfo = {}

            def body(a, b):
                fusion.reset_qinfo(qinfo)
                return tuple(fusion.packed_psum(
                    [a, b], (comm.axis_name,), qinfo=qinfo, quant=qk,
                    chunks=ck))

            fn = jax.jit(shard_map(body, mesh=comm.mesh,
                                   in_specs=(P(), P()),
                                   out_specs=(P(), P()),
                                   check_vma=False))
            hlo = fn.lower(v1, v2).compile().as_text()
            o1, o2 = fn(v1, v2)
        return np.asarray(o1), np.asarray(o2), hlo, qinfo

    @pytest.mark.parametrize("codec", [None, "bf16", "int8"])
    def test_chunked_bitwise_and_wire_equal(self, codec):
        _multi_device()
        rng = np.random.default_rng(0)
        v1 = rng.standard_normal(1500).astype(np.float32) * 8
        v2 = rng.standard_normal(700).astype(np.float32)
        base = self._run(codec, 1, v1, v2)
        world = ht.MESH_WORLD.size
        for n in (2, 4):
            got = self._run(codec, n, v1, v2)
            np.testing.assert_array_equal(got[0], base[0])
            np.testing.assert_array_equal(got[1], base[1])
            assert (hlo_audit.collective_bytes(got[2], world)
                    ["total_wire_bytes"]
                    == hlo_audit.collective_bytes(base[2], world)
                    ["total_wire_bytes"])
            assert got[3].get("chunk_collectives") == 1

    def test_fault_site_silent_when_nothing_qualifies(self):
        """An armed fusion.chunk.dispatch plan must be a no-op on a
        packed_psum whose payloads all stay unchunked: the site fires
        only for INTENDED chunk legs (matching _chunk_flush_plan), so a
        sub-floor call neither consumes fire indices nor ticks
        chunk_fallbacks (review finding, pinned)."""
        from heat_tpu.utils import faults

        _multi_device()
        comm = ht.get_comm()
        keys = ("op_engine.chunk_fallbacks",
                "faults.fusion.chunk.dispatch.fires")
        before = _counters(*keys)
        with fusion.chunk_override(4, min_numel=10 ** 9):
            ck = fusion.chunk_key()

            def body(a):
                return fusion.packed_psum([a], (comm.axis_name,),
                                          chunks=ck)[0]

            with faults.inject("fusion.chunk.dispatch=nth:1"):
                fn = jax.jit(shard_map(body, mesh=comm.mesh,
                                       in_specs=(P(),), out_specs=P(),
                                       check_vma=False))
                out = np.asarray(fn(np.ones(64, np.float32)))
        assert _counters(*keys) == before
        np.testing.assert_array_equal(
            out, np.full(64, comm.size, np.float32))

    def test_scalar_and_int_payloads_keep_exact_unchunked_psum(self):
        _multi_device()
        comm = ht.get_comm()
        with fusion.quant_override(None), \
                fusion.chunk_override(4, min_numel=8):
            ck = fusion.chunk_key()

            def body(s, i):
                o = fusion.packed_psum([s, i], (comm.axis_name,),
                                       chunks=ck)
                return tuple(o)

            fn = jax.jit(shard_map(body, mesh=comm.mesh,
                                   in_specs=(P(), P()),
                                   out_specs=(P(), P()),
                                   check_vma=False))
            s, i = fn(jnp.float32(1.5), jnp.arange(4, dtype=jnp.int32))
        # scalar loss and the 4-element int payload are both sub-floor:
        # values are the plain psums, bitwise
        assert float(s) == 1.5 * comm.size
        np.testing.assert_array_equal(
            np.asarray(i), np.arange(4) * comm.size)


# --------------------------------------------------------------------- #
# acceptance: the transformer packed train step, chunked per codec       #
# --------------------------------------------------------------------- #
# one shared model/toks/params for the WHOLE module (the §2b executable
# budget discipline from tests/test_quant_collectives.py: transformer
# step programs are the largest compiles here — every test reuses the
# same model objects, and the module-scoped teardown drops the compiled
# state so the suite's end-state executable count is unchanged)
_ACCEPT: dict = {}


def _accept():
    if not _ACCEPT:
        from heat_tpu.nn.transformer import (TransformerLM,
                                             TransformerLMConfig)

        ndev = ht.MESH_WORLD.size
        grid = ht.MeshGrid((ndev, 1, 1, 1), ("dp", "pp", "tp", "sp"))
        cfg = TransformerLMConfig(
            vocab=64, d_model=32, n_heads=4, n_layers=2, d_ff=64)
        model = TransformerLM(grid, cfg)
        rng = np.random.default_rng(0)
        toks = model.shard_batch(
            rng.integers(0, cfg.vocab, (2 * ndev, 8)).astype(np.int32))
        _ACCEPT.update(model=model, toks=toks, params=model.init(0))
    return _ACCEPT


@pytest.fixture(scope="module", autouse=True)
def _drop_compiled_state():
    yield
    _ACCEPT.clear()
    fusion.reset()
    gc.collect()


class TestTransformerChunkAcceptance:
    @pytest.fixture(autouse=True)
    def _force_fused(self):
        with fusion.override(True), fusion.step_override(True):
            yield

    @pytest.mark.parametrize("codec", [None, "int8"])
    def test_chunked_step_equal_wire_bytes_and_n_legs(self, codec,
                                                      _legs_as_emitted):
        """THE acceptance audit: the N-chunked packed train step moves
        wire bytes equal to the unchunked plan, with N communicating
        collective groups per leg, per codec — and the loss parity is
        bitwise (same codec, chunked vs unchunked)."""
        _multi_device()
        acc = _accept()
        model, toks = acc["model"], acc["toks"]
        world = ht.MESH_WORLD.size
        results = {}
        for n in (1, 4):
            with fusion.quant_override(codec, min_numel=8), \
                    fusion.chunk_override(n, min_numel=8):
                lg = model.loss_and_grad_fn()
                hlo = lg.lower(acc["params"], toks).compile().as_text()
                loss, _grads = lg(acc["params"], toks)
                results[n] = (float(loss), hlo)
        l1, h1 = results[1]
        l4, h4 = results[4]
        assert l4 == l1  # chunking is value-exact per codec
        b1 = hlo_audit.collective_bytes(h1, world)["total_wire_bytes"]
        b4 = hlo_audit.collective_bytes(h4, world)["total_wire_bytes"]
        assert b4 == b1
        s1 = hlo_audit.communicating_collective_stats(h1)
        s4 = hlo_audit.communicating_collective_stats(h4)
        if codec == "int8":
            assert s4["all-to-all"]["count"] == \
                4 * s1["all-to-all"]["count"]
            assert s4["all-gather"]["count"] == \
                4 * s1["all-gather"]["count"]
        else:
            # the packed plan's ONE gradient all-reduce becomes 4 chunk
            # legs (the sub-floor scalar loss keeps its own exact psum
            # packed with nothing — the flattened payload absorbs it)
            assert s1["all-reduce"]["count"] <= 2
            assert s4["all-reduce"]["count"] == \
                s1["all-reduce"]["count"] + 3

    def test_step_cache_siblings_and_toggle_back_rehit(self):
        _multi_device()
        acc = _accept()
        model = acc["model"]
        with fusion.quant_override(None), fusion.chunk_override(1):
            fn1 = model.loss_and_grad_fn()
        with fusion.quant_override(None), \
                fusion.chunk_override(4, min_numel=8):
            fn4 = model.loss_and_grad_fn()
            assert fn4 is not fn1
        with fusion.quant_override(None), fusion.chunk_override(1):
            assert model.loss_and_grad_fn() is fn1  # toggle-back re-hit


# --------------------------------------------------------------------- #
# async trace_step: parity, donation, sync                               #
# --------------------------------------------------------------------- #
class TestAsyncTraceStep:
    @pytest.fixture(autouse=True)
    def _force_fused(self):
        with fusion.override(True), fusion.step_override(True):
            yield

    @staticmethod
    def _step(p, g):
        return {k: p[k] - 0.1 * g[k] for k in p}

    def _state(self):
        p = {"w": ht.arange(1024, dtype=ht.float32, split=0) / 1024.0,
             "b": ht.ones(256, dtype=ht.float32, split=0)}
        g = {"w": ht.ones(1024, dtype=ht.float32, split=0),
             "b": ht.ones(256, dtype=ht.float32, split=0) * 0.5}
        return p, g

    def test_async_steps_bitwise_equal_synchronous(self):
        p0, g = self._state()
        ts_sync = fusion.trace_step(self._step, donate_argnums=(0,))
        ts_async = fusion.trace_step(self._step, donate_argnums=(0,),
                                     block=False)

        def clone(p):
            return {k: ht.array(v.numpy(), split=0) for k, v in p.items()}

        ps = clone(p0)
        for _ in range(4):
            ps = ts_sync(ps, g)
        pa = clone(p0)
        for _ in range(4):
            pa = ts_async(pa, g)
        fusion.sync()
        for k in ps:
            np.testing.assert_array_equal(ps[k].numpy(), pa[k].numpy())

    def test_async_donation_still_invalidates(self):
        p0, g = self._state()
        ts = fusion.trace_step(self._step, donate_argnums=(0,),
                               block=False)
        p1 = ts(p0, g)
        fusion.sync()
        assert p0["w"].larray.is_deleted()
        with pytest.raises(RuntimeError):
            p0["w"].numpy()
        # the non-donated argument survives, the result is readable
        assert not g["w"].larray.is_deleted()
        assert np.isfinite(p1["w"].numpy()).all()

    def test_async_steady_state_zero_recompiles(self):
        p, g = self._state()
        ts = fusion.trace_step(self._step, donate_argnums=(0,),
                               block=False)
        p = ts(p, g)  # compile
        before = fusion.program_cache().stats()
        for _ in range(3):
            p = ts(p, g)
        fusion.sync()
        after = fusion.program_cache().stats()
        assert after["misses"] - before["misses"] == 0

    def test_sync_on_explicit_trees(self):
        p, g = self._state()
        ts = fusion.trace_step(self._step, block=False)
        out = ts(p, g)
        fusion.sync(out)  # tree form: blocks the DNDarray leaves
        assert np.isfinite(out["w"].numpy()).all()

    def test_async_eager_escape_hatch(self):
        p, g = self._state()
        ts = fusion.trace_step(self._step, block=False)
        with fusion.step_override(False):
            out = ts(p, g)  # eager body, no program, still correct
        np.testing.assert_allclose(
            out["w"].numpy(), p["w"].numpy() - 0.1 * g["w"].numpy(),
            rtol=1e-6)


def test_chunk_stats_surface_in_runtime_stats():
    st = ht.runtime_stats()["op_engine"]["fusion"]
    for k in ("chunk_count", "chunk_min_numel", "chunk_collectives",
              "chunk_fallbacks"):
        assert isinstance(st[k], int)
    assert st["chunk_count"] >= 1
