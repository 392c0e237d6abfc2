"""Quantized packed collectives (``HEAT_TPU_QUANT_COLLECTIVES``, ISSUE 10).

The contract under test (doc/fusion.md "Quantized packed collectives"):

* the quant-off leg is BITWISE today's behavior; integer/bool payloads,
  pmax/pmin and sub-floor payloads stay bitwise-exact under every codec;
* quantized float psums stay within the documented per-codec rel-err
  bounds (bf16 <= 4e-3, int8 <= 1e-2, norm-wise per collective);
* the codec keys the program caches: toggling compiles sibling programs
  and NEVER poisons a cached exact program (steady state per codec = 0
  misses);
* the acceptance figure — >= 2x collective-WIRE-byte reduction on the
  2-layer TransformerLM packed train step under int8 block scaling, with
  gradients within 1e-2 rel-err of the exact path — audited through
  ``hlo_audit.collective_bytes`` on both the full mesh and its half-size
  sub-mesh (the 4/8-dev ladder shapes);
* the counters (``op_engine.quant_collectives`` / ``quant_bytes_saved``)
  tick per dispatch and surface in ``runtime_stats()``.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import heat_tpu as ht
from heat_tpu.core import fusion
from heat_tpu.core._compat import shard_map
from heat_tpu.utils import hlo_audit, metrics

from jax.sharding import PartitionSpec as P

# documented per-codec norm-wise rel-err bounds (doc/fusion.md)
BOUNDS = {"bf16": 4e-3, "int8": 1e-2}


def _multi_device():
    if ht.MESH_WORLD.size < 2:
        pytest.skip("needs a multi-device mesh for a communicating psum")


def _counters(*keys):
    c = metrics.counters()
    return tuple(int(c.get(k, 0)) for k in keys)


def _rel(err, ref):
    a = np.asarray(err).astype(np.float64)
    b = np.asarray(ref).astype(np.float64)
    return np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30)


# --------------------------------------------------------------------- #
# hlo_audit.collective_bytes unit tests (satellite 1): every             #
# replica-group form, every kind's wire formula                          #
# --------------------------------------------------------------------- #
class TestCollectiveBytes:
    def _one(self, line, world=None):
        out = hlo_audit.collective_bytes(line, world=world)
        assert len(out["per_instruction"]) == 1
        return out["per_instruction"][0]

    def test_brace_of_braces_groups(self):
        rec = self._one(
            "  %ar = f32[100]{0} all-reduce(f32[100]{0} %x), "
            "replica_groups={{0,1},{2,3}}, to_apply=%add")
        assert rec["group_size"] == 2
        assert rec["result_bytes"] == 400
        assert rec["wire_bytes"] == 2 * 400 * 1 // 2  # 2R(g-1)/g

    def test_flat_single_group(self):
        rec = self._one(
            "  %ar = f32[8]{0} all-reduce(f32[8]{0} %x), "
            "replica_groups={0,1,2,3}, to_apply=%add")
        assert rec["group_size"] == 4
        assert rec["wire_bytes"] == 2 * 32 * 3 // 4

    def test_empty_groups_resolve_via_world(self):
        rec = self._one(
            "  %ar = f32[16]{0} all-reduce(f32[16]{0} %x), "
            "replica_groups={}, to_apply=%add", world=8)
        assert rec["group_size"] == 8
        assert rec["wire_bytes"] == 2 * 64 * 7 // 8

    def test_iota_form(self):
        rec = self._one(
            "  %ar = f32[16]{0} all-reduce(f32[16]{0} %x), "
            "replica_groups=[2,4]<=[8], to_apply=%add")
        assert rec["group_size"] == 4

    def test_singleton_groups_move_zero_wire_bytes(self):
        for groups in ("replica_groups={{0},{1},{2},{3}}",
                       "replica_groups=[8,1]<=[8]"):
            rec = self._one(
                f"  %ar = f32[16]{{0}} all-reduce(f32[16]{{0}} %x), "
                f"{groups}, to_apply=%add")
            assert rec["group_size"] == 1
            assert rec["wire_bytes"] == 0

    def test_missing_annotation_falls_back(self):
        rec = self._one("  %ar = f32[10]{0} all-reduce(f32[10]{0} %x)",
                        world=4)
        assert rec["group_size"] == 4
        rec = self._one("  %ar = f32[10]{0} all-reduce(f32[10]{0} %x)")
        assert rec["group_size"] == 2  # conservative unknown-world default

    def test_per_kind_wire_formulas(self):
        # 1000 s8 payload bytes, g=4: each kind's documented ring model
        kinds = {
            "all-reduce": 2 * 1000 * 3 // 4,
            "reduce-scatter": 1000 * 3,
            "all-gather": 1000 * 3 // 4,
            "all-to-all": 1000 * 3 // 4,
            "collective-permute": 1000,
        }
        for kind, want in kinds.items():
            rec = self._one(
                f"  %c = s8[1000]{{0}} {kind}(s8[1000]{{0}} %x), "
                f"replica_groups={{0,1,2,3}}")
            assert rec["wire_bytes"] == want, kind

    def test_tuple_result_bytes_sum(self):
        rec = self._one(
            "  %a2a = (s8[2,64]{1,0}, s8[2,64]{1,0}) all-to-all("
            "s8[2,64]{1,0} %x, s8[2,64]{1,0} %y), "
            "replica_groups={{0,1}}")
        assert rec["result_bytes"] == 256
        assert rec["group_size"] == 2

    def test_aggregates(self):
        hlo = "\n".join([
            "  %ar = f32[100]{0} all-reduce(f32[100]{0} %x), "
            "replica_groups={{0,1}}, to_apply=%add",
            "  %ag = f32[100]{0} all-gather(f32[50]{0} %y), "
            "replica_groups={{0,1}}, dimensions={0}",
        ])
        out = hlo_audit.collective_bytes(hlo)
        assert out["by_kind"]["all-reduce"]["count"] == 1
        assert out["total_result_bytes"] == 800
        assert out["total_wire_bytes"] == (2 * 400 * 1 // 2
                                           + 400 * 1 // 2)


# --------------------------------------------------------------------- #
# flush-path property sweep: quant-on vs quant-off                      #
# --------------------------------------------------------------------- #
def _chain_reduce(x, axis):
    """>= MIN_OPS elementwise chain ending in a reduction over ``axis`` —
    the reduce-fused tape shape whose packed psum the codec rewrites."""
    t = (x - 0.5) * 0.25
    t = ht.tanh(t) + 1.0
    t = t * t + t
    return t.sum(axis=axis)


class TestQuantFlushSweep:
    @pytest.mark.parametrize("codec", ["bf16", "int8"])
    @pytest.mark.parametrize("dtype", [ht.float32, ht.bfloat16])
    @pytest.mark.parametrize("split", [None, 0, 1])
    def test_sweep_within_documented_bounds(self, codec, dtype, split):
        """Uneven gshapes, both split orientations: the quantized flush
        stays within the per-codec bound; layouts without a communicating
        psum (split None, or the reduce not touching the split) are
        bitwise — nothing quantizes."""
        _multi_device()
        rng = np.random.default_rng(7)
        # reduce over the split axis with a large surviving payload
        # (>= the floor) so the rewrite engages; gshape uneven on purpose
        data = rng.standard_normal((7, 1501)).astype("float32")
        if split == 1:
            data = data.T.copy()
        axis = split if split is not None else 0
        x = ht.array(data, split=split, dtype=dtype)
        with fusion.quant_override(None):
            base = _chain_reduce(x, axis).numpy()
        with fusion.quant_override(codec):
            got = _chain_reduce(x, axis).numpy()
        communicates = split is not None
        quantizable = communicates and not (
            codec == "bf16" and dtype == ht.bfloat16)
        if not quantizable:
            # no communicating psum, or a bf16 payload under the bf16
            # codec (already wire-width): bitwise-exact by contract
            np.testing.assert_array_equal(got, base)
        else:
            assert _rel(got, base) <= BOUNDS[codec], (codec, dtype, split)

    @pytest.mark.parametrize("codec", ["bf16", "int8"])
    def test_integer_payloads_bitwise(self, codec):
        _multi_device()
        x = ht.array(np.arange(7 * 1501, dtype="int32").reshape(7, 1501) % 97,
                     split=0)
        with fusion.quant_override(None):
            base = _chain_int(x).numpy()
        with fusion.quant_override(codec):
            got = _chain_int(x).numpy()
        np.testing.assert_array_equal(got, base)

    @pytest.mark.parametrize("codec", ["bf16", "int8"])
    def test_below_floor_bitwise(self, codec):
        """A payload under HEAT_TPU_QUANT_MIN_NUMEL stays on the exact
        packed psum — bitwise."""
        _multi_device()
        x = ht.array(np.linspace(-2, 2, 1501 * 7,
                                 dtype="float32").reshape(1501, 7), split=0)
        with fusion.quant_override(None):
            base = _chain_reduce(x, 0).numpy()  # payload (7,) << floor
        assert x.gshape[1] < fusion.quant_key()[1]
        with fusion.quant_override(codec):
            got = _chain_reduce(x, 0).numpy()
        np.testing.assert_array_equal(got, base)

    def test_escape_hatch_bitwise_and_silent(self):
        """codec off (the default env, HEAT_TPU_QUANT_COLLECTIVES=0):
        bitwise today's behavior, zero quant counters."""
        _multi_device()
        x = ht.array(np.random.default_rng(3).standard_normal(
            (7, 1501)).astype("float32"), split=0)
        base = _chain_reduce(x, 0).numpy()  # the AMBIENT env leg
        c0 = _counters("op_engine.quant_collectives",
                       "op_engine.quant_bytes_saved",
                       "op_engine.quant_fallbacks")
        with fusion.quant_override(None):
            got = _chain_reduce(x, 0).numpy()
        if fusion.quant_codec() is None:
            # under the default env (codec off) the override leg IS
            # today's behavior: bitwise. (Under the ladder's QUANT=int8
            # leg the ambient base is quantized — only counter silence
            # is asserted there.)
            np.testing.assert_array_equal(got, base)
        assert _counters("op_engine.quant_collectives",
                         "op_engine.quant_bytes_saved",
                         "op_engine.quant_fallbacks") == c0

    def test_counters_tick_per_dispatch_and_surface(self):
        _multi_device()
        x = ht.array(np.random.default_rng(4).standard_normal(
            (7, 1501)).astype("float32"), split=0)
        with fusion.quant_override("int8"):
            c0 = _counters("op_engine.quant_collectives",
                           "op_engine.quant_bytes_saved")
            _chain_reduce(x, 0).numpy()
            _chain_reduce(x, 0).numpy()  # cache HIT must still tick
            c1 = _counters("op_engine.quant_collectives",
                           "op_engine.quant_bytes_saved")
            assert c1[0] - c0[0] == 2
            assert c1[1] > c0[1]
            st = ht.runtime_stats()["op_engine"]["fusion"]
            assert st["quant_codec"] == "int8"
            assert st["quant_collectives"] >= 2
            assert st["quant_bytes_saved"] > 0

    def test_steady_state_zero_recompiles_per_codec(self):
        """Each codec compiles its own program ONCE; toggling between
        codecs (exact included) hits the per-codec cached programs —
        toggling never poisons or evicts the exact program."""
        _multi_device()
        x = ht.array(np.random.default_rng(5).standard_normal(
            (7, 1501)).astype("float32"), split=0)
        legs = [None, "bf16", "int8"]
        for codec in legs:  # warm one program per codec
            with fusion.quant_override(codec):
                _chain_reduce(x, 0).numpy()
        s0 = fusion.program_cache().stats()
        for _ in range(2):
            for codec in legs:
                with fusion.quant_override(codec):
                    _chain_reduce(x, 0).numpy()
        s1 = fusion.program_cache().stats()
        assert s1["misses"] - s0["misses"] == 0
        assert s1["compiles"] - s0["compiles"] == 0


def _chain_int(x):
    t = (x + 1) * 2
    t = t - 3
    t = t * t + t
    return t.sum(axis=0)


# --------------------------------------------------------------------- #
# packed_psum: the library call site (model steps, DASO)                #
# --------------------------------------------------------------------- #
def _psum_program(qinfo=None):
    comm = ht.get_comm()

    def body(v):
        return fusion.packed_psum([v], (comm.axis_name,), qinfo=qinfo)[0]

    return jax.jit(shard_map(
        body, mesh=comm.mesh, in_specs=(P(),), out_specs=P(),
        check_vma=False))


class TestPackedPsumQuant:
    def test_int8_crafted_payload_roundtrips_bitwise(self):
        """Payload engineered so the int8 codec is EXACT (power-of-two
        scale, sums representable in bf16): quant == exact bitwise — the
        exchange's encode/route/combine/decode math is validated with no
        tolerance hiding a transpose or offset bug."""
        _multi_device()
        block = fusion.quant_key()[2]
        nblocks = 8
        v = np.zeros(nblocks * block, np.float32)
        for b in range(nblocks):
            v[b * block] = 127.0 / 16.0          # amax -> scale = 1/16
            rest = (np.arange(block - 1) % 8) / 16.0
            v[b * block + 1:(b + 1) * block] = rest
        with fusion.quant_override(None):
            exact = np.asarray(_psum_program()(v))
        with fusion.quant_override("int8"):
            got = np.asarray(_psum_program()(v))
        np.testing.assert_array_equal(got, exact)

    @pytest.mark.parametrize("codec", ["bf16", "int8"])
    def test_random_payload_within_bounds_and_qinfo(self, codec):
        _multi_device()
        rng = np.random.default_rng(11)
        v = rng.standard_normal(4096).astype(np.float32)
        with fusion.quant_override(None):
            exact = np.asarray(_psum_program()(v))
        qinfo = {}
        with fusion.quant_override(codec):
            got = np.asarray(_psum_program(qinfo=qinfo)(v))
        assert _rel(got, exact) <= BOUNDS[codec]
        assert qinfo["collectives"] == 1
        assert qinfo["bytes_saved"] > 0

    def test_scalar_and_int_values_stay_exact_in_mixed_pack(self):
        """The packed loss scalar (sub-floor) and integer values keep the
        exact flattened psum even when big float values quantize."""
        _multi_device()
        comm = ht.get_comm()
        rng = np.random.default_rng(12)
        big = rng.standard_normal(2048).astype(np.float32)
        small = np.float32(3.25)
        iv = np.arange(512, dtype=np.int32)

        def body(b, s, i):
            return tuple(fusion.packed_psum([b, s, i], (comm.axis_name,)))

        fn = jax.jit(shard_map(body, mesh=comm.mesh,
                               in_specs=(P(), P(), P()),
                               out_specs=(P(), P(), P()),
                               check_vma=False))
        with fusion.quant_override(None):
            eb, es, ei = (np.asarray(a) for a in fn(big, small, iv))
        with fusion.quant_override("int8"):
            fn2 = jax.jit(shard_map(body, mesh=comm.mesh,
                                    in_specs=(P(), P(), P()),
                                    out_specs=(P(), P(), P()),
                                    check_vma=False))
            qb, qs, qi = (np.asarray(a) for a in fn2(big, small, iv))
        np.testing.assert_array_equal(qs, es)  # scalar exact
        np.testing.assert_array_equal(qi, ei)  # ints exact
        assert _rel(qb, eb) <= BOUNDS["int8"]
        assert not np.array_equal(qb, eb)  # the big payload DID quantize


# --------------------------------------------------------------------- #
# acceptance: the transformer packed train step, 4/8-dev meshes         #
# --------------------------------------------------------------------- #
def _quant_grid(ndev):
    n = ht.MESH_WORLD.size
    if ndev > n:
        pytest.skip(f"needs {ndev} devices, have {n}")
    return ht.MeshGrid((ndev, 1, 1, 1), ("dp", "pp", "tp", "sp"),
                       devices=jax.devices()[:ndev])


def _mesh_sizes():
    n = ht.MESH_WORLD.size
    sizes = [n]
    if n >= 4 and n % 2 == 0:
        sizes.append(n // 2)
    return sizes


# one shared model/toks/params per mesh size for the WHOLE class: the
# transformer step programs are the largest compiles in this module, and
# per-process executable count is a suite-wide budget under watch
# (the per-process executable budget — an XLA:CPU compile near the END of a full tier-1 run
# crashes when the accumulated state crosses the box's threshold, so
# every test here reuses the same compiled set instead of re-lowering)
_ACCEPT: dict = {}


def _accept(ndev):
    if ndev not in _ACCEPT:
        from heat_tpu.nn.transformer import (TransformerLM,
                                             TransformerLMConfig)

        grid = _quant_grid(ndev)
        cfg = TransformerLMConfig(
            vocab=64, d_model=32, n_heads=4, n_layers=2, d_ff=64)
        model = TransformerLM(grid, cfg)
        rng = np.random.default_rng(0)
        toks = model.shard_batch(
            rng.integers(0, cfg.vocab, (2 * ndev, 8)).astype(np.int32))
        _ACCEPT[ndev] = {"model": model, "toks": toks,
                         "params": model.init(0)}
    return _ACCEPT[ndev]


@pytest.fixture(scope="module", autouse=True)
def _drop_compiled_state():
    """Release this module's compiled programs when it finishes: the
    shared transformer models (their ``_step_cache`` pins the big step
    executables) and the fusion program cache — the per-process
    executable budget is the §2b watch item, and this module should
    leave the suite's end-state where it found it."""
    import gc

    yield
    _ACCEPT.clear()
    fusion.reset()
    gc.collect()


class TestTransformerQuantAcceptance:
    @pytest.fixture(autouse=True)
    def _force_fused(self):
        with fusion.override(True), fusion.step_override(True):
            yield

    def test_int8_halves_step_wire_bytes_and_grads_within_contract(self):
        """THE acceptance audit: >= 2x collective-wire-byte reduction on
        the 2-layer packed train step under int8 block scaling, gradients
        within 1e-2 rel-err, on the full mesh AND the half-size sub-mesh
        (the 4/8-dev ladder pair at the default device count)."""
        import optax

        _multi_device()
        for ndev in _mesh_sizes():
            acc = _accept(ndev)
            model, toks, params = acc["model"], acc["toks"], acc["params"]
            tx = optax.adam(1e-2)
            opt_state = tx.init(params)
            with fusion.quant_override(None):
                hlo_e = model.make_train_step(tx).lower(
                    params, opt_state, toks).compile().as_text()
            with fusion.quant_override("int8"):
                hlo_q = model.make_train_step(tx).lower(
                    params, opt_state, toks).compile().as_text()
            be = hlo_audit.collective_bytes(hlo_e, world=ndev)
            bq = hlo_audit.collective_bytes(hlo_q, world=ndev)
            ratio = be["total_wire_bytes"] / bq["total_wire_bytes"]
            assert ratio >= 2.0, (
                f"{ndev}-dev: wire bytes {be['total_wire_bytes']} -> "
                f"{bq['total_wire_bytes']} is only {ratio:.2f}x "
                f"(by kind: {bq['by_kind']})")
            # grads within the documented contract
            with fusion.quant_override(None):
                _, grads_e = model.loss_and_grad_fn()(params, toks)
            with fusion.quant_override("int8"):
                loss_q, grads_q = model.loss_and_grad_fn()(params, toks)
            ge = np.concatenate([np.asarray(g).ravel() for g in
                                 jax.tree_util.tree_leaves(grads_e)])
            gq = np.concatenate([np.asarray(g).ravel() for g in
                                 jax.tree_util.tree_leaves(grads_q)])
            assert _rel(gq, ge) <= 1e-2, f"{ndev}-dev grads drifted"
            assert np.isfinite(float(loss_q))

    def test_bf16_codec_numerics_on_step(self):
        """bf16 leg of the same path: tighter error bound. (No CPU wire
        assertion: XLA:CPU float-normalizes bf16 all-reduces back to f32
        — the byte win is TPU-real but not CPU-auditable; doc/fusion.md.)
        The exact leg is a ``_step_cache`` hit from the int8 test."""
        _multi_device()
        acc = _accept(ht.MESH_WORLD.size)
        model, toks, params = acc["model"], acc["toks"], acc["params"]
        with fusion.quant_override(None):
            _, grads_e = model.loss_and_grad_fn()(params, toks)
        with fusion.quant_override("bf16"):
            _, grads_q = model.loss_and_grad_fn()(params, toks)
        ge = np.concatenate([np.asarray(g).ravel() for g in
                             jax.tree_util.tree_leaves(grads_e)])
        gq = np.concatenate([np.asarray(g).ravel() for g in
                             jax.tree_util.tree_leaves(grads_q)])
        assert _rel(gq, ge) <= BOUNDS["bf16"]

    def test_step_dispatch_ticks_quant_counters(self):
        import optax

        _multi_device()
        acc = _accept(ht.MESH_WORLD.size)
        model, toks = acc["model"], acc["toks"]
        params = model.init(1)
        tx = optax.adam(1e-2)
        opt_state = tx.init(params)
        with fusion.quant_override("int8"):
            step = model.make_train_step(tx)
            c0 = _counters("op_engine.quant_collectives")
            params, opt_state, lval = step(params, opt_state, toks)
            params, opt_state, lval = step(params, opt_state, toks)
            c1 = _counters("op_engine.quant_collectives")
        assert c1[0] - c0[0] == 2
        assert np.isfinite(float(lval))

    def test_codec_toggle_never_poisons_step_cache(self):
        """loss_and_grad programs are keyed per codec: exact -> int8 ->
        exact returns the SAME exact-program object, and the two legs'
        results are reproduced bitwise. (Both programs are ``_step_cache``
        hits from the earlier acceptance tests — this test compiles
        NOTHING new, which is itself the point.)"""
        _multi_device()
        acc = _accept(ht.MESH_WORLD.size)
        model, toks, params = acc["model"], acc["toks"], acc["params"]
        with fusion.quant_override(None):
            fn_e = model.loss_and_grad_fn()
            le, ge = fn_e(params, toks)
        with fusion.quant_override("int8"):
            fn_q = model.loss_and_grad_fn()
            lq, gq = fn_q(params, toks)
        assert fn_q is not fn_e
        with fusion.quant_override(None):
            fn_e2 = model.loss_and_grad_fn()
            assert fn_e2 is fn_e  # cache hit, not a recompile
            le2, ge2 = fn_e2(params, toks)
        np.testing.assert_array_equal(np.asarray(le), np.asarray(le2))
        with fusion.quant_override("int8"):
            assert model.loss_and_grad_fn() is fn_q

    def test_deferred_trace_keeps_build_time_codec(self):
        """jax traces lazily at FIRST DISPATCH: a program built (and
        cache-keyed) under the exact codec, then first-dispatched inside
        an int8 override, must still run the EXACT wire format — the
        builders pin the captured quant_key into packed_psum precisely so
        a toggle between build and trace cannot poison the keyed program
        (reproduced before the fix: the exact-keyed entry quantized)."""
        from heat_tpu.nn.transformer import (TransformerLM,
                                             TransformerLMConfig)

        _multi_device()
        grid = _quant_grid(ht.MESH_WORLD.size)
        cfg = TransformerLMConfig(  # deliberately tiny: one extra compile
            vocab=64, d_model=16, n_heads=2, n_layers=1, d_ff=32)
        model = TransformerLM(grid, cfg)
        params = model.init(0)
        toks = model.shard_batch(np.zeros(
            (2 * ht.MESH_WORLD.size, 4), np.int32))
        with fusion.quant_override(None):
            fn = model.loss_and_grad_fn()  # built + keyed, NOT yet traced
        c0 = _counters("op_engine.quant_collectives")
        with fusion.quant_override("int8"):
            loss_a, _ = fn(params, toks)   # first dispatch = the trace
        c1 = _counters("op_engine.quant_collectives")
        assert c1 == c0, "exact-keyed program quantized at deferred trace"
        with fusion.quant_override(None):
            loss_b, _ = model.loss_and_grad_fn()(params, toks)
        np.testing.assert_array_equal(np.asarray(loss_a),
                                      np.asarray(loss_b))


# --------------------------------------------------------------------- #
# DataParallel + DASO call sites                                        #
# --------------------------------------------------------------------- #
class TestDataParallelQuant:
    def _net(self):
        flax = pytest.importorskip("flax.linen")
        from heat_tpu.nn.data_parallel import DataParallel
        from heat_tpu.optim import Adam, DataParallelOptimizer

        class MLP(flax.Module):
            @flax.compact
            def __call__(self, x):
                x = flax.Dense(64)(x)
                x = flax.tanh(x)
                return flax.Dense(10)(x)

        return DataParallel(MLP(), optimizer=DataParallelOptimizer(
            Adam(1e-3)))

    def test_quant_step_descends_close_to_exact_and_ticks(self):
        _multi_device()
        rng = np.random.default_rng(0)
        X = rng.standard_normal((8 * ht.MESH_WORLD.size, 32)).astype(
            np.float32)
        Y = rng.integers(0, 10, len(X)).astype(np.int32)
        net_e, net_q = self._net(), self._net()
        losses_e, losses_q = [], []
        c0 = _counters("op_engine.quant_collectives")
        with fusion.quant_override(None):
            for _ in range(4):
                losses_e.append(net_e.step(X, Y))
        mid = _counters("op_engine.quant_collectives")
        assert mid == c0  # exact leg never ticks
        with fusion.quant_override("int8"):
            for _ in range(4):
                losses_q.append(net_q.step(X, Y))
        c1 = _counters("op_engine.quant_collectives")
        assert c1[0] - mid[0] == 4
        assert losses_q[-1] < losses_q[0]
        for a, b in zip(losses_e, losses_q):
            assert abs(a - b) / abs(a) <= 2e-2

    def test_codec_toggle_rebuilds_packed_step(self):
        _multi_device()
        rng = np.random.default_rng(1)
        X = rng.standard_normal((8 * ht.MESH_WORLD.size, 32)).astype(
            np.float32)
        Y = rng.integers(0, 10, len(X)).astype(np.int32)
        net = self._net()
        with fusion.quant_override(None):
            net.step(X, Y)
            exact_step = net._packed_steps[(fusion.quant_key(), fusion.chunk_key(), fusion.hier_key())][0]
        with fusion.quant_override("int8"):
            net.step(X, Y)
            quant_step = net._packed_steps[(fusion.quant_key(), fusion.chunk_key(), fusion.hier_key())][0]
            assert quant_step is not exact_step  # sibling, not a reuse
        with fusion.quant_override(None):
            # toggle-back RE-HITS the cached exact program — no recompile
            net.step(X, Y)
            assert net._packed_steps[(fusion.quant_key(), fusion.chunk_key(), fusion.hier_key())][0] is exact_step
        assert len(net._packed_steps) == 2


class TestDASOQuant:
    def _daso(self):
        from heat_tpu.optim.dp_optimizer import DASO, Adam

        n = ht.MESH_WORLD.size
        if n < 4 or n % 2:
            pytest.skip("needs an even mesh of >= 4 for a real slow tier")
        return DASO(Adam(1e-3), total_epochs=4, local_size=n // 2)

    def _replicated(self, daso):
        params = {"w": np.linspace(-1, 1, 4096, dtype=np.float32)
                  .reshape(64, 64),
                  "b": np.arange(64, dtype=np.float32)}
        rep = daso.replicate(params)
        # diverge the replicas so the blend is nontrivial
        return jax.tree_util.tree_map(
            lambda p: p * (1 + jnp.arange(daso.slow_size).reshape(
                (-1,) + (1,) * (p.ndim - 1)) * 0.125), rep)

    def test_packed_capture_matches_legacy_bitwise(self):
        """The packed shard_map capture is value-identical to the legacy
        per-leaf jitted mean (same bf16 wire contract, same combine)."""
        daso = self._daso()
        rep = self._replicated(daso)
        with fusion.quant_override(None):
            packed = daso._global_sync(rep)
        daso2 = self._daso()
        with fusion.step_override(False):
            legacy = daso2._global_sync(rep)
        for k in ("w", "b"):
            np.testing.assert_array_equal(np.asarray(packed[k]),
                                          np.asarray(legacy[k]))

    def test_quant_blend_within_bound_small_leaves_exact(self):
        daso = self._daso()
        rep = self._replicated(daso)
        with fusion.quant_override(None):
            base = daso._global_sync(rep)
        daso_q = self._daso()
        c0 = _counters("op_engine.quant_collectives")
        with fusion.quant_override("int8"):
            got = daso_q._global_sync(rep)
        c1 = _counters("op_engine.quant_collectives")
        assert c1[0] - c0[0] == 1
        assert _rel(got["w"], base["w"]) <= BOUNDS["int8"]
        # the 64-element bias is below the floor: exact
        np.testing.assert_array_equal(np.asarray(got["b"]),
                                      np.asarray(base["b"]))


# --------------------------------------------------------------------- #
# fault injection: encode fault falls back to the exact collective      #
# --------------------------------------------------------------------- #
class TestInt8OverflowRegression:
    """The PR 10 int8-codec gotcha (ISSUE 12 satellite): huge-magnitude
    payloads used to round-trip as inf/NaN — a finite combined value
    just above bf16 max overflowed the return leg's bf16 downcast to
    inf, and a non-finite block amax poisoned its bf16 scale into inf,
    whose decode (0·inf) is NaN. The codec now SATURATES every bf16
    downcast into finite range and pre-scales the combine by a power of
    two (exponent-exact, bitwise-neutral in range), so 1e38-magnitude
    payloads stay finite and inside the 1e-2 contract."""

    def _roundtrip(self, payload_rows):
        """int8 all-reduce vs exact psum, each device holding its own
        row of ``payload_rows`` (size, n)."""
        comm = ht.get_comm()
        n = payload_rows.shape[1]
        flat = jnp.asarray(payload_rows.reshape(-1))

        def q_body(v):
            return fusion._quant_int8_allreduce(
                v, comm.axis_name, comm.size, (), 128)

        def e_body(v):
            return jax.lax.psum(v, comm.axis_name)

        def run(body):
            fn = jax.jit(shard_map(
                body, mesh=comm.mesh, in_specs=P(comm.axis_name),
                out_specs=P(), check_vma=False))
            return np.asarray(fn(flat))

        return run(q_body), run(e_body)

    def test_1e38_magnitude_payload_round_trips_finite(self):
        size = ht.get_comm().size
        if size < 4:
            pytest.skip("needs >= 4 same-sign peers for a transient "
                        "combine overflow")
        rng = np.random.default_rng(5)
        # 1e38-magnitude per-device summands: size-1 positive peers and
        # one cancelling negative one. The finite TOTAL is ~3.3e38·base,
        # but the running combine transiently passes f32 max (the old
        # code's per-peer sum went inf and stayed there); the
        # power-of-two-downscaled combine keeps every partial in range
        base = rng.uniform(0.25, 1.0, 512).astype(np.float32)
        s = np.float32(3.3e38 / (size - 2))
        rows = np.stack([base * s] * (size - 1)
                        + [-base * s]).astype(np.float32)
        q, e = self._roundtrip(rows)
        # the TRUE total is a finite f32 — but even the exact psum's
        # fixed combine order transiently overflows here (size-1
        # same-sign peers), so the f64 host total is the honest
        # reference; the downscaled int8 combine must stay finite and
        # inside the contract where the old code (and the naive exact
        # order) read inf
        ref = rows.astype(np.float64).sum(axis=0)
        assert np.isfinite(ref.astype(np.float32)).all()
        assert np.isfinite(q).all(), "quantized leg produced inf/NaN"
        assert _rel(q, ref) <= BOUNDS["int8"], _rel(q, ref)
        del e

    def test_sum_above_bf16_max_saturates_not_inf(self):
        _multi_device()
        size = ht.get_comm().size
        # finite f32 total just above bf16 max: the old return leg
        # downcast it to inf; now it saturates at ±bf16max (0.3% off,
        # far inside the 1e-2 contract)
        rows = np.full((size, 256), 3.4e38 / size, np.float32)
        q, e = self._roundtrip(rows)
        assert np.isfinite(e).all() and np.isfinite(q).all()
        assert _rel(q, e) <= BOUNDS["int8"], _rel(q, e)

    def test_non_finite_payload_never_nans(self):
        _multi_device()
        size = ht.get_comm().size
        rows = np.ones((size, 256), np.float32)
        rows[0, 3] = np.inf
        q, _ = self._roundtrip(rows)
        # non-finite payloads still do not round-trip (documented), but
        # they SATURATE instead of poisoning blocks as NaN
        assert not np.isnan(q).any()


class TestQuantFault:
    def test_flush_encode_fault_falls_back_exact(self):
        from heat_tpu.utils import faults

        _multi_device()
        x = ht.array(np.random.default_rng(9).standard_normal(
            (7, 1501)).astype("float32"), split=0)
        with fusion.quant_override(None):
            base = _chain_reduce(x, 0).numpy()
        c0 = _counters("op_engine.quant_fallbacks")
        with fusion.quant_override("int8"), \
                faults.inject("fusion.quant.encode=nth:1"):
            got = _chain_reduce(x, 0).numpy()
        c1 = _counters("op_engine.quant_fallbacks")
        assert c1[0] - c0[0] == 1
        # the fallback leg IS the exact collective: bitwise
        np.testing.assert_array_equal(got, base)
