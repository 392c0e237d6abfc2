"""The continuous-batching decode engine contract (ISSUE 15).

What is pinned here, in the order the ISSUE lists it:

* greedy continuous-batching tokens are BITWISE-equal to
  ``TransformerLM.generate()`` per request, across mixed prompt/output
  lengths and join orders (slots are isolated lanes — results never
  depend on co-residents);
* a finished sequence (EOS or max_new_tokens) frees its slot for the
  next queued request (slot reuse);
* steady-state decoding dispatches cached executables only — 0
  program-cache misses after warmup, INCLUDING across
  quant/chunk/hier codec toggles (siblings compile once, toggle-back
  re-hits);
* the decode-step carry is donated (old cache buffers invalidate);
* slot grants follow tenant priority (FIFO within one);
* the per-step host fetch is ONLY the sampled-token vector — audited
  with ``jax.transfer_guard_device_to_host("disallow")`` around live
  decoding (the engine's one ``allow`` doorway);
* ``generate()`` program-key hygiene: prompt lengths bucket onto the
  power-of-two ladder, so varying S0 shares one compiled program.

§2b executable-budget discipline: ONE model/params/program-cache memo
for the whole module (every engine instance shares the compiled
prefill/step programs), and the module teardown drops the compiled
state so the suite's end-state executable count is unchanged.
"""

import gc

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

import heat_tpu as ht
from heat_tpu.core import fusion
from heat_tpu.nn.transformer import TransformerLM, TransformerLMConfig
from heat_tpu.serve import (DecodeConfig, DecodeEngine, ServeClosed,
                            ServeOverloaded)
from heat_tpu.serve.program_cache import ProgramCache
from heat_tpu.utils import metrics as _pm

_MEMO: dict = {}

# the meshes the engine's programs differ over: every device (dp x tp 2, the
# module's default), ONE device (the programs are plain `jit`s there) and
# dp 2 x tp 2 (`shard_map`, slots over dp and heads over tp)
MESHES = ("one", "dp2tp2")


def _fx(mesh="all", compute=jnp.float32):
    """Module-shared model/params/program-cache (§2b: one compile set a
    mesh and ``compute_dtype``; the parameters are float32 under both)."""
    key = mesh
    if compute != jnp.float32:
        key = (mesh, jnp.dtype(compute).name)
    if key not in _MEMO:
        n = ht.get_comm().size
        if mesh == "all":
            tp = 2 if n % 2 == 0 else 1
            dp, devices = n // tp, None
        else:
            dp, tp = {"one": (1, 1), "dp2tp2": (2, 2)}[mesh]
            if dp * tp > n:
                pytest.skip(f"mesh {mesh} needs {dp * tp} devices")
            devices = jax.devices()[:dp * tp]
        grid = ht.MeshGrid((dp, 1, tp, 1), ("dp", "pp", "tp", "sp"),
                           devices=devices)
        cfg = TransformerLMConfig(vocab=29, d_model=32, n_heads=4,
                                  n_layers=2, d_ff=64, compute_dtype=compute)
        model = TransformerLM(grid, cfg)
        _MEMO[key] = dict(model=model, params=model.init(11),
                          cache=ProgramCache(name=f"decode-test-{key}"),
                          refs={})
    return _MEMO[key]


@pytest.fixture(scope="module", autouse=True)
def _drop_compiled_state():
    yield
    _MEMO.clear()
    fusion.reset()
    gc.collect()


def _engine(mesh="all", compute=jnp.float32, **over):
    fx = _fx(mesh, compute)
    kw = dict(slots=2 * fx["model"].dp_world, max_seq_len=64)
    kw.update(over)
    return DecodeEngine(fx["model"], fx["params"], DecodeConfig(**kw),
                        program_cache=fx["cache"])


def _prompt(seed, s0):
    rng = np.random.default_rng(seed)
    return rng.integers(0, _fx()["model"].cfg.vocab, (s0,)).astype(np.int32)


def _ref(prompt, max_new, mesh="all", compute=jnp.float32):
    """generate()'s tokens for one request (memoized — the reference
    programs are the module's biggest compiles)."""
    fx = _fx(mesh, compute)
    key = (prompt.tobytes(), int(max_new))
    if key not in fx["refs"]:
        B = fx["model"].dp_world
        out = np.asarray(fx["model"].generate(
            fx["params"], np.tile(prompt, (B, 1)), max_new))
        fx["refs"][key] = out[0]
    return fx["refs"][key]


# --------------------------------------------------------------------- #
# parity                                                                #
# --------------------------------------------------------------------- #
MIX = ((3, 6), (9, 3), (5, 10), (12, 4), (7, 8), (4, 2))


def test_greedy_matches_generate_mixed_lengths():
    """THE acceptance parity: continuous batching with mixed prompt and
    output lengths produces, per request, exactly generate()'s greedy
    tokens (prompt + continuation).

    CPU-ONLY ORACLE. The engine (S_cap-row cache, bucketed prefill) and
    generate() (Sb+max_new rows, one scan) are differently shaped
    programs; their greedy tokens are bitwise-equal here because XLA:CPU
    computes both in float32. On the TPU (bf16 compute, default matmul
    precision) an argmax flips on rounding alone — measured on a v5e at
    d1024/L8: 1 token of 3 requests differed while both programs stayed
    within 0.0083 logits of the float32 reference's maximum (PR 27). The
    on-chip check is therefore logit-level (``chip_smoke.py`` decode
    phase, ``tests/test_reference.py``); never port this equality to the
    chip."""
    with _engine() as eng:
        eng.warmup()
        futs = [eng.submit(_prompt(40 + i, s0), mn)
                for i, (s0, mn) in enumerate(MIX)]
        outs = [f.result(120) for f in futs]
    for i, ((s0, mn), out) in enumerate(zip(MIX, outs)):
        want = _ref(_prompt(40 + i, s0), mn)
        np.testing.assert_array_equal(out, want)
        assert out.shape == (s0 + mn,)


def test_join_order_independent():
    """Slots are isolated lanes: submitting the same mix in a different
    join order (and joining mid-flight of other sequences) changes no
    request's tokens."""
    order = [3, 0, 5, 2, 4, 1]
    with _engine() as eng:
        # joins staggered: first two start decoding before the rest join
        futs = {}
        for j in order[:2]:
            futs[j] = eng.submit(_prompt(40 + j, MIX[j][0]), MIX[j][1])
        for j in order[2:]:
            futs[j] = eng.submit(_prompt(40 + j, MIX[j][0]), MIX[j][1])
        outs = {j: f.result(120) for j, f in futs.items()}
    for j, out in outs.items():
        np.testing.assert_array_equal(
            out, _ref(_prompt(40 + j, MIX[j][0]), MIX[j][1]))


def test_eos_stops_early_with_exact_prefix():
    """eos_id: generation stops on sampling it; the result is exactly
    generate()'s token stream truncated at (and including) the first
    EOS hit."""
    prompt, mn = _prompt(43, MIX[3][0]), MIX[3][1]
    full = _ref(prompt, mn)
    gen = full[prompt.size:]
    eos = int(gen[1])  # force a stop after the 2nd generated token
    with _engine() as eng:
        out = eng.generate(prompt, mn, eos_id=eos, timeout=120)
    cut = int(np.nonzero(gen == eos)[0][0]) + 1
    np.testing.assert_array_equal(out, full[:prompt.size + cut])


# --------------------------------------------------------------------- #
# slot lifecycle                                                        #
# --------------------------------------------------------------------- #
def test_slot_reuse_after_finish():
    """More requests than slots: every finished sequence frees its lane
    for a queued one — all requests complete with one engine-sized slot
    pool, and the engine ends empty."""
    with _engine() as eng:
        n_req = 3 * eng.slots
        futs = [eng.submit(_prompt(100 + i, 3 + (i % 5)), 2 + (i % 3))
                for i in range(n_req)]
        outs = [f.result(180) for f in futs]
        st = eng.stats()
        assert st["prefills"] == n_req
        assert st["live"] == 0 and st["queue_depth"] == 0
    for i, out in enumerate(outs):
        np.testing.assert_array_equal(
            out, _ref(_prompt(100 + i, 3 + (i % 5)), 2 + (i % 3)))


@pytest.mark.parametrize("mesh", ("all",) + MESHES)
def test_donation_invalidates_old_cache(mesh):
    """The decode-step carry is donated: after a request runs, EVERY leaf
    of the cache the engine started with (a K and a V lane a layer) is
    deleted (device memory stays ONE cache, not one per step)."""
    with _engine(mesh) as eng:
        leaves0 = jax.tree.leaves(eng._cache)
        assert len(leaves0) == 2 * eng.model.cfg.n_layers
        eng.generate(_prompt(40, 3), 4, timeout=120)
        assert all(leaf.is_deleted() for leaf in leaves0)
        assert not any(leaf.is_deleted()
                       for leaf in jax.tree.leaves(eng._cache))


# the three ways a lane's rows can be wrong without a wrong shape: a short
# prompt in the slot a long tenant just left (its rows beyond the prompt are
# the old tenant's, masked by position alone), a prompt padded up to its
# bucket (the pad's rows are garbage until decode overwrites them), and a
# prompt that fills the largest bucket an engine of 64 positions has
SLOT_CASES = {
    "reused_slot": ((20, 8), (3, 6)),
    "padded_prompt": ((5, 6),),
    "largest_bucket": ((32, 8),),
}


@pytest.mark.parametrize("case", SLOT_CASES)
@pytest.mark.parametrize("mesh", MESHES)
def test_engine_matches_generate_on_one_device_and_on_dp_tp(mesh, case):
    """Tokens served through the engine equal ``generate()``'s token for
    token where the programs are plain ``jit``s (one device) and where
    they are ``shard_map``s (dp 2 x tp 2), with the cache a leaf a layer
    in both. One request at a time into an engine of ``dp_world`` slots,
    so every request of a case is served from slot 0."""
    with _engine(mesh, slots=_fx(mesh)["model"].dp_world) as eng:
        for i, (s0, mn) in enumerate(SLOT_CASES[case]):
            prompt = _prompt(300 + i, s0)
            out = eng.generate(prompt, mn, timeout=120)
            np.testing.assert_array_equal(out, _ref(prompt, mn, mesh))
        assert eng.stats()["prefills"] == len(SLOT_CASES[case])
        assert eng.stats()["decode_fallbacks"] == 0


@pytest.mark.parametrize("mesh", MESHES)
def test_logprobs_are_the_full_forwards_log_softmax_at_the_served_token(mesh):
    """``DecodeConfig(logprobs=True)``: the same tokens as without it, and on
    each done future one float32 a generated token, the log-softmax of the
    logits its slot's step computed (against ``logits_fn``'s one forward over
    the served sequence), with a second request live beside it."""
    fx = _fx(mesh)
    model = fx["model"]
    sizes = ((9, 6), (4, 9), (20, 5))
    with _engine(mesh, logprobs=True) as eng:
        futs = [eng.submit(_prompt(500 + i, s0), mn)
                for i, (s0, mn) in enumerate(sizes)]
        outs = [f.result(120) for f in futs]
    for i, ((s0, mn), fut, out) in enumerate(zip(sizes, futs, outs)):
        np.testing.assert_array_equal(out, _ref(_prompt(500 + i, s0), mn, mesh))
        logits = np.asarray(model.logits_fn()(
            fx["params"], np.tile(out, (model.dp_world, 1))))[0, s0 - 1:-1]
        want = np.asarray(jax.nn.log_softmax(logits.astype(np.float64), -1))[
            np.arange(mn), out[s0:]]
        assert fut.logprobs.dtype == np.float32
        np.testing.assert_allclose(fut.logprobs, want, atol=1e-4)


@pytest.mark.parametrize("mesh", MESHES)
def test_programs_are_plain_jits_on_one_device_only(mesh):
    """On a mesh of ONE device the step and the prefill are plain ``jit``s
    (a ``shard_map`` of one shard computes the same and may copy donated
    lanes at its boundary); on dp 2 x tp 2 the bodies name mesh axes and
    the ``shard_map`` stays. The program names are the same either way."""
    with _engine(mesh) as eng:
        assert eng._one_device == (mesh == "one")
        n = eng.slots
        step = eng._step_prog().trace(
            eng.params, *eng._cache, eng._pos, np.zeros(n, bool), eng._toks,
            jax.random.key(0))
        prefill = eng._prefill_prog(8).trace(
            eng.params, *eng._cache, eng._pos, eng._toks,
            np.zeros(8, np.int32), np.int32(3), np.int32(0),
            jax.random.key(0))
        for traced, name in ((step, "jit_decode_step"),
                             (prefill, "jit_decode_prefill")):
            assert ("shard_map" in str(traced.jaxpr)) == (mesh != "one")
            assert f"module @{name}" in traced.lower().as_text()


# --------------------------------------------------------------------- #
# the degraded step                                                     #
# --------------------------------------------------------------------- #
# three requests granted in one turn into an engine of four slots: by the
# third step two are live, one has finished (a dead slot that was live) and
# one slot was never granted
BURST = ((5, 10), (9, 6), (3, 2))


def _burst(mesh, fault=None):
    """Serve ``BURST`` (under the fault plan ``fault``). Returns (the
    answers, the final per-slot positions and tokens, the engine's
    stats)."""
    import contextlib

    from heat_tpu.utils import faults

    with _engine(mesh, slots=4) as eng:
        eng.pause()
        futs = [eng.submit(_prompt(500 + i, s0), mn)
                for i, (s0, mn) in enumerate(BURST)]
        with faults.inject(fault) if fault else contextlib.nullcontext():
            eng.resume()
            outs = [f.result(timeout=300) for f in futs]
        return outs, np.asarray(eng._pos), np.asarray(eng._toks), eng.stats()


@pytest.mark.parametrize("mesh", MESHES)
def test_the_degraded_step_serves_the_same_tokens(mesh):
    """A step whose dispatch fails runs the step program's OWN body
    uncompiled, on the engine's mesh (directly on one device, under its
    ``shard_map`` on dp 2 x tp 2): the answers are the unfaulted run's
    (and ``generate()``'s), the step counts as ONE fallback, and a dead
    slot's token and position stay as the compiled step leaves them."""
    want, pos0, toks0, st0 = _burst(mesh)
    assert st0["decode_fallbacks"] == 0
    fb0 = int(_pm.counters().get("serve.decode_fallbacks", 0))
    got, pos, toks, st = _burst(mesh, fault="serve.decode.step=nth:3")
    assert st["decode_fallbacks"] == 1
    assert int(_pm.counters()["serve.decode_fallbacks"]) == fb0 + 1
    assert st["decode_steps"] == st0["decode_steps"] == 9
    for i, (s0, mn) in enumerate(BURST):
        np.testing.assert_array_equal(got[i], want[i])
        np.testing.assert_array_equal(
            got[i], _ref(_prompt(500 + i, s0), mn, mesh))
    # slot 2 finished after the first step and slot 3 never ran: the
    # degraded third step advanced neither
    np.testing.assert_array_equal(pos, pos0)
    np.testing.assert_array_equal(toks, toks0)
    assert pos[2] == 3 + 1 and pos[3] == 0


# --------------------------------------------------------------------- #
# what the engine holds                                                 #
# --------------------------------------------------------------------- #
BF16 = jnp.bfloat16


@pytest.mark.parametrize("mesh", MESHES)
def test_engine_holds_a_compute_dtype_copy_with_the_callers_shardings(mesh):
    """Float32 parameters under ``compute_dtype=bfloat16``: the engine holds
    every floating leaf in bfloat16, sharded as the caller's (``wqkv`` with
    each head's matrix contiguous, its heads over tp as before), reports
    those bytes, and the caller's tree is untouched (the masters are its
    own)."""
    fx = _fx(mesh, BF16)
    model, given = fx["model"], fx["params"]
    with _engine(mesh, BF16) as eng:
        want = dict(given, stages=dict(given["stages"]))
        wqkv = want["stages"].pop("wqkv")
        assert wqkv.sharding.spec == P("pp", None, None, None, "tp", None)
        want["stages"][model.HELD_QKV] = jax.device_put(
            jnp.moveaxis(wqkv, 2, 4), NamedSharding(
                model.grid.mesh, P("pp", None, None, "tp", None, None)))
        assert jax.tree.structure(eng.params) == jax.tree.structure(want)
        for h, g in zip(jax.tree.leaves(eng.params), jax.tree.leaves(want)):
            assert h.dtype == BF16 and g.dtype == jnp.float32
            assert h.shape == g.shape and h.sharding == g.sharding
            np.testing.assert_array_equal(np.asarray(h),
                                          np.asarray(g.astype(BF16)))
        assert eng.stats()["param_bytes"] == sum(
            2 * g.size for g in jax.tree.leaves(given))
        assert all(g.dtype == jnp.float32 for g in jax.tree.leaves(given))
        # a tree that is held in the step's dtype already is held as it is
        again = fx["model"].serving_params(eng.params)
        assert again is eng.params


@pytest.mark.parametrize("mesh", ("all",) + MESHES)
def test_float32_compute_holds_the_callers_tree_itself(mesh):
    fx = _fx(mesh)
    assert fx["model"].serving_params(fx["params"]) is fx["params"]
    with _engine(mesh) as eng:
        assert eng.params is fx["params"]
        assert eng.stats()["param_bytes"] == sum(
            4 * a.size for a in jax.tree.leaves(fx["params"]))


def _walked(fx, eng, params, prompt, n_out):
    """Greedy tokens and their log-probabilities through the cache on
    ``params``, the cast wherever that tree's dtypes put it: the bodies the
    engine compiles, jitted bare (one device: they name no mesh axis), a
    prefill stored into slot 0 and then one step of every slot a token."""
    model, wire = fx["model"], eng._wire()

    @jax.jit
    def first(params, cache, prompt, n):
        kept, logits = model.prefill(params, prompt[None], n, wire=wire)
        return model.cache_store(cache, kept, jnp.int32(0),
                                 jnp.bool_(True)), logits[0]

    @jax.jit
    def step(params, cache, toks, pos):
        logits, cache = model.decode_step_logits(params, cache, toks, pos,
                                                 wire=wire)
        return cache, logits[0]

    shapes, _specs, _bytes = model.cache_layout(eng.slots, eng.S_cap)
    cache = jax.tree.map(lambda sd: jnp.zeros(sd.shape, sd.dtype), shapes)
    n = len(prompt)
    padded = np.zeros(model.serving_bucket(n), np.int32)
    padded[:n] = prompt
    cache, logits = first(params, cache, jnp.asarray(padded), jnp.int32(n))
    toks, pos = np.zeros(eng.slots, np.int32), np.zeros(eng.slots, np.int32)
    seq, logp = [], []
    for i in range(n_out):
        seq.append(int(jnp.argmax(logits)))
        logp.append(np.asarray(eng._logprob_of(logits, jnp.int32(seq[-1]))))
        toks[0], pos[0] = seq[-1], n + i
        cache, logits = step(params, cache, jnp.asarray(toks),
                             jnp.asarray(pos))
    return np.asarray(seq, np.int32), np.asarray(logp, np.float32)


@pytest.mark.parametrize("s0", (5, 11))
def test_the_held_copy_computes_what_the_cast_inside_computed(s0):
    """One rounding, done once: a prompt and 16 steps of the programs' own
    bodies on the caller's float32 tree (``_cast_params`` and ``_head``
    casting inside, as in every step before the engine held a copy) and on
    the held bfloat16 tree give the same greedy tokens and the same
    log-probabilities, bit for bit. The engine serves those tokens, and
    those log-probabilities to the float32 rounding of a log-sum that its
    program fuses with the step."""
    fx = _fx("one", BF16)
    prompt = _prompt(700 + s0, s0)
    with _engine("one", BF16, logprobs=True) as eng:
        fut = eng.submit(prompt, 17)
        out = fut.result(120)
        inside = _walked(fx, eng, fx["params"], prompt, 17)
        once = _walked(fx, eng, eng.params, prompt, 17)
    np.testing.assert_array_equal(once[0], inside[0])
    np.testing.assert_array_equal(once[1], inside[1])
    np.testing.assert_array_equal(out[s0:], inside[0])
    np.testing.assert_allclose(fut.logprobs, inside[1], rtol=0, atol=1e-6)


@pytest.mark.parametrize("mesh", ("all", "dp2tp2"))
def test_the_held_copy_on_dp_tp_gives_generates_tokens(mesh):
    """Under ``shard_map`` the held tree goes in where the float32 one did
    (``param_specs()`` says nothing of dtypes): the engine's greedy tokens
    are those of ``generate()``, one program over the caller's float32 tree
    with the cast inside, across prompt buckets and slots."""
    with _engine(mesh, BF16) as eng:
        sizes = ((5, 17), (9, 6), (12, 9), (3, 12))
        futs = [eng.submit(_prompt(800 + i, s0), mn)
                for i, (s0, mn) in enumerate(sizes)]
        for i, ((s0, mn), fut) in enumerate(zip(sizes, futs)):
            np.testing.assert_array_equal(
                fut.result(120), _ref(_prompt(800 + i, s0), mn, mesh, BF16))
        assert eng.stats()["decode_fallbacks"] == 0


def test_serve_holds_no_layer_math():
    """``serve/decode.py`` is a client of ``TransformerLM``'s four decode
    functions: it imports no private of ``nn/transformer.py`` and has no
    degraded step of its own, so a second statement of a layer cannot grow
    back unseen."""
    import re

    import heat_tpu.serve.decode as mod

    with open(mod.__file__) as f:
        text = f.read()
    assert "_step_eager" not in text
    imported = re.findall(
        r"from \.\.nn\.transformer import (\([^)]*\)|[^\n]*)", text)
    assert not [n for names in imported for n in re.findall(r"\w+", names)
                if n.startswith("_")]
    assert not re.search(r"\b(_rmsnorm|rope_apply|einsum)\b", text)


# --------------------------------------------------------------------- #
# steady state + codec keying                                           #
# --------------------------------------------------------------------- #
def test_steady_state_zero_misses_with_codec_toggles():
    """After warmup, traffic over the same prompt ladder compiles
    NOTHING — and toggling the quant/chunk/hier configuration compiles
    SIBLING programs exactly once each (the keys carry
    quant_key()/chunk_key()/hier_key()), with toggle-back re-hitting
    the original executables."""
    fx = _fx()
    with _engine() as eng:
        eng.warmup()
        m0 = fx["cache"].stats()["misses"]
        futs = [eng.submit(_prompt(40 + i, s0), mn)
                for i, (s0, mn) in enumerate(MIX)]
        for f in futs:
            f.result(120)
        assert fx["cache"].stats()["misses"] - m0 == 0

        # codec toggles compile siblings (new keys) ...
        with fusion.quant_override("int8"):
            eng.generate(_prompt(40, 3), 2, timeout=120)
        with fusion.chunk_override(4):
            eng.generate(_prompt(40, 3), 2, timeout=120)
        with fusion.hier_override(True, tiers=(2, 2)):
            eng.generate(_prompt(40, 3), 2, timeout=120)
        toggled = fx["cache"].stats()["misses"] - m0
        assert toggled > 0

        # ... toggle-back re-hits: the exact programs are still cached
        m1 = fx["cache"].stats()["misses"]
        eng.generate(_prompt(40, 3), 2, timeout=120)
        assert fx["cache"].stats()["misses"] == m1

        # and re-toggling re-hits the sibling programs too
        with fusion.quant_override("int8"):
            eng.generate(_prompt(40, 3), 2, timeout=120)
        assert fx["cache"].stats()["misses"] == m1


def test_quant_toggle_keeps_greedy_tokens():
    """On tp-sharded grids the decode psums ride packed_psum, so the
    int8 wire codec applies — greedy argmax must survive it for this
    model (and on tp=1 grids there is no collective at all, bitwise by
    construction)."""
    prompt, mn = _prompt(41, 9), 3
    with _engine() as eng:
        with fusion.quant_override("int8"):
            out = eng.generate(prompt, mn, timeout=120)
    np.testing.assert_array_equal(out, _ref(prompt, mn))


# --------------------------------------------------------------------- #
# tenancy                                                               #
# --------------------------------------------------------------------- #
def test_tenant_priority_orders_slot_grants():
    """Queued requests wait in tenant-priority order (FIFO within a
    priority) — the order slot grants pop — and per-tenant
    admitted/completed counters fold into the engine stats."""
    with _engine() as eng:
        eng.register_tenant("hi", priority=10)
        eng.register_tenant("lo", priority=0)
        eng.pause()
        lo = [eng.submit(_prompt(100 + i, 3), 2, tenant="lo")
              for i in range(3)]
        hi = [eng.submit(_prompt(200 + i, 3), 2, tenant="hi")
              for i in range(2)]
        # the queue IS the grant order: both hi requests outrank every lo
        assert [r.tenant for r in eng._q] == ["hi", "hi", "lo", "lo", "lo"]
        eng.resume()
        for f in hi + lo:
            f.result(120)
        st = eng.stats()["tenants"]
        assert st["hi"]["admitted"] == 2 and st["hi"]["completed"] == 2
        assert st["lo"]["admitted"] == 3 and st["lo"]["completed"] == 3


def test_unknown_tenant_rejected():
    with _engine() as eng:
        with pytest.raises(ValueError, match="register_tenant"):
            eng.submit(_prompt(40, 3), 2, tenant="ghost")


# --------------------------------------------------------------------- #
# device-residency audit                                                #
# --------------------------------------------------------------------- #
def test_per_step_host_fetch_is_only_the_token_vector():
    """THE device-residency audit: with device→host transfers
    DISALLOWED process-wide, live decoding still runs — the engine's one
    ``allow`` doorway (``DecodeEngine._fetch``) moves only the sampled
    token vector / first-token scalar, and nothing else (cache,
    positions, logits) ever crosses."""
    with _engine() as eng:
        eng.warmup()
        eng.pause()
        futs = [eng.submit(_prompt(40 + i, s0), mn)
                for i, (s0, mn) in enumerate(MIX[:3])]
        with jax.transfer_guard_device_to_host("disallow"):
            eng.resume()
            outs = [f.result(120) for f in futs]
        st = eng.stats()
        assert st["decode_steps"] > 0
    for i, out in enumerate(outs):
        np.testing.assert_array_equal(
            out, _ref(_prompt(40 + i, MIX[i][0]), MIX[i][1]))


# --------------------------------------------------------------------- #
# admission / lifecycle edges                                           #
# --------------------------------------------------------------------- #
def test_validation_and_shed():
    with _engine(queue_limit=2) as eng:
        with pytest.raises(ValueError, match="at least one token"):
            eng.submit(np.zeros(0, np.int32), 2)
        with pytest.raises(ValueError, match="vocab"):
            eng.submit(np.full(3, 10_000, np.int32), 2)
        with pytest.raises(ValueError, match="max_new_tokens"):
            eng.submit(_prompt(40, 3), 0)
        with pytest.raises(ValueError, match="sequence bucket"):
            eng.submit(_prompt(40, 3), 10_000)
        eng.pause()
        eng.submit(_prompt(40, 3), 2)
        eng.submit(_prompt(41, 3), 2)
        shed0 = int(_pm.counters().get("serve.decode_shed", 0))
        with pytest.raises(ServeOverloaded):
            eng.submit(_prompt(42, 3), 2)
        assert int(_pm.counters().get("serve.decode_shed", 0)) == shed0 + 1
        eng.resume()
        eng.flush(120)


def test_close_no_drain_with_inflight_request():
    """Regression (review round): a slot-granted request's future is
    already RUNNING — close(drain=False) must fail it with ServeClosed,
    not raise RuntimeError from set_running_or_notify_cancel (which
    would also skip the worker join and, from __exit__, mask the user's
    exception)."""
    import time

    eng = _engine()
    # long enough that it is still mid-decode when close lands
    f = eng.submit(_prompt(40, 3), 40)
    deadline = time.monotonic() + 60
    while eng.live_slots == 0 and time.monotonic() < deadline:
        time.sleep(0.005)
    assert eng.live_slots > 0
    eng.close(drain=False)  # must not raise
    with pytest.raises(ServeClosed):
        f.result(10)
    assert not eng.worker_alive


def test_close_paths():
    eng = _engine()
    eng.pause()
    f = eng.submit(_prompt(40, 3), 2)
    eng.close(drain=False)
    with pytest.raises(ServeClosed):
        f.result(10)
    with pytest.raises(ServeClosed):
        eng.submit(_prompt(40, 3), 2)
    assert not eng.worker_alive
    # drain close answers what is queued
    eng2 = _engine()
    f2 = eng2.submit(_prompt(40, 3), 2)
    eng2.close(drain=True)
    assert f2.result(10).shape == (5,)


def test_runtime_stats_decode_fold():
    steps0 = ht.runtime_stats()["serve"]["decode"]["decode_steps"]
    with _engine() as eng:
        eng.generate(_prompt(40, 3), 4, timeout=120)
        rt = ht.runtime_stats()["serve"]["decode"]
        assert rt["slots"] >= eng.slots
        assert rt["decode_steps"] > steps0
        assert rt["tokens_out"] > 0


# --------------------------------------------------------------------- #
# generate() program-key hygiene (ISSUE 15 satellite)                   #
# --------------------------------------------------------------------- #
def test_generate_prompt_bucket_shares_programs():
    """Varying prompt lengths within one power-of-two bucket share ONE
    compiled generate() program (pad + traced n_valid); crossing the
    bucket boundary compiles exactly one more."""
    fx = _fx()
    model, params = fx["model"], fx["params"]
    B = model.dp_world
    rng = np.random.default_rng(0)

    def gen(s0):
        # max_new=13 is unique to this test: no other module test may
        # have pre-populated a ("generate", B, bucket, 13, ...) program
        prompts = rng.integers(0, model.cfg.vocab, (B, s0)).astype(np.int32)
        return np.asarray(model.generate(params, prompts, 13))

    gen(5)
    n0 = len(model._step_cache)
    gen(6)
    gen(7)
    gen(8)  # bucket(5..8) == 8: all share the first program
    assert len(model._step_cache) == n0
    gen(9)  # bucket 16: exactly one sibling
    assert len(model._step_cache) == n0 + 1
    gen(12)
    assert len(model._step_cache) == n0 + 1


def test_generate_bucketed_results_unpadded_exact():
    """Bucketing pads the prompt and threads the true length as a traced
    scalar — results must be invariant to how much padding the bucket
    added (S0=8 runs unpadded in its bucket; S0=5 pads by 3)."""
    fx = _fx()
    model, params = fx["model"], fx["params"]
    B = model.dp_world
    rng = np.random.default_rng(5)
    p8 = rng.integers(0, model.cfg.vocab, (B, 8)).astype(np.int32)
    out8 = np.asarray(model.generate(params, p8, 3))
    # the padded-bucket program and an exact-length run agree: re-run the
    # 8-token prompt THROUGH the 16-bucket program by extending length
    p5 = p8[:, :5]
    out5 = np.asarray(model.generate(params, p5, 3))
    assert out5.shape == (B, 8) and out8.shape == (B, 11)
    # prefix property: the 5-token prompt's continuation is computed on
    # exactly the 5 valid rows (padding masked), so feeding generate the
    # same 5 tokens twice is deterministic
    np.testing.assert_array_equal(
        out5, np.asarray(model.generate(params, p5, 3)))
