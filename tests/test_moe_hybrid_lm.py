"""A hybrid of Mamba-2 mixers, plain grouped-query attention and routed experts
beside a shared one in ``TransformerLM``'s pattern, SERVED: the pieces that
``granite-4.0-h-small`` is made of (RMSNorm, no positions, the embedding,
residual, attention and logit multipliers, top-k of all experts with gates over
the chosen, a device that holds a share of the experts), judged by LOGITS
against the plain float32 forward of ``heat_tpu.nn.reference.pattern_logits``
(one full forward, a scan over positions, the held experts one after another).

Small size: D 64, 8 query / 2 key-value heads of 8, V 128, five layers (two
Mamba-2, attention, two Mamba-2: two scanned runs and a layer between), 4 state
heads of 32 with state 16 and a prompt chunk of 8, 8 experts of width 32, 3 a
token, experts 0..3 held, a shared expert of width 48.

Tolerances. float32 on the CPU: the cached path (chunked prompt, sorted
grouped products) and the reference (a scan, a masked loop) differ by summation
order, so 1e-4 of the logits' spread holds with room. bfloat16 compute reads
0.010 to 0.030 of the spread here (48 positions of 8 sequences; the residual
multiplier 0.22 damps what a layer's rounding adds), so 0.06; the float8
control reads 0.097 and more at every position and has to fail that.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import heat_tpu as ht
from heat_tpu.nn import mixers, parallel
from heat_tpu.nn import reference as ref
from heat_tpu.nn.transformer import TransformerLM, TransformerLMConfig
from heat_tpu.serve import serve_transformer

AXES = ("dp", "pp", "tp", "sp")
PATTERN = ("mamba2", "mamba2", "gqa", "mamba2", "mamba2")
E, K_TOP, HELD = 8, 3, (0, 4)
F32_TOL, BF16_TOL = 1e-4, 0.06
BASE = dict(
    vocab=128, d_model=64, n_heads=8, n_kv_heads=2, n_layers=5, rope=False,
    pattern=PATTERN, ffn=("moe",) * 5, norm_kind="rmsnorm", d_inner=128,
    d_state=16, ssm_heads=4, ssm_chunk=8, n_experts=E,
    experts_per_token=K_TOP, d_expert=32, d_shared=48, experts_held=HELD,
    embedding_multiplier=12.0, residual_multiplier=0.22,
    attention_multiplier=0.0625, logits_scaling=16.0, init_scale=0.2)


def make(dtype=jnp.float32, seed=0, **over):
    grid = ht.MeshGrid((1, 1, 1, 1), AXES, devices=jax.devices()[:1])
    cfg = TransformerLMConfig(**dict(BASE, compute_dtype=dtype,
                                     param_dtype=dtype, **over))
    model = TransformerLM(grid, cfg)
    return model, model.init(seed)


_MEMO = {}


def small(dtype=jnp.float32):
    """One model (and its jitted bodies) a dtype for the whole file."""
    key = jnp.dtype(dtype).name
    if key not in _MEMO:
        model, params = make(dtype)
        _MEMO[key] = {
            "model": model, "params": params,
            "hp": ref.host_params(params),
            "prefill": jax.jit(model.prefill),
            "step": jax.jit(model.decode_step_logits),
            "store": jax.jit(model.cache_store)}
    return _MEMO[key]


def teardown_module(module):
    _MEMO.clear()


def prompt_of(seed, n):
    return np.random.default_rng([seed, n]).integers(0, 128, n).astype(np.int32)


def fresh_cache(model, slots, s_cap):
    shapes, _specs, _bytes = model.cache_layout(slots, s_cap)
    return jax.tree.map(lambda sd: jnp.zeros(sd.shape, sd.dtype), shapes)


def through_cache(fx, prompt, n_out, slot=0, slots=2, s_cap=64, bucket=None):
    """Prefill ``prompt`` (padded to ``bucket``, default its own) into lane
    ``slot`` and decode greedily through the cache: the bodies the engine
    compiles. Returns (the sequence, the logits of every served position, the
    pairs by held expert summed over the prefill and the steps)."""
    model, params = fx["model"], fx["params"]
    cache = fresh_cache(model, slots, s_cap)
    n = len(prompt)
    padded = np.zeros(bucket or model.serving_bucket(n), np.int32)
    padded[:n] = prompt
    kept, logits, pairs = fx["prefill"](params, jnp.asarray(padded)[None],
                                        jnp.int32(n))
    cache = fx["store"](cache, kept, jnp.int32(slot), jnp.bool_(True))
    rows, seq, held = [np.asarray(logits[0])], list(prompt), np.asarray(pairs)
    toks, pos = np.zeros(slots, np.int32), np.zeros(slots, np.int32)
    live = np.zeros(slots, bool)
    live[slot] = True
    for i in range(n_out - 1):
        seq.append(int(rows[-1].argmax()))
        toks[slot], pos[slot] = seq[-1], n + i
        logits, cache, pairs = fx["step"](
            params, cache, jnp.asarray(toks), jnp.asarray(pos),
            live=jnp.asarray(live))
        rows.append(np.asarray(logits[slot]))
        held = held + np.asarray(pairs)
    seq.append(int(rows[-1].argmax()))
    return np.asarray(seq, np.int32), np.stack(rows), held


def served_gap(fx, seq, rows, n_prompt, fp8=False):
    want = np.asarray(ref.pattern_logits(
        fx["hp"], seq, fx["model"].cfg, fp8=fp8))[n_prompt - 1:len(seq) - 1]
    return float(np.abs(rows - want).max() / want.std())


# --------------------------------------------------------------------- #
# the cached path against the one full forward                          #
# --------------------------------------------------------------------- #
@pytest.mark.parametrize("n_prompt,n_out,bucket", [
    (3, 12, None), (13, 9, None), (21, 6, None), (8, 10, None),
    (11, 5, 32)])
def test_prefill_then_decode_matches_the_full_forward(n_prompt, n_out, bucket):
    """Prompts under a chunk, of whole chunks, and of chunks and a part (the
    chunk is 8), each padded to its bucket; the last to a bucket twice its
    own, whose pad rows must reach neither the state nor the counts."""
    fx = small()
    seq, rows, _ = through_cache(fx, prompt_of(1, n_prompt), n_out,
                                 bucket=bucket)
    assert served_gap(fx, seq, rows, n_prompt) < F32_TOL


def test_engine_serves_what_the_cached_path_computes_and_counts_its_pairs():
    """`DecodeEngine.submit` is those same bodies behind the scheduler; what
    it counts of the routing is what the reference routes: every token of
    every "moe" layer k pairs, those on held experts by expert."""
    fx = small()
    model, cfg = fx["model"], fx["model"].cfg
    sizes = [(3, 12), (13, 9), (21, 6), (8, 10), (16, 7)]
    with serve_transformer(model, fx["params"], 64, decode=True,
                           slots=2) as eng:
        futs = [eng.submit(prompt_of(2, p), o) for p, o in sizes]
        outs = [f.result(timeout=300) for f in futs]
        st = eng.stats()
    by_expert, tokens = np.zeros(HELD[1], np.int64), 0
    for (p, o), out in zip(sizes, outs):
        seq, _rows, held = through_cache(fx, prompt_of(2, p), o)
        np.testing.assert_array_equal(out, seq)
        logits = np.asarray(ref.pattern_logits(fx["hp"], out, cfg))
        assert ref.greedy_gaps(logits, out, p).max() < F32_TOL * logits.std()
        # the last token served is fed to no layer
        chosen = ref.pattern_routing(fx["hp"], out[:-1], cfg)
        want = np.bincount(chosen.reshape(-1), minlength=E)[:HELD[1]]
        np.testing.assert_array_equal(held, want)
        by_expert += want
        tokens += len(out) - 1
    assert st["moe_pairs_total"] == tokens * 5 * K_TOP
    assert st["moe_pairs_by_expert"] == by_expert.tolist()
    assert st["moe_pairs_held"] == by_expert.sum() > 0
    assert st["cache_bytes"].keys() == {"state", "lane"}


def logprobs_of(rows, seq, n_prompt):
    """log softmax(rows)[the token served there], float64."""
    rows = rows.astype(np.float64)
    lse = np.log(np.exp(rows - rows.max(-1, keepdims=True)).sum(-1)) \
        + rows.max(-1)
    return rows[np.arange(len(rows)), seq[n_prompt:]] - lse


@pytest.mark.parametrize("shape", [(1, 1, 1, 1), (2, 1, 1, 1)])
def test_the_engine_hands_back_each_tokens_log_probability(shape):
    """`DecodeConfig(logprobs=True)`: a done future carries one float32 a
    generated token, the log-softmax of the logits that slot's step (the
    first: its prefill) computed, whichever slots were live beside it, on one
    device and on two data-parallel shards; the counts of pairs ride the same
    vector and stay what they were."""
    fx = small()
    model, params = fx["model"], fx["params"]
    if shape != (1, 1, 1, 1):
        grid = ht.MeshGrid(shape, AXES, devices=jax.devices()[:2])
        model = TransformerLM(grid, fx["model"].cfg)
        params = model.shard_params(jax.tree.map(np.asarray, fx["params"]))
    sizes = [(3, 12), (13, 9), (21, 6), (8, 10), (16, 7)]
    with serve_transformer(model, params, 64, decode=True, slots=2,
                           logprobs=True) as eng:
        futs = [eng.submit(prompt_of(2, p), o) for p, o in sizes]
        outs = [f.result(timeout=300) for f in futs]
        st = eng.stats()
    by_expert = np.zeros(HELD[1], np.int64)
    for (p, o), fut, out in zip(sizes, futs, outs):
        seq, rows, held = through_cache(fx, prompt_of(2, p), o)
        np.testing.assert_array_equal(out, seq)
        assert fut.logprobs.dtype == np.float32 and fut.logprobs.shape == (o,)
        np.testing.assert_allclose(fut.logprobs, logprobs_of(rows, seq, p),
                                   atol=2e-5)
        by_expert += held
    assert st["moe_pairs_by_expert"] == by_expert.tolist()


def test_without_the_option_no_future_carries_log_probabilities():
    fx = small()
    with serve_transformer(fx["model"], fx["params"], 64, decode=True,
                           slots=2) as eng:
        fut = eng.submit(prompt_of(2, 5), 4)
        fut.result(timeout=300)
    assert not hasattr(fut, "logprobs")


def test_a_model_without_experts_counts_none():
    grid = ht.MeshGrid((1, 1, 1, 1), AXES, devices=jax.devices()[:1])
    dense = TransformerLM(grid, TransformerLMConfig(
        vocab=128, d_model=32, n_heads=4, n_layers=1))
    with serve_transformer(dense, dense.init(0), 32, decode=True,
                           slots=1) as eng:
        eng.generate(prompt_of(3, 4), 3, timeout=300)
        assert not [k for k in eng.stats() if k.startswith("moe_")]


def test_a_reused_slot_answers_as_a_fresh_engine_does():
    """One slot, three tenants one after another: each prefill writes the
    Mamba-2 states and the tails whole."""
    fx = small()
    sizes = [(13, 9), (5, 12), (21, 6)]
    with serve_transformer(fx["model"], fx["params"], 64, decode=True,
                           slots=1) as eng:
        outs = [eng.generate(prompt_of(4, p), o, timeout=300)
                for p, o in sizes]
        assert eng.stats()["state_resets"] == 3
    for (p, o), out in zip(sizes, outs):
        with serve_transformer(fx["model"], fx["params"], 64, decode=True,
                               slots=1) as fresh:
            np.testing.assert_array_equal(
                out, fresh.generate(prompt_of(4, p), o, timeout=300))


def test_cache_layout_by_kind():
    model = small()["model"]
    (per_layer,), _specs, nbytes = model.cache_layout(3, 64)
    for kind, mine in zip(PATTERN, per_layer):
        if kind == "mamba2":
            assert mine["s"].shape == (3, 4, 32, 16)
            assert mine["s"].dtype == jnp.float32
            assert mine["conv"].shape == (3, 3, 128 + 2 * 16)
        else:
            assert mine["k"].shape == mine["v"].shape == (3, 64, 2 * 8)
    assert nbytes == {"state": 4 * 3 * (4 * 32 * 16 + 3 * 160) * 4,
                      "lane": 2 * 3 * 64 * 16 * 4}


def test_the_pattern_is_two_scanned_runs_and_a_layer_between():
    model = small()["model"]
    assert model.segments == ((0, 1, 2), (2, 1, 1), (3, 1, 2))
    shapes = model.pattern_param_shapes()
    run = shapes["segments"][0][0]
    assert run["we1"].shape == (2, HELD[1], 64, 2 * 32)      # (repeats, held,
    assert run["we2"].shape == (2, HELD[1], 32, 64)          #  ...)
    assert run["router"].shape == (2, 64, E)
    assert run["w_in"].shape == (2, 64, 2 * 128 + 2 * 16 + 4)
    assert "ln1_b" not in run and "final_ln_b" not in shapes
    assert shapes["segments"][1][0]["wqkv"].shape == (1, 64, (8 + 4) * 8)


# --------------------------------------------------------------------- #
# the expert layer                                                      #
# --------------------------------------------------------------------- #
def expert_weights(seed=5, T=24, D=16, F=8):
    rng = np.random.default_rng(seed)
    u = rng.standard_normal((T, D)).astype(np.float32)
    w_r = rng.standard_normal((D, E)).astype(np.float32)
    w1 = (0.3 * rng.standard_normal((E, D, 2 * F))).astype(np.float32)
    w2 = (0.3 * rng.standard_normal((E, F, D))).astype(np.float32)
    return u, w_r, w1, w2


def expert_out(u, w1_e, w2_e):
    gp = u @ w1_e
    F = gp.shape[-1] // 2
    return (gp[..., :F] / (1 + np.exp(-gp[..., :F])) * gp[..., F:]) @ w2_e


def test_the_shares_add_up_to_the_whole_layer():
    """Experts 0..3 on one device, 4..7 on another: the two parts, with the
    mixer and the shared expert (which both compute alike) counted once, are
    the uncut layer as the reference computes it."""
    whole, params = make(experts_held=(0, E), n_layers=1,
                         pattern=("mamba2",), ffn=("moe",))
    cfg = whole.cfg
    p = whole.layer_params(params, 0)
    hp = jax.tree.map(np.asarray, p)
    x = jnp.asarray(np.random.default_rng(6).standard_normal((1, 16, 64)),
                    jnp.float32)
    outs = []
    for first in (0, E // 2):
        share, _ = make(experts_held=(first, E // 2), n_layers=1,
                        pattern=("mamba2",), ffn=("moe",))
        mine = dict(p, we1=p["we1"][first:first + E // 2],
                    we2=p["we2"][first:first + E // 2])
        outs.append(np.asarray(share._prompt_layer(
            "mamba2", "moe", 0, mine, x, jnp.arange(16), jnp.int32(16),
            share._fresh_carry(jnp.ones((1, 16), bool)), None)[0][0]))

    def rms(a, g):
        return a / np.sqrt((a * a).mean(-1, keepdims=True) + cfg.norm_eps) * g

    h1 = np.asarray(x[0]) + cfg.residual_multiplier * np.asarray(
        ref._mamba2(hp, jnp.asarray(rms(np.asarray(x[0]), hp["ln1"])), cfg,
                    False))
    u = rms(h1, hp["ln2"])
    once = h1 + cfg.residual_multiplier * expert_out(u, hp["ws1"], hp["ws2"])
    routed, _chosen = ref._experts(
        dict(hp, ws1=0 * hp["ws1"]), jnp.asarray(u), cfg, False)
    want = once + cfg.residual_multiplier * np.asarray(routed)
    got = outs[0] + outs[1] - once
    assert np.abs(got - want).max() < 1e-4 * np.abs(want).max()
    # and a share alone is NOT the layer
    assert np.abs(outs[0] - want).max() > 1e-2 * np.abs(want).max()


def test_gates_are_a_softmax_over_the_chosen_k_and_nothing_is_dropped():
    u, w_r, w1, w2 = expert_weights()
    got, pairs = parallel.routed_experts(
        jnp.asarray(u), jnp.asarray(w_r), jnp.asarray(w1), jnp.asarray(w2),
        k=K_TOP, held=(0, E))
    logits = u @ w_r
    want = np.zeros_like(u)
    count = np.zeros(E, int)
    for t in range(len(u)):
        top = np.argsort(-logits[t])[:K_TOP]
        g = np.exp(logits[t, top] - logits[t, top].max())
        g /= g.sum()                       # over the k chosen, not over all E
        for e, ge in zip(top, g):
            want[t] += ge * expert_out(u[t], w1[e], w2[e])
            count[e] += 1
    np.testing.assert_allclose(got, want, atol=2e-5)
    np.testing.assert_array_equal(pairs, count)
    assert pairs.sum() == len(u) * K_TOP
    # gates over all E would be another answer
    g_all = np.exp(logits - logits.max(-1, keepdims=True))
    g_all /= g_all.sum(-1, keepdims=True)
    other = sum(g_all[:, e:e + 1] * (np.argsort(-logits)[:, :K_TOP] == e)
                .any(-1, keepdims=True) * expert_out(u, w1[e], w2[e])
                for e in range(E))
    assert np.abs(other - want).max() > 0.01 * np.abs(want).max()


def test_a_skewed_router_sends_every_pair_to_the_same_experts():
    """No capacity: all T tokens choose experts 1, 2 and 5, and all 3 T pairs
    are computed."""
    u, w_r, w1, w2 = expert_weights(seed=8)
    w_r = 0.01 * w_r
    u[:, 0] = 5.0
    w_r[0, [1, 2, 5]] = [3.0, 2.0, 1.0]
    got, pairs = parallel.routed_experts(
        jnp.asarray(u), jnp.asarray(w_r), jnp.asarray(w1[:6]),
        jnp.asarray(w2[:6]), k=K_TOP, held=(0, 6))
    assert pairs.tolist() == [0, len(u), len(u), 0, 0, len(u)]
    logits = (u @ w_r)[:, [1, 2, 5]]
    g = np.exp(logits - logits.max(-1, keepdims=True))
    g /= g.sum(-1, keepdims=True)
    want = sum(g[:, i:i + 1] * expert_out(u, w1[e], w2[e])
               for i, e in enumerate((1, 2, 5)))
    np.testing.assert_allclose(got, want, atol=2e-5)


def test_a_token_with_no_held_expert_gets_the_shared_expert_alone():
    model, params = make(experts_held=(6, 2), n_layers=1, pattern=("gqa",),
                         ffn=("moe",))
    p = dict(model.layer_params(params, 0))
    router = np.zeros((64, E), np.float32)
    router[0, :3] = [3.0, 2.0, 1.0]              # every token: experts 0, 1, 2
    p["router"] = jnp.asarray(router)
    x = np.random.default_rng(9).standard_normal((1, 6, 64)).astype(np.float32)
    x[..., 0] = 4.0
    got, pairs = model._experts_residual(p, jnp.asarray(x),
                                         jnp.ones((1, 6), bool))
    u = x[0] / np.sqrt((x[0] ** 2).mean(-1, keepdims=True) + 1e-5)
    want = x[0] + 0.22 * expert_out(u, np.asarray(p["ws1"]),
                                    np.asarray(p["ws2"]))
    np.testing.assert_allclose(got[0], want, atol=2e-5)
    assert pairs.tolist() == [0, 0]


def test_pad_rows_and_dead_slots_are_not_counted():
    u, w_r, w1, w2 = expert_weights(seed=10)
    valid = np.arange(len(u)) < 7
    _, pairs = parallel.routed_experts(
        jnp.asarray(u), jnp.asarray(w_r), jnp.asarray(w1), jnp.asarray(w2),
        k=K_TOP, held=(0, E), valid=jnp.asarray(valid))
    top = np.argsort(-(u @ w_r), axis=-1)[:7, :K_TOP]
    np.testing.assert_array_equal(pairs, np.bincount(top.reshape(-1),
                                                     minlength=E))


# --------------------------------------------------------------------- #
# the mixers                                                            #
# --------------------------------------------------------------------- #
def mamba2_layer(fx):
    return fx["model"].layer_params(fx["params"], 0)


@pytest.mark.parametrize("S,n_valid", [(5, 5), (8, 8), (16, 11), (19, 19),
                                       (32, 17)])
def test_the_chunked_prompt_form_is_the_recurrence_written_out(S, n_valid):
    """Under a chunk, one chunk, two chunks with pad rows, chunks and a part,
    a bucket half pad: against the reference's scan over positions."""
    fx = small()
    p, cfg = mamba2_layer(fx), fx["model"].cfg
    u = np.random.default_rng([11, S]).standard_normal((1, S, 64)).astype(
        np.float32)
    out, s_end, tail = mixers.mamba2_prompt(
        p, jnp.asarray(u), jnp.int32(n_valid), cfg.d_state, cfg.ssm_chunk,
        cfg.norm_eps)
    hp = jax.tree.map(np.asarray, p)
    want = np.asarray(ref._mamba2(hp, jnp.asarray(u[0, :n_valid]), cfg, False))
    np.testing.assert_allclose(out[0, :n_valid], want, atol=2e-5)
    # the state and the tail are those after the last VALID position
    _, s_cut, tail_cut = mixers.mamba2_prompt(
        p, jnp.asarray(u[:, :n_valid]), jnp.int32(n_valid), cfg.d_state,
        cfg.ssm_chunk, cfg.norm_eps)
    np.testing.assert_allclose(s_end, s_cut, atol=1e-5)
    np.testing.assert_array_equal(tail, tail_cut)


def test_a_mamba2_step_is_the_scans_last_position():
    fx = small()
    p, cfg = mamba2_layer(fx), fx["model"].cfg
    u = jnp.asarray(np.random.default_rng(12).standard_normal((2, 14, 64)),
                    jnp.float32)
    whole, s_whole, tail_whole = mixers.mamba2_prompt(
        p, u, jnp.int32(14), cfg.d_state, cfg.ssm_chunk, cfg.norm_eps)
    _, s, tail = mixers.mamba2_prompt(
        p, u[:, :13], jnp.int32(13), cfg.d_state, cfg.ssm_chunk, cfg.norm_eps)
    out, s, tail = mixers.mamba2_step(p, u[:, 13:], s, tail, cfg.d_state,
                                      cfg.norm_eps)
    np.testing.assert_allclose(out[:, 0], whole[:, 13], atol=2e-5)
    np.testing.assert_allclose(s, s_whole, atol=1e-5)
    np.testing.assert_array_equal(tail, tail_whole)


def test_grouped_query_attention_takes_its_multiplier_and_no_positions():
    fx = small()
    model, cfg = fx["model"], fx["model"].cfg
    p = model.layer_params(fx["params"], 2)
    rng = np.random.default_rng(13)
    u = rng.standard_normal((1, 12, 64)).astype(np.float32)
    want = np.asarray(ref._gqa(jax.tree.map(np.asarray, p), jnp.asarray(u[0]),
                               cfg, False))
    # the step: every row against the lanes the rows before it wrote
    q, k, v = model._gqa_qkv(p, jnp.asarray(u))
    kl, vl = mixers.lanes(k), mixers.lanes(v)
    for t in (0, 5, 11):
        a = mixers.gqa_lanes(q[:, t:t + 1], kl, vl, jnp.asarray([t + 1]),
                             cfg.attention_multiplier)
        got = a.reshape(1, -1) @ p["wo"]
        np.testing.assert_allclose(got[0], want[t], atol=2e-5)
    # 1 / sqrt(d) in the multiplier's place is another layer
    a = mixers.gqa_lanes(q[:, 11:], kl, vl, jnp.asarray([12]), 8 ** -0.5)
    assert np.abs(a.reshape(1, -1) @ p["wo"] - want[11]).max() > 1e-3
    # no positions: the same keys in another order give the same row
    perm = rng.permutation(12)
    a1 = mixers.gqa_lanes(q[:, 11:], kl, vl, jnp.asarray([12]), 0.0625)
    a2 = mixers.gqa_lanes(q[:, 11:], kl[:, perm], vl[:, perm],
                          jnp.asarray([12]), 0.0625)
    np.testing.assert_allclose(a1, a2, atol=1e-6)


@pytest.mark.parametrize("field,other", [
    ("embedding_multiplier", 1.0), ("residual_multiplier", 1.0),
    ("logits_scaling", 1.0), ("attention_multiplier", 8 ** -0.5)])
def test_each_multiplier_changes_the_logits(field, other):
    """The same weights under another multiplier: the program follows the
    reference there too, and neither answers as before."""
    fx = small()
    model, params = make(**{field: other})
    prompt = prompt_of(14, 9)
    kept, logits, _ = jax.jit(model.prefill)(
        params, jnp.asarray(np.pad(prompt, (0, 7)))[None], jnp.int32(9))
    want = np.asarray(ref.pattern_logits(fx["hp"], prompt, model.cfg))[-1]
    assert np.abs(logits[0] - want).max() < F32_TOL * want.std()
    before = np.asarray(ref.pattern_logits(fx["hp"], prompt,
                                           fx["model"].cfg))[-1]
    assert np.abs(before - want).max() > 0.02 * before.std()


# --------------------------------------------------------------------- #
# precision: the configuration's, and the one below it                  #
# --------------------------------------------------------------------- #
def test_bfloat16_compute_stays_inside_its_tolerance_and_float8_does_not():
    """Position by position. The 90th percentile of the bfloat16 program's
    positions, not their maximum: where two experts lie within rounding of
    each other at the k-th place the program and the float32 reference choose
    differently, and that position moves by one expert's gate (0.12 here, one
    position of 48), which is no rounding error. EVERY position of the
    float8 control lies above the tolerance."""
    fx16 = small(jnp.bfloat16)
    # the bfloat16 model's own weights, as float32, are the reference's
    fx = dict(fx16, hp=ref.host_params(fx16["params"]))
    cfg, served, control = fx["model"].cfg, [], []
    for i in range(8):
        n = 5 + 3 * i
        seq, rows, _ = through_cache(fx, prompt_of(15 + i, n), 6)
        want = np.asarray(ref.pattern_logits(fx["hp"], seq, cfg))
        low = np.asarray(ref.pattern_logits(fx["hp"], seq, cfg, fp8=True))
        at = slice(n - 1, len(seq) - 1)
        served += list(np.abs(rows.astype(np.float32) - want[at]).max(-1)
                       / want.std())
        control += list(np.abs(low - want)[at].max(-1) / want.std())
    assert np.percentile(served, 90) < BF16_TOL < min(control), (
        np.sort(served)[-5:], np.sort(control)[:5])


# --------------------------------------------------------------------- #
# what cannot run is refused by name                                    #
# --------------------------------------------------------------------- #
@pytest.mark.parametrize("change,match", [
    (dict(experts_held=(6, 4)), "reaches outside the 8 experts"),
    (dict(experts_held=(0, 0)), "reaches outside the 8 experts"),
    (dict(experts_per_token=9), r"must lie in 1..n_experts \(8\)"),
    (dict(ffn=("moe",) * 4), "ffn names 4 layers"),
    (dict(ffn=("moe", "glu", "mlp", "mlp", "mlp")), "ffn kinds must be of"),
    (dict(norm_kind="batchnorm"), "norm_kind must be"),
    (dict(ssm_heads=0), "ssm_heads >= 1 that divides d_inner"),
    (dict(ssm_heads=3), "ssm_heads >= 1 that divides d_inner"),
    (dict(n_kv_heads=3), "is no multiple of 3"),
    (dict(d_expert=0), "d_expert >= 1"),
    (dict(pattern=None), "needs a pattern"),
])
def test_a_configuration_that_cannot_run_is_refused(change, match):
    with pytest.raises(ValueError, match=match):
        TransformerLMConfig(**dict(BASE, **change))


@pytest.mark.parametrize("shape", [(1, 1, 2, 1), (1, 2, 1, 1)])
def test_experts_under_tp_or_pp_are_refused(shape):
    grid = ht.MeshGrid(shape, AXES, devices=jax.devices()[:2])
    cfg = TransformerLMConfig(**dict(BASE, n_layers=6, pattern=PATTERN +
                                     ("gqa",), ffn=("moe",) * 6))
    with pytest.raises(ValueError, match=r"'moe' layer holds its experts "
                                         r"\(0, 4\) whole .* pp = tp = 1"):
        TransformerLM(grid, cfg)


@pytest.mark.parametrize("entry", ["make_train_step", "loss_and_grad_fn",
                                   "logits_fn", "generate"])
def test_training_and_generate_name_the_pattern_they_do_not_support(entry):
    model, params = small()["model"], small()["params"]
    args = {"make_train_step": (None,), "generate": (
        params, np.zeros((1, 4), np.int32), 2)}.get(entry, ())
    with pytest.raises(NotImplementedError,
                       match=r"mamba2 x2, gqa, mamba2 x2"):
        getattr(model, entry)(*args)


def test_a_mixed_feed_forward_pattern_is_served():
    """"mlp" and "moe" layers in one model: the runs are cut where the
    feed-forward changes, and the cached path follows the reference."""
    model, params = make(ffn=("mlp", "moe", "moe", "mlp", "mlp"), d_ff=96)
    assert model.segments == ((0, 1, 1), (1, 1, 1), (2, 1, 1), (3, 1, 2))
    fx = {"model": model, "params": params, "hp": ref.host_params(params),
          "prefill": jax.jit(model.prefill),
          "step": jax.jit(model.decode_step_logits),
          "store": jax.jit(model.cache_store)}
    seq, rows, held = through_cache(fx, prompt_of(16, 10), 7)
    assert served_gap(fx, seq, rows, 10) < F32_TOL
    chosen = ref.pattern_routing(fx["hp"], seq[:-1], model.cfg)
    assert chosen.shape == (2, 16, K_TOP)
    np.testing.assert_array_equal(
        held, np.bincount(chosen.reshape(-1), minlength=E)[:HELD[1]])


def test_slots_shard_over_dp_and_the_pairs_are_summed():
    """Two data-parallel shards, a slot each, both holding the same experts:
    the answers and the counts are the one-device engine's."""
    fx = small()
    grid = ht.MeshGrid((2, 1, 1, 1), AXES, devices=jax.devices()[:2])
    model = TransformerLM(grid, fx["model"].cfg)
    params = model.shard_params(jax.tree.map(np.asarray, fx["params"]))
    sizes = [(5, 9), (12, 6), (3, 8)]
    with serve_transformer(model, params, 64, decode=True, slots=2) as eng:
        futs = [eng.submit(prompt_of(17, p), o) for p, o in sizes]
        outs = [f.result(timeout=300) for f in futs]
        st = eng.stats()
    by_expert = np.zeros(HELD[1], np.int64)
    for (p, o), out in zip(sizes, outs):
        seq, _rows, held = through_cache(fx, prompt_of(17, p), o, slots=1)
        np.testing.assert_array_equal(out, seq)
        by_expert += held
    assert st["moe_pairs_by_expert"] == by_expert.tolist()
