"""A per-layer PATTERN in ``TransformerLM``, served: Mamba-1, window, full and
cross differential attention and gated memory units behind
``serve_transformer(..., decode=True)``, judged by LOGITS against the plain
float32 forward of ``heat_tpu.nn.reference.pattern_logits`` (one full forward
over prompt + output, no cache, a scan over positions).

Small size, the decoder-hybrid-decoder's own placement rule at 8 layers so that
all five kinds occur: D 64, 8 query / 4 key-value heads of 8, F 128, V 128,
window 8, d_inner 128, N 4, R 4.

Tolerances. float32 compute on the CPU: the cached path and the reference
differ by summation order alone, so 1e-4 of the logits' spread (their standard
deviation) holds with room (read: 1e-5). bfloat16 compute: operands rounded to
8 bits of mantissa through 8 layers read 0.12 to 0.19 of the spread here (12
sequences at initializer range 0.2), so 0.4; the float8 control (the reference
with every product's operands rounded to e4m3) reads 1.8 to 3.0, and has to
fail that.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import heat_tpu as ht
from heat_tpu.nn import mixers
from heat_tpu.nn import reference as ref
from heat_tpu.nn.transformer import (TransformerLM, TransformerLMConfig,
                                     sambay_pattern)
from heat_tpu.serve import serve_transformer

AXES = ("dp", "pp", "tp", "sp")
W = 8
F32_TOL, BF16_TOL = 1e-4, 0.4


def make(dtype=jnp.float32, seed=0):
    grid = ht.MeshGrid((1, 1, 1, 1), AXES, devices=jax.devices()[:1])
    cfg = TransformerLMConfig(
        vocab=128, d_model=64, n_heads=8, n_kv_heads=4, n_layers=8, d_ff=128,
        rope=False, pattern=sambay_pattern(8), window=W, d_inner=128,
        d_state=4, dt_rank=4, init_scale=0.2, compute_dtype=dtype,
        param_dtype=dtype)
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


def through_cache(fx, prompt, n_out, slot=0, slots=2, s_cap=64, cache=None):
    """Prefill ``prompt`` (padded to its bucket) into lane ``slot`` and decode
    greedily through the cache, the bodies the engine compiles. Returns (the
    sequence, the logits of every served position (n_out, V), the cache)."""
    model, params = fx["model"], fx["params"]
    cache = fresh_cache(model, slots, s_cap) if cache is None else cache
    n = len(prompt)
    padded = np.zeros(model.serving_bucket(n), np.int32)
    padded[:n] = prompt
    kept, logits = fx["prefill"](params, jnp.asarray(padded)[None],
                                 jnp.int32(n))
    cache = fx["store"](cache, kept, jnp.int32(slot), jnp.bool_(True))
    rows, seq = [np.asarray(logits[0])], list(prompt)
    toks = np.zeros(slots, np.int32)
    pos = np.zeros(slots, np.int32)
    for i in range(n_out - 1):
        seq.append(int(rows[-1].argmax()))
        toks[slot], pos[slot] = seq[-1], n + i
        logits, cache = fx["step"](params, cache, jnp.asarray(toks),
                                   jnp.asarray(pos))
        rows.append(np.asarray(logits[slot]))
    seq.append(int(rows[-1].argmax()))
    return np.asarray(seq, np.int32), np.stack(rows), cache


def served_gap(fx, seq, rows, n_prompt, fp8=False):
    """Widest |served logit - reference logit| over the served positions, in
    units of the reference logits' spread."""
    want = np.asarray(ref.pattern_logits(
        fx["hp"], seq, fx["model"].cfg, fp8=fp8))[n_prompt - 1:len(seq) - 1]
    return float(np.abs(rows - want).max() / want.std())


# --------------------------------------------------------------------- #
# the cached path against the one full forward                          #
# --------------------------------------------------------------------- #
@pytest.mark.parametrize("n_prompt,n_out", [(3, 30), (13, 20), (21, 12)])
def test_prefill_then_decode_matches_the_full_forward(n_prompt, n_out):
    """Prompts shorter and longer than the window, in three buckets (8, 16,
    32), each with prompt + output > 3 windows so that every ring wraps."""
    fx = small()
    assert n_prompt + n_out > 3 * W
    seq, rows, _ = through_cache(fx, prompt_of(1, n_prompt), n_out)
    assert served_gap(fx, seq, rows, n_prompt) < F32_TOL


def test_engine_serves_what_the_cached_path_computes():
    """`DecodeEngine.submit` is those same bodies behind the scheduler: its
    greedy tokens are the cached path's, request by request, and the
    reference's argmax wherever it is not a near-tie."""
    fx = small()
    with serve_transformer(fx["model"], fx["params"], 64, decode=True,
                           slots=2) as eng:
        sizes = [(3, 30), (13, 20), (21, 12), (8, 25), (16, 17)]
        futs = [eng.submit(prompt_of(2, p), o) for p, o in sizes]
        outs = [f.result(timeout=300) for f in futs]
        st = eng.stats()
    for (p, o), out in zip(sizes, outs):
        seq, _rows, _ = through_cache(fx, prompt_of(2, p), o)
        np.testing.assert_array_equal(out, seq)
        logits = np.asarray(ref.pattern_logits(fx["hp"], out, fx["model"].cfg))
        assert ref.greedy_gaps(logits, out, p).max() < F32_TOL * logits.std()
    assert st["prefills"] == 5 and st["state_resets"] == 5
    assert st["prefill_tokens"] == sum(p for p, _o in sizes)
    assert st["decode_fallbacks"] == 0
    assert set(st["cache_bytes"]) == {"ring", "lane", "state"}


def test_padded_prompt_gives_the_unpadded_logits_and_state():
    """The scan stops at n_valid; pad rows reach neither the logits nor what
    the cache keeps (rings at their ring positions, the lane's valid rows,
    the state and the convolution tail)."""
    fx = small()
    model, params = fx["model"], fx["params"]
    for n in (5, 11):                       # shorter and longer than W
        prompt = prompt_of(3, n)
        exact, l_exact = model.prefill(params, jnp.asarray(prompt)[None],
                                       jnp.int32(n))
        padded = np.full(model.prompt_bucket(n), 77, np.int32)
        padded[:n] = prompt
        kept, l_pad = fx["prefill"](params, jnp.asarray(padded)[None],
                                    jnp.int32(n))
        np.testing.assert_allclose(l_pad, l_exact, atol=2e-5)
        for kind, a, b in zip(model.kinds, kept, exact):
            if kind == "mamba":
                np.testing.assert_allclose(a["s"], b["s"], atol=1e-5)
                np.testing.assert_allclose(a["conv"], b["conv"], atol=1e-5)
            elif kind == "window":
                live = min(n, W)          # ring rows a valid position maps to
                rows = [p % W for p in range(n - live, n)]
                for n_ in ("k", "v"):
                    np.testing.assert_allclose(a[n_][:, rows], b[n_][:, rows],
                                               atol=1e-5)
            elif kind == "full":
                for n_ in ("k", "v"):
                    np.testing.assert_allclose(a[n_][:, :n], b[n_][:, :n],
                                               atol=1e-5)
            else:
                assert a == {} and b == {}


def test_ring_rows_sit_at_position_mod_window():
    x = jnp.arange(20.0).reshape(1, 20, 1)
    kept = np.asarray(mixers.ring_rows(x, 13, W))[0, :, 0]
    for p in range(13 - W, 13):
        assert kept[p % W] == p
    short = np.asarray(mixers.ring_rows(x, 3, W))[0, :, 0]
    assert list(short[:3]) == [0, 1, 2]


# --------------------------------------------------------------------- #
# slots: reset at grant, neighbours undisturbed                         #
# --------------------------------------------------------------------- #
def solo(fx, prompt, n_out):
    with serve_transformer(fx["model"], fx["params"], 64, decode=True,
                           slots=1) as eng:
        return eng.generate(prompt, n_out, timeout=300)


def test_a_reused_slot_answers_as_a_fresh_engine_does():
    """The slot's last tenant leaves a recurrent state, a convolution tail
    and full rings behind; the next request's prefill resets them."""
    fx = small()
    a, b = prompt_of(4, 19), prompt_of(5, 4)
    with serve_transformer(fx["model"], fx["params"], 64, decode=True,
                           slots=1) as eng:
        eng.generate(a, 30, timeout=300)
        again = eng.generate(b, 28, timeout=300)
        assert eng.stats()["state_resets"] == 2
    np.testing.assert_array_equal(again, solo(fx, b, 28))


def test_a_state_left_from_the_last_tenant_would_show():
    """The planted fault: write the prompt's state ON TOP of a used lane's
    (add, not overwrite) and the served logits leave the reference."""
    fx = small()
    _seq, _rows, used = through_cache(fx, prompt_of(4, 19), 30, slots=1)
    prompt = prompt_of(5, 4)
    seq, rows, _ = through_cache(fx, prompt, 28, slots=1, cache=used)
    assert served_gap(fx, seq, rows, len(prompt)) < F32_TOL
    dirty = ([dict(lane, s=lane["s"] + 1.0) if "s" in lane else lane
              for lane in used[0]],)
    real_store = fx["store"]

    def keep_state(cache, kept, slot, ok):
        stored = real_store(cache, kept, slot, ok)
        return ([dict(new, s=new["s"] + old["s"]) if "s" in new else new
                 for new, old in zip(stored[0], cache[0])],)

    fx2 = dict(fx, store=keep_state)
    seq, rows, _ = through_cache(fx2, prompt, 28, slots=1, cache=dirty)
    assert served_gap(fx, seq, rows, len(prompt)) > 100 * F32_TOL


def test_requests_joining_and_leaving_do_not_disturb_their_neighbours():
    fx = small()
    sizes = [(5, 26), (12, 9), (3, 31), (20, 6), (9, 14), (17, 11)]
    with serve_transformer(fx["model"], fx["params"], 64, decode=True,
                           slots=3) as eng:
        futs = [eng.submit(prompt_of(6, p), o) for p, o in sizes]
        outs = [f.result(timeout=300) for f in futs]
    for (p, o), out in zip(sizes, outs):
        seq, _rows, _ = through_cache(fx, prompt_of(6, p), o, slots=1)
        np.testing.assert_array_equal(out, seq)


def test_the_degraded_step_serves_the_same_tokens():
    from heat_tpu.utils import faults

    fx = small()
    prompt = prompt_of(7, 6)
    want = solo(fx, prompt, 12)
    with serve_transformer(fx["model"], fx["params"], 64, decode=True,
                           slots=1) as eng:
        with faults.inject("serve.decode.step=nth:3"):
            got = eng.generate(prompt, 12, timeout=300)
        assert eng.stats()["decode_fallbacks"] == 1
    np.testing.assert_array_equal(got, want)


def test_submit_names_the_cache_kind_that_ran_out():
    fx = small()
    with serve_transformer(fx["model"], fx["params"], 64, decode=True,
                           slots=1) as eng:
        with pytest.raises(ValueError, match="a slot's lane"):
            eng.submit(prompt_of(8, 9), 60)


# --------------------------------------------------------------------- #
# runs of repeating layers: stacked parameters, a scanned prompt        #
# --------------------------------------------------------------------- #
def test_the_published_pattern_is_two_runs_and_two_layers_between():
    from heat_tpu.nn.transformer import _segments

    assert _segments(sambay_pattern(32)) == (
        (0, 2, 8), (16, 1, 1), (17, 1, 1), (18, 2, 7))
    assert small()["model"].segments == (
        (0, 2, 2), (4, 1, 1), (5, 1, 1), (6, 1, 1), (7, 1, 1))
    # the longest repeat from each layer on; no repeat, a run of one
    assert _segments(tuple("abababc")) == ((0, 2, 3), (6, 1, 1))
    assert _segments(tuple("aaab")) == ((0, 1, 3), (3, 1, 1))
    assert _segments(tuple("abcabcab")) == ((0, 3, 2), (6, 1, 1), (7, 1, 1))


def test_stacked_parameters_come_apart_into_the_layers_they_were_made_of():
    fx = small()
    model, params = fx["model"], fx["params"]
    layers = [model.layer_params(params, l) for l in range(8)]
    assert [sorted(p) for p in layers] == [sorted(p) for p in fx["hp"]["layers"]]
    again = model.stack_layers(lambda l: layers[l])
    for a, b in zip(jax.tree.leaves(again),
                    jax.tree.leaves(params["segments"])):
        np.testing.assert_array_equal(a, b)
    # layer 2 is the second repeat of the first run's first place
    np.testing.assert_array_equal(layers[2]["w_in"],
                                  params["segments"][0][0]["w_in"][1])
    with pytest.raises(IndexError):
        model.layer_params(params, 8)


@pytest.mark.parametrize("n_prompt", [5, 16])
def test_a_scanned_run_is_its_layers_one_after_another(n_prompt):
    """The prompt's forward scans a run's period over its stacked parameters;
    with every layer a run of its own (nothing scanned) the same weights give
    the same logits and the same rows for the cache."""
    fx = small()
    model, params = fx["model"], fx["params"]
    flat = TransformerLM(model.grid, model.cfg)
    flat.segments = tuple((l, 1, 1) for l in range(8))
    flat_params = dict(params, segments=flat.stack_layers(
        lambda l: model.layer_params(params, l)))
    toks = np.zeros(16, np.int32)
    toks[:n_prompt] = prompt_of(23, n_prompt)
    kept, logits = fx["prefill"](params, jnp.asarray(toks)[None],
                                 jnp.int32(n_prompt))
    lowered = jax.jit(model.prefill).lower(params, jnp.asarray(toks)[None],
                                           jnp.int32(n_prompt)).as_text()
    assert "stablehlo.while" in lowered
    kept1, logits1 = jax.jit(flat.prefill)(
        flat_params, jnp.asarray(toks)[None], jnp.int32(n_prompt))
    assert jax.tree.structure(kept) == jax.tree.structure(kept1)
    spread = float(np.asarray(logits1).std())
    assert np.abs(np.asarray(logits) - np.asarray(logits1)).max() \
        < F32_TOL * spread
    for a, b in zip(jax.tree.leaves(kept), jax.tree.leaves(kept1)):
        np.testing.assert_allclose(a, b, atol=1e-5)


# --------------------------------------------------------------------- #
# each mixer against its equations                                      #
# --------------------------------------------------------------------- #
def layer_of(fx, kind):
    l = fx["model"].kinds.index(kind)
    return l, fx["model"].layer_params(fx["params"], l)


def test_a_mamba_step_is_the_scans_last_position():
    fx = small()
    _l, p = layer_of(fx, "mamba")
    u = jnp.asarray(np.random.default_rng(9).standard_normal((2, 11, 64)),
                    jnp.float32)
    out, mem, s_end, tail = mixers.mamba_prompt(p, u, jnp.int32(11), 4)
    _o, _m, s10, tail10 = mixers.mamba_prompt(p, u[:, :10], jnp.int32(10), 4)
    o1, m1, s11, tail11 = mixers.mamba_step(p, u[:, 10:], s10, tail10, 4)
    np.testing.assert_allclose(o1[:, 0], out[:, 10], atol=1e-5)
    np.testing.assert_allclose(m1[:, 0], mem[:, 10], atol=1e-5)
    np.testing.assert_allclose(s11, s_end, atol=1e-6)
    np.testing.assert_allclose(tail11, tail, atol=1e-6)


def test_a_mamba_scan_is_the_recurrence_written_out():
    fx = small()
    _l, p = layer_of(fx, "mamba")
    hp = jax.tree.map(lambda a: np.asarray(a, np.float64), p)
    u = np.random.default_rng(10).standard_normal((7, 64))
    out, mem, _s, _t = mixers.mamba_prompt(
        p, jnp.asarray(u, jnp.float32)[None], jnp.int32(7), 4)
    xz = u @ hp["w_in"]
    x, z = xz[:, :128], xz[:, 128:]
    xp = np.concatenate([np.zeros((3, 128)), x])
    x = sum(xp[j:j + 7] * hp["conv_w"][j] for j in range(4)) + hp["conv_b"]
    x = x / (1 + np.exp(-x))
    dbc = x @ hp["w_x"]
    delta = np.log1p(np.exp(dbc[:, :4] @ hp["w_dt"] + hp["b_dt"]))
    A = -np.exp(hp["A_log"])                                  # (N, d_inner)
    s, ys = np.zeros((4, 128)), []
    for t in range(7):
        s = np.exp(delta[t] * A) * s + (delta[t] * x[t]) * dbc[t, 4:8, None]
        ys.append(dbc[t, 8:12] @ s + hp["D_skip"] * x[t])
    y = np.stack(ys)
    np.testing.assert_allclose(mem[0], y, atol=1e-5)
    np.testing.assert_allclose(
        out[0], (y * z / (1 + np.exp(-z))) @ hp["w_out"], atol=1e-5)


def test_differential_maps_are_grouped_query_attention_on_each_head():
    """Without the subtraction (lambda 0) and the pair norm (gamma 1) a pair's
    output is its EVEN head's plain attention over the group's joined values;
    the odd head's map is what lambda weighs."""
    rng = np.random.default_rng(11)
    q = jnp.asarray(rng.standard_normal((1, 6, 8, 8)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((1, 6, 4, 8)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((1, 6, 4, 8)), jnp.float32)
    mask = mixers.window_mask(6, 6, 6)
    a = np.asarray(mixers.diff_attention(q, k, v, mask))
    for h in range(8):
        g, c = h // 4, h % 2
        s = np.asarray(q[0, :, h] @ k[0, :, 2 * g + c].T) / np.sqrt(8)
        s = np.where(np.asarray(mask), s, -np.inf)
        w = np.exp(s - s.max(-1, keepdims=True))
        w /= w.sum(-1, keepdims=True)
        vbar = np.concatenate([v[0, :, 2 * g], v[0, :, 2 * g + 1]], -1)
        np.testing.assert_allclose(a[0, :, h], w @ vbar, atol=1e-5)
    # lambda = exp(0) - exp(0) + lambda_init: zero vectors leave lambda_init
    lam, init = mixers.diff_lambda(jnp.zeros((4, 8)), 5)
    init = float(init)      # an array: a scanned segment's index is traced
    assert float(lam) == pytest.approx(init) == pytest.approx(
        0.8 - 0.6 * np.exp(-1.5))
    o = np.asarray(mixers.diff_finish(jnp.asarray(a), jnp.zeros((4, 8)),
                                      jnp.ones(16), 5, 1e-5, jnp.float32))
    d = a[0, :, 0] - init * a[0, :, 1]
    d = d / np.sqrt((d * d).mean(-1, keepdims=True) + 1e-5) * (1 - init)
    np.testing.assert_allclose(o[0, :, :16], d, atol=1e-5)


def test_the_steps_plain_matrix_form_is_the_grouped_form():
    """`diff_attention_lanes` reads the lanes as they lie (zeros outside a
    head's own key rows, every head times every pair's values, the own pair
    kept): the same maps as the grouped einsum."""
    rng = np.random.default_rng(18)
    q = jnp.asarray(rng.standard_normal((3, 1, 8, 8)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((3, 12, 4, 8)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((3, 12, 4, 8)), jnp.float32)
    seen = jnp.asarray([12, 5, 1])
    got = mixers.diff_attention_lanes(q, mixers.lanes(k), mixers.lanes(v),
                                      seen)
    mask = (jnp.arange(12)[None, :] < seen[:, None])[:, None, None, None,
                                                      None, :]
    np.testing.assert_allclose(got, mixers.diff_attention(q, k, v, mask),
                               atol=1e-5)


def test_flash_layout_of_the_pairs_is_the_grouped_form():
    """One plain head a (query head, value half), as the prompt's kernel
    takes it, computes what the grouped einsum does."""
    from heat_tpu.nn.attention import local_attention

    rng = np.random.default_rng(12)
    q = jnp.asarray(rng.standard_normal((1, 9, 8, 8)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((1, 9, 4, 8)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((1, 9, 4, 8)), jnp.float32)
    kk, vv = mixers.diff_heads(k, v, 8)
    flat = jnp.moveaxis(local_attention(
        jnp.moveaxis(jnp.repeat(q, 2, axis=2), 2, 1), jnp.moveaxis(kk, 2, 1),
        jnp.moveaxis(vv, 2, 1), causal=True), 1, 2).reshape(1, 9, 8, 16)
    want = mixers.diff_attention(q, k, v, mixers.window_mask(9, 9, 9))
    np.testing.assert_allclose(flat, want, atol=1e-5)


@pytest.mark.parametrize("S", [5, 8, 16, 32, 20])
def test_window_attention_by_blocks_is_the_masked_whole(S):
    rng = np.random.default_rng(13)
    q = jnp.asarray(rng.standard_normal((2, S, 8, 8)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((2, S, 4, 8)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((2, S, 4, 8)), jnp.float32)
    got = mixers.window_attention_prompt(q, k, v, W)
    want = mixers.diff_attention(q, k, v, mixers.window_mask(S, S, W))
    np.testing.assert_allclose(got, want, atol=1e-5)


def test_a_cross_layer_reads_the_full_layers_lane_and_keeps_nothing():
    fx = small()
    model, params = fx["model"], fx["params"]
    shapes, _specs, nbytes = model.cache_layout(2, 64)
    (per_layer,) = shapes
    lanes = [l for l, lane in enumerate(per_layer)
             if "k" in lane and lane["k"].shape[1] == 64]
    assert lanes == [model.kinds.index("full")]
    for kind, lane in zip(model.kinds, per_layer):
        assert (lane == {}) == (kind in ("cross", "gmu"))
    assert nbytes["lane"] == 2 * 2 * 64 * 4 * 8 * 4
    # the step: perturb every ring, state and tail AFTER the full layer has
    # run, and a cross layer's output moves only with the lane
    l, p = layer_of(fx, "cross")
    rng = np.random.default_rng(14)
    x = jnp.asarray(rng.standard_normal((2, 1, 64)), jnp.float32)
    lane_k = jnp.asarray(rng.standard_normal((2, 64, 32)), jnp.float32)
    lane_v = jnp.asarray(rng.standard_normal((2, 64, 32)), jnp.float32)
    cache = fresh_cache(model, 2, 64)
    pos = jnp.asarray([9, 30], jnp.int32)
    carry = {"k": lane_k, "v": lane_v, "m": None}
    out, cache2, _ = model._step_layer(l, p, x, cache, pos, carry, None)
    noisy = jax.tree.map(lambda a: a + 1.0, cache)
    out2, _c, _ = model._step_layer(l, p, x, noisy, pos, carry, None)
    np.testing.assert_array_equal(out, out2)
    assert jax.tree.all(jax.tree.map(lambda a, b: bool((a == b).all()),
                                     cache, cache2))
    beyond = dict(carry, k=lane_k.at[0, 10:].add(1.0).at[1, 31:].add(1.0))
    out3, _c, _ = model._step_layer(l, p, x, cache, pos, beyond, None)
    np.testing.assert_array_equal(out, out3)          # rows > pos are masked
    seen = dict(carry, k=lane_k.at[0, 9].add(1.0))
    out4, _c, _ = model._step_layer(l, p, x, cache, pos, seen, None)
    assert float(jnp.abs(out4 - out)[0].max()) > 1e-3
    np.testing.assert_array_equal(out4[1], out[1])


def test_a_gated_memory_unit_gates_the_last_state_space_output():
    fx = small()
    _l, p = layer_of(fx, "gmu")
    rng = np.random.default_rng(15)
    u = rng.standard_normal((3, 64)).astype(np.float32)
    m = rng.standard_normal((3, 128)).astype(np.float32)
    got = mixers.gmu(p, jnp.asarray(u)[None], jnp.asarray(m)[None])[0]
    gate = u @ np.asarray(p["w1"])
    want = (m * gate / (1 + np.exp(-gate))) @ np.asarray(p["w2"])
    np.testing.assert_allclose(got, want, atol=1e-5)


# --------------------------------------------------------------------- #
# precision: the configuration's, and the one below it                  #
# --------------------------------------------------------------------- #
def test_bfloat16_compute_stays_inside_its_tolerance_and_float8_does_not():
    fx = small(jnp.bfloat16)
    assert all(a.dtype == jnp.bfloat16 for a in (
        fx["params"]["embed"], fx["params"]["segments"][0][0]["w_in"]))
    for n_prompt, n_out in [(3, 30), (13, 20)]:
        seq, rows, _ = through_cache(fx, prompt_of(16, n_prompt), n_out)
        gap = served_gap(fx, seq, rows, n_prompt)
        assert F32_TOL < gap < BF16_TOL
        # the control: the reference itself, computed in float8, against the
        # float32 reference, at the same positions
        control = np.asarray(ref.pattern_logits(
            fx["hp"], seq, fx["model"].cfg, fp8=True))[n_prompt - 1:-1]
        assert served_gap(fx, seq, control, n_prompt) > BF16_TOL


def test_serving_params_are_held_in_the_configurations_dtype():
    model, params = make(jnp.float32)
    assert model.serving_params(params) is params
    cfg = TransformerLMConfig(**dict(model.cfg.__dict__,
                                     param_dtype=jnp.bfloat16,
                                     compute_dtype=jnp.bfloat16))
    held = TransformerLM(model.grid, cfg).serving_params(params)
    layer = held["segments"][0][0]
    assert layer["w_in"].dtype == jnp.bfloat16 == held["embed"].dtype
    assert layer["A_log"].dtype == jnp.float32 == layer["ln1"].dtype


# --------------------------------------------------------------------- #
# the configuration                                                     #
# --------------------------------------------------------------------- #
def test_the_placement_rule_at_32_layers_is_the_published_one():
    kinds = sambay_pattern(32)
    assert [l for l, k in enumerate(kinds) if k == "mamba"] == list(
        range(0, 17, 2))
    assert [l for l, k in enumerate(kinds) if k == "window"] == list(
        range(1, 16, 2))
    assert kinds[17] == "full"
    assert [l for l, k in enumerate(kinds) if k == "gmu"] == list(
        range(18, 31, 2))
    assert [l for l, k in enumerate(kinds) if k == "cross"] == list(
        range(19, 32, 2))
    with pytest.raises(ValueError, match="multiple of four"):
        sambay_pattern(6)


@pytest.mark.parametrize("change,match", [
    (dict(pattern=("mamba",) * 7), "names 7 layers"),
    (dict(pattern=("mamba", "conv") * 4), "kinds must be of"),
    (dict(rope=True), "no positional"),
    (dict(n_kv_heads=8), "twice n_kv_heads"),
    (dict(window=0), "window >= 1"),
    (dict(pattern=("cross",) + ("mamba",) * 7), "no 'full' layer before"),
    (dict(pattern=("gmu",) + ("mamba",) * 7), "no 'mamba' layer before"),
])
def test_a_pattern_that_cannot_be_run_is_refused(change, match):
    base = dict(vocab=128, d_model=64, n_heads=8, n_kv_heads=4, n_layers=8,
                d_ff=128, rope=False, pattern=sambay_pattern(8), window=W)
    with pytest.raises(ValueError, match=match):
        TransformerLMConfig(**dict(base, **change))


@pytest.mark.parametrize("entry", ["make_train_step", "loss_and_grad_fn",
                                   "logits_fn", "generate"])
def test_training_and_generate_name_the_pattern_they_do_not_support(entry):
    model, params = small()["model"], small()["params"]
    args = {"make_train_step": (None,), "generate": (
        params, np.zeros((1, 4), np.int32), 2)}.get(entry, ())
    with pytest.raises(NotImplementedError,
                       match=r"mamba, window, mamba, window, mamba, full"):
        getattr(model, entry)(*args)


def test_the_prompt_ladder_of_a_pattern_starts_at_its_window():
    model = small()["model"]                                  # window 8
    assert [model.serving_bucket(n) for n in (1, 8, 9, 33)] == [8, 8, 16, 64]
    wide = TransformerLM(model.grid, TransformerLMConfig(
        **dict(model.cfg.__dict__, window=32)))
    assert [wide.serving_bucket(n) for n in (1, 20, 33)] == [32, 32, 64]
    with serve_transformer(wide, wide.init(0), 128, decode=True,
                           slots=1) as eng:
        eng.warmup(prompt_lens=[3, 9, 17, 40])
        assert eng.stats()["program_cache"]["compiles"] == 3   # 32, 64, step
        with pytest.raises(ValueError, match="prompt bucket 32"):
            eng.submit(prompt_of(19, 5), 100)
    dense = TransformerLM(model.grid, TransformerLMConfig(
        vocab=64, d_model=32, n_heads=4, n_layers=1))
    assert dense.serving_bucket(3) == dense.prompt_bucket(3) == 8


def test_a_pattern_wants_a_dp_only_grid():
    grid = ht.MeshGrid((1, 1, 2, 1), AXES, devices=jax.devices()[:2])
    with pytest.raises(ValueError, match="dp-only grids"):
        TransformerLM(grid, small()["model"].cfg)


def test_slots_shard_over_dp():
    """Two data-parallel shards, a slot each: the prefill lands in the owning
    shard's lanes only, and the answers are the one-device engine's."""
    fx = small()
    grid = ht.MeshGrid((2, 1, 1, 1), AXES, devices=jax.devices()[:2])
    model = TransformerLM(grid, fx["model"].cfg)
    params = model.shard_params(jax.tree.map(np.asarray, fx["params"]))
    sizes = [(5, 20), (12, 9), (3, 14)]
    with serve_transformer(model, params, 64, decode=True, slots=2) as eng:
        futs = [eng.submit(prompt_of(17, p), o) for p, o in sizes]
        outs = [f.result(timeout=300) for f in futs]
    for (p, o), out in zip(sizes, outs):
        seq, _rows, _ = through_cache(fx, prompt_of(17, p), o, slots=1)
        np.testing.assert_array_equal(out, seq)
