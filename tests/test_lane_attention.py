"""``lane_attention``: the decode step's attention over cache lanes, one
Mosaic kernel for the dense layer, differential attention (a full lane, a
cross layer's borrowed lane, a window layer's ring) and grouped queries.

Run here in the Pallas interpreter at small sizes, against the jnp forms it
replaces (``TransformerLM._attn_from_cache``'s float32 products,
``mixers.diff_attention_lanes``, ``mixers.gqa_lanes`` with the kernels
off): what a slot reads stops at its own ``seen``, whatever the rows after
it hold, and the dense value product keeps 16 bits of the probabilities'
mantissa. ``tests/test_chip_compile.py`` compiles it for the chip.
"""

import gc
import math
import types

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import heat_tpu as ht
from heat_tpu.core import fusion
from heat_tpu.core import lane_kernels as lk
from heat_tpu.core import pallas_kernels as pk
from heat_tpu.nn import mixers
from heat_tpu.nn.transformer import TransformerLM, TransformerLMConfig
from heat_tpu.serve import DecodeConfig, DecodeEngine
from heat_tpu.serve.program_cache import ProgramCache

BK, S = 8, 32                       # a lane of four blocks


@pytest.fixture(autouse=True)
def _kernels_as_the_default():
    pk.set_pallas(None)
    yield
    pk.set_pallas(None)


@pytest.fixture(scope="module", autouse=True)
def _drop_compiled_state():
    yield
    fusion.reset()
    gc.collect()


# the three forms: (query heads, key/value heads, head width, value width)
FORMS = {"dense": (4, 4, 8, 8), "diff": (8, 4, 8, 16), "gqa": (8, 2, 8, 8)}


def _maps(form):
    H, Hkv, d, _ = FORMS[form]
    h = np.arange(H)
    if form == "dense":
        return tuple(h.tolist()), tuple(h.tolist())
    if form == "gqa":
        return (tuple((h // (H // Hkv)).tolist()),) * 2
    return tuple((2 * (h // 4) + h % 2).tolist()), tuple((h // 4).tolist())


def _operands(form, seed, B=3, rows=S, dtype=jnp.float32):
    H, Hkv, d, _ = FORMS[form]
    rng = np.random.default_rng(seed)
    q = rng.standard_normal((B, H, d))
    kl, vl = rng.standard_normal((2, B, rows, Hkv * d))
    return (jnp.asarray(q, dtype), jnp.asarray(kl, dtype),
            jnp.asarray(vl, dtype))


def _scale(form):
    return 1.0 / math.sqrt(FORMS[form][2])


def _kernel(form, q, kl, vl, seen, block_k=BK, **kw):
    k_of, v_of = _maps(form)
    kw.setdefault("precision", jax.lax.Precision.HIGH if form == "dense"
                  else jax.lax.Precision.DEFAULT)
    return lk.lane_attention(q, kl, vl, jnp.asarray(seen, jnp.int32),
                             k_of=k_of, v_of=v_of, scale=_scale(form),
                             block_k=block_k, **kw)


def _reference(form, q, kl, vl, seen):
    """The jnp form the kernel replaces, the kernels off: (B, H, width)."""
    pk.set_pallas(False)
    try:
        seen = jnp.asarray(seen, jnp.int32)
        if form == "dense":
            stub = types.SimpleNamespace(
                cfg=types.SimpleNamespace(head_dim=q.shape[-1]))
            return TransformerLM._attn_from_cache(stub, q[:, None], kl, vl,
                                                  seen)[:, 0]
        if form == "diff":
            return mixers.diff_attention_lanes(q[:, None], kl, vl, seen)[:, 0]
        return mixers.gqa_lanes(q[:, None], kl, vl, seen, _scale(form))[:, 0]
    finally:
        pk.set_pallas(None)


def _close(got, want):
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("n", [1, BK - 1, BK, BK + 1, S])
@pytest.mark.parametrize("form", sorted(FORMS))
def test_kernel_matches_the_form_it_replaces(form, n):
    """Every slot at ``n`` rows: one row, a block but one, a block, a block
    and one, the whole lane."""
    q, kl, vl = _operands(form, seed=n)
    seen = [n] * 3
    got = _kernel(form, q, kl, vl, seen)
    assert got.shape == (3, FORMS[form][0], FORMS[form][3])
    assert got.dtype == jnp.float32
    _close(got, _reference(form, q, kl, vl, seen))


@pytest.mark.parametrize("form", sorted(FORMS))
def test_short_and_full_slots_in_one_batch(form):
    q, kl, vl = _operands(form, seed=5, B=5)
    seen = [1, S, 3, BK + 1, S - 1]
    _close(_kernel(form, q, kl, vl, seen),
           _reference(form, q, kl, vl, seen))


def test_a_ring_read_at_its_window():
    """A window layer's ring of W rows: before it wraps the rows < pos + 1
    count, after it every row (``min(pos + 1, window)``)."""
    window = 2 * BK
    q, kl, vl = _operands("diff", seed=9, B=4, rows=window)
    pos = np.array([0, window - 2, window - 1, 3 * window + 5])
    seen = np.minimum(pos + 1, window)
    assert seen.tolist() == [1, window - 1, window, window]
    _close(_kernel("diff", q, kl, vl, seen),
           _reference("diff", q, kl, vl, seen))


@pytest.mark.parametrize("form", sorted(FORMS))
def test_rows_past_seen_are_neither_read_nor_mixed_in(form):
    """Poison: every row at or past a slot's ``seen`` holds NaN, in K and V.
    A block past the last live one is never fetched or computed, and the
    last one masks its scores AND its values (0 x NaN is NaN in a product),
    so the output is finite and what the clean lanes give."""
    q, kl, vl = _operands(form, seed=13, B=4)
    seen = np.array([1, BK + 3, 2 * BK, S - 1])
    dead = np.arange(S)[None, :, None] >= seen[:, None, None]
    kp, vp = (jnp.where(dead, jnp.nan, x) for x in (kl, vl))
    got = _kernel(form, q, kp, vp, seen)
    assert np.isfinite(np.asarray(got)).all()
    np.testing.assert_array_equal(np.asarray(got),
                                  np.asarray(_kernel(form, q, kl, vl, seen)))


def test_the_dense_value_product_keeps_sixteen_bits():
    """Against bfloat16 values the dense layer's float32 probabilities go
    as two bfloat16 terms (what its float32 product at ``high`` did): that
    lands within 1e-4 of the exact product, where rounding them to bfloat16
    in one pass (what the pattern's layers do, ``DEFAULT``) is off by
    several times that."""
    q, kl, vl = _operands("dense", seed=21, B=4, dtype=jnp.bfloat16)
    seen = [S, S - 5, 2 * BK, BK + 1]
    exact = _reference("dense", q.astype(jnp.float32),
                       kl.astype(jnp.float32), vl.astype(jnp.float32), seen)
    two = _kernel("dense", q, kl, vl, seen)
    one = _kernel("dense", q, kl, vl, seen,
                  precision=jax.lax.Precision.DEFAULT)
    err_two = float(jnp.max(jnp.abs(two - exact)))
    err_one = float(jnp.max(jnp.abs(one - exact)))
    assert err_two < 1e-4, err_two
    assert err_one > 8 * err_two, (err_one, err_two)


@pytest.mark.parametrize("S_cap,width,itemsize,rows", [
    (2048, 2048, 2, 256),           # the dense cell's lane: 1 MiB a block
    (4096, 1280, 2, 256),           # phi's full lane (384 rows do not divide)
    (512, 1280, 2, 256),            # phi's ring
    (4096, 1024, 2, 512),           # granite's lane
    (64, 32, 4, 64),                # under a block: whole
    (384, 32, 4, 384),              # a few narrow rows: one block
    (2024, 2048, 2, 256),           # generate(): 1,024 + 1,000 rows, 8 blocks
    (1152, 2048, 2, 128),           # 9 x 128: the largest that divides
])
def test_block_rows_by_width(S_cap, width, itemsize, rows):
    assert lk._lane_rows(S_cap, width, itemsize, None) == rows


@pytest.mark.parametrize("block_k", [12, 20, 40])
@pytest.mark.parametrize("form", sorted(FORMS))
def test_a_last_block_past_the_lane_end(form, block_k):
    """Blocks that do not divide the lane: the last runs past its end, and
    what that part holds is masked like any row past ``seen``; a block
    longer than the lane is the lane."""
    q, kl, vl = _operands(form, seed=block_k, B=4)
    seen = [S, S - 1, block_k + 1, 1]
    _close(_kernel(form, q, kl, vl, seen, block_k=block_k),
           _reference(form, q, kl, vl, seen))


@pytest.mark.parametrize("k_of,v_of", [
    ((0, 1, 2, 3), (0, 1, 2)),      # a head without a value block
    ((0, 1, 2, 3), (1, 0, 3, 2)),   # value blocks out of the heads' order
    ((1, 0, 2, 3), (0, 0, 1, 1)),   # fine: key heads inside their block
    ((2, 1, 2, 3), (0, 0, 1, 1)),   # a key head outside its value block
])
def test_head_maps_that_do_not_fit_are_refused(k_of, v_of):
    q, kl, vl = _operands("dense", seed=2)
    call = lambda: lk.lane_attention(  # noqa: E731
        q, kl, vl, jnp.full((3,), S, jnp.int32), k_of=k_of, v_of=v_of,
        scale=0.5)
    if k_of == (1, 0, 2, 3):
        assert call().shape == (3, 4, 16)
    else:
        with pytest.raises(ValueError, match="head maps"):
            call()


@pytest.mark.parametrize("form", sorted(FORMS))
def test_the_callers_take_the_kernel_where_kernels_run(form, monkeypatch):
    """With the kernels on, the three call sites hand their lanes to
    ``lane_attention`` (counted), and compute what their jnp forms do."""
    calls = []
    real = lk.lane_attention

    def counted(*a, **kw):
        calls.append(kw["k_of"])
        return real(*a, **kw)

    monkeypatch.setattr(mixers, "lane_attention", counted)
    q, kl, vl = _operands(form, seed=31, B=2, rows=256)
    seen = jnp.asarray([200, 37], jnp.int32)
    want = _reference(form, q, kl, vl, seen)
    assert not calls
    pk.set_pallas(True)
    if form == "dense":
        stub = types.SimpleNamespace(
            cfg=types.SimpleNamespace(head_dim=q.shape[-1]))
        got = TransformerLM._attn_from_cache(stub, q[:, None], kl, vl,
                                             seen)[:, 0]
    elif form == "diff":
        got = mixers.diff_attention_lanes(q[:, None], kl, vl, seen)[:, 0]
    else:
        got = mixers.gqa_lanes(q[:, None], kl, vl, seen, _scale(form))[:, 0]
    assert calls == [_maps(form)[0]]
    _close(got, want)


@pytest.mark.parametrize("mesh", [(2, 1), (2, 2)], ids=["dp2", "dp2tp2"])
def test_the_engine_serves_through_the_kernel(mesh, monkeypatch):
    """The dense engine on a CPU mesh of dp 2 (and dp 2 x tp 2: a slot's
    lane split by heads), its step and prefill programs compiled with the
    kernels on: every layer of the step calls ``lane_attention`` inside the
    engine's ``shard_map``, and the served tokens and their
    log-probabilities are ``generate()``'s with the kernels off."""
    dp, tp = mesh
    if ht.get_comm().size < dp * tp:
        pytest.skip(f"needs {dp * tp} devices")
    grid = ht.MeshGrid((dp, 1, tp, 1), ("dp", "pp", "tp", "sp"),
                       devices=jax.devices()[:dp * tp])
    model = TransformerLM(grid, TransformerLMConfig(
        vocab=29, d_model=32, n_heads=4, n_layers=2, d_ff=64))
    params = model.init(3)
    prompts = [np.random.default_rng(s).integers(0, 29, (n,)).astype(
        np.int32) for s, n in ((1, 5), (2, 9), (3, 3))]
    new = 7
    want = []
    pk.set_pallas(False)
    for p in prompts:
        want.append(np.asarray(model.generate(
            params, np.tile(p, (dp, 1)), new))[0, len(p):])
    calls = []
    real = lk.lane_attention

    def counted(*a, **kw):
        calls.append(kw["k_of"])
        return real(*a, **kw)

    monkeypatch.setattr(mixers, "lane_attention", counted)
    pk.set_pallas(True)
    with DecodeEngine(model, params, DecodeConfig(
            slots=2 * dp, max_seq_len=32, logprobs=True),
            program_cache=ProgramCache(name="lane-kernel")) as eng:
        futs = [eng.submit(p, new) for p in prompts]
        got = [f.result(300) for f in futs]
    assert len(calls) >= model.cfg.n_layers
    assert set(calls) == {tuple(range(4 // tp))}
    for p, out, w, f in zip(prompts, got, want, futs):
        np.testing.assert_array_equal(np.asarray(out)[len(p):], w)
        assert np.isfinite(np.asarray(f.logprobs)).all()
