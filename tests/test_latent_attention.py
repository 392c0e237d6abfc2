"""``latent_attention``: the absorbed latent core of a latent-attention decode
step (``mixers.mla_step``) as one Mosaic kernel, each slot's latent lane
fetched only up to its position and each block read once for the scores and
the values.

Run here in the Pallas interpreter at small sizes, against the XLA form it
replaces: ``mla_step`` with the kernels off and W_UK, W_UV the identity, so
that its output IS the core's. What a slot reads stops at its own ``seen``,
whatever the rows after it hold. ``tests/test_chip_compile.py`` compiles the
kernel for the chip inside the pangu cell's step.
"""

import gc

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import heat_tpu as ht
from heat_tpu.core import fusion
from heat_tpu.core import latent_kernels as lt
from heat_tpu.core import pallas_kernels as pk
from heat_tpu.nn import mixers
from heat_tpu.nn.transformer import TransformerLM, TransformerLMConfig
from heat_tpu.serve import DecodeConfig, DecodeEngine
from heat_tpu.serve.program_cache import ProgramCache

BK, S = 8, 32                       # a lane of four blocks
H, C, DR, W = 4, 16, 4, 128         # heads, latent, rope key, row width
SCALE = 0.3


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


def _operands(seed, B=3, rows=S, dtype=jnp.float32):
    """(q (B, H, W) as ``mla_step`` builds it, the lane (B, rows, W))."""
    rng = np.random.default_rng(seed)
    q = np.zeros((B, H, W))
    q[..., :C + DR] = rng.standard_normal((B, H, C + DR))
    lane = np.zeros((B, rows, W))
    lane[..., :C + DR] = rng.standard_normal((B, rows, C + DR))
    lane[..., C + DR] = 1.0
    return jnp.asarray(q, dtype), jnp.asarray(lane, dtype)


def _step(q, lane, seen):
    """``mla_step`` with W_UK and W_UV the identity: the core's (B, H, C)."""
    eye = jnp.broadcast_to(jnp.eye(C, dtype=q.dtype), (H, C, C))
    return mixers.mla_step({"w_uk": eye, "w_uv": eye}, q[:, None, :, :C],
                           q[:, None, :, C:C + DR], lane,
                           jnp.asarray(seen, jnp.int32), SCALE)[:, 0]


def _kernel(q, lane, seen, block_k=BK):
    return lt.latent_attention(q, lane, jnp.asarray(seen, jnp.int32), C=C,
                               scale=SCALE, block_k=block_k)


def _reference(q, lane, seen):
    """The XLA form the kernel replaces, the kernels off."""
    pk.set_pallas(False)
    try:
        return _step(q, lane, seen)
    finally:
        pk.set_pallas(None)


def _close(got, want):
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("n", [1, BK - 1, BK, BK + 1, S])
def test_kernel_matches_the_form_it_replaces(n):
    """Every slot at ``n`` rows: one row, a block but one, a block, a block
    and one, the whole lane."""
    q, lane = _operands(seed=n)
    seen = [n] * 3
    got = _kernel(q, lane, seen)
    assert got.shape == (3, H, C) and got.dtype == jnp.float32
    _close(got, _reference(q, lane, seen))


def test_short_and_full_slots_in_one_batch():
    q, lane = _operands(seed=5, B=5)
    seen = [1, S, 3, BK + 1, S - 1]
    _close(_kernel(q, lane, seen), _reference(q, lane, seen))


def test_rows_past_seen_are_neither_read_nor_mixed_in():
    """Poison: every column of every row at or past a slot's ``seen`` holds
    NaN. A block past the last live one is never fetched or computed, and
    the last one zeroes those rows before both products (0 x NaN is NaN),
    so the output is finite and what the clean lane gives."""
    q, lane = _operands(seed=13, B=4)
    seen = np.array([1, BK + 3, 2 * BK, S - 1])
    dead = np.arange(S)[None, :, None] >= seen[:, None, None]
    got = _kernel(q, jnp.where(dead, jnp.nan, lane), seen)
    assert np.isfinite(np.asarray(got)).all()
    np.testing.assert_array_equal(np.asarray(got),
                                  np.asarray(_kernel(q, lane, seen)))


@pytest.mark.parametrize("block_k", [12, 20, 40])
def test_a_last_block_past_the_lane_end(block_k):
    """Blocks that do not divide the lane: the last runs past its end, and
    what that part holds is masked like any row past ``seen``; a block
    longer than the lane is the lane."""
    q, lane = _operands(seed=block_k, B=4)
    seen = [S, S - 1, block_k + 1, 1]
    _close(_kernel(q, lane, seen, block_k=block_k),
           _reference(q, lane, seen))


def test_bfloat16_operands_stay_as_close_as_the_xla_form():
    """Against bfloat16 operands both forms round the probabilities once to
    bfloat16 for the value product; the kernel's online softmax lands as
    close to the float32 core as the XLA form does."""
    q, lane = _operands(seed=21, B=4, dtype=jnp.bfloat16)
    seen = [S, S - 5, 2 * BK, BK + 1]
    exact = _reference(q.astype(jnp.float32), lane.astype(jnp.float32), seen)
    err_kernel = float(jnp.max(jnp.abs(_kernel(q, lane, seen) - exact)))
    err_xla = float(jnp.max(jnp.abs(_reference(q, lane, seen) - exact)))
    assert err_kernel < 1e-2 and err_xla < 1e-2, (err_kernel, err_xla)
    assert err_kernel < 2 * err_xla + 1e-3, (err_kernel, err_xla)


@pytest.mark.parametrize("S_cap,rows", [
    (4096, 1024),                   # the pangu cell's lane: four blocks
    (64, 64),                       # under a block: whole
    (1500, 1024),                   # a partial last block
])
def test_block_rows(S_cap, rows):
    assert lt.latent_rows(S_cap) == rows
    assert lt.latent_rows(S_cap, 256) == min(256, S_cap)


def test_operands_that_do_not_fit_are_refused():
    q, lane = _operands(seed=2)
    with pytest.raises(ValueError, match="latent"):
        lt.latent_attention(q, lane[:2], jnp.full((3,), S, jnp.int32), C=C,
                            scale=SCALE)
    with pytest.raises(ValueError, match="latent"):
        lt.latent_attention(q, lane, jnp.full((3,), S, jnp.int32),
                            C=W + 1, scale=SCALE)


def test_mla_step_takes_the_kernel_where_kernels_run(monkeypatch):
    """With the kernels on, ``mla_step`` hands its queries and lane to
    ``latent_attention`` (counted) and computes what its XLA form does."""
    calls = []
    real = lt.latent_attention

    def counted(*a, **kw):
        calls.append(kw["C"])
        return real(*a, **kw)

    monkeypatch.setattr(mixers, "latent_attention", counted)
    q, lane = _operands(seed=31, B=2, rows=256)
    seen = [200, 37]
    want = _reference(q, lane, seen)
    assert not calls
    pk.set_pallas(True)
    got = _step(q, lane, seen)
    assert calls == [C]
    _close(got, want)


def _mla_model(grid):
    return TransformerLM(grid, TransformerLMConfig(
        vocab=48, d_model=32, n_heads=4, n_layers=3, rope=True,
        rope_theta=10000.0, pattern=("mla",) * 3, d_ff=48,
        norm_kind="rmsnorm", q_lora_rank=16, kv_lora_rank=16,
        qk_nope_head_dim=8, qk_rope_head_dim=4, v_head_dim=8,
        tie_head=False, init_scale=0.2))


def _serve(model, params, slots, prompts, new, kernels):
    pk.set_pallas(kernels)
    with DecodeEngine(model, params, DecodeConfig(
            slots=slots, max_seq_len=32, logprobs=True),
            program_cache=ProgramCache(name=f"latent-{kernels}")) as eng:
        futs = [eng.submit(p, new) for p in prompts]
        got = [np.asarray(f.result(300)) for f in futs]
        return got, [np.asarray(f.logprobs) for f in futs], eng.stats()


@pytest.mark.parametrize("mesh", [1, 2], ids=["one", "dp2"])
def test_the_engine_serves_through_the_kernel(mesh, monkeypatch):
    """An engine of three "mla" layers on one device and on dp 2 (a pattern
    runs on dp-only grids), its step compiled with the kernels on: every
    layer of the step calls ``latent_attention``, the served tokens are
    those of the same engine with the kernels off and their
    log-probabilities within float32 rounding of them, and the engine counts
    the rows the kernel fetched (0 with the kernels off)."""
    if ht.get_comm().size < mesh:
        pytest.skip(f"needs {mesh} devices")
    grid = ht.MeshGrid((mesh, 1, 1, 1), ("dp", "pp", "tp", "sp"),
                       devices=jax.devices()[:mesh])
    model = _mla_model(grid)
    params = model.init(3)
    prompts = [np.random.default_rng(s).integers(0, 48, (n,)).astype(
        np.int32) for s, n in ((1, 5), (2, 9), (3, 3))]
    new = 7
    want, want_lp, st_off = _serve(model, params, 2 * mesh, prompts, new,
                                   False)
    calls = []
    real = lt.latent_attention

    def counted(*a, **kw):
        calls.append(kw["C"])
        return real(*a, **kw)

    monkeypatch.setattr(mixers, "latent_attention", counted)
    got, got_lp, st = _serve(model, params, 2 * mesh, prompts, new, True)
    assert len(calls) >= model.cfg.n_layers and set(calls) == {16}
    for out, w, lp, wlp in zip(got, want, got_lp, want_lp):
        np.testing.assert_array_equal(out, w)
        np.testing.assert_allclose(lp, wlp, atol=2e-5)
    assert st_off["latent_rows_fetched"] == 0
    # the lane of 32 rows is one block: every live slot fetches it whole in
    # each of the three layers, every step (the prefill's token is no step's)
    assert st["attended_positions"] == st_off["attended_positions"] > 0
    assert st["latent_rows_fetched"] == len(prompts) * (new - 1) * 32 * 3
