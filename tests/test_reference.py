"""The plain float32 reference forward (``heat_tpu.nn.reference``) against
``TransformerLM`` at a small size — the oracle ``chip_smoke.py`` judges the
train loss and the decode engine by on the chip.

On XLA:CPU the model computes in float32, so agreement here is to float32
reassociation; the same comparisons on the TPU (bf16 compute, default matmul
precision) hold to bf16 rounding only, which is why the chip check is
logit-level and never token-level.

One model per grid for the whole module; compiled state dropped at the end
(the suite's executable-budget discipline).
"""

import gc

import numpy as np
import pytest

import jax.numpy as jnp

import heat_tpu as ht
from heat_tpu.core import fusion
from heat_tpu.nn import reference
from heat_tpu.nn.transformer import TransformerLM, TransformerLMConfig
from heat_tpu.serve import DecodeConfig, DecodeEngine

_MEMO: dict = {}
# tp=1 (heads whole on every device) and tp=2 (heads split, psum in the block)
GRIDS = ("dp", "dp_tp")


def _fx(kind):
    if kind not in _MEMO:
        n = ht.get_comm().size
        tp = 2 if (kind == "dp_tp" and n % 2 == 0) else 1
        grid = ht.MeshGrid((n // tp, 1, tp, 1), ("dp", "pp", "tp", "sp"))
        cfg = TransformerLMConfig(vocab=67, d_model=32, n_heads=4,
                                  n_layers=2, d_ff=64)
        model = TransformerLM(grid, cfg)
        params = model.init(5)
        _MEMO[kind] = dict(model=model, params=params, cfg=cfg,
                           hp=reference.host_params(params))
    return _MEMO[kind]


@pytest.fixture(scope="module", autouse=True)
def _drop_compiled_state():
    yield
    _MEMO.clear()
    fusion.reset()
    gc.collect()


def _toks(fx, B, S, seed=0):
    return np.random.default_rng(seed).integers(
        0, fx["cfg"].vocab, (B, S)).astype(np.int32)


@pytest.mark.parametrize("kind", GRIDS)
def test_logits_match_model_forward(kind):
    fx = _fx(kind)
    m = fx["model"]
    toks = _toks(fx, 2 * m.dp_world, 16)
    got = np.asarray(m.logits_fn()(fx["params"], m.shard_batch(toks)))
    want = np.asarray(reference.reference_logits(fx["hp"], toks, fx["cfg"]))
    assert got.shape == want.shape == toks.shape + (fx["cfg"].vocab,)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=1e-4)


@pytest.mark.parametrize("kind", GRIDS)
def test_loss_matches_train_step_loss(kind):
    fx = _fx(kind)
    m = fx["model"]
    toks = _toks(fx, 2 * m.dp_world, 16, seed=1)
    loss, _ = m.loss_and_grad_fn()(fx["params"], m.shard_batch(toks))
    want = reference.reference_loss(fx["hp"], toks, fx["cfg"])
    np.testing.assert_allclose(float(loss), want, rtol=1e-5)


def test_reference_is_causal_and_position_aware():
    """The oracle's own sanity: a later token never changes an earlier
    logit, and the rotary positions enter the result."""
    fx = _fx("dp")
    a = _toks(fx, 1, 12, seed=2)
    b = a.copy()
    b[0, -1] = (b[0, -1] + 1) % fx["cfg"].vocab
    la = np.asarray(reference.reference_logits(fx["hp"], a, fx["cfg"]))
    lb = np.asarray(reference.reference_logits(fx["hp"], b, fx["cfg"]))
    np.testing.assert_array_equal(la[:, :-1], lb[:, :-1])
    assert np.abs(la[:, -1] - lb[:, -1]).max() > 1e-4
    import dataclasses

    norope = dataclasses.replace(fx["cfg"], rope=False)
    ln = np.asarray(reference.reference_logits(fx["hp"], a, norope))
    assert np.abs(la - ln).max() > 1e-4


@pytest.mark.parametrize("kind", GRIDS)
@pytest.mark.parametrize("s0", [3, 8, 13])
def test_prefill_logits_match_reference(kind, s0):
    """The engine's padded-bucket prefill (pad rows, traced length) gives
    the unpadded reference's last-position logits — below, at and above a
    bucket edge."""
    fx = _fx(kind)
    prompt = _toks(fx, 1, s0, seed=10 + s0)[0]
    got = reference.prefill_logits(fx["model"], fx["params"], prompt)
    want = np.asarray(reference.reference_logits(
        fx["hp"], prompt[None], fx["cfg"]))[0, -1]
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=1e-4)


@pytest.mark.parametrize("kind", GRIDS)
def test_engine_tokens_are_reference_argmax(kind):
    """The chip check at CPU size: teacher-forced on the engine's own
    output, the token chosen at every generated position carries the
    reference's maximum logit (gap 0 up to float32 reassociation) —
    slot cache scatter, per-slot positions and masking included."""
    fx = _fx(kind)
    m = fx["model"]
    mix = ((3, 6), (9, 4), (12, 5), (5, 7))
    prompts = [_toks(fx, 1, s0, seed=20 + i)[0]
               for i, (s0, _mn) in enumerate(mix)]
    with DecodeEngine(m, fx["params"],
                      DecodeConfig(slots=2 * m.dp_world,
                                   max_seq_len=32)) as eng:
        eng.warmup()
        outs = [f.result(120) for f in
                [eng.submit(p, mn) for p, (_s, mn) in zip(prompts, mix)]]
        assert eng.stats()["decode_fallbacks"] == 0
    # ONE reference call: the rows right-padded to a common length (the
    # reference is causal, so padding cannot reach an earlier position)
    batch = np.zeros((len(outs), 32), np.int32)
    for i, out in enumerate(outs):
        batch[i, :len(out)] = out
    ref = np.asarray(reference.reference_logits(fx["hp"], batch, fx["cfg"]))
    for i, (out, (s0, mn)) in enumerate(zip(outs, mix)):
        assert out.shape == (s0 + mn,)
        gaps = reference.greedy_gaps(ref[i], out, s0)
        assert gaps.shape == (mn,)
        assert gaps.max() <= 2e-5, gaps


def test_greedy_gaps_flags_a_wrong_token():
    fx = _fx("dp")
    prompt = _toks(fx, 1, 6, seed=30)[0]
    logits = np.asarray(reference.reference_logits(
        fx["hp"], prompt[None], fx["cfg"]))[0, -1]
    best, worst = int(np.argmax(logits)), int(np.argmin(logits))
    full = np.asarray(reference.reference_logits(
        fx["hp"], prompt[None], fx["cfg"]))[0]
    g_ok = reference.greedy_gaps(full, np.append(prompt, best), 6)
    g_bad = reference.greedy_gaps(full, np.append(prompt, worst), 6)
    assert g_ok[0] == 0.0
    np.testing.assert_allclose(g_bad[0], logits.max() - logits.min(),
                               rtol=1e-6)


def test_host_params_refuses_moe():
    n = ht.get_comm().size
    grid = ht.MeshGrid((n, 1, 1, 1), ("dp", "pp", "tp", "sp"))
    cfg = TransformerLMConfig(vocab=17, d_model=16, n_heads=2, n_layers=1,
                              d_ff=32, moe_experts=n)
    params = TransformerLM(grid, cfg).init(0)
    with pytest.raises(NotImplementedError):
        reference.host_params(params)
    assert jnp.asarray(params["embed"]).shape == (17, 16)
