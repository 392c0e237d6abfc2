"""`work.py` against hand counts."""

import pytest

from perfbench import work

CFG = {"hidden_size": 8, "intermediate_size": 32, "num_hidden_layers": 2,
       "vocab_size": 100, "num_attention_heads": 2}


def test_matmul_params_leave_out_the_embedding_gather():
    per_layer = 8 * 24 + 8 * 8 + 2 * 8 * 32          # qkv, proj, up + down
    assert work.lm_matmul_params(CFG) == 2 * per_layer + 8 * 100


def test_forward_and_train_flops_by_hand():
    n = work.lm_matmul_params(CFG)
    ctx = 5.0
    fwd = 2 * n + 2 * (2 * 8 * ctx + 2 * 8 * ctx)     # QK^T and PV per layer
    assert work.lm_forward_flops_per_token(CFG, ctx) == pytest.approx(fwd)
    S = 9                                             # mean context (S+1)/2 = 5
    assert work.lm_train_flops_per_token(CFG, S) == pytest.approx(3 * fwd)


def test_decode_flops_prefill_plus_steps():
    p, n_out = 4, 3
    want = (4 * work.lm_forward_flops_per_token(CFG, 2.5)
            + work.lm_forward_flops_per_token(CFG, 5)
            + work.lm_forward_flops_per_token(CFG, 6))
    assert work.lm_decode_flops(CFG, p, n_out) == pytest.approx(want)


def test_lloyd_bytes_are_one_read_of_x():
    assert work.lloyd_bytes_per_iteration(25_000_000, 64, 4) == 6_400_000_000


def test_pythia_width_flops_per_token():
    cfg = {"hidden_size": 2048, "intermediate_size": 8192,
           "num_hidden_layers": 8, "vocab_size": 50304}
    assert work.lm_matmul_params(cfg) == 8 * 12 * 2048 ** 2 + 2048 * 50304
    # about 3.3 GFLOP a trained token at S=2048
    assert work.lm_train_flops_per_token(cfg, 2048) == pytest.approx(
        3 * (2 * 505_675_776 + 8 * 4 * 2048 * 1024.5))


def test_mfu_percent():
    assert work.mfu_percent(197e12, 2.0, 1, 197e12) == pytest.approx(50.0)
