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


def test_attention_flops_are_the_forward_count_less_the_matrices():
    n = work.lm_matmul_params(CFG)
    assert work.lm_attention_flops_per_token(CFG, 5.0) == pytest.approx(
        work.lm_forward_flops_per_token(CFG, 5.0) - 2 * n) \
        == pytest.approx(2 * 4 * 8 * 5.0)


def test_cdist_bytes_are_one_write_of_the_result():
    assert work.cdist_bytes(40_000) == 6_400_000_000
    assert work.cdist_bytes(10_000, 40_000) == 1_600_000_000
    assert work.cdist_bytes(8, 4, itemsize=2) == 64


def test_tokens_delivered_spreads_a_request_over_its_life():
    reqs = [(0.0, 10.0, 100),      # whole life inside
            (-5.0, 5.0, 100),      # half of it before the window opened
            (35.0, 45.0, 200),     # half of it after the close
            (-20.0, 60.0, 800),    # spans the whole window: 40 of 80 s
            (41.0, 50.0, 99), (-9.0, -1.0, 99),   # outside
            (3.0, 3.0, 7)]         # no life at all: nothing to spread
    assert work.tokens_delivered(reqs, 0.0, 40.0) == pytest.approx(
        100 + 50 + 100 + 400)
    # a close that falls just before or just after an answer moves it by the
    # answer's rate times the shift, not by the whole answer
    near = [(30.0, 39.93, 360)]
    a = work.tokens_delivered(near, 0.0, 39.9)
    b = work.tokens_delivered(near, 0.0, 40.0)
    assert a == pytest.approx(360 * 9.9 / 9.93) and b == pytest.approx(360.0)
    # windows that tile the time add up to every token
    all_ = [(0.0, 7.0, 70), (2.0, 31.0, 500), (6.5, 12.0, 16)]
    assert sum(work.tokens_delivered(all_, t, t + 8.0)
               for t in (0.0, 8.0, 16.0, 24.0)) == pytest.approx(586.0)


def test_lloyd_bytes_are_one_read_of_x():
    assert work.lloyd_bytes_per_iteration(25_000_000, 64, 4) == 6_400_000_000


def test_pythia_width_flops_per_token():
    cfg = {"hidden_size": 2048, "intermediate_size": 8192,
           "num_hidden_layers": 8, "vocab_size": 50304}
    assert work.lm_matmul_params(cfg) == 8 * 12 * 2048 ** 2 + 2048 * 50304
    # about 3.3 GFLOP a trained token at S=2048
    assert work.lm_train_flops_per_token(cfg, 2048) == pytest.approx(
        3 * (2 * 505_675_776 + 8 * 4 * 2048 * 1024.5))
    # 67.1 M of 1,078.5 M forward FLOPs a token are the attention core's
    assert work.lm_attention_flops_per_token(cfg, 1024.5) == pytest.approx(
        67.1e6, rel=1e-3)


def test_mfu_percent():
    assert work.mfu_percent(197e12, 2.0, 1, 197e12) == pytest.approx(50.0)
