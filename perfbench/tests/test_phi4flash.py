"""The `phi-4-mini-flash` configuration and its cell
(`phi-4-mini-flash.decode-reason-closed96`), on the CPU at the rehearsal's
size: the benchmark's own reference agrees with the program's test oracle
(`heat_tpu/nn/reference.py`, written apart from it); the sound program is
`correct` and each planted fault (a recurrent state not reset, a ring row off
by one, lambda dropped) is not; the float8 control fails the cell's limit; the
configuration's file carries the catalog's config unchanged and the parameter
table it states is what the shapes add up to; the work counts and the three
readers read what they say, and nothing where the program lacks the counter.
"""

import json
import math
import os
import types

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from perfbench import run, work_phi4flash as work
from perfbench.references import phi4flash as ref

CELL = "phi-4-mini-flash.decode-reason-closed96"
CONFIG = os.path.join(run.HERE, "configs", "phi-4-mini-flash.json")

# the catalog row's `config` (model-configs guide, architectures.jsonl)
CATALOG = {
    "embd_pdrop": 0, "hidden_act": "silu", "hidden_size": 2560,
    "intermediate_size": 10240, "layer_norm_eps": 1e-05,
    "max_position_embeddings": 262144, "mb_per_layer": 2,
    "model_type": "phi4flash", "num_attention_heads": 40,
    "num_hidden_layers": 32, "num_key_value_heads": 20, "resid_pdrop": 0,
    "sliding_window": 512, "tie_word_embeddings": True, "mlp_bias": False,
    "lm_head_bias": False, "vocab_size": 200064}


def last_line(capsys, seed=2 ** 31 + 11, seconds="1.0", trace=0):
    capsys.readouterr()
    assert run.main(["--workload", CELL, "--rehearse", "--seed", str(seed),
                     "--seconds", seconds, "--trace", str(trace)]) == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def failed(line):
    return sorted(n for n, c in line["checks"].items()
                  if not c["value"] <= c["limit"])


def small_config():
    return run.overlay(run.load_json(CONFIG), True)


# -- the configuration's file -------------------------------------------------
def test_the_file_carries_the_catalogs_config_and_reduces_nothing():
    cfg = run.load_json(CONFIG)
    for key, value in CATALOG.items():
        assert cfg[key] == value and type(cfg[key]) is type(value), key
    assert cfg["reduced"] == [] and cfg["param_dtype"] == "bfloat16"
    assert cfg["compute_dtype"] == "bfloat16"
    assert cfg["assumed_sizes"] == {"d_inner": 5120, "d_state": 16,
                                    "d_conv": 4, "dt_rank": 160}
    assert len(cfg["source"]) <= 200 and "config.json" in cfg["source"]


def test_the_parameter_table_is_what_the_shapes_add_up_to():
    cfg = run.load_json(CONFIG)
    z = ref.sizes(cfg)
    mlp_norms = ("w_gate_up", "w_down", "ln1", "ln1_b", "ln2", "ln2_b")
    own = {kind: sum(math.prod(s) for n, (s, _h) in
                     ref.layer_shapes(kind, z).items() if n not in mlp_norms)
           for kind in ("mamba", "window", "cross", "gmu")}
    table = list(cfg["parameters"].values())
    assert table[:7] == [z["V"] * z["D"], 3 * z["D"] * z["F"], own["mamba"],
                         own["window"], own["cross"], own["gmu"],
                         32 * 4 * z["D"] + 2 * z["D"]]
    total = table[0] + 32 * table[1] + 9 * table[2] + 9 * table[3] \
        + 7 * table[4] + 7 * table[5] + table[6]
    assert total == table[7] == 3852562944                       # 3.85 B
    assert work.param_bytes(cfg) == table[8] == 7707462656       # 7.71 GB
    kinds = ref.kinds(32)
    assert [kinds.count(k) for k in ("mamba", "window", "full", "cross",
                                     "gmu")] == [9, 8, 1, 7, 7]
    assert kinds[16] == "mamba" and kinds[17] == "full" and kinds[18] == "gmu"


# -- the reference ------------------------------------------------------------
def test_the_benchmarks_reference_agrees_with_the_programs_oracle():
    """Two plain forwards written apart (this one by layer over a batch, the
    pairs under `lax.map`; the oracle a sequence at a time) on the weights
    the benchmark makes."""
    from heat_tpu.nn import reference as oracle
    from perfbench.drivers import lm_decode_pattern as driver

    cfg = small_config()
    key = jax.random.key(7)
    model = driver.build_model(cfg, jax.devices(), {})
    hp = oracle.host_params(ref.params_tree(key, cfg))
    toks = np.random.default_rng(1).integers(0, 128, 37).astype(np.int32)
    mine = np.asarray(ref.row_logits(key, cfg, toks))
    want = np.asarray(oracle.pattern_logits(hp, toks, model.cfg))
    assert np.abs(mine - want).max() < 1e-4 * want.std()
    # the control is another answer, by far more than the program's rounding
    low = np.asarray(ref.row_logits(key, cfg, toks, fp8=True))
    assert np.abs(low - want).max() > 0.3 * want.std()


def test_six_bfloat16_passes_are_a_float32_product_at_highest():
    """`ref._mul` writes out what `precision=highest` is on the chip; on the
    CPU, where `highest` is a float32 product, both are float32's rounding
    from the float64 product (and one bfloat16 pass is a thousand times
    that)."""
    rng = np.random.default_rng(5)
    a = rng.standard_normal((3, 48, 512)).astype(np.float32)
    b = (0.02 * rng.standard_normal((512, 96))).astype(np.float32)
    exact = np.einsum("bsd,de->bse", a.astype(np.float64),
                      b.astype(np.float64))
    six = np.asarray(ref._mul("bsd,de->bse", jnp.asarray(a), jnp.asarray(b),
                              False))
    high = np.asarray(jnp.einsum("bsd,de->bse", a, b,
                                 precision=jax.lax.Precision.HIGHEST))
    one = np.asarray(jnp.einsum(
        "bsd,de->bse", jnp.asarray(a, jnp.bfloat16),
        jnp.asarray(b, jnp.bfloat16), preferred_element_type=jnp.float32))
    scale = np.abs(exact).max()
    assert np.abs(six - exact).max() < 2e-6 * scale
    assert np.abs(high - exact).max() < 2e-6 * scale
    assert np.abs(one - exact).max() > 1e-3 * scale


def test_weights_come_a_layer_at_a_time_and_are_rounded_once():
    cfg = dict(small_config(), param_dtype="bfloat16")
    key = jax.random.key(3)
    tree = ref.params_tree(key, cfg)
    held = ref.layer_weights(key, 2, cfg)
    as_f32 = ref.layer_weights(key, 2, cfg, jnp.float32)
    assert held["w_in"].dtype == jnp.bfloat16
    assert held["A_log"].dtype == jnp.float32 == held["ln1"].dtype
    np.testing.assert_array_equal(
        np.asarray(held["w_in"].astype(jnp.float32)), as_f32["w_in"])
    np.testing.assert_array_equal(np.asarray(tree["layers"][2]["w_in"]
                                             .astype(jnp.float32)),
                                  as_f32["w_in"])
    a = np.asarray(held["A_log"])
    np.testing.assert_allclose(a[:, 0], np.log(np.arange(1, 5)), rtol=1e-6)
    step = np.log1p(np.exp(np.asarray(held["b_dt"])))
    assert 1e-3 * 0.99 <= step.min() and step.max() <= 1e-1 * 1.01
    assert np.abs(np.asarray(as_f32["conv_w"])).max() <= 0.5


# -- correct ------------------------------------------------------------------
def test_sound_program_is_correct(capsys):
    line = last_line(capsys)
    assert line["correct"] is True and failed(line) == []
    assert line["attempted"] > 0 and line["failed"] == 0
    assert set(line["metrics"]) == {"decode_tokens_per_s",
                                    "req_ms_per_token_p90", "setup_s"}


def test_traced_rehearsal_reports_what_a_cpu_trace_can_feed(capsys):
    line = last_line(capsys, seconds="1.5", trace=1)
    bench = run.load_json(os.path.join(run.ROOT, "BENCHMARK.json"))
    listed = {m["name"] for m in run.metrics_of(bench, "per_layer", CELL)}
    # no device plane in a rehearsal: nothing to read by scope or program
    silent = {"cache_move_share.decode", "step_hbm_roofline.phi4flash",
              "prefill_share.phi4flash"}
    assert set(line["metrics"]) == listed - silent
    assert line["correct"] is True


def test_a_state_not_reset_is_not_correct(capsys, monkeypatch):
    """The next tenant's prefill ADDS its state to what the lane held."""
    from heat_tpu.nn.transformer import TransformerLM

    store = TransformerLM.cache_store

    def kept_on(self, cache, kept, slot, ok):
        new = store(self, cache, kept, slot, ok)
        return ([dict(n, s=n["s"] + o["s"]) if "s" in n else n
                 for n, o in zip(new[0], cache[0])],)

    monkeypatch.setattr(TransformerLM, "cache_store", kept_on)
    line = last_line(capsys)
    assert line["correct"] is False and failed(line) == ["token_gap"]


def test_a_ring_row_off_by_one_is_not_correct(capsys, monkeypatch):
    from heat_tpu.nn import mixers

    rows = mixers.ring_rows
    monkeypatch.setattr(
        mixers, "ring_rows",
        lambda x, n_valid, window: jnp.roll(rows(x, n_valid - 1, window), 1,
                                            axis=1))
    line = last_line(capsys)
    assert line["correct"] is False and failed(line) == ["token_gap"]


def test_lambda_dropped_is_not_correct(capsys, monkeypatch):
    from heat_tpu.nn import mixers

    lam = mixers.diff_lambda
    monkeypatch.setattr(mixers, "diff_lambda",
                        lambda v, layer: (0.0 * lam(v, layer)[0],
                                          lam(v, layer)[1]))
    line = last_line(capsys)
    assert line["correct"] is False and failed(line) == ["token_gap"]


def test_the_float8_control_fails_the_cells_limit(capsys):
    from perfbench.tools import readings

    capsys.readouterr()
    readings.main(["--workload", CELL, "--seeds", str(2 ** 31 + 21),
                   "--control", "1", "--rehearse"])
    by_kind = {ln["kind"]: ln for ln in map(
        json.loads, (ln for ln in capsys.readouterr().out.splitlines()
                     if ln.startswith("{")))}
    assert by_kind["program"]["correct"] is True
    assert by_kind["control"]["correct"] is False
    assert by_kind["control"]["checks"]["token_gap"]["value"] > 3 * max(
        by_kind["program"]["checks"]["token_gap"]["value"], 0.01)


# -- the work counts ----------------------------------------------------------
def test_flops_and_bytes_by_kind():
    cfg = run.load_json(CONFIG)
    z = ref.sizes(cfg)
    mm = work.matmul_params_by_kind(cfg)
    assert mm["gmu"] - mm["cross"] == 2 * z["D"] * (z["di"] - z["H"] * z["d"])
    f0, f1 = work.flops_per_token(cfg, 100), work.flops_per_token(cfg, 1100)
    # 1,000 more attended positions: the full layer and the 7 cross layers at
    # 6 H d each, the 8 window layers only up to the window
    assert f1 - f0 == pytest.approx(
        6 * 2560 * (8 * 1000 + 8 * (512 - 100)))
    assert work.flops_per_token(cfg, 100) - work.flops_per_token(
        cfg, 100, head=False) == 2 * z["D"] * z["V"]
    assert 7.0e9 < f1 < 8.5e9                  # 2 x 3.85 B and a little
    slot = work.slot_bytes_per_step(cfg, 4096)
    lane = 8 * 4096 * 5120
    ring = 8 * 512 * 5120
    state = 9 * 2 * (16 * 5120 * 4 + 3 * 5120 * 2)
    assert slot == lane + ring + 9 * 5120 + state
    assert work.step_bytes(cfg, 64, 4096) == work.param_bytes(cfg) + 64 * slot
    out_ctx, prompt_ctx = work.mean_contexts([(100, 10), (50, 30)])
    assert out_ctx == pytest.approx((10 * 105.5 + 30 * 65.5) / 40)
    assert prompt_ctx == pytest.approx((100 * 50.5 + 50 * 25.5) / 150)


# -- the readers --------------------------------------------------------------
def fake_run(counters0, counters1, programs, busy=4.0, window=5.0):
    bench = run.load_json(os.path.join(run.ROOT, "BENCHMARK.json"))
    cell, _c = run.find_cell(bench, CELL)
    probe = types.SimpleNamespace(traced={
        "counters0": counters0, "counters1": counters1, "units": 3})
    return run.Run(
        probe=probe, cell=cell, chips=1, config=run.load_json(CONFIG),
        traffic=run.load_json(os.path.join(
            run.HERE, "traffic", cell["traffic"] + ".json")),
        peaks={"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
        work=__import__("perfbench.work", fromlist=["work"]),
        trace={"window_s": window, "device0_busy_s": busy,
               "programs": programs})


def reader(name):
    return run.load_by_name("layer_metrics", name).read


def test_readers_read_what_they_say():
    from perfbench import traffic

    c0 = {"tokens_out": 0, "prefills": 0, "decode_steps": 0,
          "prefill_tokens": 0}
    c1 = {"tokens_out": 6400 + 10, "prefills": 10, "decode_steps": 100,
          "prefill_tokens": 6000}
    r = fake_run(c0, c1, {"jit_decode_step": {"runs": 100, "device_s": 3.0},
                          "jit_decode_prefill": {"runs": 10, "device_s": 1.0}})
    out_ctx, prompt_ctx = work.mean_contexts(traffic.request_sizes(r.traffic))
    flops = (6410 * work.flops_per_token(r.config, out_ctx)
             + 6000 * work.flops_per_token(r.config, prompt_ctx, head=False))
    assert reader("decode_mfu.phi4flash")(r) == pytest.approx(
        100 * flops / (5.0 * 197e12))
    least = 100 * work.step_bytes(r.config, 64.0, out_ctx) / 819e9
    assert reader("step_hbm_roofline.phi4flash")(r) == pytest.approx(
        100 * least / 3.0)
    assert reader("prefill_share.phi4flash")(r) == pytest.approx(25.0)
    assert all(0 < reader(n)(r) < 100 for n in (
        "decode_mfu.phi4flash", "step_hbm_roofline.phi4flash"))


def test_readers_find_nothing_in_a_program_without_the_counter():
    """The parent's engine counts no `prefill_tokens`: the three new metrics
    are left out of its line, they do not raise."""
    c0 = {"tokens_out": 0, "prefills": 0, "decode_steps": 0}
    c1 = {"tokens_out": 700, "prefills": 10, "decode_steps": 100}
    r = fake_run(c0, c1, {"jit_decode_step": {"runs": 100, "device_s": 3.0},
                          "jit_decode_prefill": {"runs": 10, "device_s": 1.0}})
    for name in ("decode_mfu.phi4flash", "step_hbm_roofline.phi4flash",
                 "prefill_share.phi4flash"):
        assert reader(name)(r) is None
    r.trace = None
    for name in ("decode_mfu.phi4flash", "step_hbm_roofline.phi4flash",
                 "prefill_share.phi4flash"):
        assert reader(name)(r) is None
