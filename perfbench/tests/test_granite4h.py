"""The `granite-4.0-h-small-d10e36` configuration and its cell
(`granite-4.0-h-small-d10e36.decode-rag-closed96`), on the CPU at the
rehearsal's size: the configuration's file carries the catalog's config and
reduces only what it says, and the parameter table it states is what the shapes
add up to; the benchmark's own reference agrees with the program's test oracle
(`heat_tpu/nn/reference.py`, written apart from it); the sound program is
`correct` (judged by `logprob_err`: the log-probabilities the ENGINE handed back
with the tokens of the timed run against the reference's, see the driver) and
each planted fault is not: in the model (a dropped pair, gates taken over all
experts instead of the chosen, a recurrent state not reset, the attention
multiplier replaced by 1/sqrt(d), a multiplier left out) and in the engine
alone, where only the timed path can show it (a prefill program of a smaller
bucket that computes another model, a live slot's token overwritten between
steps, a slot read for its neighbour's log-probability); the float8 control
fails the cell's limit; the work counts and the six readers read what they say, and
nothing where the program lacks the counters.
"""

import json
import math
import os
import types

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from perfbench import run, work_granite4h as work
from perfbench.references import granite4h as ref

CELL = "granite-4.0-h-small-d10e36.decode-rag-closed96"
CONFIG = os.path.join(run.HERE, "configs", "granite-4.0-h-small-d10e36.json")
READERS = ("decode_mfu.granite4h", "step_hbm_roofline.granite4h",
           "moe_hbm_roofline.granite4h", "mixer_state_roofline.granite4h",
           "moe_share.granite4h", "expert_load_max_over_mean.granite4h")

# the catalog row's `config` (model-configs guide, architectures.jsonl)
LAYER_TYPES = ((["mamba"] * 5 + ["attention"] + ["mamba"] * 4) * 4)
CATALOG = {
    "attention_bias": False, "attention_multiplier": 0.0078125,
    "embedding_multiplier": 12, "hidden_act": "silu", "hidden_size": 4096,
    "intermediate_size": 768, "layer_types": LAYER_TYPES,
    "logits_scaling": 16, "mamba_chunk_size": 256, "mamba_conv_bias": True,
    "mamba_d_conv": 4, "mamba_d_head": 64, "mamba_d_state": 128,
    "mamba_expand": 2, "mamba_n_groups": 1, "mamba_n_heads": 128,
    "mamba_proj_bias": False, "max_position_embeddings": 131072,
    "model_type": "granitemoehybrid", "normalization_function": "rmsnorm",
    "num_attention_heads": 32, "num_experts_per_tok": 10,
    "num_hidden_layers": 40, "num_key_value_heads": 8,
    "num_local_experts": 72, "position_embedding_type": "nope",
    "residual_multiplier": 0.22, "rms_norm_eps": 1e-05, "rope_scaling": None,
    "rope_theta": 10000, "shared_intermediate_size": 1536,
    "tie_word_embeddings": True, "vocab_size": 100352}


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
def test_the_file_carries_the_catalogs_config_and_reduces_what_it_says():
    cfg = run.load_json(CONFIG)
    reduced = {"num_hidden_layers": 10, "num_local_experts": 36}
    for key, value in CATALOG.items():
        want = reduced.get(key, value)
        assert cfg[key] == want and type(cfg[key]) is type(want), key
    assert cfg["reduced"] == sorted(reduced)
    assert cfg["published"]["num_hidden_layers"] == 40
    assert cfg["published"]["num_local_experts"] == 72
    assert cfg["experts_held"] == [0, 36]
    assert cfg["param_dtype"] == cfg["compute_dtype"] == "bfloat16"
    assert len(cfg["source"]) <= 200 and "config.json" in cfg["source"]
    assert "two chips share each layer" in cfg["deployment"]
    # the layers run are a whole period of the published pattern
    assert ref.kinds(cfg) == ("mamba2",) * 5 + ("gqa",) + ("mamba2",) * 4
    z = ref.sizes(cfg)
    assert (z["di"], z["E"], z["count"], z["k"]) == (8192, 72, 36, 10)
    assert z["di"] == cfg["mamba_expand"] * z["D"] and z["d"] == 128


def test_the_parameter_table_is_what_the_shapes_add_up_to():
    cfg = run.load_json(CONFIG)
    z = ref.sizes(cfg)

    def count(kind, names=None, skip=()):
        return sum(math.prod(s) for n, (s, _h) in
                   ref.layer_shapes(kind, z).items()
                   if (names is None or n in names) and n not in skip)

    ffn = ("ln1", "ln2") + ref.FFN_NAMES
    table = list(cfg["parameters"].values())
    one_expert = count("gqa", ("we1", "we2")) // 36
    assert table[:11] == [
        count("mamba2", skip=ffn), count("gqa", skip=ffn),
        count("gqa", ("ws1", "ws2")), count("gqa", ("router",)),
        count("gqa", ("ln1", "ln2")), one_expert, 36 * one_expert,
        count("mamba2"), count("gqa"), z["V"] * z["D"], z["D"]]
    assert table[:6] == [102286976, 41943040, 18874368, 294912, 8192, 9437184]
    assert 9 * table[7] + table[8] + table[9] + table[10] == table[11] \
        == 4962732672                                            # 4.96 B
    assert work.param_bytes(cfg) == table[12] == 9925791744      # 9.93 GB
    # and the program's own tree is that many
    from perfbench.drivers import lm_decode_granite4h as driver

    model = driver.build_model(cfg, jax.devices(), {})
    leaves = jax.tree.leaves(model.pattern_param_shapes())
    assert sum(math.prod(a.shape) for a in leaves) == table[11]
    assert sum(math.prod(a.shape) * a.dtype.itemsize for a in leaves) \
        == table[12]
    _shapes, _specs, nbytes = model.cache_layout(64, 4096)
    assert nbytes == {"state": 9 * 64 * (128 * 64 * 128 * 4 + 3 * 8448 * 2),
                      "lane": 2 * 64 * 4096 * 1024 * 2}
    assert sum(nbytes.values()) == 3518857216                    # 3.52 GB


# -- the reference ------------------------------------------------------------
def test_the_benchmarks_reference_agrees_with_the_programs_oracle():
    """Two plain forwards written apart (this one by layer over a batch, the
    experts under `lax.scan`, the heads under `lax.map`; the oracle a sequence
    at a time) on the weights the benchmark makes."""
    from heat_tpu.nn import reference as oracle
    from perfbench.drivers import lm_decode_granite4h as driver

    cfg = small_config()
    key = jax.random.key(7)
    model = driver.build_model(cfg, jax.devices(), {})
    hp = oracle.host_params(ref.params_tree(key, cfg))
    toks = np.random.default_rng(1).integers(0, 128, 37).astype(np.int32)
    mine = np.asarray(ref.row_logits(key, cfg, toks))
    want = np.asarray(oracle.pattern_logits(hp, toks, model.cfg))
    assert np.abs(mine - want).max() < 1e-4 * want.std()
    routing = []
    ref.hidden(key, cfg, jnp.asarray(toks)[None], routing=routing)
    np.testing.assert_array_equal(
        np.stack([np.asarray(c[0]) for c in routing]),
        oracle.pattern_routing(hp, toks, model.cfg))
    # the control is another answer, by far more than the program's rounding
    low = np.asarray(ref.row_logits(key, cfg, toks, fp8=True))
    assert np.abs(low - want).max() > 0.05 * want.std()


def test_weights_come_a_layer_at_a_time_and_are_rounded_once():
    cfg = dict(small_config(), param_dtype="bfloat16")
    key = jax.random.key(3)
    held = ref.layer_weights(key, 1, cfg)
    as_f32 = ref.layer_weights(key, 1, cfg, jnp.float32)
    assert held["we1"].dtype == jnp.bfloat16 and held["we1"].shape[0] == 8
    assert held["router"].shape == (64, 16)              # all the experts
    assert held["A_log"].dtype == jnp.float32 == held["ln1"].dtype
    np.testing.assert_array_equal(
        np.asarray(held["w_in"].astype(jnp.float32)), as_f32["w_in"])
    a = np.exp(np.asarray(held["A_log"]))
    assert 1.0 <= a.min() and a.max() <= 16.0
    step = np.log1p(np.exp(np.asarray(held["dt_bias"])))
    assert 1e-3 * 0.99 <= step.min() and step.max() <= 1e-1 * 1.01
    assert np.abs(np.asarray(as_f32["conv_w"])).max() <= 0.5
    assert not np.asarray(held["conv_b"]).any()


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
    assert set(READERS) <= listed
    # no device plane in a rehearsal: nothing to read by scope or program
    silent = {"cache_move_share.decode", "prefill_share.phi4flash",
              "step_hbm_roofline.granite4h", "moe_hbm_roofline.granite4h",
              "mixer_state_roofline.granite4h", "moe_share.granite4h"}
    assert set(line["metrics"]) == listed - silent
    assert 1.0 <= line["metrics"]["expert_load_max_over_mean.granite4h"][
        "value"] < 1.5
    assert line["correct"] is True


def test_a_dropped_pair_is_not_correct(capsys, monkeypatch):
    """Every token's first choice computes nothing (a capacity that is
    full)."""
    from heat_tpu.nn import parallel

    sound = parallel._top_k_gates

    def dropped(logits, k):
        gates, chosen = sound(logits, k)
        return gates.at[:, 0].set(0.0), chosen

    monkeypatch.setattr(parallel, "_top_k_gates", dropped)
    line = last_line(capsys)
    assert line["correct"] is False and "logprob_err" in failed(line)


def test_gates_over_all_experts_are_not_correct(capsys, monkeypatch):
    from heat_tpu.nn import parallel

    def over_all(logits, k):
        _top, chosen = jax.lax.top_k(logits, k)
        return jnp.take_along_axis(jax.nn.softmax(logits, axis=-1), chosen,
                                   axis=-1), chosen

    monkeypatch.setattr(parallel, "_top_k_gates", over_all)
    line = last_line(capsys)
    assert line["correct"] is False and "logprob_err" in failed(line)


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
    assert line["correct"] is False and "logprob_err" in failed(line)


def test_one_over_root_d_for_the_attention_multiplier_is_not_correct(
        capsys, monkeypatch):
    from heat_tpu.nn import mixers

    lanes = mixers.gqa_lanes
    monkeypatch.setattr(
        mixers, "gqa_lanes",
        lambda q, kl, vl, seen, scale: lanes(q, kl, vl, seen,
                                             q.shape[-1] ** -0.5))
    line = last_line(capsys)
    assert line["correct"] is False and "logprob_err" in failed(line)


def test_a_multiplier_left_out_is_not_correct(capsys, monkeypatch):
    """The embedding enters the stream as it is, not times 12."""
    from heat_tpu.nn.transformer import TransformerLM

    monkeypatch.setattr(
        TransformerLM, "_embed",
        lambda self, params, toks: params["embed"][toks].astype(
            self.cfg.compute_dtype))
    line = last_line(capsys)
    assert line["correct"] is False and "logprob_err" in failed(line)


# faults of the ENGINE: the model's functions are sound, and only what the
# timed run itself handed back can show them
def test_a_smaller_buckets_prefill_program_gone_wrong_is_not_correct(
        capsys, monkeypatch):
    """Every prefill program but the largest bucket's runs on a first layer
    whose output projection is halved: a check that replayed the largest
    bucket alone would pass it."""
    from heat_tpu.serve.decode import DecodeEngine

    sound = DecodeEngine._prefill_prog

    def broken(self, Sp):
        prog = sound(self, Sp)
        if 2 * Sp >= self.S_cap:        # the rehearsal's largest bucket
            return prog

        def run(params, *rest):
            first = dict(params["segments"][0][0])
            first["w_out"] = first["w_out"] * 0.5
            segments = [[first, *params["segments"][0][1:]],
                        *params["segments"][1:]]
            return prog(dict(params, segments=segments), *rest)

        return run

    monkeypatch.setattr(DecodeEngine, "_prefill_prog", broken)
    line = last_line(capsys)
    assert line["correct"] is False and "logprob_err" in failed(line)


def test_a_live_slots_token_overwritten_is_not_correct(capsys, monkeypatch):
    """Now and then the device's last-token vector is rolled by a slot before
    a step: a live slot is fed its neighbour's token."""
    from heat_tpu.serve.decode import DecodeEngine

    sound = DecodeEngine._dispatch_step

    def rolled(self, live, record=True):
        if record and self._step_seq % 7 == 3:
            self._toks = jnp.roll(self._toks, 1)
        return sound(self, live, record)

    monkeypatch.setattr(DecodeEngine, "_dispatch_step", rolled)
    line = last_line(capsys)
    assert line["correct"] is False and "token_gap" in failed(line)


def test_a_neighbours_log_probability_is_not_correct(capsys, monkeypatch):
    """The host reads the fetched vector a slot off: the tokens are right,
    each request is told its neighbour's log-probabilities."""
    from heat_tpu.serve.decode import DecodeEngine

    sound = DecodeEngine._dispatch_step

    def off_by_one(self, live, record=True):
        toks, logp = sound(self, live, record)
        return toks, np.roll(logp, 1)

    monkeypatch.setattr(DecodeEngine, "_dispatch_step", off_by_one)
    line = last_line(capsys)
    assert line["correct"] is False and failed(line) == ["logprob_err"]


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
    assert by_kind["control"]["checks"]["logprob_err"]["value"] > 100 * \
        by_kind["program"]["checks"]["logprob_err"]["value"]


# -- the work counts ----------------------------------------------------------
def test_flops_and_bytes_from_the_shapes():
    cfg = run.load_json(CONFIG)
    mm = work.matmul_params(cfg)
    assert mm["expert"] == 9437184 and mm["shared"] == 18874368
    assert mm["mamba2"] == 4096 * 16768 + 8192 * 4096
    f0 = work.flops_per_token(cfg, 100, 5.0)
    # 1,000 more attended positions: the one attention layer, 4 H d each
    assert work.flops_per_token(cfg, 1100, 5.0) - f0 == pytest.approx(
        4 * 4096 * 1000)
    # one more held pair a layer: one expert's three matrices in ten layers
    assert work.flops_per_token(cfg, 100, 6.0) - f0 == pytest.approx(
        2 * 10 * 9437184)
    assert f0 - work.flops_per_token(cfg, 100, 5.0, head=False) \
        == 2 * 4096 * 100352
    # ISSUE 37: a prompt token needs 3.25 GFLOP at the expected 5 of 10
    assert 3.2e9 < work.flops_per_token(cfg, 470, 5.0, head=False) < 3.4e9
    assert work.moe_bytes(cfg) == 10 * 2 * (36 * 9437184 + 18874368 + 294912)
    state = 9 * 2 * (8192 * 128 * 4 + 3 * 8448 * 2)
    assert work.state_bytes_per_step(cfg, 1100) == state + 1101 * 4096
    assert work.step_bytes(cfg, 64, 1100) == work.param_bytes(cfg) + 64 * (
        state + 1101 * 4096)
    # ISSUE 37: 15.0 GB a step at full occupancy, 18.4 ms at 819 GB/s
    assert 14.9e9 < work.step_bytes(cfg, 64, 1100) < 15.2e9


# -- the readers --------------------------------------------------------------
def fake_run(counters0, counters1, programs, by_scope, busy=4.0, window=5.0):
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
               "programs": programs, "by_scope": by_scope})


def reader(name):
    return run.load_by_name("layer_metrics", name).read


PROGRAMS = {"jit_decode_step": {"runs": 100, "device_s": 3.0},
            "jit_decode_prefill": {"runs": 10, "device_s": 1.0}}
# the grouped products come out of the compiler without the program's scope
# (`ragged-dot-none.<n>`, unscoped): the readers take them by name
BY_SCOPE = {
    ("jit_decode_step", "moe", "fwd"): {"self_s": 0.4, "ops": {}},
    ("jit_decode_step", "unscoped", "fwd"): {"self_s": 1.5, "ops": {
        "ragged-dot-none.1": 0.7, "ragged-dot-none.2": 0.5, "fusion.9": 0.3}},
    ("jit_decode_prefill", "moe", "fwd"): {"self_s": 0.3, "ops": {}},
    ("jit_decode_prefill", "unscoped", "fwd"): {"self_s": 0.1, "ops": {
        "ragged-dot-none": 0.1}},
    ("jit_decode_step", "attn.core", "fwd"): {"self_s": 0.8, "ops": {}}}


def test_readers_read_what_they_say():
    from perfbench import traffic

    pairs = {f"moe_pairs_expert_{e}": 0 for e in range(36)}
    c0 = dict(pairs, tokens_out=0, prefills=0, decode_steps=0,
              prefill_tokens=0, moe_pairs_total=0, moe_pairs_held=0)
    sent = {f"moe_pairs_expert_{e}": 17000 + 10 * e for e in range(36)}
    tokens = 6400 + 6000                    # through every expert layer
    c1 = dict(sent, tokens_out=6400 + 10, prefills=10, decode_steps=100,
              prefill_tokens=6000, moe_pairs_total=tokens * 100,
              moe_pairs_held=sum(sent.values()))
    r = fake_run(c0, c1, PROGRAMS, BY_SCOPE)
    out_ctx, prompt_ctx = work.mean_contexts(traffic.request_sizes(r.traffic))
    held = sum(sent.values()) / (tokens * 10)           # a token a layer
    flops = (6410 * work.flops_per_token(r.config, out_ctx, held)
             + 6000 * work.flops_per_token(r.config, prompt_ctx, held,
                                           head=False))
    assert reader("decode_mfu.granite4h")(r) == pytest.approx(
        100 * flops / (5.0 * 197e12))
    least = 100 * work.step_bytes(r.config, 64.0, out_ctx) / 819e9
    assert reader("step_hbm_roofline.granite4h")(r) == pytest.approx(
        100 * least / 3.0)
    assert reader("moe_hbm_roofline.granite4h")(r) == pytest.approx(
        100 * (100 * work.moe_bytes(r.config) / 819e9) / (0.4 + 0.7 + 0.5))
    assert reader("mixer_state_roofline.granite4h")(r) == pytest.approx(
        100 * (6400 * work.state_bytes_per_step(r.config, out_ctx) / 819e9)
        / 0.8)
    assert reader("moe_share.granite4h")(r) == pytest.approx(
        100 * (0.4 + 1.2 + 0.3 + 0.1) / 4.0)
    assert reader("expert_load_max_over_mean.granite4h")(r) == pytest.approx(
        17350 / 17175)
    assert all(0 < reader(n)(r) < 100 for n in READERS[:5])


def test_readers_find_nothing_in_a_program_without_the_counters():
    """The parent's engine counts no routed pairs: the six new metrics are
    left out of its line, they do not raise."""
    c0 = {"tokens_out": 0, "prefills": 0, "decode_steps": 0,
          "prefill_tokens": 0}
    c1 = {"tokens_out": 700, "prefills": 10, "decode_steps": 100,
          "prefill_tokens": 600}
    r = fake_run(c0, c1, PROGRAMS, BY_SCOPE)
    for name in READERS:
        assert reader(name)(r) is None, name
    r.trace = None
    for name in READERS:
        assert reader(name)(r) is None, name
