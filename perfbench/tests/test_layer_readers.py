"""The per-layer readers that read by the program's names: each on a trace
recorded on the TPU v5e (the small train step of PR 29; a short piece of the
decode cell's own trace, PR 32) and on hand-made scopes and spans where every
answer can be worked out. A reader that finds nothing to read returns nothing."""

import collections
import os

import pytest

from perfbench import run, trace_scopes, work
from perfbench.tools import stall_probe

HERE = os.path.dirname(os.path.abspath(__file__))
Span = collections.namedtuple(
    "Span", "name t0 t1 id parent_id thread attrs events")
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def reader(name):
    return run.load_by_name("layer_metrics", name).read


def span(name, t0, t1, id=0, parent=0, **attrs):
    return Span(name, t0, t1, id, parent, 1, attrs, ())


class FakeProbe:
    def __init__(self, traced=None, t0=0.0, window_s=40.0):
        self.traced, self.t0, self.window_s = traced, t0, window_s
        self.spans = {}


def a_run(**kw):
    kw.setdefault("probe", FakeProbe({"t0": 10.0, "t1": 15.0, "units": 4}))
    kw.setdefault("spans", [])
    kw.setdefault("trace", None)
    kw.setdefault("chips", 1)
    kw.setdefault("peaks", PEAKS)
    kw.setdefault("work", work)
    return run.Run(**kw)


def scopes_of(rows, busy):
    return {"device0_busy_s": busy, "by_scope": {
        key: {"self_s": sum(ops.values()), "ops": ops}
        for key, ops in rows.items()}}


# -- the train cell's readers ------------------------------------------------
SMALL = {"hidden_size": 256, "num_hidden_layers": 2}


def test_train_readers_on_the_recorded_small_step():
    scopes = trace_scopes.reduce_file(
        os.path.join(HERE, "recorded_train_small.xplane.pb"))
    r = a_run(trace=scopes, config=SMALL, traffic={"seq": 128},
              result={"tokens_per_step": 256},
              probe=FakeProbe({"t0": 0.0, "t1": 1.0, "units": 1}))
    took = 158.194e-6 + 102.143e-6                   # attn.core, fwd + bwd
    assert trace_scopes.scope_seconds(scopes, "attn.core") == pytest.approx(
        took, abs=1e-9)
    flops = 3 * 256 * (2 * 4 * 256 * 64.5)           # needed, causal
    assert reader("attn_core_roofline")(r) == pytest.approx(
        100 * flops / took / 197e12, rel=1e-6)
    assert reader("pipeline_share.train")(r) == pytest.approx(
        100 * (60.615 + 1.797) / 645.116, rel=1e-5)


def test_scope_readers_fall_silent_without_names_or_without_the_scope():
    bare = scopes_of({("jit_train_step", "mlp", "fwd"): {"fusion.1": 0.5}}, 0.5)
    for name in ("attn_core_roofline", "pipeline_share.train",
                 "cache_move_share.decode", "weights_cast_share.decode"):
        for scopes in (None, bare):
            r = a_run(trace=scopes, config=SMALL, traffic={"seq": 128},
                      result={"tokens_per_step": 256})
            assert reader(name)(r) is None, name


def test_attn_core_roofline_counts_the_work_needed_not_the_kernel():
    """Twice the time under the name, half the share; whichever operations
    carry the name."""
    def of(ops):
        return a_run(config=SMALL, traffic={"seq": 2048},
                     result={"tokens_per_step": 4096},
                     trace=scopes_of({
                         ("jit_train_step", "attn.core", "fwd"): ops}, 1.0))
    one = reader("attn_core_roofline")(of({"flash_fwd.1": 0.010}))
    two = reader("attn_core_roofline")(of({"fusion.9": 0.015, "dot.3": 0.005}))
    flops = 3 * 4 * 4096 * (2 * 4 * 256 * 1024.5)
    assert one == pytest.approx(100 * flops / 0.010 / 197e12)
    assert two == pytest.approx(one / 2)


# -- the decode cell's readers -----------------------------------------------
def test_cache_move_and_weights_cast_shares_by_hand():
    scopes = scopes_of({
        ("jit_decode_step", "cache.read", "fwd"): {"slice_bitcast_fusion": 0.20},
        ("jit_decode_step", "cache.write", "fwd"): {"dus_fusion": 0.15},
        ("jit_decode_prefill", "cache.write", "fwd"): {"dus_fusion.2": 0.05},
        ("jit_decode_step", "cast", "fwd"): {"convert.1": 0.02},
        ("jit_decode_step", "unscoped", "fwd"): {
            "convert_bitcast_fusion.1": 0.06, "convert.164": 0.02,
            "copy.5": 0.30},
        # a cast in the prefill is the prefill's, not the step's
        ("jit_decode_prefill", "unscoped", "fwd"): {"convert.9": 0.10},
        ("jit_decode_step", "attn.core", "fwd"): {"fusion.7": 0.10},
    }, busy=1.0)
    r = a_run(trace=scopes)
    assert reader("cache_move_share.decode")(r) == pytest.approx(40.0)
    assert reader("weights_cast_share.decode")(r) == pytest.approx(10.0)


def test_decode_scope_readers_on_the_recorded_piece_of_the_cells_own_trace():
    """0.447 s of `pythia-1.4b-d8.decode-conv-closed48` on the TPU v5e (7 steps
    and one prefill; my chip run, PR 32: seed 3300000001, on that PR's first mix
    (prompts ~1,020; the programs and their operations are the same), with a
    short trace plan), stripped to device 0's plane and the host lines that hold
    `pb.`/`ht.` spans, nothing inside them rewritten (2.2 MB raw). The run
    itself printed 56.5009% and 11.5103%."""
    path = os.path.join(HERE, "recorded_decode_short.xplane.pb")
    assert os.path.getsize(path) <= 768 * 1024
    scopes = trace_scopes.reduce_file(path)
    assert scopes["window_s"] == pytest.approx(0.447361454)
    assert scopes["busy_s"] == scopes["device0_busy_s"] == pytest.approx(
        0.31829899, abs=1e-9)
    assert reader("device_idle_share.decode")(a_run(trace=scopes)) \
        == pytest.approx(100 * (1 - 0.31829899 / 0.447361454), rel=1e-6)
    assert scopes["programs"]["jit_decode_step"]["runs"] == 7
    assert scopes["programs"]["jit_decode_prefill"]["runs"] == 1
    r = a_run(trace=scopes)
    assert reader("cache_move_share.decode")(r) == pytest.approx(
        100 * (0.090991878 + 0.088849805) / 0.31829899, rel=1e-6) \
        == pytest.approx(56.5009, abs=1e-4)
    assert trace_scopes.scope_seconds(
        scopes, "unscoped", "jit_decode_step", "convert") == pytest.approx(
        0.036629987, abs=1e-9)
    assert reader("weights_cast_share.decode")(r) == pytest.approx(
        11.5103, abs=1e-4)
    assert reader("pipeline_share.train")(r) is None   # no such scope in it
    # the ledger's breakdown: operations by scope, gaps by the program's spans
    b = run.breakdown_of(scopes)
    assert len(b["device_ops"]) == 10
    assert b["device_ops"][0][0] == "unscoped:convert_bitcast_fusion.1"
    assert any(n.startswith("cache.read:slice_bitcast_fusion")
               for n, _t in b["device_ops"])
    assert [n for n, _t in b["idle_gaps"]][:3] == [
        "pb.wait", "ht.decode.fetch", "ht.decode.prefill.dispatch"]


def test_fetch_wait_is_the_steps_fetch_inside_the_traced_window():
    spans = [
        span("decode.step", 10.1, 10.2, id=1),
        span("decode.fetch", 10.11, 10.19, id=2, parent=1),       # 80 ms
        span("decode.step", 10.3, 10.4, id=3),
        span("decode.fetch", 10.31, 10.35, id=4, parent=3),       # 40 ms
        span("decode.prefill.dispatch", 10.5, 10.51, id=5),
        span("decode.fetch", 10.51, 10.56, id=6, parent=0),       # a prefill's
        span("decode.step", 9.95, 10.05, id=7),                   # straddles
        span("decode.fetch", 10.0, 10.04, id=8, parent=7),
        span("decode.step", 20.0, 20.1, id=9),                    # outside
        span("decode.fetch", 20.0, 20.09, id=10, parent=9),
    ]
    assert reader("fetch_wait_ms.decode")(a_run(spans=spans)) \
        == pytest.approx(60.0)
    assert reader("fetch_wait_ms.decode")(a_run(spans=[])) is None
    assert reader("fetch_wait_ms.decode")(
        a_run(spans=spans, probe=FakeProbe(None))) is None


def test_queue_and_ttft_over_the_requests_of_the_whole_window():
    spans = []
    for rid in range(20):                        # queue 1 s, prefill 0.1 s
        t = 1.0 + rid
        spans.append(span("decode.queue", t, t + 1.0, rid=rid))
        spans.append(span("decode.prefill", t + 1.0, t + 1.1, rid=rid, slot=0))
    spans.append(span("decode.queue", 30.0, 34.0, rid=20))       # the tail
    spans.append(span("decode.prefill", 34.0, 34.5, rid=20, slot=1))
    spans.append(span("decode.queue", 38.0, 47.0, rid=21))       # after it
    spans.append(span("decode.prefill", 47.0, 47.1, rid=21, slot=2))
    spans.append(span("decode.prefill", 5.0, 5.1, rid=99, slot=3))  # no queue
    r = a_run(spans=spans, probe=FakeProbe(None, t0=0.0, window_s=40.0))
    import numpy as np
    assert reader("queue_ms_p95")(r) == pytest.approx(
        1e3 * np.percentile([1.0] * 20 + [4.0], 95))
    assert reader("ttft_ms_p95")(r) == pytest.approx(
        1e3 * np.percentile([1.1] * 20 + [4.5], 95))
    assert reader("queue_ms_p95")(a_run(spans=[])) is None
    assert reader("ttft_ms_p95")(a_run(spans=[])) is None


# -- the distance-matrix cell's reader -----------------------------------------
def test_cdist_write_roofline_is_one_write_of_the_result_over_busy_time():
    r = a_run(config={"n_rows": 40_000}, trace={"device0_busy_s": 0.4},
              probe=FakeProbe({"t0": 0.0, "t1": 1.0, "units": 8}))
    least = 6.4e9 / 819e9
    assert reader("cdist_write_roofline")(r) == pytest.approx(
        100 * least / 0.05)
    assert reader("cdist_write_roofline")(a_run(
        config={"n_rows": 40_000}, trace=None)) is None


def test_breakdown_names_operations_by_scope_and_gaps_by_the_programs_spans():
    scopes = scopes_of({
        ("jit_decode_step", "cache.read", "fwd"): {"slice.1": 0.2},
        ("jit_decode_prefill", "cache.read", "fwd"): {"slice.1": 0.1},
        ("jit_decode_step", "unscoped", "fwd"): {"copy.5": 0.25}}, 0.55)
    scopes["idle_gaps"] = {"ht.decode.fetch": 0.04, "pb.wait": 0.01}
    got = run.breakdown_of(scopes)
    assert got["device_ops"][0] == ["cache.read:slice.1",
                                    pytest.approx(0.3)]
    assert got["device_ops"][1] == ["unscoped:copy.5", 0.25]
    assert got["idle_gaps"] == [["ht.decode.fetch", 0.04], ["pb.wait", 0.01]]


def test_stall_probe_lays_the_longest_span_beside_the_late_wakeups():
    """`tools/stall_probe.py`: ONE long unit with the ticker on time is a wait
    for the device; with a late wake-up at the same moment the process stood
    still. The machine's counters are read where /proc has them."""
    probe = FakeProbe(None, t0=100.0, window_s=40.0)
    probe.units, probe.after_close_s = 3, 21.5
    probe.spans = {"job": [(100.0, 100.04), (100.04, 102.04), (102.04, 102.08)]}
    probe.machine0 = {"stat.steal": 5, "stat.user": 10, "gone": 1}
    probe.machine1 = {"stat.steal": 155, "stat.user": 10}

    class T:
        ticks = 2000
        late = [(50.0, 0.5), (101.9, 1.85), (150.0, 0.3)]

    got = stall_probe.report(probe, T, child_late=[(101.95, 1.8), (7.0, 9.9)])
    assert got["child_late_wakeups_in_window"] == [
        {"woke_at_s": pytest.approx(1.95), "late_ms": pytest.approx(1800.0)}]
    assert got["spans"]["job"] == {
        "count": 3, "mean_ms": pytest.approx(2080 / 3),
        "longest_ms": pytest.approx(2000.0),
        "longest_began_s": pytest.approx(0.04)}
    assert got["late_wakeups_in_window"] == [
        {"woke_at_s": pytest.approx(1.9), "late_ms": pytest.approx(1850.0)}]
    assert got["machine_delta"] == {"stat.steal": 150}
    assert stall_probe._lines("/proc/no_such_file") == []
    assert stall_probe.machine_counters()["self.cpu_s"] > 0
