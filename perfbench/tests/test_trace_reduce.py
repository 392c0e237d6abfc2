"""Busy time, idle time and operations of the ONE reduction from trace to
numbers (`perfbench/trace_scopes.py`; `test_trace_scopes.py` has its names):
on hand-made planes, where every answer can be worked out, and on two traces
recorded on the TPU v5e (`kmeans-w25m.fit30` on one chip and on four, 2 fits,
PR 28), whose numbers were pinned from `jax.profiler.ProfileData`'s reading
of the same files before PR 32 merged the two readers."""

import os

import pytest

from perfbench import run
from perfbench import trace_scopes as tr

HERE = os.path.dirname(os.path.abspath(__file__))
MS = 1e6  # ns


def planes(dev_ops, spans, n_dev=1):
    p = {"/host:CPU": {"python3": [ev + ({},) for ev in spans]}}
    for d in range(n_dev):
        p[f"/device:TPU:{d}"] = {"XLA Ops": [ev + ({},) for ev in dev_ops[d]],
                                 "XLA Modules": []}
    return p


def ops_of(r):
    out = {}
    for row in r["by_scope"].values():
        for n, t in row["ops"].items():
            out[n] = out.get(n, 0.0) + t
    return out


def test_union_clip_and_length():
    u = tr.union([(0, 2), (1, 3), (5, 6), (6, 6)])
    assert u == [[0, 3], [5, 6]]
    assert tr.length(u) == 4
    assert tr.clip([(0, 10)], 2, 5) == [(2, 5)]


def test_op_name_keeps_what_stands_before_the_equals():
    assert tr.op_name("%fusion.3 = f32[8]{0} fusion(f32[8] %all-reduce.1)") \
        == "fusion.3"
    assert not tr.is_collective(tr.op_name(
        "fusion.3 = f32[8]{0} fusion(f32[8] %all-reduce.1)"))
    assert tr.is_collective(tr.op_name("all-reduce.1 = f32[8] all-reduce(x)"))


def test_busy_idle_collectives_and_gap_owner():
    ops = [("fusion.1", 10 * MS, 20 * MS), ("all-reduce.2", 30 * MS, 10 * MS),
           ("fusion.1", 60 * MS, 20 * MS), ("outside", 200 * MS, 5 * MS)]
    spans = [("pb.window", 0.0, 100 * MS), ("pb.fit", 0.0, 45 * MS),
             ("pb.wait", 45 * MS, 55 * MS)]
    r = tr.reduce_planes(planes([ops], spans), chips=1)
    assert r["window_s"] == pytest.approx(0.100)
    assert r["busy_s"] == pytest.approx(0.050)
    assert tr.idle_share_percent(r) == pytest.approx(50.0)
    assert r["collectives"]["0"]["total_s"] == pytest.approx(0.010)
    assert ops_of(r) == pytest.approx({"fusion.1": 0.040, "all-reduce.2": 0.010})
    # gaps: [0,10) under fit, [40,60) mostly under wait, [80,100) under wait
    assert r["idle_gaps"] == pytest.approx({"pb.fit": 0.010, "pb.wait": 0.040})
    assert run.breakdown_of(r) == {
        "device_ops": [["unscoped:fusion.1", pytest.approx(0.040)],
                       ["unscoped:all-reduce.2", pytest.approx(0.010)]],
        "idle_gaps": [["pb.wait", pytest.approx(0.040)],
                      ["pb.fit", pytest.approx(0.010)]]}


def test_self_time_of_an_enclosing_operation():
    evs = [("while.1", 0.0, 100.0, {}), ("fusion.a", 10.0, 30.0, {}),
           ("fusion.b", 50.0, 20.0, {})]
    assert {e[0]: (own, encloses)
            for e, own, encloses in tr.self_time_per_event(evs)} == {
        "while.1": (50.0, True), "fusion.a": (30.0, False),
        "fusion.b": (20.0, False)}


def test_busy_is_the_mean_over_the_chips_used():
    ops0 = [("f", 0.0, 50 * MS)]
    ops1 = [("f", 0.0, 100 * MS)]
    spans = [("pb.window", 0.0, 100 * MS)]
    r = tr.reduce_planes(planes([ops0, ops1], spans, 2), chips=2)
    assert r["busy_s"] == pytest.approx(0.075)
    assert r["busy_s_by_device"] == pytest.approx({"0": 0.05, "1": 0.1})


def test_no_window_span_or_no_device_work_is_an_error():
    with pytest.raises(ValueError, match="pb.window"):
        tr.reduce_planes(planes([[("f", 0.0, 1.0)]], []), chips=1)
    with pytest.raises(ValueError, match="no operation"):
        tr.reduce_planes(planes([[("f", 500 * MS, MS)]],
                                [("pb.window", 0.0, 100 * MS)]), chips=1)
    with pytest.raises(ValueError, match="device planes"):
        tr.reduce_planes(planes([[("f", 0.0, MS)]],
                                [("pb.window", 0.0, 100 * MS)]), chips=4)


def test_a_rehearsals_cpu_workers_stand_in_for_the_device():
    p = {"/host:CPU": {
        "python3": [("pb.window", 0.0, 100 * MS, {})],
        "tf_XLAEigen/123": [("dot.1", 10 * MS, 30 * MS, {})],
        "tf_XLAPjRtCpuClient/9": [("add.2", 30 * MS, 20 * MS, {}),
                                  ("marker", 60 * MS, 0.0, {})]}}
    with pytest.raises(ValueError, match="device planes"):
        tr.reduce_planes(p, chips=1)
    r = tr.reduce_planes(p, chips=4, rehearse=True)   # one stand-in for all
    assert r["busy_s"] == r["device0_busy_s"] == pytest.approx(0.040)
    assert set(r["by_scope"]) == {("unknown", "unscoped", "fwd")}


def test_recorded_tpu_trace():
    path = os.path.join(HERE, "recorded_kmeans_1chip.xplane.pb")
    p = tr.load_planes(path)
    assert "/device:TPU:0" in p and "XLA Ops" in p["/device:TPU:0"]
    r = tr.reduce_planes(p, chips=1)
    # my chip run, PR 28: 2 fits of 30 iterations, 2.1166 s traced
    assert r["window_s"] == pytest.approx(2.116613435, rel=1e-6)
    assert r["busy_s"] == pytest.approx(2.003013582, rel=1e-6)
    assert r["busy_s"] == r["device0_busy_s"] == r["busy_s_by_device"]["0"]
    assert r["collectives"]["0"]["total_s"] == 0.0
    assert 5.0 < tr.idle_share_percent(r) < 6.0
    top = max(ops_of(r).items(), key=lambda kv: kv[1])
    assert top[0] == "fusion" and top[1] == pytest.approx(0.6081, abs=2e-3)
    assert list(r["idle_gaps"]) == ["pb.fit"]


def test_recorded_four_chip_trace_collectives_by_name():
    path = os.path.join(HERE, "recorded_kmeans_4chip.xplane.pb")
    p = tr.load_planes(path)
    assert all(f"/device:TPU:{d}" in p for d in range(4))
    r = tr.reduce_planes(p, chips=4)
    # my chip run, PR 28: 2 fits of 30 iterations on 100Mx64 over four chips
    assert r["window_s"] == pytest.approx(2.147488868, rel=1e-6)
    assert r["busy_s"] == pytest.approx(2.0040630925, rel=1e-6)
    assert set(r["busy_s_by_device"]) == {"0", "1", "2", "3"}
    assert [n for n in ops_of(r) if tr.is_collective(n)] == ["all-reduce"]
    assert r["collectives"]["0"]["total_s"] == pytest.approx(7.9638e-05,
                                                             rel=1e-4)
    with pytest.raises(ValueError):          # a one-chip trace is no 4-chip run
        tr.reduce_planes(tr.load_planes(os.path.join(
            HERE, "recorded_kmeans_1chip.xplane.pb")), chips=4)
