"""The reduction by name (`perfbench/trace_scopes.py`): on hand-made planes
where every answer can be worked out, on the two KMeans traces PR 28 recorded
(programs without scopes: everything `unscoped`), and on one small trace recorded on the TPU v5e by PR 29
(one step of a two-layer width-256 `TransformerLM`), whose table is pinned."""

import os

import pytest

from perfbench import trace_scopes as ts
from perfbench.tools import scopes as tool

HERE = os.path.dirname(os.path.abspath(__file__))
MS = 1e6  # ns
P1, P2 = 11, 22


def op(name, start, dur, tf_op=None, program=P1, flops=0, nbytes=0):
    meta = {"program_id": program, "flops": flops, "bytes_accessed": nbytes}
    if tf_op is not None:
        meta["tf_op"] = tf_op
    return (name, start, dur, meta)


def planes(dev_ops, spans, modules=None, n_dev=1, async_ops=None):
    p = {"/host:CPU": {"python3": [(n, s, d, {}) for n, s, d in spans]}}
    for d in range(n_dev):
        p[f"/device:TPU:{d}"] = {
            "XLA Ops": dev_ops[d],
            "XLA Modules": list(modules or []),
            "Async XLA Ops": list((async_ops or {}).get(d, []))}
    return p


def test_scope_of_innermost_name_wrappers_and_direction():
    f = ts.scope_of
    assert f("jit(train_step)/jit(main)/attn.core/dot_general:") \
        == ("attn.core", "fwd")
    assert f("jit(train_step)/transpose(jvp(attn.core))/mul:") \
        == ("attn.core", "bwd")
    # innermost wins: a cast inside the mlp is the cast's
    assert f("jit(train_step)/jvp(mlp)/cast/convert_element_type:") \
        == ("cast", "fwd")
    assert f("jit(train_step)/transpose(jvp(mlp))/transpose(jvp(cast))/c:") \
        == ("cast", "bwd")
    assert f("jit(lloyd_step)/lloyd.dist/dot_general") == ("lloyd.dist", "fwd")
    assert f("jit(device_step)/reduce_sum:") == ("unscoped", "fwd")
    assert f(None) == ("unscoped", "fwd") and f("") == ("unscoped", "fwd")
    # an operation NAMED like a scope is no scope: only whole path parts count
    assert f("jit(f)/mlp_like/add:") == ("unscoped", "fwd")
    assert f("jit(f)/myscope/add:", scopes={"myscope"}) == ("myscope", "fwd")


def test_self_time_by_scope_with_a_while_around_scoped_children():
    ops = [
        op("while.1", 0.0, 100 * MS, "jit(train_step)/while:", flops=999),
        op("fusion.a", 10 * MS, 30 * MS, "jit(train_step)/attn.core/dot:",
           flops=300, nbytes=30),
        op("fusion.b", 50 * MS, 20 * MS,
           "jit(train_step)/transpose(jvp(attn.core))/dot:", flops=200),
        op("fusion.c", 120 * MS, 40 * MS, "jit(train_step)/optimizer/mul:",
           nbytes=4000),
        op("copy.7", 160 * MS, 10 * MS),                  # no tf_op at all
        op("fusion.a", 300 * MS, 30 * MS, "jit(train_step)/attn.core/dot:",
           flops=300, nbytes=30),                         # outside the window
    ]
    spans = [("pb.window", 0.0, 200 * MS)]
    mods = [(f"jit_train_step({P1})", 0.0, 170 * MS, {})]
    r = ts.reduce_planes(planes([ops], spans, mods))
    got = {k: v["self_s"] for k, v in r["by_scope"].items()}
    assert got == pytest.approx({
        ("jit_train_step", "unscoped", "fwd"): 0.050 + 0.010,   # while's own
        ("jit_train_step", "attn.core", "fwd"): 0.030,
        ("jit_train_step", "attn.core", "bwd"): 0.020,
        ("jit_train_step", "optimizer", "fwd"): 0.040})
    assert sum(got.values()) == pytest.approx(r["device0_busy_s"]) \
        == pytest.approx(0.150)
    rows = r["by_scope"]
    # the while's own 999 flops are not summed: its body would count twice
    assert rows[("jit_train_step", "unscoped", "fwd")]["xla_flops"] == 0
    assert rows[("jit_train_step", "attn.core", "fwd")]["xla_flops"] == 300
    assert rows[("jit_train_step", "attn.core", "fwd")]["xla_bytes_accessed"] == 30
    assert rows[("jit_train_step", "optimizer", "fwd")]["xla_bytes_accessed"] == 4000
    assert rows[("jit_train_step", "unscoped", "fwd")]["ops"] \
        == pytest.approx({"while.1": 0.050, "copy.7": 0.010})
    shares = ts.scope_shares(r)
    assert shares["attn.core"] == pytest.approx(50 / 150)
    assert shares["unscoped"] == pytest.approx(60 / 150)
    assert "attn.core" in tool.render(r)


def test_program_runs_from_the_modules_line():
    ops = [op("f", 0.0, 10 * MS, program=P1), op("g", 20 * MS, 5 * MS,
                                                program=P2)]
    mods = [(f"jit_train_step({P1})", 0.0, 10 * MS, {}),
            (f"jit_lloyd_assign({P2})", 20 * MS, 5 * MS, {}),
            (f"jit_train_step({P1})", 90 * MS, 30 * MS, {}),   # clipped to 10
            (f"jit_train_step({P1})", 500 * MS, 30 * MS, {})]  # outside
    r = ts.reduce_planes(planes([ops], [("pb.window", 0.0, 100 * MS)], mods))
    assert r["programs"] == {
        "jit_train_step": {"runs": 2, "device_s": pytest.approx(0.020)},
        "jit_lloyd_assign": {"runs": 1, "device_s": pytest.approx(0.005)}}
    assert set(r["by_scope"]) == {("jit_train_step", "unscoped", "fwd"),
                                  ("jit_lloyd_assign", "unscoped", "fwd")}


def test_idle_gap_goes_to_the_innermost_span_not_the_outermost():
    ops = [op("f", 10 * MS, 20 * MS), op("f", 60 * MS, 20 * MS)]
    spans = [("pb.window", 0.0, 100 * MS), ("pb.step", 0.0, 58 * MS),
             ("ht.train_step", 1 * MS, 50 * MS),
             ("ht.train_step.place", 1 * MS, 8 * MS),
             ("ht.train_step.dispatch", 9 * MS, 41 * MS),
             ("pb.wait", 58 * MS, 24 * MS)]
    p = planes([ops], spans)
    r = ts.reduce_planes(p)
    # gaps: [0,10) mostly under place; [30,60) under dispatch for 20 of its
    # 30 ms; [80,100) under wait for 2 ms only, so the window's own
    assert r["idle_gaps"] == pytest.approx({
        "ht.train_step.place": 0.010, "ht.train_step.dispatch": 0.030,
        "pb.window": 0.020})
    assert sum(r["idle_gaps"].values()) == pytest.approx(
        r["window_s"] - r["device0_busy_s"])
    # a gap no span covers by half, window gone too, is nobody's
    assert ts.gap_owner((0.0, 10.0), [("ht.x", 0.0, 4.0, {})]) == "none"
    assert ts.gap_owner((0.0, 10.0), [("ht.x", 0.0, 5.0, {}),
                                      ("ht.long", 0.0, 50.0, {})]) == "ht.x"


def test_collectives_exposed_against_hidden():
    ops0 = [op("fusion.1", 0.0, 40 * MS),
            op("all-reduce.2", 40 * MS, 10 * MS),                # exposed
            op("all-gather-start.3", 50 * MS, 1 * MS),
            op("fusion.4", 51 * MS, 30 * MS),                    # hides it
            op("all-gather-done.3", 81 * MS, 1 * MS)]
    async0 = [op("all-gather-start.3", 50 * MS, 32 * MS)]
    ops1 = [op("fusion.1", 0.0, 90 * MS)]
    r = ts.reduce_planes(planes([ops0, ops1], [("pb.window", 0.0, 100 * MS)],
                                n_dev=2, async_ops={0: async0}), chips=2)
    c0 = r["collectives"]["0"]
    assert c0["total_s"] == pytest.approx(0.042)     # [40,50) and [50,82)
    assert c0["hidden_s"] == pytest.approx(0.030)    # under fusion.4
    assert c0["exposed_s"] == pytest.approx(0.012)
    assert r["collectives"]["1"] == {"total_s": 0.0, "hidden_s": 0.0,
                                     "exposed_s": 0.0}


def test_no_window_or_too_few_devices_is_an_error():
    with pytest.raises(ValueError, match="pb.window"):
        ts.reduce_planes(planes([[op("f", 0.0, 1.0)]], []))
    with pytest.raises(ValueError, match="device planes"):
        ts.reduce_planes(planes([[op("f", 0.0, MS)]],
                                [("pb.window", 0.0, 100 * MS)]), chips=4)


def test_wire_reader_rejects_what_is_no_xplane(tmp_path):
    bad = tmp_path / "bad.pb"
    bad.write_bytes(b"\x0b\x00")                 # wire type 3: a group
    with pytest.raises(ValueError, match="wire type"):
        ts.load_planes(str(bad))


@pytest.mark.parametrize("name,chips", [("recorded_kmeans_1chip", 1),
                                        ("recorded_kmeans_4chip", 4)])
def test_recorded_kmeans_traces_by_name(name, chips):
    path = os.path.join(HERE, name + ".xplane.pb")
    mine = ts.load_planes(path)
    # the hand reader sees what `jax.profiler.ProfileData` sees: planes,
    # lines, names, whole nanoseconds
    from jax.profiler import ProfileData
    for plane in ProfileData.from_file(path).planes:
        theirs = {}
        for line in plane.lines:
            theirs.setdefault(line.name, []).extend(
                (ts.op_name(ev.name), float(ev.start_ns), float(ev.duration_ns))
                for ev in line.events)
        assert sorted(theirs) == sorted(mine[plane.name])
        for ln, evs in theirs.items():
            assert [e[:3] for e in mine[plane.name][ln]] == evs, (plane.name, ln)
    r = ts.reduce_planes(mine, chips)
    old = {1: {"window_s": 2.116613435, "device0_busy_s": 2.003013582,
               "device0_collective_s": 0.0},
           4: {"window_s": 2.147488868, "device0_busy_s": 2.004102558,
               "device0_collective_s": 7.9638e-05}}[chips]
    assert r["window_s"] == pytest.approx(old["window_s"], rel=1e-9)
    assert abs(r["device0_busy_s"] - old["device0_busy_s"]) < 1e-9
    rows = r["by_scope"]
    # PR 28's programs carry no scope: three programs, all unscoped
    assert sorted(rows) == [("jit__assign", "unscoped", "fwd"),
                            ("jit_copy", "unscoped", "fwd"),
                            ("jit_device_step", "unscoped", "fwd")]
    assert abs(sum(v["self_s"] for v in rows.values())
               - old["device0_busy_s"]) < 1e-9
    assert r["programs"]["jit_device_step"]["runs"] == 60     # 2 fits of 30
    assert r["programs"]["jit__assign"]["runs"] == 2
    assert list(r["idle_gaps"]) == ["pb.fit"]
    assert abs(sum(r["idle_gaps"].values())
               - (old["window_s"] - old["device0_busy_s"])) < 1e-9
    # the metadata ProfileData does not hand out: tf_op, XLA's flops and bytes
    gemm = next(e for e in mine["/device:TPU:0"]["XLA Ops"]
                if e[0] == "fusion" and e[3].get("tf_op", "").startswith(
                    "jit(device_step)"))
    assert gemm[3]["tf_op"] == ("jit(device_step)/dot_general:" if chips == 1
                                else "jit(device_step)/shard_map/dot_general:")
    assert gemm[3]["hlo_category"] == "convolution fusion"
    assert gemm[3]["flops"] > 1e10 and gemm[3]["bytes_accessed"] > 1e9
    c = r["collectives"]["0"]
    if chips == 1:
        assert c == {"total_s": 0.0, "hidden_s": 0.0, "exposed_s": 0.0}
    else:
        assert set(r["collectives"]) == {"0", "1", "2", "3"}
        assert c["total_s"] == pytest.approx(old["device0_collective_s"])
        assert c["exposed_s"] == pytest.approx(7.9638e-05, rel=1e-4)
        assert c["hidden_s"] == 0.0


def test_recorded_train_step_by_scope_is_pinned():
    """My chip run, PR 29: ONE train step of a two-layer width-256
    `TransformerLM` (2 heads of 128, ff 1024, vocab 512, B=2 x S=512, bf16,
    adam) on the TPU v5e, `pb.window` around `pb.step` + `pb.wait`. The file
    is the profiler's own bytes with whole planes and whole host lines dropped
    (device 0 and the host line that holds the `pb.`/`ht.` spans are kept;
    2.2 MB raw), nothing inside them rewritten. At this size the step is
    overheads: 0.65 ms of device time in a 2.76 ms window."""
    path = os.path.join(HERE, "recorded_train_small.xplane.pb")
    assert os.path.getsize(path) <= 512 * 1024
    planes_ = ts.load_planes(path)
    r = ts.reduce_planes(planes_)
    assert r["window_s"] == pytest.approx(0.002761929)
    assert r["busy_s"] == r["device0_busy_s"] == pytest.approx(
        0.000645116, abs=1e-9)
    # the module line: one program, named by its family
    assert r["programs"] == {"jit_train_step": {
        "runs": 1, "device_s": pytest.approx(0.000650705, abs=1e-9)}}
    assert {p for p, _s, _d in r["by_scope"]} == {"jit_train_step"}
    # self time by (scope, direction), in microseconds
    got = {(s, d): v["self_s"] * 1e6 for (_p, s, d), v in r["by_scope"].items()}
    assert got == pytest.approx({
        ("attn.core", "fwd"): 158.194, ("unscoped", "fwd"): 142.993,
        ("attn.core", "bwd"): 102.143, ("pipeline", "fwd"): 60.615,
        ("mlp", "fwd"): 38.042, ("mlp", "bwd"): 32.201,
        ("optimizer", "fwd"): 21.255, ("attn.qkv", "bwd"): 18.571,
        ("attn.qkv", "fwd"): 17.549, ("loss", "fwd"): 14.774,
        ("embed", "bwd"): 11.489, ("attn.proj", "fwd"): 5.659,
        ("embed", "fwd"): 5.128, ("head", "bwd"): 4.808,
        ("attn.proj", "bwd"): 3.660, ("cast", "bwd"): 3.312,
        ("head", "fwd"): 1.906, ("pipeline", "bwd"): 1.797,
        ("loss", "bwd"): 0.579, ("cast", "fwd"): 0.441}, abs=1e-3)
    assert sum(got.values()) == pytest.approx(645.116, abs=1e-3)
    shares = ts.scope_shares(r)
    assert list(shares)[:3] == ["attn.core", "unscoped", "mlp"]
    assert shares["attn.core"] == pytest.approx(0.403551, abs=1e-6)
    assert 1.0 - shares["unscoped"] == pytest.approx(0.778345, abs=1e-6)
    # the three flash kernels are three names, all under attn.core
    kernels = {}
    for (_p, scope, d), v in r["by_scope"].items():
        for name in v["ops"]:
            if name.startswith("flash_"):
                kernels.setdefault(name.split(".")[0], set()).add((scope, d))
    assert kernels == {"flash_fwd": {("attn.core", "fwd")},
                       "flash_bwd_dkv": {("attn.core", "bwd")},
                       "flash_bwd_dq": {("attn.core", "bwd")}}
    # XLA's own count beside the time: the MLP's GEMMs, not the kernels'
    mlp = r["by_scope"][("jit_train_step", "mlp", "fwd")]
    assert mlp["xla_flops"] == 3310631936
    assert mlp["xla_bytes_accessed"] == 51981828
    assert r["by_scope"][("jit_train_step", "attn.core", "bwd")]["xla_flops"] \
        == 36864                    # a Mosaic kernel's work is not counted
    # idle: the first 1.54 ms are the program's own `train_step` (placement
    # and dispatch, neither half of it), then the wait for the device
    assert r["idle_gaps"] == pytest.approx({
        "ht.train_step": 0.001544692, "pb.wait": 0.000572121}, abs=1e-9)
    # the program's span lies inside the benchmark's on the host plane
    spans = {e[0]: e for e in ts.host_spans(planes_)}
    step, inner = spans["pb.step"], spans["ht.train_step"]
    assert step[1] <= inner[1] and inner[1] + inner[2] <= step[1] + step[2]
    assert {"ht.train_step.place", "ht.train_step.dispatch"} <= set(spans)
    assert r["collectives"]["0"]["total_s"] == 0.0
