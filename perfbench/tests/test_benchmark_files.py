"""`BENCHMARK.json` against the contract's limits that can be checked here,
and the data-driven loading: every file it names exists, every driver and
every per-layer reader loads BY NAME, so a later PR adds files and entries
and edits nothing."""

import json
import os
import re

import pytest

from perfbench import run

ROOT = run.ROOT
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


BENCH_FILES = ["BENCHMARK.json", "perfbench/HELD.json"]


@pytest.fixture(scope="module", params=BENCH_FILES)
def bench(request):
    """The benchmark, and the file of cells held back: the same form, the
    same checks (`_doc` is the held file's own key)."""
    held = run.load_json(os.path.join(ROOT, request.param))
    held.pop("_doc", None)
    return held


def test_keys_and_names(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert all(os.path.getsize(os.path.join(ROOT, f)) < 64 * 1024
               for f in BENCH_FILES)
    assert 1 <= bench["run_seconds"] <= 51
    names = []
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for e in bench[group]:
            assert NAME.match(e["name"]), e["name"]
            names.append((group in ("end_to_end", "per_layer"), e["name"]))
    assert len(names) == len(set(names))
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
    for m in bench["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    assert any(m["name"] == "setup_s" and "workloads" not in m
               for m in bench["end_to_end"])


def test_cells_configs_and_their_files(bench):
    configs = {c["name"]: c for c in bench["configs"]}
    cells = bench["workloads"]
    assert {w["config"] for w in cells} == set(configs)
    assert len({(w["config"], w["traffic"]) for w in cells}) == len(cells)
    four = [w for w in cells if w["chips"] == 4]
    assert len(four) <= max(1, len(cells) // 4)
    for w in cells:
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
        assert os.path.isfile(os.path.join(
            run.HERE, "traffic", w["traffic"] + ".json"))
        assert os.path.isfile(os.path.join(
            run.HERE, "limits", w["name"] + ".json"))
    files = [c["file"] for c in configs.values()]
    assert len(files) == len(set(files))
    for c in configs.values():
        assert c["file"].startswith("perfbench/")
        held = run.load_json(os.path.join(ROOT, c["file"]))
        assert held["reduced"] == c["reduced"] and len(c["reduced"]) <= 16
        for key in c["reduced"]:
            assert not re.search(r"(_dim|_rank|hidden_size|intermediate_size"
                                 r"|head|features)$", key), key


def test_every_cell_reports_setup_another_metric_and_a_layer_metric(bench):
    cell_names = {w["name"] for w in bench["workloads"]}
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    for w in cell_names:
        assert len(run.metrics_of(bench, "end_to_end", w)) >= 2
        assert len(run.metrics_of(bench, "per_layer", w)) >= 1
    for m in bench["per_layer"]:
        assert m["moves"] in e2e and m["moves"] != "setup_s"
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        moved = e2e[m["moves"]].get("workloads", cell_names)
        assert set(m.get("workloads", moved)) <= set(moved)
        assert set(m.get("workloads", [])) <= cell_names


def test_drivers_and_readers_load_by_name(bench):
    for w in bench["workloads"]:
        mix = run.load_json(os.path.join(
            run.HERE, "traffic", w["traffic"] + ".json"))
        driver = run.load_by_name("drivers", mix["driver"])
        for method in ("setup", "window", "counters", "sync", "release",
                       "check", "close", "readings", "control"):
            assert callable(getattr(driver.Cell, method)), (mix["driver"],
                                                            method)
    for m in bench["per_layer"]:
        assert callable(run.load_by_name("layer_metrics", m["name"]).read)


def test_a_new_reader_is_a_new_file_and_nothing_else(tmp_path, monkeypatch):
    """What a later PR does: drop a file, name it in BENCHMARK.json."""
    (tmp_path / "layer_metrics").mkdir()
    (tmp_path / "layer_metrics" / "new.metric-x.py").write_text(
        "def read(run):\n    return 42.0\n")
    monkeypatch.setattr(run, "HERE", str(tmp_path))
    assert run.load_by_name("layer_metrics", "new.metric-x").read(None) == 42.0
    with pytest.raises(SystemExit):
        run.load_by_name("layer_metrics", "not-there")


def test_peaks_have_a_source_and_no_default():
    table = run.load_json(os.path.join(run.HERE, "peaks.json"))
    rows = {k: v for k, v in table.items() if isinstance(v, dict)}
    assert "TPU v5 lite" in rows and "cpu" not in rows
    for row in rows.values():
        assert row["source"] and row["bf16_flops_per_s"] > 0 \
            and row["hbm_bytes_per_s"] > 0
    assert rows["TPU v5 lite"]["bf16_flops_per_s"] == 197e12
    assert rows["TPU v5 lite"]["hbm_bytes_per_s"] == 819e9


def test_limits_lie_between_their_two_readings(bench):
    for w in bench["workloads"]:
        lim = run.load_json(os.path.join(run.HERE, "limits",
                                         w["name"] + ".json"))
        for name, e in lim.items():
            if name == "_doc":
                continue
            if e.get("exact"):
                assert e["limit"] == 0.0
            else:
                assert e["lower"] < e["limit"] < e["upper"], (w["name"], name)
                assert e["upper"] >= 3 * e["lower"]
