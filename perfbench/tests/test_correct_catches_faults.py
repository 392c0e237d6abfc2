"""`correct` has to come out FALSE when the timed path is broken underneath.

Each test skips the harness's look for a chip (`--rehearse`: tiny sizes, the
CPU) and drives the REST of a run through `perfbench.run.main`, with one
fault planted in the program: a step that returns its state unchanged; half
of the batch left out, the mean taken over the rest; the exchange between
chips left out; a token or an answer altered where it is produced; for the
distance matrix a row block left out and operands rounded to bfloat16. The
limits are the cells' own (`perfbench/limits/`).

The controls are kept here too, at a size a test can hold: the nearest lower
precision in the program's place has to fail one of the cell's numbers.
"""

import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from perfbench import run

KM1, KM4 = "kmeans-w25m.fit30", "kmeans-w25m-x4.fit30"
TRAIN = "pythia-1.4b-d8.train-s2048"
DECODE = "pythia-1.4b-d8.decode-conv-closed48"
CDIST = "heat-cdist-40k.cdist"
HELD = "perfbench/HELD.json"     # cells built and held back (PERF.md s. 7)


def bench_of(cell):
    return HELD if cell in (KM1, KM4) else "BENCHMARK.json"


def last_line(capsys, cell, seed=2 ** 31 + 11, seconds="0.5", trace=0):
    capsys.readouterr()
    assert run.main(["--workload", cell, "--rehearse", "--seed", str(seed),
                     "--seconds", seconds, "--bench", bench_of(cell),
                     "--trace", str(trace)]) == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def failed(line):
    return sorted(n for n, c in line["checks"].items()
                  if not c["value"] <= c["limit"])


@pytest.fixture
def kmeans_module(monkeypatch):
    from heat_tpu.cluster import kmeans as km
    from heat_tpu.core import fusion

    # programs compiled before the fault was planted must not be reused
    monkeypatch.setattr(km, "_STEP_CACHE", {})
    fusion.reset()
    yield km
    fusion.reset()


@pytest.fixture
def distance_module():
    from heat_tpu.spatial import distance

    distance._RING_CACHE.clear()  # programs built before the fault was planted
    yield distance
    distance._RING_CACHE.clear()


@pytest.mark.parametrize("cell", [KM1, KM4, TRAIN, DECODE, CDIST])
def test_sound_program_is_correct(capsys, cell):
    line = last_line(capsys, cell)
    assert line["correct"] is True and failed(line) == []
    assert line["rehearsal"] is True and line["device"]["platform"] == "cpu"
    assert line["attempted"] > 0 and line["failed"] == 0
    assert list(line)[-1] == "checks"
    bench = run.load_json(os.path.join(run.ROOT, bench_of(cell)))
    assert set(line["metrics"]) == {
        m["name"] for m in run.metrics_of(bench, "end_to_end", cell)}


@pytest.mark.parametrize("cell,silent", [
    # what a rehearsal's trace cannot feed stays silent: there is no device
    # plane, so nothing is read by the program's scopes
    (DECODE, {"cache_move_share.decode", "weights_cast_share.decode"}),
    (CDIST, set())])
def test_traced_rehearsal_of_a_new_cell_reports_its_layer_metrics(
        capsys, cell, silent):
    line = last_line(capsys, cell, seconds="1.5", trace=1)
    bench = run.load_json(os.path.join(run.ROOT, "BENCHMARK.json"))
    listed = {m["name"] for m in run.metrics_of(bench, "per_layer", cell)}
    assert set(line["metrics"]) == listed - silent
    assert line["correct"] is True and line["device"]["busy_s"] > 0
    assert len(line["breakdown"]["device_ops"]) <= 10
    assert all(m["value"] <= 100.0 for m in line["metrics"].values()
               if m["unit"] == "%")


def test_cdist_row_block_left_out(capsys, distance_module, monkeypatch):
    sound = distance_module._euclidean_tile

    def holed(x, y, expand):
        return sound(x, y, expand).at[64:128].set(0.0)

    monkeypatch.setattr(distance_module, "_euclidean_tile", holed)
    line = last_line(capsys, CDIST)
    assert line["correct"] is False and failed(line) == ["dist_err"]
    assert line["checks"]["dist_err"]["value"] > 0.5    # whole distances gone


def test_cdist_operands_rounded_to_bfloat16(capsys, distance_module,
                                            monkeypatch):
    sound = distance_module._euclidean_tile

    def rounded(x, y, expand):
        return sound(x.astype(jnp.bfloat16), y.astype(jnp.bfloat16),
                     expand).astype(x.dtype)

    monkeypatch.setattr(distance_module, "_euclidean_tile", rounded)
    line = last_line(capsys, CDIST)
    assert line["correct"] is False and failed(line) == ["dist_err"]


def test_cdist_answer_altered_where_it_is_produced(capsys, distance_module,
                                                   monkeypatch):
    sound = distance_module._euclidean_tile

    def altered(x, y, expand):
        return sound(x, y, expand).at[3, 5].add(0.1)      # ONE entry of n x n

    monkeypatch.setattr(distance_module, "_euclidean_tile", altered)
    line = last_line(capsys, CDIST)
    assert line["correct"] is False and failed(line) == ["dist_err"]


def test_cdist_result_of_another_shape_is_no_answer(capsys, distance_module,
                                                    monkeypatch):
    sound = distance_module.cdist

    def fewer(x, y=None, quadratic_expansion=False):
        return sound(x, y, quadratic_expansion)[:-1]

    import heat_tpu as ht

    monkeypatch.setattr(ht.spatial, "cdist", fewer)
    line = last_line(capsys, CDIST)
    assert line["correct"] is False and failed(line) == ["dist_err"]


def test_kmeans_step_returns_its_state_unchanged(capsys, kmeans_module):
    km = kmeans_module
    km._finish_update = lambda sums, counts, c: (c, jnp.zeros((), sums.dtype))
    try:
        line = last_line(capsys, KM1)
    finally:
        import importlib
        importlib.reload(km)
    assert line["correct"] is False and "centroid_err" in failed(line)


def test_kmeans_half_of_the_rows_left_out(capsys, kmeans_module, monkeypatch):
    km = kmeans_module
    whole = km._lloyd_partial

    def half(xp, centroids, valid, k, jdt, acc):
        keep = jax.lax.broadcasted_iota(jnp.int32, valid.shape, 0) % 2 == 0
        return whole(xp, centroids, valid & keep, k, jdt, acc)

    monkeypatch.setattr(km, "_lloyd_partial", half)
    # ... and the assignment pass, where labels and inertia are produced
    assign = km._assign_fn

    def half_assign(*a):
        fn = assign(*a)
        return lambda xp, c: (lambda lab, i: (lab, i / 2))(*fn(xp, c))

    monkeypatch.setattr(km, "_assign_fn", half_assign)
    line = last_line(capsys, KM1)
    assert line["correct"] is False and "inertia_rel" in failed(line)


def test_kmeans_exchange_between_chips_left_out(capsys, kmeans_module,
                                                monkeypatch):
    from heat_tpu.core import fusion

    monkeypatch.setattr(fusion, "packed_psum",
                        lambda values, axes, **_kw: list(values))
    line = last_line(capsys, KM4)
    assert line["device"]["count"] == 4
    assert line["correct"] is False and "centroid_err" in failed(line)


def test_kmeans_answer_altered_where_it_is_produced(capsys, kmeans_module,
                                                    monkeypatch):
    km = kmeans_module
    sound = km._finish_update

    def altered(sums, counts, centroids):
        new, shift = sound(sums, counts, centroids)
        return new.at[0, 0].add(0.01), shift

    monkeypatch.setattr(km, "_finish_update", altered)
    line = last_line(capsys, KM1)
    assert line["correct"] is False and "centroid_err" in failed(line)


def test_kmeans_fewer_iterations_than_the_configuration_states(
        capsys, monkeypatch):
    from heat_tpu.cluster import _kcluster

    monkeypatch.setattr(_kcluster._KCluster, "_converged",
                        lambda self, shift: True)
    line = last_line(capsys, KM1)
    assert line["correct"] is False and "iters_missing" in failed(line)


def _patch_step(monkeypatch, wrap):
    from heat_tpu.nn.transformer import TransformerLM

    make = TransformerLM.make_train_step

    def patched(self, tx):
        return wrap(make(self, tx))

    monkeypatch.setattr(TransformerLM, "make_train_step", patched)


def test_train_step_returns_its_state_unchanged(capsys, monkeypatch):
    def wrap(step):
        def same(params, opt, toks):
            keep = jax.tree.map(jnp.copy, (params, opt))
            _p, _o, loss = step(params, opt, toks)
            return keep[0], keep[1], loss
        return same

    _patch_step(monkeypatch, wrap)
    line = last_line(capsys, TRAIN)
    assert line["correct"] is False
    assert line["checks"]["change_gap"]["value"] == pytest.approx(1.0)


def test_train_half_of_the_batch_left_out(capsys, monkeypatch):
    def wrap(step):
        def half(params, opt, toks):
            n = toks.shape[0] // 2
            return step(params, opt, jnp.concatenate([toks[:n], toks[:n]]))
        return half

    _patch_step(monkeypatch, wrap)
    line = last_line(capsys, TRAIN)
    assert line["correct"] is False and "grad_norm_gap" in failed(line)


def test_decode_token_altered_where_it_is_produced(capsys, monkeypatch):
    from heat_tpu.serve.decode import DecodeEngine

    fetch = DecodeEngine._fetch

    def altered(arr):
        return (np.asarray(fetch(arr)) + 1) % 128       # the rehearsal's vocab

    monkeypatch.setattr(DecodeEngine, "_fetch", staticmethod(altered))
    line = last_line(capsys, DECODE, seconds="1.0")
    assert line["correct"] is False and "token_gap" in failed(line)


def test_decode_answer_cut_short(capsys, monkeypatch):
    from heat_tpu.serve.decode import DecodeEngine

    finish = DecodeEngine._finish

    def short(self, slot, req):
        req.generated = req.generated[:-1] or req.generated
        return finish(self, slot, req)

    monkeypatch.setattr(DecodeEngine, "_finish", short)
    line = last_line(capsys, DECODE, seconds="1.0")
    assert line["correct"] is False and "wrong_answers" in failed(line)


# -- the controls, at a size a test can hold --------------------------------
def _cell(workload, seed=2 ** 31 + 5):
    _bench, ctx, driver, _c = run.prepare(workload, seed, True,
                                          bench_of(workload))
    cell = driver.Cell(ctx)
    cell.setup()
    return cell, ctx.limits


def test_kmeans_control_bfloat16_storage_fails_centroid_err():
    cell, limits = _cell(KM1)
    got = cell.readings()
    ctl = cell.control()
    assert got["centroid_err"] <= limits["centroid_err"]
    assert ctl["centroid_err"] > limits["centroid_err"]
    assert ctl["centroid_err"] > 3 * got["centroid_err"]
    assert run.judge(cell.check(got)) and not run.judge(cell.check(ctl))


def test_kmeans_common_shrink_of_the_size_read_on_the_chip_is_not_correct(
        capsys, kmeans_module, monkeypatch):
    """Centroids that come out 0.147% small, every coordinate alike: what
    the program does at 25M rows a chip (PERF.md section 7.1). `correct` has
    to say so."""
    real = kmeans_module._finish_update

    def shrunk(sums, counts, centroids):
        return real(sums * (1.0 - 1.47e-3), counts, centroids)

    monkeypatch.setattr(kmeans_module, "_finish_update", shrunk)
    line = last_line(capsys, KM1)
    assert line["correct"] is False and "centroid_err" in failed(line)


def test_float64_witness_sides_with_the_plain_reference():
    """`tools/witness_lloyd64.py`: float64 NumPy Lloyd against the float32
    reference on the driver's own blobs, at a size a test can hold."""
    from perfbench.tools import witness_lloyd64 as w

    cell, _limits = _cell(KM1)
    c64, i64, counts = w.lloyd64(np.asarray(cell.xj), cell.init, cell.iters,
                                 block=3000)
    cell.readings()
    c_ref, i_ref, n_ref = cell._ref
    got = w.distance(np.asarray(c_ref, np.float64), c64)
    assert got["centroid_err"] < 1e-5 and abs(got["centroid_shrink"]) < 1e-6
    assert abs(float(i_ref) - i64) / i64 < 1e-5
    assert np.array_equal(np.asarray(n_ref), counts)


def test_train_control_float8_fails_a_number():
    cell, limits = _cell(TRAIN)
    got = cell.readings()
    ctl = cell.control()
    assert all(got[n] <= limits[n] for n in limits)
    assert any(ctl[n] > limits[n] for n in limits)
    assert ctl["grad_norm_gap"] > 3 * got["grad_norm_gap"]
    assert run.judge(cell.check(got)) and not run.judge(cell.check(ctl))


def test_decode_control_float8_reads_a_gap_where_the_program_reads_none():
    cell, _limits = _cell(DECODE)
    got = cell.readings()
    ctl = cell.control()
    assert got["tokens_judged"] > 0 and got["wrong_answers"] == 0
    assert ctl["token_gap"] > 3 * got["token_gap"] and ctl["token_gap"] > 0.01


def test_cdist_controls_fail_dist_err_and_the_program_does_not():
    """The reference's expansion with its product at `high` (three bfloat16
    passes) and with bfloat16 operands, in the answer's place."""
    cell, limits = _cell(CDIST)
    got = cell.readings()
    ctl = cell.control()
    assert got["dist_err"] <= limits["dist_err"]
    assert ctl["dist_err.high"] > limits["dist_err"]
    assert ctl["dist_err.bf16"] > 3 * ctl["dist_err.high"]
    assert ctl["dist_err"] == ctl["dist_err.high"] > 3 * got["dist_err"]
    # the control in the program's place, through the run's own judgement
    assert run.judge(cell.check(got)) and not run.judge(cell.check(ctl))


@pytest.mark.parametrize("workload", [CDIST, DECODE])
def test_readings_tool_judges_the_control_not_correct(workload, capsys):
    """`tools/readings.py` puts the control's numbers through the driver's
    `check()` and the harness's `judge()`: the line a chip call prints."""
    from perfbench.tools import readings

    capsys.readouterr()
    readings.main(["--workload", workload, "--seeds", str(2 ** 31 + 21),
                   "--control", "1", "--rehearse"])
    lines = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()
             if ln.startswith("{")]
    by_kind = {ln["kind"]: ln for ln in lines}
    assert by_kind["program"]["correct"] is True
    assert by_kind["control"]["correct"] is False
    assert set(by_kind["control"]["checks"]) == set(by_kind["program"]["checks"])


def test_a_share_over_100_percent_fails_the_run(capsys, monkeypatch):
    real = run.load_by_name

    def fake(kind, name):
        mod = real(kind, name)
        if (kind, name) == ("layer_metrics", "lloyd_roofline"):
            mod.read = lambda _run: 150.0
        return mod

    monkeypatch.setattr(run, "load_by_name", fake)
    with pytest.raises(SystemExit) as exc:
        run.main(["--workload", KM1, "--rehearse", "--seconds", "0.5",
                  "--trace", "1", "--bench", HELD])
    assert exc.value.code == 3
    out = capsys.readouterr().out
    assert '"correct"' not in out


def test_no_accelerator_is_an_exit_and_no_result(capsys):
    with pytest.raises(SystemExit) as exc:
        run.main(["--workload", TRAIN, "--seconds", "0.5"])
    assert exc.value.code == 2
    assert '"correct"' not in capsys.readouterr().out
