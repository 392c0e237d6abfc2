"""Entry points: the program's OWN host span around a train step
(`train_step` of `heat_tpu.utils.profiling`: the optimizer state's placement
and the jitted call's dispatch, up to its return), mean over the steps that
lie inside the traced window, in ms. `dispatch_ms.train` times the same call
from outside. The ring's clock is `time.perf_counter()`, the probe's. A
program without spans (a parent commit) or with none in the window reads
nothing, and the metric is left out."""


def read(run):
    tr = run.probe.traced
    if not tr or "t1" not in tr:
        return None
    try:
        from heat_tpu.utils import profiling

        records = profiling.spans()
    except (ImportError, AttributeError):
        return None
    took = [r.t1 - r.t0 for r in records
            if r.name == "train_step" and tr["t0"] <= r.t0 and r.t1 <= tr["t1"]]
    if not took:
        return None
    return 1e3 * sum(took) / len(took)
