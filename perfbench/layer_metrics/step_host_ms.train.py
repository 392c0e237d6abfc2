"""Entry points: the program's OWN host span around a train step
(`train_step` of `heat_tpu.utils.profiling`: the optimizer state's placement
and the jitted call's dispatch, up to its return), mean over the steps that
lie inside the traced window, in ms. `dispatch_ms.train` times the same call
from outside. The ring's clock is `time.perf_counter()`, the probe's. A
program without spans (a parent commit) or with none in the window reads
nothing, and the metric is left out."""


def read(run):
    took = [r.t1 - r.t0 for r in run.spans_named("train_step")]
    if not took:
        return None
    return 1e3 * sum(took) / len(took)
