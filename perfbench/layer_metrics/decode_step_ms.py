"""Serving: the trace's own window (`pb.window`) over the decode steps the
engine ran in it (prefills, fetches and the host loop between steps are all
inside)."""


def read(run):
    steps = run.counter_delta("decode_steps", traced=True)
    if not steps or not run.trace:
        return None
    return 1e3 * run.trace["window_s"] / steps
