"""Fusion engine: `perfbench`'s own host span around each `step(...)` call up
to its return (the enqueue), mean over the window, in ms."""


def read(run):
    spans = run.probe.spans.get("step")
    if not spans:
        return None
    return 1e3 * sum(b - a for a, b in spans) / len(spans)
