"""Serving: device 0's self time under the program's `cache.read` and
`cache.write` scopes (a layer's lane copied out of the arena and written back,
in the step and in the prefills) over device 0's busy time in the traced
window."""

from perfbench.trace_scopes import scope_seconds


def read(run):
    if not run.trace or not run.trace["device0_busy_s"]:
        return None
    took = (scope_seconds(run.trace, "cache.read")
            + scope_seconds(run.trace, "cache.write"))
    if not took:
        return None
    return 100.0 * took / run.trace["device0_busy_s"]
