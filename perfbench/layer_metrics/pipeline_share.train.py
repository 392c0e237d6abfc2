"""Model step: device 0's self time under the program's `pipeline` scope (what
`pipeline_apply`'s schedule costs AROUND the layers: the scan's stacking and
copies, no layer mathematics) over device 0's busy time in the traced steps."""

from perfbench.trace_scopes import scope_seconds


def read(run):
    if not run.trace or not run.trace["device0_busy_s"]:
        return None
    took = scope_seconds(run.trace, "pipeline")
    if not took:
        return None
    return 100.0 * took / run.trace["device0_busy_s"]
