"""Model step: device time the decode step spends casting the weights to the
compute dtype, over device 0's busy time in the traced window. XLA merges the
per-layer casts and drops their `cast` scope, so this counts, inside
`jit_decode_step`, what is left under `cast` plus the unscoped operations
named `convert...` (`convert_bitcast_fusion`, `convert.<n>`: PERF.md section 5).
A step that casts nothing has neither, and the metric falls silent."""

from perfbench.trace_scopes import UNSCOPED, scope_seconds

PROGRAM = "jit_decode_step"


def read(run):
    if not run.trace or not run.trace["device0_busy_s"]:
        return None
    took = (scope_seconds(run.trace, "cast", PROGRAM)
            + scope_seconds(run.trace, UNSCOPED, PROGRAM, "convert"))
    if not took:
        return None
    return 100.0 * took / run.trace["device0_busy_s"]
