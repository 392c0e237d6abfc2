"""Reshard planner and packed collectives: device 0's time in operations whose
HLO name is a collective (`all-reduce`, `all-gather`, `reduce-scatter`,
`all-to-all`, `collective-permute`) over the traced window. A cell on one chip
has no collective and reports nothing."""


def read(run):
    if not run.trace or run.chips < 2:
        return None
    return (100.0 * run.trace["collectives"]["0"]["total_s"]
            / run.trace["window_s"])
