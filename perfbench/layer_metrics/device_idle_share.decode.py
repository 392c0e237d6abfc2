"""Device: 1 - (union of the devices' operation intervals) / traced window,
mean over the chips used, in the serving cells."""

from perfbench.trace_scopes import idle_share_percent


def read(run):
    return idle_share_percent(run.trace) if run.trace else None
