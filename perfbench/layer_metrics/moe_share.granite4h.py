"""Model step: device 0's self time in the expert layers (router, grouped
products, shared expert, combine; the step and the prefills:
`work_granite4h.moe_seconds`, the `moe` scope and the unscoped grouped
products by name) over its busy time in the traced window: what the expert
layers take of the chip. Read only where the program counts its routed
pairs."""


def read(run):
    if not run.trace or not run.trace["device0_busy_s"] \
            or run.counter_delta("moe_pairs_total", traced=True) is None:
        return None
    from perfbench import work_granite4h as w

    took = w.moe_seconds(run.trace)
    if not took:
        return None
    return 100.0 * took / run.trace["device0_busy_s"]
