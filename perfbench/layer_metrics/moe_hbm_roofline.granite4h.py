"""Kernels: the expert layer's share of its roofline in the decode step,
whatever implements it. Bytes of the held experts, the shared experts and the
routers of all layers (`work_granite4h.moe_bytes`: a step reads each once; at
the cell's occupancy every held expert is hit every step) times the traced
steps, over the chip's HBM bandwidth, over device 0's self time in the expert
layers of `jit_decode_step` (`work_granite4h.moe_seconds`: the program's `moe`
scope and the grouped products, which the compiler leaves unscoped, by name).
Falls silent where nothing carries the names or the program counts no routed
pairs."""

PROGRAM = "jit_decode_step"


def read(run):
    steps = run.counter_delta("decode_steps", traced=True)
    if not run.trace or not steps \
            or run.counter_delta("moe_pairs_total", traced=True) is None:
        return None
    from perfbench import work_granite4h as w

    took = w.moe_seconds(run.trace, PROGRAM)
    if not took:
        return None
    least = steps * w.moe_bytes(run.config) / run.peaks["hbm_bytes_per_s"]
    return 100.0 * least / took
