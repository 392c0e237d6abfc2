"""Serving: how near the decode step is to the bytes it has to move, for the
`granite-4.0-h-small-d10e36` configuration. Bytes the traced steps NEED
(`work_granite4h.step_bytes`: the parameters once a step, the held experts
among them, and per live slot the nine recurrent states read and written and
the one lane to the mix's mean attended context; live slots from the engine's
own counts) over the chip's HBM bandwidth, over device 0's time in the step
program (`jit_decode_step` in `run.trace["programs"]`). Decode is bound by
bytes: this is the least time the steps could take over the time they took."""

PROGRAM = "jit_decode_step"


def read(run):
    steps = run.counter_delta("decode_steps", traced=True)
    prog = (run.trace or {}).get("programs", {}).get(PROGRAM)
    if not steps or not prog or not prog["device_s"] \
            or run.counter_delta("moe_pairs_total", traced=True) is None:
        return None
    from perfbench import traffic, work_granite4h as w

    live = (run.counter_delta("tokens_out", traced=True)
            - run.counter_delta("prefills", traced=True)) / steps
    out_ctx, _p = w.mean_contexts(traffic.request_sizes(run.traffic))
    least = (steps * w.step_bytes(run.config, live, out_ctx)
             / run.peaks["hbm_bytes_per_s"])
    return 100.0 * least / prog["device_s"]
