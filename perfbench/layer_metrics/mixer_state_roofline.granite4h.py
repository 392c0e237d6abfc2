"""Kernels: the mixers' share of their roofline in the decode step. Bytes the
live slots' states cost the traced steps (`work_granite4h.
state_bytes_per_step`: nine float32 recurrent states and their convolution
tails read and written, the attention layer's lane rows read to the mix's
mean attended context) over the chip's HBM bandwidth, over device 0's self
time under the program's `attn.core` scope in `jit_decode_step` (the
recurrence's update and the one query row's attention). Falls silent where
nothing carries the name or the program counts no routed pairs (this
configuration's mark)."""

from perfbench.trace_scopes import scope_seconds

PROGRAM = "jit_decode_step"


def read(run):
    steps = run.counter_delta("decode_steps", traced=True)
    if not run.trace or not steps \
            or run.counter_delta("moe_pairs_total", traced=True) is None:
        return None
    took = scope_seconds(run.trace, "attn.core", program=PROGRAM)
    if not took:
        return None
    from perfbench import traffic, work_granite4h as w

    live_steps = (run.counter_delta("tokens_out", traced=True)
                  - run.counter_delta("prefills", traced=True))
    out_ctx, _p = w.mean_contexts(traffic.request_sizes(run.traffic))
    least = (live_steps * w.state_bytes_per_step(run.config, out_ctx)
             / run.peaks["hbm_bytes_per_s"])
    return 100.0 * least / took
