"""Model step: the serving loop's share of the chip's bf16 peak. FLOPs the
output tokens of the traced window NEED (`work.lm_forward_flops_per_token` at
the mix's mean attended context, prefill not counted) over the trace's own
window (`pb.window`, the one the device readers divide by) x chips x peak."""


def read(run):
    tr = run.probe.traced
    tokens = run.counter_delta("tokens_out", traced=True)
    if not run.trace or not tr or not tokens:
        return None
    from perfbench import traffic

    sizes = traffic.request_sizes(run.traffic)
    p, o = sizes[:, 0].astype(float), sizes[:, 1].astype(float)
    mean_ctx = float((o * (p + (o + 1) / 2.0)).sum() / o.sum())
    flops = tokens * run.work.lm_forward_flops_per_token(run.config, mean_ctx)
    return run.work.mfu_percent(flops, run.trace["window_s"], run.chips,
                                run.peaks["bf16_flops_per_s"])
