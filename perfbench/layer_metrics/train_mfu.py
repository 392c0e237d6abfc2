"""Model step: the whole step's share of the chip's bf16 peak. FLOPs that
forward and backward NEED per token (`work.lm_train_flops_per_token`: matrix
parameters without the embedding gather, causal attention, recomputation not
counted) x tokens of the traced steps / (traced window x chips x peak). The
window is the trace's own (`pb.window` on the profiler's clock, the one the
device readers divide by), which holds whole steps."""


def read(run):
    tr = run.probe.traced
    if not run.trace or not tr or not tr.get("units"):
        return None
    tokens = tr["units"] * run.result["tokens_per_step"]
    flops = tokens * run.work.lm_train_flops_per_token(
        run.config, run.traffic["seq"])
    return run.work.mfu_percent(flops, run.trace["window_s"], run.chips,
                                run.peaks["bf16_flops_per_s"])
