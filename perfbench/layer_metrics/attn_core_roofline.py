"""Kernels: the attention core's share of the chip's bf16 peak. FLOPs that
causal attention NEEDS for the tokens of the traced steps
(`work.lm_attention_flops_per_token` at the mean attended context (seq + 1) / 2,
forward x 3 for forward and backward, masked positions and recomputation not
counted) over device 0's self time under the program's `attn.core` scope
(both directions), over the peak. By scope, so it reads the same work
whichever kernels compute it, and falls silent when nothing carries the name."""

from perfbench.trace_scopes import scope_seconds


def read(run):
    tr = run.probe.traced
    if not run.trace or not tr or not tr.get("units"):
        return None
    took = scope_seconds(run.trace, "attn.core")
    if not took:
        return None
    tokens = tr["units"] * run.result["tokens_per_step"]
    flops = 3.0 * tokens * run.work.lm_attention_flops_per_token(
        run.config, (run.traffic["seq"] + 1) / 2.0)
    return run.work.mfu_percent(flops / run.chips, took, 1,
                                run.peaks["bf16_flops_per_s"])
