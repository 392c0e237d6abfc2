"""Model step: the serving loop's share of the chip's bf16 peak, for the
`granite-4.0-h-small-d10e36` configuration. FLOPs NEEDED on this chip
(`work_granite4h.flops_per_token`) by the traced window's output tokens at the
mix's mean attended context, plus by the prompt tokens prefilled in it
(`prefill_tokens`, no head, at a prompt token's mean context), over the
trace's own window (`pb.window`) x chips x peak. The routed experts count as
many pairs a token a layer as fell on HELD experts in that window (the
program's `moe_pairs_held` over its tokens: `moe_pairs_total` / 10). The
share of the whole step that bounds any later kernel claim in the cell. A
program without the experts' counters has nothing to read."""


def read(run):
    tr = run.probe.traced
    tokens = run.counter_delta("tokens_out", traced=True)
    prompt_tokens = run.counter_delta("prefill_tokens", traced=True)
    routed = run.counter_delta("moe_pairs_total", traced=True)
    if not run.trace or not tr or not tokens or prompt_tokens is None \
            or not routed:
        return None
    from perfbench import traffic, work_granite4h as w

    held = (run.counter_delta("moe_pairs_held", traced=True)
            * run.config["num_experts_per_tok"] / routed)
    out_ctx, prompt_ctx = w.mean_contexts(traffic.request_sizes(run.traffic))
    flops = (tokens * w.flops_per_token(run.config, out_ctx, held)
             + prompt_tokens * w.flops_per_token(run.config, prompt_ctx, held,
                                                 head=False))
    return run.work.mfu_percent(flops, run.trace["window_s"], run.chips,
                                run.peaks["bf16_flops_per_s"])
