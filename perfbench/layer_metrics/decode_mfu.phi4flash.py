"""Model step: the serving loop's share of the chip's bf16 peak, for the
`phi-4-mini-flash` configuration. FLOPs NEEDED (`work_phi4flash.flops_per_token`)
by the traced window's output tokens at the mix's mean attended context, plus
by the prompt tokens prefilled in it (`prefill_tokens`, no head, at a prompt
token's mean context), over the trace's own window (`pb.window`) x chips x
peak. The share of the whole step that bounds any later kernel claim in the
cell. A program without the `prefill_tokens` counter has nothing to read."""


def read(run):
    tr = run.probe.traced
    tokens = run.counter_delta("tokens_out", traced=True)
    prompt_tokens = run.counter_delta("prefill_tokens", traced=True)
    if not run.trace or not tr or not tokens or prompt_tokens is None:
        return None
    from perfbench import traffic, work_phi4flash as w

    out_ctx, prompt_ctx = w.mean_contexts(traffic.request_sizes(run.traffic))
    flops = (tokens * w.flops_per_token(run.config, out_ctx)
             + prompt_tokens * w.flops_per_token(run.config, prompt_ctx,
                                                 head=False))
    return run.work.mfu_percent(flops, run.trace["window_s"], run.chips,
                                run.peaks["bf16_flops_per_s"])
