"""Serving: live slots per decode step over the slots there are, in the
traced window, from the engine's own counts: (tokens_out - prefills) is the
sum of live slots over the steps (`DecodeEngine._do_step`)."""


def read(run):
    steps = run.counter_delta("decode_steps", traced=True)
    if not steps:
        return None
    live = (run.counter_delta("tokens_out", traced=True)
            - run.counter_delta("prefills", traced=True))
    return 100.0 * live / (steps * run.traffic["slots"])
