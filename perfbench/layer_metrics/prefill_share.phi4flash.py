"""Serving: device 0's time in the prefill programs (`jit_decode_prefill`, one
a prompt bucket, all under one name) over its busy time in the traced window:
what the prompts' scans and attention take from the steps. Read only where
the program counts `prefill_tokens` (a program from before that counter has
another prefill, and this metric is not its)."""

PROGRAM = "jit_decode_prefill"


def read(run):
    prog = (run.trace or {}).get("programs", {}).get(PROGRAM)
    if not prog or not run.trace["device0_busy_s"] \
            or run.counter_delta("prefill_tokens", traced=True) is None:
        return None
    return 100.0 * prog["device_s"] / run.trace["device0_busy_s"]
