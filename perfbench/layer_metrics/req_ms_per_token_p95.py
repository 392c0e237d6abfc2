"""Serving: over all requests completed in the window, (time from `submit`
to the `Future`'s result) over the request's output tokens, 95th percentile;
a failed or refused request counts as the worst. Taken by the driver on the
host's clock, from the client's side. In this saturated closed loop (48
clients on 32 slots) it is mostly the wait for a slot, so it stands among the
per-layer metrics; an open-loop cell below the knee would carry it end to end
(PERF.md, Open questions)."""


def read(run):
    return run.result["metrics"].get("req_ms_per_token_p95")
