"""Serving: how long the engine's worker waits in the one device-to-host read
of a decode step (`decode.fetch` directly under `decode.step`: the token
vector; it returns when the step is done on the device and the copy has
landed), mean over the steps whole inside the traced window, in ms. Beside
`decode_step_ms` it says how much of a step the host only waits."""


def read(run):
    steps = {r.id for r in run.spans_named("decode.step")}
    took = [r.t1 - r.t0 for r in run.spans_named("decode.fetch")
            if r.parent_id in steps]
    if not took:
        return None
    return 1e3 * sum(took) / len(took)
