"""Serving: time to first token (`submit`, where `decode.queue` begins, to the
end of `decode.prefill`, which is the first token's arrival), 95th percentile
over the requests whose first token came inside the run's window, in ms. The
two spans of one request share `rid`. Recorded, not judged (see
`queue_ms_p95`)."""

import numpy as np


def read(run):
    sent = {r.attrs.get("rid"): r.t0 for r in run.spans
            if r.name == "decode.queue"}
    took = [r.t1 - sent[r.attrs.get("rid")]
            for r in run.spans_named("decode.prefill", traced=False)
            if r.attrs.get("rid") in sent]
    if not took:
        return None
    return 1e3 * float(np.percentile(took, 95))
