"""Serving: a request's wait for a slot (`decode.queue`: `submit` to the
grant), 95th percentile over the requests granted inside the run's window, in
ms. Recorded, not judged: in a closed loop with more clients than slots the
queue is the design."""

import numpy as np


def read(run):
    took = [r.t1 - r.t0 for r in run.spans_named("decode.queue", traced=False)]
    if not took:
        return None
    return 1e3 * float(np.percentile(took, 95))
