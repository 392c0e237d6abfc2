"""Kernels: a distance-matrix job's share of the HBM roofline on device 0.
Least time: ONE write of this device's rows of the result
(`work.cdist_bytes`) over the published HBM bandwidth. Time taken: device 0's
busy time in the traced jobs over the jobs. The count is of the result, so it
reads the same whichever path computes it (the Mosaic tile, XLA's expansion),
and padding, a slice after the kernel or a second pass only lower it."""


def read(run):
    tr = run.probe.traced
    busy = run.trace["device0_busy_s"] if run.trace else None
    if not busy or not tr or not tr.get("units"):
        return None
    n = run.config["n_rows"]
    least = run.work.cdist_bytes(n // run.chips, n) / run.peaks["hbm_bytes_per_s"]
    return 100.0 * least / (busy / tr["units"])
