"""Kernels: the Lloyd iteration's share of the HBM roofline on device 0.

Least time: ONE read of the device's rows of X (`work.lloyd_bytes_per_
iteration`) over the published HBM bandwidth. Time taken: device 0's busy time
in the traced fits over the iterations they ran. The count is of the
algorithm, so it reads the same whether XLA's passes or a one-pass kernel run.
The final assignment pass of each fit is in the busy time and not in the
count, which keeps the share under what the step alone reaches (by about 1/31).
"""


def read(run):
    iters = run.counter_delta("iterations", traced=True)
    busy = run.trace["device0_busy_s"] if run.trace else None
    if not iters or not busy:
        return None
    c = run.config
    rows = c["n_rows"] // run.chips
    itemsize = {"float32": 4, "bfloat16": 2}[c["dtype"]]
    least = run.work.lloyd_bytes_per_iteration(
        rows, c["n_features"], itemsize) / run.peaks["hbm_bytes_per_s"]
    return 100.0 * least / (busy / iters)
