"""A second witness for the KMeans cells: float64 Lloyd in plain NumPy on the
CPU, over the same seeded blobs at the cell's own size.

    JAX_PLATFORMS=cpu python3 -m perfbench.tools.witness_lloyd64 \
        --config heat-kmeans-w25m --seed 4100000001 [--rows N] [--out FILE]

It makes X with the driver's own `make_blobs` (threefry gives the CPU the
bits the chip gets), then runs three things on it: this file's float64 Lloyd
(blocks of rows, BLAS), the plain float32 reference (`references/lloyd.py`)
and the program (`ht.cluster.KMeans.fit`), both on the CPU. It prints how far
the reference and the program lie from the float64 centroids, by the driver's
own measure (`centroid_err`, `centroid_shrink`), and writes all three sets of
centroids to `--out` so that a chip run of the same seed can be laid beside
them (`tools/readings.py --dump`). Never part of a benchmark run: nothing
here is a device number.
"""

import argparse
import json
import os
import time

import numpy as np


def lloyd64(x, init, iters, block=1 << 20):
    """`iters` Lloyd iterations and an assignment pass, all in float64."""
    c = np.asarray(init, np.float64)
    k = c.shape[0]

    def one_pass(c):
        sums, counts, inertia = np.zeros_like(c), np.zeros(k), 0.0
        for i in range(0, x.shape[0], block):
            xb = np.asarray(x[i:i + block], np.float64)
            d2 = (c * c).sum(1)[None, :] - 2.0 * (xb @ c.T)
            lab = d2.argmin(1)
            onehot = np.zeros((xb.shape[0], k))
            onehot[np.arange(xb.shape[0]), lab] = 1.0
            sums += onehot.T @ xb
            counts += onehot.sum(0)
            diff = xb - c[lab]
            inertia += float((diff * diff).sum())
        return sums, counts, inertia

    for _ in range(iters):
        sums, counts, _inertia = one_pass(c)
        c = np.where((counts > 0)[:, None],
                     sums / np.maximum(counts, 1.0)[:, None], c)
    _sums, counts, inertia = one_pass(c)
    return c, inertia, counts


def distance(c, c_ref):
    """The driver's measure of centroids `c` against `c_ref`."""
    scale = float(np.abs(c_ref).max())
    e = c - c_ref
    a = float((e * c_ref).sum() / (c_ref * c_ref).sum())
    return {"centroid_err": float(np.abs(e).max()) / scale,
            "centroid_shrink": a,
            "centroid_resid": float(np.abs(e - a * c_ref).max()) / scale}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", default="heat-kmeans-w25m")
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--rows", type=int, default=None)
    ap.add_argument("--out", default=None)
    args = ap.parse_args()
    os.environ.setdefault("JAX_PLATFORMS", "cpu")

    import jax
    import jax.numpy as jnp

    import heat_tpu as ht
    from heat_tpu.core.communication import TPUCommunication
    from perfbench import run as harness
    from perfbench.drivers import kmeans_fit
    from perfbench.references import lloyd as ref

    cfg = harness.load_json(os.path.join(harness.HERE, "configs",
                                         args.config + ".json"))
    rows = args.rows or int(cfg["n_rows"])
    k, f, iters = cfg["n_clusters"], cfg["n_features"], cfg["max_iter"]
    comm = TPUCommunication(devices=jax.devices()[:1])
    t = time.perf_counter()
    xj, init = kmeans_fit.make_blobs(args.seed, rows, f, k,
                                     float(cfg["blob_sigma"]), comm)
    x = np.asarray(xj)
    print(f"blobs {x.shape} in {time.perf_counter() - t:.0f} s", flush=True)

    t = time.perf_counter()
    c64, i64, _n = lloyd64(x, init, iters)
    print(f"float64 lloyd in {time.perf_counter() - t:.0f} s", flush=True)

    t = time.perf_counter()
    c_ref, i_ref, _counts = jax.device_get(
        ref.lloyd(xj, None, iters)(xj, jnp.asarray(init)))
    print(f"reference in {time.perf_counter() - t:.0f} s", flush=True)

    t = time.perf_counter()
    km = ht.cluster.KMeans(n_clusters=k, init=ht.array(init, comm=comm),
                           max_iter=iters, tol=float(cfg["tol"]))
    km.fit(ht.array(xj, split=0, copy=False, comm=comm))
    c_prog = np.asarray(km.cluster_centers_.numpy(), np.float64)
    print(f"program (CPU) in {time.perf_counter() - t:.0f} s", flush=True)

    out = {"seed": args.seed, "rows": rows, "device": "cpu",
           "reference_vs_float64": dict(
               distance(np.asarray(c_ref, np.float64), c64),
               inertia_rel=abs(float(i_ref) - i64) / i64),
           "program_cpu_vs_float64": dict(
               distance(c_prog, c64),
               inertia_rel=abs(float(km.inertia_) - i64) / i64)}
    print(json.dumps(out), flush=True)
    if args.out:
        out.update(float64=c64.tolist(),
                   reference_cpu=np.asarray(c_ref, np.float64).tolist(),
                   program_cpu=c_prog.tolist())
        with open(args.out, "w") as fh:
            json.dump(out, fh)


if __name__ == "__main__":
    main()
