"""Tour of the distributed array surface: splits, indexing, manipulations,
linalg, statistics, and I/O — every operation below stays gather-free on a
device mesh (see doc/distributed_internals.md for how).

Run on a virtual mesh:

    JAX_PLATFORMS=cpu \
      XLA_FLAGS=--xla_force_host_platform_device_count=8 \
      python examples/arrays/demo_distributed_arrays.py
"""

import numpy as np

try:
    import heat_tpu as ht
except ModuleNotFoundError:  # running from a source checkout without install
    import os, sys

    sys.path.insert(0, os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "..")))
    import heat_tpu as ht


def main():
    print(f"mesh: {ht.get_comm().size} device(s)")
    rng = np.random.default_rng(0)

    # --- creation & reductions -------------------------------------- #
    x = ht.arange(1_000_003, dtype=ht.float32, split=0)  # uneven on purpose
    print("sum  :", float(x.sum()))
    print("mean :", float(x.mean()), " std:", float(x.std()))

    # --- fancy indexing (ring programs) ------------------------------ #
    a = ht.array(rng.standard_normal((100_000, 8)).astype(np.float32), split=0)
    top_rows = a[np.array([0, 99_999, 12_345]), 2:6]        # mixed key
    heavy = a[a[:, 0] > 2.5]                                # boolean mask
    print("mixed-key slice:", top_rows.shape, " mask rows:", heavy.shape)
    a[np.array([7, 11])] = 0.0                              # scatter ring

    # --- manipulations (scheduled window fetches) -------------------- #
    b = ht.roll(x, 12_345)
    c = ht.flip(x)
    d = ht.concatenate([x, x], axis=0)
    e = ht.reshape(ht.arange(2 * 3 * 4 * 1000, split=0), (2000, 12))
    print("roll/flip/concat/reshape:", b.shape, c.shape, d.shape, e.shape)
    vals, idx = ht.sort(ht.array(rng.permutation(100_001).astype(np.float32),
                                 split=0))
    print("sorted head:", vals[np.array([0, 1, 2])].numpy())

    # --- statistics --------------------------------------------------- #
    h, edges = ht.histogram(a[:, 0], bins=8)
    print("histogram:", np.asarray(h.numpy()))
    print("median col0:", float(ht.median(a[:, 0])))
    tv, ti = ht.topk(a[:, 0], 3)
    print("top-3 col0:", np.asarray(tv.numpy()).round(3))

    # --- linalg ------------------------------------------------------- #
    m = ht.array((rng.standard_normal((64, 64)) + 64 * np.eye(64)
                  ).astype(np.float32), split=0)
    inv = ht.linalg.inv(m)            # distributed Gauss-Jordan
    resid = ht.matmul(m, inv).numpy() - np.eye(64, dtype=np.float32)
    print("max |I - m @ inv| entry:", float(np.abs(resid).max()))
    q, r = ht.linalg.qr(ht.array(rng.standard_normal((48, 96)
                                                     ).astype(np.float32),
                                 split=0))  # panel CAQR (wide split-0)
    print("QR shapes:", q.shape, r.shape)

    # --- I/O ---------------------------------------------------------- #
    import tempfile, os

    path = os.path.join(tempfile.mkdtemp(), "demo.h5")
    ht.save_hdf5(a, path, "data")     # shard-streamed write, no gather
    back = ht.load_hdf5(path, "data", split=0)
    print("h5 round-trip ok:", bool((back[:5].numpy() == a[:5].numpy()).all()))


if __name__ == "__main__":
    main()
