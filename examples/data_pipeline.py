"""An end-to-end analytics pipeline on the tape-compiled data engine.

The full scenario ladder in one script (doc/data_engine.md):

1. **Ingest** a sensor-readings table out-of-core — written to HDF5 and
   streamed back chunk by chunk via ``ht.load_hdf5(stream=True)`` when
   h5py is available, otherwise a chunked in-memory source.
2. **Analyze** with ``heat_tpu.data``: per-station mean via a bounded-
   memory ``stream_groupby`` fold, the exact p90 magnitude via the
   multi-pass ``stream_quantile``, the hottest individual readings via
   ``topk`` — every op one audited collective plan, zero all-gather.
3. **Filter** the readings above the p90 threshold (a split-axis
   boolean mask — stays sharded) and **fit** a ``KMeans`` on their
   features through the tape-compiled fit-step engine (analytics.md).
4. **Serve** the fitted model behind the batching executor
   (``serve_estimator``) and read the one observability surface:
   ``ht.runtime_stats()["data_engine"]`` with zero eager fallbacks.

Usage (4 virtual devices):
  JAX_PLATFORMS=cpu \
  XLA_FLAGS=--xla_force_host_platform_device_count=4 \
  python examples/data_pipeline.py
"""

import argparse
import os
import tempfile

import numpy as np

try:
    import heat_tpu as ht
except ModuleNotFoundError:  # running from a source checkout without install
    import sys

    sys.path.insert(0, os.path.abspath(os.path.join(
        os.path.dirname(__file__), "..")))
    import heat_tpu as ht


def make_table(rng, rows, stations, feats, clusters):
    """Synthetic readings: station id, magnitude, and a feature block
    drawn from ``clusters`` hidden modes (recoverable by KMeans)."""
    station = rng.integers(0, stations, rows).astype(np.float64)
    mode = rng.integers(0, clusters, rows)
    centers = rng.normal(0.0, 6.0, size=(clusters, feats))
    x = centers[mode] + rng.normal(0.0, 0.4, size=(rows, feats))
    magnitude = np.abs(rng.standard_normal(rows)) + (mode == 0) * 1.5
    return station, magnitude.astype(np.float64), x.astype(np.float32)


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--rows", type=int, default=200_000)
    p.add_argument("--stations", type=int, default=16)
    p.add_argument("--features", type=int, default=8)
    p.add_argument("--clusters", type=int, default=4)
    p.add_argument("--topk", type=int, default=5)
    p.add_argument("--rows-per-chunk", type=int, default=1 << 14)
    args = p.parse_args()
    if os.environ.get("HEAT_TPU_EXAMPLE_SMOKE"):  # CI ladder smoke: shrink
        args.rows, args.rows_per_chunk = 20_000, 1 << 12

    from heat_tpu import data
    from heat_tpu.serve import serve_estimator

    n_dev = ht.get_comm().size
    rng = np.random.default_rng(7)
    station, magnitude, feats = make_table(
        rng, args.rows, args.stations, args.features, args.clusters)
    table = np.stack([station, magnitude], axis=1)
    print(f"{args.rows} readings from {args.stations} stations "
          f"over {n_dev} device(s)")

    # -- 1. ingest: an out-of-core chunked source over the (station,   --
    # --    magnitude) table — HDF5-backed when h5py is present        --
    tmp = None
    try:
        import h5py  # noqa: F401

        tmp = tempfile.TemporaryDirectory()
        path = os.path.join(tmp.name, "readings.h5")
        with h5py.File(path, "w") as f:
            f.create_dataset("table", data=table)
        source = ht.load_hdf5(path, "table", dtype=ht.float64,
                              split=0, stream=True)
        print(f"ingest: streaming {os.path.getsize(path) >> 10} KiB HDF5 "
              f"in {args.rows_per_chunk}-row chunks")
    except ImportError:
        def source():
            return iter(ht.array(table[i:i + args.rows_per_chunk], split=0)
                        for i in range(0, args.rows, args.rows_per_chunk))
        print("ingest: h5py unavailable — chunked in-memory source")

    # -- 2. analytics: bounded-memory folds + the in-memory engine ops --
    per_station = data.stream_groupby(
        source, args.stations, "mean",
        rows_per_chunk=args.rows_per_chunk).numpy()
    p90 = float(np.asarray(data.stream_quantile(
        source, 0.90, col=1, rows_per_chunk=args.rows_per_chunk)))
    hottest = int(np.argmax(per_station))
    print(f"per-station mean magnitude: hottest station {hottest} "
          f"at {per_station[hottest]:.3f}; exact p90 = {p90:.3f}")

    mag = ht.array(magnitude, split=0)
    tv, ti = data.topk(mag, args.topk)
    med = float(np.asarray(ht.median(mag).numpy()))  # engine-routed
    print(f"top-{args.topk} readings: {np.round(tv.numpy(), 3).tolist()} "
          f"at rows {ti.numpy().tolist()}; median {med:.3f}")

    # -- 3. filter above-p90 readings (sharded mask) and fit KMeans     --
    x = ht.array(feats, split=0)
    hot = x[mag >= p90]
    km = ht.cluster.KMeans(n_clusters=args.clusters, init="kmeans++",
                           random_state=3)
    km.fit(hot)
    print(f"KMeans over {hot.shape[0]} above-p90 readings: "
          f"converged in {km.n_iter_} iterations, "
          f"inertia {float(km.inertia_):.1f}")

    # -- 4. serve the fitted model behind the batching executor        --
    ex = serve_estimator(km)
    ex.warmup((args.features,), np.float32, rows=(1, n_dev * 2))
    batches = [feats[rng.integers(0, args.rows, r)] for r in (3, 7, 5)]
    futs = [ex.submit(b) for b in batches]
    labels = [np.asarray(f.result(60)) for f in futs]
    serve_stats = ex.stats()
    ex.close()
    print(f"served {sum(len(b) for b in batches)} rows in "
          f"{len(batches)} requests: labels {[l.tolist() for l in labels]}")

    st = ht.runtime_stats()["data_engine"]
    assert st["exchange_fallbacks"] == 0 and st["stream_fallbacks"] == 0
    print(f"data engine: {st['dispatches']} dispatches, "
          f"{st['stream_chunks']} chunks folded, 0 fallbacks; "
          f"program cache {st['program_cache']}; "
          f"serve p99 {serve_stats['latency_ms']['p99']:.1f} ms")
    if tmp is not None:
        tmp.cleanup()


if __name__ == "__main__":
    main()
