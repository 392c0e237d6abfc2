"""Shared k-clustering machinery (reference ``heat/cluster/_kcluster.py``).

The reference's per-centroid ``Bcast`` initialization (``_kcluster.py:87-194``)
and cdist+argmin assignment (``:196``) become, respectively, gathers of k
sampled rows (k tiny) and one fused GEMM-tile + argmin program per shard.

The Lloyd driver lives HERE, once: :meth:`_KCluster._run_lloyd` is the one
``for it in range(1, self.max_iter + 1)`` loop every estimator's ``fit``
(and every ``fit_stream`` epoch) runs, so the tape-compiled fit step —
``fusion.fit_step_call`` dispatching ONE donated packed-collective
executable per iteration — lands in one place instead of the historic
copy-pasted batched/non-batched loop pairs (``kmedians.py:130/:144``,
``kmedoids.py:120/:134``). :meth:`fit_stream` is the out-of-core entry
point: a re-iterable chunk source (``io.DataStream`` or any chunk
iterable) is consumed epoch-by-epoch, chunk-by-chunk, so datasets larger
than host RAM train without ever materializing.
"""

from __future__ import annotations

from typing import Callable, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from ..core import factories, fusion, random as ht_random, types
from ..core.base import BaseEstimator, ClusteringMixin
from ..core.dndarray import DNDarray
from ..utils.profiling import span

__all__ = ["_KCluster"]


def _chunk_source(stream, rows_per_chunk):
    """Normalize a ``fit_stream`` source into ``(factory, shape_hint)``:
    ``factory()`` yields a fresh pass of split-0 DNDarray chunks each
    epoch. Accepts an ``io.DataStream`` (re-opened per pass), a zero-arg
    callable returning an iterable, or a concrete chunk sequence."""
    if hasattr(stream, "iter_chunks"):
        if rows_per_chunk is None:
            raise ValueError(
                "rows_per_chunk is required when streaming from a "
                "DataStream source")
        return (lambda: stream.iter_chunks(rows_per_chunk),
                tuple(getattr(stream, "shape", ()) or ()) or None)
    if callable(stream):
        return stream, None
    seq = list(stream)
    if not seq:
        raise ValueError("fit_stream needs at least one chunk")
    return (lambda: iter(seq)), None


class _KCluster(ClusteringMixin, BaseEstimator):
    """Base class for KMeans/KMedians/KMedoids (reference ``_kcluster.py:16``)."""

    def __init__(self, metric: Callable, n_clusters: int, init, max_iter: int, tol: float, random_state):
        import numbers

        if (
            isinstance(n_clusters, bool)
            or not isinstance(n_clusters, numbers.Integral)
            or n_clusters < 1
        ):
            raise ValueError(f"n_clusters must be a positive int, got {n_clusters!r}")
        self.n_clusters = int(n_clusters)
        self.init = init
        self.max_iter = max_iter
        self.tol = tol
        self.random_state = random_state

        self._cluster_centers = None
        self._labels = None
        self._inertia = None
        self._n_iter = None
        self._metric = metric

    @property
    def cluster_centers_(self) -> DNDarray:
        return self._cluster_centers

    @property
    def labels_(self) -> DNDarray:
        return self._labels

    @property
    def inertia_(self) -> float:
        return self._inertia

    @property
    def n_iter_(self) -> int:
        return self._n_iter

    # ------------------------------------------------------------------ #
    # the ONE Lloyd driver (tape-compiled fit steps land here)           #
    # ------------------------------------------------------------------ #
    def _converged(self, shift_sq: float) -> bool:
        """Convergence predicate on the squared centroid shift; KMedoids
        overrides with its exact-fixpoint test."""
        return self.tol >= 0 and shift_sq <= self.tol * self.tol

    def _run_lloyd(self, step, xp, centroids):
        """The shared ``for it in range(1, self.max_iter + 1)`` loop.

        ``step(xp, centroids) -> (new_centroids, shift, aux)`` — under
        ``fusion.fit_enabled()`` one compiled donated executable per
        iteration (key lookup + one dispatch); the ``float(shift)`` read
        is the per-iteration host sync (it also serializes back-to-back
        collective programs, the PR-2-era CPU rendezvous discipline).
        Returns ``(centroids, aux, n_iter)``.
        """
        it = 0
        aux = None
        for it in range(1, self.max_iter + 1):
            with span("kmeans.iter", it=it):
                centroids, shift, aux = step(xp, centroids)
                with span("kmeans.sync"):
                    shift = float(shift)    # the host waits for the device
            if self._converged(shift):
                break
        return centroids, aux, it

    # ------------------------------------------------------------------ #
    # out-of-core streaming fit                                          #
    # ------------------------------------------------------------------ #
    def _stream_chunk_update(self, chunk: DNDarray, centroids):
        """One minibatch update from one chunk (the default
        ``_stream_epoch`` hook): one distributed fit step for split-0
        multi-device chunks, the replicated local step otherwise.
        Serves any subclass that defines ``_step_dispatcher`` /
        ``_local_step`` (KMedians, KMedoids); KMeans overrides the whole
        epoch with the exact accumulation form instead."""
        if not hasattr(self, "_step_dispatcher"):
            raise NotImplementedError(
                f"{type(self).__name__} does not implement streamed fitting")
        n = chunk.shape[0]
        if chunk.split == 0 and chunk.comm.size > 1 and n > 0:
            xp = chunk.larray.astype(jnp.float32)
            centroids, _, _ = self._step_dispatcher(
                xp.shape, n, chunk.comm)(xp, centroids)
            return centroids
        logical = chunk._logical().astype(jnp.float32)
        centroids, _, _ = self._local_step(logical, centroids)
        return centroids

    def _stream_epoch(self, chunks, centroids, meta):
        """One pass over all chunks. Default: MINIBATCH semantics — the
        centroids are updated after every chunk with that chunk's own
        update (approximate; the per-chunk update has no memory of the
        other chunks). Returns ``(new_centroids, epoch_shift_sq)``."""
        # copy: the first chunk's fused step DONATES the carried buffer,
        # and the epoch shift still needs the starting values
        start = jnp.array(centroids)
        for chunk in chunks():
            centroids = self._stream_chunk_update(chunk, centroids)
        shift = jnp.sum((centroids - start) ** 2)
        return centroids, shift

    def _stream_dtype(self, chunk: DNDarray):
        return jnp.dtype(jnp.float32)

    def _init_stream_centers(self, chunks, shape_hint):
        """Streamed centroid seeding, value-equal to the in-memory
        ``_initialize_cluster_centers`` for the supported inits:

        * an explicit ``(k, d)`` DNDarray — used as-is (replicated);
        * ``"random"`` — the SAME ``ht_random.randint`` draw as the
          in-memory path (same seed → same global row indices), with the
          sampled rows collected during one metadata pass over the
          chunks, so streamed and in-memory fits see identical seeds;
        * ``"kmeans++"`` — rejected: D²-weighted seeding needs one full
          distance pass over the data per seed and is not available
          out-of-core.

        Returns ``(centroids, meta)`` where ``meta`` carries the stream
        geometry (n rows, feature count, comm/device, dtype).
        """
        k = self.n_clusters
        if self.random_state is not None:
            ht_random.seed(self.random_state)
        if isinstance(self.init, str) and self.init in (
                "kmeans++", "probability_based"):
            raise ValueError(
                "fit_stream supports init='random' or explicit centroids; "
                "kmeans++ seeding needs full-data distance passes")
        meta = {"n": 0, "d": None, "comm": None, "device": None}
        want = None
        rows: dict = {}
        if isinstance(self.init, str) and self.init == "random":
            # shape hint (DataStream) lets the draw happen before the
            # pass; otherwise a first metadata pass counts rows
            if shape_hint is not None:
                meta["n"] = int(shape_hint[0])
            else:
                for chunk in chunks():
                    meta["n"] += chunk.shape[0]
        lo = 0
        for chunk in chunks():
            if meta["d"] is None:
                if chunk.ndim != 2:
                    raise ValueError(
                        "fit_stream chunks must be 2-D (rows, features)")
                meta["d"] = chunk.shape[1]
                meta["comm"] = chunk.comm
                meta["device"] = chunk.device
                meta["jdt"] = self._stream_dtype(chunk)
                if isinstance(self.init, str) and self.init == "random":
                    if shape_hint is None and meta["n"] <= 0:
                        raise ValueError("fit_stream saw zero rows")
                    idx = ht_random.randint(
                        0, meta["n"], (k,), split=None, comm=chunk.comm)
                    want = np.asarray(idx.larray)
            hi = lo + chunk.shape[0]
            if want is not None:
                sel = [(j, int(g) - lo) for j, g in enumerate(want)
                       if lo <= int(g) < hi]
                if sel:
                    got = chunk[np.asarray([r for _, r in sel])] \
                        .resplit(None)._logical()
                    for (j, _), row in zip(sel, got):
                        rows[j] = row
                if len(rows) == len(want):
                    # every drawn seed row collected — don't pay the
                    # rest of the disk pass for nothing
                    lo = hi
                    break
            else:
                # explicit init: only the stream geometry was needed —
                # don't pay a full disk pass for it
                lo = hi
                break
            lo = hi
        if shape_hint is not None:
            meta["n"] = int(shape_hint[0])
        else:
            meta["n"] = max(meta["n"], lo)
        if meta["d"] is None:
            raise ValueError("fit_stream needs at least one chunk")
        if isinstance(self.init, DNDarray):
            if self.init.shape != (k, meta["d"]):
                raise ValueError(
                    f"passed centroids must have shape ({k}, {meta['d']}),"
                    f" got {self.init.shape}")
            centers = self.init.resplit(None)._logical()
        elif want is not None:
            missing = [int(want[j]) for j in range(k) if j not in rows]
            if missing:
                raise ValueError(
                    f"fit_stream random init: drawn seed rows {missing} "
                    f"were never produced by the stream (stream shorter "
                    f"than its declared {meta['n']} rows?)")
            centers = jnp.stack([rows[j] for j in range(k)])
        else:
            raise ValueError(
                f"initialization method {self.init!r} is not supported "
                "for fit_stream")
        return jnp.array(centers, meta["jdt"]), meta

    def fit_stream(self, stream, rows_per_chunk: Optional[int] = None):
        """Out-of-core fit from a re-iterable chunk source.

        ``stream`` is an ``io.DataStream`` (``ht.load_hdf5(...,
        stream=True)``) — each epoch calls
        ``stream.iter_chunks(rows_per_chunk)`` and the data re-streams
        from disk, so the peak resident footprint is ONE chunk, never
        the dataset — or a zero-arg callable returning a fresh chunk
        iterable, or a concrete sequence of split-0 DNDarray chunks.

        KMeans runs the EXACT epoch form (per-chunk partial sums/counts
        accumulated into donated device buffers, centroids updated once
        per epoch — value-equal to the in-memory fit up to float
        summation reassociation, ``doc/analytics.md``); KMedians and
        KMedoids run the documented minibatch form (per-chunk updates,
        approximate). ``labels_`` is not materialized (an n-vector for
        an out-of-core n — use ``predict`` chunk-wise); ``n_iter_`` and
        ``cluster_centers_`` are set as in ``fit``.
        """
        chunks, shape_hint = _chunk_source(stream, rows_per_chunk)
        centroids, meta = self._init_stream_centers(chunks, shape_hint)
        it = 0
        for it in range(1, self.max_iter + 1):
            centroids, shift = self._stream_epoch(chunks, centroids, meta)
            if self._converged(float(shift)):
                break
        self._stream_finalize(chunks, centroids, meta)
        self._cluster_centers = DNDarray.from_logical(
            centroids, None, meta["device"], meta["comm"])
        self._labels = None
        self._n_iter = it
        return self

    def _stream_finalize(self, chunks, centroids, meta):
        """Post-loop hook with the FINAL centroids. Default no-op;
        KMeans spends one extra pass here to measure ``inertia_``
        against the final centroids — the same semantics as ``fit()``'s
        final assignment pass."""

    # ------------------------------------------------------------------ #
    def _initialize_cluster_centers(self, x: DNDarray):
        """Centroid init (reference ``_kcluster.py:87-194``)."""
        k = self.n_clusters
        if self.random_state is not None:
            ht_random.seed(self.random_state)
        if isinstance(self.init, DNDarray):
            if self.init.shape != (k, x.shape[1]):
                raise ValueError(
                    f"passed centroids must have shape ({k}, {x.shape[1]}), got {self.init.shape}"
                )
            self._cluster_centers = self.init.resplit(None)
            return
        if self.init == "random":
            idx = ht_random.randint(0, x.shape[0], (k,), split=None, comm=x.comm)
            # ring-gather the k sampled rows (the reference Bcasts each
            # sampled row, ``_kcluster.py:87-194``) — no materialization
            rows = x[np.asarray(idx.larray)].resplit(None)
            self._cluster_centers = rows
            return
        if self.init in ("kmeans++", "probability_based"):
            self._cluster_centers = self._kmeanspp(x)
            # synchronize before the caller launches its iteration programs:
            # concurrently-executing collective programs can interleave
            # their rendezvous on the CPU backend and deadlock (observed
            # with the seeding cdist ring vs the first Lloyd step)
            jax.block_until_ready(self._cluster_centers.larray)
            return
        raise ValueError(f"initialization method {self.init!r} is not supported")

    def _kmeanspp(self, x: DNDarray) -> DNDarray:
        """k-means++ D²-weighted seeding (reference ``_kcluster.py:120-194``).

        The heavy part (min squared distance per point) runs sharded on
        device; the D²-weighted draw itself is O(n) on k tiny vectors and
        runs on HOST with concrete indices. Device-side cumsum/searchsorted/
        gather-by-traced-index would each be a separate tiny collective
        program — a stampede of in-process rendezvous that can starve the
        host thread pool and hard-abort XLA's CPU runtime (observed on
        single-core CI hosts with an 8-device mesh).
        """
        n = x.shape[0]
        k = self.n_clusters
        first = int(ht_random.randint(0, n, (1,), comm=x.comm)._logical()[0])

        def row(i):  # one sampled row, ring-gathered — never the array
            return x[np.asarray([i])].resplit(None)._logical()

        centers = row(first)
        for _ in range(1, k):
            d2 = np.asarray(self._pairwise_sq_dist_to(x, centers))  # (n,), host
            u = float(ht_random.rand(1, comm=x.comm)._logical()[0])
            total = max(float(d2.sum()), 1e-30)
            cdf = np.cumsum(d2 / total)
            nxt = min(int(np.searchsorted(cdf, u)), n - 1)
            centers = jnp.concatenate([centers, row(nxt)], axis=0)
        return DNDarray.from_logical(centers, None, x.device, x.comm)

    def _pairwise_sq_dist_to(self, x: DNDarray, centers) -> jnp.ndarray:
        """Min squared distance of every point to the current center set."""
        from ..spatial.distance import cdist

        c = DNDarray.from_logical(centers, None, x.device, x.comm)
        d = cdist(x, c, quadratic_expansion=True)
        # replicate before the caller's host-side draw: a split array's
        # shards span non-addressable devices on multi-host pods, where a
        # host fetch of the sharded value would raise
        dmin = d.min(axis=1).resplit(None)
        return dmin._logical() ** 2

    def _assign_to_cluster(self, x: DNDarray) -> DNDarray:
        """Nearest-centroid assignment (reference ``_kcluster.py:196``)."""
        d = self._metric(x, self._cluster_centers)
        return d.argmin(axis=1)

    def predict(self, x: DNDarray) -> DNDarray:
        """Nearest learned centroid for each sample (reference ``_kcluster.py:230``)."""
        if not isinstance(x, DNDarray):
            raise TypeError(f"input needs to be a DNDarray, but was {type(x)}")
        return self._assign_to_cluster(x)
