"""KMeans clustering (reference ``heat/cluster/kmeans.py``).

The benchmark workload (SURVEY.md §3.4, §6). The reference's Lloyd epoch is a
chain of cdist → argmin → k masked sum/count Allreduces
(``kmeans.py:73-139``). Here one **fused jitted Lloyd step** runs per
iteration, with two backends:

* **Pallas (TPU)**: :func:`heat_tpu.core.pallas_kernels.kmeans_step_tile`
  streams each device's X shard from HBM exactly ONCE per iteration — the
  assignment GEMM, argmin, one-hot update GEMM and inertia terms all
  consume the same VMEM-resident tile — wrapped in ``shard_map`` with a
  ``psum`` for the cross-device centroid reduction.
* **XLA (fallback)**: squared-distance GEMM tile (MXU) → argmin → one-hot
  matmul for the centroid sums → GSPMD ``psum``.

Labels are not materialized in the hot loop (an N-vector write per
iteration); ``fit`` computes them once after convergence.
"""

from __future__ import annotations

from typing import Optional, Union

import numpy as np

import jax
import jax.numpy as jnp
from ..core._compat import shard_map
from jax.sharding import PartitionSpec as P

from ..core.dndarray import DNDarray
from ..core import fusion, types
from ..core.pallas_kernels import (kmeans_step_tile, kmeans_pallas_enabled,
                                   _kmeans_sums_mode, _kmeans_block_rows)
from ..utils.profiling import scope, span
from ._kcluster import _KCluster

__all__ = ["KMeans"]

# cache of jitted Lloyd steps keyed by (physical shape, dtype, k, comm, path)
_STEP_CACHE: dict = {}


_acc_dtype = types.accumulation_dtype


@scope("lloyd.update")
def _finish_update(sums, counts, centroids):
    """Centroid division + empty-cluster keep + shift (replicated inputs).
    Runs in the accumulation dtype; the returned centroids match the
    carried-in centroid dtype so iteration carries stay dtype-stable."""
    acc = sums.dtype
    cacc = centroids.astype(acc)
    new_centroids = sums / jnp.maximum(counts, 1.0)[:, None]
    new_centroids = jnp.where((counts > 0)[:, None], new_centroids, cacc)
    shift = jnp.sum((new_centroids - cacc) ** 2)
    return new_centroids.astype(centroids.dtype), shift


def _lloyd_partial(xp, centroids, valid, k, jdt, acc):
    """Masked per-shard Lloyd partials ``(sums, counts, inertia)`` —
    squared-distance GEMM tile → argmin → one-hot GEMM. ``valid`` is the
    ``(rows, 1)`` bool row mask (canonical padding / chunk tail); the
    same function serves the global GSPMD body, the shard_map block body
    and the streaming partial program."""
    with scope("lloyd.norms"):
        xf = xp.astype(acc)
        x2 = jnp.sum(xf * xf, axis=1, keepdims=True)
        cacc = centroids.astype(acc)
        c2 = jnp.sum(cacc * cacc, axis=1, keepdims=True).T
    with scope("lloyd.dist"):
        xc = jax.lax.dot_general(
            xp, centroids.astype(jdt),
            dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=acc)
        d2 = x2 + c2 - 2.0 * xc  # (rows, k) distances in acc
    with scope("lloyd.argmin"):
        labels = jnp.argmin(d2, axis=1)
    with scope("lloyd.sums"):
        onehot = (labels[:, None] == jnp.arange(k)[None, :]) & valid
        counts = jnp.sum(onehot.astype(acc), axis=0)  # (k,)
        sums = jax.lax.dot_general(  # (k, d) GEMM
            onehot.astype(jdt), xp,
            dimension_numbers=(((0,), (0,)), ((), ())),
            preferred_element_type=acc)
    with scope("lloyd.inertia"):
        inertia = jnp.sum(jnp.where(valid[:, 0], jnp.min(d2, axis=1),
                                    jnp.zeros((), acc)))
    return sums, counts, inertia


def _make_step_body(phys_shape, jdt, k, n_valid, comm, sums_mode,
                    block_rows=None):
    """(xp, centroids) -> (new_centroids, inertia, shift); one Lloyd step.

    ``sums_mode`` is resolved by the CALLER and passed down explicitly so the
    step cache key and the traced kernel can never disagree (resolving the
    env var again at trace time could bake a different mode into an entry
    keyed under the lookup-time mode)."""
    if sums_mode:
        chunk = phys_shape[0] // comm.size
        axis = comm.axis_name

        def lloyd_step(xp_blk, centroids):
            rank = jax.lax.axis_index(axis)
            row = rank * chunk + jax.lax.broadcasted_iota(
                jnp.int32, (chunk, 1), 0)
            mask = (row < n_valid).astype(xp_blk.dtype)
            sums, counts, inertia = kmeans_step_tile(
                xp_blk, centroids, mask, block_rows=block_rows,
                sums_mode=sums_mode)
            with scope("lloyd.psum"):
                sums = jax.lax.psum(sums, axis)
                counts = jax.lax.psum(counts, axis)
                inertia = jax.lax.psum(inertia, axis)
            new_centroids, shift = _finish_update(sums, counts, centroids)
            return new_centroids, inertia, shift

        return shard_map(
            lloyd_step, mesh=comm.mesh,
            in_specs=(comm.spec(2, 0), P()),
            out_specs=(P(), P(), P()),
            check_vma=False)

    acc = _acc_dtype(jdt)

    def lloyd_step(xp, centroids):
        # valid-row mask for canonical padding; elementwise consumers
        # cast in-register (HBM reads stay bf16 for half-precision
        # storage); GEMMs take the narrow inputs at MXU rate and
        # accumulate in ``acc`` via preferred_element_type — the psums
        # are GSPMD-placed on this path
        row = jax.lax.broadcasted_iota(jnp.int32, (phys_shape[0], 1), 0)
        sums, counts, inertia = _lloyd_partial(
            xp, centroids, row < n_valid, k, jdt, acc)
        new_centroids, shift = _finish_update(sums, counts, centroids)
        return new_centroids, inertia, shift

    return lloyd_step


def _use_pallas_step(jdt) -> bool:
    """The fused kernel returns sums/counts/inertia in the INPUT dtype
    (``pallas_kernels._kmeans_step_tile``); half-precision inputs would
    round cluster counts >256 before the psum, so they stay on the XLA
    mixed-precision path (bf16 reads, f32 accumulation)."""
    return (kmeans_pallas_enabled()
            and _acc_dtype(jdt) == jnp.dtype(jdt))


def _lloyd_step_fn(phys_shape, jdt, k, n_valid, comm):
    sums_mode = _use_pallas_step(jdt) and _kmeans_sums_mode()
    block_rows = _kmeans_block_rows() if sums_mode else None
    key = (phys_shape, str(jdt), k, n_valid, comm.cache_key, sums_mode,
           block_rows)
    fn = _STEP_CACHE.get(key)
    if fn is None:
        fn = jax.jit(_make_step_body(phys_shape, jdt, k, n_valid, comm,
                                     sums_mode, block_rows))
        _STEP_CACHE[key] = fn
    return fn


def _lloyd_fused_fn(phys_shape, jdt, k, n_valid, comm, qk, ck, hk):
    """The tape-compiled Lloyd step for split-0 data: ONE donated
    shard_map executable per iteration — distance GEMM tile → argmin →
    masked one-hot sums/counts → convergence shift on shard-local
    blocks, with the centroid sums, counts AND inertia PACKED into a
    single flattened all-reduce (``fusion.packed_psum``; the captured
    quant/chunk/hier tuples are pinned so the traced wire format always
    matches the program key). The carried centroids are DONATED — XLA
    updates the replicated (k, d) buffer in place across iterations.
    Returns ``(new_centroids, shift, inertia)``."""
    sums_mode = _use_pallas_step(jdt) and _kmeans_sums_mode()
    block_rows = _kmeans_block_rows() if sums_mode else None
    key = ("fused", phys_shape, str(jdt), k, n_valid, comm.cache_key,
           sums_mode, block_rows, qk, ck, hk)
    fn = _STEP_CACHE.get(key)
    if fn is not None:
        return fn
    acc = _acc_dtype(jdt)
    chunk = phys_shape[0] // comm.size
    axis = comm.axis_name

    def lloyd_step(xp_blk, centroids):
        rank = jax.lax.axis_index(axis)
        row = rank * chunk + jax.lax.broadcasted_iota(
            jnp.int32, (chunk, 1), 0)
        if sums_mode:
            mask = (row < n_valid).astype(xp_blk.dtype)
            sums, counts, inertia = kmeans_step_tile(
                xp_blk, centroids, mask, block_rows=block_rows,
                sums_mode=sums_mode)
        else:
            sums, counts, inertia = _lloyd_partial(
                xp_blk, centroids, row < n_valid, k, jdt, acc)
        with scope("lloyd.psum"):
            sums, counts, inertia = fusion.packed_psum(
                [sums, counts, inertia], (axis,), quant=qk, chunks=ck,
                hier=hk)
        new_centroids, shift = _finish_update(sums, counts, centroids)
        return new_centroids, shift, inertia

    fn = jax.jit(
        shard_map(lloyd_step, mesh=comm.mesh,
                  in_specs=(comm.spec(2, 0), P()),
                  out_specs=(P(), P(), P()), check_vma=False),
        donate_argnums=(1,))
    _STEP_CACHE[key] = fn
    return fn


def _lloyd_fused_gspmd_fn(phys_shape, jdt, k, n_valid, comm, qk, ck, hk):
    """The tape-compiled Lloyd step for replicated (split=None) data:
    the GSPMD body compiled as one donated executable — replicated data
    places zero collectives, so there is nothing to pack; the codec
    tuples still key the program for uniformity."""
    key = ("fusedg", phys_shape, str(jdt), k, n_valid, comm.cache_key,
           qk, ck, hk)
    fn = _STEP_CACHE.get(key)
    if fn is not None:
        return fn
    single = _make_step_body(phys_shape, jdt, k, n_valid, comm, False)

    def lloyd_step(xp, centroids):
        new_centroids, inertia, shift = single(xp, centroids)
        return new_centroids, shift, inertia

    fn = jax.jit(lloyd_step, donate_argnums=(1,))
    _STEP_CACHE[key] = fn
    return fn


def _lloyd_eager_step(phys_shape, jdt, k, n_valid):
    """The SAME Lloyd mathematics dispatched op-by-op (unjitted jnp with
    GSPMD-placed collectives): the ``fit.step.dispatch`` degrade path.
    Returns the fit-step tuple
    ``(new_centroids, shift, inertia)``."""
    acc = _acc_dtype(jdt)

    def step(xp, centroids):
        row = jax.lax.broadcasted_iota(jnp.int32, (phys_shape[0], 1), 0)
        sums, counts, inertia = _lloyd_partial(
            xp, centroids, row < n_valid, k, jdt, acc)
        new_centroids, shift = _finish_update(sums, counts, centroids)
        return new_centroids, shift, inertia

    return step


def _stream_partial_fn(phys_shape, jdt, k, comm, split, qk, ck, hk):
    """The out-of-core epoch's per-chunk program: masked Lloyd partials
    over one chunk, the (sums, counts, inertia) family packed into one
    all-reduce, ADDED into donated device accumulators —
    ``(xp, centroids, n_valid, s_acc, c_acc, i_acc) -> updated accs``.
    ``n_valid`` is a TRACED scalar so the tail chunk shares the full
    chunks' program; the accumulators are donated so an epoch is one
    dispatch per chunk with zero host round-trips."""
    key = ("spart", phys_shape, str(jdt), k, comm.cache_key, split,
           qk, ck, hk)
    fn = _STEP_CACHE.get(key)
    if fn is not None:
        return fn
    acc = _acc_dtype(jdt)
    if split == 0:
        chunk = phys_shape[0] // comm.size
        axis = comm.axis_name

        def lloyd_stream(xp_blk, centroids, n_valid, s_acc, c_acc, i_acc):
            rank = jax.lax.axis_index(axis)
            row = rank * chunk + jax.lax.broadcasted_iota(
                jnp.int32, (chunk, 1), 0)
            sums, counts, inertia = _lloyd_partial(
                xp_blk, centroids, row < n_valid, k, jdt, acc)
            with scope("lloyd.psum"):
                sums, counts, inertia = fusion.packed_psum(
                    [sums, counts, inertia], (axis,), quant=qk, chunks=ck,
                    hier=hk)
            return s_acc + sums, c_acc + counts, i_acc + inertia

        fn = jax.jit(
            shard_map(lloyd_stream, mesh=comm.mesh,
                      in_specs=(comm.spec(2, 0), P(), P(), P(), P(), P()),
                      out_specs=(P(), P(), P()), check_vma=False),
            donate_argnums=(3, 4, 5))
    else:
        fn = jax.jit(_stream_partial_eager(phys_shape, jdt, k),
                     donate_argnums=(3, 4, 5))
    _STEP_CACHE[key] = fn
    return fn


def _stream_partial_eager(phys_shape, jdt, k):
    """GSPMD/global form of the streaming partial — unjitted it is the
    chunk program's eager degrade path."""
    acc = _acc_dtype(jdt)

    def lloyd_stream(xp, centroids, n_valid, s_acc, c_acc, i_acc):
        row = jax.lax.broadcasted_iota(jnp.int32, (phys_shape[0], 1), 0)
        sums, counts, inertia = _lloyd_partial(
            xp, centroids, row < n_valid, k, jdt, acc)
        return s_acc + sums, c_acc + counts, i_acc + inertia

    return lloyd_stream


def _stream_partial_legacy_fn(phys_shape, jdt, k):
    """The ``HEAT_TPU_FUSION_FIT=0`` streaming partial: the GSPMD body
    jitted plain — XLA-placed separate collectives, NO packed_psum, NO
    donation, no fusion keying — honoring the escape hatch's documented
    contract on the out-of-core path too."""
    key = ("spart-legacy", phys_shape, str(jdt), k)
    fn = _STEP_CACHE.get(key)
    if fn is None:
        fn = jax.jit(_stream_partial_eager(phys_shape, jdt, k))
        _STEP_CACHE[key] = fn
    return fn


def _assign_fn(phys_shape, jdt, k, n_valid, comm):
    """Final assignment pass: labels AND inertia against the same (final)
    centroids, so ``labels_``/``cluster_centers_``/``inertia_`` are mutually
    consistent (sklearn convention). The x^2 term does not change the
    argmin; it is added back only for the inertia."""
    key = ("assign", phys_shape, str(jdt), k, n_valid, comm.cache_key)
    fn = _STEP_CACHE.get(key)
    if fn is None:

        acc = _acc_dtype(jdt)

        def lloyd_assign(xp, centroids):
            row = jax.lax.broadcasted_iota(jnp.int32, (phys_shape[0],), 0)
            valid = row < n_valid
            with scope("lloyd.norms"):
                cacc = centroids.astype(acc)
                c2 = jnp.sum(cacc * cacc, axis=1)[None, :]
                xf = xp.astype(acc)
                x2 = jnp.sum(xf * xf, axis=1)
            with scope("lloyd.dist"):
                xc = jax.lax.dot_general(
                    xp, centroids.astype(jdt),
                    dimension_numbers=(((1,), (1,)), ((), ())),
                    preferred_element_type=acc)
                scores = c2 - 2.0 * xc
            with scope("lloyd.argmin"):
                labels = jnp.argmin(scores, axis=1)
            with scope("lloyd.inertia"):
                inertia = jnp.sum(
                    jnp.where(valid, x2 + jnp.min(scores, axis=1),
                              jnp.zeros((), acc)))
            return labels, inertia

        fn = jax.jit(lloyd_assign)
        _STEP_CACHE[key] = fn
    return fn


def _lloyd_fori_fn(phys_shape, jdt, k, n_valid, comm):
    """Lloyd iterations with a *runtime* trip count (``lax.fori_loop``).

    The whole hot loop is one XLA program compiled once and reused for any
    iteration count (the compiled-epoch discipline SURVEY.md §7 calls for,
    hard part 5). Audited by ``scripts/collective_audit.py``: one packed
    all-reduce an iteration at every device count."""
    sums_mode = _use_pallas_step(jdt) and _kmeans_sums_mode()
    block_rows = _kmeans_block_rows() if sums_mode else None
    key = ("fori", phys_shape, str(jdt), k, n_valid, comm.cache_key,
           sums_mode, block_rows)
    fn = _STEP_CACHE.get(key)
    if fn is None:
        if sums_mode:
            # shard_map OUTSIDE the loop: the valid mask is computed once
            # and the whole iteration sequence is one per-device program
            chunk = phys_shape[0] // comm.size
            axis = comm.axis_name

            def lloyd_fori(xp_blk, centroids, iters):
                rank = jax.lax.axis_index(axis)
                row = rank * chunk + jax.lax.broadcasted_iota(
                    jnp.int32, (chunk, 1), 0)
                mask = (row < n_valid).astype(xp_blk.dtype)

                def body(_, carry):
                    c, _, _ = carry
                    sums, counts, inertia = kmeans_step_tile(
                        xp_blk, c, mask, block_rows=block_rows,
                        sums_mode=sums_mode)
                    with scope("lloyd.psum"):
                        sums = jax.lax.psum(sums, axis)
                        counts = jax.lax.psum(counts, axis)
                        inertia = jax.lax.psum(inertia, axis)
                    new_c, shift = _finish_update(sums, counts, c)
                    return new_c, inertia, shift

                z = jnp.zeros((), jdt)
                return jax.lax.fori_loop(0, iters, body, (centroids, z, z))

            fn = jax.jit(shard_map(
                lloyd_fori, mesh=comm.mesh,
                in_specs=(comm.spec(2, 0), P(), P()),
                out_specs=(P(), P(), P()),
                check_vma=False))
        else:
            single = _make_step_body(phys_shape, jdt, k, n_valid, comm,
                                     sums_mode)

            def lloyd_fori(xp, centroids, iters):
                def body(_, carry):
                    c, _, _ = carry
                    return single(xp, c)

                z = jnp.zeros((), _acc_dtype(jdt))
                c, inertia, shift = jax.lax.fori_loop(
                    0, iters, body, (centroids, z, z))
                return c, inertia, shift

            fn = jax.jit(lloyd_fori)
        _STEP_CACHE[key] = fn
    return fn


class KMeans(_KCluster):
    """K-Means with Lloyd's algorithm (reference ``kmeans.py:12``).

    Parameters match the reference: ``n_clusters``, ``init`` ("random",
    "kmeans++", or a (k, d) DNDarray), ``max_iter``, ``tol``, ``random_state``.
    """

    def __init__(
        self,
        n_clusters: int = 8,
        init: Union[str, DNDarray] = "random",
        max_iter: int = 300,
        tol: float = 1e-4,
        random_state: Optional[int] = None,
    ):
        from ..spatial.distance import cdist

        super().__init__(
            metric=lambda x, y: cdist(x, y, quadratic_expansion=True),
            n_clusters=n_clusters,
            init=init,
            max_iter=max_iter,
            tol=tol,
            random_state=random_state,
        )

    def _lloyd_dispatcher(self, phys_shape, jdt, n, comm, split):
        """The per-iteration step callable ``(xp, centroids) ->
        (new_centroids, shift, inertia)``. Under ``fusion.fit_enabled()``
        it is a ``fusion.fit_step_call`` dispatch of the donated,
        packed-collective executable (key lookup + one dispatch per
        Lloyd iteration, ``fit.step.dispatch`` degrading to the eager
        op-by-op iteration); with the engine off it is the legacy
        GSPMD step program, bitwise today's behavior."""
        k = self.n_clusters
        if not fusion.fit_enabled():
            legacy = _lloyd_step_fn(phys_shape, jdt, k, n, comm)

            def legacy_step(xp, centroids):
                new_centroids, inertia, shift = legacy(xp, centroids)
                return new_centroids, shift, inertia

            return legacy_step
        sums_mode = _use_pallas_step(jdt) and _kmeans_sums_mode()
        block_rows = _kmeans_block_rows() if sums_mode else None
        builder = _lloyd_fused_fn if split == 0 else _lloyd_fused_gspmd_fn
        eager = _lloyd_eager_step(phys_shape, jdt, k, n)

        def step(xp, centroids):
            return fusion.fit_step_call(
                ("kmeans.lloyd", phys_shape, str(jdt), k, n,
                 comm.cache_key, split, sums_mode, block_rows),
                lambda qk, ck, hk: builder(
                    phys_shape, jdt, k, n, comm, qk, ck, hk),
                (xp, centroids), eager)

        return step

    def fit(self, x: DNDarray) -> "KMeans":
        """Lloyd iteration to convergence (reference ``kmeans.py:102-139``):
        the shared ``_run_lloyd`` driver dispatching ONE compiled step per
        iteration. The per-iteration ``float(shift)`` read doubles as the
        program serialization sync (see ``_run_lloyd``), including when
        ``tol < 0`` disables the convergence break (the benchmarks'
        run-all-iterations mode)."""
        if not isinstance(x, DNDarray):
            raise TypeError(f"input needs to be a DNDarray, but was {type(x)}")
        if x.ndim != 2:
            raise ValueError("input needs to be 2-dimensional (n_samples, n_features)")
        with span("kmeans.fit"):
            return self._fit(x)

    def _fit(self, x: DNDarray) -> "KMeans":
        if x.split not in (None, 0):
            x = x.resplit(0)

        self._initialize_cluster_centers(x)
        jdt = x.dtype.jax_type()
        if types.heat_type_is_exact(x.dtype):
            jdt = jnp.dtype(jnp.float32)
        xp = x.larray.astype(jdt)
        n = x.shape[0]
        # fresh buffer: the fused step DONATES the carried centroids, and
        # the seed array may alias self._cluster_centers' storage
        centroids = jnp.array(self._cluster_centers._logical(), jdt)
        step = self._lloyd_dispatcher(xp.shape, jdt, n, x.comm, x.split)
        centroids, _, it = self._run_lloyd(step, xp, centroids)

        self._cluster_centers = DNDarray.from_logical(centroids, None, x.device, x.comm)
        with span("kmeans.assign"):
            labels, inertia = _assign_fn(
                xp.shape, jdt, self.n_clusters, n, x.comm)(xp, centroids)
            self._labels = DNDarray(
                labels, (n,), types.canonical_heat_type(labels.dtype),
                0 if x.split == 0 else None, x.device, x.comm,
            )
            self._inertia = float(inertia)
        self._n_iter = it
        return self

    # ------------------------------------------------------------------ #
    # out-of-core streaming fit: the EXACT epoch form                    #
    # ------------------------------------------------------------------ #
    def _stream_dtype(self, chunk: DNDarray):
        jdt = chunk.dtype.jax_type()
        if types.heat_type_is_exact(chunk.dtype):
            jdt = jnp.dtype(jnp.float32)
        return jnp.dtype(jdt)

    def _stream_accumulate(self, chunks, centroids, meta):
        """One full pass over the stream: the additive (sums, counts,
        inertia) family accumulates chunk-by-chunk into donated device
        buffers — one compiled dispatch per chunk, zero host round-trips
        inside the pass (``HEAT_TPU_FUSION_FIT=0`` runs the plain-jit
        legacy partial: separate collectives, no donation)."""
        k = self.n_clusters
        jdt = meta["jdt"]
        acc = _acc_dtype(jdt)
        comm = meta["comm"]
        sums = jnp.zeros((k, meta["d"]), acc)
        counts = jnp.zeros((k,), acc)
        inertia = jnp.zeros((), acc)
        for chunk in chunks():
            xp = chunk.larray.astype(jdt)
            split = 0 if chunk.split == 0 else None
            nvalid = jnp.asarray(chunk.shape[0], jnp.int32)
            args = (xp, centroids, nvalid, sums, counts, inertia)
            if fusion.fit_enabled():
                sums, counts, inertia = fusion.fit_step_call(
                    ("kmeans.stream", xp.shape, str(jdt), k,
                     comm.cache_key, split),
                    lambda qk, ck, hk, _s=xp.shape, _sp=split:
                        _stream_partial_fn(_s, jdt, k, comm, _sp,
                                           qk, ck, hk),
                    args, _stream_partial_eager(xp.shape, jdt, k))
            else:
                sums, counts, inertia = _stream_partial_legacy_fn(
                    xp.shape, jdt, k)(*args)
        return sums, counts, inertia

    def _stream_epoch(self, chunks, centroids, meta):
        """One EXACT full-batch Lloyd epoch out-of-core: the centroids
        update ONCE per epoch from the accumulated pass, so the streamed
        fit is value-equal to the in-memory fit up to float summation
        reassociation (``doc/analytics.md`` numerics contract)."""
        sums, counts, _ = self._stream_accumulate(chunks, centroids, meta)
        return _finish_update(sums, counts, centroids)

    def _stream_finalize(self, chunks, centroids, meta):
        """One extra accumulation pass against the FINAL centroids so
        ``inertia_`` means the same thing as after ``fit()`` (whose
        final assignment pass scores the final centroids) — without it
        the streamed figure would be one Lloyd update stale."""
        _, _, inertia = self._stream_accumulate(chunks, centroids, meta)
        self._inertia = float(inertia)
