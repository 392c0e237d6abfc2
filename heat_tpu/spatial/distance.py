"""Pairwise distance matrices (reference ``heat/spatial/distance.py``).

The reference's ``_dist`` (``distance.py:209-494``) is a systolic **ring**:
each iteration sends the moving block to ``(rank+iter) % size`` and computes
one local tile (``:280-362``) — the exact communication skeleton of ring
attention. The TPU-native version is a ``shard_map`` over the mesh whose body
unrolls the ring as ``size`` ppermute steps; XLA overlaps the permute DMA
with the tile GEMM (double buffering), and the tile itself is a
matmul-expansion on the MXU.

Replicated-``Y`` inputs (the KMeans inner loop) skip the ring entirely: one
local GEMM tile per shard, zero communication — same as the reference's
replicated fast path.
"""

from __future__ import annotations

from typing import Callable, Optional

import numpy as np

import jax
import jax.numpy as jnp
from ..core._compat import shard_map

from ..core import types
from ..core.dndarray import DNDarray
from ..core.pallas_kernels import cdist_tile, pallas_enabled

__all__ = ["cdist", "manhattan", "rbf"]

# cache of jitted ring kernels keyed by (shapes, dtype, metric, comm key)
_RING_CACHE: dict = {}


def _l2_tile(x, y, expand: bool, sqrt: bool, keep_acc: bool = False):
    """One (tile_x, tile_y) block of pairwise L2 distances (squared when
    ``sqrt=False`` — the KMeans/rbf form that skips the root). Half
    precision keeps bf16 HBM/MXU inputs but accumulates in f32
    (``types.accumulation_dtype``); the result casts back to the input
    dtype unless ``keep_acc`` (rbf applies exp before narrowing)."""
    acc = types.accumulation_dtype(x.dtype)
    out_dt = acc if keep_acc else x.dtype
    if expand:
        if pallas_enabled():
            # fused Pallas tile: norms + MXU GEMM (+ sqrt) in one VMEM
            # pass, accumulated in f32; rbf (keep_acc) gets the f32
            # output so the exp sees unrounded distances
            return cdist_tile(x, y, sqrt=sqrt, out_dtype=str(out_dt))
        # |x-y|² = |x|² + |y|² - 2·x·yᵀ — the GEMM form (MXU)
        xf, yf = x.astype(acc), y.astype(acc)
        x2 = jnp.sum(xf * xf, axis=1, keepdims=True)
        y2 = jnp.sum(yf * yf, axis=1, keepdims=True).T
        xy = jax.lax.dot_general(
            x, y, dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=acc)
        d2 = jnp.maximum(x2 + y2 - 2.0 * xy, 0.0)
        return (jnp.sqrt(d2) if sqrt else d2).astype(out_dt)
    diff = x.astype(acc)[:, None, :] - y.astype(acc)[None, :, :]
    d2 = jnp.sum(diff * diff, axis=-1)
    return (jnp.sqrt(d2) if sqrt else d2).astype(out_dt)


def _euclidean_tile(x, y, expand: bool):
    return _l2_tile(x, y, expand, sqrt=True)


def _manhattan_tile(x, y, expand: bool):
    acc = types.accumulation_dtype(x.dtype)
    diff = jnp.abs(x.astype(acc)[:, None, :] - y.astype(acc)[None, :, :])
    return jnp.sum(diff, axis=-1).astype(x.dtype)


def _gaussian_tile(sigma: float):
    def tile(x, y, expand: bool):
        # exp runs on the f32-accumulated d2 — rounding d2 to bf16 first
        # would put ~20% error on the kernel value at large exponents
        d2 = _l2_tile(x, y, expand, sqrt=False, keep_acc=True)
        return jnp.exp(-d2 / (2.0 * sigma * sigma)).astype(x.dtype)

    return tile


def _dist(X: DNDarray, Y: Optional[DNDarray], tile_fn: Callable, expand: bool, metric_key=("euclidean",)) -> DNDarray:
    """Distance-matrix driver (reference ``_dist``, ``distance.py:209``)."""
    if not isinstance(X, DNDarray):
        raise TypeError(f"X must be a DNDarray, got {type(X)}")
    if X.ndim != 2:
        raise NotImplementedError(f"X must be 2-dimensional, got {X.ndim}")

    symmetric = Y is None
    if Y is None:
        Y = X
    if not isinstance(Y, DNDarray):
        raise TypeError(f"Y must be a DNDarray, got {type(Y)}")
    if Y.ndim != 2:
        raise NotImplementedError(f"Y must be 2-dimensional, got {Y.ndim}")
    if X.shape[1] != Y.shape[1]:
        raise ValueError(f"feature dimensions differ: {X.shape[1]} != {Y.shape[1]}")

    promoted = types.promote_types(X.dtype, Y.dtype)
    if types.heat_type_is_exact(promoted):
        promoted = types.float32
    jdt = promoted.jax_type()
    n, m = X.shape[0], Y.shape[0]
    comm = X.comm

    if X.split is None and Y.split is None:
        d = tile_fn(X._logical().astype(jdt), Y._logical().astype(jdt), expand)
        return DNDarray.from_logical(d, None, X.device, comm)

    if X.split == 1 or Y.split == 1:
        X = X.resplit(0) if X.split == 1 else X
        Y = Y.resplit(0) if Y.split == 1 else Y

    if X.split is None and Y.split == 0:
        # compute the transposed problem with the fast row-split path
        return _dist(Y, X, tile_fn, expand, metric_key).T

    # X.split == 0 from here
    if Y.split is None:
        # local tiles only (KMeans inner loop): one GEMM per shard
        fn = _local_kernel(X, Y, tile_fn, expand, jdt, comm, metric_key)
        d_phys = fn(X.larray, Y.larray)
        return DNDarray(d_phys, (n, m), promoted, 0, X.device, comm)

    # ring: X stationary, Y circulates (reference ``distance.py:280-362``)
    fn = _ring_kernel(X, Y, tile_fn, expand, jdt, comm, metric_key)
    d_phys = fn(X.larray, Y.larray)
    return DNDarray(d_phys, (n, m), promoted, 0, X.device, comm)


def _local_kernel(X, Y, tile_fn, expand, jdt, comm, metric_key):
    key = (
        "local", X.larray.shape, Y.larray.shape, str(jdt), metric_key, expand,
        comm.cache_key, pallas_enabled(),
    )
    fn = _RING_CACHE.get(key)
    if fn is None:
        out_sharding = comm.sharding(2, 0)

        def _go(xp, yp):
            return tile_fn(xp.astype(jdt), yp.astype(jdt), expand)

        fn = jax.jit(_go, out_shardings=out_sharding)
        _RING_CACHE[key] = fn
    return fn


def _ring_kernel(X, Y, tile_fn, expand, jdt, comm, metric_key):
    """shard_map ring over the mesh: size unrolled ppermute+tile steps."""
    size = comm.size
    m = Y.shape[0]
    c_y = Y.larray.shape[0] // size
    m_pad = Y.larray.shape[0]
    key = (
        "ring", X.larray.shape, Y.larray.shape, str(jdt), metric_key, expand,
        comm.cache_key, pallas_enabled(),
    )
    fn = _RING_CACHE.get(key)
    if fn is None:
        spec = comm.spec(2, 0)
        axis = comm.axis_name
        perm = [(j, (j + 1) % size) for j in range(size)]

        def body(x_blk, y_blk):
            x_blk = x_blk.astype(jdt)
            y_cur = y_blk.astype(jdt)
            if size == 1:
                # single-device (the benchmark's cdist cell): the tile IS the
                # whole output at its own shape (one shard is never padded),
                # so nothing may follow the kernel — the zeros buffer +
                # dynamic_update_slice + final slice of the general ring
                # would each be a full extra pass over the n*m matrix
                return tile_fn(x_blk, y_cur, expand)
            me = jax.lax.axis_index(axis)
            out = jnp.zeros((x_blk.shape[0], m_pad), jdt)
            for step in range(size):
                # block currently held came from device (me - step) % size
                src = (me - step) % size
                tile = tile_fn(x_blk, y_cur, expand)
                zero = jnp.zeros((), src.dtype)
                out = jax.lax.dynamic_update_slice(out, tile, (zero, src * c_y))
                if step != size - 1:
                    y_cur = jax.lax.ppermute(y_cur, axis, perm)
            return out[:, :m]  # identity slice when m_pad == m (XLA elides)

        sm = shard_map(
            body, mesh=comm.mesh, in_specs=(spec, spec), out_specs=spec, check_vma=False
        )
        fn = jax.jit(sm)
        _RING_CACHE[key] = fn
    return fn


def cdist(X: DNDarray, Y: Optional[DNDarray] = None, quadratic_expansion: bool = False) -> DNDarray:
    """Euclidean distance matrix (reference ``cdist``, ``distance.py:136``)."""
    return _dist(X, Y, _euclidean_tile, quadratic_expansion, ("euclidean",))


def manhattan(X: DNDarray, Y: Optional[DNDarray] = None, expand: bool = False) -> DNDarray:
    """Manhattan distance matrix (reference ``manhattan``, ``distance.py:186``)."""
    return _dist(X, Y, _manhattan_tile, False, ("manhattan",))


def rbf(
    X: DNDarray,
    Y: Optional[DNDarray] = None,
    sigma: float = 1.0,
    quadratic_expansion: bool = False,
) -> DNDarray:
    """Gaussian (RBF) kernel matrix (reference ``rbf``, ``distance.py:159``)."""
    return _dist(X, Y, _gaussian_tile(sigma), quadratic_expansion, ("rbf", float(sigma)))
