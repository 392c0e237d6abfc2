"""Explicit reshard planner: split-layout changes as planned collectives.

``DNDarray.resplit``/``resplit_`` used to hand every layout change to GSPMD
as a blind ``out_shardings`` constraint (the old ``_reshard_physical`` in
``dndarray.py``), which XLA is free to lower as an all-gather — materializing
the full global array on every device: O(N) peak memory and bandwidth per
device. "Memory-efficient array redistribution through portable collective
communication" (arXiv:2112.01075) shows the same reshard decomposes into a
single all-to-all plus local slicing at O(N/p) peak. This module plans each
``(from_split, to_split)`` case explicitly inside ``shard_map``:

=================  =====================================================
case               program (collectives emitted)
=================  =====================================================
split j → split k  local pad of axis k → ONE ``all_to_all``
                   (split_axis=k, concat_axis=j) → local slice of axis j.
                   Zero all-gathers; payload is the O(N/p) local block.
None → split k     local dynamic-slice of the replicated array per device.
                   ZERO collectives.
split j → None     ``all_gather`` along j + local slice — the only case
                   where gathering is the semantics, not an accident.
=================  =====================================================

Why the split→split decomposition is correct: device ``i`` owns the
canonical (ceil-chunked, tail-padded) rows ``i*c_j..(i+1)*c_j`` of axis
``j``; the target wants device ``e`` to own columns ``e*c_k..(e+1)*c_k`` of
axis ``k``. A tiled ``all_to_all`` with ``split_axis=k, concat_axis=j``
sends exactly sub-block (my j-rows × your k-cols) to each peer and
concatenates received pieces in sender order — which IS ascending global
j-order, so the result is each device's full-j / own-k canonical block, up
to the tail padding of axis j (sliced off locally) and of axis k (zero-
padded locally before the exchange so the tile split divides evenly).

Plans compile once per ``(physical shape, dtype, gshape, from, to, mesh)``
and are cached; hit/miss counts feed :mod:`heat_tpu.utils.metrics`
(counters ``resharding.plan_hits`` / ``resharding.plan_misses``) and
:func:`plan_cache_stats`. The GSPMD-blind program is kept as
:func:`gspmd_reshard_fn` — the audited baseline
(``scripts/collective_audit.py --resplit``) and the fallback for degenerate
layouts (single device, zero-size arrays, non-canonical physicals).
"""

from __future__ import annotations

from typing import Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from ._compat import shard_map

__all__ = [
    "reshard",
    "planned_reshard_fn",
    "gspmd_reshard_fn",
    "plan_kind",
    "plan_cache_stats",
    "reset_plan_cache",
]

# compiled plans keyed by (phys_shape, dtype, gshape, from, to, mesh)
_PLAN_CACHE: dict = {}
# GSPMD-blind baseline programs, same key shape (kept for audit + fallback)
_GSPMD_CACHE: dict = {}
_HITS = 0
_MISSES = 0

_FAULTS = None  # lazy module handle (utils imports back into core)


def _faults():
    global _FAULTS
    if _FAULTS is None:
        from ..utils import faults

        _FAULTS = faults
    return _FAULTS


def plan_cache_stats() -> dict:
    """Plan-cache observability: hits/misses since process start (also
    mirrored into the default metrics registry) and live entry count."""
    return {"hits": _HITS, "misses": _MISSES, "entries": len(_PLAN_CACHE)}


def reset_plan_cache() -> None:
    global _HITS, _MISSES
    _PLAN_CACHE.clear()
    _GSPMD_CACHE.clear()
    _HITS = 0
    _MISSES = 0


def plan_kind(gshape, from_split: Optional[int], to_split: Optional[int],
              comm) -> str:
    """Which program :func:`reshard` would run for this layout change:
    ``"noop"`` / ``"all_to_all"`` / ``"local_slice"`` / ``"all_gather"`` /
    ``"gspmd"`` (degenerate fallback)."""
    if from_split == to_split:
        return "noop"
    if not _plannable(gshape, from_split, to_split, comm):
        return "gspmd"
    if from_split is None:
        return "local_slice"
    if to_split is None:
        return "all_gather"
    return "all_to_all"


def _plannable(gshape, from_split, to_split, comm) -> bool:
    """The explicit programs assume a multi-device mesh and a non-empty
    canonical layout; everything else (p==1, zero-size arrays, 0-d) is
    local-only anyway and keeps the simple slice→pad→constrain program."""
    if comm.size <= 1 or len(gshape) == 0:
        return False
    if any(int(s) <= 0 for s in gshape):
        return False
    return True


def _slice_logical(x, gshape):
    """Physical → logical: cut tail padding (static shapes)."""
    if tuple(x.shape) != tuple(gshape):
        x = jax.lax.slice(x, (0,) * x.ndim, tuple(gshape))
    return x


def _pad_axis(x, axis: int, target: int):
    """Zero-pad ``axis`` up to ``target`` rows (padding is don't-care)."""
    pad = target - x.shape[axis]
    if pad <= 0:
        return x
    cfg = [(0, pad if i == axis else 0, 0) for i in range(x.ndim)]
    return jax.lax.pad(x, jnp.zeros((), x.dtype), cfg)


def gspmd_reshard_fn(phys_shape, jdt, gshape, from_split, to_split, comm):
    """The pre-planner program: slice-off-old-padding → pad-new-axis →
    ``out_shardings`` constraint, one jitted XLA program with GSPMD choosing
    the collectives. Kept as the audited baseline and the degenerate-layout
    fallback."""
    gshape = tuple(int(s) for s in gshape)
    key = (tuple(phys_shape), str(jdt), gshape, from_split, to_split,
           comm.cache_key)
    fn = _GSPMD_CACHE.get(key)
    if fn is not None:
        return fn
    out_sharding = comm.sharding(len(gshape), to_split)

    def _go(x):
        x = _slice_logical(x, gshape)
        if to_split is not None:
            x = _pad_axis(x, to_split, comm.padded_size(gshape[to_split]))
        return x

    if any(s == 0 for s in gshape):
        # a zero-size result holds no data: XLA makes the empty output
        # replicated whatever ``out_shardings`` asks, and jax >= 0.9
        # asserts on that override. Nothing moves, so compute the empty
        # shape and place it under the target layout directly.
        go = jax.jit(_go)

        def fn(x):
            return jax.device_put(go(x), out_sharding)
    else:
        fn = jax.jit(_go, out_shardings=out_sharding)
    _GSPMD_CACHE[key] = fn
    return fn


def _build_plan(phys_shape, jdt, gshape, from_split, to_split, comm):
    """Compile the explicit shard_map program for one layout change."""
    p = comm.size
    ndim = len(gshape)

    if from_split is None:
        # None → k: every device slices its own canonical chunk out of the
        # replicated array. ZERO collectives.
        k = to_split
        c = comm.chunk_size(gshape[k])

        def body_slice(x):
            me = jax.lax.axis_index(comm.axis_name)
            x = _pad_axis(x, k, c * p)
            return jax.lax.dynamic_slice_in_dim(x, me * c, c, axis=k)

        return jax.jit(shard_map(
            body_slice, mesh=comm.mesh, in_specs=comm.spec(ndim, None),
            out_specs=comm.spec(ndim, k), check_vma=False))

    if to_split is None:
        # j → None: the only case where gathering IS the semantics.
        j = from_split

        def body_gather(x):
            full = jax.lax.all_gather(x, comm.axis_name, axis=j, tiled=True)
            return _slice_logical(full, gshape)

        return jax.jit(shard_map(
            body_gather, mesh=comm.mesh, in_specs=comm.spec(ndim, j),
            out_specs=comm.spec(ndim, None), check_vma=False))

    # j → k: the 2112.01075 decomposition — one all_to_all + local reslice.
    j, k = from_split, to_split
    c_k = comm.chunk_size(gshape[k])

    def body_a2a(x):
        # local zero-pad of axis k so the tile split divides evenly
        x = _pad_axis(x, k, c_k * p)
        # ONE all_to_all: my j-rows × peer e's k-cols go to e; received
        # pieces concatenate along j in sender (= global j) order
        x = jax.lax.all_to_all(x, comm.axis_name, split_axis=k,
                               concat_axis=j, tiled=True)
        # axis j is now the full padded extent locally: cut its tail padding
        if x.shape[j] != gshape[j]:
            x = jax.lax.slice_in_dim(x, 0, gshape[j], axis=j)
        return x

    return jax.jit(shard_map(
        body_a2a, mesh=comm.mesh, in_specs=comm.spec(ndim, j),
        out_specs=comm.spec(ndim, k), check_vma=False))


def planned_reshard_fn(phys_shape, jdt, gshape, from_split, to_split, comm):
    """Cached compiled reshard program ``physical(from) -> physical(to)``.

    Falls back to :func:`gspmd_reshard_fn` for degenerate layouts (see
    :func:`_plannable`); otherwise builds the explicit program for the
    ``(from, to)`` case. Counters ``resharding.plan_hits`` /
    ``resharding.plan_misses`` track cache behavior.
    """
    global _HITS, _MISSES
    # lazy: utils.checkpointing imports back into core — a module-level
    # import here would cycle during package init
    from ..utils import metrics

    gshape = tuple(int(s) for s in gshape)
    key = (tuple(phys_shape), str(jdt), gshape, from_split, to_split,
           comm.cache_key)
    fn = _PLAN_CACHE.get(key)
    if fn is not None:
        _HITS += 1
        metrics.inc("resharding.plan_hits")
        return fn
    _MISSES += 1
    metrics.inc("resharding.plan_misses")
    if not _plannable(gshape, from_split, to_split, comm):
        fn = gspmd_reshard_fn(phys_shape, jdt, gshape, from_split, to_split,
                              comm)
    else:
        try:
            _faults().check("reshard.plan.build")
            fn = _build_plan(phys_shape, jdt, gshape, from_split, to_split,
                             comm)
        except Exception:
            # HARDENED FAILURE DOMAIN (doc/robustness.md): the explicit
            # plan is an optimization — a failed plan build degrades to
            # the audited GSPMD baseline program (value-identical layout
            # move, XLA-placed collectives) instead of failing the
            # resplit. The fallback is cached under the same key so a
            # hot loop pays the failed build once.
            metrics.inc("resharding.plan_build_fallbacks")
            fn = gspmd_reshard_fn(phys_shape, jdt, gshape, from_split,
                                  to_split, comm)
    _PLAN_CACHE[key] = fn
    return fn


def reshard(parray, gshape, from_split: Optional[int],
            to_split: Optional[int], comm):
    """Move a canonical physical array between split layouts, on device.

    The planner entry point used by ``DNDarray.resplit``/``resplit_``, the
    op-engine split alignment and the manipulations reshape path. Returns
    the physical array of the target layout (tail-padded along
    ``to_split``).
    """
    if from_split == to_split:
        return parray
    gshape = tuple(int(s) for s in gshape)
    # a physical that does not match the canonical from-layout (e.g. a
    # zero-size axis placed replicated by ``from_logical``) cannot feed the
    # shard_map programs — the GSPMD constraint program handles any input
    expected = list(gshape)
    if from_split is not None and gshape and all(s > 0 for s in gshape):
        expected[from_split] = comm.padded_size(gshape[from_split])
    if tuple(parray.shape) != tuple(expected):
        fn = gspmd_reshard_fn(parray.shape, parray.dtype, gshape, from_split,
                              to_split, comm)
    else:
        fn = planned_reshard_fn(parray.shape, parray.dtype, gshape,
                                from_split, to_split, comm)
    try:
        _faults().check("reshard.dispatch")
        return fn(parray)
    except Exception:
        # HARDENED FAILURE DOMAIN (doc/robustness.md): a failed collective
        # dispatch gets ONE retry through the GSPMD baseline program (a
        # distinct executable — if the planned program itself is the
        # problem, the retry does not re-run it). A second failure is a
        # real device/runtime error and surfaces.
        from ..utils import metrics

        metrics.inc("resharding.dispatch_fallbacks")
        return gspmd_reshard_fn(parray.shape, parray.dtype, gshape,
                                from_split, to_split, comm)(parray)
