"""Fused lazy op-chain engine: trace ``ht.*`` chains into one cached program.

Eagerly, every elementwise ``ht.*`` op is its own XLA dispatch: a 16-op
chain costs 16 program launches and 15 materialized intermediates with
zero cross-op fusion — exactly the op-by-op overhead the HeAT reference
accepts on MPI+torch but that XLA is built to eliminate. This module makes
the op engine *deferred* instead: ``__local_op`` / ``__binary_op`` (and
the split-preserving ``__cum_op``) record :class:`_Node` entries into a
per-array expression DAG, and the first **materialization point** flushes
the whole chain as ONE jitted program.

Materialization points (flush triggers)
---------------------------------------
Everything in the codebase reads the physical array through
``DNDarray.larray``, so the property is the single choke point: resplits
and split-changing ops, ``out=`` / ``where=`` (the op engine falls back to
eager there), ``.numpy()`` / ``__array__`` / ``item()`` / printing,
comparisons used in control flow (``__bool__``), and the tape-depth cap
(``HEAT_TPU_FUSION_MAX_OPS``, default 32). Padding discipline survives by
construction: recorded nodes never read across the split axis *blindly* —
a reduction records a neutral-element **mask node** over the canonical
padding first (the tape form of ``DNDarray.filled``), a cum over the
split axis or an alignment resplit materializes its inputs first, so
collective placement stays exactly where the explicit resharding planner
(arXiv:2112.01075) put it, and fused programs for split-preserving chains
lower with ZERO collectives (audited in ``tests/test_fusion.py``).

Reduction nodes (terminal collectives on the tape)
--------------------------------------------------
``__reduce_op`` (sum/prod/max/min/any/all and the mean/var/std/norm
family built on them) records a **reduce node** instead of forcing
``filled()``-materialization. A flush whose DAG contains a reduce node
over the split axis compiles the whole chain as ONE ``shard_map`` program
— elementwise chain on shard-local blocks, neutral-element pad masking
(global-index iota, reusing the pad bookkeeping so uneven gshapes stay
correct), shard-local reduce, then one ``lax.psum``/``pmax``/``pmin``.
Mutually independent same-kind reductions in one DAG (weighted average's
``sum(x*w)``/``sum(w)``, single-pass var's ``sum(x²)``/``sum(x)``) are
packed into ONE flattened collective per phase, so XLA emits exactly one
(tuple-fused) all-reduce and the O(n) elementwise intermediate never
exists. Heat itself merges split-axis reductions into a single MPI
Allreduce (arXiv:2007.13552); folding the combiner into the collective is
where the traffic win lives (arXiv:2004.09362). Reduce tapes the
translator cannot prove shard_map-safe (unregistered combiner such as
``prod``, exotic operand layouts) still fuse as one ``jax.jit`` program
with GSPMD-placed collectives — never eagerly. Opt-out:
``HEAT_TPU_FUSION_REDUCE=0`` restores the eager ``filled()`` flush.

Contraction nodes (planned distributed GEMM on the tape)
--------------------------------------------------------
``linalg.matmul`` (and through it ``dot``/``outer``, plus the 2-operand
``einsum``/``tensordot`` paths) records a **contract node** instead of
forcing ``filled(0)``-materialization of both operand tapes. The per-
split-case collective plan is explicit in the shard_map translation —
the by-construction discipline the reference Heat spends ~670 lines of
hand-scheduled Bcasts on (arXiv:2007.13552, ``basics.py:424-1095``):

* ``a.split=0`` (× replicated ``b``) or ``b.split=1`` (× replicated
  ``a``): local GEMM on blocks, output keeps the split, ZERO collectives;
* contracted-dim sharded (``a.split=1`` / ``b.split=0`` in any
  combination with a replicated other side): shard-local partial GEMM +
  ``lax.psum``, PACKED into the same phase-sorted flattened collective as
  any independent same-kind reductions on the tape (arXiv:2004.09362);
* mixed 2-D layouts outside the block model fall back to ONE plain-jit
  GSPMD program, exactly like non-translatable reduce tapes. Batched
  (>2-D) matmul never records — it dispatches eagerly on shard-local
  blocks in ``linalg.basics._matmul_batched``.

Zero-fill masking of contracted-axis padding rides the tape as MASK
nodes (skipped entirely when the operand's ``pad_is_zero`` bit proves
the buffer is already canonically zero-padded), so ``x @ w + b`` then an
activation then a split-axis reduction compiles as ONE cached executable
with exactly the planner's collectives. Opt-out:
``HEAT_TPU_FUSION_CONTRACT=0`` restores the eager ``_filled0`` GEMM.

Resplit nodes (the reshard planner folded into the DAG)
-------------------------------------------------------
``DNDarray.resplit``/``resplit_`` on a PENDING tape records a **RESPLIT
node** (:func:`record_resplit`) instead of flushing: the reshard
planner (:mod:`.resharding`, arXiv:2112.01075) already knows the exact
one-collective move per ``(from, to)`` pair, and ``_plan_sm`` translates
it mid-body inside the one shard_map program — local pad → ONE
``lax.all_to_all`` → local reslice for split→split, a zero-collective
local ``dynamic_slice`` for None→split, ``all_gather`` for split→None —
with per-node split state switching from the source to the target layout
downstream of the node. ``chain → resplit → chain → reduce`` therefore
compiles as ONE executable containing exactly the planner's collective
count, and the op-engine's binary-op alignment resplits plus the
manipulations family's pre-alignment resplits stop being flush barriers.
Non-translatable cases (degenerate layouts, non-canonical physicals,
foreign meshes) decline recording and take the historic
flush-then-planned-resplit path — correctness never depends on the
translation. Opt-out: ``HEAT_TPU_FUSION_RESPLIT=0``; counters
``op_engine.fusion_resplit_nodes`` / ``_fallbacks`` / ``_flushes``.

Program identity and caching
----------------------------
A flush compiles at most once per *chain signature*: a structural key over
(comm cache key, per-leaf ``(shape, dtype, weak, sharding)``, the node
list ``(op, arg slots, static kwargs)``, output slots, donation slots),
served from a generalized :class:`~heat_tpu.utils.program_cache.ProgramCache`
(``fusion.program_hits`` / ``_misses`` / ``_compiles`` counters). Python
scalars enter the program as 0-d *arguments* (weak-typed, value-cached) —
never as baked constants — so XLA cannot constant-fold them differently
from the eager dispatch (e.g. div-by-const → reciprocal-multiply), and one
program serves every scalar value.

Donation
--------
Leaves whose owning DNDarray is dead and whose buffer the tape provably
holds the only references to (exact ``sys.getrefcount`` accounting) are
donated to XLA, so ``x = ht.exp(x * 2)``-style rebinding chains reuse the
input buffer. Interior nodes never materialize at all unless another live
array shares them.

Numerics
--------
Fused results are bitwise-equal to eager for integer/bool dtypes and for
float chains without a multiply feeding directly into an add/sub. Where
such pairs fuse, XLA's backend contracts them into an FMA — a *more*
accurate single rounding that can differ from eager (and NumPy) by 1 ulp.
``tests/test_fusion.py`` pins both properties; ``doc/fusion.md`` documents
the contract.

Differentiable tapes (whole-train-step tracing)
-----------------------------------------------
:func:`trace_step` compiles an entire user train step — loss, gradients
via :func:`value_and_grad`, optimizer update — into ONE cached, donated
executable over the ``DNDarray`` leaves of its arguments: the classic JAX
one-jitted-train-step idiom the eager NumPy surface otherwise denies.
Tracing reuses the op engine itself: under a jax trace every recorded-op
entry point declines (tracers must never be captured into a cross-call
tape), so the step body dispatches through the *eager* op semantics onto
abstract leaves and the whole step lowers as one jaxpr. Gradient
all-reduces for the model-level fused steps
(:meth:`heat_tpu.nn.TransformerLM.make_train_step`,
:class:`heat_tpu.nn.DataParallel`) are PACKED by :func:`packed_psum` —
one flattened collective per dtype, the train-step form of the flush
body's phase-barrier packing (arXiv:2004.09362). Step bodies that cannot
trace (host branching on values, ``.numpy()``/``float()`` round-trips)
fall back to the eager path, counted in
``op_engine.fusion_step_fallbacks``. Opt-out: ``HEAT_TPU_FUSION_STEP=0``.

Quantized packed collectives (block-scaled wire formats)
--------------------------------------------------------
``HEAT_TPU_QUANT_COLLECTIVES`` selects an opt-in wire codec for the
packed float all-reduces this engine emits — the flush body's
:func:`packed all-reduce <_sm_body>` packing and every
:func:`packed_psum` call site (the model-level fused train steps,
``DataParallel.step``, DASO's slow-tier blending). EQuARX
(arXiv:2506.17615) shows block-scaled quantized all-reduce recovers ~2×
collective bytes at negligible accuracy cost, and the decomposition it
rides is exactly the generalized-allreduce structure
(arXiv:2004.09362) the phase scheduler already plans around:

* ``bf16`` — the payload crosses the wire as ONE bf16 all-reduce
  (encode = round-to-nearest downcast, decode = upcast): half the f32
  bytes on hardware with native bf16 reductions (TPU ICI).
* ``int8`` — block-scaled (``HEAT_TPU_QUANT_BLOCK``-element blocks,
  default 128, bf16 scales riding the payload): encode int8 → reduce-scatter-style ``all_to_all`` over the
  shard axis → exact f32 combine of the dequantized summand blocks →
  bf16 ``all_gather`` of the combined chunks → decode. The float wire
  legs travel bitcast to ``u16`` so XLA:CPU's float normalization
  cannot silently upcast them back to f32.

Integer/bool collectives, ``pmax``/``pmin``, f64, and payloads below
``HEAT_TPU_QUANT_MIN_NUMEL`` (default 256 elements) stay exact. The
codec (and floor) join the program keys, so toggling never poisons a
cached exact program; ``HEAT_TPU_QUANT_COLLECTIVES=0`` is bitwise
today's behavior. Counters: ``op_engine.quant_collectives`` /
``quant_bytes_saved`` (ring-wire model, the same formulas
``heat_tpu.utils.hlo_audit.collective_bytes`` applies to real HLO) /
``quant_fallbacks``. Error contract and the when-not-to table live in
``doc/fusion.md``.

Chunked, double-buffered packed collectives (software pipelining)
-----------------------------------------------------------------
``HEAT_TPU_FUSION_CHUNKS=N`` (default 1 = off) splits every packed
collective payload this engine emits — the flush body's phase-barrier
packing and every :func:`packed_psum` call site — into up to N contiguous
pipeline chunks, each a separate collective, chained with
``lax.optimization_barrier`` so at most TWO chunks are ever in flight
(double buffering): chunk k's reduce-scatter/all-gather legs can cross
the wire while chunk k-1's combine and consumer compute runs — the
pipelined form of the generalized-allreduce decomposition
(arXiv:2004.09362; the PR 9 int8 exchange is already structured as
RS→combine→AG legs that chunk naturally). Chunk boundaries are
block-aligned per codec (exact/bf16: the communicating group size; int8:
``primary_axis × HEAT_TPU_QUANT_BLOCK`` so no scale block ever spans a
chunk), which makes the N-chunk emission VALUE-BITWISE-equal to the
unchunked plan per codec and keeps total wire bytes identical (the
``hlo_audit.collective_bytes`` ring model sums per chunk to the
whole-payload figure — tail chunks are never double-charged for
alignment padding). Payloads below ``HEAT_TPU_FUSION_CHUNK_MIN_NUMEL``
(default 4096 elements) stay unchunked: small collectives are
latency-bound and extra legs only add dispatches. The chunk
configuration (:func:`chunk_key`) joins the flush program key and every
model-level step cache next to :func:`quant_key`, so toggling N compiles
SIBLINGS and ``HEAT_TPU_FUSION_CHUNKS=1`` is bitwise (and
program-identical to) today's behavior. Counters:
``op_engine.chunk_collectives`` / ``chunk_fallbacks``; fault site
``fusion.chunk.dispatch`` degrades to the unchunked packed collective.

Asynchronous train-step dispatch
--------------------------------
``trace_step(fn, donate_argnums, block=False)`` dispatches without the
per-step host sync: on this jax, XLA DONATION of an in-flight buffer
blocks the dispatching thread until the producer step completes, so
back-to-back donated train steps serialize the host (probed: 10 chained
donated dispatches cost the full compute wall, 10 plain ones cost
~0.2 ms). The ``block=False`` sibling program compiles WITHOUT XLA
donation and instead ``delete()``-s the donated input buffers right
after dispatch — invalidation semantics preserved (``is_deleted()``,
use-after raises) while the dispatch queue stays asynchronous, so
queued steps run back-to-back with the host free between them.
:func:`sync` blocks on the outstanding async results (or on any pytree
of arrays passed to it) — the one explicit host barrier.

Opt-out: ``HEAT_TPU_FUSION=0`` (or :func:`set_enabled` at runtime).
Counters: ``op_engine.fusion_flushes``, ``op_engine.fusion_ops`` (their
ratio is the ops-per-flush figure in ``ht.runtime_stats()``), plus the
program-cache hit/miss/compile set.
"""

from __future__ import annotations

import contextlib
import functools
import os
import sys
import threading
import weakref
from typing import Any, Dict, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from ..utils import profiling as _prof

__all__ = [
    "enabled",
    "set_enabled",
    "override",
    "materialize",
    "cancel",
    "record_unary",
    "record_binary",
    "record_cum",
    "record_reduce",
    "record_contract",
    "record_contract_einsum",
    "record_resplit",
    "alias_pending",
    "register_reduce_collective",
    "program_cache",
    "stats",
    "reset",
    "capture_hlo",
    "last_hlo",
    "trace_step",
    "value_and_grad",
    "grad",
    "packed_psum",
    "step_enabled",
    "set_step_enabled",
    "step_override",
    "quant_codec",
    "set_quant_codec",
    "quant_override",
    "quant_key",
    "chunk_count",
    "set_chunk_count",
    "chunk_override",
    "chunk_key",
    "hier_enabled",
    "set_hier_enabled",
    "hier_override",
    "mesh_tiers",
    "set_mesh_tiers",
    "hier_key",
    "sync",
]


def _env_on(name: str, default: str = "1") -> bool:
    return os.environ.get(name, default) not in ("0", "false", "False")


_ENABLED = _env_on("HEAT_TPU_FUSION")
_MAX_OPS = int(os.environ.get("HEAT_TPU_FUSION_MAX_OPS", "32"))
# chains shorter than this replay op-by-op at flush (XLA's per-op cache,
# shared across ALL chains) instead of compiling a per-signature program:
# a test-suite-shaped workload materializes thousands of DISTINCT 1-3 op
# chains once each, where per-chain executables are pure compile-time loss
_MIN_OPS = int(os.environ.get("HEAT_TPU_FUSION_MIN_OPS", "4"))
_DONATE = _env_on("HEAT_TPU_FUSION_DONATE")
# escape hatch for the reduction-node extension alone: with 0, reductions
# flush their input tape and dispatch eagerly (the pre-reduction-fusion
# behavior), while elementwise recording stays on
_REDUCE = _env_on("HEAT_TPU_FUSION_REDUCE")
# escape hatch for the contraction-node extension alone: with 0, GEMMs
# dispatch eagerly on zero-filled physical arrays (the pre-contract-fusion
# behavior), while elementwise/reduction recording stays on
_CONTRACT = _env_on("HEAT_TPU_FUSION_CONTRACT")
# escape hatch for the resplit-node extension alone: with 0, a resplit on
# a pending tape flushes it and runs the eager planned reshard (the
# pre-resplit-fusion behavior), while all other recording stays on
_RESPLIT = _env_on("HEAT_TPU_FUSION_RESPLIT")
# escape hatch for the differentiable-tape extension alone: with 0,
# trace_step-wrapped steps run their body eagerly (per-op dispatch, host
# round-trips and all) and the model-level fused steps revert to their
# historic GSPMD/check_vma train programs
_STEP = _env_on("HEAT_TPU_FUSION_STEP")
# escape hatch for the tape-compiled analytics fit steps alone: with 0,
# the estimator family (KMeans/KMedians/KMedoids Lloyd iterations, the
# Lanczos inner loop, Lasso coordinate sweeps, the KNN/GaussianNB
# predict-assign programs) runs its legacy step programs — the exact
# pre-fit-fusion dispatch, without donation, packed collectives or the
# fusion program-cache keying
_FIT = _env_on("HEAT_TPU_FUSION_FIT")


def _parse_codec(val):
    """``HEAT_TPU_QUANT_COLLECTIVES`` value -> codec name or None (exact).
    Unknown values raise immediately: a typo'd codec silently running the
    exact path would defeat the whole byte-reduction intent."""
    if val is None or val in ("", "0", "false", "False", "off", "none"):
        return None
    if val == "1":
        return "bf16"  # the conservative default codec
    if val in ("bf16", "int8"):
        return val
    raise ValueError(
        f"HEAT_TPU_QUANT_COLLECTIVES={val!r}: expected 0, 1, bf16 or int8")


# opt-in quantized wire codec for packed float all-reduces (None = exact)
_QUANT = _parse_codec(os.environ.get("HEAT_TPU_QUANT_COLLECTIVES"))
# payloads below this many elements stay exact: small collectives are
# latency-bound, and quantizing them buys nothing while still paying the
# encode/decode epilogue (it also keeps packed scalar losses exact)
_QUANT_FLOOR = int(os.environ.get("HEAT_TPU_QUANT_MIN_NUMEL", "256"))
# elements per int8 scale block (bf16 scales travel with the payload).
# 128 balances scale overhead (2 bytes per 128 payload bytes, ~1.6%)
# against within-block dynamic range: transformer grads are spiky
# (embedding rows span orders of magnitude), and 256-blocks measured at
# the edge of the documented 1e-2 rel-err contract where 128 leaves
# ~15% margin (tests/test_quant_collectives.py pins the figure)
_QUANT_BLOCK = int(os.environ.get("HEAT_TPU_QUANT_BLOCK", "128"))

# pipeline-chunk count for packed collectives (1 = off, today's emission;
# N splits every qualifying packed payload into up to N double-buffered
# chunk collectives so chunk k's wire legs overlap chunk k-1's compute)
_CHUNKS = int(os.environ.get("HEAT_TPU_FUSION_CHUNKS", "1"))
# payloads below this many elements stay unchunked: a small collective is
# latency-bound, and splitting it into N legs multiplies the latency
# while overlapping nothing worth overlapping
_CHUNK_FLOOR = int(os.environ.get("HEAT_TPU_FUSION_CHUNK_MIN_NUMEL",
                                  "4096"))


def _parse_tiers(val):
    """``HEAT_TPU_MESH_TIERS`` value -> tier declaration or None.

    Two declaration forms (arXiv:2004.09362's two-tier topology model):

    * ``"2,4"`` (integers) — a ``(dcn, ici)`` FACTORIZATION for flat 1-D
      meshes: the mesh's device order is dcn-major (``d`` hosts × ``i``
      devices per host, device ``h*i + j`` = host ``h``, local slot
      ``j``), exactly how ``jax.devices()`` orders a real multi-host pod.
      Drives the flush path's grouped hierarchical exchange and the
      default 2-D ``DataParallel`` grid.
    * ``"dcn,ici"`` (names) — the axis-NAME declaration for named grids:
      the FIRST name is the slow (DCN) tier's mesh-axis name, every other
      axis in a reduction scope is the fast (ICI) tier. ``"dcn"`` alone
      is equivalent (and is the built-in default: a grid that names an
      axis ``"dcn"`` — DASO's ``MeshGrid``, a 5-axis ``TransformerLM``
      grid — has declared its tiers by construction).

    Unknown/mixed forms raise immediately: a typo'd declaration silently
    running flat would defeat the whole DCN-byte-reduction intent."""
    if val is None or val in ("", "0", "false", "False", "off", "none"):
        return None
    parts = tuple(p.strip() for p in str(val).split(",") if p.strip())
    if not parts:
        return None
    if all(p.lstrip("-").isdigit() for p in parts):
        ints = tuple(int(p) for p in parts)
        if len(ints) != 2 or ints[0] < 1 or ints[1] < 1:
            raise ValueError(
                f"HEAT_TPU_MESH_TIERS={val!r}: factor form wants exactly "
                "two positive sizes 'dcn,ici' (e.g. 2,4)")
        return ints
    if any(p.lstrip("-").isdigit() for p in parts):
        raise ValueError(
            f"HEAT_TPU_MESH_TIERS={val!r}: mix of names and sizes "
            "(want 'dcn,ici' names or 'D,I' integer factors)")
    return parts


def _parse_ici_codec(val):
    """``HEAT_TPU_HIER_ICI_CODEC`` -> ``None`` (exact) or ``"bf16"``.
    ``int8`` is deliberately rejected for the fast tier: the ICI legs
    include a reduce-scatter (a reduction, not pure data movement), and
    EQuARX's tier-selective result is exactly that the cheap fast tier
    should stay (near-)exact while the slow tier carries the aggressive
    codec."""
    if val is None or val in ("", "0", "false", "False", "off", "none"):
        return None
    if val in ("1", "bf16"):
        return "bf16"
    raise ValueError(
        f"HEAT_TPU_HIER_ICI_CODEC={val!r}: expected 0, none or bf16 "
        "(the DCN-tier codec is HEAT_TPU_QUANT_COLLECTIVES)")


# master gate for tier-aware hierarchical packed collectives (default on;
# inert until a mesh declares tiers — a "dcn"-named grid axis or the
# HEAT_TPU_MESH_TIERS factorization — so the default is bitwise flat)
_HIER = _env_on("HEAT_TPU_HIER")
_TIERS = _parse_tiers(os.environ.get("HEAT_TPU_MESH_TIERS"))
# fast-tier (ICI) wire codec for the hierarchical exchange's RS/AG legs
# (None = exact; the slow-tier/DCN codec is the quant codec above)
_HIER_ICI = _parse_ici_codec(os.environ.get("HEAT_TPU_HIER_ICI_CODEC"))
# psum payload GROUPS below this many total elements keep the flat
# collective: the decomposition trades one collective for three, which
# only pays when the slow tier's bandwidth (not latency) dominates.
# Default 0 = decompose everything — model-step gradient payloads are
# large, and the tiny members (the packed scalar loss) ride the same
# group as the gradients rather than paying their own legs
_HIER_FLOOR = int(os.environ.get("HEAT_TPU_HIER_MIN_NUMEL", "0"))

_PROGRAMS = None  # lazy singleton (utils imports back into core)

# result-aval memo: (fn, kwargs_key, arg descriptors) -> ShapeDtypeStruct,
# or None for "declined" (non-array result, un-eval-shapeable op)
_AVAL_CACHE: Dict[Tuple, Any] = {}
_AVAL_CACHE_CAP = 8192
_UNSET = object()

# value-keyed 0-d leaves for python/numpy scalars, so repeat chains with
# the same scalar hit the same program AND the same buffer
_SCALAR_CACHE: Dict[Tuple, Any] = {}
_SCALAR_CACHE_CAP = 512

_capture_hlo = False
_last_hlo: Optional[str] = None


def program_cache():
    """The fusion :class:`~heat_tpu.utils.program_cache.ProgramCache`."""
    global _PROGRAMS
    if _PROGRAMS is None:
        from ..utils.program_cache import ProgramCache

        # fusion's key space is open (leaf shapes x chain signatures), so
        # the cache is capped — unbounded pinned executables are the exact
        # accumulated-executable pathology this engine exists to reduce
        _PROGRAMS = ProgramCache(
            name="fusion", aot=False,
            max_entries=int(os.environ.get(
                "HEAT_TPU_FUSION_MAX_PROGRAMS", "1024")))
    return _PROGRAMS


def _metrics():
    from ..utils import metrics

    return metrics


_FAULTS = None  # lazy module handle (utils imports back into core)


def _faults():
    global _FAULTS
    if _FAULTS is None:
        from ..utils import faults

        _FAULTS = faults
    return _FAULTS


# ---------------------------------------------------------------------- #
# switches                                                               #
# ---------------------------------------------------------------------- #
def enabled() -> bool:
    """Whether op recording is on (``HEAT_TPU_FUSION``, default on)."""
    return _ENABLED


def set_enabled(flag: bool) -> bool:
    """Toggle recording; returns the previous setting. Pending tapes stay
    valid — they flush on their next materialization either way."""
    global _ENABLED
    prev = _ENABLED
    _ENABLED = bool(flag)
    return prev


@contextlib.contextmanager
def override(flag: bool):
    """Context manager form of :func:`set_enabled` (used by the eager-vs-
    fused property tests)."""
    prev = set_enabled(flag)
    try:
        yield
    finally:
        set_enabled(prev)


def step_enabled() -> bool:
    """Whether trace_step tracing (and the model-level fused train steps)
    are on (``HEAT_TPU_FUSION_STEP``, default on; also requires the master
    ``HEAT_TPU_FUSION`` switch)."""
    return _ENABLED and _STEP


def set_step_enabled(flag: bool) -> bool:
    """Toggle the differentiable-tape extension alone; returns the
    previous setting."""
    global _STEP
    prev = _STEP
    _STEP = bool(flag)
    return prev


@contextlib.contextmanager
def step_override(flag: bool):
    """Context manager form of :func:`set_step_enabled` (the traced-vs-
    eager property tests)."""
    prev = set_step_enabled(flag)
    try:
        yield
    finally:
        set_step_enabled(prev)


def fit_enabled() -> bool:
    """Whether the analytics fit-step engine is on: estimator ``fit()``
    hot loops (and the KNN/GaussianNB predict-assign programs) dispatch
    ONE donated, packed-collective executable per iteration through
    :func:`fit_step_call` (``HEAT_TPU_FUSION_FIT``, default on; also
    requires the master ``HEAT_TPU_FUSION`` switch)."""
    return _ENABLED and _FIT


def set_fit_enabled(flag: bool) -> bool:
    """Toggle the analytics fit-step extension alone; returns the
    previous setting."""
    global _FIT
    prev = _FIT
    _FIT = bool(flag)
    return prev


@contextlib.contextmanager
def fit_override(flag: bool):
    """Context manager form of :func:`set_fit_enabled` (the fused-vs-
    legacy estimator parity tests)."""
    prev = set_fit_enabled(flag)
    try:
        yield
    finally:
        set_fit_enabled(prev)


def quant_codec() -> Optional[str]:
    """The active quantized-collective codec: ``None`` (exact, the
    default), ``"bf16"`` or ``"int8"`` (``HEAT_TPU_QUANT_COLLECTIVES``)."""
    return _QUANT


def set_quant_codec(codec) -> Optional[str]:
    """Select the quantized-collective codec at runtime; returns the
    previous one. Accepts the env-var spellings (``None``/``"0"``/
    ``"bf16"``/``"int8"``). Cached exact programs stay valid — the codec
    is part of every quantization-sensitive program key."""
    global _QUANT
    prev = _QUANT
    _QUANT = _parse_codec(codec)
    return prev


def quant_key() -> Tuple:
    """Hashable identity of the quantization configuration (codec, size
    floor, scale-block size) — model-level step caches (``TransformerLM``,
    ``DataParallel``, DASO) and the flush program key carry it so toggling
    any knob rebuilds instead of reusing a program with the wrong wire
    format."""
    return (_QUANT, _QUANT_FLOOR, _QUANT_BLOCK)


@contextlib.contextmanager
def quant_override(codec, min_numel: Optional[int] = None):
    """Context manager form of :func:`set_quant_codec`; ``min_numel``
    optionally overrides the size floor (the quant property sweeps use a
    low floor so small test payloads exercise the codec)."""
    global _QUANT_FLOOR
    prev = set_quant_codec(codec)
    prev_floor = _QUANT_FLOOR
    if min_numel is not None:
        _QUANT_FLOOR = int(min_numel)
    try:
        yield
    finally:
        set_quant_codec(prev)
        _QUANT_FLOOR = prev_floor


def chunk_count() -> int:
    """The configured pipeline-chunk count for packed collectives
    (``HEAT_TPU_FUSION_CHUNKS``; 1 = unchunked, today's emission)."""
    return _CHUNKS


def set_chunk_count(n) -> int:
    """Select the packed-collective pipeline-chunk count at runtime;
    returns the previous one. Cached programs stay valid — the chunk
    configuration is part of every chunk-sensitive program key, so
    toggling compiles siblings and toggling back re-hits."""
    global _CHUNKS
    prev = _CHUNKS
    n = int(n)
    if n < 1:
        raise ValueError(f"HEAT_TPU_FUSION_CHUNKS={n}: expected >= 1")
    _CHUNKS = n
    return prev


def chunk_key() -> Tuple:
    """Hashable identity of the chunking configuration ``(count,
    payload floor)`` — joins the flush program key and the model-level
    step caches next to :func:`quant_key` so a chunk-count toggle
    rebuilds instead of reusing a program with the wrong leg structure."""
    return (_CHUNKS, _CHUNK_FLOOR)


@contextlib.contextmanager
def chunk_override(n, min_numel: Optional[int] = None):
    """Context manager form of :func:`set_chunk_count`; ``min_numel``
    optionally overrides the payload floor (the chunk property sweeps use
    a low floor so small test payloads exercise the pipeline)."""
    global _CHUNK_FLOOR
    prev = set_chunk_count(n)
    prev_floor = _CHUNK_FLOOR
    if min_numel is not None:
        _CHUNK_FLOOR = int(min_numel)
    try:
        yield
    finally:
        set_chunk_count(prev)
        _CHUNK_FLOOR = prev_floor


def hier_enabled() -> bool:
    """Whether tier-aware hierarchical packed collectives are on
    (``HEAT_TPU_HIER``, default on). Inert without a tier declaration —
    a reduction scope containing a slow-named (``"dcn"``) grid axis, or
    a flat mesh with a declared ``HEAT_TPU_MESH_TIERS`` factorization."""
    return _HIER


def set_hier_enabled(flag: bool) -> bool:
    """Toggle the hierarchical-collective extension alone; returns the
    previous setting. Cached programs stay valid — :func:`hier_key` is
    part of every hierarchy-sensitive program key, so toggling compiles
    siblings and toggling back re-hits."""
    global _HIER
    prev = _HIER
    _HIER = bool(flag)
    return prev


@contextlib.contextmanager
def hier_override(flag: bool, tiers=_UNSET, ici_codec=_UNSET,
                  min_numel=None):
    """Context manager form of :func:`set_hier_enabled`; ``tiers`` /
    ``ici_codec`` / ``min_numel`` optionally override the declaration,
    the fast-tier codec and the payload floor for the block (the hier
    property sweeps pin all of them). Arguments are VALIDATED before any
    global is touched — a bad declaration raises with the configuration
    untouched, never with a half-toggled gate leaked into later code."""
    global _TIERS, _HIER_ICI
    global _HIER_FLOOR
    if tiers is not _UNSET:
        parsed_tiers = _parse_tiers(
            tiers if tiers is None or isinstance(tiers, str)
            else ",".join(str(s) for s in tiers))
    if ici_codec is not _UNSET:
        parsed_ici = _parse_ici_codec(ici_codec)
    if min_numel is not None:
        min_numel = int(min_numel)
    prev = set_hier_enabled(flag)
    prev_tiers, prev_ici, prev_floor = _TIERS, _HIER_ICI, _HIER_FLOOR
    try:
        if tiers is not _UNSET:
            _TIERS = parsed_tiers
        if ici_codec is not _UNSET:
            _HIER_ICI = parsed_ici
        if min_numel is not None:
            _HIER_FLOOR = min_numel
        yield
    finally:
        set_hier_enabled(prev)
        _TIERS, _HIER_ICI, _HIER_FLOOR = prev_tiers, prev_ici, prev_floor


def mesh_tiers():
    """The active tier declaration: ``None`` (undeclared), a ``(d, i)``
    integer factorization for flat meshes, or a name tuple whose first
    entry is the slow (DCN) axis name (``HEAT_TPU_MESH_TIERS``)."""
    return _TIERS


def set_mesh_tiers(spec):
    """Declare (or clear) the mesh tier split at runtime; returns the
    previous declaration. Accepts the env-var spellings (``None`` /
    ``"2,4"`` / ``"dcn,ici"``) or ready tuples."""
    global _TIERS
    prev = _TIERS
    if spec is None or isinstance(spec, str):
        _TIERS = _parse_tiers(spec)
    else:
        _TIERS = _parse_tiers(",".join(str(s) for s in spec))
    return prev


def hier_key() -> Tuple:
    """Hashable identity of the hierarchical-collective configuration
    ``(enabled, tier declaration, ici codec, payload floor)`` — joins
    the flush program key and every model-level step cache next to
    :func:`quant_key` / :func:`chunk_key`, so toggling the hierarchy (or
    re-declaring tiers) rebuilds siblings instead of reusing a program
    with the wrong collective structure; toggling back re-hits the
    cached sibling."""
    return (_HIER, _TIERS, _HIER_ICI, _HIER_FLOOR)


def capture_hlo(flag: bool) -> None:
    """Debug switch: compile flush programs ahead-of-time and keep the
    optimized-HLO text of the most recent compile for :func:`last_hlo`
    (the collective audit in ``tests/test_fusion.py``). Only *compiles*
    capture — reset :func:`program_cache` first to force one. Arming the
    capture clears any previous dump: a cache-hit (or compile-error) path
    must read as a loud ``last_hlo() is None``, never silently satisfy an
    audit with a stale program's HLO."""
    global _capture_hlo, _last_hlo
    _capture_hlo = bool(flag)
    if _capture_hlo:
        _last_hlo = None


def last_hlo() -> Optional[str]:
    return _last_hlo


# ---------------------------------------------------------------------- #
# the expression DAG                                                     #
# ---------------------------------------------------------------------- #
class _Leaf:
    """A concrete physical array entering a chain, plus a weakref to the
    DNDarray that owned it at record time (None for scalar constants) —
    the donation analysis input. ``split`` is the owner's split axis at
    record time (the shard_map translator's layout source of truth)."""

    __slots__ = ("array", "owner", "split")

    def __init__(self, array, owner=None, split=None):
        self.array = array
        self.owner = owner
        self.split = split


class _Node:
    """One recorded op. ``args`` are ``_Node`` / ``_Leaf`` handles;
    ``kwargs`` are static (hashability enforced at record time). ``value``
    is set once a flush evaluates the node (it then acts as a leaf for any
    later chain that still references it).

    ``kind``/``split``/``rmeta``/``cmeta``/``smeta``/``comm`` drive the
    shard_map translation of collective-carrying tapes: ``kind`` is
    ``"ew"`` (elementwise/cum/astype), ``"pad"`` (replicated-operand
    physical pad), ``"mask"`` (neutral-element padding fill),
    ``"reduce"``, ``"contract"`` (distributed GEMM/einsum), ``"resplit"``
    (the reshard planner's layout change folded into the DAG), or
    ``"crop"`` (static slice back to canonical extents — never
    blockwise); ``split`` is the physical split axis of the node's VALUE;
    ``rmeta`` holds the reduce metadata (collective kind, whether the
    split axis is reduced, the input split); ``cmeta`` the contract
    metadata (split case, collective, translatability); ``smeta`` the
    resplit metadata (source/target split); ``comm`` is set on reduce,
    contract and resplit nodes only."""

    __slots__ = ("fn", "args", "kwargs", "kwargs_key", "aval", "depth",
                 "owner", "ext_refs", "value", "kind", "split", "rmeta",
                 "cmeta", "smeta", "comm", "__weakref__")

    def __init__(self, fn, args, kwargs, kwargs_key, aval, depth):
        self.fn = fn
        self.args = args
        self.kwargs = kwargs
        self.kwargs_key = kwargs_key
        self.aval = aval
        self.depth = depth
        self.owner = None       # weakref.ref(DNDarray) once wrapped
        self.ext_refs = 0       # times used as an argument of another node
        self.value = None       # concrete result once evaluated
        self.kind = "ew"
        self.split = None
        self.rmeta = None
        self.cmeta = None
        self.smeta = None
        self.comm = None


# partial_op -> collective kind ("psum"/"pmax"/"pmin"); a registered None
# means "no collective primitive exists" (prod): the tape still records,
# and the flush compiles ONE jax.jit program whose collective GSPMD places
_COLLECTIVE: Dict[Any, Optional[str]] = {}


def register_reduce_collective(fn, kind: Optional[str]) -> None:
    """Declare the mesh collective that combines ``fn``'s shard-local
    partials (``"psum"``/``"pmax"``/``"pmin"``, or None for ops without a
    collective primitive). Ops modules register their partial reducers at
    import (``jnp.sum`` etc. are pre-registered below)."""
    _COLLECTIVE[fn] = kind


register_reduce_collective(jnp.sum, "psum")
register_reduce_collective(jnp.max, "pmax")
register_reduce_collective(jnp.min, "pmin")
register_reduce_collective(jnp.prod, None)  # no pprod primitive: GSPMD path


def _key_val(v):
    """Type-aware hashable identity for one static kwarg value, or None to
    decline. Plain ``(k, v)`` tuples would alias values that compare equal
    across types (``0 == 0.0 == False``) and let one call's cached aval or
    compiled program serve another call's different dtype — floats key by
    ``repr`` (distinguishes ``-0.0`` and NaN, like the scalar-leaf cache)
    and everything carries its type name."""
    if isinstance(v, (np.ndarray, jnp.ndarray)):
        return None
    if isinstance(v, (list, tuple)):
        parts = tuple(_key_val(x) for x in v)
        return None if any(p is None for p in parts) else ("tuple", parts)
    if isinstance(v, (float, complex, np.floating, np.complexfloating)):
        return (type(v).__name__, repr(v))
    try:
        hash(v)
    except TypeError:
        return None
    return (type(v).__name__, v)


def _kwargs_key(kwargs: dict):
    """Hashable identity for static kwargs, or None to decline recording
    (array-valued kwargs must stay eager — baking them as constants would
    both bloat the key space and change numerics)."""
    if not kwargs:
        return ()
    items = []
    for k in sorted(kwargs):
        vk = _key_val(kwargs[k])
        if vk is None:
            return None
        items.append((k, vk))
    return tuple(items)


def _scalar_leaf(s) -> Optional[_Leaf]:
    """A 0-d leaf for a python/numpy scalar operand, value-cached.

    ``jnp.asarray`` preserves NumPy-style weak typing for python scalars,
    so passing the leaf as a program *argument* reproduces eager promotion
    exactly ((f32 array) * 0.5 stays f32). ``repr`` keys the cache so
    ``-0.0``/``0.0`` and NaN never alias."""
    key = (type(s).__name__, repr(s))
    leaf = _SCALAR_CACHE.get(key)
    if leaf is None:
        try:
            arr = jnp.asarray(s)
        except Exception:
            return None
        if isinstance(arr, jax.core.Tracer):
            # inside a jax trace (user jit / trace_step) even a python
            # constant lifts to a tracer on this jax; caching it would
            # poison every later EAGER chain that reuses the same scalar
            # (the flush reads leaf.array.sharding — tracers have none)
            return None
        if len(_SCALAR_CACHE) >= _SCALAR_CACHE_CAP:
            _SCALAR_CACHE.clear()
        leaf = _Leaf(arr, None)
        _SCALAR_CACHE[key] = leaf
    return leaf


def _handle_of(x) -> Optional[object]:
    """The symbolic handle for a DNDarray operand: its pending node, or a
    leaf over its concrete physical array. None declines recording (jax
    tracers must not be captured into a cross-turn tape)."""
    node = x._lazy_node
    if node is not None:
        if node.value is not None:
            return _Leaf(node.value, node.owner, node.split)
        return node
    arr = x._phys_or_none()
    if arr is None or isinstance(arr, jax.core.Tracer):
        return None
    return _Leaf(arr, weakref.ref(x), x.split)


def _descr(h) -> tuple:
    """Aval descriptor of a handle, for the eval-shape memo key."""
    if isinstance(h, _Node):
        return (tuple(h.aval.shape), str(h.aval.dtype), False)
    a = h.array
    return (tuple(a.shape), str(a.dtype), bool(a.aval.weak_type))


def _proxy(h):
    """What :func:`jax.eval_shape` sees for a handle: pending nodes by
    abstract aval, leaves by their concrete array (weak types ride along)."""
    if isinstance(h, _Node):
        return jax.ShapeDtypeStruct(tuple(h.aval.shape), h.aval.dtype)
    return h.array


def _result_aval(fn, kwargs, kwargs_key, handles):
    """Memoized ``eval_shape`` of one op application; None declines (op not
    abstractly traceable, or result is not a single array)."""
    key = (fn, kwargs_key, tuple(_descr(h) for h in handles))
    aval = _AVAL_CACHE.get(key, _UNSET)
    if aval is not _UNSET:
        return aval
    try:
        aval = jax.eval_shape(lambda *a: fn(*a, **kwargs),
                              *[_proxy(h) for h in handles])
        if not isinstance(aval, jax.ShapeDtypeStruct):
            aval = None
    except Exception:
        aval = None
    if len(_AVAL_CACHE) >= _AVAL_CACHE_CAP:
        _AVAL_CACHE.clear()
    _AVAL_CACHE[key] = aval
    return aval


def _depth_of(handles) -> int:
    return 1 + max((h.depth for h in handles if isinstance(h, _Node)),
                   default=0)


def _stable_fn(fn) -> bool:
    """Only module-level callables may be recorded: a lambda / closure /
    ``functools.partial`` built per call has a fresh identity every time,
    so every chain containing one would compile a brand-new executable per
    invocation and pin it forever in the program cache — unbounded
    compile-time and memory growth (the exact executable-count pathology
    this engine exists to reduce). Those ops dispatch eagerly instead."""
    if isinstance(fn, functools.partial):
        return False
    if getattr(fn, "__name__", "") == "<lambda>":
        return False
    return "<locals>" not in getattr(fn, "__qualname__", "")


def _make_node(fn, kwargs, handles, expected_shape) -> Optional[_Node]:
    """Record one op over ``handles``; enforces the tape-depth cap (flush
    the deep inputs, then record over their values) and validates the
    abstract result against the expected physical shape — any mismatch
    declines, and the caller's eager path reproduces historic behavior."""
    if not _stable_fn(fn):
        return None
    kwargs_key = _kwargs_key(kwargs)
    if kwargs_key is None:
        return None
    aval = _result_aval(fn, kwargs, kwargs_key, handles)
    if aval is None or tuple(aval.shape) != tuple(expected_shape):
        return None
    if _depth_of(handles) > _MAX_OPS:
        handles = tuple(_flushed_handle(h) for h in handles)
    node = _Node(fn, tuple(handles), dict(kwargs), kwargs_key, aval,
                 _depth_of(handles))
    with _FLUSH_LOCK:
        # ext_refs feeds the flush-time shared-node output promotion; an
        # unsynchronized += could lose an increment under concurrent
        # recording off one shared pending node and strand its value
        for h in handles:
            if isinstance(h, _Node):
                h.ext_refs += 1
    return node


def _flushed_handle(h):
    """Depth-cap helper: evaluate a pending node and hand back its value
    as a leaf (the chain splits into two programs at the cap)."""
    if isinstance(h, _Node) and h.value is None:
        _flush(h)
    if isinstance(h, _Node):
        return _Leaf(h.value, h.owner, h.split)
    return h


def _wrap(node: _Node, gshape, split, device, comm):
    """A lazy DNDarray owning ``node``."""
    from . import types
    from .dndarray import DNDarray

    arr = DNDarray._lazy(node, gshape, types.canonical_heat_type(aval_dtype(node)),
                         split, device, comm)
    node.owner = weakref.ref(arr)
    return arr


def aval_dtype(node: _Node):
    return node.aval.dtype


# ---------------------------------------------------------------------- #
# record entry points (called from the op engine)                        #
# ---------------------------------------------------------------------- #
def record_unary(operation, x, kwargs) -> Optional[object]:
    """Lazy form of ``__local_op`` (no ``out=``): shape-preserving
    elementwise op on the physical array."""
    if not _ENABLED:
        return None
    h = _handle_of(x)
    if h is None:
        return None
    node = _make_node(operation, kwargs, (h,), x._phys_shape())
    if node is None:
        return None
    node.split = x.split
    return _wrap(node, x.gshape, x.split, x.device, x.comm)


def _pad_op(a, cfg):
    """Module-level (stable identity for program keys) physical pad of a
    replicated operand onto the padded split-axis length."""
    return jnp.pad(a, list(cfg))


def record_binary(operation, t1, t2, fn_kwargs, pad1, pad2,
                  out_shape, out_split, device, comm) -> Optional[object]:
    """Lazy form of ``__binary_op``'s compute tail (no ``out=``/``where=``).

    Called AFTER distribution alignment — any needed resplit already ran
    (and materialized its operand), so both handles are layout-compatible
    and the recorded op never crosses the split axis. ``pad1``/``pad2``
    are the replicated-operand pad configs the eager path would apply;
    they become nodes of their own."""
    from .dndarray import DNDarray

    if not _ENABLED:
        return None

    def handle(t, pad_cfg):
        if isinstance(t, DNDarray):
            h = _handle_of(t)
        else:
            h = _scalar_leaf(t)
        if h is None or pad_cfg is None:
            return h
        hp = _make_node(_pad_op, {"cfg": tuple(tuple(p) for p in pad_cfg)},
                        (h,), _padded_shape(h, pad_cfg))
        if hp is not None:
            # the padded operand aligns with the split operand: to the
            # shard_map translator its value is sharded along the axis the
            # pad extended (pad-to-physical, then slice the local block)
            hp.kind = "pad"
            hp.split = next(i for i, p in enumerate(pad_cfg)
                            if tuple(p) != (0, 0))
        return hp

    h1 = handle(t1, pad1)
    h2 = handle(t2, pad2)
    if h1 is None or h2 is None:
        return None
    expected = tuple(comm.padded_size(s) if i == out_split else int(s)
                     for i, s in enumerate(out_shape))
    node = _make_node(operation, fn_kwargs, (h1, h2), expected)
    if node is None:
        return None
    node.split = out_split
    return _wrap(node, out_shape, out_split, device, comm)


def _padded_shape(h, cfg):
    base = h.aval.shape if isinstance(h, _Node) else h.array.shape
    return tuple(int(s) + int(cfg[i][0]) + int(cfg[i][1])
                 for i, s in enumerate(base))


def _astype_op(a, dtype):
    return a.astype(dtype)


def record_astype(x, heat_dtype) -> Optional[object]:
    """Lazy form of ``DNDarray.astype(copy=True)``: a dtype conversion is
    elementwise, so it records like any unary op — this keeps predicate
    chains fusible through ``ht.where``'s bool cast instead of forcing a
    flush at every ``astype`` boundary."""
    if not _ENABLED:
        return None
    h = _handle_of(x)
    if h is None:
        return None
    node = _make_node(_astype_op, {"dtype": jnp.dtype(heat_dtype.jax_type())},
                      (h,), x._phys_shape())
    if node is None:
        return None
    node.split = x.split
    return _wrap(node, x.gshape, x.split, x.device, x.comm)


def record_cum(x, partial_op, axis, dtype) -> Optional[object]:
    """Lazy form of ``__cum_op`` for scans that do NOT read across the
    split axis (``axis != split``) — the split-crossing case materializes
    first so the neutral-element padding discipline stays eager."""
    if not _ENABLED:
        return None
    if x.split is not None and axis == x.split:
        return None
    h = _handle_of(x)
    if h is None:
        return None
    node = _make_node(partial_op, {"axis": axis}, (h,), x._phys_shape())
    if node is None:
        return None
    node.split = x.split
    if dtype is not None:
        from . import types

        jdt = types.canonical_heat_type(dtype).jax_type()
        node2 = _make_node(_astype_op, {"dtype": jnp.dtype(jdt)}, (node,),
                           x._phys_shape())
        if node2 is None:
            return None
        node2.split = x.split
        node = node2
    return _wrap(node, x.gshape, x.split, x.device, x.comm)


def _mask_pad(a, axis, n, fill):
    """Module-level (stable identity) neutral-element fill of the padding
    beyond logical length ``n`` along ``axis`` — the tape form of
    ``DNDarray.filled``. Global semantics: the shard_map translator swaps
    in a per-shard version whose iota carries the block's global offset."""
    iota = jax.lax.broadcasted_iota(jnp.int32, a.shape, axis)
    return jnp.where(iota < n, a, jnp.asarray(fill, a.dtype))


def record_reduce(x, partial_op, neutral, axis, axes, keepdims,
                  touches_split, gshape, out_split, kwargs) -> Optional[object]:
    """Lazy form of ``__reduce_op`` (no ``out=``): a neutral-element mask
    node over the canonical padding (when the reduction reads across a
    padded split axis) followed by a terminal reduce node. The flush
    compiles elementwise chain → mask → shard-local reduce → one grouped
    collective as ONE program (:func:`_plan_sm`)."""
    if not _ENABLED or not _REDUCE:
        return None
    h = _handle_of(x)
    if h is None:
        return None
    phys_in = x._phys_shape()
    if touches_split and x.pad:
        try:
            hash(neutral)
        except TypeError:
            return None
        h = _mask0(h, x.split, x.gshape[x.split], phys_in, fill=neutral)
        if h is None:
            return None
    rkw = dict(kwargs)
    rkw["axis"] = None if axis is None else axes
    rkw["keepdims"] = keepdims
    if axis is None:
        expected = (1,) * len(phys_in) if keepdims else ()
    elif keepdims:
        expected = tuple(1 if i in axes else s for i, s in enumerate(phys_in))
    else:
        expected = tuple(s for i, s in enumerate(phys_in) if i not in axes)
    node = _make_node(partial_op, rkw, (h,), expected)
    if node is None:
        return None
    node.kind = "reduce"
    node.split = out_split
    node.rmeta = {"collective": _COLLECTIVE.get(partial_op),
                  "touches": bool(touches_split), "in_split": x.split}
    node.comm = x.comm
    return _wrap(node, gshape, out_split, x.device, x.comm)


def _hshape(h) -> Tuple[int, ...]:
    """Physical shape of a handle (node aval or leaf array)."""
    return tuple(h.aval.shape) if isinstance(h, _Node) else tuple(h.array.shape)


def _crop_op(a, limits):
    """Module-level (stable identity) static slice back to the canonical
    physical extents — the tape form of the eager ``res[:, :m]`` crop when
    two operand paddings cannot both stay in a contraction's output. Crop
    nodes never translate blockwise (kind ``"crop"``): their limits span
    the GLOBAL padded extent, which a shard-local block cannot satisfy."""
    return jax.lax.slice(a, (0,) * len(limits), tuple(limits))


def _einsum_op(x, y, expr):
    """Module-level (stable identity) two-operand einsum contraction."""
    return jnp.einsum(expr, x, y)


def _mask0(h, axis, n, phys, fill=0) -> Optional[_Node]:
    """Fill-mask node over the padding beyond logical length ``n`` along
    ``axis`` — the tape form of ``DNDarray.filled``. Contractions mask
    with the default zero (``linalg.basics._filled0``: padding must
    contribute nothing); reductions pass their neutral element."""
    hm = _make_node(_mask_pad, {"axis": int(axis), "n": int(n),
                                "fill": fill}, (h,), phys)
    if hm is None:
        return None
    hm.kind = "mask"
    hm.split = int(axis)
    return hm


def _masked_operand(op, axis, n) -> Optional[object]:
    """Zero-filled handle for a contraction operand whose padding holds
    garbage. A CONCRETE operand takes the eager ``_filled0`` write-back:
    the select runs ONCE per buffer (padding is don't-care), the
    ``pad_is_zero`` bit is set, and every later GEMM on the same array —
    fused or eager — skips the masking pass entirely. A pending tape
    records a MASK node instead, fusing the mask into the chain program
    (zero materialization barrier — the point of recording); its
    ``op_engine.zero_fills`` tick is per flush, honestly counting each
    fused program that carries the select."""
    from ._operations import _count_zero_fill

    if op._lazy_node is None:
        op._write_back_zero_fill()
        return _handle_of(op)
    h = _handle_of(op)
    if h is None:
        return None
    hm = _mask0(h, axis, n, op._phys_shape())
    if hm is not None:
        _count_zero_fill()
    return hm


def _zero_pad_node(h, cfg, operand_split) -> Optional[_Node]:
    """Zero-pad node aligning one operand's extents onto another's padded
    extents. A replicated operand padded along exactly one axis becomes a
    ``"pad"`` node (the translator pads then slices the local block — the
    contracted-split psum case with a replicated side); anything else
    stays an ordinary node (blockwise-safe for non-split axes, and the
    plan validator rejects the rest into the GSPMD path)."""
    hp = _make_node(_pad_op, {"cfg": tuple(tuple(p) for p in cfg)}, (h,),
                    _padded_shape(h, cfg))
    if hp is None:
        return None
    padded_axes = [i for i, p in enumerate(cfg) if tuple(p) != (0, 0)]
    if operand_split is None and len(padded_axes) == 1:
        hp.kind = "pad"
        hp.split = padded_axes[0]
    else:
        hp.split = operand_split
    return hp


def record_contract(a, b) -> Optional[object]:
    """Lazy form of the 2-D ``matmul`` compute tail: zero-fill masks for
    contracted-axis padding, the physical contracted-extent alignment, the
    GEMM itself and (when two paddings cannot coexist in the output) a
    canonical crop all become tape nodes, so ``matmul(x, w) + b`` →
    activation → reduction is ONE flush. ``cmeta["case"]`` names the
    split-combination plan the shard_map translator implements:

    ========== ============================ ======================
    case       layouts                      collectives
    ========== ============================ ======================
    local0     a.split=0, b replicated      none (output split 0)
    local1     a replicated, b.split=1      none (output split 1)
    psum       contracted dim sharded       one packed ``psum``
               (a.split=1 and/or b.split=0)
    replicated both replicated              none (local GEMM)
    gspmd      anything else                GSPMD-placed, one
                                            plain-jit program
    ========== ============================ ======================
    """
    if not _ENABLED or not _CONTRACT:
        return None
    comm = a.comm
    if b.comm is not comm or a.size == 0 or b.size == 0:
        return None
    n, k = (int(s) for s in a.gshape)
    m = int(b.gshape[1])
    sa, sb = a.split, b.split

    # zero-fill the contracted-axis padding (the tape form of ``_filled0``);
    # skipped when the buffer is already canonically zero-padded, written
    # back once for concrete operands (repeat GEMMs are then free). Masks
    # run BEFORE handle acquisition: a concrete write-back swaps the
    # operand's buffer, and an aliased sibling (``matmul(x, x)``) must see
    # the shared post-write-back buffer — and its bit — not a stale leaf
    ha = hb = None
    if sa == 1 and a.pad and not a.pad_is_zero:
        ha = _masked_operand(a, 1, k)
        if ha is None:
            return None
    if sb == 0 and b.pad and not b.pad_is_zero:
        hb = _masked_operand(b, 0, k)
        if hb is None:
            return None
    if ha is None:
        ha = _handle_of(a)
    if hb is None:
        hb = _handle_of(b)
    if ha is None or hb is None:
        return None

    # align the contracted dimension physically (zero rows/cols up to the
    # sharded side's padded extent — zeros contribute nothing to the GEMM)
    ka_phys, kb_phys = _hshape(ha)[1], _hshape(hb)[0]
    if ka_phys < kb_phys:
        ha = _zero_pad_node(ha, ((0, 0), (0, kb_phys - ka_phys)), sa)
    elif kb_phys < ka_phys:
        hb = _zero_pad_node(hb, ((0, ka_phys - kb_phys), (0, 0)), sb)
    if ha is None or hb is None:
        return None

    out_split = 0 if sa == 0 else (1 if sb == 1 else None)
    if sa == 0 and sb is None:
        case = "local0"
    elif sa is None and sb == 1:
        case = "local1"
    elif (sa == 1 or sb == 0) and sa in (1, None) and sb in (0, None):
        case = "psum"
    elif sa is None and sb is None:
        case = "replicated"
    else:
        case = "gspmd"

    raw = (_hshape(ha)[0], _hshape(hb)[1])
    node = _make_node(jnp.matmul, {}, (ha, hb), raw)
    if node is None:
        return None
    node.kind = "contract"
    node.split = out_split
    node.comm = comm
    node.cmeta = {"case": case,
                  "collective": "psum" if case == "psum" else None,
                  "translatable": case != "gspmd"}
    canonical = (comm.padded_size(n) if out_split == 0 else n,
                 comm.padded_size(m) if out_split == 1 else m)
    if raw != canonical:
        # only one axis may carry canonical padding (a.split=0 × b.split=1)
        node2 = _make_node(_crop_op, {"limits": canonical}, (node,),
                           canonical)
        if node2 is None:
            return None
        node2.kind = "crop"
        node2.split = out_split
        node = node2
    # the output's padding is never claimed zero (``_pad_zero`` stays
    # False): even zero operand padding contracted against a non-finite
    # value yields NaN padding (0 * inf), so the bit would lie for legal
    # data. Consumers pay at most one select per buffer (the write-back).
    return _wrap(node, (n, m), out_split, a.device, comm)


def record_contract_einsum(in_specs, out_part, a, b, out_split) -> Optional[object]:
    """Lazy form of the 2-operand distributed einsum (and ``tensordot``
    riding it): zero-fill masks, the label-extent normalization pads, the
    contraction and the logical-output crop all become tape nodes. The
    contraction compiles via the plain-jit GSPMD path unless both operands
    are replicated (``matmul`` owns the block-planned split cases; einsum's
    general layouts stay GSPMD-placed) — the win here is epilogue fusion
    and the removal of the ``filled(0)`` materialization barrier."""
    if not _ENABLED or not _CONTRACT:
        return None
    comm = a.comm
    if b.comm is not comm or a.size == 0 or b.size == 0:
        return None
    handles = []
    for op, spec in ((a, in_specs[0]), (b, in_specs[1])):
        if op.split is not None and op.pad and not op.pad_is_zero:
            h = _masked_operand(op, op.split, op.gshape[op.split])
        else:
            h = _handle_of(op)
        if h is None:
            return None
        handles.append(h)
    # one physical extent per label (a label can pair a padded split dim
    # with an unpadded one across operands; zero-pad the shorter dims)
    sizes: Dict[str, int] = {}
    for h, spec in zip(handles, in_specs):
        for ax, label in enumerate(spec):
            sizes[label] = max(sizes.get(label, 0), _hshape(h)[ax])
    for j, (op, spec) in enumerate(((a, in_specs[0]), (b, in_specs[1]))):
        shape = _hshape(handles[j])
        cfg = tuple((0, sizes[l] - shape[ax]) for ax, l in enumerate(spec))
        if any(w for _, w in cfg):
            handles[j] = _zero_pad_node(handles[j], cfg, op.split)
            if handles[j] is None:
                return None
    expr = ",".join(in_specs) + "->" + out_part
    raw_shape = tuple(sizes[l] for l in out_part)
    node = _make_node(_einsum_op, {"expr": expr}, tuple(handles), raw_shape)
    if node is None:
        return None
    node.kind = "contract"
    node.split = out_split
    node.comm = comm
    replicated = a.split is None and b.split is None and out_split is None
    node.cmeta = {"case": "replicated" if replicated else "gspmd",
                  "collective": None, "translatable": replicated}
    logical = []
    for label in out_part:
        for op, spec in ((a, in_specs[0]), (b, in_specs[1])):
            if label in spec:
                logical.append(int(op.gshape[spec.index(label)]))
                break
    canonical = tuple(comm.padded_size(s) if i == out_split else s
                      for i, s in enumerate(logical))
    if raw_shape != canonical:
        node2 = _make_node(_crop_op, {"limits": canonical}, (node,),
                           canonical)
        if node2 is None:
            return None
        node2.kind = "crop"
        node2.split = out_split
        node = node2
    # padding never claimed zero — zero-filled input padding contracted
    # against a non-finite value is NaN (0 * inf); see record_contract
    return _wrap(node, tuple(logical), out_split, a.device, comm)


def _resplit_op(a, gshape, pad, sharding):
    """Module-level (stable identity) GLOBAL form of a planned resplit:
    cut the source-axis tail padding, zero-pad the target axis, constrain
    the target layout. Pure value semantics — the data motion is a
    sharding change, which ``_sm_body`` renders as exactly the planner's
    collective; this global form serves the plain-jit GSPMD fallback
    (where the constraint hands XLA the intended layout) and the
    eval-shape/aval machinery. The slice/pad steps are the PLANNER'S OWN
    helpers so the fallback can never drift from the planner programs the
    audits pin against. ``_flush_inline`` never calls it: short tapes
    dispatch the eager planner program instead."""
    from . import resharding

    a = resharding._slice_logical(a, gshape)
    for ax, (_lo, w) in enumerate(pad):
        if w:
            a = resharding._pad_axis(a, ax, int(a.shape[ax]) + int(w))
    return jax.lax.with_sharding_constraint(a, sharding)


def alias_pending(x) -> Optional[object]:
    """A lazy copy-wrapper sharing ``x``'s pending node — the no-op
    (same-split) ``resplit`` case, which the eager path serves as a
    buffer-sharing wrapper and which must not flush the tape either.
    The shared node's ``ext_refs`` is bumped under the flush lock so any
    sibling flush promotes its value to a program output — the alias can
    always materialize later, even after ``x`` dies (the same
    stranded-value discipline as shared interior nodes)."""
    from .dndarray import DNDarray

    node = x._lazy_node
    if node is None:
        return None
    with _FLUSH_LOCK:
        if node.value is not None:
            return None  # evaluated already: the concrete path is exact
        node.ext_refs += 1
    return DNDarray._lazy(node, x.gshape, x.dtype, x.split, x.device,
                          x.comm)


def record_resplit(x, to_split) -> Optional[object]:
    """Lazy form of ``DNDarray.resplit``/``resplit_`` on a PENDING tape:
    the reshard planner's one-collective move (arXiv:2112.01075 — one
    all-to-all + local reslice for split→split, a zero-collective local
    slice for None→split, all-gather for split→None) records as a RESPLIT
    node instead of flushing the tape, and the flush translates it
    mid-body inside the one shard_map program, with per-node split state
    switching from the source to the target layout downstream of the
    node. Declines (→ the historic flush-then-planned-resplit path,
    counted in ``op_engine.fusion_resplit_fallbacks``) whenever the
    planner itself would fall back to GSPMD: degenerate layouts, a
    physical shape off the canonical from-layout. Concrete arrays (no
    pending tape) never record — the eager planner path is already one
    cached program, and the ``resharding.plan_*`` counters stay honest."""
    from . import resharding

    if x._lazy_node is None:
        return None  # concrete arrays keep the eager planner path
    if not _ENABLED or not _RESPLIT:
        _metrics().inc("op_engine.fusion_resplit_fallbacks")
        return None
    comm = x.comm
    gshape = tuple(int(s) for s in x.gshape)
    from_split = x.split
    if resharding.plan_kind(gshape, from_split, to_split, comm) == "gspmd":
        _metrics().inc("op_engine.fusion_resplit_fallbacks")
        return None
    # the planner programs (and the blockwise translation) assume the
    # canonical from-layout physical; anything else keeps the eager path
    expect = list(gshape)
    if from_split is not None:
        expect[from_split] = comm.padded_size(gshape[from_split])
    if tuple(x._phys_shape()) != tuple(expect):
        _metrics().inc("op_engine.fusion_resplit_fallbacks")
        return None
    h = _handle_of(x)
    if h is None:
        _metrics().inc("op_engine.fusion_resplit_fallbacks")
        return None
    out_phys = list(gshape)
    pad = [(0, 0)] * len(gshape)
    if to_split is not None:
        out_phys[to_split] = comm.padded_size(gshape[to_split])
        pad[to_split] = (0, out_phys[to_split] - gshape[to_split])
    node = _make_node(_resplit_op,
                      {"gshape": gshape, "pad": tuple(pad),
                       "sharding": comm.sharding(len(gshape), to_split)},
                      (h,), tuple(out_phys))
    if node is None:
        _metrics().inc("op_engine.fusion_resplit_fallbacks")
        return None
    node.kind = "resplit"
    node.split = to_split
    node.smeta = {"from": from_split, "to": to_split}
    node.comm = comm
    _metrics().inc("op_engine.fusion_resplit_nodes")
    return _wrap(node, gshape, to_split, x.device, comm)


# ---------------------------------------------------------------------- #
# flush                                                                  #
# ---------------------------------------------------------------------- #
# Serializes flush against flush: two threads materializing overlapping
# DAGs would otherwise race plan construction against the post-run
# ``node.args = ()`` release (the eager engine's immutable __parray reads
# had no such hazard). Flushes are host-side bookkeeping around one
# program call, so serializing them costs nothing on the XLA:CPU backend
# (dispatch is serialized there anyway) and little elsewhere. RLock:
# a depth-cap flush can nest inside a record that nested inside a flush-
# adjacent path.
_FLUSH_LOCK = threading.RLock()


def materialize(arr) -> None:
    """Evaluate ``arr``'s pending chain (the ``DNDarray.larray`` hook)."""
    node = arr._lazy_node
    if node is None:
        return
    with _FLUSH_LOCK:
        if node.value is None:
            _flush(node)
        arr._set_materialized(node.value)


def cancel(arr) -> None:
    """Detach ``arr`` from its pending node (its ``larray`` is being
    overwritten): the node stays evaluable for any chain that references
    it, but no longer writes back into ``arr``."""
    node = arr._lazy_node
    if node is not None:
        node.owner = None
        arr._lazy_node = None


def _topo(root: _Node):
    """Iterative post-order over the pending sub-DAG reachable from
    ``root`` (evaluated nodes act as leaves). Returns the node list and a
    per-node in-DAG parent-reference count."""
    order = []
    state: Dict[int, int] = {}  # id -> 0 visiting / 1 done
    in_refs: Dict[int, int] = {}
    stack = [(root, False)]
    while stack:
        node, processed = stack.pop()
        if processed:
            state[id(node)] = 1
            order.append(node)
            continue
        if state.get(id(node)) is not None:
            continue
        state[id(node)] = 0
        stack.append((node, True))
        for h in node.args:
            if isinstance(h, _Node) and h.value is None:
                in_refs[id(h)] = in_refs.get(id(h), 0) + 1
                if state.get(id(h)) is None:
                    stack.append((h, False))
    return order, in_refs


def _donatable(leaves, occurs) -> Tuple[int, ...]:
    """Leaf slots whose buffer the tape provably holds the only remaining
    references to: the owning DNDarray is gone and ``sys.getrefcount``
    matches the tape's own reference bookkeeping exactly (list entry +
    loop variable + getrefcount argument + in-tape ``_Leaf`` holders).
    Anything else — a live owner, another pending tape, a user variable —
    shows up as an extra reference and vetoes donation."""
    if not _DONATE:
        return ()
    out = []
    for j, a in enumerate(leaves):
        if a.ndim == 0:
            continue  # cached scalar leaves are shared by design
        if sys.getrefcount(a) == occurs[j] + 3:
            out.append(j)
    return tuple(out)


def _flush(root: _Node) -> None:
    """Compile-and-run the pending chain under ``root`` as ONE program.

    Outputs are the root plus every interior node some live DNDarray or
    other pending chain still needs; everything else stays a fused
    temporary inside XLA. The program is cached by structural signature;
    donation slots are part of the key.

    Chains below ``HEAT_TPU_FUSION_MIN_OPS`` replay inline instead (eager
    per-op dispatch through XLA's shared op cache): compiling one
    executable per 1-3-op signature costs more than it saves, and the
    inline path is bitwise-eager by construction. ``capture_hlo`` forces
    compilation so audits can look at short chains too."""
    with _FLUSH_LOCK:
        _flush_locked(root)


def _flush_locked(root: _Node) -> None:
    with _prof.span("flush") as sp:
        _flush_spanned(root, sp)


def _flush_spanned(root: _Node, sp) -> None:
    order, in_refs = _topo(root)
    has_reduce = any(n.kind == "reduce" for n in order)
    has_contract = any(n.kind == "contract" for n in order)
    has_resplit = any(n.kind == "resplit" for n in order)

    if len(order) < _MIN_OPS and not _capture_hlo:
        _flush_inline(order, has_reduce, has_contract, has_resplit)
        return

    leaves = []        # unique concrete arrays, first-encounter order
    leaf_slot = {}     # id(array) -> slot
    leaf_splits = []   # recorded split axis per slot (shard_map in_specs)
    leaf_occurs = []   # in-tape _Leaf/value holders per slot
    leaf_owner_dead = []
    plan = []          # (fn, codes, kwargs) per node
    sig_nodes = []
    index = {}

    for pos, node in enumerate(order):
        index[id(node)] = pos
        codes = []
        for h in node.args:
            if isinstance(h, _Node) and h.value is None:
                codes.append((0, index[id(h)]))
                continue
            if isinstance(h, _Node):
                arr, owner, split, from_node = h.value, h.owner, h.split, True
            else:
                arr, owner, split, from_node = h.array, h.owner, h.split, False
            slot = leaf_slot.get(id(arr))
            if slot is None:
                slot = len(leaves)
                leaf_slot[id(arr)] = slot
                leaves.append(arr)
                leaf_splits.append(split)
                leaf_occurs.append(0)
                leaf_owner_dead.append(True)
            leaf_occurs[slot] += 1
            # a value still pinned inside a node may be referenced by other
            # pending chains through that node — never donate those
            if from_node or owner is None or owner() is not None:
                leaf_owner_dead[slot] = False
            codes.append((1, slot))
        plan.append((node.fn, tuple(codes), node.kwargs))
        sig_nodes.append((node.fn, tuple(codes), node.kwargs_key))

    out_idx = []
    root_pos = index[id(root)]
    for pos, node in enumerate(order):
        live_owner = node.owner is not None and node.owner() is not None
        shared = node.ext_refs > in_refs.get(id(node), 0)
        if pos == root_pos or live_owner or shared:
            out_idx.append(pos)
    out_idx = tuple(out_idx)

    touching = [n for n in order
                if (n.kind == "reduce" and n.rmeta["touches"])
                or (n.kind == "contract" and n.cmeta["case"] != "replicated")
                or n.kind == "resplit"]
    comm = touching[0].comm if touching else None
    sm = None
    if touching and all(n.cmeta["translatable"] for n in order
                        if n.kind == "contract"):
        # a gspmd-case contract anywhere on the tape dooms the plan at
        # that node — skip the O(tape) walk and go straight to plain-jit
        sm = _plan_sm(order, plan, leaves, leaf_splits, out_idx, comm)
    if has_reduce or has_contract or has_resplit:
        # reduce-, contract- and resplit-carrying tapes compile without
        # donation (documented contract, doc/fusion.md): the program is
        # shard_map-shaped or collective-carrying, so buffer reuse buys
        # little — and donated inputs would complicate the
        # packed-collective body for zero win
        donate = ()
    else:
        donate = tuple(j for j in _donatable(leaves, leaf_occurs)
                       if leaf_owner_dead[j])

    # mesh identity rides in through the per-leaf sharding strings (axis
    # layout + device kind); ``jax.jit`` itself re-lowers per concrete
    # input sharding, so a signature collision across distinct device sets
    # degrades to an internal recompile, never a wrong program. The
    # recorded split axes join the key because they pick the shard_map
    # in_specs; the reduce mode and comm identity key the collective form.
    # tier-aware hierarchical decomposition (HEAT_TPU_HIER + declared
    # HEAT_TPU_MESH_TIERS factorization): planned FIRST — the quant byte
    # model follows the tiered legs — and captured like the quant/chunk
    # keys below; a gate-off/undeclared/fault decision keys as None and
    # HITS any cached flat program
    hplan = _hier_flush_plan(order, sm, comm) if sm is not None else None
    hcfg = hplan[0] if hplan is not None else None
    # quantized-collective selection (HEAT_TPU_QUANT_COLLECTIVES): static
    # per-flush, so the decision, the program key and the traced body all
    # agree; a fault/floor/codec-off decision keys as None and therefore
    # HITS any cached exact program instead of compiling a duplicate
    qplan = (_quant_flush_plan(order, sm, comm, hcfg=hcfg)
             if sm is not None else None)
    # codec/block from the PLAN's captured key, never re-read from the
    # globals: a concurrent set_quant_codec between planning and build
    # (or the deferred jit trace) must not trace a body whose wire format
    # mismatches the selection or the program key
    qcfg = qplan[3] if qplan is not None else (None, 0, 0)
    qsel = qplan[0] if qplan is not None else frozenset()
    # chunk selection under the same captured-key discipline: the plan
    # fires the fault site, keys the program, and its (count, floor) is
    # what the traced body reads — never the live globals
    cplan = (_chunk_flush_plan(order, sm, comm, qsel, qcfg, hcfg=hcfg)
             if sm is not None else None)
    ccfg = cplan[0] if cplan is not None else (1, 0)

    leaf_descrs = tuple(
        (tuple(a.shape), str(a.dtype), bool(a.aval.weak_type),
         str(a.sharding), leaf_splits[j])
        for j, a in enumerate(leaves))
    key = (leaf_descrs, tuple(sig_nodes), out_idx, donate)
    if touching:
        qtag = qplan[3] if qplan is not None else None
        ctag = cplan[0] if cplan is not None else None
        htag = hplan[1] if hplan is not None else None
        key = key + (("sm" if sm is not None else "gspmd"), comm.cache_key,
                     qtag, ctag, htag)

    def build():
        _faults().check("fusion.flush.compile")
        if sm is not None:
            replay = _sm_body(plan, sm, out_idx, comm, qsel, qcfg, ccfg,
                              hcfg)
            from ._compat import shard_map

            sched, instrs, phases, in_specs, out_specs = sm
            fn = shard_map(_prof.named(replay, "flush"), mesh=comm.mesh,
                           in_specs=in_specs, out_specs=out_specs,
                           check_vma=False)
            jitted = jax.jit(fn)
        else:
            def replay(*leaf_vals):
                vals = []
                for fn, codes, kwargs in plan:
                    args = [vals[i] if tag == 0 else leaf_vals[i]
                            for tag, i in codes]
                    vals.append(fn(*args, **kwargs))
                return tuple(vals[i] for i in out_idx)

            jitted = jax.jit(_prof.named(replay, "flush"),
                             donate_argnums=donate)
        if _capture_hlo:
            global _last_hlo
            try:
                compiled = jitted.lower(*leaves).compile()
                _last_hlo = compiled.as_text()
                return compiled
            except Exception:
                pass
        return jitted

    def spanned_build():
        with _prof.span("flush.build"):
            return build()

    sp.set(n_nodes=len(order))
    try:
        misses = program_cache().misses
        program = program_cache().get_custom(key, spanned_build)
        sp.set(hit=program_cache().misses == misses)
        _faults().check("fusion.flush.dispatch")
        with _prof.span("flush.dispatch"):
            results = program(*leaves)
    except Exception:
        # HARDENED FAILURE DOMAIN (doc/robustness.md): a failed fused
        # compile or dispatch must not strand the tape. No node has been
        # mutated yet (values land only below), so the whole chain
        # replays inline through the eager per-op path — bitwise the
        # pre-fusion semantics — and the tape ends exactly as consistent
        # as a successful flush (values set, owners written back, args
        # released). A stale captured HLO from an earlier compile must
        # not satisfy a later audit either: the dump is cleared before
        # the fallback (same trap PR 6 fixed for reset(), now for the
        # error path). A genuinely-broken op raises again from the
        # inline replay and surfaces to the caller as eager dispatch
        # would have. The one unreplayable case: a DONATING program that
        # failed mid-dispatch may already have invalidated its input
        # buffers — then the original error re-raises (replaying from
        # deleted buffers would surface a misleading "Array deleted").
        if any(getattr(a, "is_deleted", lambda: False)() for a in leaves):
            raise
        global _last_hlo
        _last_hlo = None
        _metrics().inc("op_engine.fusion_flush_fallbacks")
        _flush_inline(order, has_reduce, has_contract, has_resplit,
                      is_fallback=True)
        return

    m = _metrics()
    m.inc("op_engine.fusion_flushes")
    m.inc("op_engine.fusion_ops", len(order))
    if has_reduce:
        m.inc("op_engine.fusion_reduce_flushes")
    if has_contract:
        m.inc("op_engine.fusion_contract_flushes")
    if has_resplit:
        m.inc("op_engine.fusion_resplit_flushes")
    if qplan is not None:
        # per DISPATCH (cache hits included): the counters mirror what
        # this program's collectives moved, not what compiling cost
        m.inc("op_engine.quant_collectives", qplan[1])
        m.inc("op_engine.quant_bytes_saved", qplan[2])
    if cplan is not None:
        m.inc("op_engine.chunk_collectives", cplan[1])
    if hplan is not None:
        m.inc("op_engine.hier_collectives", hplan[2])

    for pos, res in zip(out_idx, results):
        node = order[pos]
        node.value = res
        owner = node.owner() if node.owner is not None else None
        if owner is not None:
            owner._set_materialized(res)
            if node.kind == "resplit":
                # the translation zero-pads the target axis (shard_map
                # body and GSPMD fallback alike) — certify exactly this
                # buffer, matching the eager planner's _pad_zero claim
                owner._pad_zero_buf = res
    # evaluated interior nodes can never be demanded again (every external
    # holder was promoted to an output) — release their inputs promptly
    for node in order:
        node.args = ()
        node.kwargs = {}


# collective kind -> jax.lax combiner over the mesh axis
_COLL_FNS = {"psum": jax.lax.psum, "pmax": jax.lax.pmax,
             "pmin": jax.lax.pmin}


# ---------------------------------------------------------------------- #
# quantized packed collectives (HEAT_TPU_QUANT_COLLECTIVES)              #
# ---------------------------------------------------------------------- #
def _numel(shape) -> int:
    n = 1
    for s in shape:
        n *= int(s)
    return n


def _quant_dtype_ok(dt, codec) -> bool:
    """Whether a psum payload of ``dt`` is quantizable under ``codec``.
    Only additive float reductions quantize (pmax/pmin and integer/bool
    payloads must stay exact); f64 is excluded (a user reaching for f64
    asked for the precision); bf16/f16 payloads only gain under ``int8``
    (the bf16 codec would be a no-op re-encode)."""
    if codec == "int8":
        return dt in (jnp.dtype(jnp.float32), jnp.dtype(jnp.bfloat16),
                      jnp.dtype(jnp.float16))
    return dt == jnp.dtype(jnp.float32)


def _quant_payload_numel(numels, codec, block) -> int:
    """Wire-payload element count for a group of summands: the int8 codec
    BLOCK-ALIGNS every part (a scale block must never span two packed
    values — one spiky leaf's amax would crush a small-magnitude
    neighbor's elements sharing its block), so each part pads to a block
    multiple; bf16 packs raw."""
    if codec != "int8":
        return sum(numels)
    return sum(n + ((-n) % block) for n in numels)


def _quant_wire_bytes(numels, itemsize: int, codec: str,
                      sizes, block: int) -> Tuple[int, int]:
    """(exact, quantized) modeled ring-wire bytes for one float all-reduce
    of the ``numels``-element summands over mesh axes of the given
    ``sizes`` — the same per-kind formulas
    :func:`heat_tpu.utils.hlo_audit.collective_bytes` applies to real HLO
    dumps, so the ``quant_bytes_saved`` counter and the audit agree by
    construction (up to the exchange's device-chunk tail padding, which
    the model ignores). The EXACT baseline carries the raw concatenated
    payload; only the int8 leg pays the per-part block alignment
    (:func:`_quant_payload_numel`). Exact all-reduce rides reduce-scatter
    + all-gather (2 passes of the payload) over the FULL group; the int8
    codec's a2a/gather legs run over the LARGEST axis only (matching
    :func:`_quant_allreduce_parts`'s primary-axis choice), plus the exact
    f32 psum of the combined chunk over the remaining axes. NOTE for the
    bf16 codec: the model reflects the INTENDED wire dtype — on backends
    whose float normalization upcasts bf16 collectives back to f32
    (XLA:CPU), the real wire saves nothing while the counter still ticks;
    doc/fusion.md documents the caveat (the int8 legs are bitcast-guarded
    precisely to avoid it)."""
    group = 1
    for s in sizes:
        group *= s
    raw = sum(numels)
    exact = 2 * raw * itemsize * (group - 1) // group
    if codec == "bf16":
        quant = 2 * raw * 2 * (group - 1) // group
    else:  # int8
        padded = _quant_payload_numel(numels, codec, block)
        p = max(sizes)           # the primary-axis size (a2a/gather legs)
        r = group // p           # remaining-axes scope (exact chunk psum)
        nblocks = -(-padded // block)
        quant = ((padded + 2 * nblocks) * (p - 1) // p  # a2a s8+u16 scales
                 + 2 * padded * (p - 1) // p)           # u16 gather
        if r > 1:
            # f32 psum of the 1/p-size combined chunk over the rest axes
            quant += 2 * (padded * 4 // p) * (r - 1) // r
    return exact, quant


# ---------------------------------------------------------------------- #
# chunked, double-buffered packed collectives (HEAT_TPU_FUSION_CHUNKS)   #
# ---------------------------------------------------------------------- #
def _chunk_bounds(total: int, n: int, align: int):
    """``[(start, stop), ...]`` contiguous pieces of a ``total``-element
    flat payload: up to ``n`` pieces, every boundary a multiple of
    ``align`` (the tail piece carries any sub-``align`` remainder), sizes
    as even as the alignment admits. ``None`` when fewer than two aligned
    pieces exist — the caller emits the unchunked collective.

    The alignment is what makes chunking VALUE- and BYTE-exact: with
    boundaries on multiples of the communicating group size the per-chunk
    ring-model wire bytes sum to exactly the whole-payload figure
    (``floor((M·g + t)·c/g) == M·c + floor(t·c/g)``), and with the int8
    codec's ``group × block`` alignment every scale block and device
    chunk of each piece coincides with the unchunked exchange's — the
    tail piece pays exactly the padding the unchunked payload would, so
    the audit never double-charges it."""
    if n <= 1 or align < 1:
        return None
    units = total // align
    n = min(int(n), units)
    if n <= 1:
        return None
    base, extra = divmod(units, n)
    bounds, off = [], 0
    for i in range(n):
        stop = off + (base + (1 if i < extra else 0)) * align
        if i == n - 1:
            stop = total  # the tail carries the sub-align remainder
        bounds.append((off, stop))
        off = stop
    return bounds


def _pipe_gate(piece, prev_out):
    """Double-buffer gate: make chunk k's input depend on chunk k-2's
    combined output via ``lax.optimization_barrier`` (values untouched),
    so the scheduler can hold at most TWO chunk collectives in flight —
    chunk k issues while chunk k-1 crosses the wire and chunk k-2's
    consumers compute. Without the gate XLA is free to launch all N legs
    at once, which buys no overlap and N× the in-flight buffer peak."""
    barrier = getattr(jax.lax, "optimization_barrier", None)
    if barrier is None:  # ancient jax: ungated legs are still correct
        return piece
    return barrier((piece, prev_out))[0]


def _chunked_exact(flat, axes, coll, bounds):
    """The exact (or bf16-wire) packed collective over ``flat``, emitted
    as one collective per ``bounds`` piece, double-buffered. Elementwise
    reductions make each piece bitwise the matching slice of the
    unchunked result."""
    outs = []
    for i, (a, b) in enumerate(bounds):
        piece = jax.lax.slice_in_dim(flat, a, b, axis=0)
        if i >= 2:
            piece = _pipe_gate(piece, outs[i - 2])
        outs.append(coll(piece, axes))
    return jnp.concatenate(outs)


def _quant_chunk_bounds(numels, sizes, codec, block, nchunks):
    """Chunk boundaries for one quantized payload group (or ``None``):
    the bf16 codec chunks the raw concatenated payload on group-size
    boundaries like the exact path; the int8 codec chunks the
    block-ALIGNED payload (:func:`_quant_payload_numel`) on
    ``primary_axis_size × block`` boundaries, so every piece's device
    chunks and scale blocks coincide with the unchunked exchange's."""
    if nchunks <= 1:
        return None
    group = 1
    for s in sizes:
        group *= s
    if codec == "int8":
        total = _quant_payload_numel(numels, codec, block)
        align = max(sizes) * block
    else:
        total = sum(numels)
        align = group
    return _chunk_bounds(total, nchunks, align)


def _wire_u16(x):
    """bf16 -> u16 bitcast for float wire legs: XLA:CPU's float
    normalization upcasts bf16 collectives back to f32 (probed on this
    jax — the convert folds THROUGH the collective), which would silently
    un-save the bytes; integer collectives are left alone on every
    backend. Bitwise free both ways."""
    return jax.lax.bitcast_convert_type(x, jnp.uint16)


def _unwire_u16(x):
    return jax.lax.bitcast_convert_type(x, jnp.bfloat16)


def _quant_bf16_allreduce(flat, axes):
    """The bf16 codec: ONE all-reduce with the payload rounded to bf16 —
    EQuARX's BF16 AR. The reduction itself runs at wire precision; the
    downcast saturates (``_sat_bf16``) so a just-above-bf16-max payload
    enters the wire at ±bf16max instead of inf."""
    return jax.lax.psum(_sat_bf16(flat), axes).astype(flat.dtype)


# largest finite bf16 value: the int8 codec's scales and combined chunks
# travel bf16, and every downcast SATURATES into this range instead of
# rounding to inf — a finite f32 sum just above bf16 max must round-trip
# as the saturated value (0.3% off, inside the 1e-2 contract), never as
# inf, and an inf block amax must not poison its scale into inf (whose
# decode is 0*inf = NaN — the PR 10 drive gotcha, regression-pinned in
# tests/test_quant_collectives.py)
_BF16_MAX = 3.3895313892515355e38


def _sat_bf16(x):
    """Saturating f32 -> bf16 downcast (clip into finite bf16 range).
    Identity for in-range values — the clip changes nothing below
    ``_BF16_MAX`` — so in-range payloads stay bitwise the unclipped
    cast. NaN propagates (clip keeps NaN): a NaN payload is the caller's
    bug either way; only the overflow-to-inf poisoning is removed."""
    return jnp.clip(x, -_BF16_MAX, _BF16_MAX).astype(jnp.bfloat16)


def _quant_int8_allreduce(flat, primary, size, rest, block, groups=None,
                          rest_size=1):
    """The int8 block-scaled codec over mesh axis ``primary`` (static size
    ``size``; any ``rest`` axes combine the dequantized chunks exactly):

    encode     per-(device-chunk, ``_QUANT_BLOCK``-block) bf16 scale =
               amax/127, SATURATED into finite bf16 range,
               payload rounded to s8;
    exchange   reduce-scatter as ONE tiled ``all_to_all`` of the s8
               payload (+ scales bitcast u16) — device i receives every
               peer's i-th chunk;
    combine    dequantize + sum in f32 (exact given s8 inputs; the
               summands are pre-scaled down by a power of two so a
               transient partial overflow cannot turn a finite total
               into inf — the shift is exponent-exact, bitwise-neutral
               for in-range payloads);
    return     bf16 ``all_gather`` (bitcast u16 on the wire) of the
               combined chunks — saturating downcast — decoded back to
               the payload dtype.

    This is the arXiv:2004.09362 generalized-allreduce decomposition with
    quantized phases (EQuARX, arXiv:2506.17615). Wire bytes: ~3/8 of the
    exact f32 all-reduce (1 byte down + 2 bytes back vs 4 bytes each
    way). Values combine and return within bf16's finite range: payloads
    whose true sum exceeds it SATURATE at ±bf16max (they no longer
    round-trip as inf/NaN — doc/fusion.md when-not-to). ``groups``
    optionally restricts the exchange to ``axis_index_groups`` subsets of
    ``primary`` — the hierarchical decomposition's DCN leg on a flat
    mesh, where ``size`` is the per-group participant count.
    ``rest_size`` is the product of the ``rest`` axes' sizes: the
    downscale covers the WHOLE summation scope (local combine and the
    rest-axes psum), so the shift back to true magnitude happens only
    after every addition has run."""
    dt = flat.dtype
    f = flat.astype(jnp.float32)
    n = f.shape[0]
    chunk = -(-n // size)
    chunk = -(-chunk // block) * block
    total = chunk * size
    if total != n:
        f = jnp.pad(f, (0, total - n))
    m = f.reshape(size, chunk // block, block)
    amax = jnp.max(jnp.abs(m), axis=-1, keepdims=True)
    # the scale is rounded to bf16 BEFORE the encode divide, so encode and
    # decode use the identical value — no scale-rounding skew. Saturated:
    # an inf amax (non-finite payload block) must yield a finite scale,
    # or the decode's 0 * inf poisons the whole block as NaN
    scale = _sat_bf16(jnp.where(amax > 0, amax, 1.0) * (1.0 / 127.0))
    q = jnp.clip(jnp.round(m / scale.astype(jnp.float32)),
                 -127, 127).astype(jnp.int8)
    q = jax.lax.all_to_all(q, primary, split_axis=0, concat_axis=0,
                           tiled=True, axis_index_groups=groups)
    s = jax.lax.all_to_all(_wire_u16(scale), primary, split_axis=0,
                           concat_axis=0, tiled=True,
                           axis_index_groups=groups)
    s = _unwire_u16(s).astype(jnp.float32)
    # combine with power-of-two downscaled summands: partial sums of
    # `size * rest_size` terms each bounded by amax can transiently pass
    # f32 max even when the total is representable (±1e38-magnitude
    # gradients) — dividing the SCALES by 2^ceil(log2(scope)) bounds
    # every partial (including the rest-axes psum's) by max|amax|, and
    # the final shift back is exact (exponent arithmetic)
    k = float(1 << max(0, (size * max(1, int(rest_size)) - 1)
                       .bit_length()))
    part = jnp.sum(q.astype(jnp.float32) * (s * (1.0 / k)), axis=0)
    if rest:
        part = jax.lax.psum(part, rest)
    part = part * k
    g = jax.lax.all_gather(_wire_u16(_sat_bf16(part)), primary, axis=0,
                           tiled=True, axis_index_groups=groups)
    out = _unwire_u16(g).astype(jnp.float32).reshape(-1)
    if total != n:
        out = out[:n]
    return out.astype(dt)


def _quant_allreduce_parts(parts, axes, sizes, codec, block, bounds=None):
    """Quantized all-reduce of mutually independent same-dtype shard-local
    summands: flatten-concat (the int8 codec block-ALIGNS each part —
    see :func:`_quant_payload_numel`), one quantized exchange, unpack.
    The int8 exchange runs over the LARGEST axis (best chunking) with any
    remaining axes combined exactly on the already-reduced chunks.
    ``bounds`` (:func:`_quant_chunk_bounds`) splits the exchange into
    double-buffered pipeline chunks — per-codec block alignment makes the
    chunked exchange bitwise the unchunked one."""
    if codec == "int8":
        flats = []
        for p in parts:
            v = p.reshape(-1)
            pad = (-_numel(p.shape)) % block
            flats.append(jnp.pad(v, (0, pad)) if pad else v)
        flat = flats[0] if len(flats) == 1 else jnp.concatenate(flats)
        k, rest, rest_size = _slow_primary(axes, sizes)
        if bounds is None:
            comb = _quant_int8_allreduce(flat, axes[k], sizes[k], rest,
                                         block, rest_size=rest_size)
        else:
            def int8_leg(piece, _axes):
                return _quant_int8_allreduce(piece, axes[k], sizes[k],
                                             rest, block,
                                             rest_size=rest_size)

            comb = _chunked_exact(flat, None, int8_leg, bounds)
        stride = block
    else:
        flat = parts[0].reshape(-1) if len(parts) == 1 else \
            jnp.concatenate([p.reshape(-1) for p in parts])
        if bounds is None:
            comb = _quant_bf16_allreduce(flat, tuple(axes))
        else:
            comb = _chunked_exact(flat, tuple(axes), _quant_bf16_allreduce,
                                  bounds)
        stride = 1
    out, off = [], 0
    for p in parts:
        n = _numel(p.shape)
        out.append(comb[off:off + n].reshape(p.shape))
        off += n + ((-n) % stride)
    return out


# ---------------------------------------------------------------------- #
# tier-aware hierarchical packed collectives (HEAT_TPU_HIER)             #
# ---------------------------------------------------------------------- #
def _slow_axis_name(hk) -> str:
    """The slow (DCN) tier's mesh-axis name under declaration ``hk[1]``:
    the first name of a name-form declaration, else the built-in
    ``"dcn"`` (a grid that names an axis ``"dcn"`` has declared it)."""
    t = hk[1]
    if isinstance(t, tuple) and t and isinstance(t[0], str):
        return t[0]
    return "dcn"


def _hier_factor(size, hk):
    """The declared ``(d, i)`` factorization when it exactly factors a
    flat ``size``-device scope into d>1 hosts × i>1 devices, else None."""
    t = hk[1]
    if not (isinstance(t, tuple) and len(t) == 2
            and all(isinstance(v, int) for v in t)):
        return None
    d, i = t
    if d > 1 and i > 1 and d * i == int(size):
        return (d, i)
    return None


def _hier_dtype_ok(dt) -> bool:
    """bool payloads keep the flat collective (a reduce-scattered pred
    reduction is not portably expressible); every other dtype decomposes
    exactly (sum reassociation: bitwise for ints, few-ulp for floats)."""
    return dt != jnp.dtype(jnp.bool_)


def _hier_subgroups(members, qset, numel_of, dt, dcn_codec, ici_codec,
                    ici_floor):
    """The qm/im/rest tier-subgroup split — ONE source for the
    predicates the plan/key/body-agreement argument depends on, shared
    by the flush body (``_sm_body.emit_all``), :func:`packed_psum` and
    :func:`_chunk_flush_plan`: quant-selected members (``qset``) carry
    the DCN codec plus the ICI codec on the fast legs; with the ICI
    codec armed but no DCN selection, floor-qualifying f32 members still
    ride the bf16 fast legs; everything else goes exact. Returns
    ``((qm, dcn_codec, ici), (im, None, ici), (rest, None, None))``."""
    qm = [m for m in members if m in qset]
    im = []
    if ici_codec == "bf16" and dt == jnp.dtype(jnp.float32):
        im = [m for m in members if m not in qset
              and numel_of(m) >= ici_floor]
    taken = set(qm) | set(im)
    rest = [m for m in members if m not in taken]
    return ((qm, dcn_codec, ici_codec), (im, None, ici_codec),
            (rest, None, None))


def _slow_primary(axes, sizes):
    """``(primary index, rest axis names, rest size product)`` — the
    largest-axis primary selection of the int8 exchange, shared by
    :func:`_quant_allreduce_parts` and ``_TierComm.slow_allreduce`` so
    the axis the a2a/gather legs ride (and the overflow downscale's
    scope) can never drift between the flat and tiered paths."""
    k = max(range(len(axes)), key=lambda j: sizes[j])
    rest = tuple(a for j, a in enumerate(axes)
                 if j != k and sizes[j] > 1)
    rest_size = 1
    for j, s in enumerate(sizes):
        if j != k and s > 1:
            rest_size *= s
    return k, rest, rest_size


class _TierComm:
    """Static leg descriptor for ONE hierarchical packed exchange: how to
    reduce-scatter / all-gather over the fast (ICI) tier and all-reduce
    over the slow (DCN) tier. Two forms share the interface:

    * **named** — the scope's mesh axes split by name into fast/slow
      tiers (a ``MeshGrid`` with a ``"dcn"`` axis: the 5-axis
      ``TransformerLM`` grid, ``DataParallel``'s 2-D tier grid, DASO);
    * **flat** — a single mesh axis with a declared ``(d, i)``
      factorization, tiers expressed as ``axis_index_groups`` (the flush
      path's 1-D communicator; device order is dcn-major, matching
      ``jax.devices()`` on a real pod).

    ``replicated=True`` marks values already replicated over the fast
    tier (DASO's slow-tier capture): the reduce-scatter degenerates to a
    zero-collective static slice of each device's own tile."""

    __slots__ = ("pf", "ps", "fast_axes", "fast_sizes", "slow_axes",
                 "slow_sizes", "axn", "fast_groups", "slow_groups",
                 "replicated")

    def __init__(self):
        self.axn = None
        self.fast_groups = self.slow_groups = None
        self.replicated = False

    @classmethod
    def named(cls, fast_axes, fast_sizes, slow_axes, slow_sizes,
              replicated=False):
        tc = cls()
        tc.fast_axes = tuple(fast_axes)
        tc.fast_sizes = tuple(int(s) for s in fast_sizes)
        tc.slow_axes = tuple(slow_axes)
        tc.slow_sizes = tuple(int(s) for s in slow_sizes)
        tc.pf = 1
        for s in tc.fast_sizes:
            tc.pf *= s
        tc.ps = 1
        for s in tc.slow_sizes:
            tc.ps *= s
        tc.replicated = bool(replicated)
        return tc

    @classmethod
    def flat(cls, axn, d, i):
        tc = cls()
        tc.axn = axn
        tc.pf, tc.ps = int(i), int(d)
        tc.fast_sizes, tc.slow_sizes = (int(i),), (int(d),)
        tc.fast_axes = tc.slow_axes = ()
        # dcn-major device order: device h*i + j = host h, local slot j
        tc.fast_groups = tuple(tuple(h * i + j for j in range(i))
                               for h in range(d))
        tc.slow_groups = tuple(tuple(h * i + j for h in range(d))
                               for j in range(i))
        return tc

    # -- fast (ICI) tier legs ----------------------------------------- #
    def rs(self, x):
        """Tiled reduce-scatter of a flat payload over the fast tier."""
        if self.axn is not None:
            return jax.lax.psum_scatter(
                x, self.axn, scatter_dimension=0, tiled=True,
                axis_index_groups=self.fast_groups)
        return jax.lax.psum_scatter(x, self.fast_axes,
                                    scatter_dimension=0, tiled=True)

    def ag(self, x):
        """Tiled all-gather of the combined shard over the fast tier."""
        if self.axn is not None:
            return jax.lax.all_gather(x, self.axn, axis=0, tiled=True,
                                      axis_index_groups=self.fast_groups)
        return jax.lax.all_gather(x, self.fast_axes, axis=0, tiled=True)

    def fast_index(self):
        """This device's flattened index along the fast tier (the tile
        the replicated form slices in place of the reduce-scatter)."""
        if self.axn is not None:
            return jax.lax.axis_index(self.axn) % self.pf
        idx = None
        for a, s in zip(self.fast_axes, self.fast_sizes):
            ai = jax.lax.axis_index(a)
            idx = ai if idx is None else idx * s + ai
        return idx

    # -- slow (DCN) tier leg ------------------------------------------ #
    def _slow_psum(self, x):
        if self.axn is not None:
            return jax.lax.psum(x, self.axn,
                                axis_index_groups=self.slow_groups)
        return jax.lax.psum(x, self.slow_axes)

    def slow_allreduce(self, x, codec, block, bounds=None):
        """All-reduce of the 1/pf shard across the slow tier with the
        DCN wire codec; ``bounds`` pipelines this leg into
        double-buffered chunks (the PR 10 chunking composed onto the
        slow tier — the legs worth overlapping are the slow ones)."""
        if codec == "int8":
            if self.axn is not None:
                def leg(piece, _axes):
                    return _quant_int8_allreduce(
                        piece, self.axn, self.ps, (), block,
                        groups=self.slow_groups)
            else:
                k, rest, rest_size = _slow_primary(self.slow_axes,
                                                   self.slow_sizes)

                def leg(piece, _axes):
                    return _quant_int8_allreduce(
                        piece, self.slow_axes[k], self.slow_sizes[k],
                        rest, block, rest_size=rest_size)
        elif codec == "bf16":
            def leg(piece, _axes):
                return self._slow_psum(_sat_bf16(piece)).astype(piece.dtype)
        else:
            def leg(piece, _axes):
                return self._slow_psum(piece)
        if bounds is None:
            return leg(x, None)
        return _chunked_exact(x, None, leg, bounds)


def _tier_scope(axes, sizes, hk, replicated=()):
    """A :class:`_TierComm` for a ``packed_psum`` reduction scope, or
    None when no hierarchy applies: the REPLICATED form when the caller
    declares fast axes its values are replicated over (DASO), the named
    split when the scope contains the slow-named axis plus fast axes
    (tiered model grids), or the flat ``(d, i)`` factorization when the
    scope is one axis of exactly that size."""
    if replicated:
        rep = tuple(replicated)
        rsizes = tuple(int(jax.lax.psum(1, a)) for a in rep)
        pf = 1
        for s in rsizes:
            pf *= s
        if pf > 1:
            return _TierComm.named(rep, rsizes, axes, sizes,
                                   replicated=True)
        return None
    slow_name = _slow_axis_name(hk)
    slow = tuple(j for j, a in enumerate(axes)
                 if a == slow_name and sizes[j] > 1)
    fast = tuple(j for j, a in enumerate(axes)
                 if a != slow_name and sizes[j] > 1)
    if slow and fast:
        return _TierComm.named(
            tuple(axes[j] for j in fast), tuple(sizes[j] for j in fast),
            tuple(axes[j] for j in slow), tuple(sizes[j] for j in slow))
    if len(axes) == 1:
        f = _hier_factor(sizes[0], hk)
        if f is not None:
            return _TierComm.flat(axes[0], f[0], f[1])
    return None


def _hier_leg_bounds(numels, codec, block, pf, ps, cn):
    """Pipeline-chunk bounds for the DCN leg of one hierarchical payload
    group (PR 10 chunking composed onto the slow tier), or None: the
    1/pf shard splits on ps-aligned (int8: ps×block-aligned) boundaries
    so every piece's device chunks and scale blocks coincide with the
    unchunked slow exchange's — value- and byte-exact per the
    ``_chunk_bounds`` lemma."""
    stride = pf * (block if codec == "int8" else 1)
    shard_total = sum(n + ((-n) % stride) for n in numels) // pf
    align = ps * (block if codec == "int8" else 1)
    return _chunk_bounds(shard_total, cn, align)


def _hier_allreduce_parts(parts, tc, dcn_codec, block, ici_codec,
                          bounds=None):
    """Hierarchical all-reduce of mutually independent same-dtype
    shard-local summands: flatten-concat (each part padded so every
    fast-tier tile boundary — and, under the int8 DCN codec, every scale
    block — stays within one part), reduce-scatter over the fast (ICI)
    tier, all-reduce of the 1/pf shard over the slow (DCN) tier with the
    DCN wire codec (``bounds`` pipelines THIS leg), then all-gather back
    over the fast tier — the generalized-allreduce decomposition
    (arXiv:2004.09362) with EQuARX's tier-selective codecs
    (arXiv:2506.17615): full-precision bytes cross the fast wire, only
    the 1/pf shard (optionally block-scaled int8) crosses the slow one.

    ``ici_codec="bf16"`` rounds the payload to bf16 for the fast legs
    (native on TPU ICI; the all-gather travels bitcast u16 so XLA:CPU
    float normalization cannot upcast it — the reduce-scatter is a
    reduction and keeps the usual bf16-collective CPU caveat). With
    ``tc.replicated`` the reduce-scatter degenerates to each device's
    zero-collective static slice of its own tile (values already agree
    across the fast tier — DASO's capture).

    Value contract: the decomposition re-associates the flat psum —
    bitwise for integer payloads, few-ulp for floats (the documented
    psum-reassociation freedom); tier codecs add their documented error
    on top, on their tier only."""
    dt = parts[0].dtype
    pf = tc.pf
    stride = pf * (block if dcn_codec == "int8" else 1)
    flats = []
    for p in parts:
        v = p.reshape(-1)
        pad = (-v.shape[0]) % stride
        flats.append(jnp.pad(v, (0, pad)) if pad else v)
    flat = flats[0] if len(flats) == 1 else jnp.concatenate(flats)
    wire_bf16 = ici_codec == "bf16" and flat.dtype == jnp.dtype(jnp.float32)
    if tc.replicated:
        chunkn = flat.shape[0] // pf
        shard = jax.lax.dynamic_slice_in_dim(
            flat, tc.fast_index() * chunkn, chunkn, axis=0)
        if wire_bf16:
            shard = _sat_bf16(shard).astype(flat.dtype)
    elif wire_bf16:
        shard = tc.rs(_sat_bf16(flat)).astype(flat.dtype)
    else:
        shard = tc.rs(flat)
    comb = tc.slow_allreduce(shard, dcn_codec, block, bounds=bounds)
    if wire_bf16:
        out_flat = _unwire_u16(tc.ag(_wire_u16(_sat_bf16(comb)))).astype(dt)
    else:
        out_flat = tc.ag(comb)
    out, off = [], 0
    for p in parts:
        n = _numel(p.shape)
        out.append(out_flat[off:off + n].reshape(p.shape))
        off += n + ((-n) % stride)
    return out


def _hier_wire_bytes(numels, itemsize: int, dcn_codec, ici_codec,
                     pf: int, ps: int, block: int) -> Tuple[int, int]:
    """(flat exact, hierarchical) modeled ring-wire bytes for one psum
    payload group under the tier decomposition — the same per-kind
    formulas :func:`heat_tpu.utils.hlo_audit.collective_bytes` applies
    to real HLO (AR = 2R(g-1)/g, RS = R_out(g-1), AG = R_out(g-1)/g),
    so the counters and the audit agree by construction. The exact
    baseline is the flat full-mesh all-reduce of the raw payload; the
    hierarchical figure sums the fast RS+AG legs (bf16-halved under the
    ICI codec) and the slow leg at 1/pf payload with the DCN codec."""
    g = pf * ps
    raw = sum(numels)
    exact = 2 * raw * itemsize * (g - 1) // g
    if dcn_codec == "int8":
        padded = sum(n + ((-n) % (pf * block)) for n in numels)
    else:
        padded = sum(n + ((-n) % pf) for n in numels)
    item_fast = 2 if ici_codec == "bf16" else itemsize
    hier = 2 * padded * item_fast * (pf - 1) // pf  # RS + AG over ici
    shard = padded // pf
    if dcn_codec == "int8":
        nblocks = -(-shard // block)
        hier += ((shard + 2 * nblocks) * (ps - 1) // ps  # a2a s8 + scales
                 + 2 * shard * (ps - 1) // ps)           # u16 gather
    elif dcn_codec == "bf16":
        hier += 2 * shard * 2 * (ps - 1) // ps
    else:
        hier += 2 * shard * itemsize * (ps - 1) // ps
    return exact, hier


def _hier_flush_plan(order, sm, comm):
    """Static hierarchical-decomposition selection for one shard_map
    flush: ``(hcfg, htag, n_groups)`` — the ``(d, i, ici_codec,
    ici_floor)`` leg configuration captured AT PLANNING TIME (a
    concurrent ``set_mesh_tiers``/``set_hier_enabled``/floor change
    between planning and the deferred jit trace must not change the
    collective structure out from under the program key; the floor
    selects which payloads ride the bf16 fast legs when no quant codec
    is armed), the tag that keys the program, and the number of psum
    payload groups the body decomposes (ticked per dispatch as
    ``op_engine.hier_collectives``) — or None when the hierarchy does
    not apply (gate off, no/mismatched factorization for this flat
    communicator, no qualifying psum group). The ``fusion.hier.exchange``
    fault site fires here: a fault degrades the WHOLE flush to the flat
    packed emission — keyed as such, so it HITS any cached flat program
    — counted in ``op_engine.hier_fallbacks``."""
    hkey = hier_key()
    if not hkey[0]:
        return None
    f = _hier_factor(comm.size, hkey)
    if f is None:
        return None
    sched, instrs, phases, _, _ = sm
    totals: Dict[Tuple, int] = {}
    for pos in sched:
        ins = instrs[pos]
        if ins[0] in ("reduce", "contract") and ins[1] == "psum" \
                and _hier_dtype_ok(jnp.dtype(order[pos].aval.dtype)):
            key = (phases[pos], str(jnp.dtype(order[pos].aval.dtype)))
            totals[key] = totals.get(key, 0) + _numel(order[pos].aval.shape)
    # the hier payload floor gates per GROUP total (hkey[3], captured):
    # latency-bound tiny groups keep the flat collective
    n = sum(1 for v in totals.values() if v >= hkey[3])
    if not n:
        return None
    try:
        _faults().check("fusion.hier.exchange")
    except Exception:
        _metrics().inc("op_engine.hier_fallbacks")
        return None
    floor = _QUANT_FLOOR
    return (f[0], f[1], hkey[2], floor, hkey[3]), (hkey, floor), n


def reset_qinfo(qinfo: dict) -> None:
    """Reset a ``packed_psum`` accounting dict at the START of a traced
    body — runs once per trace, so the dict is stable (and idempotent
    across retraces) by the time any dispatch completes."""
    qinfo["collectives"] = 0
    qinfo["bytes_saved"] = 0
    qinfo["chunk_collectives"] = 0
    qinfo["hier_collectives"] = 0


def tick_quant(qinfo: dict) -> None:
    """Tick ``op_engine.quant_collectives`` / ``quant_bytes_saved`` (and
    ``op_engine.chunk_collectives`` for chunk-pipelined payload groups)
    from a trace-time ``packed_psum`` accounting dict — call once per
    DISPATCH of the program whose body filled it (the model-level step
    wrappers and DASO's capture do; the flush path ticks from its static
    plan)."""
    if qinfo.get("collectives"):
        m = _metrics()
        m.inc("op_engine.quant_collectives", qinfo["collectives"])
        m.inc("op_engine.quant_bytes_saved", qinfo["bytes_saved"])
    if qinfo.get("chunk_collectives"):
        _metrics().inc("op_engine.chunk_collectives",
                       qinfo["chunk_collectives"])
    if qinfo.get("hier_collectives"):
        _metrics().inc("op_engine.hier_collectives",
                       qinfo["hier_collectives"])


def _quant_flush_plan(order, sm, comm, hcfg=None):
    """Static quant selection for one shard_map flush: ``(qsel, n,
    bytes_saved, qkey)`` — the pending-psum node positions routed through
    the quantized exchange, the rewritten-collective count, the modeled
    wire bytes saved (both ticked per dispatch by ``_flush_locked``) and
    the :func:`quant_key` captured AT PLANNING TIME (a concurrent
    ``set_quant_codec`` between planning and build must not key or trace
    the program with a different codec than the one the selection is
    valid for) — or None when nothing qualifies. Mirrors ``emit_all``'s
    phase grouping exactly (same (phase, kind, dtype) keys), so the
    selection, the program key and the body agree by construction. The
    ``fusion.quant.encode`` fault site fires here: a fault falls back to
    the exact collectives (and, via the key, to any cached exact
    program), counted in ``op_engine.quant_fallbacks``."""
    qkey = quant_key()  # one coherent read of the codec configuration
    codec, floor, block = qkey
    if codec is None or comm.size < 2:
        return None
    sched, instrs, phases, _, _ = sm
    groups: Dict[Tuple, list] = {}
    for pos in sched:
        ins = instrs[pos]
        if ins[0] not in ("reduce", "contract") or ins[1] != "psum":
            continue
        dt = jnp.dtype(order[pos].aval.dtype)
        groups.setdefault((phases[pos], str(dt)), []).append(pos)
    sel, n, saved = set(), 0, 0
    for (_ph, _dt), members in groups.items():
        dt = jnp.dtype(_dt)
        if not _quant_dtype_ok(dt, codec):
            continue
        mq = [p for p in members
              if _numel(order[p].aval.shape) >= floor]
        if not mq:
            continue
        numels = [_numel(order[p].aval.shape) for p in mq]
        if hcfg is not None:
            # hierarchical flush: the byte model follows the tiered legs
            # (pf = hcfg[1] ici, ps = hcfg[0] dcn, ici codec hcfg[2]),
            # not the flat exchange the body no longer emits
            e, q = _hier_wire_bytes(numels, dt.itemsize, codec, hcfg[2],
                                    hcfg[1], hcfg[0], block)
        else:
            e, q = _quant_wire_bytes(numels, dt.itemsize, codec,
                                     (comm.size,), block)
        sel.update(mq)
        n += 1
        saved += max(0, e - q)
    if not sel:
        return None
    try:
        _faults().check("fusion.quant.encode")
    except Exception:
        _metrics().inc("op_engine.quant_fallbacks")
        return None
    return frozenset(sel), n, saved, qkey


def _chunk_flush_plan(order, sm, comm, qsel, qcfg, hcfg=None):
    """Static chunk selection for one shard_map flush: ``(ckey,
    n_groups)`` — the :func:`chunk_key` captured AT PLANNING TIME (a
    concurrent ``set_chunk_count`` between planning and the deferred jit
    trace must not change the leg structure out from under the program
    key) and the number of packed payload groups the body will emit
    chunked (ticked per dispatch as ``op_engine.chunk_collectives``) —
    or None when nothing qualifies. Mirrors ``emit_all``'s grouping and
    its quant split exactly (same (phase, kind, dtype) keys, same
    payload-floor and alignment predicates over the same static shapes),
    so the selection, the program key and the traced body agree by
    construction. The ``fusion.chunk.dispatch`` fault site fires here,
    once per intended chunk leg: a fault degrades the WHOLE flush to the
    unchunked packed emission — keyed as such, so it HITS any cached
    unchunked program — counted in ``op_engine.chunk_fallbacks``."""
    ckey = chunk_key()  # one coherent read of the chunk configuration
    cn, cfloor = ckey
    if cn <= 1 or comm.size < 2:
        return None
    sched, instrs, phases, _, _ = sm
    groups: Dict[Tuple, list] = {}
    for pos in sched:
        ins = instrs[pos]
        if ins[0] not in ("reduce", "contract") or ins[1] is None:
            continue
        dt = jnp.dtype(order[pos].aval.dtype)
        groups.setdefault((phases[pos], ins[1], str(dt)), []).append(pos)
    chunked = 0
    for (_ph, _kind, _dt), members in groups.items():
        numel_of = lambda p: _numel(order[p].aval.shape)  # noqa: E731
        hier_grp = (hcfg is not None and _kind == "psum"
                    and _hier_dtype_ok(jnp.dtype(_dt))
                    and sum(numel_of(p) for p in members) >= hcfg[4])
        if hier_grp:
            # hierarchical group: chunking rides the DCN leg of each
            # subgroup — the SAME shared split + bounds predicates the
            # body applies (_hier_subgroups / _hier_leg_bounds)
            for sub, sub_codec, _si in _hier_subgroups(
                    members, qsel, numel_of, jnp.dtype(_dt), qcfg[0],
                    hcfg[2], hcfg[3]):
                if not sub:
                    continue
                numels = [numel_of(p) for p in sub]
                if sum(numels) >= cfloor and _hier_leg_bounds(
                        numels, sub_codec, qcfg[2], hcfg[1], hcfg[0],
                        cn) is not None:
                    chunked += 1
            continue
        qm = [p for p in members if p in qsel]
        rest = [p for p in members if p not in qsel]
        if qm:
            numels = [numel_of(p) for p in qm]
            if sum(numels) >= cfloor and _quant_chunk_bounds(
                    numels, (comm.size,), qcfg[0], qcfg[2],
                    cn) is not None:
                chunked += 1
        if rest:
            total = sum(numel_of(p) for p in rest)
            if total >= cfloor and _chunk_bounds(
                    total, cn, comm.size) is not None:
                chunked += 1
    if not chunked:
        return None
    try:
        for _ in range(cn):  # the site fires per intended chunk leg
            _faults().check("fusion.chunk.dispatch")
    except Exception:
        _metrics().inc("op_engine.chunk_fallbacks")
        return None
    return ckey, chunked


def _plan_sm(order, plan, leaves, leaf_splits, out_idx, comm):
    """Translate a reduce-carrying tape into a shard_map execution plan, or
    None when the tape is not provably block-safe (the caller then
    compiles the global replay under plain ``jax.jit`` and GSPMD places
    the collectives — still one program, just not hand-placed).

    The plan tracks each value's layout state (split axis or replicated),
    schedules nodes into **phases** so that mutually independent split-axis
    reductions land in the same phase (one packed collective per
    ``(phase, kind, dtype)`` — the fused tuple all-reduce), and notes where
    a replicated operand must be sliced to the local block.

    Returns ``(sched, instrs, phases, in_specs, out_specs)``.
    """
    size = comm.size
    states = []   # split axis of each produced value (None = replicated)
    instrs = []   # per node: ("ew", blocks) | ("pad", ax) | ("mask",)
                  #           | ("reduce", collective-or-None)
    phases = []   # emission phase per node (barrier between phases)

    def state_of(tag, i):
        return states[i] if tag == 0 else leaf_splits[i]

    def shape_of(tag, i):
        return (tuple(order[i].aval.shape) if tag == 0
                else tuple(leaves[i].shape))

    for pos, node in enumerate(order):
        _, codes, kwargs = plan[pos]
        phase = 0
        for tag, i in codes:
            if tag == 0:
                p = phases[i]
                inner = order[i]
                if (inner.kind == "reduce" and inner.rmeta["touches"]) or \
                        (inner.kind == "contract"
                         and inner.cmeta["collective"] is not None):
                    p += 1  # consumes a combined value: next phase
                phase = max(phase, p)
        if node.kind == "reduce":
            m = node.rmeta
            (tag, i), = codes
            if m["touches"]:
                if m["collective"] is None or node.comm is not comm:
                    return None
                if state_of(tag, i) != m["in_split"]:
                    return None
            elif state_of(tag, i) != m["in_split"]:
                return None
            instrs.append(("reduce", m["collective"] if m["touches"] else None))
        elif node.kind == "contract":
            cm = node.cmeta
            if not cm["translatable"] or node.comm is not comm:
                return None
            (ta, ia), (tb, ib) = codes
            sa, sb = state_of(ta, ia), state_of(tb, ib)
            blocks = ()
            if cm["case"] == "psum":
                # partial GEMM + psum. A replicated side (even contracted
                # extent — no alignment pad node carried it to block
                # state) is dynamic-sliced to its contracted-axis block
                # in the body, like replicated "ew" operands; extents are
                # aligned by construction (record_contract pads), checked
                # here so a mismatch falls back instead of miscomputing
                ok = (sa in (1, None) and sb in (0, None)
                      and (sa, sb) != (None, None))
                ka = shape_of(ta, ia)[1]
                sl = []
                if ok and sa is None:
                    ok = ka == shape_of(tb, ib)[0] and ka % size == 0
                    sl.append((0, 1))
                if ok and sb is None:
                    kb = shape_of(tb, ib)[0]
                    ok = kb == ka and kb % size == 0
                    sl.append((1, 0))
                blocks = tuple(sl)
            else:
                ok = {"local0": sa == 0 and sb is None,  # block GEMM, out 0
                      "local1": sa is None and sb == 1,  # block GEMM, out 1
                      "replicated": sa is None and sb is None,
                      }.get(cm["case"], False)
            if not ok:
                return None
            instrs.append(("contract", cm["collective"], blocks))
        elif node.kind == "resplit":
            # the planner's move mid-body: the collective sits between the
            # upstream and downstream block computations, and the value's
            # layout state switches from the source to the target split
            if node.comm is not comm:
                return None
            (tag, i), = codes
            j, k = node.smeta["from"], node.smeta["to"]
            if state_of(tag, i) != j:
                return None
            gs = kwargs["gshape"]
            expect = list(gs)
            if j is not None:
                expect[j] = comm.padded_size(gs[j])
            if tuple(shape_of(tag, i)) != tuple(expect):
                return None  # off-canonical value: let GSPMD sort it out
            instrs.append(("resplit", j, k))
        elif node.kind == "crop":
            # a crop's limits span the GLOBAL padded extent — no blockwise
            # form exists (it only ever follows a gspmd-case contract)
            return None
        elif node.kind == "mask":
            (tag, i), = codes
            if state_of(tag, i) != kwargs["axis"] or node.split != kwargs["axis"]:
                return None
            instrs.append(("mask",))
        elif node.kind == "pad":
            (tag, i), = codes
            if state_of(tag, i) is not None or node.split is None:
                return None
            instrs.append(("pad", node.split))
        else:
            k = node.split
            nshape = tuple(node.aval.shape)
            blocks = []
            for ci, (tag, i) in enumerate(codes):
                s = state_of(tag, i)
                oshape = shape_of(tag, i)
                offset = len(nshape) - len(oshape)
                if s is None:
                    if k is not None:
                        ax = k - offset
                        if ax >= 0 and oshape[ax] == nshape[k] \
                                and nshape[k] != 1:
                            blocks.append((ci, ax))
                elif k is None or s + offset != k or oshape[s] != nshape[k]:
                    return None  # layout the block model cannot express
            instrs.append(("ew", tuple(blocks)))
        states.append(node.split)
        phases.append(phase)

    for a, s in zip(leaves, leaf_splits):
        if s is None:
            continue
        if a.ndim <= s or a.shape[s] == 0 or a.shape[s] % size != 0:
            return None
        if getattr(getattr(a, "sharding", None), "mesh", None) != comm.mesh:
            return None  # foreign-mesh leaf: let GSPMD sort the layout out

    # stable phase-major topological schedule: same-phase touching reduces
    # become one packed collective at the phase barrier
    sched = sorted(range(len(order)), key=lambda p: (phases[p], p))
    in_specs = tuple(comm.spec(a.ndim, s)
                     for a, s in zip(leaves, leaf_splits))
    out_specs = tuple(comm.spec(len(order[p].aval.shape), states[p])
                      for p in out_idx)
    return sched, instrs, phases, in_specs, out_specs


def _sm_body(plan, sm, out_idx, comm, qsel=frozenset(),
             qcfg=(None, 0, 0), ccfg=(1, 0), hcfg=None):
    """The shard_map replay body for a :func:`_plan_sm` plan: every value
    is a shard-local block (replicated values are full arrays), reduce
    partials accumulate per phase and combine in ONE flattened collective
    per ``(kind, dtype)`` at each phase barrier. Positions in ``qsel``
    (:func:`_quant_flush_plan`) route through the quantized exchange for
    the CAPTURED ``qcfg = (codec, floor, block)`` instead (never the live
    globals — the trace may run after a toggle); sub-floor members of the
    same group keep the exact flattened psum alongside. ``ccfg = (count,
    floor)`` (:func:`_chunk_flush_plan`'s captured :func:`chunk_key`)
    splits qualifying payload groups into double-buffered pipeline chunk
    collectives — same floor/alignment predicates as the plan, so the
    body emits exactly the leg structure the plan counted and keyed.
    ``hcfg = (d, i, ici_codec)`` (:func:`_hier_flush_plan`'s captured
    tier factorization) routes every psum payload group through the
    hierarchical decomposition instead — reduce-scatter inside each
    i-device ICI group, all-reduce of the 1/i shard across the d DCN
    peers (quant members with the DCN codec, chunk bounds on this leg),
    all-gather back — so full-precision bytes never cross the slow tier
    whole. pmax/pmin (and bool) groups keep the flat collective."""
    sched, instrs, phases, _, _ = sm
    axn = comm.axis_name
    size = comm.size
    cn, cfloor = ccfg
    tc = _TierComm.flat(axn, hcfg[0], hcfg[1]) if hcfg is not None else None
    hier_ici = hcfg[2] if hcfg is not None else None
    # lazy (utils/core cycle): the resplit branch reuses the planner's
    # pad helper so the blockwise translation shares its one source
    from . import resharding

    def body(*leaf_vals):
        vals = [None] * len(plan)
        pend = {}  # pos -> collective kind (partials awaiting combine)

        def emit_all():
            groups: Dict[Tuple, list] = {}
            for pos2, kind in pend.items():
                groups.setdefault((kind, jnp.dtype(vals[pos2].dtype)),
                                  []).append(pos2)
            pend.clear()
            for (kind, _dt), members in groups.items():
                coll = _COLL_FNS[kind]
                if tc is not None and kind == "psum" \
                        and _hier_dtype_ok(_dt) \
                        and sum(_numel(vals[p2].shape)
                                for p2 in members) >= hcfg[4]:
                    # hierarchical decomposition (group total at/above
                    # the captured hier floor): the shared subgroup
                    # split — qsel members carry the DCN codec (and the
                    # ICI codec on the fast legs); with no quant codec
                    # armed the ICI codec still applies to the
                    # floor-qualifying f32 payloads (the plan's
                    # CAPTURED floor, mirroring packed_psum); the rest
                    # ride exact tiered legs. PR 10 chunk bounds
                    # pipeline each DCN sub-leg
                    for sub, sub_codec, sub_ici in _hier_subgroups(
                            members, qsel,
                            lambda p2: _numel(vals[p2].shape), _dt,
                            qcfg[0], hier_ici, hcfg[3]):
                        if not sub:
                            continue
                        numels = [_numel(vals[p2].shape) for p2 in sub]
                        bounds = None
                        if cn > 1 and sum(numels) >= cfloor:
                            bounds = _hier_leg_bounds(
                                numels, sub_codec, qcfg[2], tc.pf,
                                tc.ps, cn)
                        for p2, v in zip(sub, _hier_allreduce_parts(
                                [vals[p2] for p2 in sub], tc, sub_codec,
                                qcfg[2], sub_ici, bounds=bounds)):
                            vals[p2] = v
                    continue
                if qsel:
                    qm = [p2 for p2 in members if p2 in qsel]
                    if qm:
                        numels = [_numel(vals[p2].shape) for p2 in qm]
                        bounds = None
                        if cn > 1 and sum(numels) >= cfloor:
                            bounds = _quant_chunk_bounds(
                                numels, (size,), qcfg[0], qcfg[2], cn)
                        for p2, v in zip(qm, _quant_allreduce_parts(
                                [vals[p2] for p2 in qm], (axn,), (size,),
                                qcfg[0], qcfg[2], bounds=bounds)):
                            vals[p2] = v
                        members = [p2 for p2 in members if p2 not in qsel]
                        if not members:
                            continue
                total = sum(_numel(vals[p2].shape) for p2 in members)
                bounds = (_chunk_bounds(total, cn, size)
                          if cn > 1 and total >= cfloor else None)
                if bounds is None and len(members) == 1:
                    p2 = members[0]
                    vals[p2] = coll(vals[p2], axn)
                    continue
                packed = jnp.concatenate([vals[p2].reshape(-1)
                                          for p2 in members])
                combined = (coll(packed, axn) if bounds is None
                            else _chunked_exact(packed, axn, coll, bounds))
                off = 0
                for p2 in members:
                    shp = vals[p2].shape
                    n = 1
                    for s in shp:
                        n *= s
                    vals[p2] = combined[off:off + n].reshape(shp)
                    off += n

        def block(a, ax):
            chunk = a.shape[ax] // size
            return jax.lax.dynamic_slice_in_dim(
                a, jax.lax.axis_index(axn) * chunk, chunk, axis=ax)

        cur = 0
        for pos in sched:
            if phases[pos] != cur:
                emit_all()
                cur = phases[pos]
            fn, codes, kwargs = plan[pos]
            args = [vals[i] if tag == 0 else leaf_vals[i]
                    for tag, i in codes]
            ins = instrs[pos]
            op = ins[0]
            if op == "ew":
                for ci, ax in ins[1]:
                    args[ci] = block(args[ci], ax)
                vals[pos] = fn(*args, **kwargs)
            elif op == "pad":
                vals[pos] = block(fn(*args, **kwargs), ins[1])
            elif op == "mask":
                a = args[0]
                kax = kwargs["axis"]
                start = jax.lax.axis_index(axn) * a.shape[kax]
                iota = jax.lax.broadcasted_iota(jnp.int32, a.shape, kax) \
                    + start
                vals[pos] = jnp.where(iota < kwargs["n"], a,
                                      jnp.asarray(kwargs["fill"], a.dtype))
            elif op == "resplit":
                # the reshard planner's per-(from, to) move on the local
                # block (core/resharding.py, arXiv:2112.01075) — the
                # collective placed mid-body, not at a flush barrier
                a = args[0]
                j, k = ins[1], ins[2]
                gs = kwargs["gshape"]
                if k is None:
                    # split j → None: gathering IS the semantics here
                    a = jax.lax.all_gather(a, axn, axis=j, tiled=True)
                    if a.shape[j] != gs[j]:
                        a = jax.lax.slice_in_dim(a, 0, gs[j], axis=j)
                else:
                    pad = kwargs["pad"]
                    if pad[k][1]:
                        # local zero-pad of axis k so the tile split (or
                        # the canonical chunking) divides evenly — the
                        # planner's own helper (core/resharding.py)
                        a = resharding._pad_axis(
                            a, k, a.shape[k] + pad[k][1])
                    if j is None:
                        # None → k: every device slices its own canonical
                        # chunk out of the replicated value; ZERO
                        # collectives
                        ck = a.shape[k] // size
                        a = jax.lax.dynamic_slice_in_dim(
                            a, jax.lax.axis_index(axn) * ck, ck, axis=k)
                    else:
                        # j → k: ONE all_to_all (split_axis=k,
                        # concat_axis=j) then cut axis j's tail padding
                        a = jax.lax.all_to_all(
                            a, axn, split_axis=k, concat_axis=j, tiled=True)
                        if a.shape[j] != gs[j]:
                            a = jax.lax.slice_in_dim(a, 0, gs[j], axis=j)
                vals[pos] = a
            else:  # reduce/contract: shard-local partial (or local GEMM on
                # blocks), combined at the phase barrier when a collective
                # kind is attached
                if op == "contract":
                    for ci, ax in ins[2]:
                        args[ci] = block(args[ci], ax)
                vals[pos] = fn(*args, **kwargs)
                if ins[1] is not None:
                    pend[pos] = ins[1]
        emit_all()
        return tuple(vals[i] for i in out_idx)

    return body


def _flush_inline(order, has_reduce: bool = False,
                  has_contract: bool = False,
                  has_resplit: bool = False,
                  is_fallback: bool = False) -> None:
    """Evaluate a short chain op-by-op (children first — ``order`` is
    post-order): each dispatch reuses XLA's per-op executable cache, which
    every other chain in the process shares. Values land on every node, so
    later chains referencing them see leaves. Reduce and mask nodes carry
    global semantics, so the eager dispatch (GSPMD collective placement)
    is exactly the pre-recording behavior; a resplit node dispatches the
    eager PLANNER program (:func:`heat_tpu.core.resharding.reshard` —
    plan-cache counters tick, like pre-recording)."""
    for node in order:
        args = [h.value if isinstance(h, _Node) else h.array
                for h in node.args]
        if node.kind == "resplit":
            from . import resharding

            node.value = resharding.reshard(
                args[0], node.kwargs["gshape"], node.smeta["from"],
                node.smeta["to"], node.comm)
        else:
            node.value = node.fn(*args, **node.kwargs)
        owner = node.owner() if node.owner is not None else None
        if owner is not None:
            owner._set_materialized(node.value)
            if node.kind == "resplit":
                owner._pad_zero_buf = node.value  # planner zero-pads
    m = _metrics()
    m.inc("op_engine.fusion_flushes")
    m.inc("op_engine.fusion_ops", len(order))
    if not is_fallback:
        # error-path fallbacks are counted in fusion_flush_fallbacks;
        # inline_flushes keeps its documented meaning (short chains)
        m.inc("op_engine.fusion_inline_flushes")
    if has_reduce:
        m.inc("op_engine.fusion_reduce_flushes")
    if has_contract:
        m.inc("op_engine.fusion_contract_flushes")
    if has_resplit:
        m.inc("op_engine.fusion_resplit_flushes")
    for node in order:
        node.args = ()
        node.kwargs = {}


# ---------------------------------------------------------------------- #
# differentiable tapes: grads + whole-train-step tracing                 #
# ---------------------------------------------------------------------- #
class _Untraceable(Exception):
    """A step argument/structure trace_step cannot key or trace."""


def _isdnd(x) -> bool:
    from .dndarray import DNDarray

    return isinstance(x, DNDarray)


def _is_arr(x) -> bool:
    return isinstance(x, (jnp.ndarray, np.ndarray, np.generic, float,
                          complex))


def packed_psum(values, axes, qinfo: Optional[dict] = None,
                quant: Optional[Tuple] = None,
                chunks: Optional[Tuple] = None,
                hier: Optional[Tuple] = None,
                replicated: Tuple = ()):
    """ONE flattened all-reduce per dtype over mesh ``axes`` for a list of
    mutually independent shard-local partials — the train-step form of the
    flush body's phase-barrier packing (``_sm_body.emit_all``; the
    generalized-allreduce flattening of arXiv:2004.09362). Call inside a
    ``shard_map`` body; returns the combined values in order. ``axes``
    empty (all trivial mesh axes) returns the inputs untouched — no
    collective is emitted for a 1-device reduction scope. Flatten-concat-
    psum is bitwise-equal to per-value solo psums (probed in PR 4: XLA
    neither tuple-fuses grouped psums itself nor re-associates the
    concatenated reduce), so packing never moves the numerics.

    Under ``HEAT_TPU_QUANT_COLLECTIVES`` the qualifying float payloads
    (additive, at/above the size floor) ride the quantized exchange
    instead — sub-floor values (e.g. the packed scalar loss), integer
    payloads and every value under a fault-injected encode keep the exact
    flattened psum. ``qinfo`` (a dict the caller resets at body start)
    accumulates ``collectives``/``bytes_saved`` at trace time so step
    wrappers can tick the ``op_engine.quant_*`` counters per dispatch.
    ``quant`` pins the configuration to a :func:`quant_key` tuple captured
    when the caller BUILT (and cache-keyed) its program — jax traces
    lazily at first dispatch, and a codec toggle in between must not
    produce a program whose wire format contradicts its cache key; when
    None (direct in-body use) the live configuration is read at trace
    time. ``chunks`` pins the :func:`chunk_key` tuple the same way: under
    ``HEAT_TPU_FUSION_CHUNKS=N`` every payload group at/above the chunk
    floor splits into up to N double-buffered pipeline chunk collectives
    (per-codec block-aligned boundaries — bitwise the unchunked packing);
    the ``fusion.chunk.dispatch`` fault site degrades the call to the
    unchunked emission, counted in ``op_engine.chunk_fallbacks``.

    Under ``HEAT_TPU_HIER`` with declared tiers, every psum payload
    group whose reduction scope splits into a slow (DCN) and a fast
    (ICI) tier — the scope contains the slow-named axis plus fast axes,
    or is one flat axis with the declared ``(d, i)`` factorization —
    rides the HIERARCHICAL exchange instead
    (:func:`_hier_allreduce_parts`): reduce-scatter over the fast tier,
    all-reduce of the 1/pf shard over the slow tier with the DCN codec
    (the quant codec above; chunk bounds pipeline this leg), all-gather
    back with the ICI codec on the fast legs. ``hier`` pins the
    :func:`hier_key` tuple the way ``quant``/``chunks`` do;
    ``replicated`` names fast axes the values are already replicated
    over (DASO's slow-tier capture) — the reduce-scatter then
    degenerates to each device's zero-collective slice of its own tile,
    so only 1/pf of the payload ever crosses the slow tier per device.
    The ``fusion.hier.exchange`` fault site degrades the call to the
    flat emission, counted in ``op_engine.hier_fallbacks``."""
    values = list(values)
    if not axes:
        return values
    axes = tuple(axes)
    groups: Dict[Any, list] = {}
    for i, v in enumerate(values):
        groups.setdefault(jnp.dtype(v.dtype), []).append(i)
    out = list(values)
    codec, floor, block = quant if quant is not None else quant_key()
    cn, cfloor = chunks if chunks is not None else chunk_key()
    hk = hier if hier is not None else hier_key()
    sizes, group_size = (), 1
    quant_ok = codec is not None
    if quant_ok or cn > 1 or hk[0]:
        # lax.psum of a python int is STATIC (the axis-size idiom):
        # sizes are concrete here, usable for the int8/pipeline chunking
        # and the tier split. Only computed when a codec, chunking or
        # the hierarchy is armed — the exact flat path is untouched
        sizes = tuple(jax.lax.psum(1, a) for a in axes)
        for s in sizes:
            group_size *= s
        quant_ok = quant_ok and group_size > 1
    tc = None
    if hk[0] and group_size > 1:
        tc = _tier_scope(axes, sizes, hk, replicated)
    if quant_ok:
        try:
            _faults().check("fusion.quant.encode")
        except Exception:
            _metrics().inc("op_engine.quant_fallbacks")
            quant_ok = False
    chunk_state = {"ok": cn > 1 and group_size > 1, "checked": False}
    hier_state = {"ok": tc is not None, "checked": False}

    def hier_gate():
        """Arm the ``fusion.hier.exchange`` site on the FIRST payload
        group that would actually decompose (matching
        ``_hier_flush_plan``): a call with no qualifying group neither
        fires the site nor ticks the fallback counter. A raise degrades
        the WHOLE call to the flat packed emission."""
        if not hier_state["ok"]:
            return None
        if not hier_state["checked"]:
            hier_state["checked"] = True
            try:
                _faults().check("fusion.hier.exchange")
            except Exception:
                _metrics().inc("op_engine.hier_fallbacks")
                hier_state["ok"] = False
                return None
        return tc

    def chunk_gate(bounds):
        """Arm the ``fusion.chunk.dispatch`` site on the FIRST payload
        group that actually qualifies (once per intended chunk leg,
        matching ``_chunk_flush_plan``): a call whose payloads all stay
        unchunked never fires the site nor ticks the fallback counter.
        A raise degrades the WHOLE call to the unchunked emission."""
        if bounds is None or not chunk_state["ok"]:
            return None
        if not chunk_state["checked"]:
            chunk_state["checked"] = True
            try:
                for _ in range(cn):
                    _faults().check("fusion.chunk.dispatch")
            except Exception:
                _metrics().inc("op_engine.chunk_fallbacks")
                chunk_state["ok"] = False
                return None
        return bounds

    for _dt, members in groups.items():
        dt = jnp.dtype(_dt)
        tcg = None
        if _hier_dtype_ok(dt) and sum(
                _numel(values[i].shape) for i in members) >= hk[3]:
            tcg = hier_gate()
        if tcg is not None:
            # hierarchical decomposition for this payload group (total
            # at/above the hier floor): the SHARED subgroup split —
            # codec-qualifying members carry the DCN codec on the slow
            # leg (and the ICI codec on the fast legs), floor-qualifying
            # f32 members ride bf16 fast legs when only the ICI codec is
            # armed, the rest go exact; PR 10 chunk bounds pipeline the
            # DCN leg of each subgroup
            qset = set()
            if quant_ok and _quant_dtype_ok(dt, codec):
                qset = {i for i in members
                        if _numel(values[i].shape) >= floor}
            nhier = 0
            for sub, sub_codec, sub_ici in _hier_subgroups(
                    members, qset,
                    lambda i: _numel(values[i].shape), dt,
                    codec if qset else None, hk[2], floor):
                if not sub:
                    continue
                numels = [_numel(values[i].shape) for i in sub]
                bounds = None
                if chunk_state["ok"] and sum(numels) >= cfloor:
                    bounds = chunk_gate(_hier_leg_bounds(
                        numels, sub_codec, block, tcg.pf, tcg.ps, cn))
                for i, v in zip(sub, _hier_allreduce_parts(
                        [values[i] for i in sub], tcg, sub_codec, block,
                        sub_ici, bounds=bounds)):
                    out[i] = v
                nhier += 1
                if qinfo is not None:
                    if sub_codec is not None:
                        # only DCN-codec rewrites tick the quant
                        # counters: ici-bf16-only savings belong to the
                        # hier feature, not the quant one (stats
                        # attribution — a dashboard reading
                        # quant_collectives with quant_codec None would
                        # otherwise see phantom rewrites)
                        e, q = _hier_wire_bytes(
                            numels, dt.itemsize, sub_codec, sub_ici,
                            tcg.pf, tcg.ps, block)
                        qinfo["collectives"] = \
                            qinfo.get("collectives", 0) + 1
                        qinfo["bytes_saved"] = (qinfo.get("bytes_saved", 0)
                                                + max(0, e - q))
                    if bounds is not None:
                        qinfo["chunk_collectives"] = \
                            qinfo.get("chunk_collectives", 0) + 1
            if qinfo is not None and nhier:
                qinfo["hier_collectives"] = \
                    qinfo.get("hier_collectives", 0) + 1
            continue
        qm = []
        if quant_ok and _quant_dtype_ok(dt, codec):
            qm = [i for i in members
                  if _numel(values[i].shape) >= floor]
        if qm:
            numels = [_numel(values[i].shape) for i in qm]
            bounds = None
            if chunk_state["ok"] and sum(numels) >= cfloor:
                bounds = chunk_gate(_quant_chunk_bounds(
                    numels, sizes, codec, block, cn))
            for i, v in zip(qm, _quant_allreduce_parts(
                    [values[i] for i in qm], axes, sizes, codec, block,
                    bounds=bounds)):
                out[i] = v
            if qinfo is not None:
                e, q = _quant_wire_bytes(numels, dt.itemsize,
                                         codec, sizes, block)
                qinfo["collectives"] = qinfo.get("collectives", 0) + 1
                qinfo["bytes_saved"] = (qinfo.get("bytes_saved", 0)
                                        + max(0, e - q))
                if bounds is not None:
                    qinfo["chunk_collectives"] = \
                        qinfo.get("chunk_collectives", 0) + 1
            qset = set(qm)
            members = [i for i in members if i not in qset]
            if not members:
                continue
        total = sum(_numel(values[i].shape) for i in members)
        bounds = (chunk_gate(_chunk_bounds(total, cn, group_size))
                  if chunk_state["ok"] and total >= cfloor else None)
        if bounds is None and len(members) == 1:
            i = members[0]
            out[i] = jax.lax.psum(values[i], axes)
            continue
        packed = jnp.concatenate([values[i].reshape(-1) for i in members])
        combined = (jax.lax.psum(packed, axes) if bounds is None
                    else _chunked_exact(packed, axes, jax.lax.psum,
                                        bounds))
        if bounds is not None and qinfo is not None:
            qinfo["chunk_collectives"] = \
                qinfo.get("chunk_collectives", 0) + 1
        off = 0
        for i in members:
            n = 1
            for s in values[i].shape:
                n *= s
            out[i] = combined[off:off + n].reshape(values[i].shape)
            off += n
    return out


def _dnd_meta(x):
    """(rebuild metadata, signature entry) for one DNDarray leaf. The
    signature entry is hashable and pins everything program identity
    depends on; the metadata carries the live python objects (heat dtype,
    device, comm) the rebuild needs."""
    meta = ("dnd", x.gshape, x.dtype, x.split, x.device, x.comm)
    sig = ("dnd", tuple(x.gshape), str(jnp.dtype(x.dtype.jax_type())),
           x.split, x.comm.cache_key, str(x.device))
    return meta, sig


def _rebuild_dnd(meta, array):
    from .dndarray import DNDarray

    _, gshape, dtype, split, device, comm = meta
    return DNDarray(array, gshape, dtype, split, device, comm)


def value_and_grad(fun, argnums=0, has_aux=False):
    """``jax.value_and_grad`` over functions of ``DNDarray`` pytrees — the
    tape's grad-capable form.

    ``fun`` must return a scalar (0-d ``DNDarray`` or jax scalar; with
    ``has_aux`` a ``(scalar, aux)`` pair). The wrapper rebuilds the
    differentiated arguments' ``DNDarray`` leaves around jax's abstract
    leaves and traces ``fun`` through the op engine's EAGER semantics
    (recording declines on tracers by design, so the traced jaxpr is
    exactly the eager dispatch sequence); gradients come back as
    ``DNDarray`` leaves mirroring each parameter's layout. No loss
    cotangent ever flows into split-axis padding (every padding-crossing
    read is masked by the op engine's neutral-element discipline), so
    padded grad positions are don't-care — exact zeros for canonically
    zero-padded parameters (factories, planner outputs); grads are NOT
    certified ``pad_is_zero``, so consumers mask as usual.

    Called EAGERLY this traces per invocation (the torch-autograd cost
    shape); inside :func:`trace_step` the whole thing lowers into the one
    cached step executable — that composition is the supported hot path.
    The loss is returned as a 0-d ``DNDarray``; ``aux`` may contain
    ``DNDarray`` leaves (rebuilt on the way out).
    """
    multi = isinstance(argnums, (tuple, list))
    idxs = tuple(argnums) if multi else (int(argnums),)

    def wrapped(*args, **kwargs):
        from . import types
        from .communication import sanitize_comm
        from .dndarray import DNDarray

        per_arg = [jax.tree_util.tree_flatten(args[i], is_leaf=_isdnd)
                   for i in idxs]
        metas, phys, spans = [], [], []
        for leaves, _td in per_arg:
            start = len(phys)
            for leaf in leaves:
                if _isdnd(leaf):
                    m, _s = _dnd_meta(leaf)
                    metas.append(m)
                    phys.append(leaf.larray)
                else:
                    metas.append(("raw",))
                    phys.append(jnp.asarray(leaf))
            spans.append((start, len(phys)))
        aux_meta = []

        def pure(*leaf_arrays):
            rebuilt = [_rebuild_dnd(m, a) if m[0] == "dnd" else a
                       for m, a in zip(metas, leaf_arrays)]
            args2 = list(args)
            for j, i in enumerate(idxs):
                lo, hi = spans[j]
                args2[i] = jax.tree_util.tree_unflatten(
                    per_arg[j][1], rebuilt[lo:hi])
            out = fun(*args2, **kwargs)
            if has_aux:
                out, aux = out
                aflat, atree = jax.tree_util.tree_flatten(aux,
                                                          is_leaf=_isdnd)
                del aux_meta[:]
                aux_meta.append(atree)
                aux_arrs = []
                for a in aflat:
                    if _isdnd(a):
                        aux_meta.append(_dnd_meta(a)[0])
                        aux_arrs.append(a.larray)
                    else:
                        aux_meta.append(("raw",))
                        aux_arrs.append(a)
            val = out.larray if _isdnd(out) else jnp.asarray(out)
            val = val.reshape(())
            return (val, tuple(aux_arrs)) if has_aux else val

        vg = jax.value_and_grad(pure, argnums=tuple(range(len(phys))),
                                has_aux=has_aux)
        if has_aux:
            (val, aux_arrs), gphys = vg(*phys)
        else:
            val, gphys = vg(*phys)
        gleaves = [_rebuild_dnd(m, g) if m[0] == "dnd" else g
                   for m, g in zip(metas, gphys)]
        grads = tuple(
            jax.tree_util.tree_unflatten(per_arg[j][1],
                                         gleaves[spans[j][0]:spans[j][1]])
            for j in range(len(idxs)))
        if not multi:
            grads = grads[0]
        first_dnd = next((m for m in metas if m[0] == "dnd"), None)
        comm = first_dnd[5] if first_dnd is not None else sanitize_comm(None)
        device = first_dnd[4] if first_dnd is not None else None
        from .devices import sanitize_device

        vout = DNDarray(val, (), types.canonical_heat_type(val.dtype),
                        None, sanitize_device(device), comm)
        if has_aux:
            atree, ams = aux_meta[0], aux_meta[1:]
            aleaves = [_rebuild_dnd(m, a) if m[0] == "dnd" else a
                       for m, a in zip(ams, aux_arrs)]
            return (vout, jax.tree_util.tree_unflatten(atree, aleaves)), \
                grads
        return vout, grads

    return wrapped


def grad(fun, argnums=0, has_aux=False):
    """:func:`value_and_grad` without the value."""
    vg = value_and_grad(fun, argnums=argnums, has_aux=has_aux)

    def wrapped(*args, **kwargs):
        out, grads = vg(*args, **kwargs)
        return (grads, out[1]) if has_aux else grads

    return wrapped


class _StepRecord:
    """One compiled traced step: the jitted pure function plus the output
    rebuild metadata captured during its first trace. ``delete_slots``
    (async siblings only) are the dynamic-argument slots whose buffers
    the wrapper invalidates by hand after each dispatch — the
    donation-semantics half of the ``block=False`` contract."""

    __slots__ = ("jitted", "out_meta", "delete_slots")

    def __init__(self, jitted, delete_slots=()):
        self.jitted = jitted
        self.out_meta = None
        self.delete_slots = tuple(delete_slots)


# outstanding async trace_step results, for the no-argument sync():
# device execution is FIFO per dispatch order, so a bounded recent window
# is enough — blocking the newest results implies the older ones
# finished. The window is deliberately SMALL: each entry pins its step's
# output buffers (a full parameter tree for a train step) until sync()
# or eviction, and 8 steps of lookback already covers every in-flight
# execution a double-buffered device queue can hold
_ASYNC_LOCK = threading.Lock()
_ASYNC_PENDING: list = []
_ASYNC_PENDING_CAP = 8


def _note_async(results) -> None:
    with _ASYNC_LOCK:
        _ASYNC_PENDING.append(tuple(results))
        if len(_ASYNC_PENDING) > _ASYNC_PENDING_CAP:
            del _ASYNC_PENDING[:-_ASYNC_PENDING_CAP]


def sync(*trees) -> None:
    """The explicit host barrier of the async-dispatch path. With
    arguments, block until every ``DNDarray`` / jax-array leaf of the
    given pytrees is computed; with none, block on all outstanding
    ``block=False`` :func:`trace_step` results (then forget them). Call
    it before reading wall-clock time, checkpointing to host, or exiting
    a training loop that queued steps asynchronously."""
    if trees:
        for t in trees:
            for leaf in jax.tree_util.tree_leaves(t, is_leaf=_isdnd):
                if _isdnd(leaf):
                    jax.block_until_ready(leaf.larray)
                elif isinstance(leaf, jnp.ndarray):
                    jax.block_until_ready(leaf)
        return
    with _ASYNC_LOCK:
        pending = list(_ASYNC_PENDING)
        del _ASYNC_PENDING[:]
    for res in pending:
        for a in res:
            if not getattr(a, "is_deleted", lambda: False)():
                jax.block_until_ready(a)


class _TracedStep:
    """The callable :func:`trace_step` returns. Caches one compiled
    program per structural signature of the arguments in the fusion
    :func:`program_cache` (steady-state repeat calls are a key lookup and
    one donated program dispatch — zero host round-trips)."""

    def __init__(self, fn, donate_argnums=(), block=True):
        self.fn = fn
        self.donate_argnums = tuple(sorted(set(int(i)
                                               for i in donate_argnums)))
        # block=False: the async-dispatch sibling. XLA donation of an
        # in-flight buffer BLOCKS the dispatching thread until the
        # producer completes (probed on this jax — chained donated
        # dispatches serialize the host), so the async program compiles
        # WITHOUT donate_argnums and the wrapper delete()s the donated
        # input buffers after dispatch instead: invalidation semantics
        # preserved, dispatch queue asynchronous. fusion.sync() is the
        # explicit barrier.
        self.block = bool(block)
        # signatures whose first call failed to trace/compile: those
        # stay eager. PER-SIGNATURE, not per-fn — one oversized batch
        # failing to compile must not un-fuse the signatures already
        # running fused (each new signature pays at most one failed
        # trace before settling eager)
        self._eager_keys = set()

    def __call__(self, *args, **kwargs):
        if not (_ENABLED and _STEP):
            return self.fn(*args, **kwargs)
        with _prof.span("traced_step") as sp:
            return self._call(sp, args, kwargs)

    def _call(self, sp, args, kwargs):
        try:
            flat, treedef = jax.tree_util.tree_flatten((args, kwargs),
                                                       is_leaf=_isdnd)
            metas, sig, phys = self._classify(flat)
        except _Untraceable:
            _metrics().inc("op_engine.fusion_step_fallbacks")
            return self.fn(*args, **kwargs)
        # quant/chunk/hier keys ride along: a step body may call
        # packed_psum directly (trace-time config read), and a config
        # toggle must compile a SIBLING instead of reusing a program
        # traced under the other wire format / leg structure — the same
        # discipline as the flush key's qtag/ctag/htag
        key = ("step", self.fn, treedef, tuple(sig), self.donate_argnums,
               self.block, quant_key(), chunk_key(), hier_key())
        if key in self._eager_keys:
            _metrics().inc("op_engine.fusion_step_fallbacks")
            return self.fn(*args, **kwargs)
        record = program_cache().get_custom(
            key, lambda: self._build(args, treedef, metas))
        primed = record.out_meta is not None  # this program ran before
        sp.set(hit=primed)
        # a donated tree reused after its step is refused HERE, before
        # dispatch, for primed and first-call programs alike
        refuse_deleted(phys, "trace_step")
        try:
            _faults().check("fusion.step.dispatch" if primed
                            else "fusion.step.trace")
            # the first call of a signature traces and compiles inside the
            # jitted call: that one is `prime`, every later one `dispatch`
            with _prof.span("traced_step.dispatch" if primed
                            else "traced_step.prime"):
                results = record.jitted(*phys)
        except Exception:
            if primed:
                # a previously-successful program failed at DISPATCH
                # (donated tree reused, device error): that is a real
                # runtime error — surface it, don't silently degrade
                # every later step to the eager path
                raise
            # first-call trace/compile failure: the body is not
            # traceable at this signature. It may have half-run with
            # tracers — step bodies must be functional (the standard jax
            # contract) — so the eager re-run below is exact; this
            # signature stays eager
            self._eager_keys.add(key)
            _metrics().inc("op_engine.fusion_step_fallbacks")
            return self.fn(*args, **kwargs)
        if not self.block:
            # the async sibling's manual donation: invalidate the donated
            # input buffers now that the (non-donating) dispatch holds its
            # own references — use-after raises exactly like XLA donation.
            # Passthrough outputs are fresh buffers on this backend
            # (probed), but an identity guard keeps a future aliasing
            # backend from deleting its own result
            out_ids = {id(r) for r in results}
            for slot in record.delete_slots:
                a = phys[slot]
                if id(a) not in out_ids and not a.is_deleted():
                    a.delete()
            _note_async(results)
        _metrics().inc("op_engine.fusion_step_flushes")
        # out_meta is always set by the time jitted() returns: compiling
        # needs the jaxpr, the jaxpr needs pure() to complete, and pure()
        # writes the metadata before returning — in every thread
        ometa, otree = record.out_meta
        it = iter(results)
        oleaves = []
        for m in ometa:
            if m[0] == "static":
                oleaves.append(m[1])
            elif m[0] == "dnd":
                oleaves.append(_rebuild_dnd(m, next(it)))
            else:
                oleaves.append(next(it))
        return jax.tree_util.tree_unflatten(otree, oleaves)

    # -------------------------------------------------------------- #
    def _classify(self, flat):
        """Per-leaf (rebuild meta, hashable signature entry, program
        argument). DNDarray leaves flush any pending tape here (the step
        boundary) and enter as their physical arrays; raw arrays and
        python floats enter as (weak-typed) arguments so one program
        serves every value; ints/bools/strings are STATIC — they key the
        program (shape-like and control-flow-like roles)."""
        metas, sig, phys = [], [], []
        for leaf in flat:
            if _isdnd(leaf):
                m, s = _dnd_meta(leaf)
                metas.append(m)
                sig.append(s)
                phys.append(leaf.larray)
            elif isinstance(leaf, jax.core.Tracer):
                raise _Untraceable("tracer argument")  # nested-trace call
            elif _is_arr(leaf):
                a = jnp.asarray(leaf)
                metas.append(("raw",))
                sig.append(("arr", tuple(a.shape), str(a.dtype),
                            bool(a.aval.weak_type)))
                phys.append(a)
            else:
                k = _key_val(leaf)
                if k is None:
                    raise _Untraceable("unhashable static argument")
                metas.append(("static", leaf))
                sig.append(("static", k))
        return metas, tuple(sig), phys

    def _build(self, args, treedef, metas):
        record = [None]  # box: pure() runs inside the jit trace

        def pure(*leaf_arrays):
            it = iter(leaf_arrays)
            rebuilt = []
            for m in metas:
                if m[0] == "static":
                    rebuilt.append(m[1])
                elif m[0] == "dnd":
                    rebuilt.append(_rebuild_dnd(m, next(it)))
                else:
                    rebuilt.append(next(it))
            args2, kwargs2 = jax.tree_util.tree_unflatten(treedef, rebuilt)
            out = self.fn(*args2, **kwargs2)
            oflat, otree = jax.tree_util.tree_flatten(out, is_leaf=_isdnd)
            ometa, oarrs = [], []
            for o in oflat:
                if _isdnd(o):
                    ometa.append(_dnd_meta(o)[0])
                    oarrs.append(o.larray)
                elif isinstance(o, (jnp.ndarray, np.ndarray, np.generic,
                                    jax.core.Tracer)):
                    ometa.append(("raw",))
                    oarrs.append(jnp.asarray(o))
                else:
                    # host-static output (int epoch counters, flags):
                    # baked into the record; data-dependent host values
                    # cannot reach here (float(tracer) raises upstream)
                    ometa.append(("static", o))
            record[0].out_meta = (tuple(ometa), otree)
            return tuple(oarrs)

        donate = self._donate_slots(args, metas)
        _prof.named(pure, "traced_step")
        if self.block:
            record[0] = _StepRecord(jax.jit(pure, donate_argnums=donate))
        else:
            # async sibling: no XLA donation (donating an in-flight
            # buffer blocks the dispatching thread on this jax) — the
            # wrapper invalidates these slots by hand after dispatch
            record[0] = _StepRecord(jax.jit(pure), delete_slots=donate)
        return record[0]

    def _donate_slots(self, args, metas):
        """Flat dynamic-argument slots of the donated step arguments.
        Donated ``DNDarray`` buffers are INVALIDATED by the call — the
        functional-update idiom (``params, ... = step(params, ...)``)
        rebinds them anyway, and XLA reuses the memory in place."""
        if not self.donate_argnums:
            return ()
        spans, pos = [], 0
        for a in args:
            n = len(jax.tree_util.tree_flatten(a, is_leaf=_isdnd)[0])
            spans.append((pos, pos + n))
            pos += n
        wanted = set()
        for i in self.donate_argnums:
            if i < len(spans):
                wanted.update(range(*spans[i]))
        out, dyn = [], 0
        for slot, m in enumerate(metas):
            if m[0] == "static":
                continue
            if slot in wanted:
                out.append(dyn)
            dyn += 1
        return tuple(out)


def trace_step(fn, donate_argnums=(), block=True):
    """Compile a whole (functional) train step over ``DNDarray`` / jax
    pytrees as ONE cached executable — loss, backward and optimizer
    update in a single program with donated state.

    ``block=False`` selects ASYNC dispatch: repeat calls return
    device-resident results without a host sync, so back-to-back train
    steps queue on the device and the host never sits between steps (XLA
    donation of an in-flight buffer blocks the dispatching thread on
    this jax — the async sibling program skips XLA donation and
    invalidates the donated input buffers by hand instead, preserving
    the use-after-donation contract). Read results through
    :func:`sync` (or any materialization) when you actually need the
    values; queued steps are bitwise the synchronous ones.

    ``fn`` must be functional: pytrees in, pytrees out, no host-side
    value inspection (``float()``, ``.numpy()``, value-dependent
    branches). The first call per argument signature traces ``fn`` on
    abstract leaves — recorded ops decline tracers, so the body runs the
    op engine's eager semantics symbolically — and compiles the jaxpr
    once; repeat calls are a cache hit plus one program dispatch with
    zero host round-trips (``op_engine.fusion_step_flushes`` counts
    them). Non-traceable bodies fall back to the eager path — per
    argument signature, so one failing signature never un-fuses the
    others (``op_engine.fusion_step_fallbacks``; the semantics are
    identical, the fusion is lost). ``donate_argnums`` marks positional
    arguments
    (params, optimizer state) whose buffers XLA may update in place —
    their input ``DNDarray``\\ s are invalidated by the call.

    Escape hatch: ``HEAT_TPU_FUSION_STEP=0`` (or
    :func:`step_override`) runs every wrapped step eagerly.
    """
    return _TracedStep(fn, donate_argnums, block=block)


def refuse_deleted(args, who):
    """Raise before dispatch if any of ``args`` is an already-deleted
    (donated) buffer. The runtime would refuse it too, but on jax 0.9
    XLA:CPU a multi-device executable refused at dispatch leaves the
    process unable to complete the NEXT multi-device program — the one
    deadlock that used to cut tier-1 at its limit."""
    for a in args:
        if getattr(a, "is_deleted", lambda: False)():
            raise RuntimeError(
                f"{who}: an input buffer has been deleted — a donated "
                "argument was reused after the call that consumed it; "
                "rebind the outputs (p, l = step(p, ...))")


# ---------------------------------------------------------------------- #
# tape-compiled analytics fit steps                                      #
# ---------------------------------------------------------------------- #
def fit_step_call(key, build, args, eager):
    """Dispatch ONE compiled analytics fit/predict step through the
    fusion program cache — the estimator-family sibling of
    :func:`trace_step` (KMeans/KMedians/KMedoids Lloyd iterations, the
    Lanczos inner loop, Lasso coordinate sweeps, the KNN ring and
    GaussianNB likelihood programs ride this).

    ``key`` is the caller's structural signature (shapes, dtypes, the
    communicator cache key); the full program key appends the captured
    :func:`quant_key`/:func:`chunk_key`/:func:`hier_key` tuples, so a
    wire-codec toggle compiles a SIBLING program instead of reusing one
    traced under the other wire format (the PR 9 deferred-trace
    discipline). ``build(qk, ck, hk)`` returns the compiled callable and
    must PIN the captured tuples into any :func:`packed_psum` it traces.
    ``eager`` replays the same mathematics per-op (unjitted, GSPMD
    collectives) — the degrade path of the ``fit.step.dispatch`` fault
    site and of real compile/dispatch failures, counted in
    ``op_engine.fit_step_fallbacks``; a failure after a donated input
    buffer was already invalidated re-raises (replaying from dead
    buffers would be the PR 8 flush-fallback hazard). Successful
    dispatches count ``op_engine.fit_step_flushes``.

    With the engine off (``HEAT_TPU_FUSION_FIT=0`` or the master
    switch), callers run their legacy step programs and never reach
    here — see :func:`fit_enabled`.
    """
    with _prof.span("fit_step") as sp:
        qk, ck, hk = quant_key(), chunk_key(), hier_key()
        full_key = ("fit",) + tuple(key) + (qk, ck, hk)
        try:
            misses = program_cache().misses
            with _prof.span("fit_step.lookup"):
                prog = program_cache().get_custom(
                    full_key, lambda: build(qk, ck, hk))
            sp.set(hit=program_cache().misses == misses)
            _faults().check("fit.step.dispatch")
            refuse_deleted(args, "fit_step_call")
            with _prof.span("fit_step.dispatch"):
                out = prog(*args)
        except Exception:
            for a in args:
                if getattr(a, "is_deleted", lambda: False)():
                    raise  # donated buffer already invalidated — no replay
            _metrics().inc("op_engine.fit_step_fallbacks")
            with _prof.span("fit_step.fallback"):
                return eager(*args)
        _metrics().inc("op_engine.fit_step_flushes")
        return out


# ---------------------------------------------------------------------- #
# observability                                                          #
# ---------------------------------------------------------------------- #
def stats() -> dict:
    """Fusion engine snapshot (folded into ``ht.runtime_stats()``)."""
    c = _metrics().counters()
    flushes = int(c.get("op_engine.fusion_flushes", 0))
    ops = int(c.get("op_engine.fusion_ops", 0))
    return {
        "enabled": _ENABLED,
        "reduce_enabled": _REDUCE,
        "contract_enabled": _CONTRACT,
        "resplit_enabled": _RESPLIT,
        "step_enabled": _STEP,
        "step_flushes": int(c.get("op_engine.fusion_step_flushes", 0)),
        "step_fallbacks": int(c.get("op_engine.fusion_step_fallbacks", 0)),
        "fit_enabled": _FIT,
        "fit_step_flushes": int(c.get("op_engine.fit_step_flushes", 0)),
        "fit_step_fallbacks": int(
            c.get("op_engine.fit_step_fallbacks", 0)),
        "flushes": flushes,
        "flush_fallbacks": int(
            c.get("op_engine.fusion_flush_fallbacks", 0)),
        "inline_flushes": int(c.get("op_engine.fusion_inline_flushes", 0)),
        "reduce_flushes": int(c.get("op_engine.fusion_reduce_flushes", 0)),
        "contract_flushes": int(
            c.get("op_engine.fusion_contract_flushes", 0)),
        "resplit_flushes": int(
            c.get("op_engine.fusion_resplit_flushes", 0)),
        "resplit_nodes": int(c.get("op_engine.fusion_resplit_nodes", 0)),
        "resplit_fallbacks": int(
            c.get("op_engine.fusion_resplit_fallbacks", 0)),
        "fused_ops": ops,
        "ops_per_flush": round(ops / flushes, 3) if flushes else 0.0,
        "max_ops": _MAX_OPS,
        "min_ops": _MIN_OPS,
        "quant_codec": _QUANT,
        "quant_min_numel": _QUANT_FLOOR,
        "quant_collectives": int(c.get("op_engine.quant_collectives", 0)),
        "quant_bytes_saved": int(c.get("op_engine.quant_bytes_saved", 0)),
        "quant_fallbacks": int(c.get("op_engine.quant_fallbacks", 0)),
        "chunk_count": _CHUNKS,
        "chunk_min_numel": _CHUNK_FLOOR,
        "chunk_collectives": int(c.get("op_engine.chunk_collectives", 0)),
        "chunk_fallbacks": int(c.get("op_engine.chunk_fallbacks", 0)),
        "hier_enabled": _HIER,
        "mesh_tiers": list(_TIERS) if _TIERS is not None else None,
        "hier_ici_codec": _HIER_ICI,
        "hier_collectives": int(c.get("op_engine.hier_collectives", 0)),
        "hier_fallbacks": int(c.get("op_engine.hier_fallbacks", 0)),
        "program_cache": program_cache().stats(),
    }


def reset() -> None:
    """Drop cached programs, memoized avals and the captured HLO (tests)."""
    global _last_hlo
    program_cache().reset()
    _AVAL_CACHE.clear()
    _SCALAR_CACHE.clear()
    _last_hlo = None
