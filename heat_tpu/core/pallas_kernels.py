"""Pallas (Mosaic) TPU kernels for the hot ops.

The reference gets all local-compute performance from ATen's CUDA kernels
(SURVEY.md §2: ``_operations.py:172``, ``spatial/distance.py:28``). The
TPU-native equivalents here are hand-tiled Pallas kernels for the two
GB/s-critical tiles the framework runs in its hot loops:

* :func:`cdist_tile` — one fused pairwise-L2 block: the norm terms, the
  ``-2·x·yᵀ`` GEMM on the MXU, the clamp and the sqrt all execute inside a
  single VMEM-resident tile, so the ``(bm, bn)`` distance block is produced
  in one pass with no HBM round-trip for intermediates, and the kernel's
  output array has the result's own ``(m, n)`` shape: the last row and
  column of tiles are edge blocks whose out-of-range part Mosaic does not
  store, so the result is written once and nothing slices it afterwards.
  This is the tile under the ``ppermute`` ring of
  :mod:`heat_tpu.spatial.distance` (the reference's systolic loop,
  ``distance.py:280-362``).
* :func:`flash_attention` — blockwise attention with online-softmax
  statistics (flash style). Returns the normalized block output together
  with the log-sum-exp per query row, which is exactly the merge state ring
  attention needs: per ring step each device runs this kernel on its
  resident K/V block and folds the result with the running ``(out, lse)``
  pair. The backward is blockwise too (``_flash_bwd_impl``: dK/dV and dQ
  grid kernels recomputing probabilities from the saved lse) — O(S·D)
  memory instead of the dense fallback's O(Sq·Sk), so long-context
  *training* fits in HBM, not just inference.

On non-TPU backends every wrapper falls back to the interpreter
(``interpret=True``), so the CPU test mesh exercises the same kernel code
path; the jnp reference implementations remain available for equivalence
checks. Enablement: by default the cdist/attention kernels are used iff the
active backend is TPU; override with :func:`set_pallas` or
``HEAT_TPU_PALLAS=0/1``. The fused KMeans kernel is the exception — it is
OPT-IN only (:func:`kmeans_pallas_enabled`): its defaults (``loop`` sums,
128-row tiles) are the ones the v5e compiler accepts, and whether it beats
the XLA Lloyd step has not been measured.
"""

from __future__ import annotations

import functools
import math
import os
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = [
    "pallas_enabled",
    "kmeans_pallas_enabled",
    "set_pallas",
    "cdist_tile",
    "flash_attention",
    "kmeans_step_tile",
]

_NEG_BIG = -1e30  # finite stand-in for -inf so exp() of masked rows is safe

# KMeans-kernel GEMM precision. DEFAULT (1-pass bf16 on the MXU) matches the
# XLA Lloyd path, which calls `xp @ centroids.T` without a precision override;
# HIGHEST would emulate f32 in multiple passes and dominates the kernel cost.
_MM_PRECISION = jax.lax.Precision.DEFAULT

_override: Optional[bool] = None


def set_pallas(enabled: Optional[bool]) -> None:
    """Force Pallas kernels on/off; ``None`` restores backend autodetection."""
    global _override
    _override = enabled


def pallas_enabled() -> bool:
    """True when the hot ops should route through the Pallas kernels."""
    if _override is not None:
        return _override
    env = os.environ.get("HEAT_TPU_PALLAS")
    if env in ("0", "false", "False"):
        return False
    if env in ("1", "true", "True"):
        return True
    # on the TPU a kernel the compiler refuses RAISES: nothing probes for
    # it and nothing falls back to the XLA path behind the caller's back
    return jax.default_backend() == "tpu"


def kmeans_pallas_enabled() -> bool:
    """The fused KMeans kernel is OPT-IN (explicit ``set_pallas(True)`` or
    ``HEAT_TPU_PALLAS=1``) rather than backend-autoselected: only its
    ``loop``/128-row form compiles for the v5e (larger tiles exceed the
    scoped-VMEM budget) and it has never been timed against the XLA step.
    The cdist/attention kernels keep the backend-default behavior."""
    if _override is not None:
        return _override
    return os.environ.get("HEAT_TPU_PALLAS") in ("1", "true", "True")


def _interpret() -> bool:
    # off-TPU the Mosaic compiler is unavailable; run the kernels interpreted
    return jax.default_backend() != "tpu"


def interpret_vma_hazard(*ts) -> bool:
    """True when the kernels would run INTERPRETED (off-TPU) on operands
    carrying a nonempty varying-across-mesh-axes type: the Pallas HLO
    interpreter's dynamic_slice rejects mixed-vma operands inside a
    ``check_vma=True`` shard_map (the flagship transformer's train step), so
    call sites with a jnp fallback should take it. Real Mosaic lowering on
    TPU is unaffected — this never fires there."""
    return _interpret() and bool(_vma(*ts))


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def _i32(v):
    # index maps must return int32: with jax_enable_x64 (which the package
    # turns on) they otherwise trace to int64 and Mosaic fails to legalize
    # the kernel ('func.return' lowering error)
    return jnp.asarray(v, jnp.int32)


def _pad_axis(x, axis: int, target: int):
    pad = target - x.shape[axis]
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


# --------------------------------------------------------------------------- #
# cdist tile                                                                  #
# --------------------------------------------------------------------------- #


def _cdist_kernel(x_ref, y_ref, o_ref, *, sqrt: bool, acc_dtype):
    x = x_ref[...].astype(acc_dtype)
    y = y_ref[...].astype(acc_dtype)
    x2 = jnp.sum(x * x, axis=1, keepdims=True)  # (bm, 1)
    y2 = jnp.sum(y * y, axis=1)[None, :]  # (1, bn)
    xy = jax.lax.dot_general(
        x, y, dimension_numbers=(((1,), (1,)), ((), ())), preferred_element_type=acc_dtype,
        precision=jax.lax.Precision.HIGHEST,  # Mosaic rejects HIGH; DEFAULT is 1-pass bf16
    )
    d2 = jnp.maximum(x2 + y2 - 2.0 * xy, 0.0)
    o_ref[...] = (jnp.sqrt(d2) if sqrt else d2).astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("sqrt", "block_m", "block_n", "out_dtype"))
def cdist_tile(x, y, sqrt: bool = True, block_m: int = 256,
               block_n: int = 256, out_dtype=None):
    """Fused pairwise L2 distance block ``(m, d) × (n, d) → (m, n)``.

    One Pallas grid pass: each ``(block_m, block_n)`` output tile computes
    its norm terms and MXU GEMM entirely in VMEM. ``sqrt=False`` returns
    squared distances (the KMeans assignment form). ``out_dtype`` overrides
    the output dtype (the kernel accumulates in f32/f64 regardless — rbf
    passes f32 here so the exp sees unrounded distances).

    The result is written ONCE, at its own shape: ``out_shape`` is
    ``(m, n)`` and the grid ``cdiv(m, bm) × cdiv(n, bn)``, so the last row
    and the last column of tiles are edge blocks. An edge tile computes all
    of its ``(bm, bn)`` entries and Mosaic stores only the part that lies
    inside ``(m, n)``; nothing slices or copies the result afterwards. The
    INPUTS stay padded (rows to the tile, features to 128 lanes, with
    zeros), so an edge tile reads zeros and never unspecified memory; and
    entry ``(i, j)`` reads row ``i`` of ``x`` and row ``j`` of ``y`` only,
    so what an out-of-range row holds cannot reach an in-range entry."""
    m, d = x.shape
    n = y.shape[0]
    if out_dtype is None:
        # preserve the callers' (promoted) floating dtype — a bf16 input
        # must yield a bf16 distance block, not silently upcast to f32
        out_dtype = jnp.promote_types(x.dtype, y.dtype)
    out_dtype = jnp.dtype(out_dtype)
    if not jnp.issubdtype(out_dtype, jnp.floating):
        out_dtype = jnp.dtype(jnp.float32)
    acc_dtype = jnp.float64 if out_dtype == jnp.float64 else jnp.float32
    # Mosaic tiling: sublane block multiple of 8, lane block multiple of 128
    bm = min(_round_up(block_m, 8), _round_up(m, 8))
    bn = min(_round_up(block_n, 128), _round_up(n, 128))
    mp, np_, dp = _round_up(m, bm), _round_up(n, bn), _round_up(d, 128)
    xp = _pad_axis(_pad_axis(x, 0, mp), 1, dp)
    yp = _pad_axis(_pad_axis(y, 0, np_), 1, dp)

    return pl.pallas_call(
        functools.partial(_cdist_kernel, sqrt=sqrt, acc_dtype=acc_dtype),
        grid=(mp // bm, np_ // bn),
        in_specs=[
            pl.BlockSpec((bm, dp), lambda i, j: (_i32(i), _i32(0))),
            pl.BlockSpec((bn, dp), lambda i, j: (_i32(j), _i32(0))),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (_i32(i), _i32(j))),
        out_shape=_sds((m, n), out_dtype, vma=_vma(xp, yp)),
        name="cdist_tile",
        interpret=_interpret(),
    )(xp, yp)


# --------------------------------------------------------------------------- #
# flash attention                                                             #
# --------------------------------------------------------------------------- #


_NT = ((1,), (1,))  # a·bᵀ: contract both operands' minor dim
_NN = ((1,), (0,))  # a·b


def _all_bf16(*refs) -> bool:
    """The flash kernels hand the MXU bfloat16 exactly when every tensor
    operand arrives bfloat16; any other dtype (or a mix) keeps float32
    products at ``HIGHEST``. Fixed at trace time by what the caller passed."""
    return all(r.dtype == jnp.bfloat16 for r in refs)


def _gemm(a, b, contract, acc_dtype, mxu_bf16: bool):
    """``a·b`` over ``contract``, accumulated in ``acc_dtype``.

    Off the bfloat16 path: both operands in ``acc_dtype`` at ``HIGHEST``
    (six bfloat16 passes on the MXU for float32). On it ``b`` is an input
    tile holding bfloat16 VALUES, and a bf16×bf16 product is exact in
    float32, so an ``a`` that is an input tile too takes ONE pass and loses
    nothing; an ``a`` the kernel computed in float32 (``p``, ``ds``) goes as
    two bfloat16 terms ``hi + lo`` — 16 bits of mantissa, two passes."""
    dims = (contract, ((), ()))
    if not mxu_bf16:
        return jax.lax.dot_general(
            a.astype(acc_dtype), b.astype(acc_dtype), dimension_numbers=dims,
            preferred_element_type=acc_dtype,
            precision=jax.lax.Precision.HIGHEST)
    # DEFAULT said aloud: the package's global default is "high", which
    # Mosaic refuses and which bfloat16 operands have no use for
    one_pass = functools.partial(
        jax.lax.dot_general, dimension_numbers=dims,
        preferred_element_type=acc_dtype, precision=jax.lax.Precision.DEFAULT)
    if a.dtype == jnp.bfloat16:
        return one_pass(a, b)
    hi = a.astype(jnp.bfloat16)
    lo = (a - hi.astype(a.dtype)).astype(jnp.bfloat16)
    return one_pass(hi, b) + one_pass(lo, b)


def _scores(q, k, scale: float, acc_dtype, mxu_bf16: bool, transposed=False):
    """The score tile ``scale·q·kᵀ`` (``transposed``: ``scale·k·qᵀ``). On the
    bfloat16 path ``scale`` multiplies the float32 tile after the dot, so the
    operands stay the bfloat16 values they arrived as."""
    if mxu_bf16:
        a, b = (k, q) if transposed else (q, k)
        return _gemm(a, b, _NT, acc_dtype, True) * scale
    qs = q.astype(acc_dtype) * scale
    a, b = (k, qs) if transposed else (qs, k)
    return _gemm(a, b, _NT, acc_dtype, False)


def _block_mask(shape, row_axis: int, row0, col0, kv_valid: int,
                causal_offset: Optional[int]):
    """Live positions of one score block: keys before the padded tail and,
    causal, on or below the end-aligned diagonal."""
    row = jax.lax.broadcasted_iota(jnp.int32, shape, row_axis) + row0
    col = jax.lax.broadcasted_iota(jnp.int32, shape, 1 - row_axis) + col0
    mask = col < kv_valid
    if causal_offset is not None:
        mask = jnp.logical_and(mask, col <= row + causal_offset)
    return mask


def _run_blocks(step, qi, kb, block_q: int, block_k: int, kv_valid: int,
                causal_offset: Optional[int], bare_interior: bool = True):
    """Run ``step(masked)`` for grid cell (q-block ``qi``, k-block ``kb``):
    not at all for a block wholly above the diagonal, and ``masked=True``
    where the diagonal or the padded tail crosses it. A block every position
    of which is live runs ``step(False)`` (no iota, compare or select) when
    ``bare_interior``; the masks select nothing there either way."""
    live = True
    crossed = []  # where a block needs its masks
    if kv_valid % block_k:  # the last K block holds padded keys
        crossed.append((kb + 1) * block_k > kv_valid)
    if causal_offset is not None:
        # live: the block's first key is visible to its last query row;
        # crossed: its last key is NOT visible to its first query row
        live = kb * block_k <= (qi + 1) * block_q - 1 + causal_offset
        crossed.append((kb + 1) * block_k - 1 > qi * block_q + causal_offset)
    if not (crossed and bare_interior):  # one copy of the step
        masked = bool(crossed)
        if live is True:
            step(masked)
        else:
            pl.when(live)(lambda: step(masked))
        return
    crossed = functools.reduce(jnp.logical_or, crossed)
    pl.when(jnp.logical_and(live, crossed))(lambda: step(True))
    pl.when(jnp.logical_and(live, jnp.logical_not(crossed)))(lambda: step(False))


def _flash_blocks(block_q: Optional[int], block_k: Optional[int], Sq: int,
                  Sk: int, mxu_bf16: bool) -> Tuple[int, int]:
    """``(bq, bk)`` of a flash kernel's score block. ``bq`` is the lane dim
    of the dK/dV kernel's (8, bq) statistics block and ``bk`` the lane dim of
    the (bq, bk) score block, so a caller's sizes are rounded up to 128
    rather than trusted, and neither exceeds its padded sequence. With none
    given: 256, and 512 on the bfloat16 path, where a grid step's fixed cost
    and not the MXU sets the pace (PERF.md section 6, PR 30)."""
    default = 512 if mxu_bf16 else 256
    bq = min(_round_up(block_q or default, 128), _round_up(Sq, 128))
    bk = min(_round_up(block_k or default, 128), _round_up(Sk, 128))
    return bq, bk


def _flash_kernel(
    q_ref,
    k_ref,
    v_ref,
    o_ref,
    lse_ref,
    acc_ref,
    m_ref,
    l_ref,
    *,
    scale: float,
    block_q: int,
    block_k: int,
    kv_valid: int,
    causal_offset: Optional[int],
    acc_dtype,
):
    """One (q-block, k-block) grid cell of blockwise attention.

    The K/V grid axis is innermost, so the VMEM scratch accumulators persist
    across its sequential iterations; only one ``(block_k, d)`` K and V tile
    is VMEM-resident at a time — long key sequences never have to fit
    on-chip. ``causal_offset`` is ``Sk - Sq`` (end-aligned diagonal, matching
    the dense fallback) or ``None`` for full attention.
    """
    qi = pl.program_id(1)
    kb = pl.program_id(2)
    num_kb = pl.num_programs(2)

    @pl.when(kb == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_BIG)
        l_ref[...] = jnp.zeros_like(l_ref)

    mxu_bf16 = _all_bf16(q_ref, k_ref, v_ref)

    def step(masked: bool):
        s = _scores(q_ref[0], k_ref[0], scale, acc_dtype, mxu_bf16)  # (bq, block_k)
        if masked:
            mask = _block_mask(s.shape, 0, qi * block_q, kb * block_k,
                               kv_valid, causal_offset)
            s = jnp.where(mask, s, jnp.asarray(_NEG_BIG, s.dtype))
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        if masked:
            # mask p explicitly: on a fully-masked row m_new is still _NEG_BIG
            # and exp(s - m_new) would be 1 at masked positions, silently
            # yielding mean(V) instead of the dense path's NaN
            p = jnp.where(mask, p, jnp.zeros((), acc_dtype))
        acc_ref[...] = acc_ref[...] * alpha + _gemm(p, v_ref[0], _NN, acc_dtype, mxu_bf16)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        m_ref[...] = m_new

    _run_blocks(step, qi, kb, block_q, block_k, kv_valid, causal_offset)

    @pl.when(kb == num_kb - 1)
    def _finalize():
        # rows with no unmasked keys (l == 0) produce NaN output and -inf
        # lse, matching softmax-over-all--inf in the dense fallback
        l = l_ref[...]
        empty = l == 0
        l_safe = jnp.where(empty, jnp.ones((), l.dtype), l)
        o = acc_ref[...] / l_safe
        o = jnp.where(empty, jnp.asarray(jnp.nan, o.dtype), o)
        o_ref[0] = o.astype(o_ref.dtype)
        # lse block is (1, bq, 8): the 8-lane tail exists only to satisfy the
        # Mosaic block-shape constraint; callers read lane 0
        lse = jnp.where(empty, jnp.asarray(-jnp.inf, l.dtype), m_ref[...] + jnp.log(l_safe))
        lse = lse.astype(lse_ref.dtype)  # (bq, 1)
        lse_ref[0] = jnp.broadcast_to(lse, (lse.shape[0], 8))


def _vma(*ts):
    """Union of the operands' varying-across-mesh-axes type, so pallas_call
    outputs typecheck inside a ``check_vma=True`` shard_map (e.g. the
    flagship transformer's train step)."""
    out = frozenset()
    for t in ts:
        out = out | frozenset(getattr(jax.typeof(t), "vma", ()) or ())
    return out


def _sds(shape, dtype, vma=frozenset()):
    """``jax.ShapeDtypeStruct`` carrying the ``vma`` type annotation."""
    return jax.ShapeDtypeStruct(shape, dtype, vma=vma)


@functools.partial(
    jax.jit, static_argnames=("scale", "causal", "block_q", "block_k")
)
def _flash_impl(
    q,
    k,
    v,
    scale: float,
    causal: bool,
    block_q: Optional[int],
    block_k: Optional[int],
):
    """Raw blockwise (flash) attention forward; returns ``(out, lse)``."""
    B, H, Sq, D = q.shape
    Sk = k.shape[2]
    acc_dtype = jnp.float64 if jnp.promote_types(q.dtype, jnp.float32) == jnp.float64 else jnp.float32
    bq, bk = _flash_blocks(block_q, block_k, Sq, Sk, _all_bf16(q, k, v))
    sqp, skp, dp = _round_up(Sq, bq), _round_up(Sk, bk), _round_up(D, 128)

    qf = _pad_axis(_pad_axis(q.reshape(B * H, Sq, D), 1, sqp), 2, dp)
    kf = _pad_axis(_pad_axis(k.reshape(B * H, Sk, D), 1, skp), 2, dp)
    vf = _pad_axis(_pad_axis(v.reshape(B * H, Sk, D), 1, skp), 2, dp)

    from jax.experimental.pallas import tpu as pltpu

    out, lse = pl.pallas_call(
        functools.partial(
            _flash_kernel,
            scale=float(scale),
            block_q=bq,
            block_k=bk,
            kv_valid=Sk,
            causal_offset=(Sk - Sq) if causal else None,
            acc_dtype=acc_dtype,
        ),
        # K/V axis innermost: scratch accumulators persist across its
        # sequential steps; only one (bk, dp) K and V tile in VMEM at a time
        grid=(B * H, sqp // bq, skp // bk),
        in_specs=[
            pl.BlockSpec((1, bq, dp), lambda b, i, j: (_i32(b), _i32(i), _i32(0))),
            pl.BlockSpec((1, bk, dp), lambda b, i, j: (_i32(b), _i32(j), _i32(0))),
            pl.BlockSpec((1, bk, dp), lambda b, i, j: (_i32(b), _i32(j), _i32(0))),
        ],
        out_specs=[
            pl.BlockSpec((1, bq, dp), lambda b, i, j: (_i32(b), _i32(i), _i32(0))),
            pl.BlockSpec((1, bq, 8), lambda b, i, j: (_i32(b), _i32(i), _i32(0))),
        ],
        out_shape=[
            _sds((B * H, sqp, dp), q.dtype, vma=_vma(q, k, v)),
            _sds((B * H, sqp, 8), jnp.float32, vma=_vma(q, k, v)),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, dp), acc_dtype),
            pltpu.VMEM((bq, 1), acc_dtype),
            pltpu.VMEM((bq, 1), acc_dtype),
        ],
        name="flash_fwd",
        interpret=_interpret(),
    )(qf, kf, vf)

    out = out[:, :Sq, :D].reshape(B, H, Sq, D)
    return out, lse[:, :Sq, 0].reshape(B, H, Sq)


def _flash_bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dmb_ref,
                          dk_ref, dv_ref, acc_dk, acc_dv, *, scale: float,
                          block_q: int, block_k: int, kv_valid: int,
                          causal_offset: Optional[int], acc_dtype):
    """dK/dV for one K/V block, accumulated over the (innermost) Q-block
    axis. Everything is computed in the TRANSPOSED (bk, bq) orientation so
    every GEMM is a dim-1×dim-1 or dim-1×dim-0 contraction — no dim-0
    contractions for Mosaic to build transpose temporaries for (the KMeans
    kernel's scoped-VMEM failure mode).

    ``lse_ref``/``dmb_ref`` blocks are (1, 8, bq): the per-row statistics
    pre-transposed host-side into an 8-sublane layout (lane dim = bq, a
    128-multiple); the kernel reads sublane 0. ``dmb = dlse - delta`` is the
    combined additive score-cotangent term (delta = rowsum(dout·out); dlse
    is the lse cotangent ring attention feeds back)."""
    kb = pl.program_id(1)
    qi = pl.program_id(2)
    num_qb = pl.num_programs(2)

    @pl.when(qi == 0)
    def _init():
        acc_dk[...] = jnp.zeros_like(acc_dk)
        acc_dv[...] = jnp.zeros_like(acc_dv)

    mxu_bf16 = _all_bf16(q_ref, k_ref, v_ref, do_ref)

    def step(masked: bool):
        q, do = q_ref[0], do_ref[0]
        lse_row = lse_ref[0][:1, :]          # (1, bq)
        dmb_row = dmb_ref[0][:1, :]          # (1, bq)
        s_t = _scores(q, k_ref[0], scale, acc_dtype, mxu_bf16,
                      transposed=True)       # (bk, bq)
        # lse = +inf on padded query rows (p -> 0)
        p_t = jnp.exp(s_t - lse_row)
        if masked:
            # -inf on fully-masked real rows would blow exp() up, so gate on
            # finiteness like the dense path
            mask = _block_mask(s_t.shape, 1, qi * block_q, kb * block_k,
                               kv_valid, causal_offset)
            p_t = jnp.where(jnp.logical_and(mask, jnp.isfinite(lse_row)),
                            p_t, jnp.zeros((), acc_dtype))
        dp_t = _gemm(v_ref[0], do, _NT, acc_dtype, mxu_bf16)  # (bk, bq)
        ds_t = p_t * (dp_t + dmb_row)
        acc_dv[...] += _gemm(p_t, do, _NN, acc_dtype, mxu_bf16)
        acc_dk[...] += _gemm(ds_t, q, _NN, acc_dtype, mxu_bf16)

    # this kernel is the one the MXU still bounds (6 passes a block): the
    # second, mask-free copy of the step cost it 2% on the chip, so it masks
    # every live block
    _run_blocks(step, qi, kb, block_q, block_k, kv_valid, causal_offset,
                bare_interior=False)

    @pl.when(qi == num_qb - 1)
    def _flush():
        dk_ref[0] = (acc_dk[...] * scale).astype(dk_ref.dtype)
        dv_ref[0] = acc_dv[...].astype(dv_ref.dtype)


def _flash_bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dmb_ref,
                         dq_ref, acc_dq, *, scale: float, block_q: int,
                         block_k: int, kv_valid: int,
                         causal_offset: Optional[int], acc_dtype):
    """dQ for one Q block, accumulated over the (innermost) K-block axis.
    ``lse_ref``/``dmb_ref`` blocks are (1, bq, 8) (the forward's lse output
    layout); the kernel reads lane 0."""
    qi = pl.program_id(1)
    kb = pl.program_id(2)
    num_kb = pl.num_programs(2)

    @pl.when(kb == 0)
    def _init():
        acc_dq[...] = jnp.zeros_like(acc_dq)

    mxu_bf16 = _all_bf16(q_ref, k_ref, v_ref, do_ref)

    def step(masked: bool):
        k = k_ref[0]
        lse_col = lse_ref[0][:, :1]          # (bq, 1)
        dmb_col = dmb_ref[0][:, :1]          # (bq, 1)
        s = _scores(q_ref[0], k, scale, acc_dtype, mxu_bf16)  # (bq, bk)
        p = jnp.exp(s - lse_col)
        if masked:
            mask = _block_mask(s.shape, 0, qi * block_q, kb * block_k,
                               kv_valid, causal_offset)
            p = jnp.where(jnp.logical_and(mask, jnp.isfinite(lse_col)),
                          p, jnp.zeros((), acc_dtype))
        dp = _gemm(do_ref[0], v_ref[0], _NT, acc_dtype, mxu_bf16)  # (bq, bk)
        ds = p * (dp + dmb_col)
        acc_dq[...] += _gemm(ds, k, _NN, acc_dtype, mxu_bf16)

    _run_blocks(step, qi, kb, block_q, block_k, kv_valid, causal_offset)

    @pl.when(kb == num_kb - 1)
    def _flush():
        dq_ref[0] = (acc_dq[...] * scale).astype(dq_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("scale", "causal", "block_q", "block_k")
)
def _flash_bwd_impl(q, k, v, out, lse, dout, dlse, scale: float, causal: bool,
                    block_q: Optional[int], block_k: Optional[int]):
    """Blockwise (flash) attention backward: O(S·D) memory per (batch, head)
    instead of the dense fallback's O(Sq·Sk) probability matrix — the memory
    profile long-context training needs. Two grid passes: dK/dV (Q-axis
    innermost) and dQ (K-axis innermost), both recomputing probabilities
    from the forward's saved lse."""
    B, H, Sq, D = q.shape
    Sk = k.shape[2]
    acc_dtype = jnp.float64 if jnp.promote_types(q.dtype, jnp.float32) == jnp.float64 else jnp.float32
    bq, bk = _flash_blocks(block_q, block_k, Sq, Sk, _all_bf16(q, k, v, dout))
    sqp, skp, dp = _round_up(Sq, bq), _round_up(Sk, bk), _round_up(D, 128)
    BH = B * H

    qf = _pad_axis(_pad_axis(q.reshape(BH, Sq, D), 1, sqp), 2, dp)
    kf = _pad_axis(_pad_axis(k.reshape(BH, Sk, D), 1, skp), 2, dp)
    vf = _pad_axis(_pad_axis(v.reshape(BH, Sk, D), 1, skp), 2, dp)
    dof = _pad_axis(_pad_axis(dout.reshape(BH, Sq, D), 1, sqp), 2, dp)

    # per-row statistics: lse (padded +inf so padded rows give p = 0) and the
    # combined additive term dmb = dlse - delta, delta = rowsum(dout·out)
    delta = jnp.sum(dout.astype(acc_dtype) * out.astype(acc_dtype), axis=-1)
    dmb = (dlse.astype(acc_dtype) - delta).reshape(BH, Sq)
    lse_f = lse.astype(acc_dtype).reshape(BH, Sq)
    pad = sqp - Sq
    lse_f = jnp.pad(lse_f, ((0, 0), (0, pad)), constant_values=jnp.inf)
    dmb = jnp.pad(dmb, ((0, 0), (0, pad)))
    # both layouts: (BH, sqp, 8) for the dQ kernel (column reads), and the
    # transposed (BH, 8, sqp) for the dK/dV kernel (row reads)
    lse_c = jnp.broadcast_to(lse_f[:, :, None], (BH, sqp, 8))
    dmb_c = jnp.broadcast_to(dmb[:, :, None], (BH, sqp, 8))
    lse_r = jnp.broadcast_to(lse_f[:, None, :], (BH, 8, sqp))
    dmb_r = jnp.broadcast_to(dmb[:, None, :], (BH, 8, sqp))

    from jax.experimental.pallas import tpu as pltpu

    common = dict(
        scale=float(scale), block_q=bq, block_k=bk, kv_valid=Sk,
        causal_offset=(Sk - Sq) if causal else None, acc_dtype=acc_dtype,
    )
    vma = _vma(q, k, v, dout, dlse)

    dk, dv = pl.pallas_call(
        functools.partial(_flash_bwd_dkv_kernel, **common),
        grid=(BH, skp // bk, sqp // bq),
        in_specs=[
            pl.BlockSpec((1, bq, dp), lambda b, kb, qi: (_i32(b), _i32(qi), _i32(0))),
            pl.BlockSpec((1, bk, dp), lambda b, kb, qi: (_i32(b), _i32(kb), _i32(0))),
            pl.BlockSpec((1, bk, dp), lambda b, kb, qi: (_i32(b), _i32(kb), _i32(0))),
            pl.BlockSpec((1, bq, dp), lambda b, kb, qi: (_i32(b), _i32(qi), _i32(0))),
            pl.BlockSpec((1, 8, bq), lambda b, kb, qi: (_i32(b), _i32(0), _i32(qi))),
            pl.BlockSpec((1, 8, bq), lambda b, kb, qi: (_i32(b), _i32(0), _i32(qi))),
        ],
        out_specs=[
            pl.BlockSpec((1, bk, dp), lambda b, kb, qi: (_i32(b), _i32(kb), _i32(0))),
            pl.BlockSpec((1, bk, dp), lambda b, kb, qi: (_i32(b), _i32(kb), _i32(0))),
        ],
        out_shape=[
            _sds((BH, skp, dp), k.dtype, vma=vma),
            _sds((BH, skp, dp), v.dtype, vma=vma),
        ],
        scratch_shapes=[
            pltpu.VMEM((bk, dp), acc_dtype),
            pltpu.VMEM((bk, dp), acc_dtype),
        ],
        name="flash_bwd_dkv",
        interpret=_interpret(),
    )(qf, kf, vf, dof, lse_r, dmb_r)

    dq = pl.pallas_call(
        functools.partial(_flash_bwd_dq_kernel, **common),
        grid=(BH, sqp // bq, skp // bk),
        in_specs=[
            pl.BlockSpec((1, bq, dp), lambda b, qi, kb: (_i32(b), _i32(qi), _i32(0))),
            pl.BlockSpec((1, bk, dp), lambda b, qi, kb: (_i32(b), _i32(kb), _i32(0))),
            pl.BlockSpec((1, bk, dp), lambda b, qi, kb: (_i32(b), _i32(kb), _i32(0))),
            pl.BlockSpec((1, bq, dp), lambda b, qi, kb: (_i32(b), _i32(qi), _i32(0))),
            pl.BlockSpec((1, bq, 8), lambda b, qi, kb: (_i32(b), _i32(qi), _i32(0))),
            pl.BlockSpec((1, bq, 8), lambda b, qi, kb: (_i32(b), _i32(qi), _i32(0))),
        ],
        out_specs=[
            pl.BlockSpec((1, bq, dp), lambda b, qi, kb: (_i32(b), _i32(qi), _i32(0))),
        ],
        out_shape=[_sds((BH, sqp, dp), q.dtype, vma=vma)],
        scratch_shapes=[pltpu.VMEM((bq, dp), acc_dtype)],
        name="flash_bwd_dq",
        interpret=_interpret(),
    )(qf, kf, vf, dof, lse_c, dmb_c)[0]

    dq = dq[:, :Sq, :D].reshape(B, H, Sq, D)
    dk = dk[:, :Sk, :D].reshape(B, H, Sk, D)
    dv = dv[:, :Sk, :D].reshape(B, H, Sk, D)
    return dq, dk, dv


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash_diff(q, k, v, scale, causal, block_q, block_k):
    return _flash_impl(q, k, v, scale, causal, block_q, block_k)


def _flash_diff_fwd(q, k, v, scale, causal, block_q, block_k):
    out, lse = _flash_impl(q, k, v, scale, causal, block_q, block_k)
    return (out, lse), (q, k, v, out, lse)


def _flash_diff_bwd(scale, causal, block_q, block_k, residuals, cotangents):
    """Flash-attention backward. Default: the blockwise Pallas kernels
    (``_flash_bwd_impl``) — O(S·D) memory, recompute-from-lse, including the
    ``dlse`` cotangent ring attention folds with (``∂lse/∂S = P`` adds
    ``dlse·P`` to the score cotangent). When Pallas is unavailable, a dense
    jnp fallback with the same math: O(Sq·Sk) memory per (batch, head),
    correct on every backend."""
    q, k, v, out, lse = residuals
    dout, dlse = cotangents
    # hazard-check the cotangents too: replicated q/k/v pass the forward's
    # guard, but a loss that mixes the output with mesh-varying data hands
    # this bwd a vma-carrying dout the interpreter would reject
    if pallas_enabled() and not interpret_vma_hazard(q, k, v, dout, dlse):
        return _flash_bwd_impl(q, k, v, out, lse, dout, dlse, scale, causal,
                               block_q, block_k)
    qf, kf, vf = (x.astype(jnp.float32) for x in (q, k, v))
    doutf, outf = dout.astype(jnp.float32), out.astype(jnp.float32)

    s = jnp.einsum("bhqd,bhkd->bhqk", qf, kf) * scale
    Sq, Sk = s.shape[-2], s.shape[-1]
    if causal:
        row = jnp.arange(Sq)[:, None]
        col = jnp.arange(Sk)[None, :]
        s = jnp.where(col <= row + (Sk - Sq), s, -jnp.inf)
    p = jnp.exp(s - lse[..., None].astype(jnp.float32))
    p = jnp.where(jnp.isfinite(s), p, 0.0)  # fully-masked rows have lse=-inf

    d_rows = jnp.sum(doutf * outf, axis=-1)  # (B, H, Sq)
    dp = jnp.einsum("bhqd,bhkd->bhqk", doutf, vf)
    ds = p * (dp - d_rows[..., None] + dlse.astype(jnp.float32)[..., None])
    dq = jnp.einsum("bhqk,bhkd->bhqd", ds, kf) * scale
    dk = jnp.einsum("bhqk,bhqd->bhkd", ds, qf) * scale
    dv = jnp.einsum("bhqk,bhqd->bhkd", p, doutf)
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


_flash_diff.defvjp(_flash_diff_fwd, _flash_diff_bwd)


def flash_attention(
    q,
    k,
    v,
    scale: Optional[float] = None,
    causal: bool = False,
    return_lse: bool = False,
    block_q: Optional[int] = None,
    block_k: Optional[int] = None,
):
    """Blockwise (flash) attention with online softmax.

    ``q``: ``(B, H, Sq, D)``; ``k``/``v``: ``(B, H, Sk, D)``. Returns the
    attention output, plus per-row log-sum-exp when ``return_lse`` — the
    merge statistic ring attention folds across ``ppermute`` steps.
    Differentiable: the Pallas forward pairs with a recompute-from-lse
    backward (``_flash_diff_bwd``), so training paths (ring attention, the
    transformer example) work on TPU.

    What the MXU is handed follows the inputs' dtype (``_gemm``): all
    bfloat16, the exact GEMMs (``q·kᵀ``, ``dout·vᵀ``) take one pass and the
    float32 tiles the kernels compute (``p``, ``ds``) go as two bfloat16
    terms; any other dtype, or a mix, keeps float32 products at ``HIGHEST``.
    Statistics, ``exp`` and accumulators are float32 (float64 for float64
    inputs) either way. ``block_q``/``block_k`` default by that path
    (``_flash_blocks``).
    """
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    bq, bk = (b if b is None else int(b) for b in (block_q, block_k))
    out, lse = _flash_diff(q, k, v, float(scale), bool(causal), bq, bk)
    if return_lse:
        return out, lse
    return out


# --------------------------------------------------------------------------- #
# fused KMeans Lloyd tile                                                     #
# --------------------------------------------------------------------------- #


def _kmeans_kernel(x_ref, c_ref, mask_ref, sums_ref, counts_ref, stats_ref,
                   acc_sums, acc_counts, acc_inertia, *, block_rows: int,
                   acc_dtype, sums_mode: str, k: int):
    """One X row-block of the fused Lloyd step.

    The assignment GEMM, argmin, one-hot update GEMM and the inertia terms
    all consume the SAME VMEM-resident ``(block_rows, d)`` X tile, so each
    Lloyd iteration streams X from HBM exactly once (the jnp path reads it
    three times: the x^2 pass and both GEMMs). Scratch accumulators persist
    across the sequential 1-D grid; outputs are written on the last step.

    ``sums_mode`` selects how the centroid-sum update is computed (the stage
    whose Mosaic compile blew the scoped-VMEM budget at bench shapes).
    ``dot_t`` does not lower on this JAX (RecursionError in Mosaic lowering,
    described v5e); it and ``dot_rev`` stay selectable for interpret mode:

    * ``"dot_rev"`` — ``onehotᵀ·x`` expressed as a dim-0 contraction of the
      ``(bm, kp)`` one-hot (the original formulation; Mosaic materializes
      transpose temporaries for it).
    * ``"dot_t"`` — build the transposed one-hot ``(kp, bm)`` directly from
      the label vector and run a standard dim-1×dim-0 GEMM; no transpose
      temporaries.
    * ``"loop"`` — ``k`` masked VPU reductions of the resident tile
      (no update GEMM at all; attractive because k is tiny for Lloyd
      benchmarks, k=8).
    """
    step = pl.program_id(0)
    nsteps = pl.num_programs(0)

    @pl.when(step == 0)
    def _init():
        acc_sums[...] = jnp.zeros_like(acc_sums)
        acc_counts[...] = jnp.zeros_like(acc_counts)
        acc_inertia[...] = jnp.zeros_like(acc_inertia)

    x = x_ref[...].astype(acc_dtype)              # (bm, d)
    c = c_ref[...].astype(acc_dtype)              # (kp, d), pad rows = +big
    valid = mask_ref[...].astype(acc_dtype)       # (bm, 1)

    c2 = jnp.sum(c * c, axis=1)[None, :]          # (1, kp)
    xc = jax.lax.dot_general(
        x, c, dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=acc_dtype,
        precision=_MM_PRECISION,
    )                                             # (bm, kp)
    scores = c2 - 2.0 * xc                        # d^2 minus the x^2 term
    # explicit int32 index dtype: under jax_enable_x64 jnp.argmin asks for
    # int64 indices, which Mosaic's reduce-index lowering rejects
    labels = jax.lax.argmin(scores, 1, jnp.int32)  # (bm,)
    kp = scores.shape[1]

    # Each mode is fully self-contained — sums AND counts come from its own
    # representation, so the VMEM A/B on real TPU isolates the formulation
    # (a shared (bm, kp) one-hot would keep the dot_rev operand live in every
    # mode). acc_counts is (1, kp) for dot_rev, (kp, 1) otherwise.
    if sums_mode == "dot_rev":
        onehot = (labels[:, None] == jax.lax.broadcasted_iota(
            jnp.int32, (block_rows, kp), 1)).astype(acc_dtype) * valid
        acc_sums[...] += jax.lax.dot_general(
            onehot, x, dimension_numbers=(((0,), (0,)), ((), ())),
            preferred_element_type=acc_dtype,
            precision=_MM_PRECISION,
        )                                         # (kp, d)
        acc_counts[...] += jnp.sum(onehot, axis=0, keepdims=True)  # (1, kp)
    elif sums_mode == "dot_t":
        # invalid (padding) rows get the out-of-range label kp so the row
        # iota never matches them — masking without a (1, bm) transpose of
        # the valid column
        labels_m = jnp.where(mask_ref[...][:, 0] > 0, labels, kp)
        onehot_t = (labels_m[None, :] == jax.lax.broadcasted_iota(
            jnp.int32, (kp, block_rows), 0)).astype(acc_dtype)  # (kp, bm)
        acc_sums[...] += jax.lax.dot_general(
            onehot_t, x, dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=acc_dtype,
            precision=_MM_PRECISION,
        )                                         # (kp, d)
        acc_counts[...] += jnp.sum(onehot_t, axis=1, keepdims=True)  # (kp, 1)
    elif sums_mode == "loop":
        for j in range(k):
            w = jnp.where(labels[:, None] == j, valid, 0.0)      # (bm, 1)
            acc_sums[j:j + 1, :] += jnp.sum(w * x, axis=0, keepdims=True)
            acc_counts[j:j + 1, :] += jnp.sum(w, axis=0, keepdims=True)
    else:  # pragma: no cover — guarded by kmeans_step_tile
        raise ValueError(f"unknown sums_mode {sums_mode!r}")
    # inertia: min d^2 = min(scores) + x^2, both from the resident tile.
    # Mosaic forbids scalar stores to VMEM, so the scalar partial is
    # broadcast-accumulated into every lane of a vector-shaped scratch; the
    # flush reads one lane's worth (all lanes hold the same running sum).
    # all 2-D with keepdims: Mosaic rejects 1-D offset-changing slices
    x2 = jnp.sum(x * x, axis=1, keepdims=True)        # (bm, 1)
    min_s = jnp.min(scores, axis=1, keepdims=True)    # (bm, 1)
    partial = jnp.sum((min_s + x2) * valid)
    acc_inertia[...] += jnp.broadcast_to(partial, acc_inertia.shape)

    @pl.when(step == nsteps - 1)
    def _flush():
        sums_ref[...] = acc_sums[...].astype(sums_ref.dtype)
        cnt = acc_counts[...]
        if sums_mode != "dot_rev":
            cnt = cnt.T  # (kp, 1) accumulator -> (1, kp); one tiny transpose
        counts_ref[...] = jnp.broadcast_to(
            cnt, counts_ref.shape).astype(counts_ref.dtype)
        stats_ref[...] = jnp.broadcast_to(
            acc_inertia[...], stats_ref.shape).astype(stats_ref.dtype)


def _kmeans_block_rows() -> int:
    """X-tile rows for the KMeans kernel; A/B on real TPU via
    ``HEAT_TPU_KMEANS_BLOCK_ROWS`` (default 128 — the scoped-VMEM lever:
    every per-step temporary scales with the tile, and 128 is the largest
    tile the v5e compiler accepts in ``loop`` mode at 64 features: 256
    needs 16.44 MB of the 16 MB scoped VMEM). Resolved by the CALLER
    like :func:`_kmeans_sums_mode`, so step-cache keys and traced kernels
    can never disagree."""
    raw = os.environ.get("HEAT_TPU_KMEANS_BLOCK_ROWS", "128")
    try:
        val = int(raw)
    except ValueError:
        raise ValueError(
            f"HEAT_TPU_KMEANS_BLOCK_ROWS={raw!r}: expected a positive int")
    if val < 1:
        raise ValueError(
            f"HEAT_TPU_KMEANS_BLOCK_ROWS={val}: expected a positive int")
    return val


def _kmeans_sums_mode() -> str:
    """Centroid-sum formulation inside the KMeans kernel; A/B on real TPU via
    ``HEAT_TPU_KMEANS_SUMS=dot_rev|dot_t|loop`` (default ``loop`` — the
    one formulation the v5e compiler accepts on this JAX; see
    :func:`_kmeans_step_tile`)."""
    mode = os.environ.get("HEAT_TPU_KMEANS_SUMS", "loop")
    if mode not in ("dot_rev", "dot_t", "loop"):
        raise ValueError(
            f"HEAT_TPU_KMEANS_SUMS={mode!r}: expected dot_rev|dot_t|loop")
    return mode


def kmeans_step_tile(x, centroids, valid_mask, block_rows: Optional[int] = None,
                     sums_mode: Optional[str] = None):
    """Fused Lloyd iteration over a local X shard: ONE HBM pass.

    ``x``: ``(N_pad, d)``; ``centroids``: ``(k, d)``; ``valid_mask``:
    ``(N_pad, 1)`` 1.0 for real rows (the canonical-padding mask, constant
    across iterations). Returns ``(sums (k, d), counts (k,), inertia)`` —
    the per-shard partials the caller psums over the mesh. Labels are not
    produced here; the fit computes them once after convergence (a single
    extra assignment pass) instead of writing N int32s every iteration.
    ``sums_mode`` (default ``HEAT_TPU_KMEANS_SUMS``) picks the centroid-sum
    formulation, see :func:`_kmeans_kernel`.
    """
    # resolve the env-selected knobs OUTSIDE the jit so they are part of the
    # cache key (a None default baked in at trace time would go stale if the
    # env var changes between calls)
    if sums_mode is None:
        sums_mode = _kmeans_sums_mode()
    if block_rows is None:
        block_rows = _kmeans_block_rows()
    return _kmeans_step_tile(x, centroids, valid_mask, block_rows, sums_mode)


@functools.partial(jax.jit, static_argnames=("block_rows", "sums_mode"))
def _kmeans_step_tile(x, centroids, valid_mask, block_rows: int,
                      sums_mode: str):
    n, d = x.shape
    k = centroids.shape[0]
    acc_dtype = jnp.float64 if jnp.promote_types(x.dtype, jnp.float32) == jnp.float64 else jnp.float32
    kp = _round_up(k, 128)
    bm = min(_round_up(block_rows, 8), _round_up(n, 8))
    npad = _round_up(n, bm)
    xp = _pad_axis(x, 0, npad)
    maskp = _pad_axis(valid_mask.astype(x.dtype), 0, npad)
    # pad centroid rows with a huge coordinate: their c^2 term dominates so
    # argmin never selects a padding cluster
    cp = jnp.full((kp, d), 1e15, x.dtype).at[:k].set(centroids)

    from jax.experimental.pallas import tpu as pltpu

    sums, counts, stats = pl.pallas_call(
        functools.partial(_kmeans_kernel, block_rows=bm, acc_dtype=acc_dtype,
                          sums_mode=sums_mode, k=k),
        grid=(npad // bm,),
        in_specs=[
            pl.BlockSpec((bm, d), lambda i: (_i32(i), _i32(0))),
            pl.BlockSpec((kp, d), lambda i: (_i32(0), _i32(0))),
            pl.BlockSpec((bm, 1), lambda i: (_i32(i), _i32(0))),
        ],
        out_specs=[
            pl.BlockSpec((kp, d), lambda i: (_i32(0), _i32(0))),
            pl.BlockSpec((8, kp), lambda i: (_i32(0), _i32(0))),
            pl.BlockSpec((8, 128), lambda i: (_i32(0), _i32(0))),
        ],
        out_shape=[
            _sds((kp, d), acc_dtype, vma=_vma(x, centroids)),
            _sds((8, kp), acc_dtype, vma=_vma(x, centroids)),
            _sds((8, 128), acc_dtype, vma=_vma(x, centroids)),
        ],
        scratch_shapes=[
            pltpu.VMEM((kp, d), acc_dtype),
            pltpu.VMEM((1, kp) if sums_mode == "dot_rev" else (kp, 1),
                       acc_dtype),
            pltpu.VMEM((8, 128), acc_dtype),  # scalar held in every lane (native tile)
        ],
        name="kmeans_step_tile",
        interpret=_interpret(),
    )(xp, cp, maskp)
    return (sums[:k].astype(x.dtype), counts[0, :k].astype(x.dtype),
            stats[0, 0].astype(x.dtype))
