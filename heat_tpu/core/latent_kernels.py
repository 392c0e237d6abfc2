"""The decode step's absorbed latent core as one Mosaic kernel:
:func:`latent_attention`.

A latent-attention layer (``mixers.mla_step``) keeps ONE lane a slot of
[c_kv | k_r | 1 | zeros] rows, and every head reads the same row of a
position: the whole row is the key of the head's absorbed query, its first C
columns the value. So the kernel fetches each block of a slot's lane once and
reads it twice, for the scores of all the heads ((H, W) against the block)
and for their values (the probabilities against its first C columns), and
it fetches blocks only as far as the slot has got: the slots' lengths,
prefetched as scalars, steer the lane's index map as in
:func:`~.lane_kernels.lane_attention`.

It is not :func:`~.lane_kernels.lane_attention` with the one lane handed in
as keys and values: that kernel maps heads onto per-head slices of a row
(static head maps, a query spread over the row's whole width), would fetch
every block twice, and at 128 heads holds (H, W) twice in scoped VMEM. Nor
does it live in that module or in :mod:`.pallas_kernels`, whose helpers it
imports: a Mosaic kernel's serialized body, which the persistent compile
cache keys on, carries the source lines it was traced from, so a kernel added
to either would give the programs of every kernel below it a new key and a
fresh compile.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from . import pallas_kernels as pk
from .lane_kernels import _lane_last
from .pallas_kernels import (_NEG_BIG, _NN, _NT, _all_bf16, _gemm, _i32, _sds,
                             _vma)

__all__ = ["latent_attention", "latent_rows"]

# rows of a lane block by default. Timed alone on a TPU v5e at 128 slots of
# 4,096 rows 640 wide, lengths from the pangu cell's mix (mean 1,719): 256
# rows 1.197 ms a layer, 512 rows 0.860, 1,024 rows 0.743, 2,048 rows 0.740
# (each grid cell costs its time whether it computes or not, and a larger
# block fetches more dead rows past a slot's position)
_LATENT_ROWS = 1024


def latent_rows(S: int, block_k: Optional[int] = None) -> int:
    """Rows of a block of a latent lane of ``S`` rows: ``block_k`` if given,
    else 1,024, and the whole lane where it is shorter (a block that does
    not divide the lane leaves a partial last block)."""
    return min(int(block_k or _LATENT_ROWS), S)


def _latent_kernel(seen_ref, q_ref, lane_ref, o_ref, m_ref, l_ref, acc_ref, *,
                   C: int, scale: float, block_k: int, n_blocks: int):
    """Grid cell (slot ``b``, lane block ``j``) of :func:`latent_attention`.

    The block is read once a cell for both products: the scores of every
    head against the whole rows, and the values out of their first ``C``
    columns. Blocks past the slot's last live one are neither fetched (the
    index map names the next slot's first block) nor computed; the last is
    masked, its rows past ``seen`` zeroed before either product (0 x NaN is
    NaN) and their scores dropped, whatever the rows hold (past the lane's
    end, in a partial last block, too). The output is written there, once
    a slot."""
    b, j = pl.program_id(0), pl.program_id(1)
    seen = seen_ref[b]
    last = _lane_last(seen, block_k, n_blocks)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_BIG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    mxu_bf16 = _all_bf16(q_ref, lane_ref)

    def step(masked: bool):
        rows = lane_ref[0]
        if masked:
            row = (jax.lax.broadcasted_iota(jnp.int32, (block_k, 1), 0)
                   + j * block_k)
            rows = jnp.where(row < seen, rows, jnp.zeros((), rows.dtype))
        s = _gemm(q_ref[0], rows, _NT, jnp.float32, mxu_bf16) * scale
        if masked:
            col = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1) + j * block_k
            s = jnp.where(col < seen, s, jnp.asarray(_NEG_BIG, s.dtype))
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        # the last block holds a live row, so a masked score's p is exp of
        # about -1e30: exactly 0
        p = jnp.exp(s - m_new)
        # rounded once to the lane's dtype for the value product
        a = p.astype(rows.dtype) if mxu_bf16 else p
        acc_ref[...] = (acc_ref[...] * alpha
                        + _gemm(a, rows[:, :C], _NN, jnp.float32, mxu_bf16))
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        m_ref[...] = m_new

    pl.when(j < last)(lambda: step(False))
    pl.when(j == last)(lambda: step(True))

    @pl.when(j == last)
    def _finish():
        o_ref[0] = (acc_ref[...] / l_ref[...]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("C", "scale", "block_k"))
def latent_attention(q, lane, seen, *, C: int, scale: float,
                     block_k: Optional[int] = None):
    """Every head's absorbed query against its slot's latent lane, read only
    as far as the slot has got: the core of a latent-attention decode step.

    ``q`` (B, H, W): slot b's heads, [q_lat,h | q_rope,h | 0] (W wide, the
    zeros over the lane's 1 and pad columns). ``lane`` (B, S, W): the latent
    lane as it lies, a position a row of [c_kv | k_r | 1 | zeros]; row r of
    slot b counts iff r < ``seen[b]``. Returns (B, H, ``C``) float32: each
    head's softmax of ``scale`` times its scores against the rows, applied
    to their first ``C`` columns (c_kv).

    Scores accumulate in float32 (one exact pass on bfloat16 operands); the
    softmax runs online in float32, and the probabilities go to the value
    product rounded once to the lane's dtype (float32 lanes take float32
    products). Its normaliser is their float32 sum.

    The grid runs over slots and blocks of ``block_k`` rows
    (:func:`latent_rows`; a last block that runs past the lane's end reads
    rows that are masked like any past ``seen``); ``seen`` rides as a
    prefetched scalar, and the lane's one index map sends a cell past the
    slot's last live block to the next slot's first, so Mosaic fetches
    ceil(seen / block_k) blocks a slot, each while the block before it
    computes, and the kernel computes those alone. The scratch holds the
    running max and sum (H, 1) and the (H, C) float32 accumulator."""
    B, H, W = q.shape
    S = lane.shape[1]
    if lane.shape != (B, S, W) or seen.shape != (B,) or not 0 < C <= W:
        raise ValueError(f"queries {q.shape} and a lane {lane.shape} with "
                         f"{seen.shape} lengths do not fit a latent of {C}")
    bk = latent_rows(S, block_k)
    n_blocks = -(-S // bk)

    from jax.experimental.pallas import tpu as pltpu

    def onward(b, j, seen_ref):
        """The (slot, block) a grid cell's lane and query blocks come from:
        its own, or, for a cell past the slot's last live block, the NEXT
        slot's first (the last slot's last)."""
        b, j = _i32(b), _i32(j)
        last = _lane_last(seen_ref[b], bk, n_blocks)
        on = jnp.logical_and(j > last, b + 1 < B)
        return (jax.lax.select(on, b + 1, b),
                jax.lax.select(on, jnp.int32(0), jnp.minimum(j, last)))

    def lane_block(b, j, seen_ref):
        slot, block = onward(b, j, seen_ref)
        return slot, block, _i32(0)

    def query_block(b, j, seen_ref):
        return onward(b, j, seen_ref)[0], _i32(0), _i32(0)

    def slot_block(b, j, seen_ref):
        return _i32(b), _i32(0), _i32(0)

    return pl.pallas_call(
        functools.partial(_latent_kernel, C=int(C), scale=float(scale),
                          block_k=bk, n_blocks=n_blocks),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B, n_blocks),
            in_specs=[pl.BlockSpec((1, H, W), query_block),
                      pl.BlockSpec((1, bk, W), lane_block)],
            out_specs=pl.BlockSpec((1, H, C), slot_block),
            scratch_shapes=[pltpu.VMEM((H, 1), jnp.float32),
                            pltpu.VMEM((H, 1), jnp.float32),
                            pltpu.VMEM((H, C), jnp.float32)]),
        out_shape=_sds((B, H, C), jnp.float32, vma=_vma(q, lane, seen)),
        name="latent_attention",
        interpret=pk._interpret(),
    )(_i32(seen), q, lane)
