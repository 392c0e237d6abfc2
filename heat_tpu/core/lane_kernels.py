"""The decode step's attention as one Mosaic kernel: :func:`lane_attention`.

One query row a slot against that slot's cache lane as it lies, read only as
far as the slot has got: the slots' lengths, prefetched as scalars, steer the
K/V index maps, so blocks past a slot's position are neither fetched nor
computed. Static head maps fit the one kernel to a dense layer
(``TransformerLM._attn_from_cache``), grouped queries (``mixers.gqa_lanes``)
and differential attention (``mixers.diff_attention_lanes``).

It shares :mod:`.pallas_kernels`' helpers (the bfloat16 products, the int32
index maps, the varying-axes type, the interpreter off the TPU) but lives in
a module of its own: a Mosaic kernel's serialized body, which the persistent
compile cache keys on, carries the source lines it was traced from, so a
kernel added to that module would give every kernel below it (``cdist_tile``,
the flash kernels) a new key and their programs a fresh compile.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from . import pallas_kernels as pk
from .pallas_kernels import (_NEG_BIG, _NN, _NT, _all_bf16, _gemm, _i32, _sds,
                             _vma)

__all__ = ["lane_attention"]

# rows of a lane block by default: about this many bytes of one lane (K or V),
# in multiples of 128 rows
_LANE_BLOCK_BYTES = 1 << 20


def _lane_rows(S: int, width: int, itemsize: int,
               block_k: Optional[int]) -> int:
    """Rows of a lane block: ``block_k`` if given, else the whole lane where
    it holds at most about ``_LANE_BLOCK_BYTES`` (and at least 128 rows),
    else the largest multiple of 128 rows within that which divides ``S``,
    else that many rows with a partial last block."""
    if block_k is not None:
        return min(int(block_k), S)
    want = max(128, _LANE_BLOCK_BYTES // (width * itemsize) // 128 * 128)
    if S <= want:
        return S
    for rows in range(want, 0, -128):
        if S % rows == 0:
            return rows
    return want


def _lane_last(seen, block_k: int, n_blocks: int):
    """The slot's last block that holds a live row (rows < ``seen``), in
    int32 throughout (Mosaic has no 64-bit integers, and the package runs
    with ``jax_enable_x64``)."""
    blocks = jax.lax.div(seen + jnp.int32(block_k - 1), jnp.int32(block_k))
    return jnp.minimum(jnp.maximum(blocks - 1, jnp.int32(0)),
                       jnp.int32(n_blocks - 1))


def _lane_kernel(seen_ref, q_ref, k_ref, v_ref, o_ref, qm_ref, m_ref, l_ref,
                 acc_ref, *, scale: float, block_k: int, n_blocks: int,
                 per_group: int, hi_lo: bool):
    """Grid cell (slot ``b``, lane block ``j``) of :func:`lane_attention`.

    At the slot's first block the queries ``q_ref`` (1, H, gw) are spread
    into ``qm_ref`` (H, W): head h's row holds its query in the gw columns of
    its group h // ``per_group`` and zeros elsewhere, so ONE product with a
    block of the lane as it lies gives every head's scores against its own
    key columns, and one product of the probabilities with the value block
    gives every head its own group's values among the (H, W) accumulated.
    Blocks past the slot's last live one are neither fetched (their index
    maps name the next slot's first block) nor computed; the last is masked:
    its scores past ``seen`` and the values of those rows, whatever they
    hold (past the lane's end, in a partial last block, too). The slot's
    output is made there too: the query and output blocks are read and
    written once a slot."""
    b, j = pl.program_id(0), pl.program_id(1)
    H, gw = q_ref.shape[1], q_ref.shape[2]
    groups = qm_ref.shape[1] // gw
    seen = seen_ref[b]
    last = _lane_last(seen, block_k, n_blocks)

    def group_rows(shape, a):
        row = jax.lax.broadcasted_iota(jnp.int32, shape, 0)
        return jnp.logical_and(row >= a * per_group, row < (a + 1) * per_group)

    @pl.when(j == 0)
    def _init():
        q = q_ref[0]
        for a in range(groups):
            qm_ref[:, a * gw:(a + 1) * gw] = jnp.where(
                group_rows(q.shape, a), q, jnp.zeros((), q.dtype))
        m_ref[...] = jnp.full_like(m_ref, _NEG_BIG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    mxu_bf16 = _all_bf16(q_ref, k_ref, v_ref)

    def step(masked: bool):
        v = v_ref[0]
        s = _gemm(qm_ref[...], k_ref[0], _NT, jnp.float32, mxu_bf16) * scale
        if masked:
            col = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1) + j * block_k
            s = jnp.where(col < seen, s, jnp.asarray(_NEG_BIG, s.dtype))
            row = (jax.lax.broadcasted_iota(jnp.int32, (block_k, 1), 0)
                   + j * block_k)
            v = jnp.where(row < seen, v, jnp.zeros((), v.dtype))
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        # the last block holds a live row, so a masked score's p is exp of
        # about -1e30: exactly 0
        p = jnp.exp(s - m_new)
        # the probabilities as two bfloat16 terms (`hi_lo`), or rounded to
        # the values' dtype in one pass
        a = p if hi_lo or not mxu_bf16 else p.astype(v.dtype)
        acc_ref[...] = (acc_ref[...] * alpha
                        + _gemm(a, v, _NN, jnp.float32, mxu_bf16))
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        m_ref[...] = m_new

    pl.when(j < last)(lambda: step(False))
    pl.when(j == last)(lambda: step(True))

    @pl.when(j == last)
    def _finish():
        acc = acc_ref[...]
        out = jnp.zeros((H, gw), jnp.float32)
        for a in range(groups):
            out = out + jnp.where(group_rows((H, gw), a),
                                  acc[:, a * gw:(a + 1) * gw],
                                  jnp.zeros((), jnp.float32))
        o_ref[0] = (out / l_ref[...]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "k_of", "v_of", "scale", "precision", "block_k"))
def lane_attention(q, kl, vl, seen, k_of: Tuple[int, ...],
                   v_of: Tuple[int, ...], scale: float,
                   precision=jax.lax.Precision.DEFAULT,
                   block_k: Optional[int] = None):
    """One query row a slot against that slot's cache lane, read only as far
    as the slot has got: the decode step's attention.

    ``q`` (B, H, d): slot b's query heads. ``kl``, ``vl`` (B, S, W): the
    cache's key and value lanes as they lie, a position a row, W = Hkv d
    (``mixers.lanes``); row r of slot b counts iff r < ``seen[b]``. Query
    head h scores against key head ``k_of[h]`` (columns ``k_of[h] d`` on, d
    wide) and reads value block ``v_of[h]`` of gw = W / (max(v_of) + 1)
    columns: the identity for a dense layer, ``h // (H / Hkv)`` for grouped
    queries, and for differential attention key head ``2 (h // 4) + h % 2``
    and, 2d wide, the value heads ``2 (h // 4)`` and ``2 (h // 4) + 1``. The
    maps are static. The value blocks split the heads into equal runs in
    order (``v_of[h] == h // (H / G)``) and each head's key columns lie in
    its own value block. Returns (B, H, gw) float32.

    Scores are ``scale`` times q.k, accumulated in float32 (one exact pass
    on bfloat16 operands); softmax online in float32. ``precision`` is the
    value product's: ``DEFAULT`` rounds the probabilities to the values'
    dtype, a higher one keeps them to 16 bits of mantissa (two bfloat16
    terms) against bfloat16 values; float32 values take float32 products.

    The grid runs over slots and blocks of ``block_k`` rows
    (:func:`_lane_rows`; a last block that runs past the lane's end reads
    rows that are masked like any past ``seen``); ``seen`` rides as a
    prefetched scalar, and the K/V index maps send a cell past the slot's
    last live block to the next slot's first, so Mosaic fetches
    ceil(seen / block_k) blocks a slot, each while the block before it
    computes, and the kernel computes those alone.

    What grows with the heads: every head's query is spread over the lane's
    whole width, so the scratch holds (H, W) twice (the queries, and a
    float32 accumulator: 6 H W bytes), the score product does Hkv times the
    FLOPs it needs and the value product as many again (twice that with two
    terms). At 16 heads of 128 that is 0.2 MB beside four blocks of 1 MiB,
    and the kernel stays bound by the lane's bytes. At 64 heads of 128 it is
    3 MB beside four blocks of 2 MB (a block keeps 128 rows), and at 128
    heads the blocks alone fill the 16 MiB of scoped VMEM a v5e kernel has:
    a dense layer that wide wants a grid over groups of heads."""
    B, H, d = q.shape
    S, W = kl.shape[1], kl.shape[2]
    k_of, v_of = tuple(int(h) for h in k_of), tuple(int(h) for h in v_of)
    groups = max(v_of) + 1
    gw, per_group = W // groups, H // groups
    offs = [k * d - v * gw for k, v in zip(k_of, v_of)]
    if (len(k_of) != H or len(v_of) != H or W % groups or H % groups
            or v_of != tuple(h // per_group for h in range(H))
            or min(offs) < 0 or max(offs) > gw - d):
        raise ValueError(f"head maps k_of={k_of}, v_of={v_of} do not fit "
                         f"{H} query heads of {d} over a lane {W} wide")
    bk = _lane_rows(S, W, kl.dtype.itemsize, block_k)
    n_blocks = -(-S // bk)
    # each head's query at its key head's place inside its value block
    qg = q if gw == d else sum(
        jnp.where(jnp.asarray([off == o for off in offs])[None, :, None],
                  jnp.pad(q, ((0, 0), (0, 0), (o, gw - d - o))),
                  jnp.zeros((), q.dtype))
        for o in sorted(set(offs)))

    from jax.experimental.pallas import tpu as pltpu

    def onward(b, j, seen_ref):
        """The (slot, block) a grid cell's K/V and query blocks come from: its
        own, or, for a cell past the slot's last live block, the NEXT slot's
        first (the last slot's last). So the next slot's first block is
        fetched while this slot's last one computes, the cells past it fetch
        nothing, and only live blocks are ever read."""
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
        functools.partial(
            _lane_kernel, scale=float(scale), block_k=bk, n_blocks=n_blocks,
            per_group=per_group,
            hi_lo=precision not in (None, jax.lax.Precision.DEFAULT)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B, n_blocks),
            in_specs=[pl.BlockSpec((1, H, gw), query_block),
                      pl.BlockSpec((1, bk, W), lane_block),
                      pl.BlockSpec((1, bk, W), lane_block)],
            out_specs=pl.BlockSpec((1, H, gw), slot_block),
            scratch_shapes=[pltpu.VMEM((H, W), q.dtype),
                            pltpu.VMEM((H, 1), jnp.float32),
                            pltpu.VMEM((H, 1), jnp.float32),
                            pltpu.VMEM((H, W), jnp.float32)]),
        out_shape=_sds((B, H, gw), jnp.float32, vma=_vma(q, kl, vl, seen)),
        name="lane_attention",
        interpret=pk._interpret(),
    )(_i32(seen), qg, kl, vl)
