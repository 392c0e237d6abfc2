"""Tensor, pipeline and expert parallelism over the mesh.

The reference's parallelism inventory (SURVEY.md §2.6) covers data
parallelism (arrays + training), implicit tensor parallelism for linalg,
and the sequence-parallel *primitives* (halo, ring, all-to-all); pipeline
and expert parallelism are absent, and tensor parallelism never reaches the
NN layer. This module completes the grid: Megatron-style tensor-parallel
layers, a GPipe-style pipeline over a named mesh axis, and Switch/GShard
top-1 expert parallelism — all as per-device functions composable inside
one ``shard_map`` program, so dp x pp x tp x sp x ep combine in a single
compiled train step (see :mod:`heat_tpu.nn.transformer`).

Design notes (TPU-first):

* Tensor parallel: the column/row-parallel pairing keeps ONE ``psum`` per
  MLP / attention block (Megatron's schedule); XLA overlaps it with the
  adjacent GEMMs over ICI.
* Pipeline: stage weights live in a leading stage axis sharded over the
  ``pp`` mesh axis; activations flow stage-to-stage via ``ppermute`` inside
  a ``lax.scan`` over ``n_micro + pp - 1`` ticks (GPipe schedule). The scan
  is differentiable — the transpose of ``ppermute`` is the reverse
  ``ppermute`` — so one ``jax.grad`` drives the whole 1F1B-equivalent
  backward.
* Expert parallel: GShard dispatch/combine einsums with a static capacity
  (TPU static shapes); token routing between devices is one ``all_to_all``
  each way (the reference's Alltoallw resplit primitive,
  ``communication.py:1199-1341``, re-purposed for MoE routing).
"""

from __future__ import annotations

import math
from functools import partial
from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax import lax

from ..utils.profiling import scope

__all__ = [
    "column_parallel_dense",
    "row_parallel_dense",
    "tp_mlp",
    "tp_attention_qkv",
    "tp_attention_out",
    "switch_moe",
    "moe_capacity",
    "routed_experts",
    "gated_ffn",
    "pipeline_apply",
]


# --------------------------------------------------------------------- #
# Megatron-style tensor parallelism (per-device code, inside shard_map) #
# --------------------------------------------------------------------- #

def column_parallel_dense(x, w_shard, b_shard=None, *, axis: Optional[str] = None,
                          gather_output: bool = False):
    """``y = x @ W`` with ``W`` column-sharded over the ``tp`` axis.

    Input ``x`` is replicated over tp; output is feature-sharded — zero
    communication (unless ``gather_output``). Pair with
    :func:`row_parallel_dense` so the whole block costs one ``psum``.
    """
    y = x @ w_shard
    if b_shard is not None:
        y = y + b_shard
    if gather_output:
        if axis is None:
            raise ValueError("gather_output=True needs the tp axis name")
        y = lax.all_gather(y, axis, axis=y.ndim - 1, tiled=True)
    return y


def row_parallel_dense(x_shard, w_shard, b=None, *, axis: str):
    """``y = psum_tp(x_shard @ W_shard)`` with ``W`` row-sharded over tp.

    Input is feature-sharded (a column-parallel output); the partial
    products are summed over the tp axis — the single collective of the
    Megatron block. The (replicated) bias is added after the psum.
    """
    y = lax.psum(x_shard @ w_shard, axis)
    if b is not None:
        y = y + b
    return y


def tp_mlp(x, w_up_shard, w_down_shard, *, axis: str,
           activation: Callable = jax.nn.gelu, b_up_shard=None, b_down=None):
    """Tensor-parallel transformer MLP: column-parallel up-projection,
    activation on the shard, row-parallel down-projection (one psum)."""
    h = column_parallel_dense(x, w_up_shard, b_up_shard)
    return row_parallel_dense(activation(h), w_down_shard, b_down, axis=axis)


def tp_attention_qkv(x, wqkv_shard, n_heads_shard: int):
    """QKV projection with heads sharded over tp.

    ``wqkv_shard``: ``(D, 3 * H_shard * Dh)`` — the columns for this
    device's head subset. Returns ``(q, k, v)`` each
    ``(..., S, H_shard, Dh)``.
    """
    h = x @ wqkv_shard
    q, k, v = jnp.split(h, 3, axis=-1)
    Dh = q.shape[-1] // n_heads_shard

    def heads(t):
        return t.reshape(*t.shape[:-1], n_heads_shard, Dh)

    return heads(q), heads(k), heads(v)


def tp_attention_out(attn_shard, wproj_shard, *, axis: str):
    """Output projection of tp-sharded attention: flatten the local head
    subset, row-parallel project, psum over tp (the block's one collective)."""
    flat = attn_shard.reshape(*attn_shard.shape[:-2], -1)
    return row_parallel_dense(flat, wproj_shard, axis=axis)


# --------------------------------------------------------------------- #
# Switch / GShard top-1 expert parallelism                              #
# --------------------------------------------------------------------- #

def moe_capacity(tokens_local: int, n_experts: int, capacity_factor: float) -> int:
    """Static per-(source device, expert) buffer size."""
    return max(1, int(math.ceil(tokens_local * capacity_factor / n_experts)))


def switch_moe(x, router_w, expert_up_shard, expert_down_shard, *, axis: str,
               capacity_factor: float = 1.25,
               activation: Callable = jax.nn.gelu):
    """Top-1 (Switch) mixture-of-experts with experts sharded over ``axis``.

    Per-device code for ``shard_map``. Shapes (per device):

    * ``x``: ``(T, D)`` local tokens (flatten batch x seq first)
    * ``router_w``: ``(D, E)`` replicated, ``E = ep * E_local``
    * ``expert_up_shard``: ``(E_local, D, F)``; ``expert_down_shard``:
      ``(E_local, F, D)`` — this device's experts.

    Routing: GShard dispatch/combine einsums with static capacity
    ``C = ceil(T * capacity_factor / E)`` per (source device, expert);
    overflow tokens fall through the residual (standard Switch drop
    semantics). Cross-device movement is one ``all_to_all`` each way.
    """
    T, D = x.shape
    E_local, _, F = expert_up_shard.shape
    ep = lax.psum(1, axis)  # axis size, available inside shard_map
    E = ep * E_local
    C = moe_capacity(T, E, capacity_factor)

    # --- router (local) --- #
    logits = x @ router_w                        # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    expert_idx = jnp.argmax(probs, axis=-1)      # (T,)
    gate = jnp.take_along_axis(probs, expert_idx[:, None], axis=-1)[:, 0]

    onehot = jax.nn.one_hot(expert_idx, E, dtype=x.dtype)          # (T, E)
    pos = jnp.cumsum(onehot, axis=0) * onehot - 1.0                # slot per token
    kept = (pos >= 0) & (pos < C)
    pos_oh = jax.nn.one_hot(pos.astype(jnp.int32), C, dtype=x.dtype)
    dispatch = pos_oh * kept[..., None].astype(x.dtype)            # (T, E, C)
    combine = dispatch * gate[:, None, None]                       # (T, E, C)

    # --- dispatch to expert shards: one all_to_all --- #
    expert_in = jnp.einsum("tec,td->ecd", dispatch, x)             # (E, C, D)
    expert_in = expert_in.reshape(ep, E_local, C, D)
    # send block i to device i; received blocks stack on the (new) source axis
    expert_in = lax.all_to_all(expert_in, axis, split_axis=0, concat_axis=0)
    # (ep_src, E_local, C, D): this device's experts, tokens from every source

    # --- expert FFN on the local expert subset --- #
    h = activation(jnp.einsum("secd,edf->secf", expert_in, expert_up_shard))
    expert_out = jnp.einsum("secf,efd->secd", h, expert_down_shard)

    # --- return to sources: the inverse all_to_all --- #
    expert_out = lax.all_to_all(expert_out, axis, split_axis=0, concat_axis=0)
    expert_out = expert_out.reshape(E, C, D)

    # --- combine (local) --- #
    return jnp.einsum("tec,ecd->td", combine, expert_out)


# --------------------------------------------------------------------- #
# GPipe pipeline parallelism                                            #
# --------------------------------------------------------------------- #

def pipeline_apply(stage_fn: Callable, stage_params, x_micro, *, axis: str):
    """Run ``pp`` pipeline stages over microbatches (per-device, shard_map).

    * ``stage_fn(params, x) -> y``: one stage's computation; activations
      must keep a fixed shape across stages.
    * ``stage_params``: this device's stage parameters (the global pytree
      carries a leading stage axis sharded over ``axis``; inside shard_map
      each device sees leading dim 1 — pass it squeezed or indexed).
    * ``x_micro``: ``(n_micro, mb, ...)`` microbatched input, replicated
      over the pp axis.

    GPipe schedule: ``T = n_micro + pp - 1`` ticks in a ``lax.scan``; at
    each tick every device computes its stage on the activation received
    via ``ppermute`` from the previous stage (stage 0 feeds the next
    microbatch) and passes the result on. Outputs are collected on the
    last stage and broadcast with a masked ``psum``. Differentiable end to
    end (scan + ppermute transpose), so ``jax.grad`` of a loss on the
    output drives the full pipeline backward pass.

    Gradient pattern: because the output is replicated over ``pp`` via a
    ``psum`` broadcast, a training loss must be counted ONCE globally —
    mask it to the last stage and ``psum``::

        out = pipeline_apply(stage_fn, params, x_micro, axis="pp")
        l = lax.psum(loss(out) * (lax.axis_index("pp") == pp - 1), "pp")

    so the cotangent enters the collective's transpose on exactly one
    device and per-stage parameter gradients land on the owning device
    with no replication factor.
    """
    pp = lax.psum(1, axis)
    stage = lax.axis_index(axis)
    n_micro = x_micro.shape[0]
    T = n_micro + pp - 1
    perm = [(i, i + 1) for i in range(pp - 1)]  # no wraparound

    # the rotating buffers assume the stage preserves dtype (a dtype change
    # would silently corrupt the masked writes). Checked on EVERY path —
    # incl. the degenerate pp==1 mesh developers test on — so the contract
    # fails loud before a real pipeline deployment
    out_struct = jax.eval_shape(stage_fn, stage_params, x_micro[0])
    if out_struct.dtype != x_micro.dtype:
        raise TypeError(
            f"pipeline stage changed activation dtype {x_micro.dtype} -> "
            f"{out_struct.dtype}; keep compute dtype uniform across stages "
            "(cast params inside the stage, not activations between stages)")

    if pp == 1:
        # degenerate pipeline: run the stage per microbatch (scan, not vmap —
        # the stage may contain collectives over other axes). The identity
        # psum clears the axis-varying type the (pp-sharded) stage params
        # impart under vma tracking, matching the pp>1 branch's out type
        # (audits count it with ``communicating_collective_stats``, which
        # ignores singleton-group all-reduces)
        _, out = lax.scan(
            lambda c, xm: (c, stage_fn(stage_params, xm)), 0, x_micro)
        return lax.psum(out, axis)

    # initial carries are device-varying (they hold per-stage activations)
    _vary = partial(lax.pcast, to="varying")
    out_buf = _vary(jnp.zeros_like(x_micro), axis)
    recv = _vary(jnp.zeros_like(x_micro[0]), axis)

    def tick(carry, t):
        recv, out_buf = carry
        # stage 0 reads microbatch t (zeros once the feed is exhausted)
        feed = lax.dynamic_index_in_dim(
            x_micro, jnp.minimum(t, n_micro - 1), keepdims=False)
        feed = jnp.where(t < n_micro, feed, jnp.zeros_like(feed))
        x_in = jnp.where(stage == 0, feed, recv)
        y = stage_fn(stage_params, x_in)
        # last stage stores microbatch t-(pp-1) when in range; the masked
        # write (no lax.cond) keeps branch types uniform under vma tracking
        slot = t - (pp - 1)
        store = (stage == pp - 1) & (slot >= 0)
        slot_c = jnp.clip(slot, 0, n_micro - 1)
        cur = lax.dynamic_index_in_dim(out_buf, slot_c, keepdims=False)
        out_buf = lax.dynamic_update_index_in_dim(
            out_buf, jnp.where(store, y, cur), slot_c, axis=0)
        recv = lax.ppermute(y, axis, perm)
        return (recv, out_buf), None

    (recv, out_buf), _ = lax.scan(tick, (recv, out_buf), jnp.arange(T))
    # broadcast the last stage's buffer to every pp rank
    mask = (stage == pp - 1).astype(out_buf.dtype)
    return lax.psum(out_buf * mask, axis)


# --------------------------------------------------------------------- #
# routed experts, a share of them held here (serving)                   #
# --------------------------------------------------------------------- #

def gated_ffn(u, w1, w2):
    """``(silu(g) * p) W2`` with ``[g, p] = u W1``: operands in ``u``'s
    dtype, accumulated in float32, the gate in float32."""
    F = w2.shape[0]
    gp = jnp.dot(u, w1.astype(u.dtype), preferred_element_type=jnp.float32)
    h = (jax.nn.silu(gp[..., :F]) * gp[..., F:]).astype(u.dtype)
    return jnp.dot(h, w2.astype(u.dtype),
                   preferred_element_type=jnp.float32).astype(u.dtype)


def _top_k_gates(logits, k: int):
    """Each row's ``k`` largest logits: (their gates, a softmax over THOSE k;
    their places)."""
    top, chosen = lax.top_k(logits, k)
    return jax.nn.softmax(top, axis=-1), chosen


def routed_experts(u, w_r, w1, w2, *, k: int, held, valid=None, at=None):
    """The routed half of an expert layer on a device that is TOLD which
    experts it holds. ``u`` (T, D) tokens; ``w_r`` (D, E) the router over ALL
    E experts; ``w1`` (count, D, 2F) and ``w2`` (count, F, D) the gated
    experts ``held = (first, count)``, that is experts ``first .. first +
    count - 1``.

    Every token chooses its ``k`` largest router logits (float32) and gates
    them by a softmax over THOSE k. Of the T k (token, expert) pairs, the
    ones whose expert is held are sorted by expert and go through two grouped
    matrix products (``lax.ragged_dot``: a group an expert, as many rows as
    it was sent); each token sums its held pairs' results by their gates.
    What the experts held elsewhere would add is left out: the result is
    this device's PART of the layer. No capacity: a pair is never dropped,
    whatever the imbalance; the shapes are static all the same (the pairs'
    array has T k rows, the held ones first).

    Returns (the part (T, D) in ``u``'s dtype, the count of pairs by held
    expert (count,) int32). ``valid`` (T,) bool keeps tokens that are no
    one's (a bucket's pad rows, a dead slot) out of the COUNT; they are
    computed like any other.

    ``at``: ``w1`` and ``w2`` are several layers' experts STACKED, (R, count,
    ...), and this layer's are entry ``at`` (an int, or a scan's traced
    index). The grouped products then take the whole stack as R count groups,
    all empty but this layer's: they read the weights where they lie. A slice
    handed to them would be copied out first, 0.7 GB a layer at the sizes
    served (the compiler fuses a slice into a plain product, not into a
    grouped one)."""
    first, count = held
    T, D = u.shape
    F = w2.shape[-2]
    with scope("moe.route"):
        logits = jnp.dot(u, w_r.astype(u.dtype),
                         preferred_element_type=jnp.float32)
        gates, chosen = _top_k_gates(logits, k)                     # (T, k)
        local = chosen - first
        here = (local >= 0) & (local < count)
        group = jnp.where(here, local, count).reshape(-1)          # (T k,)
        order = jnp.argsort(group, stable=True)
        sent = jax.nn.one_hot(group, count, dtype=jnp.int32)   # unheld: no 1
        sizes = pairs = jnp.sum(sent, axis=0, dtype=jnp.int32)
        if valid is not None:
            pairs = jnp.sum(jnp.where(jnp.repeat(valid, k)[:, None], sent, 0),
                            axis=0, dtype=jnp.int32)
    with scope("moe.experts"):
        if at is not None:
            sizes = lax.dynamic_update_slice(
                jnp.zeros(w1.shape[0] * count, jnp.int32), sizes,
                (at * count,))
            w1 = w1.reshape(-1, *w1.shape[2:])
            w2 = w2.reshape(-1, *w2.shape[2:])
        rows = jnp.take(u, order // k, axis=0)                     # (T k, D)
        # the operands are in one dtype: nothing for a precision to split
        # (the package's default "high" takes the grouped product off the
        # TPU's own kernel and onto a masked product over every group)
        gp = lax.ragged_dot(rows, w1.astype(u.dtype), sizes,
                            precision=lax.Precision.DEFAULT,
                            preferred_element_type=jnp.float32)
        h = (jax.nn.silu(gp[:, :F]) * gp[:, F:]).astype(u.dtype)
        out = lax.ragged_dot(h, w2.astype(u.dtype), sizes,
                             precision=lax.Precision.DEFAULT,
                             preferred_element_type=u.dtype)
    with scope("moe.combine"):
        # back into (token, choice) order; rows past the held groups hold
        # whatever the grouped product left there and are masked, not scaled
        back = jnp.zeros_like(order).at[order].set(
            jnp.arange(T * k, dtype=order.dtype))
        out = jnp.take(out, back, axis=0).reshape(T, k, D)
        part = jnp.sum(jnp.where(here[..., None], out.astype(jnp.float32), 0.0)
                       * gates[..., None], axis=1)
    return part.astype(u.dtype), pairs
