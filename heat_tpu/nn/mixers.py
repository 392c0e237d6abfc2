"""Token mixers beside plain causal attention, as ``TransformerLM``'s
per-layer pattern names them: a Mamba-1 selective state-space layer
(arXiv:2312.00752), differential attention (arXiv:2410.05258) over a full, a
windowed or a borrowed (cross) key/value set, the gated memory unit of the
decoder-hybrid-decoder (arXiv:2507.06607), a Mamba-2 layer (arXiv:2405.21060:
a scalar decay a head, the chunked state-space-dual form over a prompt), plain
grouped-query attention against a cache lane and multi-head latent attention
(DeepSeek-V2, arXiv:2405.04434: keys and values out of one latent row a
position, decompressed over a prompt, absorbed into the queries for a step).
Pure functions of a layer's
parameters and its input, each in two forms: over a whole prompt (``*_prompt``:
what the padded prefill runs, returning what the cache keeps) and for one token
against what the cache holds (``*_step``).

Matrix products take ``compute_dtype`` operands and accumulate in float32; the
softmax, the scan and its state are float32. The recurrent state is laid out
``(..., d_state, d_inner)`` and the convolution tail ``(..., d_conv - 1,
d_inner)``: the wide axis last, so that a TPU tile holds no padding. A Mamba-2
state is ``(..., heads, d_head, d_state)``, its 128-wide axis last for the same
reason, and its tail spans the convolved channels (x, B and C together).

Names inside the programs (``heat_tpu.utils.profiling.scope``) nest under the
scopes the trace reduction already knows: ``attn.qkv/ssm.in``,
``attn.core/ssm.scan``, ``attn.core/ssm.step``, ``attn.core/attn.window``,
``attn.core/attn.full``, ``attn.core/attn.cross``, ``attn.core/gmu``,
``attn.core/attn.gqa``, ``attn.core/attn.mla``.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..core.lane_kernels import lane_attention
from ..core.latent_kernels import latent_attention
from ..core.pallas_kernels import interpret_vma_hazard, pallas_enabled
from ..utils.profiling import scope
from .attention import local_attention

__all__ = ["layernorm", "rmsnorm", "mm", "diff_lambda", "diff_attention",
           "diff_attention_lanes", "lanes_in_kernel", "diff_heads",
           "diff_finish", "lanes",
           "window_mask", "window_attention_prompt", "ring_rows",
           "mamba_in", "mamba_prompt", "mamba_step", "gmu",
           "mamba2_in", "mamba2_prompt", "mamba2_step", "gqa_lanes",
           "mla_prompt", "mla_step"]

F32 = jnp.float32


def layernorm(x, scale, bias, eps):
    xf = x.astype(F32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(xf - mu), axis=-1, keepdims=True)
    return ((xf - mu) * lax.rsqrt(var + eps) * scale + bias).astype(x.dtype)


def rmsnorm(x, scale, eps):
    xf = x.astype(F32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    return (xf * lax.rsqrt(var + eps) * scale).astype(x.dtype)


def mm(a, w):
    """``a @ w`` with ``a``'s dtype on both sides, accumulated in float32 and
    handed back in ``a``'s dtype."""
    return jnp.dot(a, w.astype(a.dtype),
                   preferred_element_type=F32).astype(a.dtype)


# ---------------------------------------------------------------------- #
# differential attention                                                 #
# ---------------------------------------------------------------------- #
def diff_lambda(lam, layer):
    """(lambda, lambda_init) of layer ``layer`` (an int, or a scanned
    segment's traced index) from its four learned vectors ``lam``
    (4, d_head): q1, k1, q2, k2."""
    lam = lam.astype(F32)
    init = 0.8 - 0.6 * jnp.exp(-0.3 * jnp.asarray(layer, F32))
    full = (jnp.exp(jnp.sum(lam[0] * lam[1]))
            - jnp.exp(jnp.sum(lam[2] * lam[3])) + init)
    return full, init


def lanes(x):
    """Keys or values (B, S, Hkv, d) as a cache lane holds them: (B, S,
    Hkv d), a position a row, so that a token's write is one contiguous row
    and the step's two products read the lane as a plain matrix."""
    return x.reshape(x.shape[0], x.shape[1], -1)


def diff_attention(q, k, v, mask):
    """The two softmax maps of every head pair, each applied to the pair's
    joined values. ``q`` (B, Sq, H, d); ``k``, ``v`` (B, Sk, Hkv, d) with
    H = 2 Hkv; ``mask`` broadcastable to (B, 1, 1, 1, Sq, Sk), True where a
    query may look. Query head h = 4g + 2j + c reads key head 2g + c and the
    values of heads 2g and 2g + 1 side by side. Returns (B, Sq, H, 2d)
    float32."""
    B, Sq, H, d = q.shape
    Sk, G = k.shape[1], k.shape[2] // 2
    qg = q.reshape(B, Sq, G, 2, 2, d)
    kg = k.reshape(B, Sk, G, 2, d)
    vg = v.reshape(B, Sk, G, 2 * d)
    s = jnp.einsum("bqgjcd,bkgcd->bgjcqk", qg, kg,
                   preferred_element_type=F32) / math.sqrt(d)
    s = jnp.where(mask, s, -jnp.inf)
    w = jax.nn.softmax(s, axis=-1)
    a = jnp.einsum("bgjcqk,bkge->bqgjce", w.astype(v.dtype), vg,
                   preferred_element_type=F32)
    return a.reshape(B, Sq, H, 2 * d)


def diff_attention_lanes(q, kl, vl, seen):
    """:func:`diff_attention` for the step: ``q`` (B, 1, H, d) against cache
    lanes ``kl``, ``vl`` (B, Sk, Hkv d) of which row r counts iff r <
    ``seen`` (B,). Where the kernels run, :func:`lane_attention` reads each
    slot's lanes only up to ``seen``. Else the lanes are read AS THEY LIE,
    whole, as two plain matrix products a slot: every head's query sits in a
    (Hkv d, H) matrix that is zero outside its own key head's rows, and of
    the (H, Hkv d) product with the values each head keeps its own pair's 2d
    columns. That multiplies zeros (Hkv times the needed FLOPs, still far
    under the time the lane's bytes take) and in exchange nothing is
    transposed or copied: a product batched over heads would make XLA re-lay
    the whole lane every step."""
    B, _one, H, d = q.shape
    Sk, Hkv = kl.shape[1], kl.shape[2] // d
    h = np.arange(H)
    k_of = 2 * (h // 4) + h % 2
    if lanes_in_kernel(q, kl, vl):
        return lane_attention(
            q[:, 0], kl, vl, seen, k_of=tuple(k_of.tolist()),
            v_of=tuple((h // 4).tolist()), scale=1.0 / math.sqrt(d))[:, None]
    own = k_of[None, :] == np.arange(Hkv)[:, None]
    qm = jnp.where(own[None, :, None, :],
                   jnp.swapaxes(q[:, 0], 1, 2)[:, None, :, :], 0)
    s = jnp.einsum("bkx,bxh->bhk", kl, qm.reshape(B, Hkv * d, H),
                   preferred_element_type=F32) / math.sqrt(d)
    s = jnp.where(jnp.arange(Sk)[None, None, :] < seen[:, None, None], s,
                  -jnp.inf)
    w = jax.nn.softmax(s, axis=-1)
    full = jnp.einsum("bhk,bkx->bhx", w.astype(vl.dtype), vl,
                      preferred_element_type=F32)
    full = full.reshape(B, Hkv // 2, 4, Hkv // 2, 2 * d)
    a = jnp.stack([full[:, g, :, g] for g in range(Hkv // 2)], axis=1)
    return a.reshape(B, 1, H, 2 * d)


def lanes_in_kernel(q, kl, vl) -> bool:
    """Whether a step's attention over cache lanes runs as
    :func:`lane_attention`: where the kernels run
    (:func:`~heat_tpu.core.pallas_kernels.pallas_enabled`), but for operands
    the interpreter cannot take (``interpret_vma_hazard``)."""
    return pallas_enabled() and not interpret_vma_hazard(q, kl, vl)


def diff_heads(k, v, n_heads: int):
    """Keys and values laid out one per (query head, value half), so that a
    plain multi-head kernel computes what :func:`diff_attention` does:
    ``k``, ``v`` (B, S, Hkv, d) -> (B, S, 2H, d) each."""
    h = jnp.arange(n_heads)
    k_of = 2 * (h // 4) + h % 2
    v_of = (2 * (h // 4))[:, None] + jnp.arange(2)[None, :]
    return (jnp.repeat(jnp.take(k, k_of, axis=2), 2, axis=2),
            jnp.take(v, v_of.reshape(-1), axis=2))


def diff_finish(a, lam, subln, layer, eps: float, dtype):
    """``a`` (B, S, H, 2d) float32, the maps' outputs -> (B, S, H d): per pair
    of heads a1 - lambda a2, RMS-normed over its 2d values with scale
    ``subln``, times (1 - lambda_init), the pairs side by side."""
    B, S, H, e = a.shape
    full, init = diff_lambda(lam, layer)
    a = a.reshape(B, S, H // 2, 2, e)
    o = a[..., 0, :] - full * a[..., 1, :]
    o = o * lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + eps)
    o = o * subln.astype(F32) * (1.0 - init)
    return o.reshape(B, S, (H // 2) * e).astype(dtype)


def window_mask(n_q: int, n_k: int, window: int, offset: int = 0):
    """(n_q, n_k) bool: query i (at position offset + i) sees key j (at
    position j) iff i + offset - window < j <= i + offset."""
    t = jnp.arange(n_q)[:, None] + offset
    s = jnp.arange(n_k)[None, :]
    return (s <= t) & (s > t - window)


def window_attention_prompt(q, k, v, window: int):
    """Differential attention over a prompt where position t sees
    t - window < s <= t. Buckets longer than the window go block by block
    (a block of ``window`` queries against its own and the previous block's
    keys), so the scores are S x 2 window and not S x S."""
    B, S, H, d = q.shape
    if S <= window or S % window:
        return diff_attention(q, k, v, window_mask(S, S, window))
    nb = S // window
    qb = q.reshape(B * nb, window, H, d)

    def with_prev(x):
        xb = x.reshape(B, nb, window, *x.shape[2:])
        prev = jnp.concatenate([jnp.zeros_like(xb[:, :1]), xb[:, :-1]], axis=1)
        return jnp.concatenate([prev, xb], axis=2).reshape(
            B * nb, 2 * window, *x.shape[2:])

    # keys of a block pair sit at offsets 0..2W-1, its queries at W..2W-1; the
    # first block's "previous" keys are zeros its mask must hide
    mask = window_mask(window, 2 * window, window, offset=window)
    first = jnp.arange(2 * window)[None, :] >= window
    blk = jnp.arange(B * nb) % nb
    mask = jnp.where((blk == 0)[:, None, None], mask & first, mask)
    a = diff_attention(qb, with_prev(k), with_prev(v),
                       mask[:, None, None, None])
    return a.reshape(B, S, H, 2 * d)


def ring_rows(x, n_valid, window: int):
    """What a ring of ``window`` rows keeps of a prompt's ``x`` (B, S, ...):
    row r holds the LAST position p < n_valid with p mod window == r. Rows no
    valid position maps to (r >= n_valid) hold whatever is there; the step
    masks them until it overwrites them."""
    r = jnp.arange(window)
    p = r + window * ((n_valid - 1 - r) // window)
    return jnp.take(x, jnp.clip(p, 0, x.shape[1] - 1), axis=1)


# ---------------------------------------------------------------------- #
# Mamba-1                                                                #
# ---------------------------------------------------------------------- #
def mamba_in(p, u, tail, d_state: int):
    """Everything before the recurrence, for ``u`` (B, S, D) and the
    ``tail`` (B, K-1, d_inner) of inputs that came before it: the input
    projection, the causal depthwise convolution, and the step size, B and C
    the input selects. Returns (x pre-convolution, x, z, delta, B, C)."""
    with scope("attn.qkv"), scope("ssm.in"):
        di = p["w_out"].shape[0]
        xz = mm(u, p["w_in"])
        x_in, z = xz[..., :di], xz[..., di:]
        K = p["conv_w"].shape[0]
        S = u.shape[1]
        seq = jnp.concatenate([tail.astype(x_in.dtype), x_in], axis=1)
        conv, filt = p["conv_b"].astype(F32), p["conv_w"].astype(F32)
        for j in range(K):
            conv = conv + seq[:, j:j + S].astype(F32) * filt[j]
        x = jax.nn.silu(conv).astype(u.dtype)
        dbc = mm(x, p["w_x"])
        R = dbc.shape[-1] - 2 * d_state
        delta = jax.nn.softplus(
            jnp.dot(dbc[..., :R], p["w_dt"].astype(dbc.dtype),
                    preferred_element_type=F32) + p["b_dt"].astype(F32))
        Bm = dbc[..., R:R + d_state].astype(F32)
        Cm = dbc[..., R + d_state:].astype(F32)
        return x_in, x, z, delta, Bm, Cm


def _ssm_update(s, A, dt, dx, b, c):
    """One position: ``s`` (B, N, d) <- exp(dt A) s + dx (x) b; y = s . c."""
    s = jnp.exp(dt[:, None, :] * A[None]) * s + dx[:, None, :] * b[:, :, None]
    return s, jnp.sum(s * c[:, :, None], axis=1)


def _mamba_out(p, y, x, z):
    """y + D x, gated by silu(z), projected out. Also returns y + D x itself
    (float32): the memory a gated memory unit reads."""
    y = y + p["D_skip"].astype(F32) * x.astype(F32)
    with scope("attn.proj"):
        out = mm((y * jax.nn.silu(z.astype(F32))).astype(x.dtype), p["w_out"])
    return out, y


def mamba_prompt(p, u, n_valid, d_state: int):
    """The layer over a padded prompt ``u`` (B, S, D), from a zero state.
    The scan STOPS at ``n_valid``: a pad position has step size 0, so it
    leaves the state as it is. Returns (out (B, S, D), memory y (B, S,
    d_inner) float32, state after the last valid position (B, N, d_inner)
    float32, the last K-1 valid inputs of the convolution (B, K-1,
    d_inner))."""
    B, S, _ = u.shape
    K = p["conv_w"].shape[0]
    di = p["w_out"].shape[0]
    zero_tail = jnp.zeros((B, K - 1, di), u.dtype)
    x_in, x, z, delta, Bm, Cm = mamba_in(p, u, zero_tail, d_state)
    with scope("attn.core"), scope("ssm.scan"):
        A = -jnp.exp(p["A_log"].astype(F32))                   # (N, d_inner)
        live = (jnp.arange(S) < n_valid)[None, :, None]
        dt = jnp.where(live, delta, 0.0)
        dx = dt * x.astype(F32)

        def body(s, inp):
            return _ssm_update(s, A, *inp)

        s0 = jnp.zeros((B, d_state, di), F32)
        s_end, y = lax.scan(
            body, s0, tuple(jnp.moveaxis(a, 1, 0) for a in (dt, dx, Bm, Cm)),
            unroll=8)
        y = jnp.moveaxis(y, 0, 1)
        padded = jnp.concatenate([zero_tail, x_in], axis=1)
        tail = lax.dynamic_slice_in_dim(padded, n_valid, K - 1, axis=1)
    out, mem = _mamba_out(p, y, x, z)
    return out, mem, s_end, tail


def mamba_step(p, u, s, tail, d_state: int):
    """One token ``u`` (B, 1, D) against the state ``s`` (B, N, d_inner) and
    the convolution's ``tail`` (B, K-1, d_inner). Returns (out (B, 1, D),
    memory (B, 1, d_inner), new state, new tail)."""
    x_in, x, z, delta, Bm, Cm = mamba_in(p, u, tail, d_state)
    with scope("attn.core"), scope("ssm.step"):
        A = -jnp.exp(p["A_log"].astype(F32))
        dt = delta[:, 0]
        s, y = _ssm_update(s, A, dt, dt * x[:, 0].astype(F32), Bm[:, 0],
                           Cm[:, 0])
        new_tail = jnp.concatenate(
            [tail[:, 1:], x_in.astype(tail.dtype)], axis=1)
    out, mem = _mamba_out(p, y[:, None], x, z)
    return out, mem, s, new_tail


def gmu(p, u, memory):
    """Gated memory unit: (memory * silu(u W1)) W2, ``memory`` the state-space
    output of the same positions."""
    with scope("attn.core"), scope("gmu"):
        gate = jax.nn.silu(jnp.dot(u, p["w1"].astype(u.dtype),
                                   preferred_element_type=F32))
        h = (memory.astype(F32) * gate).astype(u.dtype)
    with scope("attn.proj"):
        return mm(h, p["w2"])


# ---------------------------------------------------------------------- #
# Mamba-2                                                                #
# ---------------------------------------------------------------------- #
def mamba2_in(p, u, tail, d_state: int):
    """Everything before the recurrence, for ``u`` (B, S, D) and the ``tail``
    (B, K-1, d_inner + 2 d_state) of convolution inputs that came before it:
    ``[z, xBC, dt] = u W_in``, the causal depthwise filter over x, B and C
    TOGETHER, and the step size a head. One group: B and C (d_state wide) are
    shared by all heads. Returns (xBC pre-convolution, x (B, S, H, P), z,
    delta (B, S, H) float32, B, C (B, S, d_state) float32)."""
    with scope("attn.qkv"), scope("ssm.in"):
        di = p["w_out"].shape[0]
        H = p["A_log"].shape[0]
        zxd = jnp.dot(u, p["w_in"].astype(u.dtype), preferred_element_type=F32)
        z = zxd[..., :di].astype(u.dtype)
        xbc_in = zxd[..., di:di + di + 2 * d_state].astype(u.dtype)
        delta = jax.nn.softplus(zxd[..., 2 * di + 2 * d_state:]
                                + p["dt_bias"].astype(F32))
        K = p["conv_w"].shape[0]
        S = u.shape[1]
        seq = jnp.concatenate([tail.astype(xbc_in.dtype), xbc_in], axis=1)
        conv, filt = p["conv_b"].astype(F32), p["conv_w"].astype(F32)
        for j in range(K):
            conv = conv + seq[:, j:j + S].astype(F32) * filt[j]
        xbc = jax.nn.silu(conv)
        x = xbc[..., :di].astype(u.dtype).reshape(*u.shape[:2], H, di // H)
        return (xbc_in, x, z, delta, xbc[..., di:di + d_state],
                xbc[..., di + d_state:])


def _mamba2_out(p, y, x, z, eps):
    """y + D x a head, gated by silu(z), RMS-normed over the whole inner
    width (one group), projected out."""
    y = y + p["D_skip"].astype(F32)[:, None] * x.astype(F32)
    y = y.reshape(*y.shape[:-2], -1) * jax.nn.silu(z.astype(F32))
    with scope("attn.proj"):
        return mm(rmsnorm(y, p["gnorm"].astype(F32), eps).astype(x.dtype),
                  p["w_out"])


def mamba2_prompt(p, u, n_valid, d_state: int, chunk: int, eps: float):
    """The layer over a padded prompt ``u`` (B, S, D), from a zero state, in
    the CHUNKED (state-space-dual) form: inside a chunk of ``chunk``
    positions the decay-masked ``C B^T`` product, between chunks the carried
    state (a scan over S / chunk states, not over S positions). A pad
    position (>= ``n_valid``) has step size 0: it decays nothing and adds
    nothing, so the state after the last chunk is the state after the last
    valid position. The recurrence's products and the state are float32.
    Returns (out (B, S, D), state (B, H, P, d_state) float32, the last K-1
    valid inputs of the convolution (B, K-1, d_inner + 2 d_state))."""
    B, S, _ = u.shape
    K, width = p["conv_w"].shape
    zero_tail = jnp.zeros((B, K - 1, width), u.dtype)
    xbc_in, x, z, delta, Bm, Cm = mamba2_in(p, u, zero_tail, d_state)
    H, P = x.shape[2:]
    with scope("attn.core"), scope("ssm.scan"):
        a = -jnp.exp(p["A_log"].astype(F32))                        # (H,)
        live = (jnp.arange(S) < n_valid)[None, :, None]
        dt = jnp.where(live, delta, 0.0)
        Q = min(chunk, S)
        pad = -S % Q
        nc = (S + pad) // Q

        def chunks(t):          # (B, S, ...) -> (B, nc, Q, ...), pad rows zero
            t = jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
            return t.reshape(B, nc, Q, *t.shape[2:])

        dx = chunks(dt[..., None] * x.astype(F32))               # (B,nc,Q,H,P)
        Bc, Cc = chunks(Bm), chunks(Cm)                          # (B,nc,Q,N)
        cum = jnp.cumsum(jnp.moveaxis(chunks(dt * a), 2, 3), axis=-1)
        # inside a chunk: y_i += sum_{j<=i} exp(cum_i - cum_j) (C_i.B_j) dx_j
        seen = jnp.arange(Q)[:, None] >= jnp.arange(Q)[None, :]
        decay = jnp.exp(jnp.where(seen, cum[..., :, None] - cum[..., None, :],
                                  -jnp.inf))                     # (B,nc,H,Q,Q)
        cb = jnp.einsum("bcin,bcjn->bcij", Cc, Bc)
        y = jnp.einsum("bchij,bcjhp->bcihp", cb[:, :, None] * decay, dx)
        # what a chunk adds to the state, decayed to the chunk's end
        to_end = jnp.exp(cum[..., -1:] - cum)                    # (B,nc,H,Q)
        added = jnp.einsum("bcjhp,bcjn->bchpn",
                           dx * jnp.moveaxis(to_end, 2, 3)[..., None], Bc)
        whole = jnp.exp(cum[..., -1])                            # (B,nc,H)

        def carry_on(s, inp):
            keep, add = inp
            return keep[..., None, None] * s + add, s

        s_end, before = lax.scan(
            carry_on, jnp.zeros((B, H, P, d_state), F32),
            (jnp.moveaxis(whole, 1, 0), jnp.moveaxis(added, 1, 0)))
        # what the state a chunk starts from adds inside it
        y = y + jnp.einsum("bcin,cbhpn->bcihp", Cc, before) \
            * jnp.moveaxis(jnp.exp(cum), 2, 3)[..., None]
        y = y.reshape(B, S + pad, H, P)[:, :S]
        padded = jnp.concatenate([zero_tail, xbc_in], axis=1)
        tail = lax.dynamic_slice_in_dim(padded, n_valid, K - 1, axis=1)
    return _mamba2_out(p, y, x, z, eps), s_end, tail


def mamba2_step(p, u, s, tail, d_state: int, eps: float):
    """One token ``u`` (B, 1, D) against the state ``s`` (B, H, P, d_state)
    and the convolution's ``tail``. Returns (out (B, 1, D), new state, new
    tail)."""
    xbc_in, x, z, delta, Bm, Cm = mamba2_in(p, u, tail, d_state)
    with scope("attn.core"), scope("ssm.step"):
        a = -jnp.exp(p["A_log"].astype(F32))
        dt = delta[:, 0]                                         # (B, H)
        dx = dt[..., None] * x[:, 0].astype(F32)                 # (B, H, P)
        s = (jnp.exp(dt * a)[..., None, None] * s
             + dx[..., None] * Bm[:, 0, None, None, :])
        y = jnp.sum(s * Cm[:, 0, None, None, :], axis=-1)
        new_tail = jnp.concatenate(
            [tail[:, 1:], xbc_in.astype(tail.dtype)], axis=1)
    return _mamba2_out(p, y[:, None], x, z, eps), s, new_tail


# ---------------------------------------------------------------------- #
# plain grouped-query attention                                          #
# ---------------------------------------------------------------------- #
def gqa_lanes(q, kl, vl, seen, scale: float):
    """One query row a slot, ``q`` (B, 1, H, d), against cache lanes ``kl``,
    ``vl`` (B, Sk, Hkv d) of which row r counts iff r < ``seen`` (B,); query
    head h reads key/value head h // (H / Hkv); scores times ``scale``. As
    :func:`diff_attention_lanes`: where the kernels run, :func:`lane_attention`
    reads each slot's lanes up to ``seen``; else the lanes are read AS THEY
    LIE, whole, as two plain matrix products a slot (the queries in a (Hkv
    d, H) matrix that is zero outside each head's own key head's rows; of the
    (H, Hkv d) product with the values each head keeps its own d columns):
    Hkv times the needed FLOPs, nothing transposed or copied. Returns (B, 1,
    H, d) float32."""
    B, _one, H, d = q.shape
    Sk, Hkv = kl.shape[1], kl.shape[2] // d
    head_of = tuple((np.arange(H) // (H // Hkv)).tolist())
    if lanes_in_kernel(q, kl, vl):
        return lane_attention(q[:, 0], kl, vl, seen, k_of=head_of,
                              v_of=head_of, scale=scale)[:, None]
    own = np.array(head_of)[None, :] == np.arange(Hkv)[:, None]
    qm = jnp.where(own[None, :, None, :],
                   jnp.swapaxes(q[:, 0], 1, 2)[:, None, :, :], 0)
    s = jnp.einsum("bkx,bxh->bhk", kl, qm.reshape(B, Hkv * d, H),
                   preferred_element_type=F32) * scale
    s = jnp.where(jnp.arange(Sk)[None, None, :] < seen[:, None, None], s,
                  -jnp.inf)
    w = jax.nn.softmax(s, axis=-1)
    full = jnp.einsum("bhk,bkx->bhx", w.astype(vl.dtype), vl,
                      preferred_element_type=F32)
    full = full.reshape(B, Hkv, H // Hkv, Hkv, d)
    a = jnp.stack([full[:, g, :, g] for g in range(Hkv)], axis=1)
    return a.reshape(B, 1, H, d)


# ---------------------------------------------------------------------- #
# multi-head latent attention                                            #
# ---------------------------------------------------------------------- #
def mla_prompt(p, q_nope, q_rope, row, scale: float):
    """Causal latent attention over a prompt in the DECOMPRESSED form.
    ``q_nope`` (B, S, H, dn) and ``q_rope`` (B, S, H, dr, turned) the
    queries; ``row`` (B, S, W) what the latent lane keeps of each position,
    [c_kv | k_r | 1 | zeros] (k_r turned). A head's key is [c_kv W_UK,h | k_r]
    (k_r shared by every head) and its value c_kv W_UV,h; ``p["w_uk"]`` (H,
    dn, C), ``p["w_uv"]`` (H, C, dv). One causal flash attention over every
    head, the values padded with zeros to the queries' dn + dr: no (H, S, S)
    map is made. Returns (B, S, H, dv)."""
    H, dn, C = p["w_uk"].shape
    dv, dr = p["w_uv"].shape[-1], q_rope.shape[-1]
    B, S, _ = row.shape
    dt = row.dtype
    with scope("attn.qkv"):
        c = row[..., :C]
        k_nope = jnp.einsum("bsc,hnc->bshn", c, p["w_uk"].astype(dt),
                            preferred_element_type=F32).astype(dt)
        v = jnp.einsum("bsc,hcv->bshv", c, p["w_uv"].astype(dt),
                       preferred_element_type=F32).astype(dt)
        k_r = jnp.broadcast_to(row[:, :, None, C:C + dr], (B, S, H, dr))
        q = jnp.concatenate([q_nope, q_rope.astype(dt)], axis=-1)
        k = jnp.concatenate([k_nope, k_r], axis=-1)
        width = max(q.shape[-1], dv)
        q, k, v = (jnp.pad(t, ((0, 0),) * 3 + ((0, width - t.shape[-1]),))
                   for t in (q, k, v))
    with scope("attn.core"), scope("attn.mla"):
        a = local_attention(jnp.moveaxis(q, 2, 1), jnp.moveaxis(k, 2, 1),
                            jnp.moveaxis(v, 2, 1), scale=scale, causal=True)
    return jnp.moveaxis(a, 1, 2)[..., :dv]


def mla_step(p, q_nope, q_rope, lane, seen, scale: float):
    """One query row a slot against the latent lane ``lane`` (B, Sk, W) of
    [c_kv | k_r | 1 | zeros] rows, of which row r counts iff r < ``seen``
    (B,),
    in the ABSORBED form: a
    head's query in latent space q_lat,h = q_nope,h W_UK,h^T (C wide), its
    scores [q_lat,h | q_rope,h] . [c_kv | k_r] a row, its output (sum p
    c_kv) W_UV,h. Every head reads the same row of a position (the query is
    padded with zeros to W). Where the kernels run, the core is
    :func:`~heat_tpu.core.latent_kernels.latent_attention`: each slot's lane
    read once, only up to ``seen``, for both products. Else it is two plain
    matrix products a slot, (Sk, W) x (W, H) and back, that read the whole
    lane where it lies (the second takes the rope and pad columns too: the
    one that holds 1 gives the softmax's sum). ``q_nope`` (B, 1, H, dn),
    ``q_rope`` (B, 1, H, dr). Returns (B, 1, H, dv)."""
    C = p["w_uk"].shape[-1]
    B, Sk, W = lane.shape
    H, dr, dt = q_rope.shape[2], q_rope.shape[-1], lane.dtype
    # the products a head are batched over the heads, which lead: the
    # weights' own layout (and XLA:CPU has no bfloat16 product batched over
    # an inner axis)
    with scope("attn.qkv"):
        q_lat = jnp.einsum("hbn,hnc->bhc", jnp.swapaxes(q_nope[:, 0], 0, 1)
                           .astype(dt), p["w_uk"].astype(dt),
                           preferred_element_type=F32)
        q = jnp.concatenate([q_lat.astype(dt), q_rope[:, 0].astype(dt),
                             jnp.zeros((B, H, W - C - dr), dt)],
                            axis=-1)                            # (B, H, W)
    with scope("attn.core"), scope("attn.mla"):
        if lanes_in_kernel(q, lane, lane):
            o = latent_attention(q, lane, seen, C=C, scale=scale)
        else:
            s = jnp.einsum("bkx,bxh->bhk", lane, jnp.swapaxes(q, 1, 2),
                           preferred_element_type=F32) * scale
            s = jnp.where(
                jnp.arange(Sk)[None, None, :] < seen[:, None, None], s,
                -jnp.inf)
            # the softmax's weights go to the second product UNNORMALISED,
            # in the lane's dtype; a row's column C + dr holds 1, so the
            # product's column there is their sum: no pass over the (B, H,
            # Sk) map of its own to normalise it or to sum it
            e = jnp.exp(s - jnp.max(s, axis=-1, keepdims=True))
            o = jnp.einsum("bhk,bkx->bhx", e.astype(dt), lane,
                           preferred_element_type=F32)
            o = o[..., :C] / o[..., C + dr:C + dr + 1]
    with scope("attn.proj"):
        o = jnp.einsum("hbc,hcv->bhv", jnp.swapaxes(o, 0, 1).astype(dt),
                       p["w_uv"].astype(dt), preferred_element_type=F32)
    return o[:, None].astype(q_nope.dtype)
