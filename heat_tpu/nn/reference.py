"""Plain float32 reference forward for :class:`TransformerLM` — a TEST
ORACLE, not a feature.

Embedding → [RMSNorm → fused-QKV MHA with half-split RoPE, causal →
residual → RMSNorm → tanh-GELU MLP → residual] × L → RMSNorm → unembed,
written against ``jax.numpy`` only: no KV cache, no kernels, no mesh, no
bucket padding, every contraction in float32 at
``jax.default_matmul_precision("highest")``. It shares no code with
``heat_tpu.nn.transformer`` beyond the parameter-tree layout, so the
train loss, the prefill logits and the decode engine's token choices can
all be judged against it (``chip_smoke.py``, ``tests/test_reference.py``).

Dense-MLP models only (the decode grid's own restriction).

:func:`pattern_logits` is the same kind of oracle for a model with a
per-layer PATTERN (``TransformerLMConfig.pattern``: Mamba-1, window, full
and cross differential attention, gated memory units, Mamba-2, plain
grouped-query attention; pre-norm LayerNorm or RMSNorm; a gated SiLU MLP or
routed experts beside a shared one; the embedding, residual, attention and
logit multipliers; no positional encoding, the head tied to the embedding):
one full forward over a whole sequence, a ``lax.scan`` over positions for the
state-space layers, every head pair written out, the held experts one after
another under a mask. It shares nothing with ``heat_tpu.nn.mixers`` or
``heat_tpu.nn.parallel`` but the parameter tree. :func:`pattern_routing` is
the experts each position chose, layer by layer.
"""

from __future__ import annotations

import math

import numpy as np

import jax
import jax.numpy as jnp

__all__ = ["host_params", "reference_logits", "reference_loss",
           "greedy_gaps", "prefill_logits", "pattern_logits",
           "pattern_routing"]


def host_params(params):
    """The model's parameter tree as host float32 arrays with the
    ``(pp, Ls, ...)`` stage axes flattened to one layer axis (a pattern
    model's ``segments``, stacked by repeat, come apart into ``layers``, a
    list with one dict a layer; a tree that has ``layers`` stays)."""
    host = jax.tree.map(lambda a: np.asarray(a, np.float32), params)
    if "layers" in host:
        return host
    if "segments" in host:
        host["layers"] = [
            {name: a[i] for name, a in place.items()}
            for run in host.pop("segments")
            for i in range(len(next(iter(run[0].values()))))
            for place in run]
        return host
    host["stages"] = {k: v.reshape((-1,) + v.shape[2:])
                      for k, v in host["stages"].items()}
    if "router" in host["stages"]:
        raise NotImplementedError("the reference covers the dense MLP only")
    return host


def _rms(x, scale):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + 1e-6) * scale


def _rope(x, theta):
    # x: (B, S, H, Dh); half-split rotation by absolute position
    S, half = x.shape[1], x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * freq[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _gelu(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x * x * x)))


def _forward(hp, toks, rope, theta):
    x = hp["embed"][toks]                                    # (B, S, D)
    S = toks.shape[1]
    causal = jnp.tril(jnp.ones((S, S), bool))
    st = hp["stages"]
    for l in range(st["wqkv"].shape[0]):
        a = _rms(x, st["ln1"][l])
        qkv = jnp.einsum("bsd,dohk->obshk", a, st["wqkv"][l])
        q, k, v = qkv[0], qkv[1], qkv[2]
        if rope:
            q, k = _rope(q, theta), _rope(k, theta)
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(q.shape[-1])
        w = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        o = jnp.einsum("bhqk,bkhd->bqhd", w, v)
        x = x + jnp.einsum("bqhd,hdm->bqm", o, st["wproj"][l])
        m = _rms(x, st["ln2"][l])
        x = x + _gelu(m @ st["w_up"][l]) @ st["w_down"][l]
    return _rms(x, hp["final_ln"]) @ hp["unembed"]


_forward_jit = jax.jit(_forward, static_argnums=(2, 3))


def reference_logits(hp, toks, cfg):
    """``(B, S)`` int tokens → ``(B, S, vocab)`` float32 logits.
    ``hp`` is :func:`host_params` of the model's tree (or the same tree
    already on a device)."""
    toks = jnp.asarray(toks, jnp.int32)
    with jax.default_matmul_precision("highest"):
        return _forward_jit(hp, toks, bool(cfg.rope), float(cfg.rope_theta))


def reference_loss(hp, toks, cfg):
    """Mean next-token NLL over ``B * (S - 1)`` positions — the quantity
    ``TransformerLM``'s train step reports."""
    logits = reference_logits(hp, toks, cfg)
    logp = jax.nn.log_softmax(logits[:, :-1], axis=-1)
    tgt = jnp.asarray(toks, jnp.int32)[:, 1:]
    return float(-jnp.mean(jnp.take_along_axis(logp, tgt[..., None], -1)))


def greedy_gaps(logits, seq, s0):
    """Teacher-forced judgement of a greedy continuation. ``seq`` is the
    prompt (``s0`` tokens) followed by the generated tokens and ``logits``
    the reference's ``(>= len(seq), vocab)`` logits of that sequence (the
    reference is causal, so a right-padded batch row serves). For every
    generated position returns ``max(reference logits) - reference logit
    of the token chosen``: 0 where the reference's argmax agrees, and at
    most the two programs' rounding where an argmax flipped between
    near-ties."""
    seq = np.asarray(seq, np.int32)
    rows = np.asarray(logits)[s0 - 1:len(seq) - 1]
    chosen = rows[np.arange(len(rows)), seq[s0:]]
    return rows.max(axis=-1) - chosen


def prefill_logits(model, params, prompt):
    """The code UNDER TEST, exposed for judging: the last-position logits
    the decode engine's prefill program computes for ``prompt`` — the same
    ``prefill`` body over the same power-of-two prompt bucket
    (pad rows included), on the model's own grid and compute dtype. The
    engine itself keeps only the argmax of these."""
    from jax.sharding import PartitionSpec as P

    from ..core._compat import shard_map

    prompt = np.asarray(prompt, np.int32)
    padded = np.zeros(model.serving_bucket(len(prompt)), np.int32)
    padded[:len(prompt)] = prompt

    key = ("reference.prefill_logits", len(padded))
    fn = model._step_cache.get(key)  # one compile per prompt bucket
    if fn is None:
        def body(params, toks, n_valid):
            return model.prefill(params, toks[None], n_valid)[1][0]

        fn = model._step_cache[key] = jax.jit(shard_map(
            body, mesh=model.grid.mesh,
            in_specs=(model.param_specs(), P(), P()), out_specs=P(),
            check_vma=False))
    return np.asarray(fn(params, jnp.asarray(padded),
                         jnp.int32(len(prompt))))


# ---------------------------------------------------------------------- #
# a per-layer pattern (serving only)                                     #
# ---------------------------------------------------------------------- #
F8, F8_MAX = jnp.float8_e4m3fn, 448.0


def _mul(a, b, fp8):
    """``a @ b``; ``fp8`` rounds both operands to float8 e4m3 (per-tensor
    scale) first: the control, the nearest precision below bfloat16."""
    if fp8:
        def q8(x):
            s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / F8_MAX
            return (x / s).astype(F8).astype(jnp.float32) * s
        a, b = q8(a), q8(b)
    return a @ b


def _ln(x, g, b, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * g + b


def _silu(x):
    return x / (1.0 + jnp.exp(-x))


def _mamba(p, u, cfg, fp8):
    """Mamba-1 over ``u`` (S, D); returns (out, y before the gate)."""
    di, N, K = cfg.d_inner, cfg.d_state, cfg.d_conv
    S = u.shape[0]
    xz = _mul(u, p["w_in"], fp8)
    x, z = xz[:, :di], xz[:, di:]
    xp = jnp.concatenate([jnp.zeros((K - 1, di)), x])
    x = _silu(sum(xp[j:j + S] * p["conv_w"][j] for j in range(K))
              + p["conv_b"])
    dbc = _mul(x, p["w_x"], fp8)
    R = dbc.shape[1] - 2 * N
    delta = jax.nn.softplus(_mul(dbc[:, :R], p["w_dt"], fp8) + p["b_dt"])
    Bm, Cm = dbc[:, R:R + N], dbc[:, R + N:]
    A = -jnp.exp(p["A_log"].T)                               # (d_inner, N)

    def step(s, inp):
        d_t, x_t, b_t, c_t = inp
        s = jnp.exp(d_t[:, None] * A) * s + (d_t * x_t)[:, None] * b_t[None]
        return s, s @ c_t + p["D_skip"] * x_t

    _, y = jax.lax.scan(step, jnp.zeros((di, N)), (delta, x, Bm, Cm))
    return _mul(y * _silu(z), p["w_out"], fp8), y


def _diff_attention(p, q, k, v, mask, layer, cfg, fp8):
    """Differential attention of query heads ``q`` (S, H, d) over keys and
    values (S, Hkv, d), pair by pair; ``mask`` (S, S) True where seen."""
    d = q.shape[-1]
    lam_init = 0.8 - 0.6 * math.exp(-0.3 * layer)
    lq1, lk1, lq2, lk2 = p["lam"]
    lam = jnp.exp(lq1 @ lk1) - jnp.exp(lq2 @ lk2) + lam_init
    bias = jnp.where(mask, 0.0, -jnp.inf)
    pairs = []
    for j in range(q.shape[1] // 2):
        g = j // 2
        vbar = jnp.concatenate([v[:, 2 * g], v[:, 2 * g + 1]], axis=-1)
        a1 = _mul(jax.nn.softmax(_mul(q[:, 2 * j], k[:, 2 * g].T, fp8)
                                 / math.sqrt(d) + bias, axis=-1), vbar, fp8)
        a2 = _mul(jax.nn.softmax(_mul(q[:, 2 * j + 1], k[:, 2 * g + 1].T, fp8)
                                 / math.sqrt(d) + bias, axis=-1), vbar, fp8)
        o = a1 - lam * a2
        o = o / jnp.sqrt(jnp.mean(o * o, axis=-1, keepdims=True)
                         + cfg.norm_eps) * p["subln"]
        pairs.append(o * (1.0 - lam_init))
    return _mul(jnp.concatenate(pairs, axis=-1), p["wo"], fp8) + p["bo"]


def _mamba2(p, u, cfg, fp8):
    """Mamba-2 over ``u`` (S, D): one group, a scalar decay a head, the
    recurrence a position at a time."""
    di, N, K, Hs = cfg.d_inner, cfg.d_state, cfg.d_conv, cfg.ssm_heads
    S, P = u.shape[0], cfg.d_inner // cfg.ssm_heads
    zxd = _mul(u, p["w_in"], fp8)
    z, xbc, dt = zxd[:, :di], zxd[:, di:2 * di + 2 * N], zxd[:, 2 * di + 2 * N:]
    xp = jnp.concatenate([jnp.zeros((K - 1, di + 2 * N)), xbc])
    xbc = _silu(sum(xp[j:j + S] * p["conv_w"][j] for j in range(K))
                + p["conv_b"])
    x, Bm, Cm = xbc[:, :di].reshape(S, Hs, P), xbc[:, di:di + N], xbc[:, di + N:]
    delta = jax.nn.softplus(dt + p["dt_bias"])               # (S, Hs)
    a = -jnp.exp(p["A_log"])                                 # (Hs,)

    def step(s, inp):                                        # s (Hs, P, N)
        d_t, x_t, b_t, c_t = inp
        s = (jnp.exp(d_t * a)[:, None, None] * s
             + (d_t[:, None] * x_t)[:, :, None] * b_t[None, None, :])
        return s, s @ c_t + p["D_skip"][:, None] * x_t

    _, y = jax.lax.scan(step, jnp.zeros((Hs, P, N)), (delta, x, Bm, Cm))
    y = y.reshape(S, di) * _silu(z)
    y = y / jnp.sqrt(jnp.mean(y * y, axis=-1, keepdims=True)
                     + cfg.norm_eps) * p["gnorm"]
    return _mul(y, p["w_out"], fp8)


def _gqa(p, u, cfg, fp8):
    """Plain causal grouped-query attention over ``u`` (S, D), a head at a
    time; scores times ``attention_multiplier``."""
    S = u.shape[0]
    H, Hkv, dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    qkv = _mul(u, p["wqkv"], fp8).reshape(S, H + 2 * Hkv, dh)
    q, k, v = qkv[:, :H], qkv[:, H:H + Hkv], qkv[:, H + Hkv:]
    bias = jnp.where(jnp.arange(S)[None, :] <= jnp.arange(S)[:, None], 0.0,
                     -jnp.inf)
    heads = []
    for h in range(H):
        g = h // (H // Hkv)
        w = jax.nn.softmax(_mul(q[:, h], k[:, g].T, fp8)
                           * cfg.attention_multiplier + bias, axis=-1)
        heads.append(_mul(w, v[:, g], fp8))
    return _mul(jnp.concatenate(heads, axis=-1), p["wo"], fp8)


def _gated(u, w1, w2, fp8):
    gp = _mul(u, w1, fp8)
    F = gp.shape[1] // 2
    return _mul(_silu(gp[:, :F]) * gp[:, F:], w2, fp8)


def _experts(p, u, cfg, fp8):
    """The HELD experts' part of the routed layer plus the shared expert, on
    ``u`` (S, D): each position's k largest router logits, gates a softmax
    over those k; the held experts one after another, each over every
    position, kept where the position chose it. Returns (out, the experts
    chosen (S, k))."""
    first, count = cfg.experts_held
    top, chosen = jax.lax.top_k(_mul(u, p["router"], fp8),
                                cfg.experts_per_token)
    gates = jax.nn.softmax(top, axis=-1)
    out = jnp.zeros_like(u)
    for e in range(count):
        gate = jnp.sum(jnp.where(chosen == first + e, gates, 0.0), axis=-1)
        out = out + gate[:, None] * _gated(u, p["we1"][e], p["we2"][e], fp8)
    if cfg.d_shared:
        out = out + _gated(u, p["ws1"], p["ws2"], fp8)
    return out, chosen


def _pattern_forward(hp, toks, cfg, fp8):
    S = toks.shape[0]
    H, Hkv, dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    t, s_ = jnp.arange(S)[:, None], jnp.arange(S)[None, :]
    causal = s_ <= t

    def norm(x, p, name):
        if cfg.norm_kind == "rmsnorm":
            return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                                + cfg.norm_eps) * p[name]
        return _ln(x, p[name], p[name + "_b"], cfg.norm_eps)

    h = cfg.embedding_multiplier * hp["embed"][toks]
    memory = full_k = full_v = None
    routing = []
    for l, (kind, p) in enumerate(zip(cfg.pattern, hp["layers"])):
        u = norm(h, p, "ln1")
        if kind == "mamba":
            mixed, memory = _mamba(p, u, cfg, fp8)
        elif kind == "mamba2":
            mixed = _mamba2(p, u, cfg, fp8)
        elif kind == "gqa":
            mixed = _gqa(p, u, cfg, fp8)
        elif kind == "gmu":
            mixed = _mul(memory * _silu(_mul(u, p["w1"], fp8)), p["w2"], fp8)
        elif kind == "cross":
            q = _mul(u, p["wq"], fp8) + p["bq"]
            mixed = _diff_attention(p, q.reshape(S, H, dh), full_k, full_v,
                                    causal, l, cfg, fp8)
        else:
            qkv = (_mul(u, p["wqkv"], fp8) + p["bqkv"]).reshape(
                S, H + 2 * Hkv, dh)
            q, k, v = qkv[:, :H], qkv[:, H:H + Hkv], qkv[:, H + Hkv:]
            if kind == "full":
                full_k, full_v, mask = k, v, causal
            else:
                mask = causal & (s_ > t - cfg.window)
            mixed = _diff_attention(p, q, k, v, mask, l, cfg, fp8)
        h = h + cfg.residual_multiplier * mixed
        u = norm(h, p, "ln2")
        if cfg.ffn[l] == "moe":
            out, chosen = _experts(p, u, cfg, fp8)
            routing.append(chosen)
        else:
            out = _gated(u, p["w_gate_up"], p["w_down"], fp8)
        h = h + cfg.residual_multiplier * out
    logits = _mul(norm(h, hp, "final_ln"), hp["embed"].T, fp8)
    return logits / cfg.logits_scaling, routing


def pattern_logits(hp, toks, cfg, fp8=False):
    """``(S,)`` int tokens of ONE sequence -> ``(S, vocab)`` float32 logits
    of a pattern model; ``hp`` is :func:`host_params` of its tree.
    ``fp8=True`` is the control: every matrix product's operands rounded to
    float8. A model that holds a share of its experts (``experts_held``) is
    given the same share here: what the other experts would add is left
    out, and that partial result goes on to the next layer."""
    fn = jax.jit(lambda hp, toks: _pattern_forward(hp, toks, cfg, fp8)[0])
    with jax.default_matmul_precision("highest"):
        return fn(hp, jnp.asarray(toks, jnp.int32))


def pattern_routing(hp, toks, cfg):
    """The experts (of ALL ``n_experts``) that each position of ``toks``
    (S,) chose in each "moe" layer: (moe layers, S, experts_per_token) int."""
    fn = jax.jit(lambda hp, toks: _pattern_forward(hp, toks, cfg, False)[1])
    with jax.default_matmul_precision("highest"):
        return np.stack([np.asarray(c) for c in
                         fn(hp, jnp.asarray(toks, jnp.int32))])
