"""Plain float32 reference of the `phi-4-mini-flash` configuration, the weights
it is run on, and its control. The benchmark's OWN copy of the model's
equations (ISSUE 33, section 1); it imports nothing of the program.

The model (sources: the published config.json; arXiv:2507.06607, the
decoder-hybrid-decoder with its gated memory unit and no positional encoding;
arXiv:2312.00752, Mamba-1; arXiv:2410.05258, differential attention). Block l,
pre-norm and sequential: h += Mixer_l(LN(h)); h += MLP(LN(h)), LN = LayerNorm
with scale and bias, MLP(u) = (silu(g) * p) W_down with [g, p] = u W_gate_up;
logits = LN_f(h) E^T, E the embedding. Mixer by index, n the depth: l even and
<= n/2: Mamba-1; l odd and < n/2: window self-attention (t - W < s <= t);
l = n/2 + 1: full causal self-attention, whose K and V every later odd layer
reads (cross-attention: a query projection of its own, no K, V weights);
l even and > n/2 + 1: gated memory unit (m * silu(u W1)) W2, m the LAST Mamba
layer's y (before its gate). Every attention is differential: query heads
(2j, 2j+1) pair, pair j reads key heads (2g, 2g+1), g = j // 2, and the 2d-wide
[v_2g; v_2g+1]: o_j = RMSNorm_2d(a1 - lambda a2) (1 - lambda_init),
lambda = exp(lq1.lk1) - exp(lq2.lk2) + lambda_init,
lambda_init = 0.8 - 0.6 exp(-0.3 l).

Weights come from a key, a LAYER AT A TIME (`layer_weights`), so that float32
never holds more than one layer beside the embedding: matrices N(0,
initializer_range) rounded ONCE to the configuration's `param_dtype` (the
program is handed those; the reference the same values as float32); norm scales
1, biases 0; A_log = log(1..N) a channel, D_skip 1, b_dt the inverse softplus
of a log-uniform step in [1e-3, 1e-1], the depthwise filter uniform within
1/sqrt(K) (Mamba's own init: a normal A would not be stable); the lambda
vectors N(0, 0.1), the pair norm's scale 1. The tree is the program's
(`TransformerLM.pattern_param_shapes`): a state-space layer's `A_log` is laid
out (N, d_inner).

No cache, no kernels: one forward over prompt + output of all judged requests
together, a `lax.scan` over positions for the state-space layers, the head
pairs one after another, every product in float32 as `highest` computes it
on the chip (six bfloat16 passes, `_mul`). The control
rounds every product's operands to float8 e4m3 (per-tensor scale): the nearest
precision below the bfloat16 the configuration states.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax

F8, F8_MAX = jnp.float8_e4m3fn, 448.0
F32, BF16 = jnp.float32, jnp.bfloat16


# ---------------------------------------------------------------------- #
# the configuration's sizes                                              #
# ---------------------------------------------------------------------- #
def sizes(config: dict) -> dict:
    a = config["assumed_sizes"]
    D, H = config["hidden_size"], config["num_attention_heads"]
    return {"D": D, "H": H, "Hkv": config["num_key_value_heads"],
            "d": D // H, "F": config["intermediate_size"],
            "V": config["vocab_size"], "L": config["num_hidden_layers"],
            "W": config["sliding_window"], "di": a["d_inner"],
            "N": a["d_state"], "K": a["d_conv"], "R": a["dt_rank"],
            "eps": config["layer_norm_eps"],
            "scale": config["initializer_range"]}


def kinds(n_layers: int) -> tuple:
    """The mixer of each layer (mb_per_layer 2: Mamba on the even layers of
    the self-decoder, layers 0..n/2+1; the cross-decoder after it)."""
    half = n_layers // 2
    return tuple(("mamba" if l % 2 == 0 else "window") if l <= half
                 else "full" if l == half + 1
                 else ("gmu" if l % 2 == 0 else "cross")
                 for l in range(n_layers))


def layer_shapes(kind: str, z: dict) -> dict:
    """name -> (shape, held in `param_dtype`?) of one layer."""
    D, F, H, Hkv, d = z["D"], z["F"], z["H"], z["Hkv"], z["d"]
    di, N, K, R = z["di"], z["N"], z["K"], z["R"]
    out = {"ln1": ((D,), False), "ln1_b": ((D,), False),
           "ln2": ((D,), False), "ln2_b": ((D,), False),
           "w_gate_up": ((D, 2 * F), True), "w_down": ((F, D), True)}
    if kind == "mamba":
        out.update(w_in=((D, 2 * di), True), conv_w=((K, di), True),
                   conv_b=((di,), True), w_x=((di, R + 2 * N), True),
                   w_dt=((R, di), True), b_dt=((di,), False),
                   A_log=((N, di), False), D_skip=((di,), False),
                   w_out=((di, D), True))
    elif kind == "gmu":
        out.update(w1=((D, di), True), w2=((di, D), True))
    else:
        if kind == "cross":
            out.update(wq=((D, H * d), True), bq=((H * d,), True))
        else:
            out.update(wqkv=((D, (H + 2 * Hkv) * d), True),
                       bqkv=(((H + 2 * Hkv) * d,), True))
        out.update(wo=((H * d, D), True), bo=((D,), True),
                   lam=((4, d), False), subln=((2 * d,), False))
    return out


def _leaf(key, name, shape, z):
    if name in ("ln1", "ln2", "final_ln", "subln", "D_skip"):
        return jnp.ones(shape, F32)
    if name.endswith("_b") or name in ("bo", "bq", "bqkv"):
        return jnp.zeros(shape, F32)
    if name == "A_log":
        return jnp.broadcast_to(
            jnp.log(jnp.arange(1, z["N"] + 1, dtype=F32))[:, None], shape)
    if name == "b_dt":
        dt = jnp.exp(jax.random.uniform(
            key, shape, F32, math.log(1e-3), math.log(1e-1)))
        return dt + jnp.log(-jnp.expm1(-dt))
    if name == "conv_w":
        return jax.random.uniform(key, shape, F32, -1.0, 1.0) / math.sqrt(
            z["K"])
    if name == "lam":
        return 0.1 * jax.random.normal(key, shape, F32)
    return z["scale"] * jax.random.normal(key, shape, F32)


def _tree(key, shapes, z, dtype):
    out = {}
    for i, (name, (shape, held)) in enumerate(sorted(shapes.items())):
        a = _leaf(jax.random.fold_in(key, i), name, shape, z)
        out[name] = a.astype(dtype) if held else a
    return out


@functools.partial(jax.jit, static_argnames=("kind", "zt", "dtype"))
def _layer_weights(key, kind, zt, dtype):
    z = dict(zt)
    return _tree(key, layer_shapes(kind, z), z, jnp.dtype(dtype))


def layer_weights(key, l: int, config: dict, dtype=None):
    """Layer `l`'s parameters from `key`, matrices rounded once to the
    configuration's `param_dtype` (`dtype` overrides what they come back in:
    the reference asks for float32 AFTER that rounding)."""
    z = sizes(config)
    held = _layer_weights(jax.random.fold_in(key, 1 + l), kinds(z["L"])[l],
                          tuple(sorted(z.items())), config["param_dtype"])
    if dtype is None:
        return held
    return jax.tree.map(lambda a: a.astype(dtype), held)


@functools.partial(jax.jit, static_argnames=("zt", "dtype"))
def _top_weights(key, zt, dtype):
    z = dict(zt)
    shapes = {"embed": ((z["V"], z["D"]), True),
              "final_ln": ((z["D"],), False),
              "final_ln_b": ((z["D"],), False)}
    return _tree(key, shapes, z, jnp.dtype(dtype))


def top_weights(key, config: dict, dtype=None):
    """The embedding (tied head) and the final norm."""
    held = _top_weights(jax.random.fold_in(key, 0),
                        tuple(sorted(sizes(config).items())),
                        config["param_dtype"])
    if dtype is None:
        return held
    return jax.tree.map(lambda a: a.astype(dtype), held)


def params_tree(key, config: dict) -> dict:
    """The whole tree as the program holds it (`param_dtype` matrices)."""
    tree = dict(top_weights(key, config))
    tree["layers"] = [layer_weights(key, l, config)
                      for l in range(config["num_hidden_layers"])]
    return tree


# ---------------------------------------------------------------------- #
# the forward                                                            #
# ---------------------------------------------------------------------- #
def _q8(x):
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / F8_MAX
    return (x / s).astype(F8).astype(F32) * s


def _three(x):
    """float32 `x` as three bfloat16 terms, each holding what the ones before
    it left."""
    hi = x.astype(BF16)
    rest = x - hi.astype(F32)
    mid = rest.astype(BF16)
    return hi, mid, (rest - mid.astype(F32)).astype(BF16)


def _mul(spec, a, b, fp8):
    """The float32 product as the chip computes one at `highest`: six
    bfloat16 passes over the operands' three terms, accumulated in float32.
    Written out, because the chip's compiler takes 6 to 10 s for EVERY shape
    of a product it is asked for at `precision=highest` and under 1 s for
    these six of one shape (PERF.md section 6, PR 33); on the CPU the sum
    agrees with `precision=highest` to float32's rounding
    (`perfbench/tests/test_phi4flash.py`)."""
    if fp8:
        a, b = _q8(a), _q8(b)
    (a0, a1, a2), (b0, b1, b2) = _three(a), _three(b)

    def one(x, y):
        return jnp.einsum(spec, x, y, preferred_element_type=F32)

    return (one(a0, b0) + (one(a0, b1) + one(a1, b0))
            + (one(a0, b2) + one(a2, b0) + one(a1, b1)))


def _ln(x, g, b, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * g + b


def _silu(x):
    return x / (1.0 + jnp.exp(-x))


def _mamba(p, u, z, fp8):
    """`u` (B, S, D) -> (out, y before the gate)."""
    di, N, K = z["di"], z["N"], z["K"]
    S = u.shape[1]
    xz = _mul("bsd,de->bse", u, p["w_in"], fp8)
    x, gate = xz[..., :di], xz[..., di:]
    xp = jnp.pad(x, ((0, 0), (K - 1, 0), (0, 0)))
    x = _silu(sum(xp[:, j:j + S] * p["conv_w"][j] for j in range(K))
              + p["conv_b"])
    dbc = _mul("bse,er->bsr", x, p["w_x"], fp8)
    R = dbc.shape[-1] - 2 * N
    delta = jax.nn.softplus(
        _mul("bsr,re->bse", dbc[..., :R], p["w_dt"], fp8) + p["b_dt"])
    Bm, Cm = dbc[..., R:R + N], dbc[..., R + N:]
    A = -jnp.exp(p["A_log"])                                  # (N, d_inner)

    def step(s, inp):
        d_t, x_t, b_t, c_t = inp              # (B, di), (B, di), (B, N), (B, N)
        s = (jnp.exp(d_t[:, None, :] * A) * s
             + (d_t * x_t)[:, None, :] * b_t[:, :, None])
        return s, jnp.sum(s * c_t[:, :, None], axis=1) + p["D_skip"] * x_t

    s0 = jnp.zeros((u.shape[0], N, di), F32)
    _, y = lax.scan(step, s0, tuple(jnp.swapaxes(a, 0, 1)
                                    for a in (delta, x, Bm, Cm)))
    y = jnp.swapaxes(y, 0, 1)
    return _mul("bse,ed->bsd", y * _silu(gate), p["w_out"], fp8), y


def _diff_attention(p, q, k, v, mask, lam_init, z, fp8):
    """`q` (B, S, H, d), `k`, `v` (B, S, Hkv, d), `mask` (S, S) True where
    seen; the pairs one after another (`lax.map`), so that one pair's two
    S x S maps are all that is alive."""
    d = z["d"]
    lq1, lk1, lq2, lk2 = p["lam"]
    lam = (jnp.exp(jnp.sum(lq1 * lk1)) - jnp.exp(jnp.sum(lq2 * lk2))
           + lam_init)
    bias = jnp.where(mask, 0.0, -jnp.inf)
    n_pairs = z["H"] // 2

    def pair(j):
        g = j // 2
        pick = functools.partial(lax.dynamic_index_in_dim, axis=2,
                                 keepdims=False)
        vbar = jnp.concatenate([pick(v, 2 * g), pick(v, 2 * g + 1)], axis=-1)

        def one(qh, kh):
            s = _mul("bqd,bkd->bqk", qh, kh, fp8) / math.sqrt(d) + bias
            return _mul("bqk,bke->bqe", jax.nn.softmax(s, axis=-1), vbar, fp8)

        o = (one(pick(q, 2 * j), pick(k, 2 * g))
             - lam * one(pick(q, 2 * j + 1), pick(k, 2 * g + 1)))
        o = o / jnp.sqrt(jnp.mean(o * o, axis=-1, keepdims=True) + z["eps"])
        return o * p["subln"] * (1.0 - lam_init)

    o = lax.map(pair, jnp.arange(n_pairs))               # (pairs, B, S, 2d)
    o = jnp.moveaxis(o, 0, 2).reshape(q.shape[0], q.shape[1], -1)
    return _mul("bse,ed->bsd", o, p["wo"], fp8) + p["bo"]


@functools.partial(jax.jit, static_argnames=("kind", "zt", "fp8"))
def mixer_forward(p, h, carry, kind, lam_init, zt, fp8=False):
    """`h + Mixer(LN(h))` of one block on `h` (B, S, D) float32. `carry`:
    (memory y of the last state-space layer, the full layer's K, V), handed
    on. `lam_init` (the layer's 0.8 - 0.6 exp(-0.3 l)) is an argument, so
    that one program serves every layer of a kind."""
    z = dict(zt)
    B, S, _ = h.shape
    H, Hkv, d = z["H"], z["Hkv"], z["d"]
    memory, full_k, full_v = carry
    t, s_ = jnp.arange(S)[:, None], jnp.arange(S)[None, :]
    causal = s_ <= t
    u = _ln(h, p["ln1"], p["ln1_b"], z["eps"])
    if kind == "mamba":
        mixed, memory = _mamba(p, u, z, fp8)
    elif kind == "gmu":
        mixed = _mul("bse,ed->bsd", memory * _silu(
            _mul("bsd,de->bse", u, p["w1"], fp8)), p["w2"], fp8)
    elif kind == "cross":
        q = (_mul("bsd,de->bse", u, p["wq"], fp8) + p["bq"]).reshape(
            B, S, H, d)
        mixed = _diff_attention(p, q, full_k, full_v, causal, lam_init, z,
                                fp8)
    else:
        qkv = (_mul("bsd,de->bse", u, p["wqkv"], fp8) + p["bqkv"]).reshape(
            B, S, H + 2 * Hkv, d)
        q, k, v = qkv[:, :, :H], qkv[:, :, H:H + Hkv], qkv[:, :, H + Hkv:]
        if kind == "full":
            full_k, full_v, mask = k, v, causal
        else:
            mask = causal & (s_ > t - z["W"])
        mixed = _diff_attention(p, q, k, v, mask, lam_init, z, fp8)
    return h + mixed, (memory, full_k, full_v)


@functools.partial(jax.jit, static_argnames=("eps", "fp8"))
def mlp_forward(p, h, eps, fp8=False):
    """`h + MLP(LN(h))`, the same in every block: a program of its own, since
    a float32 product at `highest` takes the chip's compiler 6 s a shape
    and these two would be compiled again with every kind of mixer."""
    gu = _mul("bsd,df->bsf", _ln(h, p["ln2"], p["ln2_b"], eps),
              p["w_gate_up"], fp8)
    F = gu.shape[-1] // 2
    return h + _mul("bsf,fd->bsd", _silu(gu[..., :F]) * gu[..., F:],
                    p["w_down"], fp8)


MLP_NAMES = ("ln2", "ln2_b", "w_gate_up", "w_down")


def layer_forward(p, h, carry, kind, lam_init, zt, fp8=False):
    """One block on `h` (B, S, D) float32: the mixer of its kind, then the
    MLP."""
    mlp = {n: p[n] for n in MLP_NAMES}
    h, carry = mixer_forward({n: a for n, a in p.items() if n not in mlp},
                             h, carry, kind, lam_init, zt, fp8)
    return mlp_forward(mlp, h, dict(zt)["eps"], fp8), carry


def hidden(key, config: dict, toks, fp8=False, weights_of=None):
    """`toks` (B, S) int -> the last block's output (B, S, D) float32 and the
    top weights (float32). One layer's weights alive at a time;
    `weights_of(l)` puts other weights in their place (a test's)."""
    z = sizes(config)
    zt = tuple(sorted(z.items()))
    top = top_weights(key, config, F32)
    h = top["embed"][toks]
    B, S = toks.shape
    carry = (jnp.zeros((B, S, z["di"]), F32),
             jnp.zeros((B, S, z["Hkv"], z["d"]), F32),
             jnp.zeros((B, S, z["Hkv"], z["d"]), F32))
    for l, kind in enumerate(kinds(z["L"])):
        p = (layer_weights(key, l, config, F32) if weights_of is None
             else weights_of(l))
        h, carry = layer_forward(
            p, h, carry, kind, jnp.float32(0.8 - 0.6 * math.exp(-0.3 * l)),
            zt, fp8)
        del p
    return h, top


@functools.partial(jax.jit, static_argnames=("eps", "fp8"))
def chunk_logits(top, h, eps, fp8=False):
    """`h` (C, D) -> (C, V) float32 logits: the final norm and the tied
    head."""
    return _mul("cd,vd->cv", _ln(h, top["final_ln"], top["final_ln_b"], eps),
                top["embed"], fp8)


def row_logits(key, config: dict, toks, fp8=False):
    """(S,) tokens of ONE sequence -> (S, V) logits (a test's size)."""
    h, top = hidden(key, config, jnp.asarray(toks, jnp.int32)[None], fp8)
    return chunk_logits(top, h[0], config["layer_norm_eps"], fp8)


def widest_gaps(key, config: dict, toks, n_prompt, n_total, control=False,
                chunk: int = 512, weights_of=None):
    """For each padded row of `toks` (B, S): over its served positions
    (n_prompt - 1 .. n_total - 2, each predicting the next token), the widest
    gap between the reference's best logit and its logit of the token judged:
    the served one, or (`control`) the float8 forward's first choice there.
    The logits are taken `chunk` positions at a time."""
    toks = jnp.asarray(toks, jnp.int32)
    eps = config["layer_norm_eps"]
    h, top = hidden(key, config, toks, weights_of=weights_of)
    h8 = hidden(key, config, toks, fp8=True)[0] if control else None
    B, S = toks.shape
    gaps = []
    for b in range(B):
        lo, hi, worst = int(n_prompt[b]) - 1, int(n_total[b]) - 1, 0.0
        for c0 in range(lo - lo % chunk, hi, chunk):
            logits = chunk_logits(top, h[b, c0:c0 + chunk], eps)
            if control:
                judged = jnp.argmax(chunk_logits(
                    top, h8[b, c0:c0 + chunk], eps, fp8=True), axis=-1)
            else:
                judged = toks[b, c0 + 1:c0 + chunk + 1]
                judged = jnp.pad(judged, (0, logits.shape[0] - len(judged)))
            gap = jnp.max(logits, -1) - jnp.take_along_axis(
                logits, judged[:, None], -1)[:, 0]
            pos = c0 + jnp.arange(logits.shape[0])
            worst = max(worst, float(jnp.max(jnp.where(
                (pos >= lo) & (pos < hi), gap, 0.0))))
        gaps.append(worst)
    return gaps
