"""Plain float32 reference of the `granite-4.0-h-small-d10e36` configuration,
the weights it is run on, and its control. The benchmark's OWN copy of the
model's equations (ISSUE 37, Tentpole); it imports nothing of the program.

The model (sources: the published config.json, `model_type` granitemoehybrid;
arXiv:2405.21060, Mamba-2). `D` 4,096, RMSNorm with a scale and no bias
everywhere, no bias in any projection, no positional encoding.

* Model: h0 = 12 E[tok]; for each layer h += 0.22 Mixer(RMSNorm(h)), then
  h += 0.22 (Routed(u) + Shared(u)), u = RMSNorm(h); logits = RMSNorm(h) E^T
  / 16, E the embedding (head tied).
* Mamba-2 mixer (128 heads of 64, state 128, one group, filter 4 with bias):
  [z, xBC, dt] = u W_in, widths 8,192 / 8,448 / 128; xBC = silu(conv4(xBC) +
  b_c), causal and depthwise over x, B and C TOGETHER; [x, B, C] = xBC; a head
  Delta_t = softplus(dt_t + dt_bias), a = -exp(A_log) (a scalar a head);
  S_t = exp(Delta_t a) S_{t-1} + Delta_t x_t (x) B_t (64 x 128 a head);
  y_t = S_t C_t + D x_t; y = RMSNorm(y silu(z); gamma) over all 8,192;
  out = y W_out.
* Attention mixer: 32 query heads, 8 key/value heads of 128; causal softmax of
  q.k x 0.0078125 (`attention_multiplier`, 1/128, NOT 1/sqrt(128)).
* Routed experts: r = u W_r (4,096 -> 72); the 10 largest; gates = softmax
  over THOSE 10 logits; expert e: (silu(u W1_e[:, :768]) * (u W1_e[:, 768:]))
  W2_e; Routed(u) = sum over e chosen AND held of g_e expert_e(u). Shared: the
  same gated form at width 1,536, every token.
* The share: the chip holds experts [first, first + count) of every layer;
  what the other experts would add is left out HERE as in the program, and
  that partial result goes on to the next layer (model-configs guide, 4).

Written plainly, NOT as the program computes: the recurrence is a `lax.scan`
over positions (the program's prompt form is chunked), the routed layer a loop
over the held experts, each over EVERY position under a mask (the program
sorts the pairs by expert and runs grouped products), attention a head at a
time over the whole S x S map.

Weights come from a key, a LAYER AT A TIME (`layer_weights`), so that float32
never holds more than one layer (1.8 GB) beside the embedding: matrices N(0,
initializer_range) rounded ONCE to the configuration's `param_dtype` (the
program is handed those, stacked as it holds them; the reference the same
values as float32); norm scales 1; Mamba-2's own init (A uniform in [1, 16] a
head, dt_bias the inverse softplus of a log-uniform step in [1e-3, 1e-1], D 1,
the filter uniform within 1/sqrt(4), its bias 0). The tree is the program's
(`TransformerLM.pattern_param_shapes`).

Every product in float32 as `highest` computes it on the chip (six bfloat16
passes, `phi4flash._mul`: the chip's compiler takes 6 to 10 s for every shape
of a product at `precision=highest`). The control rounds every product's
operands to float8 e4m3 (per-tensor scale): the nearest precision below the
bfloat16 the configuration states.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax

from perfbench.references.phi4flash import _mul, _silu

F32 = jnp.float32
KIND_OF = {"mamba": "mamba2", "attention": "gqa"}


# ---------------------------------------------------------------------- #
# the configuration's sizes                                              #
# ---------------------------------------------------------------------- #
def sizes(config: dict) -> dict:
    D, H = config["hidden_size"], config["num_attention_heads"]
    Hs, P = config["mamba_n_heads"], config["mamba_d_head"]
    assert config["mamba_n_groups"] == 1 and config["tie_word_embeddings"]
    assert config["position_embedding_type"] == "nope"
    first, count = config["experts_held"]
    assert count == config["num_local_experts"]
    return {"D": D, "H": H, "Hkv": config["num_key_value_heads"],
            "d": D // H, "V": config["vocab_size"],
            "L": config["num_hidden_layers"], "Hs": Hs, "P": P,
            "di": Hs * P, "N": config["mamba_d_state"],
            "K": config["mamba_d_conv"], "Q": config["mamba_chunk_size"],
            "Fe": config["intermediate_size"],
            "Fs": config["shared_intermediate_size"],
            "E": config["published"]["num_local_experts"],
            "first": first, "count": count,
            "k": config["num_experts_per_tok"],
            "eps": config["rms_norm_eps"],
            "emb": float(config["embedding_multiplier"]),
            "res": float(config["residual_multiplier"]),
            "att": float(config["attention_multiplier"]),
            "logit": float(config["logits_scaling"]),
            "scale": config["initializer_range"]}


def kinds(config: dict) -> tuple:
    """The mixer of each layer RUN: the first `num_hidden_layers` of the
    published `layer_types`."""
    return tuple(KIND_OF[t] for t in
                 config["layer_types"][:config["num_hidden_layers"]])


def layer_shapes(kind: str, z: dict) -> dict:
    """name -> (shape, held in `param_dtype`?) of one layer."""
    D, H, Hkv, d = z["D"], z["H"], z["Hkv"], z["d"]
    di, N, K, Hs = z["di"], z["N"], z["K"], z["Hs"]
    out = {"ln1": ((D,), False), "ln2": ((D,), False),
           "router": ((D, z["E"]), True),
           "we1": ((z["count"], D, 2 * z["Fe"]), True),
           "we2": ((z["count"], z["Fe"], D), True),
           "ws1": ((D, 2 * z["Fs"]), True), "ws2": ((z["Fs"], D), True)}
    if kind == "mamba2":
        out.update(w_in=((D, 2 * di + 2 * N + Hs), True),
                   conv_w=((K, di + 2 * N), True),
                   conv_b=((di + 2 * N,), True), dt_bias=((Hs,), False),
                   A_log=((Hs,), False), D_skip=((Hs,), False),
                   gnorm=((di,), False), w_out=((di, D), True))
    else:
        out.update(wqkv=((D, (H + 2 * Hkv) * d), True),
                   wo=((H * d, D), True))
    return out


def _leaf(key, name, shape, z):
    if name in ("ln1", "ln2", "final_ln", "gnorm", "D_skip"):
        return jnp.ones(shape, F32)
    if name == "conv_b":
        return jnp.zeros(shape, F32)
    if name == "A_log":
        return jnp.log(jax.random.uniform(key, shape, F32, 1.0, 16.0))
    if name == "dt_bias":
        dt = jnp.exp(jax.random.uniform(
            key, shape, F32, math.log(1e-3), math.log(1e-1)))
        return dt + jnp.log(-jnp.expm1(-dt))
    if name == "conv_w":
        return jax.random.uniform(key, shape, F32, -1.0, 1.0) / math.sqrt(
            z["K"])
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
    held = _layer_weights(jax.random.fold_in(key, 1 + l), kinds(config)[l],
                          tuple(sorted(sizes(config).items())),
                          config["param_dtype"])
    if dtype is None:
        return held
    return jax.tree.map(lambda a: a.astype(dtype), held)


@functools.partial(jax.jit, static_argnames=("zt", "dtype"))
def _top_weights(key, zt, dtype):
    z = dict(zt)
    shapes = {"embed": ((z["V"], z["D"]), True),
              "final_ln": ((z["D"],), False)}
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
    """The whole tree, a list of layers (`param_dtype` matrices)."""
    tree = dict(top_weights(key, config))
    tree["layers"] = [layer_weights(key, l, config)
                      for l in range(config["num_hidden_layers"])]
    return tree


# ---------------------------------------------------------------------- #
# the forward                                                            #
# ---------------------------------------------------------------------- #
def _rms(x, g, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def _mamba2(p, u, z, fp8):
    """`u` (B, S, D) -> (B, S, D); the recurrence a position at a time."""
    di, N, K, Hs, P = z["di"], z["N"], z["K"], z["Hs"], z["P"]
    B, S, _ = u.shape
    zxd = _mul("bsd,de->bse", u, p["w_in"], fp8)
    gate, xbc, dt = (zxd[..., :di], zxd[..., di:2 * di + 2 * N],
                     zxd[..., 2 * di + 2 * N:])
    xp = jnp.pad(xbc, ((0, 0), (K - 1, 0), (0, 0)))
    xbc = _silu(sum(xp[:, j:j + S] * p["conv_w"][j] for j in range(K))
                + p["conv_b"])
    x = xbc[..., :di].reshape(B, S, Hs, P)
    Bm, Cm = xbc[..., di:di + N], xbc[..., di + N:]
    delta = jax.nn.softplus(dt + p["dt_bias"])                # (B, S, Hs)
    a = -jnp.exp(p["A_log"])                                  # (Hs,)

    def step(s, inp):                                         # s (B,Hs,P,N)
        d_t, x_t, b_t, c_t = inp
        s = (jnp.exp(d_t * a)[..., None, None] * s
             + (d_t[..., None] * x_t)[..., None] * b_t[:, None, None, :])
        y = jnp.sum(s * c_t[:, None, None, :], axis=-1)
        return s, y + p["D_skip"][:, None] * x_t

    _, y = lax.scan(step, jnp.zeros((B, Hs, P, N), F32),
                    tuple(jnp.swapaxes(t, 0, 1) for t in (delta, x, Bm, Cm)))
    y = jnp.swapaxes(y, 0, 1).reshape(B, S, di) * _silu(gate)
    return _mul("bse,ed->bsd", _rms(y, p["gnorm"], z["eps"]), p["w_out"], fp8)


def _gqa(p, u, z, fp8):
    """Causal grouped-query attention, a query head at a time (`lax.map`), so
    that one head's S x S map is all that is alive."""
    H, Hkv, d = z["H"], z["Hkv"], z["d"]
    B, S, _ = u.shape
    qkv = _mul("bsd,de->bse", u, p["wqkv"], fp8).reshape(B, S, H + 2 * Hkv, d)
    q, k, v = qkv[:, :, :H], qkv[:, :, H:H + Hkv], qkv[:, :, H + Hkv:]
    bias = jnp.where(jnp.arange(S)[None, :] <= jnp.arange(S)[:, None], 0.0,
                     -jnp.inf)
    pick = functools.partial(lax.dynamic_index_in_dim, axis=2, keepdims=False)

    def head(h):
        g = h // (H // Hkv)
        s = _mul("bqd,bkd->bqk", pick(q, h), pick(k, g), fp8) * z["att"]
        return _mul("bqk,bkd->bqd", jax.nn.softmax(s + bias, axis=-1),
                    pick(v, g), fp8)

    o = lax.map(head, jnp.arange(H))                          # (H, B, S, d)
    o = jnp.moveaxis(o, 0, 2).reshape(B, S, H * d)
    return _mul("bse,ed->bsd", o, p["wo"], fp8)


@functools.partial(jax.jit, static_argnames=("kind", "zt", "fp8"))
def mixer_forward(p, h, kind, zt, fp8=False):
    """`h + 0.22 Mixer(RMSNorm(h))` of one layer on `h` (B, S, D) float32."""
    z = dict(zt)
    u = _rms(h, p["ln1"], z["eps"])
    mixed = (_mamba2 if kind == "mamba2" else _gqa)(p, u, z, fp8)
    return h + z["res"] * mixed


def _gated(u, w1, w2, fp8):
    gp = _mul("bsd,df->bsf", u, w1, fp8)
    F = gp.shape[-1] // 2
    return _mul("bsf,fd->bsd", _silu(gp[..., :F]) * gp[..., F:], w2, fp8)


@functools.partial(jax.jit, static_argnames=("zt", "fp8"))
def experts_forward(p, h, zt, fp8=False):
    """`h + 0.22 (Routed(u) + Shared(u))`, u = RMSNorm(h), the same in every
    layer: the held experts ONE AFTER ANOTHER (`lax.scan` over their stacked
    weights), each over every position, kept where the position chose it.
    Also returns the experts chosen (B, S, k)."""
    z = dict(zt)
    u = _rms(h, p["ln2"], z["eps"])
    top, chosen = lax.top_k(_mul("bsd,de->bse", u, p["router"], fp8), z["k"])
    gates = jax.nn.softmax(top, axis=-1)

    def one(out, inp):
        e, w1, w2 = inp
        gate = jnp.sum(jnp.where(chosen == z["first"] + e, gates, 0.0), -1)
        return out + gate[..., None] * _gated(u, w1, w2, fp8), None

    routed, _ = lax.scan(one, jnp.zeros_like(u),
                         (jnp.arange(z["count"]), p["we1"], p["we2"]))
    out = routed + _gated(u, p["ws1"], p["ws2"], fp8)
    return h + z["res"] * out, chosen


FFN_NAMES = ("ln2", "router", "we1", "we2", "ws1", "ws2")


def layer_forward(p, h, kind, zt, fp8=False):
    """One layer on `h` (B, S, D) float32: the mixer of its kind, then the
    experts. Returns (h, the experts chosen)."""
    ffn = {n: p[n] for n in FFN_NAMES}
    h = mixer_forward({n: a for n, a in p.items() if n not in ffn}, h, kind,
                      zt, fp8)
    return experts_forward(ffn, h, zt, fp8)


def hidden(key, config: dict, toks, fp8=False, weights_of=None,
           routing=None):
    """`toks` (B, S) int -> the last layer's output (B, S, D) float32 and the
    top weights (float32). One layer's weights alive at a time;
    `weights_of(l)` puts other weights in their place (a test's); `routing`,
    a list, is given each layer's chosen experts (B, S, k)."""
    z = sizes(config)
    zt = tuple(sorted(z.items()))
    top = top_weights(key, config, F32)
    h = z["emb"] * top["embed"][toks]
    for l, kind in enumerate(kinds(config)):
        p = (layer_weights(key, l, config, F32) if weights_of is None
             else weights_of(l))
        h, chosen = layer_forward(p, h, kind, zt, fp8)
        if routing is not None:
            routing.append(chosen)
        del p
    return h, top


@functools.partial(jax.jit, static_argnames=("eps", "scaling", "fp8"))
def chunk_logits(top, h, eps, scaling, fp8=False):
    """`h` (C, D) -> (C, V) float32 logits: the final norm, the tied head,
    the logit scaling."""
    return _mul("cd,vd->cv", _rms(h, top["final_ln"], eps), top["embed"],
                fp8) / scaling


def row_logits(key, config: dict, toks, fp8=False):
    """(S,) tokens of ONE sequence -> (S, V) logits (a test's size)."""
    h, top = hidden(key, config, jnp.asarray(toks, jnp.int32)[None], fp8)
    return chunk_logits(top, h[0], config["rms_norm_eps"],
                        float(config["logits_scaling"]), fp8)


@functools.partial(jax.jit, static_argnames=("eps", "scaling", "fp8"))
def _chunk_judged(top, h, judged, eps, scaling, fp8=False):
    """Of `h` (C, D)'s logits, a position: the log-probability and the logit
    of its token `judged` (C,), the best logit and its place, the spread."""
    logits = chunk_logits(top, h, eps, scaling, fp8)
    at = jnp.take_along_axis(logits, judged[:, None], -1)[:, 0]
    return {"logp": at - jax.nn.logsumexp(logits, axis=-1), "at": at,
            "best": jnp.max(logits, -1), "first": jnp.argmax(logits, -1),
            "spread": jnp.std(logits, -1)}


def judge_served(key, config: dict, toks, n_prompt, n_total, control=False,
                 chunk: int = 512, weights_of=None):
    """For each padded row of `toks` (B, S), over its served positions
    (n_prompt - 1 .. n_total - 2, each predicting the next token), arrays a
    position: `logp`, the reference's log-probability of the token SERVED
    there (what the engine's own `future.logprobs` are held against); `gap`,
    the reference's best logit minus its logit of the token judged (the
    served one, or (`control`) the float8 forward's first choice); `spread`,
    the standard deviation of the reference's logits; and (`control`)
    `logp8`, the float8 forward's log-probability of the served token. ONE
    forward over all rows, a layer at a time; the logits `chunk` positions at
    a time."""
    toks = jnp.asarray(toks, jnp.int32)
    eps, scaling = config["rms_norm_eps"], float(config["logits_scaling"])
    h, top = hidden(key, config, toks, weights_of=weights_of)
    h8 = hidden(key, config, toks, fp8=True)[0] if control else None
    rows = []
    chunk = min(chunk, toks.shape[1] - 1)
    for b in range(toks.shape[0]):
        lo, hi = int(n_prompt[b]) - 1, int(n_total[b]) - 1
        got = {"logp": [], "gap": [], "spread": [], "logp8": []}
        for c0 in range(lo, hi, chunk):
            # one shape a chunk: the row's last chunk starts earlier
            start = min(c0, toks.shape[1] - chunk - 1)
            mine = slice(c0 - start, min(chunk, hi - start))
            at = slice(start, start + chunk)
            served = toks[b, start + 1:start + chunk + 1]
            j = _chunk_judged(top, h[b, at], served, eps, scaling)
            gap = j["best"] - j["at"]
            if control:
                j8 = _chunk_judged(top, h8[b, at], served, eps, scaling,
                                   fp8=True)
                got["logp8"].append(j8["logp"][mine])
                gap = j["best"] - _chunk_judged(
                    top, h[b, at], j8["first"], eps, scaling)["at"]
            got["logp"].append(j["logp"][mine])
            got["gap"].append(gap[mine])
            got["spread"].append(j["spread"][mine])
        rows.append({name: jnp.concatenate(parts) for name, parts in
                     got.items() if parts})
    return jax.device_get(rows)
