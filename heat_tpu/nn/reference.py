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
"""

from __future__ import annotations

import math

import numpy as np

import jax
import jax.numpy as jnp

__all__ = ["host_params", "reference_logits", "reference_loss",
           "greedy_gaps", "prefill_logits"]


def host_params(params):
    """The model's parameter tree as host float32 arrays with the
    ``(pp, Ls, ...)`` stage axes flattened to one layer axis."""
    host = jax.tree.map(lambda a: np.asarray(a, np.float32), params)
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
    ``_prompt_kv_logits`` body over the same power-of-two prompt bucket
    (pad rows included), on the model's own grid and compute dtype. The
    engine itself keeps only the argmax of these."""
    from jax.sharding import PartitionSpec as P

    from ..core._compat import shard_map

    prompt = np.asarray(prompt, np.int32)
    padded = np.zeros(model.prompt_bucket(len(prompt)), np.int32)
    padded[:len(prompt)] = prompt

    key = ("reference.prefill_logits", len(padded))
    fn = model._step_cache.get(key)  # one compile per prompt bucket
    if fn is None:
        def body(params, toks, n_valid):
            return model._prompt_kv_logits(params, toks[None], n_valid)[2][0]

        fn = model._step_cache[key] = jax.jit(shard_map(
            body, mesh=model.grid.mesh,
            in_specs=(model.param_specs(), P(), P()), out_specs=P(),
            check_vma=False))
    return np.asarray(fn(params, jnp.asarray(padded),
                         jnp.int32(len(prompt))))
