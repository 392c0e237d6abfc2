"""Plain float32 reference of the causal LM the cells run, and its control.

Embedding -> [RMSNorm -> fused-QKV MHA, half-split rotary on the whole head,
causal -> residual -> RMSNorm -> tanh-GELU MLP -> residual] x L -> RMSNorm ->
unembed, in `jax.numpy` alone: no cache, no kernels, no mesh, no bucket
padding, every contraction in float32 at `highest`. A copy of the mathematics
of `heat_tpu/nn/reference.py` (listed in PERF.md for a later PR to delete one
of the two); it imports nothing of the program and is handed the weights the
BENCHMARK made from the seed, in the tree layout the program is handed too
(`stages` leaves carry leading `(pp=1, L)` axes).

Training: loss, gradients and three Adam steps by hand (optax.adam's
mathematics: b1 0.9, b2 0.999, eps 1e-8, bias-corrected), one row of the batch
at a time and one layer's activations at a time (`jax.checkpoint` per layer),
so that it fits beside nothing else on the chip.

The control is the same code with every matmul operand rounded to float8
(e4m3, per-tensor scale, straight-through gradient): the nearest precision
below the bfloat16 that the configuration states.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax

HI = lax.Precision.HIGHEST
B1, B2, EPS = 0.9, 0.999, 1e-8
F8 = jnp.float8_e4m3fn
F8_MAX = 448.0


def _q8(x):
    """Round to float8 e4m3 with a per-tensor scale; gradient passes."""
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / F8_MAX
    q = (x / s).astype(F8).astype(jnp.float32) * s
    return x + lax.stop_gradient(q - x)


def _ein(spec, a, b, fp8):
    if fp8:
        a, b = _q8(a), _q8(b)
    return jnp.einsum(spec, a, b, precision=HI)


def _rms(x, scale):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + 1e-6) * scale


def _rope(x, theta):
    S, half = x.shape[0], x.shape[-1] // 2            # x: (S, H, Dh)
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * freq[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _gelu(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x * x * x)))


def layers_of(params):
    """`stages` with the (pp, Ls) axes as one layer axis."""
    return {k: v.reshape((-1,) + v.shape[2:])
            for k, v in params["stages"].items()}


def _layer(x, p, theta, fp8):
    S = x.shape[0]
    a = _rms(x, p["ln1"])
    qkv = _ein("sd,dohk->oshk", a, p["wqkv"], fp8)
    q, k, v = _rope(qkv[0], theta), _rope(qkv[1], theta), qkv[2]
    s = _ein("qhd,khd->hqk", q, k, fp8) / math.sqrt(q.shape[-1])
    causal = jnp.tril(jnp.ones((S, S), bool))
    w = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
    o = _ein("hqk,khd->qhd", w, v, fp8)
    x = x + _ein("qhd,hdm->qm", o, p["wproj"], fp8)
    m = _rms(x, p["ln2"])
    return x + _ein("sf,fd->sd", _gelu(_ein("sd,df->sf", m, p["w_up"], fp8)),
                    p["w_down"], fp8)


def row_logits(params, toks, theta, fp8=False):
    """(S,) int tokens -> (S, vocab) float32 logits of ONE sequence."""
    x = params["embed"][toks]
    body = jax.checkpoint(
        lambda h, p: (_layer(h, p, theta, fp8), None))
    x, _ = lax.scan(body, x, layers_of(params))
    return _ein("sd,dv->sv", _rms(x, params["final_ln"]), params["unembed"],
                fp8)


def _row_loss(params, toks, theta, fp8):
    logp = jax.nn.log_softmax(row_logits(params, toks, theta, fp8)[:-1], -1)
    return -jnp.mean(jnp.take_along_axis(logp, toks[1:, None], -1))


def _batch_loss_grad(params, batch, theta, fp8):
    """Mean next-token loss over the rows of `batch` (B, S) and its gradient,
    a row at a time."""
    def body(acc, toks):
        l, g = jax.value_and_grad(_row_loss)(params, toks, theta, fp8)
        return (acc[0] + l, jax.tree.map(jnp.add, acc[1], g)), None

    zero = (jnp.zeros((), jnp.float32), jax.tree.map(jnp.zeros_like, params))
    (l, g), _ = lax.scan(body, zero, batch)
    n = batch.shape[0]
    return l / n, jax.tree.map(lambda a: a / n, g)


def _leaf_norms(tree):
    return jax.tree.map(lambda a: jnp.sqrt(jnp.sum(jnp.square(a))), tree)


@functools.partial(jax.jit, static_argnames=("theta", "lr", "fp8"),
                   donate_argnums=(0, 1, 2))
def adam_step(params, m, v, batch, t, theta, lr, fp8=False):
    """Step `t` (1-based) of Adam on `batch` (B, S). Returns (params, m, v,
    loss, the gradient's norm per leaf). The state is donated."""
    loss, g = _batch_loss_grad(params, batch, theta, fp8)
    m = jax.tree.map(lambda a, b: B1 * a + (1 - B1) * b, m, g)
    v = jax.tree.map(lambda a, b: B2 * a + (1 - B2) * b * b, v, g)
    c1, c2 = 1 - B1 ** t, 1 - B2 ** t
    params = jax.tree.map(
        lambda p, a, b: p - lr * (a / c1) / (jnp.sqrt(b / c2) + EPS),
        params, m, v)
    return params, m, v, loss, _leaf_norms(g)


def train_steps(params, batches, theta, lr, fp8=False):
    """Adam from `params` (donated) over `batches` (n_steps, B, S). Returns
    (params after the steps, each step's loss, the FIRST gradient's norm per
    leaf)."""
    m = jax.tree.map(jnp.zeros_like, params)
    v = jax.tree.map(jnp.zeros_like, params)
    losses, first = [], None
    for i in range(batches.shape[0]):
        params, m, v, loss, gn = adam_step(
            params, m, v, batches[i], jnp.float32(i + 1), theta=theta, lr=lr,
            fp8=fp8)
        losses.append(loss)
        first = gn if first is None else first
    return params, jnp.stack(losses), first


@functools.partial(jax.jit, static_argnames=("theta", "fp8"))
def logits_of_row(params, toks, theta, fp8=False):
    return row_logits(params, toks, theta, fp8)


def change_norms(after, before):
    """Per leaf, the norm of `after - before`."""
    return jax.tree.map(
        lambda a, b: jnp.sqrt(jnp.sum(jnp.square(a - b))), after, before)
