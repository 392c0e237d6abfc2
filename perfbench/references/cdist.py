"""Plain float32 reference of the Euclidean distance matrix, and its controls.

The reference takes the DIRECT form, d(i, j) = sqrt(sum_k (x[i,k] - y[j,k])^2),
in `jax.numpy` alone: no matrix product, so nothing cancels (d(i, i) is exactly
0) and no operand is rounded on its way into the MXU. It never holds a whole
result: everything here walks the rows in blocks and keeps two scalars, so it
fits beside the 6.4 GB answer it judges. It imports nothing of the program and
is handed the rows the BENCHMARK made from the seed.

The controls are the quadratic expansion |x|^2 + |y|^2 - 2 x.y (the form the
configuration states, `quadratic_expansion` true) with the product in a lower
precision than the float32 the configuration states: `high` (three bfloat16
passes, hi.hi + hi.lo + lo.hi of the operands' two-term split, which is what
`Precision.HIGH` is on the TPU: the nearest step below float32 at `highest`;
written out, so that it is the same arithmetic on the CPU) and `bf16`
(operands rounded to bfloat16, one pass).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax


def direct_block(xb, yt):
    """(b, f) rows against (f, m) transposed rows -> (b, m) distances, one
    feature at a time so that nothing larger than the block exists."""
    acc = jnp.zeros((xb.shape[0], yt.shape[1]), jnp.float32)
    for k in range(xb.shape[1]):
        diff = xb[:, k][:, None] - yt[k][None, :]
        acc = acc + diff * diff
    return jnp.sqrt(acc)


def _one_pass(a, b):
    """a . b^T of bfloat16 operands: exact products, float32 sums."""
    return lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                           precision=lax.Precision.DEFAULT,
                           preferred_element_type=jnp.float32)


def _split(a):
    hi = a.astype(jnp.bfloat16)
    return hi, (a - hi.astype(jnp.float32)).astype(jnp.bfloat16)


def expanded_block(xb, y, kind):
    """The same block by the quadratic expansion, the product at `kind`:
    `high` (three bfloat16 passes) or `bf16` (one)."""
    x2 = jnp.sum(xb * xb, axis=1, keepdims=True)
    y2 = jnp.sum(y * y, axis=1)[None, :]
    (ah, al), (bh, bl) = _split(xb), _split(y)
    xy = _one_pass(ah, bh)
    if kind == "high":
        xy = xy + _one_pass(ah, bl) + _one_pass(al, bh)
    elif kind != "bf16":
        raise ValueError(f"unknown control {kind!r}")
    return jnp.sqrt(jnp.maximum(x2 + y2 - 2.0 * xy, 0.0))


def _walk(x, block, other):
    """(largest |other(block) - direct(block)|, largest direct distance) over
    all row blocks of `x` against all of `x`."""
    n = x.shape[0]
    if n % block:
        raise ValueError(f"{n} rows do not divide into blocks of {block}")
    xt = x.T

    def body(i, worst):
        xb = lax.dynamic_slice_in_dim(x, i * block, block)
        want = direct_block(xb, xt)
        err = jnp.max(jnp.abs(other(i, xb) - want))
        return jnp.maximum(worst[0], err), jnp.maximum(worst[1], jnp.max(want))

    zero = jnp.zeros((), jnp.float32)
    with jax.default_matmul_precision("highest"):
        return lax.fori_loop(0, n // block, body, (zero, zero))


@functools.partial(jax.jit, static_argnames=("block",))
def worst_error(x, got, block):
    """`got` (n, n), the answer judged, against the direct form of `x` (n, f):
    (largest absolute error over EVERY entry, largest distance)."""
    return _walk(x, block, lambda i, _xb: lax.dynamic_slice_in_dim(
        got, i * block, block).astype(jnp.float32))


@functools.partial(jax.jit, static_argnames=("block", "kind"))
def control_error(x, block, kind):
    """The same two numbers for the expansion at `kind` in the answer's place."""
    return _walk(x, block, lambda _i, xb: expanded_block(xb, x, kind))
