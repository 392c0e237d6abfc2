"""Plain full-batch Lloyd in float32, every contraction at `highest`.

Independent of `heat_tpu.cluster`: it imports nothing of the program. Rows go
through in blocks (the `(k, rows)` orientation keeps the 8-wide axis off the
TPU's 128 lanes), each block's partial sums are added in float32, and where X
lies across several chips every chip scans its own rows and one `psum` adds
the partials. The inertia is the direct sum of squared differences, not the
`|x|^2 + |c|^2 - 2xc` expansion.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

HI = lax.Precision.HIGHEST
BLOCK_ROWS = 1 << 18


def n_blocks(rows: int, block_rows: int = BLOCK_ROWS) -> int:
    """The fewest equal blocks of at most `block_rows` rows."""
    nb = max(1, -(-rows // block_rows))
    while rows % nb:
        nb += 1
    return nb


def _partials(c, xt):
    """`xt` is a block of X TRANSPOSED, (features, rows): the TPU keeps an
    (n, 64) float32 array column-major (64 of 128 lanes would be padding
    otherwise), so this orientation reads it where it lies."""
    k = c.shape[0]
    xc = jnp.matmul(c, xt, precision=HI)                       # (k, rows)
    d2 = jnp.sum(xt * xt, 0)[None, :] + jnp.sum(c * c, 1)[:, None] - 2.0 * xc
    lab = jnp.argmin(d2, 0)
    onehot = (lab[None, :] == jnp.arange(k)[:, None]).astype(jnp.float32)
    sums = lax.dot_general(onehot, xt, (((1,), (1,)), ((), ())),
                           precision=HI)                       # (k, f)
    diff = xt - jnp.matmul(c.T, onehot, precision=HI)          # (f, rows)
    return sums, jnp.sum(onehot, 1), jnp.sum(diff * diff)


def _pass(c, xt):
    """One pass over this device's rows, a block at a time, sliced out of X
    where it lies."""
    nb = n_blocks(xt.shape[1])
    br = xt.shape[1] // nb

    def body(i, acc):
        xb = lax.dynamic_slice_in_dim(xt, i * br, br, 1)
        s, n, inertia = _partials(c, xb)
        return acc[0] + s, acc[1] + n, acc[2] + inertia

    zero = (jnp.zeros_like(c), jnp.zeros(c.shape[0], jnp.float32),
            jnp.zeros((), jnp.float32))
    return lax.fori_loop(0, nb, body, zero)


def _lloyd_local(x, init, iters, axis):
    x = x.T

    def reduce_(t):
        return jax.tree.map(lambda a: lax.psum(a, axis), t) if axis else t

    def step(_i, c):
        sums, counts, _inertia = reduce_(_pass(c, x))
        new = sums / jnp.maximum(counts, 1.0)[:, None]
        return jnp.where((counts > 0)[:, None], new, c)

    c = lax.fori_loop(0, iters, step, init.astype(jnp.float32))
    _sums, counts, inertia = reduce_(_pass(c, x))
    return c, inertia, counts


@functools.lru_cache(maxsize=None)
def _program(iters, mesh, axis):
    local = functools.partial(_lloyd_local, iters=iters, axis=axis)
    if mesh is None:
        return jax.jit(local)
    return jax.jit(jax.shard_map(
        local, mesh=mesh, in_specs=(P(axis, None), P()),
        out_specs=(P(), P(), P()), check_vma=False))


def lloyd(x, _init, iters: int):
    """`iters` Lloyd iterations from `init`, then one assignment pass.
    Returns (centroids (k, f), inertia, counts (k,)), all float32.
    `x` is (n, f) float32, on one device or split by rows over a 1-d mesh
    (read off `x.sharding`, so a `ShapeDtypeStruct` with a sharding serves
    `.lower`)."""
    mesh = getattr(x.sharding, "mesh", None)
    if mesh is not None and mesh.size > 1:
        return _program(iters, mesh, x.sharding.spec[0])
    return _program(iters, None, None)
