"""Pallas kernel tests (interpret mode on the CPU mesh).

The kernels are the TPU hot-op tiles (``heat_tpu/core/pallas_kernels.py``);
off-TPU they run through the Pallas interpreter, so these tests exercise the
identical kernel code path the TPU compiles. Equivalence targets are the jnp
reference implementations the rest of the suite already validates against
NumPy.
"""

import math

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import heat_tpu as ht

from utils import dense_causal_attention
from heat_tpu.core import pallas_kernels as pk


@pytest.fixture
def force_pallas():
    pk.set_pallas(True)
    yield
    pk.set_pallas(None)


def _direct64(x, y):
    """The direct form's SQUARED distances in float64, over the values the
    kernel was handed (a bfloat16 input counts as what it holds)."""
    x64 = np.asarray(x).astype(np.float64)
    y64 = np.asarray(y).astype(np.float64)
    return ((x64[:, None, :] - y64[None, :, :]) ** 2).sum(-1)


def _ref_cdist(x, y):
    return np.sqrt(_direct64(x, y)).astype(np.float32)


def _ref_attention(q, k, v, causal=False):
    scale = 1.0 / math.sqrt(q.shape[-1])
    logits = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
    if causal:
        qn, kn = logits.shape[-2], logits.shape[-1]
        mask = jnp.tril(jnp.ones((qn, kn), bool), kn - qn)
        logits = jnp.where(mask, logits, -jnp.inf)
    return np.asarray(jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(logits, -1), v))


# against the 256 x 256 tile (rows in eights, columns in 128 lanes): the
# first three are the oldest; then both axes ragged, m < 8, n < 128, one axis a
# multiple and the other not (each way), several tiles with a ragged last
# tile on both axes
_CDIST_SHAPES = [(37, 53, 19), (128, 128, 64), (8, 300, 5), (300, 200, 18),
                 (5, 700, 18), (520, 100, 18), (512, 300, 18), (300, 256, 18),
                 (520, 700, 18)]
# form: (input dtype, sqrt, out_dtype argument, result dtype, tolerance)
_CDIST_FORMS = {
    "float32": (jnp.float32, True, None, jnp.float32, 1e-4),
    "squared": (jnp.float32, False, None, jnp.float32, 1e-3),
    "bfloat16": (jnp.bfloat16, True, None, jnp.bfloat16, 1e-2),
    # rbf's form: squared distances of bfloat16 rows, kept in float32
    "rbf": (jnp.bfloat16, False, "float32", jnp.float32, 1e-3),
}


class TestCdistTile:
    @pytest.mark.parametrize("form", list(_CDIST_FORMS))
    @pytest.mark.parametrize("shape", _CDIST_SHAPES, ids=str)
    def test_matches_reference(self, shape, form):
        """EVERY entry, the last row and column included: the result has
        its own shape, so an edge tile's store must end where it ends."""
        m, n, d = shape
        dtype, sqrt, out_dtype, res_dtype, tol = _CDIST_FORMS[form]
        rng = np.random.default_rng(0)
        x = jnp.asarray(rng.standard_normal((m, d)), dtype)
        y = jnp.asarray(rng.standard_normal((n, d)), dtype)
        out = pk.cdist_tile(x, y, sqrt=sqrt, out_dtype=out_dtype)
        assert out.shape == (m, n) and out.dtype == res_dtype
        ref = _direct64(x, y)
        np.testing.assert_allclose(
            np.asarray(out.astype(jnp.float32), np.float64),
            np.sqrt(ref) if sqrt else ref, rtol=tol, atol=tol)

    def test_squared(self):
        rng = np.random.default_rng(1)
        x = rng.standard_normal((20, 7)).astype(np.float32)
        out = np.asarray(pk.cdist_tile(jnp.asarray(x), jnp.asarray(x), sqrt=False))
        np.testing.assert_allclose(out, _ref_cdist(x, x) ** 2, rtol=1e-3, atol=1e-3)

    def test_spatial_cdist_pallas_path(self, force_pallas):
        # full integration: ppermute ring in shard_map with the Pallas tile
        rng = np.random.default_rng(2)
        x = rng.standard_normal((40, 6)).astype(np.float32)
        d = ht.spatial.cdist(ht.array(x, split=0), ht.array(x, split=0), quadratic_expansion=True)
        # compare squared distances: the expansion form's cancellation error
        # near zero is amplified unboundedly by the final sqrt
        np.testing.assert_allclose(d.numpy() ** 2, _ref_cdist(x, x) ** 2, rtol=1e-3, atol=1e-3)

    @pytest.mark.parametrize("op", ["cdist", "rbf"])
    def test_split_rows_not_a_multiple_of_the_tile(self, force_pallas, op):
        """601 rows split over two devices (one, on a mesh of one): a
        device's 301 rows are more than one 256-row tile and not two, and
        the ring's padded last row (602) is cut from the result."""
        comm = ht.get_comm()
        sub = comm.Split(list(range(min(2, comm.size))))
        rng = np.random.default_rng(3)
        x = rng.standard_normal((601, 6)).astype(np.float32)
        X = ht.array(x, split=0, comm=sub)
        d2 = _direct64(x, x)
        if op == "cdist":
            got = ht.spatial.cdist(X, X, quadratic_expansion=True).numpy() ** 2
            want = d2
        else:
            got = ht.spatial.rbf(X, X, sigma=2.0, quadratic_expansion=True).numpy()
            want = np.exp(-d2 / 8.0)
        assert got.shape == (601, 601) and got.dtype == np.float32
        np.testing.assert_allclose(got, want, rtol=1e-3, atol=1e-3)


class TestFlashAttention:
    @pytest.mark.parametrize("sq,sk", [(40, 70), (64, 64), (3, 500)])
    def test_matches_reference(self, sq, sk):
        rng = np.random.default_rng(0)
        q = rng.standard_normal((2, 3, sq, 16)).astype(np.float32)
        k = rng.standard_normal((2, 3, sk, 16)).astype(np.float32)
        v = rng.standard_normal((2, 3, sk, 16)).astype(np.float32)
        out = np.asarray(pk.flash_attention(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v)))
        np.testing.assert_allclose(out, _ref_attention(q, k, v), rtol=1e-4, atol=1e-4)

    @pytest.mark.parametrize("sq,sk", [(50, 50), (24, 56)])
    def test_causal(self, sq, sk):
        # sq != sk covers the end-aligned diagonal (same convention as the
        # dense fallback's tril offset kn-qn)
        rng = np.random.default_rng(1)
        q = rng.standard_normal((1, 2, sq, 8)).astype(np.float32)
        k = rng.standard_normal((1, 2, sk, 8)).astype(np.float32)
        v = rng.standard_normal((1, 2, sk, 8)).astype(np.float32)
        out = np.asarray(
            pk.flash_attention(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), causal=True)
        )
        np.testing.assert_allclose(out, _ref_attention(q, k, v, causal=True), rtol=1e-4, atol=1e-4)

    def test_lse(self):
        rng = np.random.default_rng(2)
        q = rng.standard_normal((1, 1, 16, 8)).astype(np.float32)
        k = rng.standard_normal((1, 1, 24, 8)).astype(np.float32)
        v = rng.standard_normal((1, 1, 24, 8)).astype(np.float32)
        _, lse = pk.flash_attention(
            jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), return_lse=True
        )
        scale = 1.0 / math.sqrt(8)
        logits = jnp.einsum("bhqd,bhkd->bhqk", jnp.asarray(q), jnp.asarray(k)) * scale
        expected = jax.scipy.special.logsumexp(logits, axis=-1)
        np.testing.assert_allclose(np.asarray(lse), np.asarray(expected), rtol=1e-5, atol=1e-5)

    def test_ring_attention_pallas_path(self, force_pallas):
        # flash-per-block + lse merge across the ppermute ring
        rng = np.random.default_rng(3)
        mk = lambda: rng.normal(size=(2, 32, 4, 8)).astype(np.float32)
        q, k, v = mk(), mk(), mk()
        out = ht.nn.ring_attention(ht.array(q, split=1), ht.array(k, split=1), ht.array(v, split=1))
        qh = jnp.moveaxis(jnp.asarray(q), 2, 1)
        kh = jnp.moveaxis(jnp.asarray(k), 2, 1)
        vh = jnp.moveaxis(jnp.asarray(v), 2, 1)
        expected = _ref_attention(qh, kh, vh).transpose(0, 2, 1, 3)
        np.testing.assert_allclose(out.numpy(), expected, rtol=1e-4, atol=1e-4)

    def test_ulysses_attention_pallas_path(self, force_pallas):
        rng = np.random.default_rng(4)
        mk = lambda: rng.normal(size=(1, 32, 8, 8)).astype(np.float32)
        q, k, v = mk(), mk(), mk()
        out = ht.nn.ulysses_attention(
            ht.array(q, split=1), ht.array(k, split=1), ht.array(v, split=1)
        )
        qh = jnp.moveaxis(jnp.asarray(q), 2, 1)
        kh = jnp.moveaxis(jnp.asarray(k), 2, 1)
        vh = jnp.moveaxis(jnp.asarray(v), 2, 1)
        expected = _ref_attention(qh, kh, vh).transpose(0, 2, 1, 3)
        np.testing.assert_allclose(out.numpy(), expected, rtol=1e-4, atol=1e-4)


class TestKernelEdgeCases:
    def test_flash_fully_masked_rows_match_dense(self):
        # causal with Sq > Sk: end-aligned diagonal leaves the first
        # Sq - Sk query rows with zero allowed keys; dense softmax yields
        # NaN there and the kernel must agree (regression: it used to
        # emit mean(V) because exp(-BIG - (-BIG)) == 1)
        rng = np.random.default_rng(7)
        q = jnp.asarray(rng.normal(size=(1, 1, 6, 8)).astype(np.float32))
        k = jnp.asarray(rng.normal(size=(1, 1, 4, 8)).astype(np.float32))
        v = jnp.asarray(rng.normal(size=(1, 1, 4, 8)).astype(np.float32))
        out, lse = pk.flash_attention(q, k, v, causal=True, return_lse=True)
        expected = _ref_attention(q, k, v, causal=True)
        assert np.isnan(np.asarray(out)[0, 0, :2]).all()
        assert np.isneginf(np.asarray(lse)[0, 0, :2]).all()
        np.testing.assert_allclose(
            np.asarray(out)[0, 0, 2:], expected[0, 0, 2:], rtol=1e-4, atol=1e-4
        )

    def test_cdist_tile_preserves_bf16(self):
        x = jnp.ones((8, 4), jnp.bfloat16)
        assert pk.cdist_tile(x, x).dtype == jnp.bfloat16
        xi = jnp.ones((8, 4), jnp.int32)
        assert pk.cdist_tile(xi, xi).dtype == jnp.float32

    def test_non_multiple_block_sizes_rounded(self):
        # user-supplied block sizes that violate Mosaic's 8/128 tiling
        # multiples must be rounded up, producing the same result as the
        # default blocks (block-size invariance)
        rng = np.random.default_rng(8)
        x = rng.standard_normal((40, 9)).astype(np.float32)
        base = np.asarray(pk.cdist_tile(jnp.asarray(x), jnp.asarray(x)))
        out = np.asarray(pk.cdist_tile(jnp.asarray(x), jnp.asarray(x), block_m=100, block_n=100))
        np.testing.assert_allclose(out, base, rtol=1e-6, atol=1e-6)
        q = jnp.asarray(rng.normal(size=(1, 1, 40, 8)).astype(np.float32))
        base_o = np.asarray(pk.flash_attention(q, q, q))
        o = np.asarray(pk.flash_attention(q, q, q, block_q=100, block_k=100))
        np.testing.assert_allclose(o, base_o, rtol=1e-6, atol=1e-6)


class TestCausalRingPallas:
    def test_causal_ring_flash_path(self, force_pallas):
        import jax.numpy as jnp

        rng = np.random.default_rng(21)
        B, S, H, D = 2, 64, 8, 16
        q, k, v = (rng.normal(size=(B, S, H, D)).astype(np.float32) for _ in range(3))
        dense = dense_causal_attention(q, k, v)
        out = ht.nn.ring_attention(
            ht.array(q, split=1), ht.array(k, split=1), ht.array(v, split=1), causal=True
        )
        np.testing.assert_allclose(out.numpy(), dense, rtol=1e-4, atol=1e-4)


class TestFlashBackward:
    """The Pallas forward pairs with a recompute-from-lse backward
    (custom_vjp) — training paths must differentiate through it."""

    def test_flash_grad_matches_dense(self, force_pallas):
        import jax
        import jax.numpy as jnp

        rng = np.random.default_rng(31)
        B, H, S, D = 1, 2, 64, 16
        q, k, v = (jnp.asarray(rng.normal(size=(B, H, S, D)).astype(np.float32)) for _ in range(3))

        def dense(q, k, v, causal):
            s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / jnp.sqrt(D * 1.0)
            if causal:
                s = jnp.where(jnp.tril(jnp.ones((S, S), bool)), s, -jnp.inf)
            return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, -1), v)

        for causal in (False, True):
            f_flash = lambda a, b, c: jnp.sum(jnp.sin(pk.flash_attention(a, b, c, causal=causal)))
            f_dense = lambda a, b, c: jnp.sum(jnp.sin(dense(a, b, c, causal)))
            gf = jax.grad(f_flash, argnums=(0, 1, 2))(q, k, v)
            gd = jax.grad(f_dense, argnums=(0, 1, 2))(q, k, v)
            for a, b in zip(gf, gd):
                np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-3, atol=2e-4)

    def test_ring_training_step_with_pallas(self, force_pallas):
        import jax
        import jax.numpy as jnp

        rng = np.random.default_rng(33)
        q = ht.array(rng.normal(size=(1, 64, 4, 8)).astype(np.float32), split=1).larray
        comm = ht.get_comm()

        def loss(t):
            return jnp.sum(ht.nn.ring_attention(t, t, t, comm=comm, causal=True) ** 2)

        g = jax.jit(jax.grad(loss))(q)
        assert np.isfinite(np.asarray(g)).all()


class TestKMeansStepTile:
    @pytest.mark.parametrize("sums_mode", ["dot_rev", "dot_t", "loop"])
    def test_matches_reference(self, sums_mode):
        rng = np.random.default_rng(11)
        n, d, k, nv = 2048 + 77, 48, 8, 2048 + 13  # uneven rows + padding
        x = rng.standard_normal((n, d)).astype(np.float32)
        c = rng.standard_normal((k, d)).astype(np.float32)
        mask = (np.arange(n) < nv).astype(np.float32)[:, None]

        sums, counts, inertia = pk.kmeans_step_tile(
            jnp.asarray(x), jnp.asarray(c), jnp.asarray(mask),
            sums_mode=sums_mode)

        d2 = ((x[:, None, :] - c[None, :, :]) ** 2).sum(-1)
        lab = d2.argmin(1)
        oh = (lab[:, None] == np.arange(k)) * mask
        np.testing.assert_allclose(np.asarray(sums), oh.T @ x, rtol=1e-4, atol=1e-3)
        np.testing.assert_allclose(np.asarray(counts), oh.sum(0), rtol=0, atol=0)
        np.testing.assert_allclose(
            float(inertia), (d2.min(1) * mask[:, 0]).sum(), rtol=1e-5)

    @pytest.mark.parametrize("block_rows", [256, 512])
    def test_block_rows_invariant(self, block_rows, monkeypatch):
        """Numerics are identical at every X-tile size — the lever for the
        Mosaic scoped-VMEM A/B (HEAT_TPU_KMEANS_BLOCK_ROWS)."""
        rng = np.random.default_rng(3)
        n, d, k = 1024 + 31, 32, 8
        x = rng.standard_normal((n, d)).astype(np.float32)
        c = rng.standard_normal((k, d)).astype(np.float32)
        mask = np.ones((n, 1), np.float32)
        base = pk.kmeans_step_tile(jnp.asarray(x), jnp.asarray(c),
                                   jnp.asarray(mask), block_rows=1024)
        monkeypatch.setenv("HEAT_TPU_KMEANS_BLOCK_ROWS", str(block_rows))
        via_env = pk.kmeans_step_tile(jnp.asarray(x), jnp.asarray(c),
                                      jnp.asarray(mask))
        for a, b in zip(base, via_env):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-4, atol=1e-3)

    def test_sums_mode_env_knob(self, monkeypatch):
        monkeypatch.setenv("HEAT_TPU_KMEANS_SUMS", "bogus")
        with pytest.raises(ValueError, match="HEAT_TPU_KMEANS_SUMS"):
            pk._kmeans_sums_mode()
        monkeypatch.setenv("HEAT_TPU_KMEANS_SUMS", "loop")
        assert pk._kmeans_sums_mode() == "loop"

    def test_block_rows_env_knob(self, monkeypatch):
        monkeypatch.setenv("HEAT_TPU_KMEANS_BLOCK_ROWS", "2k")
        with pytest.raises(ValueError, match="HEAT_TPU_KMEANS_BLOCK_ROWS"):
            pk._kmeans_block_rows()
        monkeypatch.setenv("HEAT_TPU_KMEANS_BLOCK_ROWS", "0")
        with pytest.raises(ValueError, match="HEAT_TPU_KMEANS_BLOCK_ROWS"):
            pk._kmeans_block_rows()
        monkeypatch.setenv("HEAT_TPU_KMEANS_BLOCK_ROWS", "512")
        assert pk._kmeans_block_rows() == 512

    def test_kmeans_pallas_path_matches_xla(self, force_pallas):
        """Full KMeans fit through the fused kernel (interpret mode on the
        CPU mesh) against the XLA step path."""
        import heat_tpu as ht
        from heat_tpu.cluster import KMeans

        ht.random.seed(5)
        x = ht.random.rand(503, 16, split=0)  # uneven over the mesh
        km_p = KMeans(n_clusters=4, max_iter=12, random_state=3).fit(x)

        pk.set_pallas(False)
        km_x = KMeans(n_clusters=4, max_iter=12, random_state=3).fit(x)

        np.testing.assert_allclose(
            km_p.cluster_centers_.numpy(), km_x.cluster_centers_.numpy(),
            rtol=1e-4, atol=1e-4)
        np.testing.assert_array_equal(km_p.labels_.numpy(), km_x.labels_.numpy())
        np.testing.assert_allclose(km_p.inertia_, km_x.inertia_, rtol=1e-4)


class TestPallasEnablement:
    """Who decides whether the hot ops take the kernels: the override, then
    the environment, then the backend — and nothing else. On the TPU a
    kernel the compiler refuses raises; no probe turns it into an XLA path."""

    @pytest.fixture(autouse=True)
    def _reset(self):
        pk.set_pallas(None)
        yield
        pk.set_pallas(None)

    def test_backend_decides_by_default(self, monkeypatch):
        monkeypatch.delenv("HEAT_TPU_PALLAS", raising=False)
        assert pk.pallas_enabled() is False  # the CPU test mesh
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        # no kernel is compiled to answer this
        monkeypatch.setattr(pk.pl, "pallas_call", lambda *a, **k: 1 / 0)
        assert pk.pallas_enabled() is True
        assert pk._interpret() is False
        assert pk.kmeans_pallas_enabled() is False  # opt-in only

    def test_env_overrides_backend(self, monkeypatch):
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        monkeypatch.setenv("HEAT_TPU_PALLAS", "0")
        assert pk.pallas_enabled() is False
        monkeypatch.setenv("HEAT_TPU_PALLAS", "1")
        assert pk.pallas_enabled() is True
        assert pk.kmeans_pallas_enabled() is True

    def test_set_pallas_overrides_env(self, monkeypatch):
        monkeypatch.setenv("HEAT_TPU_PALLAS", "0")
        pk.set_pallas(True)
        assert pk.pallas_enabled() is True
        assert pk.kmeans_pallas_enabled() is True
        pk.set_pallas(False)
        monkeypatch.setenv("HEAT_TPU_PALLAS", "1")
        assert pk.pallas_enabled() is False

    def test_kmeans_kernel_defaults_are_what_the_chip_compiles(
            self, monkeypatch):
        monkeypatch.delenv("HEAT_TPU_KMEANS_SUMS", raising=False)
        monkeypatch.delenv("HEAT_TPU_KMEANS_BLOCK_ROWS", raising=False)
        assert pk._kmeans_sums_mode() == "loop"
        assert pk._kmeans_block_rows() == 128


class TestFlashBlockwiseBackward:
    """The Pallas blockwise backward (``_flash_bwd_impl``) vs the dense jnp
    backward — same custom_vjp math, O(S·D) vs O(S²) memory."""

    def _grads(self, q, k, v, causal, dlse_seed=None):
        scale = 1.0 / math.sqrt(q.shape[-1])

        def f(q, k, v):
            out, lse = pk._flash_diff(q, k, v, scale, causal, 128, 128)
            if dlse_seed is None:
                return (out.astype(jnp.float32) ** 2).sum()
            # fold lse into the loss so the dlse cotangent is nonzero —
            # exactly what ring attention's merge does
            w = jax.random.normal(jax.random.PRNGKey(dlse_seed), lse.shape)
            return (out.astype(jnp.float32) ** 2).sum() + (lse * w).sum()

        return jax.grad(f, argnums=(0, 1, 2))(q, k, v)

    @pytest.mark.parametrize("causal", [False, True])
    @pytest.mark.parametrize("sq,sk", [(192, 192), (100, 260)])
    def test_matches_dense_backward(self, causal, sq, sk, force_pallas):
        kq, kk, kv = jax.random.split(jax.random.PRNGKey(0), 3)
        q = jax.random.normal(kq, (1, 2, sq, 16), jnp.float32)
        k = jax.random.normal(kk, (1, 2, sk, 16), jnp.float32)
        v = jax.random.normal(kv, (1, 2, sk, 16), jnp.float32)
        got = self._grads(q, k, v, causal, dlse_seed=7)
        pk.set_pallas(False)  # dense path of the same custom_vjp
        want = self._grads(q, k, v, causal, dlse_seed=7)
        for g, w, name in zip(got, want, "qkv"):
            np.testing.assert_allclose(
                np.asarray(g), np.asarray(w), rtol=2e-4, atol=2e-4,
                err_msg=f"d{name} mismatch (causal={causal})")

    def test_bf16_inputs(self, force_pallas):
        kq, kk, kv = jax.random.split(jax.random.PRNGKey(1), 3)
        q = jax.random.normal(kq, (1, 1, 128, 32), jnp.bfloat16)
        k = jax.random.normal(kk, (1, 1, 128, 32), jnp.bfloat16)
        v = jax.random.normal(kv, (1, 1, 128, 32), jnp.bfloat16)
        dq, dk, dv = self._grads(q, k, v, causal=True)
        assert dq.dtype == jnp.bfloat16 and dk.dtype == jnp.bfloat16
        assert np.isfinite(np.asarray(dq, np.float32)).all()
        pk.set_pallas(False)
        wq, wk, wv = self._grads(q, k, v, causal=True)
        np.testing.assert_allclose(
            np.asarray(dq, np.float32), np.asarray(wq, np.float32),
            rtol=0.1, atol=0.1)


def _bf16_close(got, want32, name):
    """``got`` (bfloat16) against the float32 path's result rounded to
    bfloat16: every element within one bfloat16 ulp of
    itself or 2^-14 of the tensor's largest magnitude, whichever is larger —
    a sum taken in another order may cross one rounding edge, no more."""
    got = np.asarray(jnp.asarray(got).astype(jnp.bfloat16).astype(jnp.float32))
    want = np.asarray(jnp.asarray(want32).astype(jnp.bfloat16).astype(jnp.float32))
    assert np.isfinite(want).all(), name
    ulp = 2.0 ** (np.floor(np.log2(np.maximum(np.abs(want), 1e-30))) - 7)
    tol = np.maximum(ulp, 2.0 ** -14 * np.abs(want).max())
    bad = np.abs(got - want) > tol
    assert not bad.any(), (
        f"{name}: {int(bad.sum())} of {bad.size} beyond one bfloat16 ulp; "
        f"worst {np.abs(got - want).max():.3e} against {tol[bad].min():.3e}")


class TestFlashBf16Operands:
    """bfloat16 ``q``/``k``/``v``/``dout`` go to the MXU as they arrive
    (exact products: one pass) and the float32 tiles the kernels compute
    (``p``, ``ds``) as two bfloat16 terms. That must read what the float32
    path (``HIGHEST`` on the same values upcast) reads, to bfloat16 rounding."""

    @pytest.mark.parametrize("sq,sk,blocks", [
        (384, 384, 256), (256, 384, 256),    # one and a half blocks of 256:
        # a padded tail on both axes; sq < sk: the end-aligned diagonal
        (384, 384, None), (256, 384, None),  # the blocks the path defaults to
        (1100, 1100, None),                  # three of those, and a tail
    ])
    @pytest.mark.parametrize("d", [64, 128])
    @pytest.mark.parametrize("causal", [True, False])
    def test_reads_what_the_float32_path_reads(self, causal, d, sq, sk, blocks,
                                               force_pallas):
        ks = jax.random.split(jax.random.PRNGKey(sq + d + causal), 5)
        q, k, v, dout = (
            jax.random.normal(kk, (1, 2, n, d), jnp.bfloat16)
            for kk, n in zip(ks, (sq, sk, sk, sq)))
        dlse = jax.random.normal(ks[4], (1, 2, sq), jnp.float32)
        scale = 1.0 / math.sqrt(d)
        up = lambda t: t.astype(jnp.float32)

        (out, lse), vjp = jax.vjp(
            lambda q, k, v: pk.flash_attention(
                q, k, v, scale=scale, causal=causal, return_lse=True,
                block_q=blocks, block_k=blocks),
            q, k, v)
        dq, dk, dv = vjp((dout, dlse))
        assert {t.dtype for t in (out, dq, dk, dv)} == {jnp.dtype(jnp.bfloat16)}
        assert lse.dtype == jnp.float32

        out32, lse32 = pk._flash_impl(up(q), up(k), up(v), scale, causal, 256, 256)
        # the backward of the float32 path on the SAME residuals: the
        # bfloat16 ``out`` and its lse, as the custom_vjp saved them
        dq32, dk32, dv32 = pk._flash_bwd_impl(
            up(q), up(k), up(v), up(out), lse, up(dout), dlse, scale, causal,
            256, 256)
        for name, got, want in (("out", out, out32), ("dq", dq, dq32),
                                ("dk", dk, dk32), ("dv", dv, dv32)):
            _bf16_close(got, want, f"{name} (causal={causal}, d={d}, {sq}x{sk})")
        # lse is float32 on both paths: held to float32 rounding, which is
        # well inside a bfloat16 ulp
        np.testing.assert_allclose(np.asarray(lse), np.asarray(lse32),
                                   rtol=2e-6, atol=2e-6)

    def test_mixed_dtypes_keep_float32_products(self, force_pallas):
        """A float32 ``k`` beside bfloat16 ``q``/``v`` promotes: the kernels
        take the ``HIGHEST`` path and read what all-float32 inputs read."""
        ks = jax.random.split(jax.random.PRNGKey(5), 3)
        q, k, v = (jax.random.normal(kk, (1, 1, 256, 64), jnp.bfloat16)
                   for kk in ks)
        up = lambda t: t.astype(jnp.float32)
        out, lse = pk.flash_attention(q, up(k), v, causal=True, return_lse=True)
        out32, lse32 = pk.flash_attention(up(q), up(k), up(v), causal=True,
                                          return_lse=True)
        assert out.dtype == jnp.bfloat16
        np.testing.assert_array_equal(np.asarray(lse), np.asarray(lse32))
        np.testing.assert_array_equal(
            np.asarray(out), np.asarray(out32.astype(jnp.bfloat16)))


class TestInterpretVmaHazard:
    """force_pallas + the flagship's check_vma=True shard_map must work on
    the CPU mesh: the interpret-mode Pallas HLO interpreter rejects
    mixed-vma operands, so attention falls back to the jnp path there
    (``interpret_vma_hazard``); on real TPU the kernels stay on."""

    def test_transformer_train_step_with_force_pallas(self, force_pallas):
        import jax as _jax

        if len(_jax.devices()) < 4:
            pytest.skip("needs 4 devices")
        import optax
        from heat_tpu.nn.transformer import TransformerLM, TransformerLMConfig

        grid = ht.MeshGrid((1, 1, 1, 4), ("dp", "pp", "tp", "sp"),
                           devices=jax.devices()[:4])
        cfg = TransformerLMConfig(vocab=32, d_model=8, n_heads=2, n_layers=1,
                                  d_ff=16)
        model = TransformerLM(grid, cfg)
        params = model.init(0)
        tx = optax.sgd(0.05)
        opt = tx.init(params)
        step = model.make_train_step(tx)
        toks = model.shard_batch(
            np.random.default_rng(0).integers(0, 32, (2, 16)))
        params, opt, lval = step(params, opt, toks)
        assert np.isfinite(float(lval))

    def test_hazard_helper(self):
        x = jnp.zeros((4, 4))
        assert pk.interpret_vma_hazard(x) is False  # no vma, no hazard

    def test_bwd_with_vma_carrying_cotangent(self, force_pallas):
        import jax as _jax

        if len(_jax.devices()) < 4:
            pytest.skip("needs 4 devices")
        """Replicated q/k/v pass the forward guard, but a loss mixing the
        output with mesh-varying data hands the bwd a vma-carrying dout —
        the bwd must fall back to the dense path in interpret mode."""
        from jax.sharding import PartitionSpec as P

        mesh = jax.sharding.Mesh(np.array(jax.devices()[:4]), ("x",))
        q = jax.random.normal(jax.random.PRNGKey(0), (1, 1, 128, 8))
        w = jnp.arange(4 * 128, dtype=jnp.float32).reshape(4, 128)

        def body(q_rep, w_shard):
            def loss(q_):
                out = pk.flash_attention(q_, q_, q_, causal=True)
                return (out[0, 0] * w_shard.T).sum()  # vma-carrying cotangent

            return jax.grad(loss)(q_rep)

        from heat_tpu.core._compat import shard_map
        g = shard_map(
            body, mesh=mesh, in_specs=(P(), P("x")), out_specs=P("x"),
            check_vma=True)(q, w)
        assert np.isfinite(np.asarray(g)).all()
