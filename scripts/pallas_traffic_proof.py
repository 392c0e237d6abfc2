#!/usr/bin/env python
"""Interpret-mode traffic proof for the Pallas kernels (the half of the
Pallas story that needs no chip).

For each kernel this script emits:

1. **Numerics**: the kernel (interpret mode — same kernel code Mosaic
   compiles) matches its jnp/XLA reference implementation.
2. **HBM traffic accounting**: bytes each grid step DMAs in/out, derived
   from the kernels' OWN BlockSpecs and grids (the same shapes the
   wrappers pass to ``pallas_call``), vs the bytes the multi-pass XLA path
   moves for the same result. This is the measurable basis of the
   projected speedups for the bandwidth-bound workloads:

   - KMeans Lloyd step: the fused kernel streams X once per iteration;
     the XLA path's separate fusions read it twice (roofline:
     65.6% HBM utilization at bench size -> a 1-pass kernel is worth up
     to ~2x, bounded by the non-X terms).
   - cdist: the fused tile writes each distance block once; the XLA
     expansion materializes the squared-distance matrix, re-reads it for
     the sqrt, and writes again — 3x the output-matrix traffic.
   - flash attention: O(S*D + S) per-block intermediates instead of the
     dense path's O(Sq*Sk) probability matrix in HBM.

Block revisits with constant index maps (centroids, the resident Q tile)
are counted at both bounds: ``*_hbm_worst`` assumes every grid step
re-DMAs them, ``*_hbm_best`` assumes Mosaic keeps them VMEM-resident.
X-pass claims hold at either bound.

Writes PALLAS_TRAFFIC_r05.json. Run:
    JAX_PLATFORMS=cpu \
        python scripts/pallas_traffic_proof.py
"""

import json
import os
import sys
import time

import numpy as np

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

import heat_tpu as ht  # noqa: E402  (configures x64 + matmul precision)
from heat_tpu.core import pallas_kernels as pk  # noqa: E402


def _bytes(shape, dtype) -> int:
    return int(np.prod(shape)) * np.dtype(dtype).itemsize


def kmeans_proof(n=4096, d=64, k=8, block_rows=1024) -> dict:
    x = np.random.default_rng(0).random((n, d), np.float32)
    c = np.random.default_rng(1).random((k, d), np.float32)
    mask = np.ones((n, 1), np.float32)

    # numerics: kernel (interpret) vs the jnp Lloyd partials
    sums, counts, inertia = pk.kmeans_step_tile(
        jnp.asarray(x), jnp.asarray(c), jnp.asarray(mask),
        block_rows=block_rows)
    d2 = ((x[:, None, :] - c[None, :, :]) ** 2).sum(-1)
    labels = d2.argmin(1)
    ref_sums = np.zeros((k, d), np.float32)
    np.add.at(ref_sums, labels, x)
    ref_counts = np.bincount(labels, minlength=k).astype(np.float32)
    ok = (np.allclose(np.asarray(sums), ref_sums, rtol=2e-2, atol=2e-2)
          and np.allclose(np.asarray(counts), ref_counts)
          and np.isclose(float(inertia), float(d2.min(1).sum()), rtol=2e-2))

    # traffic: from the kernel's grid/BlockSpecs (mirrors _kmeans_step_tile)
    kp = 128  # k rounded up to the lane width
    bm = block_rows
    steps = (n + bm - 1) // bm
    f32 = np.dtype(np.float32).itemsize
    in_x = steps * bm * d * f32          # X tile: fresh block every step
    in_c = steps * kp * d * f32          # centroids: constant index map
    in_m = steps * bm * 1 * f32          # mask
    out = (kp * d + 8 * kp + 8 * 128) * f32  # flushed once, last step
    kernel_worst = in_x + in_c + in_m + out
    kernel_best = in_x + kp * d * f32 + in_m + out
    # XLA Lloyd step (optimized HLO at bench shape): X feeds two separate
    # fusions (assignment GEMM+argmin; one-hot update GEMM) -> 2 passes,
    # plus the same small centroid/score traffic
    xla = 2 * n * d * f32 + in_m + out
    return {
        "kernel": "kmeans_step_tile",
        "numerics_ok": bool(ok),
        "shape": f"n{n}_d{d}_k{k}_bm{block_rows}",
        "x_passes_kernel": 1,
        "x_passes_xla": 2,
        "kernel_hbm_best": kernel_best,
        "kernel_hbm_worst": kernel_worst,
        "xla_hbm": xla,
        "traffic_ratio_best": round(xla / kernel_best, 3),
        "traffic_ratio_worst": round(xla / kernel_worst, 3),
    }


def cdist_proof(n=1024, m=1024, d=18, bm=256, bn=256) -> dict:
    x = np.random.default_rng(0).random((n, d), np.float32)
    y = np.random.default_rng(1).random((m, d), np.float32)
    got = pk.cdist_tile(jnp.asarray(x), jnp.asarray(y), block_m=bm,
                        block_n=bn)
    ref = np.sqrt(np.maximum(
        (x * x).sum(1)[:, None] + (y * y).sum(1)[None] - 2 * x @ y.T, 0))
    ok = np.allclose(np.asarray(got), ref, atol=2e-3)

    f32 = 4
    gi, gj = (n + bm - 1) // bm, (m + bn - 1) // bn
    in_x = gi * gj * bm * d * f32        # X tile re-read per column step
    in_y = gi * gj * bn * d * f32
    out = n * m * f32                    # each distance block written ONCE
    kernel_worst = in_x + in_y + out
    kernel_best = n * d * f32 + m * d * f32 + out
    # XLA expansion: inputs once + write d^2 matrix, re-read it for the
    # sqrt, write the result -> 3 passes over the (n, m) output
    xla = (n * d + m * d) * f32 + 3 * n * m * f32
    return {
        "kernel": "cdist_tile",
        "numerics_ok": bool(ok),
        "shape": f"n{n}_m{m}_d{d}_bm{bm}_bn{bn}",
        "output_passes_kernel": 1,
        "output_passes_xla": 3,
        "kernel_hbm_best": kernel_best,
        "kernel_hbm_worst": kernel_worst,
        "xla_hbm": xla,
        "traffic_ratio_best": round(xla / kernel_best, 3),
        "traffic_ratio_worst": round(xla / kernel_worst, 3),
        "note": "ratios at the proof shape; at the bench shape (40k x 18) "
                "the output matrix dominates and the ratio approaches the "
                "3x output-pass bound",
    }


def flash_proof(B=2, H=4, S=512, D=64, bq=256, bk=256) -> dict:
    rng = np.random.default_rng(0)
    q = rng.standard_normal((B, H, S, D)).astype(np.float32)
    k = rng.standard_normal((B, H, S, D)).astype(np.float32)
    v = rng.standard_normal((B, H, S, D)).astype(np.float32)
    scale = 1.0 / np.sqrt(D)
    out, lse = pk.flash_attention(jnp.asarray(q), jnp.asarray(k),
                                  jnp.asarray(v), scale=scale,
                                  return_lse=True)
    # dense reference ((B, H, S, D) layout, the kernel's native one)
    s = np.einsum("bhqd,bhkd->bhqk", q, k) * scale
    p = np.exp(s - s.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    ref = np.einsum("bhqk,bhkd->bhqd", p, v)
    ok = np.allclose(np.asarray(out), ref, atol=2e-3)

    f32 = 4
    gq, gk = (S + bq - 1) // bq, (S + bk - 1) // bk
    per_head = (gq * gk * (bk * D) * 2 * f32      # K,V tiles (inner axis)
                + gq * bq * D * f32               # Q tile per outer step
                + S * D * f32 + S * f32)          # out + lse written once
    kernel = B * H * per_head
    # dense path: the (S, S) probability matrix hits HBM twice per head
    # (softmax write + read for the PV GEMM) when S*S exceeds cache
    dense = B * H * ((3 * S * D) * f32 + 2 * S * S * f32 + S * D * f32)
    return {
        "kernel": "flash_attention",
        "numerics_ok": bool(ok),
        "shape": f"B{B}_H{H}_S{S}_D{D}_bq{bq}_bk{bk}",
        "intermediate_kernel": "O(S*D + S) per block",
        "intermediate_dense": "O(S^2) probability matrix",
        "kernel_hbm": kernel,
        "dense_hbm": dense,
        "traffic_ratio": round(dense / kernel, 3),
        "scaling_note": "kernel traffic grows as S*(S/bk)*D (K/V restream) "
                        "vs the dense path's S^2 matrix: ratio ~ "
                        "2*bk/(2*D)=4x at these blocks and grows with S",
    }


def main() -> None:
    pk.set_pallas(True)  # interpret mode on CPU exercises the kernel code
    results = [kmeans_proof(), kmeans_proof(block_rows=256),
               cdist_proof(), flash_proof()]
    artifact = {
        "note": "Interpret-mode numerics + BlockSpec-derived HBM traffic "
                "accounting for the three Pallas kernels (no chip "
                "needed; kernel times on the chip are not measured). Traffic "
                "numbers are computed from the kernels' own grids and "
                "block shapes, not asserted.",
        "date": time.strftime("%Y-%m-%d"),
        "command": "JAX_PLATFORMS=cpu python "
                   "scripts/pallas_traffic_proof.py",
        "all_numerics_ok": all(r["numerics_ok"] for r in results),
        "kernels": results,
    }
    print(json.dumps(artifact, indent=1))
    with open(os.path.join(_REPO, "PALLAS_TRAFFIC_r05.json"), "w") as f:
        json.dump(artifact, f, indent=1)
    sys.exit(0 if artifact["all_numerics_ok"] else 1)


if __name__ == "__main__":
    main()
