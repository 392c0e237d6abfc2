"""Benchmark entrypoint: ONE process, on the chip, or a non-zero exit.

``python bench.py`` measures in THIS process on whatever accelerator JAX
finds and prints one JSON line that names the device it ran on
(``platform``, ``device_kind``, ``device_count``). With no TPU it exits 2:
there is no CPU fallback, no replay of an older record and no stage timed on
a virtual CPU mesh — a number from a CPU run is never written under the name
of a device metric. Every figure here predates the on-chip benchmark this
round is building (ROADMAP Speed 1b) and none has been re-measured; the
``--*-bench`` stage mains are kept for that PR to turn into cells.

Workload: the reference's headline benchmark — KMeans Lloyd iterations on a
synthetic ``(n, 64)`` float32 split DNDarray (reference
``benchmarks/kmeans/heat-cpu.py:20-26``, k=8). ``value`` is sustained Lloyd
iterations/second of the fused jitted step (assignment GEMM + argmin +
one-hot update GEMM + psum).

Timing: every timed run ends in a scalar device-to-host fetch, and the
constant per-call overhead is cancelled by timing the SAME compiled
executable (``lax.fori_loop`` with a runtime trip count) at two trip counts
and differencing.

``vs_baseline`` compares against a single-process PyTorch CPU implementation
of the same iteration, linearly extrapolated from a smaller sample.

Compile cache: where ``JAX_COMPILATION_CACHE_DIR`` is set it is left to
JAX; otherwise the one fixed directory ``.jax_cache/`` in the checkout.
"""

import json
import os
import sys
import time

import numpy as np

N_FULL = 1 << 23  # 8.4M points × 64 features ≈ 2.1 GB f32 (accelerator run)
N_TORCH = 1 << 19  # torch baseline sample, extrapolated linearly
D_FEATS = 64  # KMeans workload shape (reference benchmarks/kmeans: k=8, 64 feats)
K_CLUSTERS = 8

# Published per-chip peaks, keyed by a ``device_kind`` prefix:
# (bf16 matmul TFLOP/s, HBM GB/s). v5e: 197 bf16 TFLOP/s, 16 GB @ 819 GB/s.
_HW_PEAKS = {
    "TPU v5 lite": (197.0, 819.0),
    "TPU v5e": (197.0, 819.0),
    "TPU v5p": (459.0, 2765.0),
    "TPU v4": (275.0, 1228.0),
    "TPU v6": (918.0, 1640.0),
}


def _hw_peaks():
    """(bf16 peak TFLOP/s, HBM peak GB/s) of device 0 (Google Cloud
    documentation, per chip). A device that is not in the table is an
    error, not a default."""
    import jax

    kind = jax.devices()[0].device_kind
    for prefix, peaks in _HW_PEAKS.items():
        if kind.startswith(prefix):
            return peaks
    raise RuntimeError(
        f"bench: no published peaks for device_kind {kind!r}; add its row "
        f"(with a source) to _HW_PEAKS")


def matmul_bf16_tflops(m: int = 8192) -> float:
    """Sustained bf16 matmul TFLOP/s of the framework's GEMM path — the MXU
    utilization probe that contextualizes every other figure. A chained
    ``x = (x @ w) * s`` ``fori_loop`` (one compiled executable, data-dependent
    so XLA cannot elide iterations) is timed at two trip counts and
    differenced, exactly like the KMeans number. The elementwise rescale
    fuses into the GEMM epilogue and keeps magnitudes in bf16 range."""
    import jax
    import jax.numpy as jnp

    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (m, m), jnp.bfloat16)
    w = jax.random.normal(jax.random.fold_in(key, 1), (m, m), jnp.bfloat16)
    scale = jnp.bfloat16(1.0 / m)

    @jax.jit
    def run(x, w, iters):
        return jax.lax.fori_loop(0, iters, lambda _, a: (a @ w) * scale, x)

    def timed(iters: int) -> float:
        t0 = time.perf_counter()
        out = run(x, w, iters)
        float(np.asarray(out[0, 0]))  # real-completion fetch
        return time.perf_counter() - t0

    timed(2)  # compile + warm
    lo, hi = 8, 40  # ≥180 ms of MXU work between the trip counts at m=8192
    t_lo = min(timed(lo) for _ in range(3))
    t_hi = min(timed(hi) for _ in range(3))
    per_iter = (t_hi - t_lo) / (hi - lo)
    if per_iter <= 0:
        per_iter = t_hi / hi
    return 2.0 * m**3 / per_iter / 1e12


def tpu_kmeans_iter_per_s(n: int, d: int = D_FEATS, k: int = K_CLUSTERS,
                          dtype: str = None) -> float:
    """``dtype="bfloat16"`` measures the half-precision-storage variant
    (mixed-precision Lloyd step: bf16 HBM reads + MXU inputs, f32
    accumulation — half the traffic of the bandwidth-bound iteration)."""
    import heat_tpu as ht
    from heat_tpu.cluster.kmeans import _lloyd_fori_fn

    import jax.numpy as jnp

    ht.random.seed(0)
    x = ht.random.rand(n, d, dtype=ht.float32, split=0)
    comm = x.comm
    xp = x.larray if dtype is None else x.larray.astype(jnp.dtype(dtype))
    centroids = jnp.asarray(np.random.default_rng(0).random((k, d), dtype=np.float32))
    run = _lloyd_fori_fn(xp.shape, jnp.dtype(xp.dtype), k, n, comm)

    def timed(iters: int) -> float:
        t0 = time.perf_counter()
        c, inertia, shift = run(xp, centroids, iters)
        float(np.asarray(inertia))  # forces real completion on remote backends
        return time.perf_counter() - t0

    timed(1)  # compile + warm
    lo, hi = 2, 22
    t_lo = min(timed(lo) for _ in range(3))
    t_hi = min(timed(hi) for _ in range(3))
    per_iter = (t_hi - t_lo) / (hi - lo)
    if per_iter <= 0:
        # jitter exceeded the compute delta; fall back to the conservative
        # upper bound (whole-call time over the larger trip count)
        per_iter = t_hi / hi
    return 1.0 / per_iter


def tpu_cdist_gbps(n: int, d: int = 18, expand: bool = True) -> float:
    """Sustained GB/s of the ring cdist at the reference's distance_matrix
    shape family (SUSY: 40k x 18, ``benchmarks/distance_matrix``): bytes of
    the produced distance matrix per second, timed by differencing two
    repeat counts of the same compiled executable (same methodology as the
    KMeans number).

    The reference benchmark measures BOTH forms
    (``heat-cpu.py:20-32``: quadratic_expansion False then True); the
    primary figure here is ``expand=True`` — the GEMM expansion is the MXU
    form and the TPU-first choice — with the cancellation-exact diff form
    reported alongside as ``cdist_exact_gbps``."""
    import heat_tpu as ht

    ht.random.seed(1)
    x = ht.random.rand(n, d, dtype=ht.float32, split=0)

    def timed(reps: int) -> float:
        t0 = time.perf_counter()
        for _ in range(reps):
            dmat = ht.spatial.cdist(x, x, quadratic_expansion=expand)
        float(np.asarray(dmat.larray[0, 0]))  # real completion fetch
        return time.perf_counter() - t0

    timed(1)  # compile + warm
    lo, hi = 1, 3
    t_lo = min(timed(lo) for _ in range(2))
    t_hi = min(timed(hi) for _ in range(2))
    per_call = (t_hi - t_lo) / (hi - lo)
    if per_call <= 0:
        per_call = t_hi / hi
    out_bytes = float(n) * n * 4
    return out_bytes / per_call / 1e9


def tpu_resplit_gbps(n: int, d: int = D_FEATS) -> float:
    """Sustained GB/s of the explicit resplit engine at the KMeans shape
    family: bytes of an ``(n, d)`` f32 array moved through the planned
    split0→split1 reshard (ONE all-to-all + local reslice,
    ``heat_tpu/core/resharding.py``) per second. Same differenced
    two-repeat-count timing as every figure; the plan cache makes repeat
    calls reuse one compiled executable. On a single device the planner's
    degenerate local program is what's timed — still the production path."""
    import heat_tpu as ht

    ht.random.seed(3)
    x = ht.random.rand(n, d, dtype=ht.float32, split=0)

    def timed(reps: int) -> float:
        t0 = time.perf_counter()
        for _ in range(reps):
            y = x.resplit(1)
        float(np.asarray(y.larray[0, 0]))  # real completion fetch
        return time.perf_counter() - t0

    timed(1)  # compile + warm (plan cache miss happens here)
    lo, hi = 2, 6
    t_lo = min(timed(lo) for _ in range(2))
    t_hi = min(timed(hi) for _ in range(2))
    per_call = (t_hi - t_lo) / (hi - lo)
    if per_call <= 0:
        per_call = t_hi / hi
    return float(n) * d * 4 / per_call / 1e9


def transformer_train_metrics(B: int = 8, S: int = 1024, d_model: int = 1024,
                              n_layers: int = 8, n_heads: int = 16,
                              vocab: int = 32768) -> dict:
    """Flagship-model figure: full TransformerLM train step (fwd + bwd +
    adam, bf16 compute, ring attention, donated buffers) on one chip —
    tokens/second and the standard approximate train MFU
    (``(6·N_params + 12·L·S·d)·tokens`` FLOPs per step, PaLM-appendix
    accounting). Same two-trip-count differenced timing as every figure;
    the donated params/opt_state roll forward between timed calls."""
    import jax
    import jax.numpy as jnp
    import optax

    import heat_tpu as ht
    from heat_tpu.nn.transformer import TransformerLM, TransformerLMConfig

    grid = ht.MeshGrid((1, 1, 1, 1), ("dp", "pp", "tp", "sp"),
                       devices=jax.devices()[:1])
    cfg = TransformerLMConfig(vocab=vocab, d_model=d_model, n_heads=n_heads,
                              n_layers=n_layers, compute_dtype=jnp.bfloat16)
    model = TransformerLM(grid, cfg)
    state = {"p": model.init(0)}
    n_params = sum(int(x.size) for x in jax.tree_util.tree_leaves(state["p"]))
    tx = optax.adam(1e-3)
    state["o"] = tx.init(state["p"])
    step = model.make_train_step(tx)
    toks = model.shard_batch(
        np.random.default_rng(0).integers(0, vocab, (B, S)).astype(np.int32))

    def timed(steps: int) -> float:
        p, o = state["p"], state["o"]
        t0 = time.perf_counter()
        for _ in range(steps):
            p, o, loss = step(p, o, toks)
        float(np.asarray(loss))  # real-completion fetch
        dt = time.perf_counter() - t0
        state["p"], state["o"] = p, o  # donated originals are gone
        return dt

    timed(1)  # compile + warm
    lo, hi = 2, 10
    t_lo = min(timed(lo) for _ in range(2))
    t_hi = min(timed(hi) for _ in range(2))
    per_step = (t_hi - t_lo) / (hi - lo)
    if per_step <= 0:
        per_step = t_hi / hi
    tokens = float(B) * S
    flops_per_step = (6.0 * n_params + 12.0 * n_layers * S * d_model) * tokens
    return {
        "transformer_tokens_per_s": round(tokens / per_step, 1),
        "transformer_tflops": round(flops_per_step / per_step / 1e12, 2),
        "transformer_n_params": n_params,
        "transformer_shape": f"L{n_layers}_d{d_model}_h{n_heads}_B{B}_S{S}",
    }


def torch_kmeans_time_per_iter(n: int, d: int = D_FEATS, k: int = K_CLUSTERS,
                               iters: int = 3) -> float:
    """Reference-equivalent local Lloyd iteration in PyTorch (CPU)."""
    import torch

    g = torch.Generator().manual_seed(0)
    x = torch.rand((n, d), generator=g)
    c = torch.rand((k, d), generator=g)
    # warmup
    for _ in range(1):
        d2 = torch.cdist(x, c) ** 2
        labels = torch.argmin(d2, dim=1)
    t0 = time.perf_counter()
    for _ in range(iters):
        d2 = torch.cdist(x, c) ** 2
        labels = torch.argmin(d2, dim=1)
        onehot = torch.nn.functional.one_hot(labels, k).to(x.dtype)
        counts = onehot.sum(0)
        c = (onehot.T @ x) / counts.clamp(min=1.0).unsqueeze(1)
    t1 = time.perf_counter()
    return (t1 - t0) / iters


def _measure_main(n: int) -> None:
    """Measure on the chip in THIS process, print the JSON record(s); the
    last line is the fullest. Exits 2 when jax finds no TPU."""
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.stderr.write(
            f"bench: no TPU here (jax.devices()[0].platform == "
            f"{dev.platform!r}); the benchmark runs on the chip or fails.\n")
        sys.exit(2)
    if "JAX_COMPILATION_CACHE_DIR" not in os.environ:
        jax.config.update(
            "jax_compilation_cache_dir",
            os.path.join(os.path.dirname(os.path.abspath(__file__)),
                         ".jax_cache"))

    ips = tpu_kmeans_iter_per_s(n)
    t_torch_small = torch_kmeans_time_per_iter(min(n, N_TORCH))
    t_torch_full_est = t_torch_small * (n / min(n, N_TORCH))
    baseline_ips = 1.0 / t_torch_full_est

    # companion figures from BASELINE.json: ring-cdist GB/s at the reference
    # distance_matrix shape (40k x 18). ``cdist_gbps`` is
    # quadratic_expansion=False (the cancellation-exact form),
    # ``cdist_expand_gbps`` the GEMM-expansion form the reference benchmark
    # also measures (heat-cpu.py:28-32); and the planned split0->split1
    # reshard at the KMeans shape family. A figure that fails raises.
    n_cdist = 40_000
    cdist_gbps = round(tpu_cdist_gbps(n_cdist, expand=False), 3)
    cdist_expand_gbps = round(tpu_cdist_gbps(n_cdist, expand=True), 3)
    n_resplit = 1 << 22
    resplit_gbps = round(tpu_resplit_gbps(n_resplit), 3)

    # Roofline accounting (round-3 verdict: relate throughput to hardware
    # peak, not just report it). The Lloyd iteration's FLOP model counts the
    # two GEMMs (assignment x·cᵀ + update one-hotᵀ·x: 4·n·d·k); its traffic
    # model is the min-HBM bound of two passes over x (the GEMMs live in
    # separate fusions): 2·n·d·4 bytes f32. Arithmetic intensity is then
    # 4dk/(8d) = k/2 FLOP/byte — far below the MXU ridge (~240 on v5e), so
    # the iteration is bandwidth-bound and ``kmeans_hbm_util`` is the
    # meaningful utilization figure; ``kmeans_mfu`` is capped at
    # AI/ridge ≈ 1.7% by the workload, not the implementation.
    d_feats, k_cl = D_FEATS, K_CLUSTERS
    kmeans_tflops = 4.0 * n * d_feats * k_cl * ips / 1e12
    kmeans_hbm_gbps = 2.0 * n * d_feats * 4 * ips / 1e9
    peak_tf, peak_gb = _hw_peaks()
    ridge = peak_tf * 1e3 / peak_gb  # FLOP/byte at the roofline knee
    mm_tf = matmul_bf16_tflops()
    roofline = {
        "hw_peak_bf16_tflops": peak_tf,
        "hw_peak_hbm_gbps": peak_gb,
        "kmeans_tflops": round(kmeans_tflops, 3),
        "kmeans_mfu": round(kmeans_tflops / peak_tf, 4),
        "kmeans_mfu_roofline_cap": round(
            (4.0 * d_feats * k_cl) / (2.0 * d_feats * 4) / ridge, 4),
        "kmeans_hbm_gbps": round(kmeans_hbm_gbps, 1),
        "kmeans_hbm_util": round(kmeans_hbm_gbps / peak_gb, 3),
        "matmul_bf16_tflops": round(mm_tf, 1),
        "matmul_mfu": round(mm_tf / peak_tf, 3),
    }

    label = f"{n / 2 ** 20:.0f}M" if n >= 1 << 20 else str(n)
    record = {
        "metric": f"kmeans_lloyd_iterations_per_second_{label}_x64_k8_f32",
        "value": round(ips, 3),
        "unit": "iter/s",
        "vs_baseline": round(ips / baseline_ips, 3),
        "backend": dev.platform,
        "platform": dev.platform,
        "device_kind": dev.device_kind,
        "device_count": len(jax.devices()),
        "cdist_gbps": cdist_gbps,
        "cdist_expand_gbps": cdist_expand_gbps,
        "cdist_n": n_cdist,
        "resplit_gbps": resplit_gbps,
        "resplit_n": n_resplit,
        **roofline,
    }
    print(json.dumps(record), flush=True)

    # companion figures AFTER the base record is out: each prints a
    # superset record (the reader takes the LAST line). First the
    # half-precision-storage Lloyd: same workload, bf16 HBM traffic
    ips16 = tpu_kmeans_iter_per_s(n, dtype="bfloat16")
    record["kmeans_bf16_iter_per_s"] = round(ips16, 3)
    record["kmeans_bf16_hbm_util"] = round(
        2.0 * n * D_FEATS * 2 * ips16 / 1e9 / peak_gb, 3)
    print(json.dumps(record), flush=True)
    tr = transformer_train_metrics()
    tr["transformer_mfu"] = round(tr["transformer_tflops"] / peak_tf, 3)
    print(json.dumps({**record, **tr}), flush=True)


def _fusion_bench_main() -> None:
    """``--fusion-bench`` child: measure the lazy op-chain fusion engine on
    the 4-device CPU mesh this process was launched onto (a dispatch-
    overhead figure, pinned to the virtual CPU mesh like the serve stage).

    Three workloads, each timed eager (``HEAT_TPU_FUSION`` off) vs fused:

    * a 16-op elementwise chain on a split-0 ``(n, 64)`` f32 array — the
      ISSUE's headline shape: 16 dispatches + 15 materialized
      intermediates eager, ONE cached program fused;
    * a kmeans-style mixed chain (binary ops against a replicated row,
      scalar rescales, unary transcendentals) ending in a split-axis
      reduction — since PR 4 the reduction fuses INTO the program;
    * a reduction-terminated chain proper (``fusion_reduce_chain_*``):
      center → square → rescale → split-axis ``sum`` → normalize, i.e.
      the ``ht.mean((x-mu)**2)`` moment shape — eager pays the elementwise
      programs plus a separate reduce program and a full-size HBM
      intermediate; fused it is ONE program whose elementwise values never
      leave registers before the shard-local reduce;
    * a GEMM + epilogue chain (``fusion_gemm_chain_*``): row-split
      ``matmul`` → bias → activation → split-axis ``sum`` — the PR 5
      contraction-node shape. Eager pays the zero-fill pass, the GEMM
      dispatch AND one dispatch per epilogue op with full-size
      intermediates; fused it is ONE shard_map program whose GEMM plan
      carries zero collectives and whose reduce psum is the only
      all-reduce. Sized so dispatch+traffic dominates the MXU-less CPU
      GEMM (acceptance ≥ 1.5×);
    * a layout-change pipeline (``fusion_resplit_chain_*``): elementwise
      chain → ``resplit(0→1)`` → elementwise chain — the PR 6
      resplit-node shape. Eager compiles THREE programs (chain, the
      planner's reshard, chain) and materializes the intermediate at
      full shard size on both sides of the boundary; fused it is ONE
      shard_map program with the planner's single all-to-all placed
      mid-body (acceptance ≥ 1.5×);
    * a whole TRAIN STEP (``fusion_train_step_*``): tanh-MLP loss +
      ``fusion.value_and_grad`` + SGD update over DNDarray params — the
      PR 7 differentiable-tape shape. Eager pays a fresh grad trace plus
      per-op dispatch and the update's chain flushes every step; under
      ``fusion.trace_step`` the whole step is ONE cached donated
      executable (acceptance ≥ 2×, the ISSUE 7 figure).

    Prints ONE JSON line with the speedups and the fusion program-cache
    stats proving the steady state runs zero recompiles.
    """
    import jax

    import heat_tpu as ht
    from heat_tpu.core import fusion

    comm = ht.get_comm()
    n, d = 1 << 15, D_FEATS
    rng = np.random.default_rng(0)
    xd = rng.standard_normal((n, d)).astype(np.float32)
    wd = rng.standard_normal((n, d)).astype(np.float32)
    rowd = rng.standard_normal((d,)).astype(np.float32)
    x = ht.array(xd, split=0)
    w = ht.array(wd, split=0)
    row = ht.array(rowd)

    def chain16(a):
        # 16 ht-level ops, arithmetic/memory-bound mix (2 transcendentals):
        # eager reads+writes the full array per op; fused reads the inputs
        # once and writes once — the traffic elimination IS the speedup
        # (an all-transcendental chain is compute-bound either way)
        t = a * 0.5
        t = t + w
        t = t - 0.25
        t = t * a
        t = abs(t)
        t = t + row
        t = t * 1.25
        t = ht.sqrt(t + 2.0)
        t = t - w
        t = t * 0.75
        t = t + a
        t = ht.tanh(t)
        t = t * t
        t = t - 0.125
        t = t + 0.5
        t = t * 2.0
        return t

    def kmeans_mixed(a):
        # the Lloyd-style pre-assignment normalize: center against a
        # replicated row, rescale, clamp tails, then a split-axis reduce
        t = (a - row) * 0.75
        t = t * t + t
        t = ht.tanh(t / 2.0)
        t = abs(t) + 0.125
        return t.sum(axis=0)

    def reduce_chain(a):
        # the ht.mean((x-mu)**2) moment shape: elementwise chain whose ONLY
        # consumer is a split-axis reduction — the tape folds the mask,
        # the shard-local reduce and the one psum into the same program
        t = (a - row) * 0.5
        t = t * t
        t = t + 1.0
        t = t * w
        return t.sum(axis=0) * (1.0 / n)

    # GEMM stage operands: smaller n so the (MXU-less) CPU GEMM itself does
    # not drown the dispatch/traffic savings the fusion engine delivers
    ng, dg = 1 << 14, 32
    xg = ht.array(rng.standard_normal((ng, dg)).astype(np.float32), split=0)
    wg = ht.array(rng.standard_normal((dg, dg)).astype(np.float32))
    bg = ht.array(rng.standard_normal((dg,)).astype(np.float32))

    def gemm_chain(_a):
        # row-split GEMM (zero-collective plan) + bias + activation +
        # split-axis reduce (one psum) — the serve/transformer hot shape
        t = ht.matmul(xg, wg) + bg
        t = ht.tanh(t * 0.5)
        t = t * t + t
        return t.sum(axis=0)

    def resplit_chain(a):
        # chain → resplit(0→1) → chain: eager pays three programs and two
        # full-size materializations around the layout change; fused the
        # planner's ONE all-to-all rides mid-body in one program
        t = (a - row) * 0.5
        t = ht.tanh(t) + 0.25
        t = t.resplit(1)
        t = t * 2.0 + 0.125
        t = abs(t) + 1.0
        return t

    def timed(build, reps: int) -> float:
        out = build(x)  # compile + warm (cache miss lands here)
        jax.block_until_ready(out.larray)
        t0 = time.perf_counter()
        for _ in range(reps):
            out = build(x)
            jax.block_until_ready(out.larray)
        return (time.perf_counter() - t0) / reps * 1e3

    record = {"fusion_devices": comm.size, "fusion_n": n}
    for label, build, reps in (("chain16", chain16, 30),
                               ("kmeans_mixed", kmeans_mixed, 30),
                               ("reduce_chain", reduce_chain, 30),
                               ("gemm_chain", gemm_chain, 30),
                               ("resplit_chain", resplit_chain, 30)):
        with fusion.override(False):
            t_eager = min(timed(build, reps) for _ in range(2))
        with fusion.override(True):
            t_fused = min(timed(build, reps) for _ in range(2))
        record[f"fusion_{label}_eager_ms"] = round(t_eager, 3)
        record[f"fusion_{label}_fused_ms"] = round(t_fused, 3)
        record[f"fusion_{label}_speedup"] = round(t_eager / t_fused, 2)
    with fusion.override(True):
        cstats0 = fusion.program_cache().stats()
        for _ in range(5):
            jax.block_until_ready(chain16(x).larray)
            jax.block_until_ready(reduce_chain(x).larray)
            jax.block_until_ready(gemm_chain(x).larray)
            jax.block_until_ready(resplit_chain(x).larray)
        cstats = fusion.program_cache().stats()
    record["fusion_steady_misses"] = cstats["misses"] - cstats0["misses"]

    # ---- train-step stage: loss + grad + update as ONE executable ---- #
    nt, dt, ht_ = 1 << 13, 64, 32
    bx = ht.array(rng.standard_normal((nt, dt)).astype(np.float32), split=0)
    by = ht.array(rng.standard_normal((nt, 1)).astype(np.float32), split=0)
    p0 = {"w1": ht.array(rng.standard_normal((dt, ht_)).astype(np.float32)),
          "b1": ht.array(np.zeros(ht_, np.float32)),
          "w2": ht.array(rng.standard_normal((ht_, 1)).astype(np.float32))}

    def train_step(p, a, b):
        def loss_fn(q, xa, yb):
            hdn = ht.tanh(ht.matmul(xa, q["w1"]) + q["b1"])
            dlt = ht.matmul(hdn, q["w2"]) - yb
            return ht.mean(dlt * dlt)

        lval, g = fusion.value_and_grad(loss_fn)(p, a, b)
        return {k: p[k] - 0.05 * g[k] for k in p}, lval

    def timed_steps(step_fn, reps: int) -> float:
        p = dict(p0)
        p, lval = step_fn(p, bx, by)  # compile/trace warmup
        jax.block_until_ready(lval.larray)
        t0 = time.perf_counter()
        for _ in range(reps):
            p, lval = step_fn(p, bx, by)
        jax.block_until_ready(lval.larray)
        return (time.perf_counter() - t0) / reps * 1e3

    with fusion.override(True), fusion.step_override(False):
        t_eager = min(timed_steps(train_step, 10) for _ in range(2))
    traced = fusion.trace_step(train_step)
    with fusion.override(True), fusion.step_override(True):
        t_fused = min(timed_steps(traced, 10) for _ in range(2))
        sstats0 = fusion.program_cache().stats()
        p = dict(p0)
        for _ in range(5):
            p, lval = traced(p, bx, by)
        jax.block_until_ready(lval.larray)
        sstats = fusion.program_cache().stats()
    record["fusion_train_step_eager_ms"] = round(t_eager, 3)
    record["fusion_train_step_fused_ms"] = round(t_fused, 3)
    record["fusion_train_step_speedup"] = round(t_eager / t_fused, 2)
    record["fusion_train_step_steady_misses"] = \
        sstats["misses"] - sstats0["misses"]

    # ---- quantized packed collectives: step bytes + wall, quant/exact #
    # Fail-soft INSIDE the stage (like the outer stages): a quant-path
    # regression must not take down the whole fusion record. Wall time on
    # the CPU mesh is a dispatch-overhead surrogate (no real wire): the
    # honest figure is the audited collective-wire-byte reduction; wall
    # time on the chip is not measured.
    try:
        import optax

        from heat_tpu.nn.transformer import (TransformerLM,
                                             TransformerLMConfig)
        from heat_tpu.utils import hlo_audit

        ndev = comm.size
        grid = ht.MeshGrid((ndev, 1, 1, 1), ("dp", "pp", "tp", "sp"))
        cfgq = TransformerLMConfig(
            vocab=128, d_model=64, n_heads=4, n_layers=2, d_ff=128)
        modelq = TransformerLM(grid, cfgq)
        toksq = modelq.shard_batch(np.random.default_rng(0).integers(
            0, cfgq.vocab, (4 * ndev, 16)).astype(np.int32))
        txq = optax.adam(1e-2)

        def timed_quant(codec, reps=20):
            with fusion.quant_override(codec):
                step = modelq.make_train_step(txq)
                hlo = step.lower(modelq.init(0), txq.init(modelq.init(0)),
                                 toksq).compile().as_text()
                p, o = modelq.init(0), txq.init(modelq.init(0))
                p, o, l = step(p, o, toksq)  # warm
                jax.block_until_ready(l)
                t0 = time.perf_counter()
                for _ in range(reps):
                    p, o, l = step(p, o, toksq)
                jax.block_until_ready(l)
                wall = (time.perf_counter() - t0) / reps * 1e3
            return wall, hlo_audit.collective_bytes(
                hlo, world=ndev)["total_wire_bytes"]

        qstats0 = fusion.stats()
        t_exact, b_exact = timed_quant(None)
        t_int8, b_int8 = timed_quant("int8")
        qstats = fusion.stats()
        record["fusion_quant_step_exact_ms"] = round(t_exact, 3)
        record["fusion_quant_step_quant_ms"] = round(t_int8, 3)
        record["fusion_quant_step_wire_bytes_exact"] = int(b_exact)
        record["fusion_quant_step_wire_bytes_quant"] = int(b_int8)
        record["fusion_quant_step_byte_reduction"] = round(
            b_exact / max(b_int8, 1), 2)
        # STAGE deltas (snapshot-diffed like the steady-state blocks):
        # with a codec armed in the ambient env the earlier stages tick
        # the same counters, and lifetime totals would not compare
        # across runs with different stage sets
        record["fusion_quant_collectives"] = (
            qstats["quant_collectives"] - qstats0["quant_collectives"])
        record["fusion_quant_bytes_saved"] = (
            qstats["quant_bytes_saved"] - qstats0["quant_bytes_saved"])
    except Exception as exc:  # fail-soft: keep the rest of the record
        record["fusion_quant_error"] = repr(exc)[:300]

    # ---- overlap stage: chunked collectives + async step dispatch ---- #
    # Fail-soft like the quant stage. Two figures: (a) wire-byte parity
    # chunked vs unchunked on the packed transformer step (the honest
    # CPU-auditable half — chunking must move EXACTLY the same bytes in
    # N legs); (b) wall + host-blocked time of a donated synchronous
    # trace_step loop vs the block=False async loop (donating an
    # in-flight buffer blocks the dispatching host thread on this jax —
    # the async sibling frees it; on a multi-core host the freed host
    # time converts into wall-clock overlap, on a 1-core box the
    # host_blocked_ms column is the real signal; wall time on the chip
    # is not measured).
    try:
        from heat_tpu.utils import hlo_audit as _ha

        ndev = comm.size
        if "modelq" not in dir():
            raise RuntimeError("quant stage model unavailable")
        with fusion.quant_override(None):
            with fusion.chunk_override(1):
                step1 = modelq.make_train_step(txq)
                h1 = step1.lower(
                    modelq.init(0), txq.init(modelq.init(0)),
                    toksq).compile().as_text()
            with fusion.chunk_override(4, min_numel=256):
                step4 = modelq.make_train_step(txq)
                h4 = step4.lower(
                    modelq.init(0), txq.init(modelq.init(0)),
                    toksq).compile().as_text()
        b1 = _ha.collective_bytes(h1, world=ndev)["total_wire_bytes"]
        b4 = _ha.collective_bytes(h4, world=ndev)["total_wire_bytes"]
        c1 = _ha.communicating_collective_stats(h1)
        c4 = _ha.communicating_collective_stats(h4)
        record["fusion_overlap_step_wire_bytes_unchunked"] = int(b1)
        record["fusion_overlap_step_wire_bytes_chunked"] = int(b4)
        record["fusion_overlap_step_wire_bytes_equal"] = bool(b1 == b4)
        record["fusion_overlap_step_allreduce_unchunked"] = int(
            c1.get("all-reduce", {}).get("count", 0))
        record["fusion_overlap_step_allreduce_chunked"] = int(
            c4.get("all-reduce", {}).get("count", 0))

        # the SAME train_step the fusion_train_step_* stage measures —
        # the overlap figures must compare the identical program, only
        # donated-sync vs async-dispatch (trace_step keys block/donate)
        def clone_params():
            return {k: ht.array(np.asarray(v.larray), split=v.split)
                    for k, v in p0.items()}

        def timed_loop(step_fn, reps=12):
            p = clone_params()
            p, lval = step_fn(p, bx, by)  # compile/trace warmup
            fusion.sync()
            jax.block_until_ready(lval.larray)
            t0 = time.perf_counter()
            for _ in range(reps):
                p, lval = step_fn(p, bx, by)
            t_dispatch = time.perf_counter() - t0
            fusion.sync()
            jax.block_until_ready(lval.larray)
            wall = time.perf_counter() - t0
            return wall / reps * 1e3, t_dispatch / reps * 1e3

        with fusion.override(True), fusion.step_override(True), \
                fusion.chunk_override(4, min_numel=256):
            ts_sync = fusion.trace_step(train_step, donate_argnums=(0,))
            t_sync, blocked_sync = min(
                (timed_loop(ts_sync) for _ in range(2)),
                key=lambda r: r[0])
            ts_async = fusion.trace_step(train_step, donate_argnums=(0,),
                                         block=False)
            t_async, blocked_async = min(
                (timed_loop(ts_async) for _ in range(2)),
                key=lambda r: r[0])
        record["fusion_overlap_step_sync_ms"] = round(t_sync, 3)
        record["fusion_overlap_step_async_ms"] = round(t_async, 3)
        record["fusion_overlap_step_speedup"] = round(
            t_sync / max(t_async, 1e-9), 2)
        record["fusion_overlap_step_host_blocked_sync_ms"] = round(
            blocked_sync, 3)
        record["fusion_overlap_step_host_blocked_async_ms"] = round(
            blocked_async, 3)
        # the dispatch-overlap figure: how much per-step host time the
        # async path frees (on a 1-core container wall-clock cannot
        # improve — host python and XLA compute share the core — so THIS
        # is the CPU-real signal; multi-core hosts and TPU convert it
        # into wall time)
        record["fusion_overlap_dispatch_speedup"] = round(
            blocked_sync / max(blocked_async, 1e-9), 2)
    except Exception as exc:  # fail-soft: keep the rest of the record
        record["fusion_overlap_error"] = repr(exc)[:300]

    # ---- hier stage: tier-aware hierarchical packed collectives ------ #
    # Fail-soft like the quant/overlap stages. The honest CPU-auditable
    # figure is PER-TIER wire bytes on a simulated (2, ndev/2) two-host
    # grid: the flat packed step's one full-mesh all-reduce vs the
    # hierarchical RS(ici) -> AR(dcn) -> AG(ici) decomposition — the DCN
    # column is the headline (the slow tier is what dominates real
    # multi-host steps), expected 1/p_ici at the same codec and ~2.6x
    # further with int8-over-DCN. CPU wall is a dispatch surrogate (no
    # real wire); wall time on the chip is not measured.
    try:
        import optax as _optax

        from heat_tpu.nn.transformer import (
            TransformerLM as _TLM, TransformerLMConfig as _TLMC)
        from heat_tpu.utils import hlo_audit as _ha2

        ndev = comm.size
        if ndev < 4 or ndev % 2:
            raise RuntimeError(
                f"hier stage needs an even mesh of >= 4 devices, "
                f"got {ndev}")
        d_t, i_t = 2, ndev // 2
        tgrid = ht.MeshGrid((d_t, i_t, 1, 1, 1),
                            ("dcn", "dp", "pp", "tp", "sp"))
        tcfg = _TLMC(vocab=128, d_model=64, n_heads=4, n_layers=2,
                     d_ff=128)
        tmodel = _TLM(tgrid, tcfg)
        ttoks = tmodel.shard_batch(np.random.default_rng(0).integers(
            0, tcfg.vocab, (4 * ndev, 16)).astype(np.int32))
        ttx = _optax.adam(1e-2)

        def timed_hier(hier_on, codec, reps=20):
            with fusion.hier_override(hier_on, tiers=None), \
                    fusion.quant_override(codec):
                step = tmodel.make_train_step(ttx)
                p = tmodel.init(0)
                o = ttx.init(p)
                hlo = step.lower(p, o, ttoks).compile().as_text()
                p, o, l = step(p, o, ttoks)  # warm
                jax.block_until_ready(l)
                t0 = time.perf_counter()
                for _ in range(reps):
                    p, o, l = step(p, o, ttoks)
                jax.block_until_ready(l)
                wall = (time.perf_counter() - t0) / reps * 1e3
            return wall, _ha2.collective_bytes(hlo, world=ndev,
                                               tiers=(d_t, i_t))

        hstats0 = fusion.stats()
        t_flat, a_flat = timed_hier(False, None)
        t_hier, a_hier = timed_hier(True, None)
        t_hier8, a_hier8 = timed_hier(True, "int8")
        hstats = fusion.stats()
        record["fusion_hier_step_tiers"] = [d_t, i_t]
        record["fusion_hier_step_flat_ms"] = round(t_flat, 3)
        record["fusion_hier_step_hier_ms"] = round(t_hier, 3)
        record["fusion_hier_step_int8_ms"] = round(t_hier8, 3)
        record["fusion_hier_step_dcn_wire_bytes_flat"] = int(
            a_flat["total_dcn_wire_bytes"])
        record["fusion_hier_step_dcn_wire_bytes_hier"] = int(
            a_hier["total_dcn_wire_bytes"])
        record["fusion_hier_step_dcn_wire_bytes_int8"] = int(
            a_hier8["total_dcn_wire_bytes"])
        record["fusion_hier_step_dcn_reduction"] = round(
            a_flat["total_dcn_wire_bytes"]
            / max(a_hier["total_dcn_wire_bytes"], 1), 2)
        record["fusion_hier_step_dcn_reduction_int8"] = round(
            a_flat["total_dcn_wire_bytes"]
            / max(a_hier8["total_dcn_wire_bytes"], 1), 2)
        record["fusion_hier_step_total_wire_bytes_flat"] = int(
            a_flat["total_wire_bytes"])
        record["fusion_hier_step_total_wire_bytes_hier"] = int(
            a_hier["total_wire_bytes"])
        # STAGE deltas, like the quant stage's counters
        record["fusion_hier_collectives"] = (
            hstats["hier_collectives"] - hstats0["hier_collectives"])
        record["fusion_hier_fallbacks"] = (
            hstats["hier_fallbacks"] - hstats0["hier_fallbacks"])
    except Exception as exc:  # fail-soft: keep the rest of the record
        record["fusion_hier_error"] = repr(exc)[:300]

    record["fusion_program_cache"] = fusion.program_cache().stats()
    record["fusion_ops_per_flush"] = fusion.stats()["ops_per_flush"]
    record["fusion_reduce_flushes"] = fusion.stats()["reduce_flushes"]
    record["fusion_contract_flushes"] = fusion.stats()["contract_flushes"]
    record["fusion_resplit_nodes"] = fusion.stats()["resplit_nodes"]
    record["fusion_resplit_fallbacks"] = fusion.stats()["resplit_fallbacks"]
    record["fusion_step_flushes"] = fusion.stats()["step_flushes"]
    print(json.dumps(record), flush=True)


def _decode_bench_main() -> None:
    """``--decode-bench`` child: continuous-batching decode throughput vs
    the monolithic ``generate()`` convoy on the 4-device CPU mesh this
    process was launched onto (ISSUE 15 acceptance: >= 1.5x tokens/s on
    a seeded mixed-length workload).

    Workload: R requests with prompt lengths in [5, 13) and
    ``max_new_tokens`` drawn from {8, 12, 16, 24, 192} skewed short with
    a heavy 192-token tail (the LLM-serving shape: many short answers,
    occasional long generations — the tail is what convoys the
    monolithic batch), staggered arrivals.
    Baseline: the same requests grouped into slot-sized batches in
    arrival order, each batch running ``generate()`` to the LONGEST
    member (the convoy) — tokens/s counts only REQUESTED tokens on both
    paths. Both paths are warmed first so neither pays a compile in the
    timed pass. Prints ONE JSON line with tokens/s both ways, the
    speedup, mean slot occupancy and the per-phase serve.decode_*
    counter deltas.
    """
    import time as _time

    import jax

    import heat_tpu as ht
    from heat_tpu.nn.transformer import TransformerLM, TransformerLMConfig
    from heat_tpu.serve import DecodeConfig, DecodeEngine
    from heat_tpu.utils import metrics as _pm

    n = ht.get_comm().size
    grid = ht.MeshGrid((n, 1, 1, 1), ("dp", "pp", "tp", "sp"))
    # sized so per-step compute dominates the engine's per-dispatch host
    # overhead on this CPU mesh (the convoy win is a compute ratio; on
    # real TPUs dispatch cost shrinks and the ratio is the whole story)
    cfg = TransformerLMConfig(vocab=256, d_model=192, n_heads=8,
                              n_layers=2, d_ff=768)
    model = TransformerLM(grid, cfg)
    params = model.init(0)
    rng = np.random.default_rng(7)

    slots = 4 * model.dp_world
    R = 10 * slots
    lens = rng.integers(5, 13, R)
    # the chat traffic shape: mostly short answers, a heavy long tail —
    # exactly what convoys a monolithic batch (every batch runs to its
    # longest member while the engine's finished lanes take new work)
    news = rng.choice([8, 12, 16, 24, 192], size=R,
                      p=[.30, .30, .15, .10, .15])
    reqs = [(rng.integers(0, cfg.vocab, (int(s),)).astype(np.int32),
             int(m)) for s, m in zip(lens, news)]
    useful = int(sum(m for _p, m in reqs))
    gaps = rng.uniform(0.0, 2e-3, R)  # staggered (open-loop-ish) arrivals

    # ---- monolithic convoy baseline: slot-sized batches, arrival order
    batches = [reqs[i:i + slots] for i in range(0, R, slots)]

    def run_mono():
        for chunk in batches:
            s_max = max(p.size for p, _m in chunk)
            m_max = max(m for _p, m in chunk)
            toks = np.zeros((len(chunk), s_max), np.int32)
            for j, (p, _m) in enumerate(chunk):
                toks[j, :p.size] = p
            jax.block_until_ready(model.generate(params, toks, m_max))

    run_mono()  # warm every (batch, bucket, max_new) program
    t0 = _time.perf_counter()
    run_mono()
    t_mono = _time.perf_counter() - t0

    # ---- continuous batching through the slot engine
    eng = DecodeEngine(model, params,
                       DecodeConfig(slots=slots, max_seq_len=256,
                                    queue_limit=4 * R),
                       name="decode-bench")
    eng.warmup()
    misses0 = eng.program_cache.stats()["misses"]

    def run_cont():
        futs = []
        for (p, m), gap in zip(reqs, gaps):
            futs.append(eng.submit(p, m))
            if gap > 1e-3:
                _time.sleep(gap)
        for f in futs:
            f.result(600)

    run_cont()  # warm pass (programs are already compiled; steadies JIT)
    c0 = {k: int(_pm.counters().get(f"serve.decode_{k}", 0))
          for k in ("prefills", "steps", "tokens_out", "fallbacks")}
    t0 = _time.perf_counter()
    run_cont()
    t_cont = _time.perf_counter() - t0
    c1 = {k: int(_pm.counters().get(f"serve.decode_{k}", 0)) - c0[k]
          for k in c0}
    st = eng.stats()
    steady_misses = eng.program_cache.stats()["misses"] - misses0
    eng.close()

    mono_tps = useful / t_mono
    cont_tps = useful / t_cont
    record = {
        "decode_requests": R,
        "decode_slots": slots,
        "decode_useful_tokens": useful,
        "decode_cont_tokens_per_s": round(cont_tps, 1),
        "decode_mono_tokens_per_s": round(mono_tps, 1),
        "decode_speedup": round(cont_tps / mono_tps, 2),
        "decode_speedup_target": 1.5,
        "decode_mean_occupancy": round(st["occupancy"], 3),
        "decode_steady_misses": steady_misses,
        "decode_counters": c1,
        "decode_devices": n,
    }
    print(json.dumps(record), flush=True)


def _analytics_bench_main() -> None:
    """``--analytics-bench`` child: measure the tape-compiled analytics
    fit steps (ISSUE 13) on the 4-device CPU mesh this process was
    launched onto.

    Two figures:

    * ``analytics_lloyd_*``: one KMeans Lloyd iteration timed as the
      compiled donated packed-collective executable
      (``kmeans._lloyd_fused_fn`` — what ``fit()`` dispatches per
      iteration through ``fusion.fit_step_call``) vs the eager op-by-op
      replay (``_lloyd_eager_step`` — the ``fit.step.dispatch`` degrade
      path: per-op dispatch, separate psums). Sized dispatch-dominated
      (n = 2^15, the fusion-stage regime) — acceptance ≥ 2×. A repeated
      public ``fit()`` proves the steady state runs zero program-cache
      misses.
    * ``analytics_stream_*``: the out-of-core scenario — a 100M-element
      (n×64 f32, 400 MB) HDF5 dataset, sized down when the box lacks the
      disk, trained chunk-by-chunk via ``fit_stream`` with the chunk
      accounting proving the resident set never approached
      materialization (peak chunk ≪ file size).

    Prints ONE JSON line with the analytics_* fields.
    """
    import shutil
    import tempfile

    import jax
    import jax.numpy as jnp

    import heat_tpu as ht
    from heat_tpu.cluster import kmeans as km_mod
    from heat_tpu.core import fusion

    comm = ht.get_comm()
    n, d, k = 1 << 15, D_FEATS, K_CLUSTERS
    rng = np.random.default_rng(0)
    data = rng.standard_normal((n, d)).astype(np.float32)
    x = ht.array(data, split=0)
    xp = x.larray
    jdt = jnp.dtype(jnp.float32)
    cent0 = jnp.asarray(rng.standard_normal((k, d)).astype(np.float32))
    qk, ck, hk = fusion.quant_key(), fusion.chunk_key(), fusion.hier_key()
    fused = km_mod._lloyd_fused_fn(xp.shape, jdt, k, n, comm, qk, ck, hk)
    eager = km_mod._lloyd_eager_step(xp.shape, jdt, k, n)

    def timed_iter(step, reps, donating) -> float:
        c = jnp.array(cent0)
        out = step(xp, c)  # compile + warm (the donating step eats c)
        jax.block_until_ready(out[0])
        c = out[0]
        t0 = time.perf_counter()
        for _ in range(reps):
            c, _s, _i = step(xp, c if donating else jnp.array(c))
        jax.block_until_ready(c)
        return (time.perf_counter() - t0) / reps * 1e3

    record = {"analytics_devices": comm.size, "analytics_n": n}
    t_fused = min(timed_iter(fused, 20, True) for _ in range(2))
    t_eager = min(timed_iter(eager, 6, False) for _ in range(2))
    record["analytics_lloyd_fused_ms"] = round(t_fused, 3)
    record["analytics_lloyd_eager_ms"] = round(t_eager, 3)
    record["analytics_lloyd_speedup"] = round(t_eager / t_fused, 2)

    # steady state on the PUBLIC path: repeated fit() is key-lookup only
    seed = ht.array(data[:k].copy())
    kw = dict(n_clusters=k, init=seed, max_iter=4, tol=-1.0)
    ht.cluster.KMeans(**kw).fit(x)  # compile leg
    st0 = fusion.program_cache().stats()
    f0 = fusion.stats()["fit_step_flushes"]
    for _ in range(3):
        ht.cluster.KMeans(**kw).fit(x)
    st1 = fusion.program_cache().stats()
    record["analytics_fit_steady_misses"] = st1["misses"] - st0["misses"]
    record["analytics_fit_step_flushes"] = (
        fusion.stats()["fit_step_flushes"] - f0)

    # ---- out-of-core streamed clustering, 100M-element scale -------- #
    # Fail-soft inside the stage (like the quant/overlap stages): a
    # missing h5py or a full disk must not take down the Lloyd figures.
    try:
        import h5py  # noqa: F401 — availability gate

        elems = 100_000_000
        free = shutil.disk_usage(tempfile.gettempdir()).free
        while elems * 4 * 2 > free and elems > 1_000_000:
            elems //= 4  # sized to the box: never fill the disk
        ns = elems // d
        tmp = tempfile.mkdtemp(prefix="ht_analytics_")
        try:
            path = os.path.join(tmp, "stream.h5")
            with h5py.File(path, "w") as f:
                dset = f.create_dataset("data", (ns, d), dtype="f4")
                for lo in range(0, ns, 1 << 18):
                    hi = min(lo + (1 << 18), ns)
                    dset[lo:hi] = rng.standard_normal(
                        (hi - lo, d), dtype=np.float32)
            stream = ht.load_hdf5(path, "data", stream=True)
            sseed = ht.array(
                rng.standard_normal((k, d)).astype(np.float32))
            epochs = 3
            t0 = time.perf_counter()
            ht.cluster.KMeans(
                n_clusters=k, init=sseed, max_iter=epochs,
                tol=-1.0).fit_stream(stream, rows_per_chunk=1 << 17)
            t_fit = time.perf_counter() - t0
            record["analytics_stream_elements"] = ns * d
            record["analytics_stream_epochs"] = epochs
            record["analytics_stream_file_mb"] = round(
                os.path.getsize(path) / 1e6, 1)
            record["analytics_stream_mrows_per_s"] = round(
                epochs * ns / t_fit / 1e6, 2)
            record["analytics_stream_chunks_read"] = stream.chunks_read
            record["analytics_stream_peak_chunk_mb"] = round(
                stream.peak_chunk_bytes / 1e6, 1)
        finally:
            shutil.rmtree(tmp, ignore_errors=True)
    except Exception as exc:  # fail-soft: keep the Lloyd figures
        record["analytics_stream_error"] = repr(exc)[:300]

    print(json.dumps(record), flush=True)


def _data_bench_main() -> None:
    """``--data-bench`` child: measure the tape-compiled data engine
    (ISSUE 17) on the 4-device CPU mesh this process was launched onto.

    Three figures:

    * ``data_groupby_*``: groupby-sum over 10M rows (int64 keys, f32
      values) through the ONE-packed-all-reduce program — rows/s plus a
      repeated-call probe proving zero steady-state program-cache
      misses;
    * ``data_topk_*``: top-64 of the same 10M values through the
      k-sized-exchange program (zero all-gather) — rows/s;
    * ``data_quantile_*``: the out-of-core scenario — EXACT streaming
      median + p99 over a ~100M-element f32 HDF5 dataset (sized down
      when the box lacks the disk) via the multi-pass bisection folds,
      with the stream accounting proving the resident set never
      approached materialization (peak chunk ≪ file size).

    Prints ONE JSON line with the data_* fields.
    """
    import shutil
    import tempfile

    import heat_tpu as ht
    from heat_tpu import data as htdata

    comm = ht.get_comm()
    n_rows, G, K = 10_000_000, 64, 64
    rng = np.random.default_rng(0)
    keys = rng.integers(0, G, n_rows).astype(np.int64)
    vals = rng.standard_normal(n_rows).astype(np.float32)
    k = ht.array(keys, split=0)
    v = ht.array(vals, split=0)

    def timed(fn, reps) -> float:
        fn()  # compile + warm
        t0 = time.perf_counter()
        for _ in range(reps):
            fn()
        return (time.perf_counter() - t0) / reps

    record = {"data_devices": comm.size, "data_rows": n_rows}
    t_gb = timed(lambda: htdata.groupby(k, G).sum(v).numpy(), 5)
    record["data_groupby_groups"] = G
    record["data_groupby_ms"] = round(t_gb * 1e3, 2)
    record["data_groupby_mrows_per_s"] = round(n_rows / t_gb / 1e6, 1)
    t_tk = timed(lambda: htdata.topk(v, K)[0].numpy(), 5)
    record["data_topk_k"] = K
    record["data_topk_ms"] = round(t_tk * 1e3, 2)
    record["data_topk_mrows_per_s"] = round(n_rows / t_tk / 1e6, 1)
    misses0 = htdata.engine.program_cache().stats()["misses"]
    htdata.groupby(k, G).sum(v).numpy()
    htdata.topk(v, K)
    record["data_steady_misses"] = (
        htdata.engine.program_cache().stats()["misses"] - misses0)

    # ---- out-of-core streaming quantile, 100M-element scale --------- #
    # Fail-soft inside the stage (like the analytics stream leg): a
    # missing h5py or a full disk must not take down the in-memory
    # figures.
    try:
        import h5py  # noqa: F401 — availability gate

        elems = 100_000_000
        free = shutil.disk_usage(tempfile.gettempdir()).free
        while elems * 4 * 2 > free and elems > 1_000_000:
            elems //= 4  # sized to the box: never fill the disk
        tmp = tempfile.mkdtemp(prefix="ht_data_")
        try:
            path = os.path.join(tmp, "stream.h5")
            with h5py.File(path, "w") as f:
                dset = f.create_dataset("data", (elems,), dtype="f4")
                for lo in range(0, elems, 1 << 22):
                    hi = min(lo + (1 << 22), elems)
                    dset[lo:hi] = rng.standard_normal(
                        hi - lo, dtype=np.float32)
            stream = ht.load_hdf5(path, "data", stream=True)
            t0 = time.perf_counter()
            q = htdata.stream_quantile(stream, [0.5, 0.99],
                                       rows_per_chunk=1 << 20)
            t_q = time.perf_counter() - t0
            passes = max(1, stream.chunks_read
                         // -(-elems // (1 << 20)))
            record["data_quantile_elements"] = elems
            record["data_quantile_passes"] = passes
            record["data_quantile_file_mb"] = round(
                os.path.getsize(path) / 1e6, 1)
            record["data_quantile_s"] = round(t_q, 2)
            record["data_quantile_mrows_per_s"] = round(
                passes * elems / t_q / 1e6, 2)
            record["data_quantile_peak_chunk_mb"] = round(
                stream.peak_chunk_bytes / 1e6, 1)
            record["data_quantile_p50"] = round(float(q[0]), 6)
            record["data_quantile_p99"] = round(float(q[1]), 6)
        finally:
            shutil.rmtree(tmp, ignore_errors=True)
    except Exception as exc:  # fail-soft: keep the in-memory figures
        record["data_quantile_error"] = repr(exc)[:300]

    print(json.dumps(record), flush=True)


def _serve_bench_main() -> None:
    """``--serve-bench`` child: measure the serving executor on the
    4-device CPU mesh this process was launched onto (the serving stage is
    a host-concurrency figure — it is pinned to the virtual CPU mesh
    regardless of the accelerator, like the ladder's suite runs).

    Workload: a fixed mixed-shape request stream (rows 1..16, d=64)
    against a sharded nearest-centroid model (the KMeans serving shape),
    8 client threads. Prints ONE JSON line with requests/s, p99 latency,
    the sequential single-request baseline and the batched speedup, plus
    the program-cache stats proving zero steady-state recompiles.
    """
    import threading

    import heat_tpu as ht
    from heat_tpu.serve import (Pow2Buckets, ProgramCache, ServeConfig,
                                ServeMetrics, ServingExecutor)
    # the PRODUCTION serving program, not a bench re-implementation — the
    # figure must measure what serve_estimator actually runs
    from heat_tpu.serve.adapters import _centroid_assign_fn

    comm = ht.get_comm()
    d, k = D_FEATS, K_CLUSTERS
    rng = np.random.default_rng(0)
    fn = _centroid_assign_fn(
        rng.standard_normal((k, d)).astype(np.float32), comm)
    policy = Pow2Buckets(min_rows=comm.size, multiple_of=comm.size)
    cache = ProgramCache(name="bench")
    mix = (1, 2, 3, 5, 8, 13, 16, 4)
    n_threads, per_thread = 8, 25
    reqs = [rng.standard_normal((r, d)).astype(np.float32)
            for r in mix * (n_threads * per_thread // len(mix))]

    # sequential single-request baseline: same programs, no coalescing
    seq = ServingExecutor(
        fn, ServeConfig(batching=False, bucket_rows=policy),
        name="serve-seq", cache_token=comm.cache_key,
        metrics=ServeMetrics(), program_cache=cache)
    seq.warmup((d,), np.float32, rows=(1, 2, 5, 9, 17, 33, 65, 129))
    n_seq = 60
    t0 = time.perf_counter()
    for x in reqs[:n_seq]:
        seq.predict(x, timeout=60)
    t_seq = time.perf_counter() - t0
    seq.close()

    metrics = ServeMetrics()
    ex = ServingExecutor(
        fn, ServeConfig(max_batch=16, max_wait_ms=2.0, queue_limit=1024,
                        bucket_rows=policy),
        name="serve-bench", cache_token=comm.cache_key,
        metrics=metrics, program_cache=cache)
    ex.warmup((d,), np.float32, rows=(1, 2, 5, 9, 17, 33, 65, 129))
    misses0 = cache.stats()["misses"]
    metrics.reset()  # percentiles must describe traffic, not warmup

    errors = []

    def client(t):
        try:
            lo = t * per_thread
            futs = [ex.submit(x) for x in reqs[lo:lo + per_thread]]
            for f in futs:
                f.result(120)
        except Exception as exc:
            errors.append(repr(exc))

    threads = [threading.Thread(target=client, args=(t,))
               for t in range(n_threads)]
    t0 = time.perf_counter()
    for th in threads:
        th.start()
    for th in threads:
        th.join(300)
    wall = time.perf_counter() - t0
    ex.close()

    n_total = n_threads * per_thread
    snap = metrics.snapshot(program_cache=cache.stats())
    record = {
        "serve_requests_per_s": round(n_total / wall, 1),
        "serve_seq_requests_per_s": round(n_seq / t_seq, 1),
        "serve_batched_speedup": round((n_total / wall) / (n_seq / t_seq), 2),
        "serve_p99_ms": round(snap["latency_ms"]["p99"], 2),
        "serve_p50_ms": round(snap["latency_ms"]["p50"], 2),
        "serve_batch_occupancy": round(snap["batch_occupancy"]["mean"], 3),
        "serve_shed": snap["shed"],
        "serve_steady_misses": cache.stats()["misses"] - misses0,
        "serve_devices": comm.size,
        "serve_mix_rows": list(mix),
        "serve_errors": errors[:3],
    }
    print(json.dumps(record), flush=True)


def main() -> None:
    if len(sys.argv) >= 2 and sys.argv[1] == "--serve-bench":
        _serve_bench_main()
        return
    if len(sys.argv) >= 2 and sys.argv[1] == "--fusion-bench":
        _fusion_bench_main()
        return
    if len(sys.argv) >= 2 and sys.argv[1] == "--analytics-bench":
        _analytics_bench_main()
        return
    if len(sys.argv) >= 2 and sys.argv[1] == "--decode-bench":
        _decode_bench_main()
        return
    if len(sys.argv) >= 2 and sys.argv[1] == "--data-bench":
        _data_bench_main()
        return

    _measure_main(int(sys.argv[1]) if len(sys.argv) >= 2 else N_FULL)


if __name__ == "__main__":
    main()
