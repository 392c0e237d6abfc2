#!/usr/bin/env python
"""heat_tpu's main path, once, on the chip — or fail.

    python chip_smoke.py                 # one TPU chip, every phase
    python chip_smoke.py --chips 4       # the split-array phase on four chips
    python chip_smoke.py --rehearse      # same code at tiny sizes on the CPU
    python chip_smoke.py --chips 4 --rehearse   # ... on four virtual devices

ONE process: it imports jax itself and starts no child. Without
``--rehearse`` it needs ``jax.devices()[0].platform == "tpu"`` and exits 2
with a plain message otherwise. Every phase goes through the public entry
points, is compared with a plain reference that shares no code with it
(NumPy, or ``heat_tpu.nn.reference`` for the language model), and prints one
JSON line; an exception or a failed comparison in any phase makes the exit
code non-zero (nothing is caught). The last line of stdout is

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

— a rehearsal's last line carries ``"rehearsal": true`` and the CPU as its
platform, so it can never be read as a chip run. Seconds printed here are
set-up and wall seconds of a correctness run, not a benchmark.

Compile cache: where ``JAX_COMPILATION_CACHE_DIR`` is set it is JAX's
business and nothing is set in code; otherwise a chip run uses the one fixed
directory ``.jax_cache/`` inside the checkout (git-ignored). The ``cache``
line says how many compiles the persistent cache answered, i.e. whether a
second run of this process found what the first one left.
"""

import argparse
import gc
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))


def _parse():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes on the CPU; never a chip result")
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run ONLY the split-across-chips phase")
    ap.add_argument("--seed", type=int, default=0)
    return ap.parse_args()


ARGS = _parse()
if ARGS.rehearse:
    # must precede the jax import: the CPU backend, with as many virtual
    # devices as the path under rehearsal spans
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + f" --xla_force_host_platform_device_count={ARGS.chips}")

import numpy as np  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

DEV = jax.devices()
ON_TPU = DEV[0].platform == "tpu"
if not ARGS.rehearse and not ON_TPU:
    sys.stderr.write(
        f"chip_smoke: no TPU here (jax.devices()[0].platform == "
        f"{DEV[0].platform!r}); this check runs on the chip or fails. "
        f"Use --rehearse for the tiny CPU rehearsal.\n")
    sys.exit(2)
if len(DEV) < ARGS.chips:
    sys.stderr.write(f"chip_smoke: --chips {ARGS.chips} needs "
                     f"{ARGS.chips} devices, jax reports {len(DEV)}\n")
    sys.exit(2)

# ---- compile cache: placed from outside, else ONE fixed path ---------- #
_HITS = {"hits": 0, "misses": 0}
_CACHE_DIR = os.environ.get("JAX_COMPILATION_CACHE_DIR")
if _CACHE_DIR is None and ON_TPU:
    # (XLA:CPU reloads of AOT executables are unsound on shared hosts —
    # tests/conftest.py — so a rehearsal compiles fresh)
    _CACHE_DIR = os.path.join(HERE, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", _CACHE_DIR)


def _cache_entries():
    if not _CACHE_DIR or not os.path.isdir(_CACHE_DIR):
        return 0
    return sum(1 for n in os.listdir(_CACHE_DIR) if not n.startswith("."))


_ENTRIES_AT_START = _cache_entries()

# ---- compile seconds, from jax's own monitoring events ---------------- #
_COMPILE = {"s": 0.0, "n": 0}


def _on_duration(event, secs, **_kw):
    if event.startswith("/jax/core/compile/"):
        _COMPILE["s"] += secs
        if event.endswith("backend_compile_duration"):
            _COMPILE["n"] += 1


def _on_event(event, **_kw):
    if event == "/jax/compilation_cache/cache_hits":
        _HITS["hits"] += 1
    elif event == "/jax/compilation_cache/cache_misses":
        _HITS["misses"] += 1


jax.monitoring.register_event_duration_secs_listener(_on_duration)
jax.monitoring.register_event_listener(_on_event)

import heat_tpu as ht  # noqa: E402
from heat_tpu.core import fusion, pallas_kernels  # noqa: E402
from heat_tpu.core.communication import TPUCommunication  # noqa: E402
from heat_tpu.nn import reference  # noqa: E402
from heat_tpu.nn.transformer import (TransformerLM,  # noqa: E402
                                     TransformerLMConfig)
from heat_tpu.serve import serve_transformer  # noqa: E402
from heat_tpu.utils import metrics  # noqa: E402

R = ARGS.rehearse
AXES = ("dp", "pp", "tp", "sp")
# bf16 compute on the chip; a rehearsal on XLA:CPU computes in float32
BF16_EPS = 2.0 ** -8


def _peak():
    st = DEV[0].memory_stats() or {}
    return st.get("peak_bytes_in_use")


def _phase(name, fn):
    """Run one phase; print its JSON line. A failed check raises — there
    is no except here or anywhere below."""
    gc.collect()
    c0, n0, t0 = _COMPILE["s"], _COMPILE["n"], time.perf_counter()
    detail = fn()
    rec = {"phase": name, "ok": True,
           "wall_s": round(time.perf_counter() - t0, 3),
           "compile_s": round(_COMPILE["s"] - c0, 3),
           "compiles": _COMPILE["n"] - n0,
           "peak_bytes_in_use": _peak()}
    rec.update(detail)
    print(json.dumps(rec), flush=True)


def _check(cond, msg):
    if not cond:
        raise AssertionError(msg)


def _lm_config(n_layers):
    if R:
        return TransformerLMConfig(vocab=128, d_model=64, n_heads=4,
                                   n_layers=2)
    # a mid-size shape: wide enough for the MXU path, quick to compile
    return TransformerLMConfig(vocab=32768, d_model=1024, n_heads=16,
                               n_layers=n_layers, compute_dtype=jnp.bfloat16)


# ====================================================================== #
# tensor                                                                 #
# ====================================================================== #
def phase_tensor():
    rng = np.random.default_rng(ARGS.seed)
    out = {}
    if ON_TPU:
        # the hot tiles must be Mosaic-compiled kernels here, not the
        # interpreter and not the XLA expansion
        _check(pallas_kernels.pallas_enabled(), "pallas kernels are off")
        _check(not pallas_kernels._interpret(), "kernels would interpret")
    out["pallas"] = {"enabled": pallas_kernels.pallas_enabled(),
                     "interpret": pallas_kernels._interpret()}

    n = 65000  # n(n-1)/2 still fits int32
    got = int(ht.arange(n, split=0).sum().item())
    _check(got == n * (n - 1) // 2, f"arange sum {got}")

    # an uneven shape through the padded canonical layout
    a = np.arange(1001 * 7, dtype=np.float32).reshape(1001, 7) / 7.0
    x = ht.array(a, split=0)
    y = ((x * 2.0 + 1.0).sum(axis=0)).numpy()
    np.testing.assert_allclose(y, (a * 2.0 + 1.0).sum(axis=0), rtol=1e-5)

    m = (1 << 12) if R else (1 << 22)  # >= 1M elements on the chip
    v = rng.random(m, dtype=np.float32)
    hv = ht.array(v, split=0)
    s = float(hv.sum().item())
    red_err = abs(s - float(v.sum(dtype=np.float64))) / m
    _check(red_err < 1e-6, f"reduction error {red_err}")
    _check(float(hv.max().item()) == float(v.max()), "max differs")
    out["reduce"] = {"n": m, "abs_err_per_elem": red_err}

    k = 256 if R else 8192
    a32 = rng.standard_normal((k, k), dtype=np.float32)
    b32 = rng.standard_normal((k, k), dtype=np.float32)
    ha = ht.array(a32, dtype=ht.bfloat16, split=0)
    hb = ht.array(b32, dtype=ht.bfloat16, split=None)
    hc = ht.matmul(ha, hb)
    _check(hc.shape == (k, k) and hc.dtype == ht.bfloat16,
           f"matmul gave {hc.shape} {hc.dtype}")
    rows = np.array([0, k // 3, k - 1])
    got = np.asarray(hc[rows].numpy(), np.float32)
    a_r = np.asarray(ha[rows].numpy(), np.float32)   # bf16-rounded inputs
    b_r = np.asarray(jnp.asarray(b32).astype(jnp.bfloat16), np.float32)
    want = a_r @ b_r
    mm_err = float(np.abs(got - want).max() / np.abs(want).max())
    _check(mm_err < 2 * BF16_EPS, f"bf16 matmul rel err {mm_err}")
    out["matmul_bf16"] = {"n": k, "rel_err_of_max": mm_err}
    del ha, hb, hc

    # cdist 40,000 x 18: a 6.4 GB float32 result on the chip
    nc = 512 if R else 40000
    pts = rng.random((nc, 18), dtype=np.float32)
    hp = ht.array(pts, split=0)
    d = ht.spatial.cdist(hp, hp, quadratic_expansion=True)
    _check(d.shape == (nc, nc) and d.dtype == ht.float32,
           f"cdist gave {d.shape} {d.dtype}")
    blk, worst = 64, 0.0
    p64 = pts.astype(np.float64)
    for i0 in (0, nc // 3, nc - blk):
        got2 = np.square(d[i0:i0 + blk].numpy().astype(np.float64))
        diff = p64[i0:i0 + blk, None, :] - p64[None, :, :]
        want2 = np.einsum("ijk,ijk->ij", diff, diff)
        worst = max(worst, float(np.abs(got2 - want2).max()))
    # |x|^2+|y|^2-2xy in float32 at |x|^2 ~ 6: a few 1e-6 of cancellation
    _check(worst < 2e-4, f"cdist squared-distance error {worst}")
    out["cdist"] = {"n": nc, "features": 18, "result_bytes": nc * nc * 4,
                    "max_abs_err_sq": worst, "rows_checked": 3 * blk}
    return out


# ====================================================================== #
# kmeans                                                                 #
# ====================================================================== #
def _blobs(n, seed):
    """k=8 well-separated blobs in 64 features, made in bulk from the
    seed. Separation >> the distance GEMM's default-precision rounding, so
    labels — and with them the centroid sums — do not depend on it."""
    rng = np.random.default_rng(seed)
    centers = rng.standard_normal((8, 64), dtype=np.float32)
    x = rng.standard_normal((n, 64), dtype=np.float32)
    x *= np.float32(0.25)
    lab = rng.integers(0, 8, n)
    x += centers[lab]
    init = centers + 0.3 * rng.standard_normal((8, 64), dtype=np.float32)
    return x, init.astype(np.float32)


def _numpy_lloyd(x, c, iters, chunk=1 << 20):
    c = c.astype(np.float64)
    k = len(c)
    for _ in range(iters):
        sums, counts = np.zeros_like(c), np.zeros(k)
        for i in range(0, len(x), chunk):
            xb = x[i:i + chunk]
            c32 = c.astype(np.float32)
            d2 = (c32 * c32).sum(1)[None, :] - 2.0 * (xb @ c32.T)
            lab = d2.argmin(1)
            onehot = (lab[:, None] == np.arange(k)[None, :]).astype(np.float32)
            sums += (onehot.T @ xb).astype(np.float64)
            counts += onehot.sum(0, dtype=np.float64)
        c = np.where(counts[:, None] > 0, sums / np.maximum(counts, 1)[:, None], c)
    inertia = 0.0
    for i in range(0, len(x), chunk):
        xb = x[i:i + chunk].astype(np.float64)
        d2 = ((xb * xb).sum(1)[:, None] + (c * c).sum(1)[None, :]
              - 2.0 * (xb @ c.T))
        inertia += float(d2.min(1).sum())
    return c, inertia


def _fit_kmeans(x, init, iters, comm=None):
    hx = ht.array(x, split=0, comm=comm)
    km = ht.cluster.KMeans(n_clusters=8, init=ht.array(init, comm=comm),
                           max_iter=iters, tol=-1.0)  # tol<0: run them all
    km.fit(hx)
    _check(km.n_iter_ == iters, f"ran {km.n_iter_} of {iters} iterations")
    return km.cluster_centers_.numpy().astype(np.float64), float(km.inertia_)


def phase_kmeans():
    n, iters = (4096 if R else 1 << 23), 5
    x, init = _blobs(n, ARGS.seed + 1)
    cen, inertia = _fit_kmeans(x, init, iters)
    want_c, want_i = _numpy_lloyd(x, init, iters)
    c_err = float(np.abs(cen - want_c).max())
    i_err = abs(inertia - want_i) / want_i
    _check(np.isfinite(cen).all() and cen.shape == (8, 64), "bad centroids")
    # centroids: one-hot GEMM sums (one bf16 pass, float32 accumulation
    # over ~1M rows a cluster) over counts — stated at one bf16 ulp of
    # |c| ~ 1 (measured 4.9e-4 on the v5e), with the blobs ~11 apart.
    # inertia comes from the expansion GEMM at the TPU's DEFAULT (one bf16
    # pass) precision: |x|^2 ~ 68 against d^2 ~ 4, stated at 2**-8 * 68 / 4
    _check(c_err < (1e-5 if R else BF16_EPS), f"centroid error {c_err}")
    _check(i_err < (1e-4 if R else BF16_EPS * 17), f"inertia rel err {i_err}")
    return {"n": n, "features": 64, "k": 8, "iterations": iters,
            "centroid_max_abs_err": c_err, "inertia": inertia,
            "inertia_ref": want_i, "inertia_rel_err": i_err}


# ====================================================================== #
# train + decode (one model, one process)                                #
# ====================================================================== #
_LM = {}


def phase_train():
    import optax

    cfg = _lm_config(8)
    B, S = (2, 32) if R else (8, 1024)
    grid = ht.MeshGrid((1, 1, 1, 1), AXES, devices=DEV[:1])
    model = TransformerLM(grid, cfg)
    params = model.init(ARGS.seed)
    toks_h = np.random.default_rng(ARGS.seed + 2).integers(
        0, cfg.vocab, (B, S)).astype(np.int32)
    # the reference's first-step loss, before the step donates the params
    hp = jax.device_put(reference.host_params(params), DEV[0])
    ref_loss = reference.reference_loss(hp, toks_h, cfg)
    del hp

    tx = optax.adam(1e-3)
    opt = tx.init(params)
    step = model.make_train_step(tx)
    toks = model.shard_batch(toks_h)
    losses, after_first = [], None
    for i in range(3):
        params, opt, loss = step(params, opt, toks)
        losses.append(float(np.asarray(loss)))
        if i == 0:
            after_first = (_COMPILE["n"],
                           fusion.program_cache().stats()["misses"])
    steady = (_COMPILE["n"], fusion.program_cache().stats()["misses"])
    _check(all(np.isfinite(losses)), f"losses {losses}")
    _check(losses[0] > losses[1] > losses[2], f"loss not falling: {losses}")
    tol = 1e-4 if R else 0.05  # bf16 compute against float32: 0.5% of ln V
    err = abs(losses[0] - ref_loss)
    _check(err < tol, f"first loss {losses[0]} vs float32 reference "
                      f"{ref_loss}: {err} >= {tol}")
    _check(steady == after_first,
           f"steps 2-3 compiled: (compiles, program-cache misses) "
           f"{after_first} -> {steady}")
    del opt
    _LM.update(model=model, params=params, cfg=cfg)
    return {"shape": f"L{cfg.n_layers}_d{cfg.d_model}_h{cfg.n_heads}"
                     f"_V{cfg.vocab}_B{B}_S{S}",
            "compute_dtype": jnp.dtype(cfg.compute_dtype).name,
            "losses": losses, "ref_first_loss": ref_loss,
            "first_loss_abs_err": err, "tol": tol,
            "compiles_after_step1": steady[0] - after_first[0],
            "program_cache_misses_after_step1": steady[1] - after_first[1]}


def phase_decode():
    model, params, cfg = _LM["model"], _LM["params"], _LM["cfg"]
    rng = np.random.default_rng(ARGS.seed + 3)
    cap = 32 if R else 128
    mix = ((3, 6), (9, 4), (12, 5), (5, 7), (13, 3), (7, 9)) if R else \
        ((5, 12), (17, 8), (33, 16), (9, 24), (64, 8), (3, 20), (40, 12),
         (12, 16))
    prompts = [rng.integers(0, cfg.vocab, (s0,)).astype(np.int32)
               for s0, _mn in mix]
    hp = jax.device_put(reference.host_params(params), DEV[0])

    # fewer slots than requests: slots are reused mid-flight
    eng = serve_transformer(model, params, cap, decode=True,
                            slots=max(2, len(mix) // 2))
    with eng:
        eng.warmup(prompt_lens=[s0 for s0, _mn in mix])
        misses0 = eng.program_cache.stats()["misses"]
        futs = [eng.submit(p, mn) for p, (_s0, mn) in zip(prompts, mix)]
        outs = [f.result(600) for f in futs]
        st = eng.stats()
    _check(st["program_cache"]["misses"] == misses0,
           "decode traffic compiled after warm-up")
    _check(st["decode_fallbacks"] == 0, "decode step fell back")

    # ONE reference call: rows right-padded to the capacity (the reference
    # is causal — padding cannot reach an earlier position)
    batch = np.zeros((len(outs), cap), np.int32)
    for i, o in enumerate(outs):
        _check(o.shape == (mix[i][0] + mix[i][1],)
               and np.array_equal(o[:mix[i][0]], prompts[i]),
               f"request {i}: wrong shape or prompt not echoed")
        batch[i, :len(o)] = o
    ref = np.asarray(reference.reference_logits(hp, batch, cfg))
    scale = float(np.abs(ref).max())
    tol = 2e-5 if R else 4 * BF16_EPS * scale

    # (a) the prefill program's last-position logits, per prompt
    pre_err = [float(np.abs(
        reference.prefill_logits(model, params, p) - ref[i, len(p) - 1]).max())
        for i, p in enumerate(prompts)]
    # (b) teacher-forced on the engine's own output: the reference's logit
    # of each chosen token against the reference's maximum
    gaps = [reference.greedy_gaps(ref[i], o, mix[i][0])
            for i, o in enumerate(outs)]
    flips = int(sum((g > 0).sum() for g in gaps))
    n_tok = int(sum(len(g) for g in gaps))
    worst_gap = float(max(g.max() for g in gaps))

    # informational: the OLD oracle. generate() is a differently shaped
    # program (cache of Sb+max_new rows, not S_cap); it is judged by the
    # same reference (one more batch of the same shape: no new compile),
    # and its token disagreements with the engine are counted.
    picks = list(range(0, len(mix), 3))
    gens = [np.asarray(model.generate(params, prompts[i][None],
                                      mix[i][1]))[0] for i in picks]
    gbatch = np.zeros_like(batch)
    for j, g in enumerate(gens):
        gbatch[j, :len(g)] = g
    gref = np.asarray(reference.reference_logits(hp, gbatch, cfg))
    old = {"requests": len(picks),
           "token_mismatches": int(sum((g != outs[i]).sum()
                                       for g, i in zip(gens, picks))),
           "worst_gap": float(max(
               reference.greedy_gaps(gref[j], g, mix[i][0]).max()
               for j, (g, i) in enumerate(zip(gens, picks))))}

    _check(max(pre_err) < tol,
           f"prefill logits off the float32 reference: {pre_err} >= {tol}")
    _check(worst_gap < 2 * tol,
           f"engine chose tokens {worst_gap} below the reference's maximum "
           f"(allowed {2 * tol})")
    _check(old["worst_gap"] < 2 * tol, f"generate() off the reference: {old}")
    return {"requests": len(mix), "slots": st["slots"], "seq_bucket": cap,
            "tokens_out": st["tokens_out"], "decode_steps": st["decode_steps"],
            "prefills": st["prefills"],
            "ref_logit_abs_max": scale, "tol": tol,
            "prefill_logit_max_abs_err": max(pre_err),
            "generated_tokens": n_tok, "argmax_flips_vs_reference": flips,
            "worst_gap_below_reference_max": worst_gap,
            "generate_vs_engine": old}


# ====================================================================== #
# four chips: arrays split across devices                                #
# ====================================================================== #
def _shards(arr, axis, want_devices):
    sh = arr.larray.addressable_shards
    devs = {s.device.id for s in sh}
    _check(len(sh) == want_devices and len(devs) == want_devices,
           f"{len(sh)} shards on devices {sorted(devs)}, "
           f"wanted {want_devices} distinct")
    q = arr.larray.shape[axis] // want_devices
    _check(all(s.data.shape[axis] == q for s in sh),
           f"shard extents {[s.data.shape for s in sh]} are not a "
           f"quarter ({q}) each")
    return sorted(devs)


def phase_split():
    import optax

    P = ARGS.chips
    rng = np.random.default_rng(ARGS.seed + 4)
    one = TPUCommunication(devices=DEV[:1])
    out = {}

    a = rng.standard_normal((256 if R else 8192, 1024), dtype=np.float32)
    x = ht.array(a, split=0)
    out["shard_devices"] = _shards(x, 0, P)
    y = x.resplit(1)
    _check(y.split == 1, "resplit did not move the split axis")
    _shards(y, 1, P)
    np.testing.assert_array_equal(y.numpy(), a)
    # uneven: 1001 rows pad to the canonical layout across the devices
    b = np.arange(1001 * 7, dtype=np.float32).reshape(1001, 7) / 7.0
    hb = ht.array(b, split=0)
    np.testing.assert_array_equal(hb.resplit(1).numpy(), b)
    np.testing.assert_allclose((hb * 2.0 + 1.0).sum(axis=0).numpy(),
                               (b * 2.0 + 1.0).sum(axis=0), rtol=1e-5)
    out["resplit"] = {"shape": list(a.shape), "uneven": [1001, 7]}
    del x, y, hb

    n, iters = (4096 if R else 1 << 23), 5
    xk, init = _blobs(n, ARGS.seed + 1)
    c4, i4 = _fit_kmeans(xk, init, iters)
    c1, i1 = _fit_kmeans(xk, init, iters, comm=one)
    kc = float(np.abs(c4 - c1).max())
    ki = abs(i4 - i1) / i1
    _check(kc < (1e-5 if R else BF16_EPS), f"kmeans: 4-device centroids off one-device by {kc}")
    _check(ki < (1e-4 if R else 2 * BF16_EPS),
           f"kmeans inertia rel diff {ki}")
    out["kmeans"] = {"n": n, "centroid_max_abs_diff": kc,
                     "inertia_rel_diff": ki}
    del xk

    # one train step on dp2 x tp2 against the same step on one device.
    # Depth cut to 4 (widths whole): each grid compiles its own program.
    cfg = _lm_config(4)
    B, S = (4, 32) if R else (8, 1024)
    toks_h = rng.integers(0, cfg.vocab, (B, S)).astype(np.int32)
    res = {}
    for name, grid in (
            ("dp2_tp2", ht.MeshGrid((2, 1, 2, 1), AXES, devices=DEV[:4])),
            ("one", ht.MeshGrid((1, 1, 1, 1), AXES, devices=DEV[:1]))):
        model = TransformerLM(grid, cfg)
        params = model.init(ARGS.seed)
        before = reference.host_params(params)
        tx = optax.sgd(0.5)  # the parameter delta IS the gradient
        p2, _o, loss = model.make_train_step(tx)(
            params, tx.init(params), model.shard_batch(toks_h))
        after = reference.host_params(p2)
        res[name] = (float(np.asarray(loss)), jax.tree.map(
            lambda x0, x1: x1 - x0, before, after))
        if name == "dp2_tp2":
            devs = {s.device.id
                    for s in p2["stages"]["wqkv"].addressable_shards}
            _check(len(devs) == 4, f"wqkv lives on devices {sorted(devs)}")
        del params, p2, _o
    (l4, g4), (l1, g1) = res["dp2_tp2"], res["one"]
    rel = jax.tree.map(
        lambda u, v: float(np.linalg.norm(u - v) / np.linalg.norm(v)), g4, g1)
    worst = max(jax.tree.leaves(rel))
    tol = 1e-4 if R else 0.05
    _check(abs(l4 - l1) < (1e-5 if R else 4 * BF16_EPS),
           f"loss {l4} on dp2 x tp2 vs {l1} on one device")
    _check(worst < tol, f"gradient step differs: {rel}")
    out["train_step"] = {"shape": f"L{cfg.n_layers}_d{cfg.d_model}_B{B}_S{S}",
                         "loss_dp2_tp2": l4, "loss_one_device": l1,
                         "worst_update_rel_diff": worst, "tol": tol}
    return out


# ====================================================================== #
def _fallback_counters():
    found = {}

    def walk(node, path):
        if isinstance(node, dict):
            for k, v in node.items():
                walk(v, path + (str(k),))
        elif path and path[-1].endswith("fallbacks") \
                and isinstance(node, (int, float)):
            found[".".join(path)] = node

    walk(ht.runtime_stats(), ())
    for k, v in metrics.counters().items():
        if k.endswith("fallbacks"):
            found["counters." + k] = v
    return found


def main():
    if ARGS.chips == 4:
        _phase("split", phase_split)
    else:
        _phase("tensor", phase_tensor)
        _phase("kmeans", phase_kmeans)
        _phase("train", phase_train)
        _phase("decode", phase_decode)
    fb = _fallback_counters()
    nonzero = {k: v for k, v in fb.items() if v}
    print(json.dumps({"phase": "fallbacks", "ok": not nonzero,
                      "counters_checked": len(fb), "nonzero": nonzero}),
          flush=True)
    _check(fb and not nonzero, f"fallback counters moved: {nonzero}")
    print(json.dumps({
        "phase": "cache", "dir": _CACHE_DIR,
        "placed_by": ("JAX_COMPILATION_CACHE_DIR"
                      if "JAX_COMPILATION_CACHE_DIR" in os.environ
                      else "fixed path in the checkout" if _CACHE_DIR
                      else "off (rehearsal)"),
        "entries_at_start": _ENTRIES_AT_START, "entries_now": _cache_entries(),
        "persistent_hits": _HITS["hits"], "persistent_misses": _HITS["misses"],
        "compile_s_total": round(_COMPILE["s"], 3)}), flush=True)
    last = {"ok": True}
    if R:
        last["rehearsal"] = True
    last["device"] = {"platform": DEV[0].platform, "kind": DEV[0].device_kind,
                      "count": len(DEV)}
    print(json.dumps(last), flush=True)


if __name__ == "__main__":
    main()
