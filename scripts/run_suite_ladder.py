#!/usr/bin/env python
"""CI ladder with an auditable skip inventory (round-4 verdict #5).

Runs the full suite at each device count (reference ``Jenkinsfile:24-33``
runs its suite under ``mpirun -n 1..8``; a virtual CPU mesh is the TPU
analog), captures ``pytest -rs`` output, and writes a JSON artifact where
EVERY skip names its reason — so "74 skips at 1 device" decomposes into
named device-count guards instead of unexplained coverage loss.

Optionally (``--examples``) smoke-runs every script in ``examples/`` on
the largest mesh of the ladder.

    python scripts/run_suite_ladder.py --devices 1,2,4,8 \
        --out LADDER_r05.json
"""

import argparse
import json
import os
import re
import subprocess
import sys
import time

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# "SKIPPED [8] tests/test_foo.py:123: needs a multi-device mesh"
_SKIP_RE = re.compile(r"^SKIPPED \[(\d+)\] ([^:]+:\d+): (.*)$")
_SUMMARY_RE = re.compile(
    r"(?:(\d+) failed, )?(\d+) passed(?:, (\d+) skipped)?"
    r"(?:, \d+ deselected)?(?:, (\d+) error)?.* in ([\d.]+)s")


def _env(n: int) -> dict:
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["JAX_PLATFORMS"] = "cpu"
    env["HEAT_TPU_TEST_DEVICES"] = str(n)
    env["HEAT_TPU_RUN_SLOW"] = "1"  # the ladder runs the soak tests too
    return env


def run_suite(n: int, timeout: float) -> dict:
    t0 = time.time()
    # per-test executable/counter log (conftest appends one JSON line per
    # test): on the rare 4-device SIGABRT (the per-process executable budget) the last line names
    # the accumulated jit-executable count right before the abort, so the
    # flakiness can be correlated with cache growth
    stats_path = os.path.join(_REPO, f".ladder_teststats_{n}.jsonl")
    try:
        os.unlink(stats_path)
    except OSError:
        pass
    env = _env(n)
    env["HEAT_TPU_LADDER_STATS"] = stats_path
    try:
        # -X faulthandler: the rare 4-device XLA:CPU SIGABRT (the per-process executable budget)
        # kills the interpreter below pytest — only a faulthandler dump on
        # stderr survives it, and it is persisted into the ladder JSON
        out = subprocess.run(
            [sys.executable, "-X", "faulthandler", "-m", "pytest", "tests/",
             "-x", "-q", "-rs"],
            env=env, capture_output=True, text=True, timeout=timeout,
            cwd=_REPO)
    except subprocess.TimeoutExpired:
        return {"devices": n, "error": f"suite exceeded {timeout:.0f}s"}
    skips = {}
    for line in out.stdout.splitlines():
        m = _SKIP_RE.match(line.strip())
        if m:
            count, _loc, reason = m.groups()
            skips[reason] = skips.get(reason, 0) + int(count)
    rec = {"devices": n, "rc": out.returncode,
           "wall_s": round(time.time() - t0, 1),
           "skip_reasons": dict(sorted(skips.items(),
                                       key=lambda kv: -kv[1]))}
    m = _SUMMARY_RE.search(out.stdout)
    if m:
        failed, passed, skipped, errors, dur = m.groups()
        rec.update(passed=int(passed), skipped=int(skipped or 0),
                   failed=int(failed or 0), errors=int(errors or 0),
                   pytest_s=float(dur))
    else:
        rec["tail"] = out.stdout.strip().splitlines()[-3:]
    if out.returncode != 0:
        # surface what broke in the CI log and the artifact — the summary
        # line alone names no test and shows no traceback
        tail = out.stdout.strip().splitlines()[-40:]
        rec["failure_tail"] = tail
        print("\n".join(tail), file=sys.stderr, flush=True)
    stderr = out.stderr or ""
    if out.returncode < 0 or "Fatal Python error" in stderr:
        # interpreter abort (SIGABRT/SIGSEGV): pytest never reported — the
        # faulthandler dump on stderr is the only trace; keep it
        rec["abort_signal"] = -out.returncode if out.returncode < 0 else None
        rec["abort_traceback"] = stderr.strip().splitlines()[-120:]
        print("\n".join(rec["abort_traceback"][-40:]), file=sys.stderr,
              flush=True)
    # the last per-test counter line = state right before exit/abort
    # (the per-process executable budget: correlate the SIGABRT with executable-cache growth)
    try:
        with open(stats_path) as f:
            lines = [l for l in f.read().splitlines() if l.strip()]
        if lines:
            rec["executable_counters"] = json.loads(lines[-1])
            rec["executable_counters"]["tests_logged"] = len(lines)
    except OSError:
        pass
    except Exception as exc:
        rec["executable_counters"] = {"error": repr(exc)}
    finally:
        try:
            os.unlink(stats_path)
        except OSError:
            pass
    return rec


# fast, numerically-loaded subset for the fusion on/off A/B: the op-engine
# surface where deferred evaluation could drift from eager semantics.
# The reduction-heavy slice (statistics + nan-reductions + the distributed
# statistics module) exercises the PR 4 reduction-fused tapes; the
# linalg-heavy slice (linalg + transformer) the PR 5 contraction-fused
# tapes; the manipulations-heavy slice the PR 6 resplit-fused tapes (the
# alignment/pre-alignment resplit surface: concatenate/reshape/stack over
# mixed splits) — the per-test HEAT_TPU_LADDER_STATS log carries
# fusion_reduce_flushes / fusion_contract_flushes / fusion_resplit_nodes /
# fusion_step_flushes next to the executable counters so the A/B shows
# which tests actually took the collective-fused paths
_FUSION_AB_TESTS = [
    "tests/test_operations.py", "tests/test_arithmetics.py",
    "tests/test_fuzz_chains.py", "tests/test_rounding_exp_trig.py",
    "tests/test_fusion.py",
    # reduction-heavy slice
    "tests/test_statistics.py", "tests/test_nan_reductions.py",
    "tests/test_statistics_distributed.py",
    # linalg-heavy slice (contraction-fused tapes: GEMM/einsum/tensordot
    # record_contract paths + the transformer forward that inherits them)
    "tests/test_linalg.py", "tests/test_linalg_more.py",
    "tests/test_linalg_gauss.py", "tests/test_transformer.py",
    # manipulations-heavy slice (resplit-fused tapes: record_resplit plus
    # the concatenate/reshape/stack alignment resplits that now record)
    "tests/test_manipulations.py", "tests/test_manips_distributed.py",
    # training-heavy slice (differentiable tapes: trace_step train steps,
    # packed-gradient transformer/DataParallel steps, batched optimizer
    # updates — fusion_step_flushes logged per test)
    "tests/test_trace_step.py", "tests/test_nn_optim_data.py",
]


# training-heavy subset for the quantized-collective A/B: the packed
# train-step surfaces (trace_step, the TransformerLM/DataParallel packed
# steps) plus the quant property/acceptance suite itself — the per-test
# HEAT_TPU_LADDER_STATS log carries quant_collectives/quant_bytes_saved
# so the A/B shows which tests actually moved quantized bytes
_QUANT_AB_TESTS = [
    "tests/test_trace_step.py", "tests/test_transformer.py",
    "tests/test_nn_optim_data.py", "tests/test_quant_collectives.py",
]


def _run_env_ab(env_key: str, legs_spec, tests, n: int,
                timeout: float, extra_env=None) -> dict:
    """Shared A/B mechanics for the env-flag gates: run ``tests`` once
    per ``(label, env value)`` leg, both legs must pass (``agree``).
    ``legs_spec`` is ``((label, value), (label, value))``;
    ``extra_env`` rides on BOTH legs (the hier A/B declares the tier
    factorization on both sides and toggles only the gate)."""
    result = {}
    for label, value in legs_spec:
        env = _env(n)
        env[env_key] = value
        if extra_env:
            env.update(extra_env)
        t0 = time.time()
        try:
            out = subprocess.run(
                [sys.executable, "-m", "pytest", *tests, "-q"],
                env=env, capture_output=True, text=True, timeout=timeout,
                cwd=_REPO)
        except subprocess.TimeoutExpired:
            result[label] = {"error": f"exceeded {timeout:.0f}s"}
            continue
        rec = {"rc": out.returncode, "wall_s": round(time.time() - t0, 1)}
        m = _SUMMARY_RE.search(out.stdout)
        if m:
            failed, passed, skipped, errors, dur = m.groups()
            rec.update(passed=int(passed), failed=int(failed or 0),
                       skipped=int(skipped or 0), errors=int(errors or 0))
        if out.returncode != 0:
            rec["tail"] = out.stdout.strip().splitlines()[-15:]
        result[label] = rec
    result["agree"] = all(
        result.get(label, {}).get("rc") == 0 for label, _ in legs_spec)
    return result


def run_quant_ab(n: int, timeout: float) -> dict:
    """``HEAT_TPU_QUANT_COLLECTIVES=0`` vs ``int8`` on the training-heavy
    subset: the quant leg must keep every packed-step test green (the
    codec may never change WHICH path runs, only its wire format, within
    the documented error contract), and the exact leg proves the escape
    hatch restores today's behavior — exit-gating, like the fusion A/B."""
    return _run_env_ab("HEAT_TPU_QUANT_COLLECTIVES",
                       (("exact", "0"), ("quant", "int8")),
                       _QUANT_AB_TESTS, n, timeout)


def run_fusion_ab(n: int, timeout: float) -> dict:
    """One suite leg with ``HEAT_TPU_FUSION=0`` vs ``1`` on a fast subset:
    any test that passes eager but fails fused (or vice versa) is semantic
    drift the fused engine introduced — exit-gating, like the serve smoke."""
    return _run_env_ab("HEAT_TPU_FUSION",
                       (("eager", "0"), ("fused", "1")),
                       _FUSION_AB_TESTS, n, timeout)


# chunk-pipelined collectives gate: the training-heavy subset (the paths
# whose packed collectives chunk) + the chunk contract module itself; the
# HEAT_TPU_LADDER_STATS log carries chunk_collectives/chunk_fallbacks so
# the A/B shows which tests actually dispatched chunked legs
_CHUNK_AB_TESTS = [
    "tests/test_trace_step.py", "tests/test_transformer.py",
    "tests/test_nn_optim_data.py", "tests/test_chunk_collectives.py",
]


def run_chunk_ab(n: int, timeout: float) -> dict:
    """``HEAT_TPU_FUSION_CHUNKS=1`` vs ``4`` on the training-heavy
    subset: the chunked leg must keep every packed-step test green
    (chunking may never change WHICH path runs or its values — the
    N-chunk emission is value-bitwise the unchunked plan per codec), and
    the CHUNKS=1 leg proves the default is bitwise today's behavior —
    exit-gating, like the fusion/quant A/Bs."""
    return _run_env_ab("HEAT_TPU_FUSION_CHUNKS",
                       (("unchunked", "1"), ("chunked", "4")),
                       _CHUNK_AB_TESTS, n, timeout)


# training-heavy subset for the hierarchical-collective A/B: the packed
# train-step surfaces plus the hier contract module itself — the
# per-test HEAT_TPU_LADDER_STATS log carries hier_collectives /
# hier_fallbacks so the A/B shows which tests actually decomposed
_HIER_AB_TESTS = [
    "tests/test_trace_step.py", "tests/test_transformer.py",
    "tests/test_nn_optim_data.py", "tests/test_hier_collectives.py",
]


def run_hier_ab(n: int, timeout: float) -> dict:
    """``HEAT_TPU_HIER=0`` vs ``1`` with the tier factorization
    ``(2, n/2)`` declared on BOTH legs: the hier leg must keep every
    packed-step test green (the decomposition may never change WHICH
    path runs — only reassociate its psums within the documented few-ulp
    freedom, with per-tier codecs carrying their own contract), and the
    HIER=0 leg proves the escape hatch restores today's flat behavior
    bitwise — exit-gating, like the fusion/quant/chunk A/Bs."""
    return _run_env_ab("HEAT_TPU_HIER",
                       (("flat", "0"), ("hier", "1")),
                       _HIER_AB_TESTS, n, timeout,
                       extra_env={"HEAT_TPU_MESH_TIERS": f"2,{n // 2}"})


# analytics slice for the fit-step A/B: the estimator surfaces whose
# fit()/predict hot loops now dispatch through fusion.fit_step_call
# (cluster family Lloyd iterations, Lasso coordinate sweeps, the Lanczos
# inner loop behind spectral, the KNN/GaussianNB assign programs) plus
# the fit contract module itself — the per-test HEAT_TPU_LADDER_STATS
# log carries fit_step_flushes/fit_step_fallbacks so the A/B shows which
# tests actually dispatched compiled iterations
_FIT_AB_TESTS = [
    "tests/test_analytics_fit.py", "tests/test_estimators.py",
    "tests/test_estimators_distributed.py", "tests/test_spatial_cluster.py",
    "tests/test_cluster_distributed.py", "tests/test_linalg.py",
]


def run_fit_ab(n: int, timeout: float) -> dict:
    """``HEAT_TPU_FUSION_FIT=0`` vs ``1`` on the analytics slice: the
    fused leg must keep every estimator test green (the tape-compiled
    step may never change WHICH mathematics runs — only pack its psums
    and donate its carries, within the documented numerics contract),
    and the FIT=0 leg proves the escape hatch restores the legacy step
    programs — exit-gating, like the fusion/quant/chunk/hier A/Bs."""
    return _run_env_ab("HEAT_TPU_FUSION_FIT",
                       (("legacy", "0"), ("fused", "1")),
                       _FIT_AB_TESTS, n, timeout)


_CHAOS_SITE_RE = re.compile(
    r"test_chaos_site\[([^\]]+)\]\s+(PASSED|FAILED|ERROR|SKIPPED)")


def run_chaos(n: int, timeout: float) -> dict:
    """The fault-injection chaos matrix (tests/test_faults.py) as a
    ladder stage: every registered site fired one-at-a-time (seeded)
    inside its designated workload, plus the fault-free counter-silence
    leg. Per-site verdicts land in the artifact next to the executable
    counters, so a regression names its failure DOMAIN, not just a test."""
    env = _env(n)
    t0 = time.time()
    try:
        out = subprocess.run(
            [sys.executable, "-m", "pytest", "tests/test_faults.py",
             "-v", "--no-header"],
            env=env, capture_output=True, text=True, timeout=timeout,
            cwd=_REPO)
    except subprocess.TimeoutExpired:
        return {"error": f"chaos matrix exceeded {timeout:.0f}s"}
    sites = {}
    for m in _CHAOS_SITE_RE.finditer(out.stdout):
        sites[m.group(1)] = m.group(2).lower()
    silence = None
    m = re.search(r"test_no_faults_armed_is_silent\s+"
                  r"(PASSED|FAILED|ERROR)", out.stdout)
    if m:
        silence = m.group(1).lower()
    rec = {"rc": out.returncode, "wall_s": round(time.time() - t0, 1),
           "sites": dict(sorted(sites.items())),
           "counter_silence": silence}
    m = _SUMMARY_RE.search(out.stdout)
    if m:
        failed, passed, skipped, errors, _dur = m.groups()
        rec.update(passed=int(passed), failed=int(failed or 0),
                   skipped=int(skipped or 0), errors=int(errors or 0))
    if out.returncode != 0:
        rec["tail"] = out.stdout.strip().splitlines()[-20:]
    return rec


def run_examples(n: int, timeout: float) -> list:
    """Smoke-run every examples/ script end-to-end on an n-device mesh."""
    results = []
    ex_dir = os.path.join(_REPO, "examples")
    for root, _dirs, files in os.walk(ex_dir):
        for f in sorted(files):
            if not f.endswith(".py") or f.startswith("_"):
                continue
            path = os.path.join(root, f)
            rel = os.path.relpath(path, _REPO)
            env = _env(n)
            env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n}"
            env["PYTHONPATH"] = _REPO
            env["MPLBACKEND"] = "Agg"  # no display in CI
            env["HEAT_TPU_EXAMPLE_SMOKE"] = "1"  # examples shrink workloads
            t0 = time.time()
            try:
                out = subprocess.run(
                    [sys.executable, path], env=env, capture_output=True,
                    text=True, timeout=timeout, cwd=_REPO)
                rec = {"example": rel, "rc": out.returncode,
                       "wall_s": round(time.time() - t0, 1)}
                if out.returncode != 0:
                    rec["tail"] = (out.stderr or out.stdout).strip().splitlines()[-5:]
            except subprocess.TimeoutExpired:
                rec = {"example": rel, "rc": -1,
                       "error": f"exceeded {timeout:.0f}s"}
            results.append(rec)
    return results


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--devices", default="1,2,4,8")
    ap.add_argument("--out", default="LADDER_r05.json")
    ap.add_argument("--timeout", type=float, default=3600.0,
                    help="per-device-count suite budget (s)")
    ap.add_argument("--examples", action="store_true",
                    help="also smoke-run examples/ on the largest mesh")
    ap.add_argument("--examples-only", action="store_true",
                    help="skip the suite; run only the examples smoke")
    ap.add_argument("--examples-timeout", type=float, default=600.0)
    ap.add_argument("--no-resplit-audit", action="store_true",
                    help="skip the collective_audit --resplit bounds check")
    ap.add_argument("--fusion-ab", dest="fusion_ab", action="store_true",
                    default=True,
                    help="run the HEAT_TPU_FUSION=0 vs 1 A/B subset "
                         "(default on)")
    ap.add_argument("--no-fusion-ab", dest="fusion_ab", action="store_false",
                    help="skip the fusion on/off semantic-drift A/B")
    ap.add_argument("--fusion-ab-timeout", type=float, default=900.0)
    ap.add_argument("--quant-ab", dest="quant_ab", action="store_true",
                    default=True,
                    help="run the HEAT_TPU_QUANT_COLLECTIVES=0 vs int8 "
                         "A/B on the training-heavy subset (default on)")
    ap.add_argument("--no-quant-ab", dest="quant_ab", action="store_false",
                    help="skip the quantized-collective A/B")
    ap.add_argument("--quant-ab-timeout", type=float, default=900.0)
    ap.add_argument("--chunk-ab", dest="chunk_ab", action="store_true",
                    default=True,
                    help="run the HEAT_TPU_FUSION_CHUNKS=1 vs 4 A/B on "
                         "the training-heavy subset (default on)")
    ap.add_argument("--no-chunk-ab", dest="chunk_ab", action="store_false",
                    help="skip the chunked-collective A/B")
    ap.add_argument("--chunk-ab-timeout", type=float, default=900.0)
    ap.add_argument("--hier-ab", dest="hier_ab", action="store_true",
                    default=True,
                    help="run the HEAT_TPU_HIER=0/1 A/B (tiers declared "
                         "on both legs) on the training-heavy subset "
                         "(default on)")
    ap.add_argument("--no-hier-ab", dest="hier_ab", action="store_false",
                    help="skip the hierarchical-collective A/B")
    ap.add_argument("--hier-ab-timeout", type=float, default=900.0)
    ap.add_argument("--fit-ab", dest="fit_ab", action="store_true",
                    default=True,
                    help="run the HEAT_TPU_FUSION_FIT=0 vs 1 A/B on the "
                         "cluster/lasso/lanczos analytics slice "
                         "(default on)")
    ap.add_argument("--no-fit-ab", dest="fit_ab", action="store_false",
                    help="skip the tape-compiled fit-step A/B")
    ap.add_argument("--fit-ab-timeout", type=float, default=900.0)
    ap.add_argument("--serve-smoke", dest="serve_smoke", action="store_true",
                    default=True, help="run the serving smoke (default on)")
    ap.add_argument("--no-serve-smoke", dest="serve_smoke",
                    action="store_false",
                    help="skip the serving executor smoke step")
    ap.add_argument("--decode-smoke", dest="decode_smoke",
                    action="store_true", default=True,
                    help="run the continuous-batching decode smoke "
                         "(default on)")
    ap.add_argument("--no-decode-smoke", dest="decode_smoke",
                    action="store_false",
                    help="skip the decode engine smoke step")
    ap.add_argument("--data-smoke", dest="data_smoke", action="store_true",
                    default=True,
                    help="run the tape-compiled data-engine smoke "
                         "(default on)")
    ap.add_argument("--no-data-smoke", dest="data_smoke",
                    action="store_false",
                    help="skip the data engine smoke step")
    ap.add_argument("--serve-soak", dest="serve_soak", action="store_true",
                    default=True,
                    help="run the open-loop overload soak with "
                         "p99-under-load verdicts (default on)")
    ap.add_argument("--no-serve-soak", dest="serve_soak",
                    action="store_false",
                    help="skip the serve soak stage")
    ap.add_argument("--serve-soak-timeout", type=float, default=600.0)
    ap.add_argument("--chaos", dest="chaos", action="store_true",
                    default=True,
                    help="run the fault-injection chaos matrix + "
                         "counter-silence check (default on)")
    ap.add_argument("--no-chaos", dest="chaos", action="store_false",
                    help="skip the chaos matrix stage")
    ap.add_argument("--chaos-timeout", type=float, default=600.0)
    args = ap.parse_args()

    ladder = []
    devices = [int(d) for d in args.devices.split(",")]
    if not args.examples_only:
        for n in devices:
            print(f"=== suite at {n} device(s) ===", flush=True)
            rec = run_suite(n, args.timeout)
            print(json.dumps(rec), flush=True)
            ladder.append(rec)

    artifact = {
        "date": time.strftime("%Y-%m-%d"),
        "command": f"python scripts/run_suite_ladder.py "
                   f"--devices {args.devices}",
        "note": "full suite per device count on a virtual CPU mesh "
                "(reference Jenkinsfile:24-33 analog). skip_reasons maps "
                "every pytest -rs skip reason to its occurrence count - "
                "the auditable skip inventory.",
        "ladder": ladder,
    }
    ex = []
    if args.examples or args.examples_only:
        n = max(devices)
        print(f"=== examples smoke at {n} device(s) ===", flush=True)
        ex = run_examples(n, args.examples_timeout)
        for r in ex:
            print(json.dumps(r), flush=True)
        artifact["examples"] = ex

    serve_bad = False
    if args.serve_smoke and not args.examples_only:
        # serving smoke: executor up -> 50 mixed-shape requests -> metrics
        # snapshot sanity, on the 4-device CPU mesh (scripts/serve_smoke.py)
        print("=== serve smoke (4 devices) ===", flush=True)
        env = _env(4)
        env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        env["PYTHONPATH"] = _REPO
        try:
            out = subprocess.run(
                [sys.executable,
                 os.path.join(_REPO, "scripts", "serve_smoke.py")],
                env=env, capture_output=True, text=True, timeout=600.0,
                cwd=_REPO)
            line = next((l for l in reversed(out.stdout.splitlines())
                         if l.startswith("{")), None)
            artifact["serve_smoke"] = (
                json.loads(line) if line
                else {"error": (out.stderr or "no output").strip()[-300:]})
            serve_bad = out.returncode != 0
        except subprocess.TimeoutExpired:
            artifact["serve_smoke"] = {"error": "serve smoke exceeded 600s"}
            serve_bad = True
        print(json.dumps({"serve_smoke_ok": not serve_bad}), flush=True)

    decode_bad = False
    if args.decode_smoke and not args.examples_only:
        # continuous-batching gate (ISSUE 15): mixed-length two-tenant
        # decode through the slot engine — parity vs generate(), zero
        # steady-state misses, pinned stats shape (scripts/decode_smoke.py)
        print("=== decode smoke (4 devices) ===", flush=True)
        env = _env(4)
        env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        env["PYTHONPATH"] = _REPO
        try:
            out = subprocess.run(
                [sys.executable,
                 os.path.join(_REPO, "scripts", "decode_smoke.py")],
                env=env, capture_output=True, text=True, timeout=600.0,
                cwd=_REPO)
            line = next((l for l in reversed(out.stdout.splitlines())
                         if l.startswith("{")), None)
            artifact["decode_smoke"] = (
                json.loads(line) if line
                else {"error": (out.stderr or "no output").strip()[-300:]})
            decode_bad = out.returncode != 0
        except subprocess.TimeoutExpired:
            artifact["decode_smoke"] = {"error": "decode smoke exceeded 600s"}
            decode_bad = True
        print(json.dumps({"decode_smoke_ok": not decode_bad}), flush=True)

    data_bad = False
    if args.data_smoke and not args.examples_only:
        # data-engine gate (ISSUE 17): groupby/top-k/percentile + the
        # streaming folds — numpy parity, percentile == sort path, zero
        # steady-state misses, zero fallbacks (scripts/data_smoke.py)
        print("=== data engine smoke (4 devices) ===", flush=True)
        env = _env(4)
        env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        env["PYTHONPATH"] = _REPO
        try:
            out = subprocess.run(
                [sys.executable,
                 os.path.join(_REPO, "scripts", "data_smoke.py")],
                env=env, capture_output=True, text=True, timeout=600.0,
                cwd=_REPO)
            line = next((l for l in reversed(out.stdout.splitlines())
                         if l.startswith("{")), None)
            artifact["data_smoke"] = (
                json.loads(line) if line
                else {"error": (out.stderr or "no output").strip()[-300:]})
            data_bad = out.returncode != 0
        except subprocess.TimeoutExpired:
            artifact["data_smoke"] = {"error": "data smoke exceeded 600s"}
            data_bad = True
        print(json.dumps({"data_smoke_ok": not data_bad}), flush=True)

    soak_bad = False
    if args.serve_soak and not args.examples_only:
        # overload-robustness gate (ISSUE 14): short deterministic
        # open-loop soak at 1x/2x estimated capacity with
        # serve.batch.dispatch=every:5 armed mid-soak — per-tenant
        # p50/p95/p99 + shed/breaker verdicts land in the artifact next
        # to chaos; any failed verdict fails the round
        print("=== serve soak (4 devices) ===", flush=True)
        env = _env(4)
        env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        env["PYTHONPATH"] = _REPO
        try:
            out = subprocess.run(
                [sys.executable,
                 os.path.join(_REPO, "scripts", "soak_serve.py"),
                 "--quick"],
                env=env, capture_output=True, text=True,
                timeout=args.serve_soak_timeout, cwd=_REPO)
            line = next((l for l in reversed(out.stdout.splitlines())
                         if l.startswith("{")), None)
            artifact["serve_soak"] = (
                json.loads(line) if line
                else {"error": (out.stderr or "no output").strip()[-300:]})
            soak_bad = out.returncode != 0
        except subprocess.TimeoutExpired:
            artifact["serve_soak"] = {
                "error": f"serve soak exceeded {args.serve_soak_timeout:.0f}s"}
            soak_bad = True
        print(json.dumps({
            "serve_soak_ok": not soak_bad,
            "verdicts": artifact["serve_soak"].get("verdicts", {})}),
            flush=True)

    chaos_bad = False
    if args.chaos and not args.examples_only:
        # failure-domain gate: every injection site must degrade
        # gracefully (seeded, one-at-a-time) and a fault-free pass must
        # tick zero faults.* counters (4-device mesh, like serve smoke)
        print("=== chaos matrix (4 devices) ===", flush=True)
        chaos = run_chaos(4, args.chaos_timeout)
        artifact["chaos"] = chaos
        chaos_bad = chaos.get("rc") != 0
        print(json.dumps({"chaos_ok": not chaos_bad,
                          "sites": chaos.get("sites", {})}), flush=True)

    fusion_bad = False
    if args.fusion_ab and not args.examples_only:
        # semantic-drift gate: the same fast, numerically-loaded subset
        # must pass with the fused engine ON and OFF (4-device mesh)
        print("=== fusion on/off A/B (4 devices) ===", flush=True)
        ab = run_fusion_ab(4, args.fusion_ab_timeout)
        artifact["fusion_ab"] = ab
        fusion_bad = not ab.get("agree", False)
        print(json.dumps({"fusion_ab_ok": not fusion_bad}), flush=True)

    quant_bad = False
    if args.quant_ab and not args.examples_only:
        # codec gate: the training-heavy subset must pass exact AND int8
        # (4-device mesh — with the ladder's 8-dev full suites this
        # covers the 4/8-dev acceptance pair)
        print("=== quant collectives A/B (4 devices) ===", flush=True)
        qab = run_quant_ab(4, args.quant_ab_timeout)
        artifact["quant_ab"] = qab
        quant_bad = not qab.get("agree", False)
        print(json.dumps({"quant_ab_ok": not quant_bad}), flush=True)

    hier_bad = False
    if args.hier_ab and not args.examples_only:
        # tier gate: the training-heavy subset must pass flat AND
        # hierarchically decomposed on the simulated (2, 2) two-host
        # factorization of the 4-device mesh
        print("=== hierarchical collectives A/B (4 devices) ===",
              flush=True)
        hab = run_hier_ab(4, args.hier_ab_timeout)
        artifact["hier_ab"] = hab
        hier_bad = not hab.get("agree", False)
        print(json.dumps({"hier_ab_ok": not hier_bad}), flush=True)

    fit_bad = False
    if args.fit_ab and not args.examples_only:
        # fit gate: the analytics slice must pass with the tape-compiled
        # fit steps ON and OFF (4-device mesh) — any leg disagreement is
        # semantic drift the compiled estimator iteration introduced
        print("=== fit-step (analytics) A/B (4 devices) ===", flush=True)
        fab = run_fit_ab(4, args.fit_ab_timeout)
        artifact["fit_ab"] = fab
        fit_bad = not fab.get("agree", False)
        print(json.dumps({"fit_ab_ok": not fit_bad}), flush=True)

    chunk_bad = False
    if args.chunk_ab and not args.examples_only:
        # chunk gate: the training-heavy subset must pass unchunked AND
        # 4-chunked (4-device mesh) — chunking is value-exact per codec,
        # so ANY leg disagreement is a leg-structure bug
        print("=== chunk collectives A/B (4 devices) ===", flush=True)
        cab = run_chunk_ab(4, args.chunk_ab_timeout)
        artifact["chunk_ab"] = cab
        chunk_bad = not cab.get("agree", False)
        print(json.dumps({"chunk_ab_ok": not chunk_bad}), flush=True)

    audit_bad = False
    if not (args.no_resplit_audit or args.examples_only):
        # re-check the reshard planner's collective bounds every round:
        # zero all-gather on split->split, bytes/temp <= the GSPMD
        # baseline, O(N/p) payload scaling (collective_audit --resplit)
        print("=== resplit collective audit (4,8 devices) ===", flush=True)
        env = dict(os.environ)
        try:
            out = subprocess.run(
                [sys.executable,
                 os.path.join(_REPO, "scripts", "collective_audit.py"),
                 "--resplit"],
                env=env, capture_output=True, text=True, timeout=900.0,
                cwd=_REPO)
            line = next((l for l in reversed(out.stdout.splitlines())
                         if l.startswith("{\"summary\"")), None)
            artifact["resplit_audit"] = (
                json.loads(line)["summary"] if line
                else {"error": (out.stderr or "no output").strip()[-300:]})
            audit_bad = out.returncode != 0
        except subprocess.TimeoutExpired:
            artifact["resplit_audit"] = {"error": "audit exceeded 900s"}
            audit_bad = True
        print(json.dumps({"resplit_audit_ok": not audit_bad}), flush=True)

    with open(os.path.join(_REPO, args.out), "w") as f:
        json.dump(artifact, f, indent=1)
    print(f"wrote {args.out}")
    bad = ([r for r in ladder if r.get("rc") != 0]
           + [r for r in ex if r.get("rc") != 0])
    sys.exit(1 if bad or audit_bad or serve_bad or decode_bad or data_bad
             or soak_bad or fusion_bad or quant_bad or chunk_bad or hier_bad
             or fit_bad or chaos_bad else 0)


if __name__ == "__main__":
    main()
