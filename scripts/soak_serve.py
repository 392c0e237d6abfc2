#!/usr/bin/env python
"""Open-loop serve soak with p99-under-load acceptance (ISSUE 14).

Brings up a multi-tenant :class:`heat_tpu.serve.ServingExecutor` over the
launch mesh (the ladder runs it at 4 virtual CPU devices), registers
two tenants —

* ``hi``: priority 10, an SLO-derived deadline, a small share of traffic
  (the interactive tenant the acceptance bar protects), and
* ``lo``: priority 0, a queue quota + its own (looser) SLO (the bulk
  tenant overload is allowed to land on)

— estimates capacity closed-loop, then drives seeded open-loop Poisson
phases at 1× and 2× (optionally 4×) of it. The ≥2× phases run with a
fault plan armed (default ``serve.batch.dispatch=every:5`` — the bounded
dispatch-retry path absorbs every fire) and a mid-phase worker stall
that deterministically pushes the queue past its bound. A final breaker
phase opens the ``lo`` circuit under a persistent dispatch fault and
measures fast-fail latency against the dispatch-retry failure path.

Verdicts (exit 1 if any fails — the ladder gates on this):

* ``worker_alive``   — the dispatch worker survived every phase;
* ``zero_untyped``   — every rejected request carried a *typed* serve
  error (no raw exception ever reached a client);
* ``hi_p99_le_slo``  — the high-priority tenant's p99 stayed within its
  SLO at 2× offered load;
* ``shed_skew``      — ≥90% of shed volume landed on the low-priority
  tenant (and sheds actually happened — an overload harness that never
  overloads is lying);
* ``breaker_fast``   — breaker-open fast-fail latency < 1/10 of the
  dispatch-retry failure path's;
* ``breaker_recovered`` — after cool-down, a half-open probe closed the
  breaker and the tenant serves again.

Prints ONE JSON line (phase reports + per-phase serve.* counter deltas +
verdicts).

    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=4 \
        python scripts/soak_serve.py --quick
"""

import argparse
import json
import sys
import time


def _counter_delta(before: dict, after: dict) -> dict:
    keys = set(before) | set(after)
    return {k: int(after.get(k, 0)) - int(before.get(k, 0))
            for k in sorted(keys)
            if k.startswith("serve.")
            and int(after.get(k, 0)) != int(before.get(k, 0))}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--duration", type=float, default=4.0,
                    help="seconds per load phase")
    ap.add_argument("--loads", default="1,2",
                    help="offered-load multipliers over estimated capacity")
    ap.add_argument("--fault", default="serve.batch.dispatch=every:5",
                    help="fault plan armed during the >=2x phases "
                         "('' disarms)")
    ap.add_argument("--quick", action="store_true",
                    help="short deterministic form for the CI ladder "
                         "(~10 s of phases)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--max-rps", type=float, default=2000.0,
                    help="offered-rate clamp (a python generator thread "
                         "cannot emit much past this)")
    args = ap.parse_args()
    if args.quick:
        args.duration = min(args.duration, 2.0)

    import numpy as np

    import heat_tpu as ht
    from heat_tpu.serve import (Pow2Buckets, ServeCircuitOpen, ServeConfig,
                                ServeMetrics, ServingExecutor, TenantLoad,
                                estimate_capacity, run_open_loop)
    from heat_tpu.serve.adapters import _centroid_assign_fn
    from heat_tpu.utils import faults
    from heat_tpu.utils import metrics as _pm

    comm = ht.get_comm()
    # a deliberately heavy-ish model (nearest-centroid over 8192 centers)
    # keeps capacity in the hundreds-to-low-thousands req/s band a python
    # open-loop generator can genuinely exceed (and below --max-rps, so
    # the 1x/2x multipliers scale for real instead of clamping)
    d, k = 256, 8192
    rng = np.random.default_rng(args.seed)
    fn = _centroid_assign_fn(
        rng.standard_normal((k, d)).astype(np.float32), comm)
    policy = Pow2Buckets(min_rows=comm.size, multiple_of=comm.size)
    cfg = ServeConfig(max_batch=16, max_wait_ms=2.0, queue_limit=128,
                      bucket_rows=policy)
    metrics = ServeMetrics()
    ex = ServingExecutor(fn, cfg, name="soak", cache_token=comm.cache_key,
                         metrics=metrics)
    record = {"devices": comm.size, "quick": bool(args.quick),
              "model": {"d": d, "k": k}, "phases": []}
    verdicts = {}
    try:
        ex.warmup((d,), np.float32, rows=(1, 2, 5, 9, 17, 33, 65))
        # n stays under queue_limit so the estimate itself never sheds
        cap = estimate_capacity(ex, (d,), rows=1, n=96, seed=args.seed)
        metrics.reset()
        # SLOs on the same monotonic clock everything else uses: hi gets
        # a bound generous against box noise (~30 batch service times,
        # and 3x the injected stall) but far below what sitting behind
        # the low-priority backlog would cost a FIFO executor
        stall_s = 0.35 if args.quick else 0.5
        batch_ms = 1e3 * cfg.max_batch / max(cap, 1e-9)
        slo_hi_ms = max(1000.0, 30.0 * batch_ms, 3e3 * stall_s)
        slo_lo_ms = 4.0 * slo_hi_ms
        ex.register_tenant("hi", priority=10, slo_ms=slo_hi_ms)
        ex.register_tenant("lo", priority=0,
                           max_queue=int(cfg.queue_limit * 3 // 4),
                           slo_ms=slo_lo_ms,
                           breaker_cooldown_s=0.25 if args.quick else 1.0)
        record["capacity_rps"] = round(cap, 1)
        record["slo_hi_ms"] = round(slo_hi_ms, 1)
        record["slo_lo_ms"] = round(slo_lo_ms, 1)

        hi_p99 = {}
        shed_hi = shed_lo = 0
        untyped = 0
        for mult_s in args.loads.split(","):
            mult = float(mult_s)
            total = min(mult * cap, args.max_rps)
            # hi rides a small absolute share so a stall backlog of hi
            # requests never overflows the whole queue bound
            hi_rate = min(0.25 * total, 60.0)
            lo_rate = max(total - hi_rate, 1.0)
            loads = [
                TenantLoad("hi", hi_rate, rows_mix=(1, 2)),
                TenantLoad("lo", lo_rate, rows_mix=(1, 2, 3)),
            ]
            overload = mult >= 2.0
            fault_plan = args.fault if (overload and args.fault) else None
            stall = ((0.3 * args.duration, stall_s) if overload else None)
            before = dict(_pm.counters())
            if fault_plan:
                with faults.inject(fault_plan):
                    rep = run_open_loop(
                        ex, loads, args.duration, (d,), seed=args.seed,
                        stall=stall)
            else:
                rep = run_open_loop(ex, loads, args.duration, (d,),
                                    seed=args.seed, stall=stall)
            rep["load_x"] = mult
            rep["fault"] = fault_plan
            rep["counters_delta"] = _counter_delta(before,
                                                   dict(_pm.counters()))
            record["phases"].append(rep)
            hi_p99[mult] = rep["tenants"]["hi"]["latency_ms"].get("p99")
            if overload:
                shed_hi += rep["tenants"]["hi"]["shed"]
                shed_lo += rep["tenants"]["lo"]["shed"]
            untyped += rep["totals"]["untyped"]

        # ---- breaker phase: open lo's circuit under a persistent fault,
        # measure fast-fail vs the dispatch-retry failure path ---------- #
        breaker = {}
        retry_lat = []
        x1 = rng.standard_normal((1, d)).astype(np.float32)
        with faults.inject("serve.batch.dispatch=every:1"):
            trips = ex.admission.DEFAULT_BREAKER_FAILURES
            for _ in range(trips):
                t0 = time.monotonic()
                try:
                    ex.submit(x1, tenant="lo").result(60)
                except Exception:
                    pass
                retry_lat.append(time.monotonic() - t0)
        fast_lat = []
        opened = False
        for _ in range(20):
            t0 = time.monotonic()
            try:
                ex.submit(x1, tenant="lo")
            except ServeCircuitOpen:
                opened = True
            fast_lat.append(time.monotonic() - t0)
        breaker["opened"] = opened
        breaker["retry_fail_ms"] = round(
            1e3 * sum(retry_lat) / max(len(retry_lat), 1), 3)
        fast_lat.sort()
        breaker["fast_fail_ms"] = round(
            1e3 * fast_lat[len(fast_lat) // 2], 4)
        breaker["ratio"] = round(
            breaker["fast_fail_ms"] / max(breaker["retry_fail_ms"], 1e-9),
            5)
        # recovery: cool-down elapses, the half-open probe dispatches
        # clean (faults disarmed) and closes the breaker
        time.sleep((ex.admission.get("lo").breaker_cooldown_s
                    or ex.admission.DEFAULT_BREAKER_COOLDOWN_S) + 0.05)
        try:
            ex.submit(x1, tenant="lo").result(60)
            breaker["recovered"] = (
                ex.admission.breaker_state("lo") == "closed")
        except Exception as exc:
            breaker["recovered"] = False
            breaker["recover_error"] = repr(exc)[:200]
        record["breaker"] = breaker

        two_x = next((m for m in hi_p99 if m >= 2.0), None)
        total_shed = shed_hi + shed_lo
        verdicts = {
            "worker_alive": ex.worker_alive,
            "zero_untyped": untyped == 0,
            "hi_p99_le_slo": (two_x is not None
                              and hi_p99[two_x] is not None
                              and hi_p99[two_x] <= slo_hi_ms),
            "shed_skew": (total_shed > 0
                          and shed_lo / total_shed >= 0.90),
            "breaker_fast": (breaker["opened"]
                             and breaker["ratio"] < 0.1),
            "breaker_recovered": bool(breaker.get("recovered")),
        }
        record["shed_hi_2x"] = shed_hi
        record["shed_lo_2x"] = shed_lo
    except Exception as exc:  # the harness itself broke: loud, typed
        record["error"] = repr(exc)[:400]
        verdicts = {"harness": False}
    finally:
        try:
            ex.close(drain=False, timeout=30)
        except Exception:
            pass
    record["verdicts"] = verdicts
    record["ok"] = bool(verdicts) and all(verdicts.values())
    print(json.dumps(record), flush=True)
    return 0 if record["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
