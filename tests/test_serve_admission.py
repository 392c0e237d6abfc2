"""Multi-tenant admission control, SLO-aware shedding, circuit breakers
and the open-loop soak short form (ISSUE 14).

The contract under test, per the overload-robustness tentpole:

* **Backward compat** — an executor with no registered tenant is the
  PR 2 single-FIFO path exactly: no admission counters move, no tenant
  rows appear (the full legacy suite ``tests/test_serve.py`` runs
  unmodified next to this module);
* **Priority** — higher-priority tenants are served first; a full queue
  preempts the youngest strictly-lower-priority queued request (typed
  ``ServeOverloaded`` on ITS future) instead of shedding the incoming
  one; per-tenant quotas stop one tenant filling the shared bound;
* **Rate limiting** — a token bucket per tenant sheds with a typed
  ``ServeRateLimited`` at admission, deterministic under a fake clock;
* **Deadlines on one clock** — enqueue stamp, SLO-derived deadline, the
  EWMA early-shed estimate and ``_expire`` all share ``time.monotonic``;
  a queued-past-deadline request is NEVER dispatched (regression for the
  ISSUE 14 clock-audit satellite), and a request that provably cannot
  meet its deadline is shed typed BEFORE consuming a batch slot;
* **Circuit breaker** — K consecutive post-retry dispatch failures open
  a tenant's breaker; open-state submits fast-fail typed in <1/10 of the
  dispatch-retry failure path's latency; healthy tenants keep serving;
  after the cool-down a half-open probe closes it;
* **Soak short form** — 1.2 s of seeded open-loop two-tenant traffic
  with ``serve.batch.dispatch=every:5`` armed and a mid-phase worker
  stall: worker alive, zero untyped client-visible errors, >=90% of shed
  volume on the low-priority tenant, hi-p99 within its SLO. The full
  1x/2x ladder/bench form lives in ``scripts/soak_serve.py``.

the per-process executable budget discipline: one shared elemwise model program family + one
shared ProgramCache across the module, tiny bucket ladders, and a
module teardown that drops the cache and gc-collects.
"""

import gc
import threading
import time

import numpy as np
import pytest

import heat_tpu as ht
from heat_tpu.serve import (Pow2Buckets, ProgramCache, ServeCircuitOpen,
                            ServeConfig, ServeDeadlineExceeded, ServeMetrics,
                            ServeOverloaded, ServeRateLimited,
                            ServingExecutor, TenantLoad, estimate_capacity,
                            run_open_loop)
from heat_tpu.serve.admission import AdmissionController
from heat_tpu.serve.loadgen import classify_outcome
from heat_tpu.utils import faults
from heat_tpu.utils import metrics as _pm

D = 8
_SHARED_CACHE = ProgramCache(name="test-admission-shared")
_FNS: dict = {}


def _comm():
    return ht.get_comm()


def _policy(comm):
    return Pow2Buckets(min_rows=comm.size, multiple_of=comm.size)


def _elemwise_fn(comm):
    from heat_tpu.core._compat import shard_map

    key = ("elem", comm.cache_key)
    if key not in _FNS:
        def local(x):
            return x * np.float32(2.0) + np.float32(1.0)

        _FNS[key] = (local if comm.size == 1 else shard_map(
            local, mesh=comm.mesh, in_specs=comm.spec(2, 0),
            out_specs=comm.spec(2, 0), check_vma=False))
    return _FNS[key]


def _executor(comm, metrics=None, **cfg):
    cfg.setdefault("bucket_rows", _policy(comm))
    return ServingExecutor(
        _elemwise_fn(comm), ServeConfig(**cfg), cache_token=comm.cache_key,
        metrics=metrics or ServeMetrics(), program_cache=_SHARED_CACHE)


def _ones(rows, comm=None, value=1.0):
    return np.full((rows, D), value, np.float32)


def _want(x):
    return x * np.float32(2.0) + np.float32(1.0)


@pytest.fixture(scope="module", autouse=True)
def _module_budget():
    """§2b: leave the suite's executable end-state where we found it."""
    yield
    _SHARED_CACHE.reset()
    _FNS.clear()
    gc.collect()


# --------------------------------------------------------------------- #
# controller unit tests (pure host state, fake clock, zero compiles)    #
# --------------------------------------------------------------------- #
class TestAdmissionController:
    def test_token_bucket_deterministic_refill(self):
        t = [0.0]
        adm = AdmissionController(clock=lambda: t[0])
        adm.register("a", rate_limit=2.0, burst=2.0)
        adm.check_tenant("a")
        adm.check_tenant("a")          # burst of 2 spent
        with pytest.raises(ServeRateLimited):
            adm.check_tenant("a")
        t[0] = 0.5                     # 0.5 s * 2 req/s = 1 token back
        adm.check_tenant("a")
        with pytest.raises(ServeRateLimited):
            adm.check_tenant("a")
        assert adm.tenant_stats()["a"]["rate_limited"] == 2

    def test_breaker_cycle_open_half_open_closed(self):
        t = [0.0]
        adm = AdmissionController(clock=lambda: t[0])
        adm.register("b", breaker_failures=2, breaker_cooldown_s=1.0,
                     half_open_max=1)
        adm.check_tenant("b")
        adm.on_batch_outcome(["b"], ok=False)
        assert adm.breaker_state("b") == "closed"   # streak 1 < 2
        adm.on_batch_outcome(["b"], ok=False)
        assert adm.breaker_state("b") == "open"
        with pytest.raises(ServeCircuitOpen):
            adm.check_tenant("b")                   # fast fail while open
        t[0] = 1.1                                  # cool-down elapses
        adm.check_tenant("b")                       # the half-open probe
        assert adm.breaker_state("b") == "half_open"
        with pytest.raises(ServeCircuitOpen):
            adm.check_tenant("b")                   # probe budget (1) spent
        adm.on_batch_outcome(["b"], ok=True)        # probe succeeded
        assert adm.breaker_state("b") == "closed"
        adm.check_tenant("b")

    def test_breaker_half_open_failure_reopens(self):
        t = [0.0]
        adm = AdmissionController(clock=lambda: t[0])
        adm.register("c", breaker_failures=1, breaker_cooldown_s=1.0)
        adm.on_batch_outcome(["c"], ok=False)
        assert adm.breaker_state("c") == "open"
        t[0] = 1.2
        adm.check_tenant("c")                       # probe admitted
        adm.on_batch_outcome(["c"], ok=False)       # probe failed
        assert adm.breaker_state("c") == "open"
        with pytest.raises(ServeCircuitOpen):
            adm.check_tenant("c")
        assert adm.tenant_stats()["c"]["breaker_opens"] == 2

    def test_half_open_probe_budget_self_heals(self):
        """Probes shed before dispatch never report an outcome; the
        budget must replenish after another cool-down instead of wedging
        the tenant in a probe-less half-open forever."""
        t = [0.0]
        adm = AdmissionController(clock=lambda: t[0])
        adm.register("d", breaker_failures=1, breaker_cooldown_s=1.0,
                     half_open_max=1)
        adm.on_batch_outcome(["d"], ok=False)
        t[0] = 1.1
        adm.check_tenant("d")                       # probe 1, no outcome
        with pytest.raises(ServeCircuitOpen):
            adm.check_tenant("d")
        t[0] = 2.3                                  # another cool-down
        adm.check_tenant("d")                       # budget replenished
        adm.on_batch_outcome(["d"], ok=True)
        assert adm.breaker_state("d") == "closed"

    def test_reregister_policy_update(self):
        """Re-registering updates policy live (ops tuning): dropping the
        rate limit stops limiting, adding one later starts a fresh
        bucket; counters and breaker state survive."""
        t = [0.0]
        adm = AdmissionController(clock=lambda: t[0])
        adm.register("r", rate_limit=1.0, burst=1.0)
        adm.check_tenant("r")
        with pytest.raises(ServeRateLimited):
            adm.check_tenant("r")
        adm.register("r")              # limit removed
        for _ in range(5):
            adm.check_tenant("r")      # unlimited now
        adm.register("r", rate_limit=1.0, burst=1.0)  # re-added: fresh
        adm.check_tenant("r")
        with pytest.raises(ServeRateLimited):
            adm.check_tenant("r")
        assert adm.tenant_stats()["r"]["rate_limited"] == 2

    def test_register_validation(self):
        adm = AdmissionController()
        with pytest.raises(ValueError, match="rate_limit"):
            adm.register("x", rate_limit=0.0)
        with pytest.raises(ValueError, match="max_queue"):
            adm.register("x", max_queue=0)
        with pytest.raises(ValueError, match="unknown tenant"):
            adm.resolve("never-registered")

    def test_ewma_estimator(self):
        adm = AdmissionController()
        assert adm.estimate_service_s("g") is None
        adm.observe_service("g", 8, 1.0)
        adm.observe_service("g", 8, 0.0)
        est = adm.estimate_service_s("g")
        assert est == pytest.approx(0.75)  # alpha 0.25 fold


# --------------------------------------------------------------------- #
# executor-level tenant policy                                          #
# --------------------------------------------------------------------- #
class TestTenantPolicy:
    def test_priority_order_served_first(self):
        comm = _comm()
        ex = _executor(comm, max_batch=1)
        ex.register_tenant("hi", priority=10)
        ex.register_tenant("lo", priority=0)
        order = []
        ex.pause()
        futs = []
        for tenant in ("lo", "lo", "hi", "lo", "hi"):
            f = ex.submit(_ones(comm.size), tenant=tenant)
            f.add_done_callback(
                lambda _f, t=tenant: order.append(t))
            futs.append(f)
        ex.resume()
        for f in futs:
            f.result(60)
        assert order == ["hi", "hi", "lo", "lo", "lo"], order
        ex.close()

    def test_tenant_queue_quota_sheds_typed(self):
        comm = _comm()
        metrics = ServeMetrics()
        ex = _executor(comm, metrics=metrics, queue_limit=16)
        ex.register_tenant("lo", priority=0, max_queue=2)
        ex.pause()
        futs = [ex.submit(_ones(1), tenant="lo") for _ in range(2)]
        with pytest.raises(ServeOverloaded, match="quota"):
            ex.submit(_ones(1), tenant="lo")
        assert metrics.snapshot()["shed"] == 1
        assert ex.tenant_stats()["lo"]["shed"] == 1
        ex.resume()
        for f in futs:
            f.result(60)
        ex.close()

    def test_full_queue_evicts_youngest_lowest_priority(self):
        comm = _comm()
        metrics = ServeMetrics()
        ex = _executor(comm, metrics=metrics, queue_limit=4)
        ex.register_tenant("hi", priority=10)
        ex.register_tenant("lo", priority=0)
        ex.pause()
        lo_futs = [ex.submit(_ones(1, value=i), tenant="lo")
                   for i in range(4)]
        f_hi = ex.submit(_ones(1), tenant="hi")
        # the YOUNGEST lo was preempted, typed, on ITS future only
        with pytest.raises(ServeOverloaded, match="preempted"):
            lo_futs[-1].result(0)
        ex.resume()
        np.testing.assert_array_equal(np.asarray(f_hi.result(60)),
                                      _want(_ones(1)))
        for i, f in enumerate(lo_futs[:-1]):
            np.testing.assert_array_equal(np.asarray(f.result(60)),
                                          _want(_ones(1, value=i)))
        assert ex.tenant_stats()["lo"]["shed"] == 1
        assert ex.tenant_stats()["hi"]["shed"] == 0
        ex.close()

    def test_full_queue_no_lower_priority_sheds_incoming(self):
        comm = _comm()
        ex = _executor(comm, queue_limit=2)
        ex.register_tenant("a", priority=3)
        ex.register_tenant("b", priority=3)
        ex.pause()
        futs = [ex.submit(_ones(1), tenant="a") for _ in range(2)]
        with pytest.raises(ServeOverloaded, match="queue is full"):
            ex.submit(_ones(1), tenant="b")  # same priority: no victim
        ex.resume()
        for f in futs:
            f.result(60)
        ex.close()

    def test_rate_limit_typed_and_counted(self):
        comm = _comm()
        metrics = ServeMetrics()
        ex = _executor(comm, metrics=metrics)
        ex.register_tenant("rl", rate_limit=1e-3, burst=1.0)
        ex.predict(_ones(1), tenant="rl", timeout=60)
        with pytest.raises(ServeRateLimited):
            ex.submit(_ones(1), tenant="rl")
        assert metrics.snapshot()["rate_limited"] == 1
        assert ex.tenant_stats()["rl"]["rate_limited"] == 1
        ex.close()

    def test_slo_is_the_default_deadline(self):
        """A tenant's slo_ms becomes its requests' deadline; queued past
        it -> typed expiry without dispatch (per-tenant counter)."""
        comm = _comm()
        metrics = ServeMetrics()
        ex = _executor(comm, metrics=metrics)
        ex.register_tenant("slo", slo_ms=1.0)
        ex.pause()
        fut = ex.submit(_ones(1), tenant="slo")
        time.sleep(0.05)
        ex.resume()
        with pytest.raises(ServeDeadlineExceeded):
            fut.result(30)
        assert metrics.snapshot()["deadline_expired"] == 1
        assert ex.tenant_stats()["slo"]["deadline_expired"] == 1
        ex.close()

    def test_quota_shed_does_not_drain_rate_bucket(self):
        """Review regression: the rate-limit token is taken LAST among
        the tenant-local checks — a burst of quota-shed requests must
        not drain the bucket and misattribute later sheds to the rate
        limit (the backoff signal would be wrong)."""
        comm = _comm()
        ex = _executor(comm, queue_limit=16)
        ex.register_tenant("lo", max_queue=1, rate_limit=1e-3, burst=2.0)
        ex.pause()
        f1 = ex.submit(_ones(1), tenant="lo")      # token 1 of 2
        for _ in range(5):
            with pytest.raises(ServeOverloaded, match="quota"):
                ex.submit(_ones(1), tenant="lo")   # sheds take NO token
        ex.resume()
        f1.result(60)
        ex.flush(60)
        # the second token is still there: served, never rate-limited
        ex.predict(_ones(1), tenant="lo", timeout=60)
        assert ex.tenant_stats()["lo"]["rate_limited"] == 0
        ex.close()

    def test_full_queue_shed_refunds_token(self):
        """Review regression: a request shed at the shared bound (no
        preemptible victim) got no service — its token is refunded."""
        comm = _comm()
        ex = _executor(comm, queue_limit=1)
        ex.register_tenant("a", rate_limit=1e-3, burst=2.0)
        ex.pause()
        f1 = ex.submit(_ones(1), tenant="a")       # token 1 of 2, queued
        with pytest.raises(ServeOverloaded, match="queue is full"):
            ex.submit(_ones(1), tenant="a")        # taken then refunded
        ex.resume()
        f1.result(60)
        ex.flush(60)
        ex.predict(_ones(1), tenant="a", timeout=60)   # second token
        assert ex.tenant_stats()["a"]["rate_limited"] == 0
        ex.close()

    def test_runtime_stats_fold_keeps_policy_sums_counters(self):
        """Review regression: the cross-executor tenant fold must SUM
        only the declared counters — policy fields (max_queue, slo_ms,
        rate_limit, priority) keep the first registration instead of
        doubling into a bound nobody enforces."""
        comm = _comm()
        a = _executor(comm)
        b = _executor(comm)
        for ex in (a, b):
            ex.register_tenant("dup", priority=5, slo_ms=60e3,
                               max_queue=64, rate_limit=500.0)
            ex.predict(_ones(1), tenant="dup", timeout=60)
        row = ht.runtime_stats()["serve"]["tenants"]["dup"]
        assert row["max_queue"] == 64 and row["rate_limit"] == 500.0
        assert row["priority"] == 5 and row["slo_ms"] == 60e3
        assert row["admitted"] >= 2    # counters DO sum across executors
        a.close()
        b.close()

    def test_unknown_tenant_and_no_registry_raise(self):
        comm = _comm()
        ex = _executor(comm)
        with pytest.raises(ValueError, match="register_tenant"):
            ex.submit(_ones(1), tenant="nobody")
        ex.register_tenant("known")
        with pytest.raises(ValueError, match="unknown tenant"):
            ex.submit(_ones(1), tenant="nobody")
        ex.close()

    def test_default_path_untouched_without_registry(self):
        """No registry -> the PR 2 single-FIFO semantics and counters,
        exactly: no serve.admit / admission counters move, tenant stats
        stay empty, full queue sheds the INCOMING request."""
        comm = _comm()
        metrics = ServeMetrics()
        before = {k: int(_pm.counters().get(k, 0))
                  for k in ("serve.admit", "serve.breaker_open",
                            "serve.breaker_rejections",
                            "serve.admission_fallbacks",
                            "serve.breaker_fallbacks")}
        ex = _executor(comm, metrics=metrics, queue_limit=2)
        ex.pause()
        f1 = ex.submit(_ones(1))
        f2 = ex.submit(_ones(2))
        with pytest.raises(ServeOverloaded):
            ex.submit(_ones(1))
        ex.resume()
        f1.result(60)
        f2.result(60)
        assert ex.tenant_stats() == {}
        assert ex.admission is None
        snap = ex.stats()
        assert snap["shed"] == 1 and snap["tenants"] == {}
        assert snap["early_shed"] == 0 and snap["rate_limited"] == 0
        after = {k: int(_pm.counters().get(k, 0)) for k in before}
        assert after == before
        ex.close()


# --------------------------------------------------------------------- #
# deadlines: one monotonic clock, early shed                            #
# --------------------------------------------------------------------- #
class TestDeadlines:
    def test_queued_past_deadline_never_dispatched(self):
        """The clock-audit regression (ISSUE 14 satellite): a request
        whose deadline expired while queued must NEVER reach the model —
        zero batches, zero requests recorded, typed expiry. Holds on the
        legacy path (no registry), where no estimator exists at all."""
        comm = _comm()
        metrics = ServeMetrics()
        ex = _executor(comm, metrics=metrics)
        ex.pause()
        fut = ex.submit(_ones(comm.size), deadline_ms=1.0)
        time.sleep(0.05)
        ex.resume()
        with pytest.raises(ServeDeadlineExceeded):
            fut.result(30)
        ex.flush(30)
        snap = metrics.snapshot()
        assert snap["batches"] == 0 and snap["requests"] == 0, snap
        assert snap["deadline_expired"] == 1
        ex.close()

    def test_early_shed_predicted_miss_never_dispatched(self):
        """A queued request whose deadline is still in the FUTURE but
        provably unreachable (EWMA service estimate > remaining budget)
        is shed typed before consuming a batch slot."""
        comm = _comm()
        metrics = ServeMetrics()
        ex = _executor(comm, metrics=metrics)
        ex.register_tenant("lo", priority=0)
        # prime the estimator: this group "takes 10 s per batch"
        ex.admission.observe_service(
            ((D,), np.dtype(np.float32).str), comm.size, 10.0)
        ex.pause()
        fut = ex.submit(_ones(comm.size), deadline_ms=500.0, tenant="lo")
        ex.resume()
        with pytest.raises(ServeDeadlineExceeded, match="early shed"):
            fut.result(30)
        ex.flush(30)
        snap = metrics.snapshot()
        assert snap["batches"] == 0 and snap["early_shed"] == 1, snap
        assert snap["deadline_expired"] == 0  # distinct counters
        assert ex.tenant_stats()["lo"]["early_shed"] == 1
        # a deadline-less request through the same primed group runs fine
        np.testing.assert_array_equal(
            np.asarray(ex.predict(_ones(comm.size), tenant="lo",
                                  timeout=60)),
            _want(_ones(comm.size)))
        ex.close()

    def test_generous_deadline_not_early_shed(self):
        comm = _comm()
        ex = _executor(comm)
        ex.register_tenant("lo", priority=0)
        ex.admission.observe_service(
            ((D,), np.dtype(np.float32).str), comm.size, 0.001)
        out = ex.predict(_ones(comm.size), deadline_ms=60e3, tenant="lo",
                         timeout=60)
        np.testing.assert_array_equal(np.asarray(out),
                                      _want(_ones(comm.size)))
        ex.close()


# --------------------------------------------------------------------- #
# circuit breaker on the real dispatch path                             #
# --------------------------------------------------------------------- #
class TestBreakerExecutor:
    def test_breaker_rides_dispatch_retry_and_recovers(self):
        comm = _comm()
        metrics = ServeMetrics()
        ex = _executor(comm, metrics=metrics, max_batch=2,
                       max_wait_ms=10.0)
        ex.register_tenant("hi", priority=10)
        ex.register_tenant("bk", priority=0, breaker_failures=2,
                           breaker_cooldown_s=0.25)
        retry_lat = []
        with faults.inject("serve.batch.dispatch=every:1"):
            for _ in range(2):   # two post-retry batch failures
                t0 = time.monotonic()
                with pytest.raises(faults.FaultInjected):
                    ex.submit(_ones(comm.size), tenant="bk").result(60)
                retry_lat.append(time.monotonic() - t0)
        assert ex.admission.breaker_state("bk") == "open"
        assert ex.tenant_stats()["bk"]["breaker_opens"] == 1
        # open: fast-fail typed at admission, counted
        fast_lat = []
        for _ in range(10):
            t0 = time.monotonic()
            with pytest.raises(ServeCircuitOpen):
                ex.submit(_ones(comm.size), tenant="bk")
            fast_lat.append(time.monotonic() - t0)
        assert metrics.snapshot()["breaker_rejections"] == 10
        # the acceptance bar: fast-fail < 1/10 of the dispatch-retry
        # failure path (measured here at ~100x margin)
        fast = sorted(fast_lat)[len(fast_lat) // 2]
        retry = sum(retry_lat) / len(retry_lat)
        assert fast < retry / 10.0, (fast, retry)
        # the healthy tenant is untouched while bk cools down
        np.testing.assert_array_equal(
            np.asarray(ex.predict(_ones(comm.size), tenant="hi",
                                  timeout=60)),
            _want(_ones(comm.size)))
        assert metrics.snapshot()["errors"] == 2  # only bk's failures
        # cool-down -> half-open probe dispatches clean -> closed
        time.sleep(0.3)
        np.testing.assert_array_equal(
            np.asarray(ex.submit(_ones(comm.size),
                                 tenant="bk").result(60)),
            _want(_ones(comm.size)))
        assert ex.admission.breaker_state("bk") == "closed"
        ex.close()

    def test_worker_survives_everything(self):
        comm = _comm()
        ex = _executor(comm, max_batch=2)
        ex.register_tenant("bk", priority=0, breaker_failures=1,
                           breaker_cooldown_s=60.0)
        with faults.inject("serve.batch.dispatch=every:1"):
            with pytest.raises(faults.FaultInjected):
                ex.submit(_ones(comm.size), tenant="bk").result(60)
        assert ex.worker_alive
        with pytest.raises(ServeCircuitOpen):
            ex.submit(_ones(comm.size), tenant="bk")
        assert ex.worker_alive
        ex.close()


# --------------------------------------------------------------------- #
# loadgen + the tier-1 soak short form                                  #
# --------------------------------------------------------------------- #
class TestLoadgen:
    def test_classify_outcomes(self):
        assert classify_outcome(None) == "ok"
        assert classify_outcome(ServeOverloaded("x")) == "overloaded"
        assert classify_outcome(ServeRateLimited("x")) == "rate_limited"
        assert classify_outcome(ServeCircuitOpen("x")) == "circuit_open"
        assert classify_outcome(ServeDeadlineExceeded("x")) == "deadline"
        assert classify_outcome(RuntimeError("boom")) == "untyped"

    def test_open_loop_schedule_is_seed_deterministic(self):
        comm = _comm()
        offered = []
        for _ in range(2):
            ex = _executor(comm, max_batch=8, queue_limit=64)
            ex.register_tenant("t", priority=0)
            ex.warmup((D,), np.float32, rows=(1, 2, 5, 9, 17))
            rep = run_open_loop(
                ex, [TenantLoad("t", 60.0, rows_mix=(1, 2))], 0.4, (D,),
                seed=7)
            offered.append(rep["tenants"]["t"]["offered"])
            assert rep["totals"]["untyped"] == 0
            assert set(rep["tenants"]["t"]["outcomes"]) == {
                "ok", "overloaded", "rate_limited", "deadline",
                "circuit_open", "closed", "typed_other", "cancelled",
                "untyped"}
            ex.close()
        # the Poisson schedule derives from the seed alone
        assert offered[0] == offered[1] and offered[0] > 0

    def test_soak_short_form_acceptance(self):
        """The ISSUE 14 p99-under-load acceptance, tier-1 short form:
        ~2x-capacity seeded open-loop two-tenant traffic for 1.2 s with
        ``serve.batch.dispatch=every:5`` armed and a 0.4 s worker stall
        mid-phase. Worker alive, every rejection typed, >=90% of shed
        volume on the low-priority tenant, hi p99 within its SLO, and
        the bounded dispatch retry actually exercised."""
        comm = _comm()
        metrics = ServeMetrics()
        slo_hi_ms = 1500.0
        ex = _executor(comm, metrics=metrics, max_batch=8,
                       max_wait_ms=2.0, queue_limit=32)
        ex.register_tenant("hi", priority=10, slo_ms=slo_hi_ms)
        ex.register_tenant("lo", priority=0, max_queue=24, slo_ms=6000.0)
        ex.warmup((D,), np.float32, rows=(1, 2, 3, 5, 9, 17))
        cap = estimate_capacity(ex, (D,), n=24)
        # 2x estimated capacity, clamped to what a python generator can
        # emit; the deterministic stall guarantees genuine overload even
        # when the capacity estimate is conservative
        total = min(2.0 * cap, 500.0)
        hi_rate = min(0.2 * total, 50.0)
        lo_rate = max(total - hi_rate, 100.0)
        retries0 = int(_pm.counters().get("serve.batch_retries", 0))
        with faults.inject("serve.batch.dispatch=every:5"):
            rep = run_open_loop(
                ex, [TenantLoad("hi", hi_rate, rows_mix=(1, 2)),
                     TenantLoad("lo", lo_rate, rows_mix=(1, 2))],
                1.2, (D,), seed=3, stall=(0.3, 0.4))
        assert ex.worker_alive
        assert rep["totals"]["untyped"] == 0, rep["totals"]
        hi = rep["tenants"]["hi"]
        lo = rep["tenants"]["lo"]
        total_shed = hi["shed"] + lo["shed"]
        assert total_shed > 0, "no overload materialized - harness lying"
        assert lo["shed"] / total_shed >= 0.90, (hi["shed"], lo["shed"])
        assert hi["outcomes"]["ok"] > 0
        assert hi["latency_ms"]["p99"] <= slo_hi_ms, hi["latency_ms"]
        # the armed fault actually exercised the bounded retry path
        assert int(_pm.counters().get("serve.batch_retries", 0)) \
            > retries0
        # every offered request terminated (answered or typed-rejected)
        assert rep["totals"]["answered"] == rep["totals"]["offered"]
        ex.close()
