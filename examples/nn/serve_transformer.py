"""Serve a TransformerLM forward behind the batching executor.

Demonstrates the full serving path (`heat_tpu.serve`): a dp-sharded
transformer forward wrapped by :func:`heat_tpu.serve.serve_transformer`,
warmed over the shape-bucket ladder, then hit with concurrent mixed-size
requests from client threads — ending with the metrics snapshot
(latency percentiles, batch occupancy, program-cache counters: zero
steady-state misses) and ``ht.runtime_stats()``.

Usage (4 virtual devices):
  JAX_PLATFORMS=cpu \
  XLA_FLAGS=--xla_force_host_platform_device_count=4 \
  python serve_transformer.py --requests 40
"""

import argparse
import json
import os
import threading
import time

import numpy as np

try:
    import heat_tpu as ht
except ModuleNotFoundError:  # running from a source checkout without install
    import sys

    sys.path.insert(0, os.path.abspath(os.path.join(
        os.path.dirname(__file__), "..", "..")))
    import heat_tpu as ht


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--vocab", type=int, default=256)
    p.add_argument("--d-model", type=int, default=64)
    p.add_argument("--heads", type=int, default=4)
    p.add_argument("--layers", type=int, default=2)
    p.add_argument("--seq-len", type=int, default=64)
    p.add_argument("--requests", type=int, default=40)
    p.add_argument("--threads", type=int, default=4)
    p.add_argument("--max-batch", type=int, default=8)
    p.add_argument("--max-wait-ms", type=float, default=2.0)
    p.add_argument("--tenants", type=int, default=1, choices=(1, 2),
                   help="2 = register an 'interactive' (priority 10, "
                        "SLO) and a 'batch' (priority 0, queue quota) "
                        "tenant over the one executor and print "
                        "per-tenant runtime_stats")
    args = p.parse_args()
    if os.environ.get("HEAT_TPU_EXAMPLE_SMOKE"):  # CI ladder smoke: shrink
        args.d_model, args.layers, args.seq_len = 32, 1, 16
        args.requests = 12

    import jax

    from heat_tpu.nn.transformer import TransformerLM, TransformerLMConfig
    from heat_tpu.serve import metrics as serve_metrics
    from heat_tpu.serve import serve_transformer

    n_dev = len(jax.devices())
    grid = ht.MeshGrid((n_dev, 1, 1, 1), ("dp", "pp", "tp", "sp"))
    cfg = TransformerLMConfig(vocab=args.vocab, d_model=args.d_model,
                              n_heads=args.heads, n_layers=args.layers)
    model = TransformerLM(grid, cfg)
    params = model.init(0)
    print(f"model d={args.d_model} L={args.layers} over dp={n_dev}; "
          f"serving seq_len={args.seq_len}")

    ex = serve_transformer(model, params, seq_len=args.seq_len)
    ex.config.max_batch = args.max_batch
    ex.config.max_wait_ms = args.max_wait_ms
    tenant_of = None
    if args.tenants == 2:
        # two tenants over ONE executor: the interactive tenant outranks
        # the batch tenant in the queue and inherits an SLO deadline; the
        # batch tenant is quota-bounded so it can never fill the shared
        # queue bound (doc/serving.md "Multi-tenant admission")
        ex.register_tenant("interactive", priority=10, slo_ms=60e3)
        ex.register_tenant("batch", priority=0,
                           max_queue=ex.config.queue_limit * 3 // 4)

        def tenant_of(i):
            return "interactive" if i % 3 == 0 else "batch"

    rows_mix = (1, 2, 3, 1, 2, 1)
    t0 = time.perf_counter()
    # coalesced totals reach max_batch x max(rows_mix): warm every bucket
    # the policy can produce up to that total (NOT a hardcoded row set —
    # --max-batch changes the reachable ladder)
    ex.warmup((args.seq_len,), np.int32,
              rows=ex.config.bucket_rows.ladder(
                  args.max_batch * max(rows_mix)))
    print(f"warmup ({ex.program_cache.stats()['compiles']} programs) "
          f"in {time.perf_counter() - t0:.1f}s")
    misses0 = ex.program_cache.stats()["misses"]
    # warmup latencies are compile times — restart the window so the
    # percentiles below describe traffic, not warmup
    serve_metrics.DEFAULT.reset()

    rng = np.random.default_rng(0)
    reqs = [rng.integers(0, args.vocab,
                         (rows_mix[i % len(rows_mix)], args.seq_len)
                         ).astype(np.int32)
            for i in range(args.requests)]
    done = []

    def client(t):
        idx = list(range(t, len(reqs), args.threads))
        futs = [ex.submit(reqs[i],
                          tenant=tenant_of(i) if tenant_of else None)
                for i in idx]
        done.extend(np.asarray(f.result(600)).shape for f in futs)

    t0 = time.perf_counter()
    threads = [threading.Thread(target=client, args=(t,))
               for t in range(args.threads)]
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    wall = time.perf_counter() - t0
    ex.close()

    snap = ex.stats()
    assert len(done) == len(reqs)
    assert ex.program_cache.stats()["misses"] == misses0, "recompiled!"
    print(f"{len(reqs)} requests in {wall * 1e3:.0f} ms "
          f"({len(reqs) / wall:.1f} req/s), "
          f"p50={snap['latency_ms']['p50']:.1f} ms "
          f"p99={snap['latency_ms']['p99']:.1f} ms, "
          f"occupancy={snap['batch_occupancy']['mean']:.2f}, "
          f"0 steady-state recompiles")
    print("runtime_stats:", json.dumps({
        "serve": {k: ht.runtime_stats()["serve"][k]
                  for k in ("requests", "batches", "shed")},
        "resharding": ht.runtime_stats()["resharding"],
    }))
    if args.tenants == 2:
        # the per-tenant observability surface the tentpole added:
        # admission counters + breaker state per tenant, one JSON line
        for name, row in sorted(snap["tenants"].items()):
            print(f"tenant {name}: " + json.dumps(
                {k: row[k] for k in ("priority", "admitted", "completed",
                                     "shed", "breaker")}))
        assert snap["tenants"]["interactive"]["completed"] > 0
        assert snap["tenants"]["batch"]["completed"] > 0


if __name__ == "__main__":
    main()
