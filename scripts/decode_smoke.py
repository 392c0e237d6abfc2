#!/usr/bin/env python
"""Continuous-batching decode smoke for the CI ladder (ISSUE 15).

Brings up a :class:`heat_tpu.serve.DecodeEngine` over the launch mesh
(the ladder runs it at 4 virtual CPU devices), warms the prefill ladder +
the one decode-step executable, drives a seeded mixed-length two-tenant
workload through it, and checks the engine contract end to end:

* every request answered, worker alive, engine ends empty;
* greedy tokens bitwise-equal to ``TransformerLM.generate()`` for a
  sampled subset of requests (a CPU-only oracle: exact in float32 on
  XLA:CPU; on the chip an argmax flips on bf16 rounding, so
  ``chip_smoke.py`` judges by logits instead);
* ZERO steady-state program-cache misses after warmup;
* ``ht.runtime_stats()["serve"]["decode"]`` present with the pinned
  shape and non-zero steps/tokens.

Prints ONE JSON line; exit 1 on any violation (the ladder fails the
round).

    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=4 \
        python scripts/decode_smoke.py
"""

import json
import sys

import numpy as np


def main() -> int:
    import heat_tpu as ht
    from heat_tpu.nn.transformer import TransformerLM, TransformerLMConfig
    from heat_tpu.serve import DecodeConfig, DecodeEngine

    n = ht.get_comm().size
    tp = 2 if n % 2 == 0 else 1
    grid = ht.MeshGrid((n // tp, 1, tp, 1), ("dp", "pp", "tp", "sp"))
    cfg = TransformerLMConfig(vocab=61, d_model=32, n_heads=4, n_layers=2,
                              d_ff=64)
    model = TransformerLM(grid, cfg)
    params = model.init(2)
    rng = np.random.default_rng(0)

    eng = DecodeEngine(model, params,
                       DecodeConfig(slots=2 * model.dp_world,
                                    max_seq_len=64),
                       name="decode-smoke")
    eng.register_tenant("interactive", priority=10)
    eng.register_tenant("batch", priority=0)
    eng.warmup()
    misses0 = eng.program_cache.stats()["misses"]

    n_req = 24
    reqs = []
    for i in range(n_req):
        s0 = int(rng.integers(3, 13))
        mn = int(rng.integers(2, 12))
        prompt = rng.integers(0, cfg.vocab, (s0,)).astype(np.int32)
        tenant = "interactive" if i % 3 else "batch"
        reqs.append((prompt, mn, tenant))
    futs = [eng.submit(p, m, tenant=t) for p, m, t in reqs]
    outs = []
    errors = []
    for f in futs:
        try:
            outs.append(f.result(300))
        except Exception as exc:
            errors.append(repr(exc))
            outs.append(None)

    # parity spot-check: every 5th request vs the monolithic generate()
    parity_ok = True
    for i in range(0, n_req, 5):
        prompt, mn, _t = reqs[i]
        if outs[i] is None:
            parity_ok = False
            continue
        B = model.dp_world
        want = np.asarray(model.generate(params, np.tile(prompt, (B, 1)),
                                         mn))[0]
        if not np.array_equal(outs[i], want):
            parity_ok = False

    st = eng.stats()
    steady_misses = eng.program_cache.stats()["misses"] - misses0
    rt = ht.runtime_stats()["serve"]["decode"]
    eng.close()

    verdicts = {
        "all_answered": not errors and all(o is not None for o in outs),
        "parity": parity_ok,
        "zero_steady_misses": steady_misses == 0,
        "worker_survived": st["live"] == 0 and st["queue_depth"] == 0,
        "stats_shape": (set(rt) == {"slots", "occupancy", "prefills",
                                    "decode_steps", "tokens_out",
                                    "decode_fallbacks"}
                        and rt["decode_steps"] > 0
                        and rt["tokens_out"] > 0),
        "no_fallbacks": st["decode_fallbacks"] == 0,
    }
    record = {
        "devices": n,
        "grid": {"dp": n // tp, "tp": tp},
        "requests": n_req,
        "steady_misses": steady_misses,
        "prefills": st["prefills"],
        "decode_steps": st["decode_steps"],
        "tokens_out": st["tokens_out"],
        "mean_occupancy": round(st["occupancy"], 3),
        "errors": errors[:3],
        "verdicts": verdicts,
        "ok": all(verdicts.values()),
    }
    print(json.dumps(record), flush=True)
    return 0 if record["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
