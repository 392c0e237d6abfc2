"""Combined-parallelism GPT training: dp x pp x tp x sp (x ep) in one step.

The flagship demonstration of the full parallelism grid
(`heat_tpu.nn.transformer.TransformerLM`): batch over dp, pipeline stages
over pp, Megatron head/feature shards over tp, ring-attention sequence
shards over sp, and (with ``--moe-experts``) Switch-MoE experts over the dp
axis — one shard_map train step, exact gradients (verified against a dense
reference in ``tests/test_transformer.py``).

The reference framework composes exactly one split axis at a time
(SURVEY.md §2.6); this is the TPU-native superset.

Usage (8 virtual devices):
  JAX_PLATFORMS=cpu \
  XLA_FLAGS=--xla_force_host_platform_device_count=8 \
  python gpt_parallel.py --grid 1,2,2,2 --steps 20
  python gpt_parallel.py --grid 2,2,2,1 --moe-experts 4   # with ep
  python gpt_parallel.py --tiers dcn,ici --steps 20  # simulated 2-host
      # (2, n/2) ("dcn", "ici") tier grid: the packed train step's
      # gradient all-reduce decomposes as reduce-scatter(ici) ->
      # all-reduce(dcn) -> all-gather(ici), HEAT_TPU_HIER
  python gpt_parallel.py --serve --steps 5   # continuous-batching decode:
      # 2 tenants' mixed-length generation through the slot-based
      # DecodeEngine (heat_tpu.serve.decode), per-tenant tokens/s printed
"""

import argparse
import os

import numpy as np

try:
    import heat_tpu as ht
except ModuleNotFoundError:  # running from a source checkout without install
    import os, sys

    sys.path.insert(0, os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "..")))
    import heat_tpu as ht


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--grid", default="auto",
                   help="dp,pp,tp,sp sizes (product = device count); "
                        "'auto' picks 1,2,2,2 on vma-tracking jax and the "
                        "dp-only packed-step grid on older jax (whose "
                        "check_vma train path cannot trace)")
    p.add_argument("--vocab", type=int, default=256)
    p.add_argument("--d-model", type=int, default=64)
    p.add_argument("--heads", type=int, default=8)
    p.add_argument("--layers", type=int, default=2)
    p.add_argument("--seq-len", type=int, default=256)
    p.add_argument("--batch", type=int, default=4)
    p.add_argument("--n-micro", type=int, default=2)
    p.add_argument("--moe-experts", type=int, default=0)
    p.add_argument("--steps", type=int, default=20)
    p.add_argument("--lr", type=float, default=3e-3)
    p.add_argument("--tiers", default=os.environ.get(
        "HEAT_TPU_MESH_TIERS", ""),
        help="declare mesh tiers (default: $HEAT_TPU_MESH_TIERS): "
             "'dcn,ici' (or 'D,I' sizes) runs the dp grid 2-D — a "
             "simulated 2-host (2, n/2) ('dcn','ici') split on CPU — "
             "so the packed step's gradient all-reduce decomposes "
             "hierarchically (RS over ici, AR over dcn, AG over ici)")
    p.add_argument("--serve", action="store_true",
                   help="after training, serve generation through the "
                        "continuous-batching DecodeEngine: 2 tenants "
                        "(interactive prio 10 / batch prio 0), mixed "
                        "prompt/output lengths, per-tenant tokens/s + "
                        "slot occupancy printed")
    p.add_argument("--serve-requests", type=int, default=24)
    args = p.parse_args()

    import optax

    from heat_tpu.core import fusion
    from heat_tpu.nn.transformer import TransformerLM, TransformerLMConfig

    tiers = None
    if args.tiers:
        fusion.set_mesh_tiers(args.tiers)
        tiers = fusion.mesh_tiers()

    if tiers is not None and args.grid != "auto":
        # the tier grid is dp-only by construction — silently dropping a
        # requested pp/tp/sp layout would misreport what ran
        raise SystemExit(
            f"--tiers {args.tiers} builds its own (dcn, dp) grid and "
            f"cannot honor --grid {args.grid}; pass one or the other")
    if tiers is not None:
        import jax

        n = len(jax.devices())
        if isinstance(tiers[0], int):
            d, i = tiers
            if d * i != n:
                raise SystemExit(
                    f"--tiers {args.tiers}: {d}x{i} != {n} devices")
        else:
            # name form ('dcn,ici'): simulate 2 hosts on this mesh
            d, i = 2, n // 2
            if n < 4 or n % 2:
                raise SystemExit(
                    f"--tiers {args.tiers}: needs an even mesh of >= 4 "
                    f"devices to simulate a (2, n/2) pod, got {n}")
        # tiered dp-only grid: dcn x dp both shard the batch, the
        # packed-collective train step (PR 7) decomposes hierarchically
        shape = (d, i, 1, 1, 1)
        grid = ht.MeshGrid(shape, ("dcn",) + TransformerLM.AXES)
        print(f"tiers {args.tiers}: simulated {d}-host x {i}-device "
              f"('dcn', 'ici') grid — hierarchical packed collectives "
              f"{'ON' if fusion.hier_enabled() else 'OFF (HEAT_TPU_HIER=0)'}")
    elif args.grid == "auto":
        import jax

        n = len(jax.devices())
        if n % 8 == 0:
            # an 8-divisible mesh: the full composition
            shape = (n // 8, 2, 2, 2)
        else:
            # a mesh the 2x2x2 layout does not divide — run the dp-only
            # packed-collective fused step instead (PR 7)
            shape = (n, 1, 1, 1)
            print(f"grid auto: dp-only packed train step on {n} devices")
    else:
        shape = tuple(int(s) for s in args.grid.split(","))
    if tiers is None:
        grid = ht.MeshGrid(shape, ("dp", "pp", "tp", "sp"))
    cfg = TransformerLMConfig(
        vocab=args.vocab, d_model=args.d_model, n_heads=args.heads,
        n_layers=args.layers, n_micro=args.n_micro,
        moe_experts=args.moe_experts)
    model = TransformerLM(grid, cfg)
    print(f"grid {dict(zip(grid.axis_names, grid.shape))}  layers/stage "
          f"{model.layers_per_stage}  heads/shard {cfg.n_heads // model.tp}")

    rng = np.random.default_rng(0)
    # round the batch up so it divides the dp world x n_micro on any grid
    unit = model.dp_world * cfg.n_micro
    batch = -(-args.batch // unit) * unit
    base = np.arange(batch * args.seq_len).reshape(batch, args.seq_len)
    tokens = ((base + rng.integers(0, 2, base.shape)) % args.vocab)
    toks = model.shard_batch(tokens)

    params = model.init(0)
    tx = optax.adam(args.lr)
    opt_state = tx.init(params)
    step = model.make_train_step(tx)

    for i in range(args.steps):
        params, opt_state, lval = step(params, opt_state, toks)
        if i % 5 == 0 or i == args.steps - 1:
            print(f"step {i:3d}: loss {float(lval):.4f}")

    # KV-cached greedy decode needs a token-recurrent grid (pp=sp=1, dense
    # MLP); skip the demo on pipelined / sequence-sharded / MoE configs
    decode_ok = model.pp == 1 and model.sp == 1 and not cfg.moe_experts
    if decode_ok and not args.serve:
        # exactly dp prompt rows (tile if the training batch is smaller)
        reps = -(-model.dp_world // tokens.shape[0])
        prompt = np.tile(tokens, (reps, 1))[:model.dp_world,
                                            :8].astype(np.int32)
        out = np.asarray(model.generate(params, prompt, max_new_tokens=12))
        print("prompt:   ", prompt[0].tolist())
        print("generated:", out[0, 8:].tolist())
    if decode_ok and args.serve:
        run_serve(model, params, args, rng)
    elif args.serve:
        print("--serve skipped: decode needs a pp=1, sp=1 dense grid")


def run_serve(model, params, args, rng):
    """--serve: two tenants' mixed-length generation through the
    continuous-batching DecodeEngine (heat_tpu.serve.decode) — finished
    sequences free their slot mid-flight, queued requests join between
    steps, and the ONE decode executable serves every occupancy."""
    import time

    from heat_tpu.serve import serve_transformer

    vocab = model.cfg.vocab
    eng = serve_transformer(model, params, seq_len=64, decode=True,
                            slots=2 * model.dp_world)
    eng.register_tenant("interactive", priority=10, slo_ms=120e3)
    eng.register_tenant("batch", priority=0)
    eng.warmup()

    n_req = max(4, args.serve_requests)
    reqs = []
    for i in range(n_req):
        s0 = int(rng.integers(4, 13))
        max_new = int(rng.integers(4, 17))
        tenant = "interactive" if i % 3 else "batch"
        reqs.append((rng.integers(0, vocab, (s0,)).astype(np.int32),
                     max_new, tenant))
    t0 = time.perf_counter()
    futs = [(t, p.size, eng.submit(p, m, tenant=t)) for p, m, t in reqs]
    per_tenant = {"interactive": 0, "batch": 0}
    sample = None
    for tenant, s0, f in futs:
        out = f.result(600)
        per_tenant[tenant] += int(out.size) - int(s0)  # generated only
        if sample is None:
            sample = out
    wall = time.perf_counter() - t0
    st = eng.stats()
    print(f"serve: {n_req} requests in {wall:.2f}s over {st['slots']} "
          f"slots  mean occupancy {st['occupancy']:.2f}")
    for tenant, toks in per_tenant.items():
        row = st["tenants"].get(tenant, {})
        print(f"  tenant {tenant:12s} {toks / wall:8.1f} tok/s  "
              f"completed {row.get('completed', 0)}")
    print(f"  prefills {st['prefills']}  decode steps "
          f"{st['decode_steps']}  tokens out {st['tokens_out']}  "
          f"steady compiles after warmup: "
          f"{st['program_cache']['misses']} misses total")
    print("  sample:", sample.tolist())
    eng.close()


if __name__ == "__main__":
    main()
