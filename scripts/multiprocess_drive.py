#!/usr/bin/env python
"""Two-process ``distributed_init`` drive (round-4 verdict #7).

Launches 2 REAL OS processes x 2 virtual CPU devices each, joined via
``jax.distributed.initialize`` (gRPC coordinator on localhost) into one
4-device world — the same code path a multi-host TPU pod takes over DCN,
scaled down to one machine. Each process then runs, SPMD-style, the
dryrun body's core on the global mesh:

  1. ``ht.distributed_init`` -> world communicator over 4 devices
  2. a sharded array op with a cross-process reduction (global sum)
  3. a 2x2 MeshGrid ("dcn" x "ici") and the DASO two-tier slow sync:
     bf16 blend over the "dcn" (cross-process) axis with real bytes
  4. a DP train-step shape: per-device grads psum'd across the world

Writes one JSON line per process; the parent asserts both agree and
emits MULTIPROC_r05.json.

Usage:  JAX_PLATFORMS=cpu \
            python scripts/multiprocess_drive.py
"""

import json
import os
import subprocess
import sys
import time

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_PORT = 18765


def worker(pid: int, nprocs: int) -> None:
    import numpy as np
    import jax

    sys.path.insert(0, _REPO)
    import heat_tpu as ht

    comm = ht.distributed_init(
        coordinator_address=f"localhost:{_PORT}",
        num_processes=nprocs, process_id=pid)
    world = {
        "process": pid,
        "process_count": jax.process_count(),
        "local_devices": len(jax.local_devices()),
        "global_devices": len(jax.devices()),
        "comm_size": comm.size,
    }

    # ---- sharded op with a cross-process reduction --------------------
    n = 10  # uneven over 4 devices: exercises the padded canonical layout
    x = ht.arange(n, dtype=ht.float32, split=0)
    world["arange_sum"] = float(x.sum())

    # ---- two-tier grid: dcn (cross-process) x ici (intra-process) -----
    import jax.numpy as jnp

    grid = ht.MeshGrid((2, 2), ("dcn", "ici"))
    from jax.sharding import NamedSharding, PartitionSpec as P

    # per-device distinct payload, blended over the dcn axis (the DASO
    # slow tier's global sync direction) in bf16 — real cross-host bytes
    k = 256
    w = jnp.arange(4 * k, dtype=jnp.float32).reshape(4, k)
    w = jax.device_put(w, NamedSharding(grid.mesh, P(("dcn", "ici"))))

    from heat_tpu.core._compat import shard_map

    def blend(wblk):
        # bf16 on the wire, f32 math — DASO's global-sync recipe
        return jax.lax.pmean(wblk.astype(jnp.bfloat16), "dcn").astype(
            jnp.float32)

    out = jax.jit(shard_map(
        blend, mesh=grid.mesh, in_specs=P(("dcn", "ici")),
        out_specs=P(("dcn", "ici"))))(w)
    # a cross-process global array is not fetchable whole — verify this
    # process's ADDRESSABLE shards against the analytic bf16 dcn-mean
    wg = np.arange(4 * k, dtype=np.float32).reshape(4, k)
    expect = np.tile((wg[:2] + wg[2:]) / 2.0, (2, 1))
    ok = True
    for shard in out.addressable_shards:
        got = np.asarray(shard.data).reshape(-1, k)
        want = expect[shard.index[0]].reshape(-1, k)
        ok = ok and np.allclose(got, want, atol=4.0)  # bf16 wire precision
    world["daso_dcn_blend_ok"] = bool(ok and len(out.addressable_shards) > 0)

    # ---- DP train-step shape: grads psum'd across the world -----------
    def loss(p, xb):
        return jnp.sum((xb @ p) ** 2) / xb.shape[0]

    xb = ht.random.rand(8, 4, dtype=ht.float32, split=0)
    p0 = jnp.ones((4,), jnp.float32)
    g = jax.jit(jax.grad(loss))(p0, xb.larray)
    world["dp_grad_norm"] = round(float(jnp.linalg.norm(g)), 4)

    print("RESULT " + json.dumps(world), flush=True)


def main() -> None:
    if len(sys.argv) >= 3 and sys.argv[1] == "--worker":
        worker(int(sys.argv[2]), int(sys.argv[3]))
        return

    nprocs = 2
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    flags = [f for f in env.get("XLA_FLAGS", "").split()
             if "host_platform_device_count" not in f]
    flags.append("--xla_force_host_platform_device_count=2")
    env["XLA_FLAGS"] = " ".join(flags).strip()

    procs = []
    for pid in range(nprocs):
        procs.append(subprocess.Popen(
            [sys.executable, os.path.abspath(__file__), "--worker",
             str(pid), str(nprocs)],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
            text=True, cwd=_REPO))
    results, errs = [], []
    deadline = time.time() + 600
    for p in procs:
        try:
            out, err = p.communicate(timeout=max(10, deadline - time.time()))
        except subprocess.TimeoutExpired:
            p.kill()
            out, err = p.communicate()
            errs.append("timeout")
        line = next((l for l in out.splitlines()
                     if l.startswith("RESULT ")), None)
        if p.returncode == 0 and line:
            results.append(json.loads(line[len("RESULT "):]))
        else:
            errs.append(f"rc={p.returncode}: " +
                        (err or out).strip()[-300:])

    ok = (len(results) == nprocs
          and all(r["process_count"] == nprocs for r in results)
          and all(r["global_devices"] == 4 for r in results)
          and all(r["comm_size"] == 4 for r in results)
          and all(r["arange_sum"] == 45.0 for r in results)
          and all(r["daso_dcn_blend_ok"] for r in results)
          and len({r["dp_grad_norm"] for r in results}) == 1)
    artifact = {
        "note": "ht.distributed_init across 2 REAL processes x 2 virtual "
                "CPU devices (gRPC coordinator), running sharded ops, a "
                "2x2 dcn-x-ici MeshGrid with the DASO bf16 blend over the "
                "cross-process axis, and a DP gradient on the 4-device "
                "world mesh. SPMD: both processes execute the same program "
                "and must agree on every figure.",
        "date": time.strftime("%Y-%m-%d"),
        "command": "JAX_PLATFORMS=cpu python "
                   "scripts/multiprocess_drive.py",
        "ok": ok,
        "results": results,
        "errors": errs,
    }
    print(json.dumps(artifact, indent=1))
    with open(os.path.join(_REPO, "MULTIPROC_r05.json"), "w") as f:
        json.dump(artifact, f, indent=1)
    sys.exit(0 if ok else 1)


if __name__ == "__main__":
    main()
