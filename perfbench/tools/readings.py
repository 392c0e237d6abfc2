"""The readings that a limit is set from, many seeds in ONE process.

    python3 -m perfbench.tools.readings --workload <cell> --seeds 11,12,... \
        [--control 3] [--faults 3] [--rehearse] [--bench perfbench/HELD.json]
        [--set n_rows=6250000] [--dump]

For each seed: the driver's set-up at the cell's own size, then
`readings()` (the program against the plain reference, the numbers that
`correct` compares); for the first `--control` seeds also `control()` (the
nearest lower precision in the program's place) and for the first `--faults`
seeds `faults()` where the driver has them. Every reading goes through the
driver's own `check()` and the harness's `judge()`, as a run's numbers do, and
its line says `correct`: true for the program, FALSE for the control and for
each fault, or the limit separates nothing. One JSON line per reading on
stdout and appended to `chiprun_out/readings.jsonl`. `--set key=value` lays a
number over the configuration (a look at how a reading grows with the size,
never a cell); `--dump` adds what the driver keeps of the answers compared
(`Cell.dump()`, where it has one). Never part of a benchmark run.
"""

import argparse
import gc
import json
import os
import time

from perfbench import run as harness


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control", type=int, default=0)
    ap.add_argument("--faults", type=int, default=0)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--bench", default="BENCHMARK.json")
    ap.add_argument("--set", action="append", default=[],
                    metavar="KEY=NUMBER")
    ap.add_argument("--dump", action="store_true")
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",")]
    out_dir = os.path.join(harness.ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    _bench, ctx, driver, _compiles = harness.prepare(
        args.workload, seeds[0], args.rehearse, args.bench)
    laid = {k: json.loads(v) for k, v in (kv.split("=", 1) for kv in args.set)}
    ctx.config.update(laid)
    import jax

    with open(os.path.join(out_dir, "readings.jsonl"), "a") as log:
        def emit(rec):
            checks = cell.check(rec["numbers"])
            rec.update(correct=harness.judge(checks), checks={
                n: {"value": v, "limit": lim} for n, v, lim in checks})
            rec.update(workload=args.workload, rehearsal=args.rehearse,
                       device=jax.devices()[0].device_kind, **laid)
            line = json.dumps(rec)
            print(line, flush=True)
            log.write(line + "\n")
            log.flush()

        for i, seed in enumerate(seeds):
            ctx.seed = seed
            t0 = time.perf_counter()
            cell = driver.Cell(ctx)
            cell.setup()
            rec = {"seed": seed, "kind": "program", "numbers": cell.readings(),
                   "s": time.perf_counter() - t0}
            if args.dump and hasattr(cell, "dump"):
                rec["dump"] = cell.dump()
            emit(rec)
            if i < args.control:
                emit({"seed": seed, "kind": "control",
                      "numbers": cell.control()})
            if i < args.faults and hasattr(cell, "faults"):
                for name, numbers in cell.faults().items():
                    emit({"seed": seed, "kind": "fault:" + name,
                          "numbers": numbers})
            cell.release()
            cell.close()
            del cell
            gc.collect()


if __name__ == "__main__":
    main()
