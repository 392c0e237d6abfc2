"""Where set-up goes, from the program's own records. One cell, once, traced,
through `perfbench.run`'s own `main`; then one JSON line that splits `setup_s`
(process start to the window) into the compiles (the union of the program's
`jit.*` records, by cache outcome), a decode cell's warm requests (the first
`decode.request` to the window) and the rest, and names the heaviest programs
and those JAX wrote to its persistent cache (`written`: empty in a warm run of
one checkout; a program there is one whose key moved). A tool for looking at
set-up, never part of a run:

    python3 -m perfbench.tools.setup_split --workload <cell> --seed <n> \
        [--seconds <s>] [--rehearse] [--bench perfbench/HELD.json]

The line is printed after the run's own two and appended to
`chiprun_out/setup_split.jsonl`.
"""

from perfbench import run  # first: `run.T_START` is the process's start

import argparse  # noqa: E402
import contextlib  # noqa: E402
import io  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import re  # noqa: E402
import sys  # noqa: E402
import time  # noqa: E402


def union(recs, lo=float("-inf"), hi=float("inf")):
    """Seconds covered by the records' intervals, clipped to [lo, hi]: a
    trace holds its callees' traces, and threads overlap."""
    total, end = 0.0, lo
    for r in sorted(recs, key=lambda r: r.t0):
        t0, t1 = max(r.t0, end), min(r.t1, hi)
        if t1 > t0:
            total += t1 - t0
            end = t1
    return total


def setup_records(recs, t0):
    """The `jit.*` records that end before `t0`, or None when there are none
    or not all of them (their `n` runs 1, 2, ... without a gap)."""
    jit = [r for r in recs if r.name.startswith("jit.")]
    if not jit or (sorted(r.attrs.get("n", 0) for r in jit)
                   != list(range(1, len(jit) + 1))):
        return None
    return [r for r in jit if r.t1 <= t0]


def outcome(r):
    if r.attrs.get("cache") == "miss":
        return "miss_written" if r.attrs.get("writable") else "miss_unwritten"
    return r.attrs.get("cache")


def program(fun):
    """`jit(g)` and `g` are one program."""
    m = re.fullmatch(r"jit\((.*)\)", fun)
    return m.group(1) if m else fun


def split(recs, t0, setup_s):
    """`setup_s`, which ends at `t0` on the ring's clock, split by the
    records; None without the whole set of `jit.*` records."""
    before = setup_records(recs, t0)
    if before is None:
        return None
    compiles = [r for r in before if r.name == "jit.compile"]
    out = {"setup_s": setup_s, "setup_compile_s": union(before),
           # process start to the first recorded compile: imports, devices
           "to_first_compile_s": (min((r.t0 for r in before), default=t0)
                                  - (t0 - setup_s))}
    for name in ("jit.trace", "jit.lower", "jit.compile"):
        out[name + "_s"] = union([r for r in before if r.name == name])
    for kind in ("hit", "miss_written", "miss_unwritten", "off"):
        of = [r for r in compiles if outcome(r) == kind]
        out[kind] = {"n": len(of), "s": union(of)}
    heavy = {}
    for r in before:
        p = heavy.setdefault(program(r.attrs["fun"]), {"s": 0.0, "cache": []})
        p["s"] += r.t1 - r.t0
        if r.name == "jit.compile":
            p["cache"].append(outcome(r))
    out["heaviest"] = sorted(([k, v["s"], v["cache"]] for k, v in heavy.items()),
                             key=lambda kv: -kv[1])[:5]
    out["written"] = [[r.attrs["fun"], r.t1 - r.t0]
                      for r in compiles if outcome(r) == "miss_written"]
    sent = [r.t0 for r in recs if r.name == "decode.request" and r.t0 < t0]
    warm_s = overlap = 0.0
    if sent:
        warm_s = t0 - min(sent)
        overlap = union(before, lo=min(sent), hi=t0)
    out["warm_s"] = warm_s
    out["compile_in_warm_s"] = overlap
    out["rest_s"] = setup_s - (out["setup_compile_s"] + warm_s - overlap)
    return out


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", default="0")
    ap.add_argument("--seconds", default=None)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--bench", default="BENCHMARK.json")
    args = ap.parse_args(argv)
    marks = {}
    start = run.Probe.start

    def start_and_mark(probe):      # the window's start, on both clocks
        marks["wall"] = time.time()
        start(probe)
        marks["probe"] = probe

    run.Probe.start = start_and_mark
    argv = ["--workload", args.workload, "--seed", args.seed, "--trace", "1",
            "--bench", args.bench] + (["--seconds", args.seconds]
                                      if args.seconds else []) \
        + (["--rehearse"] if args.rehearse else [])
    printed = io.StringIO()
    with contextlib.redirect_stdout(printed):
        rc = run.main(argv)
    sys.stdout.write(printed.getvalue())
    from heat_tpu.utils import profiling

    parts = split(profiling.spans(), marks["probe"].t0,
                  marks["wall"] - run.T_START)
    line = {"setup_split": args.workload, "seed": int(args.seed), "rc": rc,
            "dropped": profiling.dropped(), "complete": parts is not None,
            **(parts or {})}
    text = json.dumps(line)
    print(text, flush=True)
    out_dir = os.path.join(run.ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, "setup_split.jsonl"), "a") as f:
        f.write(text + "\n")
    return rc


if __name__ == "__main__":
    sys.exit(main())
