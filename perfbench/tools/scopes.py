"""A traced step, read by name.

    python3 -m perfbench.tools.scopes <file.xplane.pb> [--chips n] [--ops k] [--json]

Prints `perfbench/trace_scopes.py`'s reduction of one kept trace
(`PERFBENCH_KEEP_TRACE=<dir>` makes `perfbench.run --trace 1` keep it):
device 0's self time inside `pb.window` by (program, scope, direction) with
the compiler's flops and bytes beside it, the `unscoped` rest by operation,
the program runs, the idle gaps by innermost host span, and collective time
exposed against hidden. Needs no JAX and no chip. It is the reduction that
`run.py` hands the per-layer readers as `run.trace`, printed for a human.
"""

import argparse
import json

from perfbench import trace_scopes as ts


def render(r: dict, ops: int = 6) -> str:
    busy = r["device0_busy_s"]
    total = sum(v["self_s"] for v in r["by_scope"].values()) or 1.0
    out = [f"window {r['window_s']:.6f} s, device 0 busy {busy:.6f} s "
           f"({100 * busy / r['window_s']:.2f}%)", "",
           "self time by (program, scope, direction); flops and bytes are "
           "XLA's count of what it emitted",
           f"{'program':<18}{'scope':<14}{'dir':<5}{'ms':>10}{'%':>7}"
           f"{'xla GFLOP':>12}{'xla GB':>9}  heaviest operations"]
    rows = sorted(r["by_scope"].items(), key=lambda kv: -kv[1]["self_s"])
    for (program, scope, direction), v in rows:
        top = sorted(v["ops"].items(), key=lambda kv: -kv[1])[:ops]
        out.append(
            f"{program:<18}{scope:<14}{direction:<5}{1e3 * v['self_s']:>10.3f}"
            f"{100 * v['self_s'] / total:>7.2f}{v['xla_flops'] / 1e9:>12.1f}"
            f"{v['xla_bytes_accessed'] / 1e9:>9.2f}  "
            + ", ".join(f"{n} {1e3 * t:.2f}" for n, t in top))
    shares = ts.scope_shares(r)
    named = 1.0 - shares.get(ts.UNSCOPED, 0.0)
    out += ["", f"under a named scope: {100 * named:.2f}% of self time; by "
            "scope: " + ", ".join(f"{s} {100 * x:.2f}%"
                                  for s, x in shares.items()), "",
            "program runs in the window (XLA Modules)"]
    for name, v in sorted(r["programs"].items(),
                          key=lambda kv: -kv[1]["device_s"]):
        out.append(f"  {name:<28}{v['runs']:>6} runs{1e3 * v['device_s']:>12.3f} ms")
    idle = sum(r["idle_gaps"].values()) or 1.0
    out += ["", f"idle gaps of device 0 by innermost span "
            f"({1e3 * sum(r['idle_gaps'].values()):.3f} ms idle)"]
    for name, t in r["idle_gaps"].items():
        out.append(f"  {name:<28}{1e3 * t:>10.3f} ms{100 * t / idle:>7.1f}%")
    out += ["", "collectives: exposed (nothing else running) against hidden"]
    for dev, c in r["collectives"].items():
        out.append(f"  device {dev}: total {1e3 * c['total_s']:.3f} ms, exposed "
                   f"{1e3 * c['exposed_s']:.3f} ms, hidden "
                   f"{1e3 * c['hidden_s']:.3f} ms")
    return "\n".join(out)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("xplane")
    ap.add_argument("--chips", type=int, default=1)
    ap.add_argument("--ops", type=int, default=6,
                    help="operations listed per row")
    ap.add_argument("--json", action="store_true")
    args = ap.parse_args(argv)
    r = ts.reduce_file(args.xplane, args.chips)
    if args.json:
        r["by_scope"] = [dict(program=p, scope=s, direction=d, **v)
                         for (p, s, d), v in r["by_scope"].items()]
        print(json.dumps(r))
    else:
        print(render(r, args.ops))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
