"""One cell of the benchmark, once: on the chip, or a non-zero exit.

    python3 -m perfbench.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>
    python3 -m perfbench.run --workload <cell> --rehearse      # tiny, CPU, never a measurement
    python3 -m perfbench.run --bench perfbench/HELD.json --workload <held cell> ...

ONE process. It reads the cell from `BENCHMARK.json` (or from the file
`--bench` names: `perfbench/HELD.json` lists, in the same form, the cells
that are built and held back, see PERF.md section 7), the configuration and
the traffic mix from the files named there, loads the mix's driver and the
cell's per-layer readers BY NAME (`drivers/<driver>.py`,
`layer_metrics/<metric>.py`), and holds no table of names itself. The driver
makes data or weights on the device from `--seed`, warms up the cell's own
shapes (all of that is `setup_s`), runs the window for `--seconds`, frees the
program's state, and compares what the window produced with the plain
reference under `references/`. The last line of stdout is one JSON object:
`correct, attempted, failed, metrics, device` (+ `breakdown` when traced) and
last the numbers compared, each beside its limit.

A traced run hands each reader two things: the ONE reduction of the traced
sub-window's xplane (`run.trace`, `trace_scopes.py`: busy and idle time over
the chips used, device 0's time by program, scope and direction, idle gaps by
innermost host span) and the program's own host spans (`run.spans`, the ring
of `heat_tpu.utils.profiling`, on the probe's clock). The ring records from
before set-up in every traced run, so that a request that was sent before the
profiler started still has its spans; `spans_named` cuts them to a window.

Exit codes: 0 a result was printed; 2 no accelerator, too few chips, an
unknown `device_kind` or an unknown cell; 3 a share read over 100%.
"""

from __future__ import annotations

import time

T_START = time.time()          # process start, for setup_s (before any import)

import argparse                # noqa: E402
import contextlib              # noqa: E402
import importlib.util          # noqa: E402
import json                    # noqa: E402
import os                      # noqa: E402
import shutil                  # noqa: E402
import sys                     # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
TRACE_DIR = os.path.join(ROOT, ".perfbench_trace")
SHARE_UNIT = "%"
SHARE_LIMIT = 100.0


class BenchError(SystemExit):
    def __init__(self, code, msg):
        sys.stderr.write(f"perfbench: {msg}\n")
        super().__init__(code)


def load_json(path):
    with open(path) as f:
        return json.load(f)


def load_by_name(kind: str, name: str):
    """`perfbench/<kind>/<name>.py` as a module. Names may hold dots
    (`dispatch_ms.train`), so this goes by path, not by import name."""
    path = os.path.join(HERE, kind, name + ".py")
    if not os.path.isfile(path):
        raise BenchError(2, f"no {kind} file {path}")
    modname = f"perfbench.{kind}." + name.replace(".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(modname, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[modname] = mod
    spec.loader.exec_module(mod)
    return mod


def find_cell(bench: dict, workload: str):
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise BenchError(2, f"no cell {workload!r} in the file of cells "
                            f"(has {sorted(cells)})")
    cell = cells[workload]
    config = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    return cell, config


def metrics_of(bench: dict, group: str, cell_name: str) -> list:
    return [m for m in bench[group]
            if "workloads" not in m or cell_name in m["workloads"]]


def overlay(base: dict, rehearse: bool) -> dict:
    """The file as it is run; a rehearsal lays its tiny sizes over it."""
    out = {k: v for k, v in base.items() if k != "rehearse"}
    if rehearse:
        out.update(base.get("rehearse", {}))
    return out


class Probe:
    """The window's clock, `perfbench`'s own host spans, and the traced
    sub-window. The driver calls `span()` around each call into the program,
    `unit()` when a job/step/request is complete, and `poll()` wherever it
    idles; the probe starts and stops the profiler at such a boundary, after
    `sync()` has drained the device, so the traced window holds whole units.
    """

    def __init__(self, seconds, plan, counters, sync, trace_dir=None):
        self.seconds = float(seconds)
        self.plan = plan or {}
        self._counters, self._sync = counters, sync
        self.trace_dir = trace_dir
        self.spans = {}
        self.units = 0
        self.tracing = False
        self.traced = None
        self._win = None
        self.t0 = self.window_s = None

    def start(self):
        self.at_start = self._counters()
        self.t0 = time.perf_counter()

    def elapsed(self):
        return time.perf_counter() - self.t0

    def done(self):
        return self.elapsed() >= self.seconds

    @contextlib.contextmanager
    def span(self, name):
        ann = None
        if self.tracing:
            import jax
            ann = jax.profiler.TraceAnnotation("pb." + name)
            ann.__enter__()
        t = time.perf_counter()
        try:
            yield
        finally:
            self.spans.setdefault(name, []).append((t, time.perf_counter()))
            if ann is not None:
                ann.__exit__(None, None, None)

    def unit(self, n=1):
        self.units += n
        self.poll()

    def poll(self):
        if self.trace_dir is None or (self.traced and not self.tracing):
            return
        p, now = self.plan, self.elapsed()
        if not self.tracing:
            if (self.units >= p.get("after_units", 0)
                    and now >= p.get("after_s", 0.0)):
                self._trace_start()
        else:
            tr = self.traced
            if ((self.units - tr["units0"] >= p.get("units", 1)
                 and time.perf_counter() - tr["t0"] >= p.get("seconds", 0.0))
                    or self.done()):
                self._trace_stop()

    def _trace_start(self):
        import jax
        self._sync()
        shutil.rmtree(self.trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0       # the python tracer slows the host
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
        self.tracing = True
        self.traced = {"units0": self.units, "counters0": self._counters()}
        self._win = jax.profiler.TraceAnnotation("pb.window")
        self._win.__enter__()
        self.traced["t0"] = time.perf_counter()

    def _trace_stop(self):
        import jax
        self._sync()
        tr = self.traced
        tr["t1"] = time.perf_counter()
        self._win.__exit__(None, None, None)
        tr["units"] = self.units - tr["units0"]
        tr["counters1"] = self._counters()
        tr["seconds"] = tr["t1"] - tr["t0"]
        self.tracing = False
        jax.profiler.stop_trace()

    def close(self):
        """The window ends HERE; what the driver still does before it returns
        (waiting for answers that were due) is not in it."""
        if self.tracing:
            self._trace_stop()
        self.window_s = self.elapsed()
        self.at_end = self._counters()

    def finish(self):
        if self.window_s is None:
            self.close()


class Run:
    """What a per-layer reader is given."""

    def __init__(self, **kw):
        self.__dict__.update(kw)

    def counter_delta(self, name, traced=False):
        a, b = ((self.probe.traced["counters0"], self.probe.traced["counters1"])
                if traced else (self.probe.at_start, self.probe.at_end))
        if name not in a or name not in b:
            return None
        return b[name] - a[name]

    def spans_named(self, name, traced=True):
        """The program's spans of that name that lie whole inside the traced
        sub-window (or, `traced=False`, end inside the run's window)."""
        tr = self.probe.traced
        if traced:
            if not tr or "t1" not in tr:
                return []
            lo, hi = tr["t0"], tr["t1"]
            return [r for r in self.spans
                    if r.name == name and lo <= r.t0 and r.t1 <= hi]
        lo, hi = self.probe.t0, self.probe.t0 + self.probe.window_s
        return [r for r in self.spans if r.name == name and lo <= r.t1 <= hi]


def program_spans(enable=False) -> list:
    """The ring of the program's host spans (`heat_tpu.utils.profiling`),
    oldest first; `enable` makes it record from now on. A program without
    the module has no spans."""
    try:
        from heat_tpu.utils import profiling
    except ImportError:
        return []
    if enable:
        profiling.enable()
    return profiling.spans()


def breakdown_of(trace: dict) -> dict:
    """What the ledger keeps of a traced run: the ten heaviest device
    operations, each under the program's scope (`scope:operation`), and the
    idle gaps by what the host was doing (the innermost `ht.`/`pb.` span)."""
    ops = {}
    for (_prog, scope, _d), row in trace["by_scope"].items():
        for name, t in row["ops"].items():
            key = f"{scope}:{name}"
            ops[key] = ops.get(key, 0.0) + t
    return {"device_ops": [list(kv) for kv in sorted(
                ops.items(), key=lambda kv: -kv[1])[:10]],
            "idle_gaps": [list(kv) for kv in
                          list(trace["idle_gaps"].items())[:10]]}


def device_summary(devices):
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in devices]
    peaks = [p for p in peaks if p is not None]
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices),
            "memory_peak_bytes": max(peaks) if peaks else 0}


def judge(checks):
    """`correct`: every number compared is at or under its limit."""
    return bool(checks) and all(
        v is not None and v == v and v <= lim for _n, v, lim in checks)


def prepare(workload, seed, rehearse, bench_file="BENCHMARK.json"):
    """Everything up to the driver: the cell's files, JAX on the right
    device or an exit, the compile cache and the compile counters."""
    bench = load_json(os.path.join(ROOT, bench_file))
    cell, config_entry = find_cell(bench, workload)
    chips = int(cell["chips"])
    if rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
        flag = "--xla_force_host_platform_device_count"
        if flag not in os.environ.get("XLA_FLAGS", ""):
            os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                                       + f" {flag}={chips}")
    config = overlay(load_json(os.path.join(ROOT, config_entry["file"])),
                     rehearse)
    traffic = overlay(load_json(os.path.join(
        HERE, "traffic", cell["traffic"] + ".json")), rehearse)

    import jax

    devices = jax.devices()
    if not rehearse and devices[0].platform != "tpu":
        raise BenchError(2, f"no accelerator: jax.devices()[0].platform is "
                            f"{devices[0].platform!r}. This runs on the chip "
                            f"or fails; --rehearse is the tiny CPU run.")
    if len(devices) < chips:
        raise BenchError(2, f"cell {cell['name']} needs {chips} chips, "
                            f"jax reports {len(devices)}")
    devices = devices[:chips]
    table = load_json(os.path.join(HERE, "peaks.json"))
    peaks = table.get(devices[0].device_kind)
    if peaks is None and rehearse:          # so that the readers run at all
        peaks = next(v for k, v in table.items() if isinstance(v, dict))
    if peaks is None:
        raise BenchError(2, f"no published peaks for device_kind "
                            f"{devices[0].device_kind!r} in perfbench/peaks.json")

    # compile cache: placed from outside, else ONE fixed path in the checkout
    if "JAX_COMPILATION_CACHE_DIR" not in os.environ and not rehearse:
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(ROOT, ".jax_cache"))
    compiles = {"n": 0, "s": 0.0, "hits": 0, "misses": 0}

    def on_duration(event, secs, **_kw):
        if event.startswith("/jax/core/compile/"):
            compiles["s"] += secs
            if event.endswith("backend_compile_duration"):
                compiles["n"] += 1

    def on_event(event, **_kw):
        if event == "/jax/compilation_cache/cache_hits":
            compiles["hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            compiles["misses"] += 1

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    jax.monitoring.register_event_listener(on_event)

    from perfbench import work

    driver = load_by_name("drivers", traffic["driver"])
    limits = {k: float(v["limit"]) for k, v in load_json(os.path.join(
        HERE, "limits", cell["name"] + ".json")).items() if k != "_doc"}
    ctx = Run(cell=cell, config=config, traffic=traffic, seed=seed,
              chips=chips, devices=devices, rehearse=rehearse,
              peaks=peaks, work=work, limits=limits, memo={})
    return bench, ctx, driver, compiles


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, default=0, choices=(0, 1))
    ap.add_argument("--bench", default="BENCHMARK.json",
                    help="the file of cells, relative to the checkout")
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes on the CPU: finds wrong paths, never "
                         "a measurement")
    args = ap.parse_args(argv)

    bench, ctx, driver, compiles = prepare(args.workload, args.seed,
                                           args.rehearse, args.bench)
    import jax

    cell, chips, devices, traffic = ctx.cell, ctx.chips, ctx.devices, ctx.traffic
    seconds = args.seconds if args.seconds is not None else (
        2.0 if args.rehearse else float(bench["run_seconds"]))
    if args.trace:
        program_spans(enable=True)
    program = driver.Cell(ctx)
    program.setup()

    probe = Probe(seconds, traffic.get("trace"), program.counters,
                  program.sync, TRACE_DIR if args.trace else None)
    compiles_at_start = compiles["n"]
    setup_s = time.time() - T_START
    probe.start()
    result = program.window(probe)
    probe.finish()
    compiles_in_window = compiles["n"] - compiles_at_start
    device = device_summary(devices)
    info = {"info": cell["name"], "seed": args.seed,
            "window_s": probe.window_s, "units": probe.units,
            "compiles_in_window": compiles_in_window,
            "compile_s_total": compiles["s"],
            "persistent_cache": {"hits": compiles["hits"],
                                 "misses": compiles["misses"]},
            "peak_bytes_in_use": device["memory_peak_bytes"],
            "counters": probe.at_end}

    metrics, breakdown = {}, None
    if args.trace:
        from perfbench import trace_scopes

        xplane = trace_scopes.find_xplane(TRACE_DIR)
        keep = os.environ.get("PERFBENCH_KEEP_TRACE")   # a builder's look
        if keep:
            os.makedirs(keep, exist_ok=True)
            shutil.copy(xplane, os.path.join(keep, cell["name"] + ".xplane.pb"))
        trace = trace_scopes.reduce_file(xplane, chips, args.rehearse)
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        device["busy_s"], device["window_s"] = trace["busy_s"], trace["window_s"]
        breakdown = breakdown_of(trace)
        run = Run(trace=trace, spans=program_spans(), probe=probe,
                  result=result, **ctx.__dict__)
        for m in metrics_of(bench, "per_layer", cell["name"]):
            value = load_by_name("layer_metrics", m["name"]).read(run)
            if value is None:
                continue            # nothing to read: left out, never a 0
            if m["unit"] == SHARE_UNIT and value > SHARE_LIMIT:
                raise BenchError(3, f"{m['name']} reads {value}% : over "
                                    f"{SHARE_LIMIT}%, so the work is counted "
                                    f"too high or the time too short")
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        values = dict(result["metrics"], setup_s=setup_s)
        for m in metrics_of(bench, "end_to_end", cell["name"]):
            if m["name"] not in values:
                raise BenchError(2, f"driver {traffic['driver']} reports no "
                                    f"{m['name']} for {cell['name']}")
            metrics[m["name"]] = {"value": values[m["name"]], "unit": m["unit"]}

    # the reference runs last: the peak is read, the program's state is freed
    program.release()
    t_ref = time.perf_counter()
    checks = list(program.check())
    checks.append(("compiles_in_window", float(compiles_in_window), 0.0))
    info["reference_s"] = time.perf_counter() - t_ref
    print(json.dumps(info), flush=True)

    line = {"correct": judge(checks), "attempted": result["attempted"],
            "failed": result["failed"], "metrics": metrics, "device": device}
    if args.rehearse:
        line["rehearsal"] = True
    if breakdown is not None:
        line["breakdown"] = breakdown
    line["checks"] = {n: {"value": v, "limit": lim} for n, v, lim in checks}
    for n, v, lim in checks:
        sys.stderr.write(f"check {n} = {v!r} limit {lim!r} "
                         f"{'ok' if v is not None and v <= lim else 'FAIL'}\n")
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    program.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
