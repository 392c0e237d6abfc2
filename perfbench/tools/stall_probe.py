"""A run that reads slow by ONE long pause (PERF.md section 7.8): who stood still?

    python3 -m perfbench.tools.stall_probe --workload <cell> --seed <n> \
        --seconds <s> [--trace 0|1] [--bench ...]

Runs `perfbench.run.main` with those arguments, unchanged and in this
process, and watches beside it:

* a ticker thread that sleeps 20 ms and notes every wake-up that came more
  than 50 ms late. It needs no device and holds no lock of the program, so a
  late wake-up says the PROCESS (or the whole machine) stood still; a driver's
  long span with the ticker on time says the host ran and the wait was for
  the device or its runtime;
* the same ticker in a CHILD process that imports nothing of the program and
  never touches the chip (both read CLOCK_MONOTONIC): late at the same moment
  as the thread, the machine stood still; on time, only this process did (a
  thread that kept the interpreter's lock, the runtime);
* the machine's counters at the window's two ends: `/proc/stat`'s steal,
  iowait and busy jiffies, `/proc/pressure/*` totals, `/proc/vmstat`'s
  stalls, compaction, direct reclaim and major faults, and this process's
  wait for a core (`/proc/self/task/*/schedstat`), involuntary switches and
  CPU time, and the cgroup's `cpu.stat` (periods throttled), each where the
  machine has it (a sandboxed kernel shows little of /proc);
* the driver's own spans (`probe.span`): count, mean and longest, and when
  the longest began, so that it can be laid beside the ticker's late wake-ups,
  and how long the driver went on after the window closed (`after_close_s`:
  the serving driver waits for the requests under way).

One line `{"stall_probe": ...}` goes to stderr after the run's own lines and
is appended to `chiprun_out/stall_probe.jsonl`. Never part of a benchmark
run; the numbers of a run made through it are the run's own (the ticker wakes
50 times a second).
"""

import glob
import json
import os
import subprocess
import sys
import threading
import time

from perfbench import run as harness

TICK_S, LATE_S = 0.02, 0.05
CHILD = f"""
import json, sys, threading, time
stop = threading.Event()
threading.Thread(target=lambda: (sys.stdin.read(), stop.set()),
                 daemon=True).start()
late = []
while not stop.is_set():
    t = time.perf_counter()
    time.sleep({TICK_S})
    now = time.perf_counter()
    if now - t - {TICK_S} > {LATE_S}:
        late.append((now, now - t - {TICK_S}))
print(json.dumps(late))
"""
VMSTAT = ("stall", "compact_", "pgscan_direct", "pgmajfault", "thp_fault",
          "numa_pages_migrated", "oom_kill")


def _lines(path) -> list:
    """A /proc file's lines, or none where the machine has no such file (a
    sandboxed kernel shows only part of /proc)."""
    try:
        with open(path) as f:
            return f.read().splitlines()
    except OSError:
        return []


def machine_counters() -> dict:
    out = {}
    names = ("user", "nice", "system", "idle", "iowait", "irq", "softirq",
             "steal", "guest", "guest_nice")
    for line in _lines("/proc/stat")[:1]:
        out.update({"stat." + n: int(v)
                    for n, v in zip(names, line.split()[1:])})
    for path in glob.glob("/proc/pressure/*"):
        for line in _lines(path):
            kind, *rest = line.split()
            total = dict(kv.split("=") for kv in rest).get("total")
            out[f"pressure.{os.path.basename(path)}.{kind}_us"] = int(total)
    for line in _lines("/proc/vmstat"):
        key, value = line.split()
        if any(p in key for p in VMSTAT):
            out["vmstat." + key] = int(value)
    waits = [int(line.split()[1])
             for path in glob.glob("/proc/self/task/*/schedstat")
             for line in _lines(path)[:1]]
    if waits:
        out["self.runqueue_wait_ns"] = sum(waits)
    for line in _lines("/proc/self/status"):
        if line.startswith("nonvoluntary_ctxt_switches"):
            out["self.nonvoluntary_switches"] = int(line.split()[1])
    for path in ("/sys/fs/cgroup/cpu.stat", "/sys/fs/cgroup/cpu/cpu.stat"):
        for line in _lines(path):
            key, value = line.split()
            out["cgroup." + key] = int(value)
    out["self.cpu_s"] = time.process_time()
    return out


class Ticker(threading.Thread):
    def __init__(self):
        super().__init__(daemon=True, name="stall-probe-ticker")
        self.stop = threading.Event()
        self.ticks, self.late = 0, []       # late: (woke at, seconds late)

    def run(self):
        while not self.stop.is_set():
            t = time.perf_counter()
            time.sleep(TICK_S)
            now = time.perf_counter()
            self.ticks += 1
            if now - t - TICK_S > LATE_S:
                self.late.append((now, now - t - TICK_S))


class WatchedProbe(harness.Probe):
    seen = None

    def start(self):
        WatchedProbe.seen = self
        self.machine0 = machine_counters()
        super().start()

    def close(self):
        super().close()
        self.machine1 = machine_counters()
        self.closed_at = time.perf_counter()

    def finish(self):
        super().finish()
        # what the driver still did after the window (waiting for answers)
        self.after_close_s = time.perf_counter() - self.closed_at


def report(probe, ticker, child_late=()) -> dict:
    spans = {}
    for name, pairs in probe.spans.items():
        took = [b - a for a, b in pairs]
        at = max(range(len(took)), key=took.__getitem__)
        spans[name] = {"count": len(took), "mean_ms": 1e3 * sum(took) / len(took),
                       "longest_ms": 1e3 * took[at],
                       "longest_began_s": pairs[at][0] - probe.t0}
    lo, hi = probe.t0, probe.t0 + probe.window_s
    return {
        "window_s": probe.window_s, "units": probe.units,
        "after_close_s": probe.after_close_s, "spans": spans,
        "ticks": ticker.ticks,
        "late_wakeups_in_window": [
            {"woke_at_s": t - probe.t0, "late_ms": 1e3 * late}
            for t, late in ticker.late if lo <= t <= hi],
        "child_late_wakeups_in_window": [
            {"woke_at_s": t - probe.t0, "late_ms": 1e3 * late}
            for t, late in child_late if lo <= t <= hi],
        "machine_delta": {k: probe.machine1[k] - v
                          for k, v in probe.machine0.items()
                          if k in probe.machine1 and probe.machine1[k] != v}}


def main(argv=None):
    harness.Probe = WatchedProbe
    child = subprocess.Popen([sys.executable, "-c", CHILD], text=True,
                             stdin=subprocess.PIPE, stdout=subprocess.PIPE)
    ticker = Ticker()
    ticker.start()
    try:
        rc = harness.main(argv)
    finally:
        ticker.stop.set()
        ticker.join()
        child_late, _ = child.communicate("", timeout=30)   # closes its stdin
    line = json.dumps({"stall_probe": report(WatchedProbe.seen, ticker,
                                             json.loads(child_late or "[]")),
                       "argv": list(argv if argv is not None else sys.argv[1:])})
    sys.stderr.write(line + "\n")
    out_dir = os.path.join(harness.ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, "stall_probe.jsonl"), "a") as f:
        f.write(line + "\n")
    return rc


if __name__ == "__main__":
    sys.exit(main())
