"""From the profiler's `.xplane.pb` to numbers, with nothing but JAX.

What a trace gives without names inside the program: the device planes
(`/device:TPU:<n>`), on each the line of XLA operations with a start and a
duration per operation, and the host plane with `perfbench`'s own spans
(`jax.profiler.TraceAnnotation("pb.<name>")`) on the same clock.

* busy: the union of the intervals in which an operation ran on a device,
  clipped to the traced window (the `pb.window` span);
* idle share: 1 - busy / window;
* time by operation name: SELF time (an operation that encloses others, a
  `while` around its body, is charged only what its children leave);
* collectives: operations whose HLO name is a collective;
* idle gaps: the complement of busy on device 0, each gap charged to the
  `pb.*` host span that covers most of it.

A rehearsal on the CPU has no device plane; there the XLA:CPU worker threads
of the host plane stand in, so that the code path is exercised. Its numbers
are never a device metric (`run.py` marks the whole line a rehearsal).
"""

from __future__ import annotations

import glob
import os
import re

WINDOW_SPAN = "pb.window"
SPAN_PREFIX = "pb."
OPS_LINE = "XLA Ops"
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute")
_DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
_CPU_WORKER = ("tf_XLAPjRtCpuClient", "tf_XLAEigen", "tf_XLATfrtCpuClient")


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def load_planes(path: str) -> dict:
    """{plane name: {line name: [(event name, start_ns, duration_ns)]}}"""
    from jax.profiler import ProfileData

    planes = {}
    for plane in ProfileData.from_file(path).planes:
        lines = planes.setdefault(plane.name, {})
        for line in plane.lines:
            lines.setdefault(line.name, []).extend(
                (op_name(ev.name), float(ev.start_ns), float(ev.duration_ns))
                for ev in line.events)
    return planes


def op_name(name: str) -> str:
    """The TPU's device lines name an operation by its whole HLO text
    (`fusion.3 = f32[...] fusion(...)`): keep what stands before ` = `."""
    return name.split(" = ", 1)[0].lstrip("%")


def union(intervals) -> list:
    """Sorted, disjoint [start, end) covering the same points."""
    out = []
    for s, e in sorted(i for i in intervals if i[1] > i[0]):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def length(disjoint) -> float:
    return sum(e - s for s, e in disjoint)


def clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def self_times(events) -> dict:
    """{name: self ns} for events of ONE line, where an event may enclose
    later ones: each is charged its duration less its direct children's."""
    total = {}
    stack = []  # (end, name)
    for name, s, d in sorted(events, key=lambda ev: (ev[1], -ev[2])):
        if d <= 0:
            continue
        while stack and stack[-1][0] <= s:
            stack.pop()
        if stack and s + d <= stack[-1][0] + 1:   # nested in its parent
            total[stack[-1][1]] = total.get(stack[-1][1], 0.0) - d
        total[name] = total.get(name, 0.0) + d
        stack.append((s + d, name))
    return {n: t for n, t in total.items() if t > 0}


def is_collective(name: str) -> bool:
    return any(c in name for c in COLLECTIVES)


def _host_spans(planes):
    spans = []
    for pname, lines in planes.items():
        if not pname.startswith("/host:"):
            continue
        for evs in lines.values():
            spans.extend(ev for ev in evs if ev[0].startswith(SPAN_PREFIX))
    return spans


def _device_ops(planes, chips, rehearse):
    """[(device id, [events])] for the first `chips` devices."""
    found = []
    for pname, lines in planes.items():
        m = _DEVICE_PLANE.match(pname)
        if m and OPS_LINE in lines:
            found.append((int(m.group(1)), lines[OPS_LINE]))
    if not found and rehearse:
        cpu = []
        for lname, evs in planes.get("/host:CPU", {}).items():
            if lname.startswith(_CPU_WORKER):
                cpu.extend(ev for ev in evs if ev[2] > 0)
        if cpu:
            found = [(0, cpu)]
    return sorted(found)[:chips]


def _gap_owner(gap, spans):
    best, covered = "none", 0.0
    for name, s, d in spans:
        c = min(gap[1], s + d) - max(gap[0], s)
        if c > covered:
            best, covered = name[len(SPAN_PREFIX):], c
    return best


def reduce_planes(planes: dict, chips: int, rehearse: bool = False) -> dict:
    """The reduction. Raises where the trace has no window span or no
    operation on a device: a traced run that saw no device is not a run."""
    spans = _host_spans(planes)
    win = [ev for ev in spans if ev[0] == WINDOW_SPAN]
    if not win:
        raise ValueError(f"the trace holds no {WINDOW_SPAN!r} span")
    w0 = min(s for _n, s, _d in win)
    w1 = max(s + d for _n, s, d in win)
    devices = _device_ops(planes, chips, rehearse)
    if len(devices) < (1 if rehearse else chips):
        raise ValueError(
            f"the trace holds {len(devices)} device planes with an "
            f"{OPS_LINE!r} line, the cell runs on {chips}: "
            f"{sorted(planes)}")
    busy, per_device = [], []
    for dev, evs in devices:
        u = union(clip([(s, s + d) for _n, s, d in evs], w0, w1))
        busy.append(length(u))
        per_device.append((dev, evs, u))
    if not any(busy):
        raise ValueError("no operation ran on a device inside the window")
    _dev0, evs0, u0 = per_device[0]
    in_win = [(n, max(s, w0), min(s + d, w1) - max(s, w0))
              for n, s, d in evs0 if min(s + d, w1) > max(s, w0)]
    by_name = self_times(in_win)
    coll = union([(s, s + d) for n, s, d in in_win if is_collective(n)])
    gaps, prev = [], w0
    for s, e in u0:
        if s > prev:
            gaps.append((prev, s))
        prev = e
    if w1 > prev:
        gaps.append((prev, w1))
    inner = [sp for sp in spans if sp[0] != WINDOW_SPAN]
    by_owner = {}
    for g in gaps:
        owner = _gap_owner(g, inner)
        by_owner[owner] = by_owner.get(owner, 0.0) + (g[1] - g[0])
    ns = 1e-9
    return {
        "window_s": (w1 - w0) * ns,
        "busy_s": sum(busy) / len(busy) * ns,
        "busy_s_by_device": {str(d): b * ns
                             for (d, _e, _u), b in zip(per_device, busy)},
        "device0_busy_s": busy[0] * ns,
        "device0_collective_s": length(coll) * ns,
        "device0_ops": sorted(((n, t * ns) for n, t in by_name.items()),
                              key=lambda kv: -kv[1]),
        "device0_idle_gaps": sorted(((n, t * ns) for n, t in by_owner.items()),
                                    key=lambda kv: -kv[1]),
        "device0_longest_gap_s": max((g[1] - g[0] for g in gaps),
                                     default=0.0) * ns,
        "n_events_device0": len(in_win),
    }


def idle_share_percent(trace: dict) -> float:
    """1 - busy / window of a reduction, mean over the chips used, in %."""
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])


def reduce_trace(trace_dir: str, chips: int, rehearse: bool = False) -> dict:
    return reduce_planes(load_planes(find_xplane(trace_dir)), chips, rehearse)


def describe(planes: dict, per_line: int = 6) -> str:
    """What a trace holds, for a human who writes code against it."""
    out = []
    for pname, lines in planes.items():
        out.append(f"PLANE {pname}")
        for lname, evs in lines.items():
            names = []
            for n, _s, _d in evs:
                if n not in names:
                    names.append(n)
                if len(names) >= per_line:
                    break
            out.append(f"  LINE {lname!r}: {len(evs)} events, e.g. {names}")
    return "\n".join(out)
