"""From the profiler's `.xplane.pb` to numbers, with nothing but the standard
library: ONE reader of the file and ONE reduction, which every per-layer
reader is handed as `run.trace` (busy and idle time, and device time BY NAME).

`jax.profiler.ProfileData` hands out each event's name, start and duration,
and the event's own stats. What the program's names ride in is the plane's
EVENT METADATA, which it does not hand out. So this file reads the wire
format of the xplane itself. Fields read (tsl/profiler/protobuf/xplane.proto):

    XSpace.planes=1
    XPlane.name=2 .lines=3 .event_metadata=4 (map: key=1, value=2)
          .stat_metadata=5 (map: key=1, value=2)
    XLine.name=2 .timestamp_ns=3 .events=4
    XEvent.metadata_id=1 .offset_ps=2 .duration_ps=3      (its stats: skipped)
    XEventMetadata.id=1 .name=2 .display_name=4 .stats=5
    XStatMetadata.id=1 .name=2
    XStat.metadata_id=1 .double_value=2 .uint64_value=3 .int64_value=4
         .str_value=5 .ref_value=7 (a string kept as a stat-metadata name)

Of a device operation's metadata it keeps `tf_op` (the HLO `op_name`: the
jit's name and the `jax.named_scope` stack, `jit(train_step)/.../attn.core/
dot_general`), `flops` and `bytes_accessed` (XLA's cost analysis of that
operation: the COMPILER'S count of what it emitted, never the work the
algorithm needs), `program_id` and `hlo_category`. A time is
`line.timestamp_ns + offset_ps // 1000` and a duration `duration_ps // 1000`,
whole nanoseconds as `jax.profiler.ProfileData` gives them.

The reduction (`reduce_planes`), inside the `pb.window` span (the traced
sub-window, which `run.py` opens and closes on whole units):

* `busy_s`: the union of the intervals in which an operation ran on a device,
  mean over the chips used (`busy_s_by_device`, `device0_busy_s`); the idle
  share is 1 - busy / window (`idle_share_percent`). A traced run in which no
  operation ran on a device is not a run: it raises.
* `by_scope`: SELF time by (program, scope, direction). `scope` is the
  innermost of `SCOPES` in `tf_op` (a fusion carries its root's `op_name`, so
  it counts under its root's scope), `unscoped` where there is none;
  `direction` is `bwd` under `transpose(`. The compiler's flops and bytes are
  summed over the operations that enclose no other (a `while` would count its
  body twice).
* `programs`: runs and device time per program name, from `XLA Modules`.
* `idle_gaps`: each gap of device 0 charged to the INNERMOST `ht.`/`pb.` host
  span that covers at least half of it: a span of the program (`ht.`) before
  one of the benchmark (`pb.`), within a kind the shortest; `pb.window` itself
  only when nothing inside it qualifies; `none` when not even that.
* `collectives`: time of collective operations, split into exposed (no other
  operation of that device running) and hidden.

A rehearsal on the CPU has no device plane; there the XLA:CPU worker threads
of the host plane stand in as device 0 (no metadata: all `unscoped`), so that
the code path is exercised. Its numbers are never a device metric (`run.py`
marks the whole line a rehearsal).
"""

from __future__ import annotations

import glob
import os
import re
import struct

WINDOW_SPAN = "pb.window"
SPAN_PREFIXES = ("ht.", "pb.")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
ASYNC_LINE = "Async XLA Ops"
UNSCOPED = "unscoped"
# the names `heat_tpu.utils.profiling.scope` is called with (ISSUE 29, PERF.md
# section 3); a name the program adds later is added here
SCOPES = frozenset((
    "embed", "cast", "pipeline", "attn.qkv", "attn.core", "attn.proj", "mlp",
    "moe", "head", "loss", "grad_psum", "optimizer", "cache.read",
    "cache.write", "sample",
    "lloyd.norms", "lloyd.dist", "lloyd.argmin", "lloyd.sums", "lloyd.psum",
    "lloyd.update", "lloyd.inertia"))
META_KEPT = ("tf_op", "flops", "bytes_accessed", "program_id", "hlo_category")
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute")
_WRAPPED = re.compile(r"^\w+\((.*)\)$")
_PROGRAM = re.compile(r"^(.*)\((\d+)\)$")
_DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
_CPU_WORKER = ("tf_XLAPjRtCpuClient", "tf_XLAEigen", "tf_XLATfrtCpuClient")


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


# ---------------------------------------------------------------------- #
# intervals and names                                                    #
# ---------------------------------------------------------------------- #
def op_name(name: str) -> str:
    """The TPU's device lines name an operation by its whole HLO text
    (`fusion.3 = f32[...] fusion(...)`): keep what stands before ` = `."""
    return name.split(" = ", 1)[0].lstrip("%")


def is_collective(name: str) -> bool:
    return any(c in name for c in COLLECTIVES)


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


# ---------------------------------------------------------------------- #
# the wire format                                                        #
# ---------------------------------------------------------------------- #
def _varint(buf, i):
    v = shift = 0
    while True:
        b = buf[i]
        i += 1
        v |= (b & 0x7F) << shift
        if b < 0x80:
            return v, i
        shift += 7


def _fields(buf):
    """(field number, wire type, value) of one message: an int for a varint
    or a fixed width, a memoryview for a length-delimited field."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        field, wire = key >> 3, key & 7
        if wire == 0:
            v, i = _varint(buf, i)
        elif wire == 2:
            ln, i = _varint(buf, i)
            v = buf[i:i + ln]
            i += ln
        elif wire == 1:
            v = int.from_bytes(buf[i:i + 8], "little")
            i += 8
        elif wire == 5:
            v = int.from_bytes(buf[i:i + 4], "little")
            i += 4
        else:
            raise ValueError(f"wire type {wire} at byte {i}: not an xplane")
        yield field, wire, v


def _text(v) -> str:
    return bytes(v).decode("utf-8", "replace")


def _signed(v: int) -> int:
    return v - (1 << 64) if v >= (1 << 63) else v


def _stat(buf):
    """(stat metadata id, value, is a reference) of one XStat."""
    key, value, ref = 0, None, False
    for f, _w, v in _fields(buf):
        if f == 1:
            key = v
        elif f == 2:
            value = struct.unpack("<d", v.to_bytes(8, "little"))[0]
        elif f == 3:
            value = v
        elif f == 4:
            value = _signed(v)
        elif f == 5:
            value = _text(v)
        elif f == 7:
            value, ref = v, True
    return key, value, ref


def _event_metadata(buf):
    out = {"id": 0, "name": "", "display_name": "", "stats": []}
    for f, _w, v in _fields(buf):
        if f == 1:
            out["id"] = v
        elif f == 2:
            out["name"] = _text(v)
        elif f == 4:
            out["display_name"] = _text(v)
        elif f == 5:
            out["stats"].append(_stat(v))
    return out


def _map_entry(buf):
    key, value = 0, b""
    for f, _w, v in _fields(buf):
        if f == 1:
            key = v
        elif f == 2:
            value = v
    return key, value


def _line(buf):
    name, t0, events = "", 0, []
    for f, _w, v in _fields(buf):
        if f == 2:
            name = _text(v)
        elif f == 3:
            t0 = _signed(v)
        elif f == 4:
            mid = off = dur = 0
            for g, _x, u in _fields(v):
                if g == 1:
                    mid = u
                elif g == 2:
                    off = u
                elif g == 3:
                    dur = u
            events.append((mid, off, dur))
    return name, t0, events


def load_planes(path: str) -> dict:
    """{plane name: {line name: [(operation name, start_ns, duration_ns,
    meta)]}}; `meta` holds the event metadata's kept stats (`META_KEPT`, one
    dict per metadata entry, shared by its events)."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    planes = {}
    for f, _w, pbuf in _fields(space):
        if f != 1:
            continue
        pname, lines, emeta, smeta = "", [], {}, {}
        for g, _x, v in _fields(pbuf):
            if g == 2:
                pname = _text(v)
            elif g == 3:
                lines.append(v)
            elif g == 4:
                k, mbuf = _map_entry(v)
                emeta[k] = _event_metadata(mbuf)
            elif g == 5:
                k, mbuf = _map_entry(v)
                smeta[k] = next((_text(u) for h, _y, u in _fields(mbuf)
                                 if h == 2), "")
        metas = {}
        for k, m in emeta.items():
            kept = {}
            for sk, value, ref in m["stats"]:
                sname = smeta.get(sk, "")
                if sname in META_KEPT:
                    kept[sname] = smeta.get(value, "") if ref else value
            metas[k] = (op_name(m["name"]), kept)
        out = planes.setdefault(pname, {})
        for lbuf in lines:
            lname, t0, events = _line(lbuf)
            into = out.setdefault(lname, [])
            for mid, off, dur in events:
                name, kept = metas.get(mid, ("", {}))
                into.append((name, float(t0 + off // 1000),
                             float(dur // 1000), kept))
    return planes


# ---------------------------------------------------------------------- #
# names                                                                  #
# ---------------------------------------------------------------------- #
def scope_of(tf_op: str, scopes=SCOPES):
    """(scope, direction) of an operation's `tf_op`."""
    path = (tf_op or "").rsplit(":", 1)[0]
    direction = "bwd" if "transpose(" in path else "fwd"
    for part in reversed(path.split("/")):
        while True:
            if part in scopes:
                return part, direction
            m = _WRAPPED.match(part)
            if not m:
                break
            part = m.group(1)
    return UNSCOPED, direction


def program_names(modules) -> dict:
    """{program_id: name} from the `XLA Modules` line's event names
    (`jit_train_step(1234)`)."""
    out = {}
    for ev in modules:
        m = _PROGRAM.match(ev[0])
        if m:
            out[int(m.group(2))] = m.group(1)
    return out


# ---------------------------------------------------------------------- #
# the reduction                                                          #
# ---------------------------------------------------------------------- #
def self_time_per_event(events):
    """[(event, self ns, encloses others)] for the events of ONE line, where
    an event may enclose later ones (a `while` around its body)."""
    evs = sorted((e for e in events if e[2] > 0),
                 key=lambda e: (e[1], -e[2]))
    own = [e[2] for e in evs]
    parent_of = [False] * len(evs)
    stack = []                                   # (end, index)
    for i, e in enumerate(evs):
        while stack and stack[-1][0] <= e[1]:
            stack.pop()
        if stack and e[1] + e[2] <= stack[-1][0] + 1:
            own[stack[-1][1]] -= e[2]
            parent_of[stack[-1][1]] = True
        stack.append((e[1] + e[2], i))
    return [(e, max(t, 0.0), p) for e, t, p in zip(evs, own, parent_of)]


def host_spans(planes, prefixes=SPAN_PREFIXES):
    spans = []
    for pname, lines in planes.items():
        if pname.startswith("/host:"):
            for evs in lines.values():
                spans.extend(ev for ev in evs if ev[0].startswith(prefixes))
    return spans


def gap_owner(gap, spans):
    """The innermost span over a gap: of those that cover at least half of
    it, the program's (`ht.`) before the benchmark's (`pb.`), which call into
    it and, where the program has a thread of its own, only wait beside it;
    within a kind the shortest; the window span only when nothing inside it
    qualifies."""
    need = 0.5 * (gap[1] - gap[0])
    best, best_rank = "none", None
    for ev in spans:
        name, s, d = ev[0], ev[1], ev[2]
        if min(gap[1], s + d) - max(gap[0], s) < need:
            continue
        rank = (name == WINDOW_SPAN, not name.startswith("ht."), d)
        if best_rank is None or rank < best_rank:
            best, best_rank = name, rank
    return best


def _clip_events(events, lo, hi):
    return [(e[0], max(e[1], lo), min(e[1] + e[2], hi) - max(e[1], lo), e[3])
            for e in events if min(e[1] + e[2], hi) > max(e[1], lo)]


def device_lines(planes, chips, rehearse=False):
    """[(device id, {line name: events})] of the first `chips` devices. A
    rehearsal's trace has no device plane: the XLA:CPU worker threads of the
    host plane stand in as device 0."""
    found = sorted((int(m.group(1)), lines) for m, lines in
                   ((_DEVICE_PLANE.match(p), ln) for p, ln in planes.items())
                   if m and OPS_LINE in lines)
    if not found and rehearse:
        cpu = [ev for lname, evs in planes.get("/host:CPU", {}).items()
               if lname.startswith(_CPU_WORKER) for ev in evs if ev[2] > 0]
        if cpu:
            found = [(0, {OPS_LINE: cpu})]
    if len(found) < (1 if rehearse else chips):
        raise ValueError(f"the trace holds {len(found)} device planes with an "
                         f"{OPS_LINE!r} line, the cell runs on {chips}: "
                         f"{sorted(planes)}")
    return found[:chips]


def reduce_planes(planes: dict, chips: int = 1, scopes=SCOPES,
                  rehearse: bool = False) -> dict:
    """Inside the `pb.window` span: busy time over the chips used, and device
    0 by name. Times in seconds. Raises where the trace has no window span,
    too few device planes or no operation on a device inside the window."""
    spans = host_spans(planes)
    win = [ev for ev in spans if ev[0] == WINDOW_SPAN]
    if not win:
        raise ValueError(f"the trace holds no {WINDOW_SPAN!r} span")
    w0 = min(ev[1] for ev in win)
    w1 = max(ev[1] + ev[2] for ev in win)
    found = device_lines(planes, chips, rehearse)
    _dev, lines0 = found[0]
    names = program_names(lines0.get(MODULES_LINE, ()))
    ops = _clip_events(lines0[OPS_LINE], w0, w1)
    ns = 1e-9

    by_scope = {}
    for ev, own, encloses in self_time_per_event(ops):
        meta = ev[3]
        scope, direction = scope_of(meta.get("tf_op"), scopes)
        program = names.get(meta.get("program_id"), "unknown")
        row = by_scope.setdefault((program, scope, direction), {
            "self_s": 0.0, "xla_flops": 0, "xla_bytes_accessed": 0, "ops": {}})
        row["self_s"] += own * ns
        row["ops"][ev[0]] = row["ops"].get(ev[0], 0.0) + own * ns
        if not encloses:
            row["xla_flops"] += int(meta.get("flops") or 0)
            row["xla_bytes_accessed"] += int(meta.get("bytes_accessed") or 0)

    programs = {}
    for ev in _clip_events(lines0.get(MODULES_LINE, ()), w0, w1):
        m = _PROGRAM.match(ev[0])
        row = programs.setdefault(m.group(1) if m else ev[0],
                                  {"runs": 0, "device_s": 0.0})
        row["runs"] += 1
        row["device_s"] += ev[2] * ns

    busy0 = union([(e[1], e[1] + e[2]) for e in ops])
    gaps, prev = [], w0
    for s, e in busy0:
        if s > prev:
            gaps.append((prev, s))
        prev = e
    if w1 > prev:
        gaps.append((prev, w1))
    idle = {}
    for g in gaps:
        owner = gap_owner(g, spans)
        idle[owner] = idle.get(owner, 0.0) + (g[1] - g[0]) * ns

    busy, collectives = {}, {}
    for dev, lines in found:
        dops = _clip_events(lines[OPS_LINE], w0, w1)
        busy[str(dev)] = length(union([(e[1], e[1] + e[2])
                                       for e in dops])) * ns
        coll = [e for e in dops if is_collective(e[0])]
        coll += [e for e in _clip_events(lines.get(ASYNC_LINE, ()), w0, w1)
                 if is_collective(e[0])]
        c = union([(e[1], e[1] + e[2]) for e in coll])
        other = union([(e[1], e[1] + e[2]) for e, _own, encloses
                       in self_time_per_event(dops)
                       if not encloses and not is_collective(e[0])])
        hidden = sum(length(clip(other, s, e)) for s, e in c)
        collectives[str(dev)] = {"total_s": length(c) * ns,
                                 "hidden_s": hidden * ns,
                                 "exposed_s": (length(c) - hidden) * ns}
    if not any(busy.values()):
        raise ValueError("no operation ran on a device inside the window")
    return {
        "window_s": (w1 - w0) * ns,
        "busy_s": sum(busy.values()) / len(busy),
        "busy_s_by_device": busy,
        "device0_busy_s": length(busy0) * ns,
        "by_scope": by_scope,
        "programs": programs,
        "idle_gaps": dict(sorted(idle.items(), key=lambda kv: -kv[1])),
        "collectives": collectives,
    }


def idle_share_percent(reduction: dict) -> float:
    """1 - busy / window of a reduction, mean over the chips used, in %."""
    return 100.0 * (1.0 - reduction["busy_s"] / reduction["window_s"])


def scope_shares(reduction: dict) -> dict:
    """{scope: share of device 0's self time in the window}, directions and
    programs summed: what the 90% of ISSUE 29 is read from."""
    total = sum(r["self_s"] for r in reduction["by_scope"].values()) or 1.0
    out = {}
    for (_p, scope, _d), row in reduction["by_scope"].items():
        out[scope] = out.get(scope, 0.0) + row["self_s"] / total
    return dict(sorted(out.items(), key=lambda kv: -kv[1]))


def scope_seconds(reduction: dict, scope: str, program: str = None,
                  op_prefix: str = "") -> float:
    """Device 0's self time under `scope` (every direction; one program or
    all), optionally only of operations whose name starts with `op_prefix`."""
    total = 0.0
    for (prog, sc, _d), row in reduction["by_scope"].items():
        if sc != scope or (program is not None and prog != program):
            continue
        total += (sum(t for n, t in row["ops"].items()
                      if n.startswith(op_prefix))
                  if op_prefix else row["self_s"])
    return total


def reduce_file(path: str, chips: int = 1, rehearse: bool = False) -> dict:
    return reduce_planes(load_planes(path), chips, rehearse=rehearse)
