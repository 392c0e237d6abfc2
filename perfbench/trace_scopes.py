"""From the profiler's `.xplane.pb` to device time BY NAME, with nothing but
the standard library (`trace_reduce.py` stays as it is; its helpers are used).

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
whole nanoseconds as `ProfileData` gives them, so that both reductions agree.

The reduction (`reduce_planes`), all on device 0 inside the `pb.window` span:

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
"""

from __future__ import annotations

import re
import struct

from perfbench import trace_reduce as tr

SPAN_PREFIXES = ("ht.", "pb.")
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
_WRAPPED = re.compile(r"^\w+\((.*)\)$")
_PROGRAM = re.compile(r"^(.*)\((\d+)\)$")


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
    meta)]}}: `trace_reduce.load_planes`' form with the event metadata's kept
    stats (`META_KEPT`, one dict per metadata entry, shared by its events) as
    a fourth member."""
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
            metas[k] = (tr.op_name(m["name"]), kept)
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
        rank = (name == tr.WINDOW_SPAN, not name.startswith("ht."), d)
        if best_rank is None or rank < best_rank:
            best, best_rank = name, rank
    return best


def _clip_events(events, lo, hi):
    return [(e[0], max(e[1], lo), min(e[1] + e[2], hi) - max(e[1], lo), e[3])
            for e in events if min(e[1] + e[2], hi) > max(e[1], lo)]


def reduce_planes(planes: dict, chips: int = 1, scopes=SCOPES) -> dict:
    """Device 0 inside the `pb.window` span, by name. Times in seconds."""
    spans = host_spans(planes)
    win = [ev for ev in spans if ev[0] == tr.WINDOW_SPAN]
    if not win:
        raise ValueError(f"the trace holds no {tr.WINDOW_SPAN!r} span")
    w0 = min(ev[1] for ev in win)
    w1 = max(ev[1] + ev[2] for ev in win)
    found = sorted((int(m.group(1)), lines) for m, lines in
                   ((tr._DEVICE_PLANE.match(p), l) for p, l in planes.items())
                   if m and tr.OPS_LINE in lines)
    if len(found) < chips:
        raise ValueError(f"the trace holds {len(found)} device planes with an "
                         f"{tr.OPS_LINE!r} line, asked for {chips}")
    _dev, lines0 = found[0]
    names = program_names(lines0.get(MODULES_LINE, ()))
    ops = _clip_events(lines0[tr.OPS_LINE], w0, w1)
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

    busy = tr.union([(e[1], e[1] + e[2]) for e in ops])
    gaps, prev = [], w0
    for s, e in busy:
        if s > prev:
            gaps.append((prev, s))
        prev = e
    if w1 > prev:
        gaps.append((prev, w1))
    idle = {}
    for g in gaps:
        owner = gap_owner(g, spans)
        idle[owner] = idle.get(owner, 0.0) + (g[1] - g[0]) * ns

    collectives = {}
    for dev, lines in found[:chips]:
        dops = _clip_events(lines[tr.OPS_LINE], w0, w1)
        coll = [e for e in dops if tr.is_collective(e[0])]
        coll += [e for e in _clip_events(lines.get(ASYNC_LINE, ()), w0, w1)
                 if tr.is_collective(e[0])]
        c = tr.union([(e[1], e[1] + e[2]) for e in coll])
        other = tr.union([(e[1], e[1] + e[2]) for e, _own, encloses
                          in self_time_per_event(dops)
                          if not encloses and not tr.is_collective(e[0])])
        hidden = sum(tr.length(tr.clip(other, s, e)) for s, e in c)
        collectives[str(dev)] = {"total_s": tr.length(c) * ns,
                                 "hidden_s": hidden * ns,
                                 "exposed_s": (tr.length(c) - hidden) * ns}
    return {
        "window_s": (w1 - w0) * ns,
        "device0_busy_s": tr.length(busy) * ns,
        "by_scope": by_scope,
        "programs": programs,
        "idle_gaps": dict(sorted(idle.items(), key=lambda kv: -kv[1])),
        "collectives": collectives,
    }


def scope_shares(reduction: dict) -> dict:
    """{scope: share of device 0's self time in the window}, directions and
    programs summed: what the 90% of ISSUE 29 is read from."""
    total = sum(r["self_s"] for r in reduction["by_scope"].values()) or 1.0
    out = {}
    for (_p, scope, _d), row in reduction["by_scope"].items():
        out[scope] = out.get(scope, 0.0) + row["self_s"] / total
    return dict(sorted(out.items(), key=lambda kv: -kv[1]))


def reduce_file(path: str, chips: int = 1) -> dict:
    return reduce_planes(load_planes(path), chips)
