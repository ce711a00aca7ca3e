"""Minimal pure-python reader for XLA profiler traces (xplane.pb).

`jax.profiler.trace` writes TensorBoard-format `*.xplane.pb` files, but
the usual consumers (tensorboard_plugin_profile + a matching tensorflow
pywrap build) are version-locked and broken on this box. The XSpace
schema is stable and tiny, and protobuf wire format skips unknown
fields, so this module decodes just the subset an op-level summary
needs: planes -> lines -> events, with per-plane event-metadata names.

Field numbers follow tsl/profiler/protobuf/xplane.proto:
  XSpace.planes=1; XPlane.name=2 .lines=3 .event_metadata=4(map)
  .stat_metadata=5(map) .stats=6;
  XLine.name=2 .timestamp_ns=3 .events=4;
  XEvent.metadata_id=1 .offset_ps=2 .duration_ps=3;
  XEventMetadata(map value).id=1 .name=2 .display_name=4 .stats=5;
  XStat.metadata_id=1 .uint64_value=3 .int64_value=4 .str_value=5.

Timestamps: an event's absolute start is line.timestamp_ns +
event.offset_ps/1000. Host work is put on that clock by the program
itself: the engine and trainer loops' phases are
`jax.profiler.TraceAnnotation`s (utils/profiling.PhaseClock), so a
capture holds them in its host plane beside the device's ops. Device
work is named by the program too: an `XLA Ops` event's metadata
carries the HLO `op_name` (its OP_NAME_STAT), the path of
`jax.named_scope`s the op was traced under, which `scope_seconds`
folds onto the program's layer vocabulary
(utils/profiling.DEVICE_SCOPES).

No dependency on tensorflow or protobuf. Used by
utils/profiling.py (op_profile, the DeviceTimeSampler).
"""

from __future__ import annotations

import bisect
import glob
import os
import re
from dataclasses import dataclass, field


def _varint(buf: bytes, i: int) -> tuple[int, int]:
    shift = result = 0
    while True:
        b = buf[i]
        i += 1
        result |= (b & 0x7F) << shift
        if not b & 0x80:
            return result, i
        shift += 7


def _fields(buf: bytes):
    """Yield (field_number, wire_type, value) over a message buffer.
    value: int for varint/fixed, bytes for length-delimited."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        fnum, wtype = key >> 3, key & 7
        if wtype == 0:  # varint
            val, i = _varint(buf, i)
        elif wtype == 2:  # length-delimited
            ln, i = _varint(buf, i)
            if i + ln > n:  # short slice = mid-write truncation
                raise ValueError("length-delimited field runs off buffer")
            val = buf[i:i + ln]
            i += ln
        elif wtype == 5:  # 32-bit
            if i + 4 > n:
                raise ValueError("fixed32 field runs off buffer")
            val = int.from_bytes(buf[i:i + 4], "little")
            i += 4
        elif wtype == 1:  # 64-bit
            if i + 8 > n:
                raise ValueError("fixed64 field runs off buffer")
            val = int.from_bytes(buf[i:i + 8], "little")
            i += 8
        else:  # groups (3/4) do not occur in proto3 xplane
            raise ValueError(f"unsupported wire type {wtype}")
        yield fnum, wtype, val


@dataclass
class Event:
    name: str
    duration_ps: int
    offset_ps: int = 0  # start offset within the owning line
    # The HLO op_name of a device op: "jit(<program>)/jit(main)/..."
    # down the named scopes to the primitive; "" where the event's
    # metadata carries none (host events, compiler-made ops).
    op_name: str = ""


@dataclass
class Line:
    name: str
    events: list[Event] = field(default_factory=list)
    timestamp_ns: int = 0  # line start (unix epoch)


@dataclass
class Plane:
    name: str
    lines: list[Line] = field(default_factory=list)
    # Integer-valued plane stats (e.g. the "Task Environment" plane's
    # profile_start_time / profile_stop_time in epoch ns — the clock
    # anchor the span<->device join needs).
    stats: dict[str, int] = field(default_factory=dict)


# The stat of an `XLA Ops` event metadata that holds the HLO op_name
# (what the trace viewer shows as the op's framework name): on a TPU
# v5e under jax 0.9.0 a `str_value` that ends in ":" (seen by hand,
# PR 58; no capture showed it interned as a `ref_value`, or under
# another name, so neither is read).
OP_NAME_STAT = "tf_op"


def _parse_event(buf: bytes) -> tuple[int, int, int]:
    meta_id = dur = offset = 0
    for fnum, _, val in _fields(buf):
        if fnum == 1:
            meta_id = val
        elif fnum == 2:
            offset = val
        elif fnum == 3:
            dur = val
    return meta_id, dur, offset


def _parse_metadata_entry(buf: bytes) -> tuple[int, str, list]:
    """One map<int64, X{Event,Stat}Metadata> entry → (id, best name,
    the metadata's string-valued stats [(stat metadata id, str)])."""
    key, name, display, stats = 0, "", "", []
    for fnum, _, val in _fields(buf):
        if fnum == 1:
            key = val
        elif fnum == 2:
            for f2, _, v2 in _fields(val):
                if f2 == 2:
                    name = v2.decode("utf-8", "replace")
                elif f2 == 4:
                    display = v2.decode("utf-8", "replace")
                elif f2 == 5:
                    mid = sval = None
                    for f3, _, v3 in _fields(v2):
                        if f3 == 1:
                            mid = v3
                        elif f3 == 5:
                            sval = v3.decode("utf-8", "replace")
                    if mid is not None and sval is not None:
                        stats.append((mid, sval))
    return key, display or name, stats


def _parse_line(buf: bytes, names: dict[int, str],
                op_names: dict[int, str]) -> Line:
    line = Line(name="")
    for fnum, _, val in _fields(buf):
        if fnum == 2:
            line.name = val.decode("utf-8", "replace")
        elif fnum == 3:
            line.timestamp_ns = val
        elif fnum == 4:
            meta_id, dur, offset = _parse_event(val)
            line.events.append(
                Event(names.get(meta_id, str(meta_id)), dur, offset,
                      op_names.get(meta_id, ""))
            )
    return line


def _parse_plane(buf: bytes) -> Plane:
    name = ""
    metadata: dict[int, str] = {}
    meta_stats: dict[int, list] = {}
    stat_names: dict[int, str] = {}
    stat_vals: list[tuple[int, int]] = []  # (metadata_id, int value)
    line_bufs: list[bytes] = []
    for fnum, _, val in _fields(buf):
        if fnum == 2:
            name = val.decode("utf-8", "replace")
        elif fnum == 3:
            line_bufs.append(val)
        elif fnum == 4:
            k, v, stats = _parse_metadata_entry(val)
            metadata[k] = v
            if stats:
                meta_stats[k] = stats
        elif fnum == 5:
            k, v, _ = _parse_metadata_entry(val)
            stat_names[k] = v
        elif fnum == 6:
            mid = ival = None
            for f2, _, v2 in _fields(val):
                if f2 == 1:
                    mid = v2
                elif f2 in (3, 4):  # uint64 / int64 value
                    ival = v2
            if mid is not None and ival is not None:
                stat_vals.append((mid, ival))
    # The stat metadata may follow the event metadata in the buffer:
    # an event metadata's op_name is looked up once both are read.
    op_names: dict[int, str] = {}
    for k, stats in meta_stats.items():
        for mid, sval in stats:
            if stat_names.get(mid) == OP_NAME_STAT:
                op_names[k] = sval
    return Plane(
        name,
        [_parse_line(b, metadata, op_names) for b in line_bufs],
        {
            stat_names[mid]: v for mid, v in stat_vals
            if mid in stat_names
        },
    )


def parse_xspace(path: str) -> list[Plane]:
    """Raises ValueError (not IndexError) on a truncated/corrupt file —
    e.g. a profiler killed mid-write by a step timeout."""
    with open(path, "rb") as f:
        buf = f.read()
    try:
        return [
            _parse_plane(val) for fnum, _, val in _fields(buf) if fnum == 1
        ]
    except (IndexError, ValueError) as e:
        raise ValueError(f"truncated/corrupt xplane file: {path}") from e


def find_xplane_files(trace_dir: str) -> list[str]:
    return sorted(
        glob.glob(
            os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True
        )
    )


def op_totals(
    planes: list[Plane],
    plane_filter: str = "",
    line_filter: str = "",
) -> dict[str, int]:
    """Total duration_ps per event name over matching planes/lines.

    TPU device planes are named like '/device:TPU:0' with 'XLA Ops' /
    'XLA Modules' lines; pass plane_filter='TPU', line_filter='Ops' for
    a per-op device-time profile."""
    totals: dict[str, int] = {}
    for plane in planes:
        if plane_filter and plane_filter not in plane.name:
            continue
        for line in plane.lines:
            if line_filter and line_filter not in line.name:
                continue
            for ev in line.events:
                totals[ev.name] = totals.get(ev.name, 0) + ev.duration_ps
    return totals


def top_ops(
    planes: list[Plane], n: int = 25, **kw
) -> list[tuple[str, float]]:
    """Top-n (name, total_ms) by duration."""
    totals = op_totals(planes, **kw)
    ranked = sorted(totals.items(), key=lambda kv: -kv[1])[:n]
    return [(name, ps / 1e9) for name, ps in ranked]


# A transformation's mark on a component of an op_name:
# "transpose(jvp(attn))" is the backward of what was traced under
# "attn", and falls under its forward's layer.
_WRAPPED = re.compile(r"^(?:transpose|jvp|vmap|checkpoint|remat\w*)\((.*)\)$")
_PROGRAM = re.compile(r"^jit\(([^)]*)\)")
_FINGERPRINT = re.compile(r"\(\d+\)$")
UNSCOPED = "unscoped"


def scope_of(op_name: str, vocabulary, below=()) -> str:
    """The scope path of one HLO op_name: the first of its components
    that is a name of `vocabulary` (the program's top-level layers) and,
    where one follows, the DEEPEST later component that is a name of
    `below` (the scopes inside a layer), joined by "/":
    ".../while/body/attn/mla/dsa_attend/dot_general" is
    "attn/dsa_attend". Components are compared with their
    transformation marks (_WRAPPED) taken off. UNSCOPED where no
    component is in the vocabulary, or there is no op_name."""
    top = sub = None
    for comp in op_name.split("/"):
        while (m := _WRAPPED.match(comp)):
            comp = m.group(1)
        if top is None:
            if comp in vocabulary:
                top = comp
        elif comp in below:
            sub = comp
    if top is None:
        return UNSCOPED
    return top if sub is None else top + "/" + sub


def _self_ps(line: Line):
    """(event, SELF picoseconds) per event of one line: its duration
    less that of the events nested directly inside it. The `XLA Ops`
    line nests (a `while` holds its body's ops, a level or more deep),
    so summed durations would count a loop once a level; self times add
    up to the line's busy time (the rule benchmark/trace.self_times
    states)."""
    out, stack = [], []  # stack of [end, event, self]
    for ev in sorted(line.events,
                     key=lambda e: (e.offset_ps, -e.duration_ps)):
        while stack and stack[-1][0] <= ev.offset_ps:
            _, done, self_ps = stack.pop()
            out.append((done, self_ps))
        if stack:
            stack[-1][2] -= ev.duration_ps
        stack.append([ev.offset_ps + ev.duration_ps, ev, ev.duration_ps])
    out.extend((ev, self_ps) for _, ev, self_ps in stack)
    return out


def scope_seconds(planes: list[Plane], vocabulary, below=()) -> dict:
    """{program: {scope path: [self seconds, count]}}: the device's time
    by the program's own layer names. SELF time (`_self_ps`) of every
    event on each `/device:TPU:` plane's `XLA Ops` line, averaged over
    the chips, under the event's `scope_of` path; `program` is the
    `jit(<name>)` that opens the op_name, or, for an op without one (a
    copy the compiler made), the `XLA Modules` event it ran inside. A
    program's paths add up to its busy time; {} where the capture has
    no device plane. A fusion carries ONE op_name, its root's: what
    was fused into it from a neighbouring scope is counted with the
    root."""
    devs = [p for p in planes if p.name.startswith("/device:TPU:")]
    out: dict = {}
    for plane in devs:
        lines = {ln.name: ln for ln in plane.lines}
        if "XLA Ops" not in lines:
            continue
        modules = sorted(
            (ev.offset_ps, ev.offset_ps + ev.duration_ps,
             _FINGERPRINT.sub("", ev.name).removeprefix("jit_"))
            for ev in getattr(lines.get("XLA Modules"), "events", ()))
        starts = [m[0] for m in modules]
        shift = 0  # the two lines' own timestamps may differ
        if modules:
            shift = (lines["XLA Ops"].timestamp_ns
                     - lines["XLA Modules"].timestamp_ns) * 1000
        for ev, self_ps in _self_ps(lines["XLA Ops"]):
            m = _PROGRAM.match(ev.op_name)
            if m:
                program = m.group(1)
            else:
                at = ev.offset_ps + shift
                i = bisect.bisect_right(starts, at) - 1
                program = (modules[i][2]
                           if i >= 0 and at < modules[i][1] else "")
            cell = out.setdefault(program, {}).setdefault(
                scope_of(ev.op_name, vocabulary, below), [0, 0])
            cell[0] += self_ps
            cell[1] += 1
    n = max(1, len(devs))
    return {
        program: {path: [ps / n / 1e12, count / n]
                  for path, (ps, count) in paths.items()}
        for program, paths in out.items()
    }


# Line timestamps below this are clearly not unix-epoch ns (10**15 ns
# past 1970 is mid-2001; any real wall clock is ~1.7e18): such a
# timeline is relative to some process-local clock and needs aligning.
_EPOCH_THRESHOLD_NS = 10**15


def profile_start_time_ns(planes: list[Plane]) -> int:
    """Epoch-ns start of the profiler session, from the "Task
    Environment" plane's stats (0 when absent). Relative line
    timestamps are offsets from this instant."""
    for plane in planes:
        if (t := plane.stats.get("profile_start_time", 0)):
            return t
    return 0


def _plane_shift_ns(plane: Plane, session_end_ns: int) -> int:
    """Fallback alignment shift for a relative-timeline plane in a
    file with no profile_start_time stat. Anchor on the trace END:
    every event a profiler session records ends at or before
    stop_trace, and the last one (thread/session-lifetime events
    included) ends AT it — so `session_end_ns - max(event end)` maps
    the plane's timeline onto the wall clock to within the stop_trace
    teardown latency (~ms)."""
    max_end = 0
    for line in plane.lines:
        for ev in line.events:
            end = line.timestamp_ns + (
                ev.offset_ps + ev.duration_ps
            ) // 1000
            max_end = max(max_end, end)
    return session_end_ns - max_end


def merge_intervals(
    intervals: list[tuple[int, int]]
) -> list[tuple[int, int]]:
    """Sorted DISJOINT union of [start_ns, end_ns) intervals — busy
    time, not summed durations, so nested/overlapping events (host
    python stacks, fused op sub-events) can never count the same wall
    nanosecond twice."""
    if not intervals:
        return []
    intervals = sorted(intervals)
    out = [intervals[0]]
    for s, e in intervals[1:]:
        cs, ce = out[-1]
        if s > ce:
            out.append((s, e))
        elif e > ce:
            out[-1] = (cs, e)
    return out


def clipped_us(merged: list[tuple[int, int]], t0_ns: int,
               t1_ns: int) -> int:
    """Microseconds of already-merged intervals inside [t0, t1) — the
    per-window clip, O(len(merged)), run against one precomputed
    merge for any number of windows."""
    total = 0
    for s, e in merged:
        lo, hi = max(s, t0_ns), min(e, t1_ns)
        if hi > lo:
            total += hi - lo
    return total // 1000


def busiest_line_spans(
    planes: list[Plane],
    plane_filter: str = "",
    line_filter: str = "",
    line_exclude: str = "",
    session_end_ns: int = 0,
    event_exclude: str = "",
) -> list[tuple[int, int]]:
    """The merged busy intervals (epoch ns) of the BUSIEST matching
    line — precomputed ONCE per capture; per-window attribution is
    then a cheap clip (utils/profiling.attribute_capture runs up to
    hundreds of windows on the engine thread, so a per-window rescan
    of every event would stall the dispatch loop).

    One line = one execution stream (a TPU core's 'XLA Ops' line, a
    host thread), so the per-line interval union is genuine busy time
    and an in-window clip can never exceed the window. Taking the
    busiest line (rather than summing lines) keeps the host-event
    fallback honest — host captures carry one line per python thread
    and summing them would charge idle threads' tracer overhead as
    device time. Clock alignment: epoch timestamps pass through,
    relative planes anchor on the file's own profile_start_time stat,
    else on session_end_ns. Events whose name starts with
    `event_exclude` are left out: the program's own phase annotations
    (`oryx.`, utils/profiling.PhaseClock) tile their thread's line and
    are not work the runtime did."""
    start_anchor = profile_start_time_ns(planes)
    best: list[tuple[int, int]] = []
    best_total = 0
    for plane in planes:
        if plane_filter and plane_filter not in plane.name:
            continue
        relative = any(
            line.timestamp_ns < _EPOCH_THRESHOLD_NS
            for line in plane.lines if line.events
        )
        shift = 0
        if relative:
            shift = start_anchor or _plane_shift_ns(
                plane, session_end_ns
            )
        for line in plane.lines:
            if line_filter and line_filter not in line.name:
                continue
            if line_exclude and line_exclude in line.name:
                continue
            base = line.timestamp_ns + shift
            merged = merge_intervals([
                (base + ev.offset_ps // 1000,
                 base + (ev.offset_ps + ev.duration_ps) // 1000)
                for ev in line.events
                if not (event_exclude
                        and ev.name.startswith(event_exclude))
            ])
            total = sum(e - s for s, e in merged)
            if total > best_total:
                best, best_total = merged, total
    return best


def busy_time_us(
    planes: list[Plane],
    t0_ns: int,
    t1_ns: int,
    plane_filter: str = "",
    line_filter: str = "",
    line_exclude: str = "",
    session_end_ns: int = 0,
) -> tuple[int, int]:
    """(busy_us inside [t0_ns, t1_ns), busy_us over the whole capture)
    on the busiest matching line — the one-window convenience over
    busiest_line_spans (multi-window callers precompute the spans and
    clip per window instead)."""
    merged = busiest_line_spans(
        planes, plane_filter=plane_filter, line_filter=line_filter,
        line_exclude=line_exclude, session_end_ns=session_end_ns,
    )
    return (
        clipped_us(merged, t0_ns, t1_ns),
        sum(e - s for s, e in merged) // 1000,
    )


def chrome_trace(planes: list[Plane], limit: int = 50000) -> dict:
    """Chrome trace-event JSON from parsed planes — loads directly in
    Perfetto / chrome://tracing (the GET /debug/profile response body).
    Planes become processes, lines become threads (named via metadata
    events); timestamps are each line's own clock in microseconds.
    `limit` caps the event count so one capture can never produce an
    unbounded response; the cap is reported when it bites."""
    events: list[dict] = []
    truncated = False
    for pi, plane in enumerate(planes):
        events.append({
            "name": "process_name", "ph": "M", "pid": pi, "tid": 0,
            "args": {"name": plane.name or f"plane {pi}"},
        })
        for li, line in enumerate(plane.lines):
            events.append({
                "name": "thread_name", "ph": "M", "pid": pi, "tid": li,
                "args": {"name": line.name or f"line {li}"},
            })
            base_us = line.timestamp_ns / 1e3
            for ev in line.events:
                if len(events) >= limit:
                    truncated = True
                    break
                events.append({
                    "name": ev.name, "ph": "X",
                    "ts": base_us + ev.offset_ps / 1e6,
                    "dur": max(ev.duration_ps / 1e6, 1e-3),
                    "pid": pi, "tid": li,
                })
            if truncated:
                break
        if truncated:
            break
    return {
        "traceEvents": events,
        "displayTimeUnit": "ms",
        "truncated": truncated,
    }
