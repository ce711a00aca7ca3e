"""From a profiler trace to numbers: the benchmark's own reduction.

The wire-format reader below (`_varint` ... `find_xplane_files`,
`merge_intervals`) is a copy of `oryx_tpu/utils/xplane.py` as of PR 21,
kept here so that no later PR can change how a trace becomes a metric
(the original is listed in PERF.md for a later PR to delete or point
here). Field numbers follow tsl/profiler/protobuf/xplane.proto:
XSpace.planes=1; XPlane.name=2 .lines=3 .event_metadata=4 .stat_metadata=5
.stats=6; XLine.name=2 .timestamp_ns=3 .events=4; XEvent.metadata_id=1
.offset_ps=2 .duration_ps=3; XEventMetadata.id=1 .name=2 .display_name=4.

What a TPU v5e trace looks like (seen by hand, PR 23): one plane per
chip named `/device:TPU:<n>` whose lines include `XLA Modules` (one
event per executed jitted program, named `jit_<fn>(<fingerprint>)`)
and `XLA Ops` (one event per HLO op inside it; a Pallas kernel shows
under the name of its custom call), and host planes `/host:CPU` with a
line per thread carrying TraceAnnotation spans.
"""

from __future__ import annotations

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


def _parse_metadata_entry(buf: bytes) -> tuple[int, str]:
    """One map<int64, XEventMetadata> entry → (id, best name)."""
    key, name, display = 0, "", ""
    for fnum, _, val in _fields(buf):
        if fnum == 1:
            key = val
        elif fnum == 2:
            for f2, _, v2 in _fields(val):
                if f2 == 2:
                    name = v2.decode("utf-8", "replace")
                elif f2 == 4:
                    display = v2.decode("utf-8", "replace")
    return key, display or name


def _parse_line(buf: bytes, names: dict[int, str]) -> Line:
    line = Line(name="")
    for fnum, _, val in _fields(buf):
        if fnum == 2:
            line.name = val.decode("utf-8", "replace")
        elif fnum == 3:
            line.timestamp_ns = val
        elif fnum == 4:
            meta_id, dur, offset = _parse_event(val)
            line.events.append(
                Event(names.get(meta_id, str(meta_id)), dur, offset)
            )
    return line


def _parse_plane(buf: bytes) -> Plane:
    name = ""
    metadata: dict[int, str] = {}
    stat_names: dict[int, str] = {}
    stat_vals: list[tuple[int, int]] = []  # (metadata_id, int value)
    line_bufs: list[bytes] = []
    for fnum, _, val in _fields(buf):
        if fnum == 2:
            name = val.decode("utf-8", "replace")
        elif fnum == 3:
            line_bufs.append(val)
        elif fnum == 4:
            k, v = _parse_metadata_entry(val)
            metadata[k] = v
        elif fnum == 5:
            k, v = _parse_metadata_entry(val)
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
    return Plane(
        name,
        [_parse_line(b, metadata) for b in line_bufs],
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




def merge_intervals(intervals):
    """Sorted DISJOINT union of [start, end) intervals: busy time, not
    summed durations, so overlapping events never count a nanosecond
    twice."""
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


# --------------------------------------------------------------------------
# reduction
# --------------------------------------------------------------------------

DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
_FINGERPRINT = re.compile(r"\(\d+\)$")


def _abs_ps(line: Line, ev: Event) -> tuple[int, int]:
    s = line.timestamp_ns * 1000 + ev.offset_ps
    return s, s + ev.duration_ps


def device_planes(planes, plane_prefix: str = DEVICE_PLANE):
    return [p for p in planes if p.name.startswith(plane_prefix)]


def _line(plane: Plane, name: str):
    for ln in plane.lines:
        if ln.name == name:
            return ln
    return None


def self_times(line: Line):
    """[(name, self picoseconds)] per event of one line: an event's
    duration less that of the events nested directly inside it. The
    `XLA Ops` line nests — a `while` holds its body's ops, one level or
    more deep — so summed durations would count a loop's time once per
    level; self times add up to the line's busy time."""
    evs = sorted(
        ((ev.offset_ps, -ev.duration_ps, ev.name) for ev in line.events)
    )
    out, stack = [], []  # stack of [end, name, self]
    for start, neg, name in evs:
        dur = -neg
        while stack and stack[-1][0] <= start:
            e = stack.pop()
            out.append((e[1], e[2]))
        if stack:
            stack[-1][2] -= dur
        stack.append([start + dur, name, dur])
    while stack:
        e = stack.pop()
        out.append((e[1], e[2]))
    return out


def reduce_planes(planes, *, window_s: float, plane_prefix=DEVICE_PLANE,
                  ops_line=OPS_LINE, modules_line=MODULES_LINE,
                  host_spans=None) -> dict:
    """The numbers every per-layer reader works from:

    busy_s      union of device-op intervals, averaged over the chips
    window_s    length of the traced window (as given; reduce_dir gives
                the device planes' own extent)
    ops         {op name: [SELF seconds, count]} summed over chips / chips
                (self time: see self_times; a kernel or a fusion is a
                leaf, so its self time is its whole time)
    modules     {program name (fingerprint stripped): [seconds, count]}
    idle_gaps   the longest gaps between device ops on chip 0, each
                labelled with the host span that covers most of it
    """
    devs = device_planes(planes, plane_prefix)
    n = max(1, len(devs))
    ops: dict[str, list] = {}
    modules: dict[str, list] = {}
    busy_ps = 0
    gaps: list[tuple[int, int, int]] = []
    for di, plane in enumerate(devs):
        ln = _line(plane, ops_line)
        iv = []
        if ln is not None:
            for name, self_ps in self_times(ln):
                o = ops.setdefault(name, [0, 0])
                o[0] += self_ps
                o[1] += 1
            iv = [_abs_ps(ln, ev) for ev in ln.events]
        merged = merge_intervals(iv)
        busy_ps += sum(e - s for s, e in merged)
        if di == 0:
            for (s0, e0), (s1, e1) in zip(merged, merged[1:]):
                gaps.append((s1 - e0, e0, s1))
        lm = _line(plane, modules_line)
        if lm is not None:
            for ev in lm.events:
                m = modules.setdefault(_FINGERPRINT.sub("", ev.name), [0, 0])
                m[0] += ev.duration_ps
                m[1] += 1
    gaps.sort(reverse=True)
    spans = host_spans if host_spans is not None else host_span_list(planes)
    idle = {}
    for dur, s, e in gaps[:200]:
        label = _covering_span(spans, s, e)
        idle[label] = idle.get(label, 0) + dur
    return {
        "chips": len(devs),
        "busy_s": busy_ps / n / 1e12,
        "window_s": window_s,
        "ops": {k: [v[0] / n / 1e12, v[1] / n] for k, v in ops.items()},
        "modules": {k: [v[0] / n / 1e12, v[1] / n]
                    for k, v in modules.items()},
        "idle_gaps": sorted(
            ([k, v / 1e12] for k, v in idle.items()), key=lambda kv: -kv[1]
        )[:10],
        "planes": [
            [p.name, [[ln.name, len(ln.events)] for ln in p.lines][:12]]
            for p in planes
        ][:12],
    }


def host_span_list(planes, plane_prefix: str = "/host:"):
    """[(start_ps, end_ps, name)] of host-thread events (TraceAnnotation
    spans among them)."""
    out = []
    for p in planes:
        if not p.name.startswith(plane_prefix):
            continue
        for ln in p.lines:
            for ev in ln.events:
                if ev.duration_ps > 0:
                    s, e = _abs_ps(ln, ev)
                    out.append((s, e, ev.name))
    return out


def _covering_span(spans, s: int, e: int) -> str:
    """Name of the SHORTEST host span that covers at least half of the
    gap [s, e) — the innermost thing the host was doing then."""
    best, best_len = "unattributed", None
    need = (e - s) / 2
    for hs, he, name in spans:
        ov = min(e, he) - max(s, hs)
        if ov >= need and (best_len is None or he - hs < best_len):
            best, best_len = name, he - hs
    return best


def device_extent_s(planes, plane_prefix: str = DEVICE_PLANE) -> float:
    """Seconds from the first device event's start to the last one's
    end, over every line of every device plane; 0.0 where the capture
    holds no device event."""
    lo = hi = None
    for plane in device_planes(planes, plane_prefix):
        for ln in plane.lines:
            for ev in ln.events:
                s, e = _abs_ps(ln, ev)
                lo = s if lo is None else min(lo, s)
                hi = e if hi is None else max(hi, e)
    return 0.0 if lo is None else (hi - lo) / 1e12


def reduce_dir(trace_dir: str, *, window_s: float) -> dict:
    """reduce_planes over the capture in `trace_dir`, with ONE clock for
    the slice and what it is divided into: `window_s` is the device
    planes' own extent (device_extent_s), and the caller's `window_s`,
    the host's stamp around start_trace / stop_trace, is kept beside it
    as `host_window_s`. The stamp is the window only where the capture
    holds no device event (the CPU rehearsal).

    The capture and the stamp differ by some ms either side (start_trace
    has returned before the first stamp, stop_trace is called after the
    second), so against the stamp a device that idles less than that
    reads busy over its window. Against the extent it cannot: `busy_s`
    is the union of intervals that all lie inside [first start, last
    end], on every chip, so busy_s <= window_s, asserted here. What the
    extent leaves out is idle time before the first device event and
    after the last."""
    files = find_xplane_files(trace_dir)
    if not files:
        return {}
    planes = parse_xspace(files[-1])
    extent = device_extent_s(planes)
    out = reduce_planes(planes, window_s=extent or window_s)
    out["host_window_s"] = window_s
    if extent:
        assert out["busy_s"] <= out["window_s"], (
            f"trace.py: busy_s {out['busy_s']} outside the device "
            f"extent {out['window_s']}")
    return out


def match_seconds(table: dict, patterns) -> tuple[float, float]:
    """(seconds, count) summed over entries of an `ops` / `modules`
    table whose name contains any of `patterns`."""
    sec = cnt = 0.0
    for name, (s, c) in table.items():
        if any(p in name for p in patterns):
            sec += s
            cnt += c
    return sec, cnt


def breakdown(trace: dict) -> dict:
    ops = sorted(trace.get("ops", {}).items(), key=lambda kv: -kv[1][0])
    return {
        "device_ops": [[k, v[0]] for k, v in ops[:10]],
        "idle_gaps": trace.get("idle_gaps", [])[:10],
    }
