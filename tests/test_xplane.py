"""Dependency-free xplane.pb reader (utils/xplane.py): decode a
hand-encoded XSpace buffer with known planes/lines/events, and parse a
real trace written by jax.profiler on CPU."""

import os

import pytest

from oryx_tpu.utils import xplane


def _varint(n: int) -> bytes:
    out = b""
    while True:
        b = n & 0x7F
        n >>= 7
        if n:
            out += bytes([b | 0x80])
        else:
            return out + bytes([b])


def _field(fnum: int, wtype: int, payload: bytes | int) -> bytes:
    key = _varint(fnum << 3 | wtype)
    if wtype == 0:
        return key + _varint(payload)
    return key + _varint(len(payload)) + payload


def _event(meta_id: int, dur_ps: int) -> bytes:
    return _field(1, 0, meta_id) + _field(3, 0, dur_ps)


def _meta_entry(meta_id: int, name: str, display: str = "") -> bytes:
    inner = _field(1, 0, meta_id) + _field(2, 2, name.encode())
    if display:
        inner += _field(4, 2, display.encode())
    return _field(1, 0, meta_id) + _field(2, 2, inner)


def _line(name: str, events: list[bytes]) -> bytes:
    buf = _field(2, 2, name.encode())
    for e in events:
        buf += _field(4, 2, e)
    return buf


def _plane(name: str, lines: list[bytes], metas: list[bytes]) -> bytes:
    buf = _field(2, 2, name.encode())
    for ln in lines:
        buf += _field(3, 2, ln)
    for m in metas:
        buf += _field(4, 2, m)
    return buf


def test_parse_synthetic_xspace(tmp_path):
    plane = _plane(
        "/device:TPU:0",
        lines=[
            _line("XLA Ops", [_event(7, 1_000_000), _event(7, 2_000_000),
                              _event(8, 500_000)]),
            _line("XLA Modules", [_event(9, 9_000_000)]),
        ],
        metas=[
            _meta_entry(7, "fusion.1", display="matmul-fused"),
            _meta_entry(8, "copy.2"),
            _meta_entry(9, "jit_train_step"),
        ],
    )
    host = _plane("/host:CPU", lines=[_line("python", [])], metas=[])
    path = tmp_path / "test.xplane.pb"
    path.write_bytes(_field(1, 2, plane) + _field(1, 2, host))

    planes = xplane.parse_xspace(str(path))
    assert [p.name for p in planes] == ["/device:TPU:0", "/host:CPU"]
    ops = xplane.op_totals(planes, plane_filter="TPU", line_filter="Ops")
    # display_name preferred; repeats accumulate; other lines excluded.
    assert ops == {"matmul-fused": 3_000_000, "copy.2": 500_000}
    top = xplane.top_ops(planes, n=1, plane_filter="TPU", line_filter="Ops")
    assert top == [("matmul-fused", 3_000_000 / 1e9)]


def test_truncated_file_raises_valueerror(tmp_path):
    plane = _plane("/device:TPU:0", lines=[_line("XLA Ops", [_event(7, 5)])],
                   metas=[_meta_entry(7, "op")])
    buf = _field(1, 2, plane)
    path = tmp_path / "trunc.xplane.pb"
    path.write_bytes(buf[: len(buf) - 3])  # mid-write kill artifact
    with pytest.raises(ValueError, match="truncated"):
        xplane.parse_xspace(str(path))


@pytest.mark.slow
def test_parse_real_jax_trace(tmp_path):
    import jax
    import jax.numpy as jnp

    with jax.profiler.trace(str(tmp_path)):
        x = jnp.ones((64, 64))
        jax.device_get(jnp.sum(x @ x))

    files = xplane.find_xplane_files(str(tmp_path))
    assert files, os.listdir(tmp_path)
    planes = xplane.parse_xspace(files[-1])
    assert planes and any(p.lines for p in planes)
    # Something was recorded with a nonzero duration and a decoded name.
    totals = xplane.op_totals(planes)
    assert totals and max(totals.values()) > 0
    assert any(name and not name.isdigit() for name in totals)


@pytest.mark.slow
def test_op_profile_end_to_end(tmp_path):
    import jax
    import jax.numpy as jnp

    from oryx_tpu.utils import profiling

    f = jax.jit(lambda x: jnp.sum(x @ x))
    x = jnp.ones((64, 64))
    f(x)  # compile outside the trace
    prof = profiling.op_profile(
        f, x, trace_dir=str(tmp_path), steps=2, top_n=10,
    )
    assert prof.source in ("tpu_xla_ops", "host_fallback")
    assert prof.top and all(ms >= 0 for _, ms in prof.top)
    assert all(isinstance(name, str) and name for name, _ in prof.top)
    assert prof.xplane_path.endswith(".xplane.pb") and prof.plane_names


def _event_with_offset(meta_id: int, dur_ps: int, offset_ps: int) -> bytes:
    return (
        _field(1, 0, meta_id) + _field(2, 0, offset_ps)
        + _field(3, 0, dur_ps)
    )


def _line_with_ts(name: str, timestamp_ns: int,
                  events: list[bytes]) -> bytes:
    buf = _field(2, 2, name.encode()) + _field(3, 0, timestamp_ns)
    for e in events:
        buf += _field(4, 2, e)
    return buf


def test_empty_plane_parses(tmp_path):
    """A plane with no lines and no metadata (e.g. an idle device) must
    parse to an empty Plane, not crash or vanish."""
    path = tmp_path / "empty.xplane.pb"
    path.write_bytes(_field(1, 2, _plane("/device:TPU:9", [], [])))
    planes = xplane.parse_xspace(str(path))
    assert [p.name for p in planes] == ["/device:TPU:9"]
    assert planes[0].lines == []
    assert xplane.op_totals(planes) == {}
    assert xplane.top_ops(planes) == []


def test_unknown_fields_skipped(tmp_path):
    """Protobuf forward-compat: unknown field numbers across all wire
    types (varint, fixed32, fixed64, length-delimited) must be skipped
    at every nesting level, not corrupt the decode."""
    unknown = (
        _field(9, 0, 42)                                  # varint
        + _varint(13 << 3 | 5) + (99).to_bytes(4, "little")   # fixed32
        + _varint(14 << 3 | 1) + (7).to_bytes(8, "little")    # fixed64
        + _field(15, 2, b"future-submessage")             # length-delim
    )
    ev = _event(7, 1_000) + _field(11, 0, 5)
    line = _line("XLA Ops", [ev]) + unknown
    plane = _plane("/device:TPU:0", [line], [_meta_entry(7, "op.a")])
    plane += unknown
    path = tmp_path / "unknown.xplane.pb"
    path.write_bytes(_field(1, 2, plane) + unknown)
    planes = xplane.parse_xspace(str(path))
    assert xplane.op_totals(planes) == {"op.a": 1_000}


def test_truncated_varint_raises_valueerror(tmp_path):
    """A buffer ending mid-varint (continuation bit set forever) is a
    mid-write kill artifact: ValueError, never a raw IndexError."""
    path = tmp_path / "varint.xplane.pb"
    path.write_bytes(b"\x80\x80\x80")
    with pytest.raises(ValueError, match="truncated"):
        xplane.parse_xspace(str(path))


def test_event_offsets_and_line_timestamps(tmp_path):
    """The join inputs: XLine.timestamp_ns and XEvent.offset_ps decode
    (both default 0 for writers that omit them)."""
    line = _line_with_ts(
        "XLA Ops", 5_000,
        [_event_with_offset(7, 2_000_000, 1_000_000)],
    )
    plane = _plane("/device:TPU:0", [line], [_meta_entry(7, "op.a")])
    path = tmp_path / "ts.xplane.pb"
    path.write_bytes(_field(1, 2, plane))
    planes = xplane.parse_xspace(str(path))
    ln = planes[0].lines[0]
    assert ln.timestamp_ns == 5_000
    assert ln.events[0].offset_ps == 1_000_000
    assert ln.events[0].duration_ps == 2_000_000
    # Writers that omit them: defaults stay 0.
    old = _plane("/d", [_line("XLA Ops", [_event(7, 5)])],
                 [_meta_entry(7, "op.b")])
    path2 = tmp_path / "old.xplane.pb"
    path2.write_bytes(_field(1, 2, old))
    ln2 = xplane.parse_xspace(str(path2))[0].lines[0]
    assert ln2.timestamp_ns == 0 and ln2.events[0].offset_ps == 0


def test_attribute_device_time_midpoint_rule():
    """Events land in the window containing their midpoint; outside
    events land in _unattributed; empty windows still appear. Line
    timestamps here are epoch-scale (a TPU device plane), so no
    alignment shift applies."""
    T0 = 1_700_000_000_000_000_000  # epoch ns
    planes = [xplane.Plane("/device:TPU:0", [xplane.Line(
        "XLA Ops",
        events=[
            # offsets/durations in ps: a mid = T0+1_000ns,
            # b mid = T0+5_000ns, c mid = T0+91_000ns.
            xplane.Event("a", duration_ps=2_000_000, offset_ps=0),
            xplane.Event("b", duration_ps=2_000_000, offset_ps=4_000_000),
            xplane.Event("c", duration_ps=2_000_000, offset_ps=90_000_000),
        ],
        timestamp_ns=T0,
    )])]
    windows = [
        ("w1", T0, T0 + 2_000),          # catches a
        ("w2", T0 + 4_000, T0 + 6_000),  # catches b
        ("empty", T0 + 40_000, T0 + 41_000),
    ]
    got = xplane.attribute_device_time(
        planes, windows, plane_filter="TPU", line_filter="Ops"
    )
    assert got == {
        "w1": 2_000_000, "w2": 2_000_000, "empty": 0,
        "_unattributed": 2_000_000,
    }
    # Overlapping (here: identical) windows SPLIT the credit — the
    # scheduler stamps one shared decode dispatch on every live
    # request, so this is the normal live-join case; first-match-wins
    # would hand all device time to one request and zero to the rest.
    shared = [("r1", T0, T0 + 2_000), ("r2", T0, T0 + 2_000)]
    got2 = xplane.attribute_device_time(
        planes, shared, plane_filter="TPU", line_filter="Ops"
    )
    assert got2["r1"] == got2["r2"] == 1_000_000
    assert got2["_unattributed"] == 4_000_000


def test_attribute_device_time_relative_timeline_aligns_on_end():
    """A plane stamped with a process-local clock (tiny timestamps) is
    aligned by anchoring its last event end at session_end_ns."""
    T0 = 1_700_000_000_000_000_000
    planes = [xplane.Plane("/host:CPU", [xplane.Line(
        "python",
        events=[
            xplane.Event("step", duration_ps=2_000_000, offset_ps=0),
            # Last event ends at rel 10_000ns + (8e6+2e6)/1e3 ns = 20_000.
            xplane.Event("tail", duration_ps=2_000_000, offset_ps=8_000_000),
        ],
        timestamp_ns=10_000,  # clearly not epoch
    )])]
    # session end T0+20_000 -> shift maps rel 20_000 -> T0+20_000:
    # "step" mid rel 11_000 -> T0+11_000.
    got = xplane.attribute_device_time(
        planes, [("w", T0 + 10_000, T0 + 12_000)],
        session_end_ns=T0 + 20_000,
    )
    assert got == {"w": 2_000_000, "_unattributed": 2_000_000}
    # No anchor given: nothing lines up, everything lands unattributed
    # (reported, not silently dropped).
    got0 = xplane.attribute_device_time(
        planes, [("w", T0 + 10_000, T0 + 12_000)]
    )
    assert got0["w"] == 0 and got0["_unattributed"] == 4_000_000


def test_span_xplane_join_smoke(tmp_path):
    """CPU smoke of the capture_trace.py loop-closer: host spans from
    utils/trace.py joined against a REAL jax profiler trace — the
    recorded host-plane events must land inside the span windows (the
    clocks genuinely line up)."""
    import jax
    import jax.numpy as jnp

    from oryx_tpu.utils import trace as trace_lib

    f = jax.jit(lambda x: jnp.sum(x @ x))
    x = jnp.ones((128, 128))
    jax.device_get(f(x))  # compile outside the trace
    tracer = trace_lib.Tracer()
    tr = tracer.start_trace("profile", id="smoke")
    with jax.profiler.trace(str(tmp_path)):
        for _ in range(3):
            with tr.span("train_step"):
                jax.device_get(f(x))
    tr.finish()
    files = xplane.find_xplane_files(str(tmp_path))
    assert files
    planes = xplane.parse_xspace(files[-1])
    # The file is self-anchoring: the Task Environment plane's
    # profile_start_time stat (epoch ns) rebases relative timelines.
    assert xplane.profile_start_time_ns(planes) > 10**15
    windows = trace_lib.windows_from_traces([tr.to_dict()], "train_step")
    assert len(windows) == 3
    got = xplane.attribute_device_time(planes, windows)
    # EVERY step window catches device/host event time — the clocks
    # genuinely line up, not just approximately overlap.
    for label, _, _ in windows:
        assert got[label] > 0, got
