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
    assert prof.top and all(ms >= 0 for _, _, ms in prof.top)
    assert all(isinstance(name, str) and name for name, _, _ in prof.top)
    # Rows carry the scope path beside the compiler's name ("" for a
    # host event); the table is the device's, empty off a TPU.
    assert all(isinstance(path, str) for _, path, _ in prof.top)
    assert (prof.scopes == {}) == (prof.source == "host_fallback")
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
