"""utils/trace.py: span recording, context propagation, Chrome/JSONL
export, flight-recorder bounds, and the stall watchdog's
exactly-one-dump-per-stall contract."""

import io
import json
import threading
import time

import pytest

from oryx_tpu.utils import trace as trace_lib


def test_span_nesting_and_parents():
    tr = trace_lib.Trace("request", label="t")
    with tr.span("outer"):
        with tr.span("inner", detail=7):
            pass
        tr.event("marker")
    tr.add_complete("tail", trace_lib.now_ns(), 1000)
    names = [s.name for s in tr.spans]
    assert names == ["outer", "inner", "marker", "tail"]
    outer, inner, marker, tail = tr.spans
    assert outer.parent is None
    assert inner.parent == 0 and inner.args == {"detail": 7}
    assert marker.parent == 0 and marker.dur_ns == 0
    assert tail.parent is None and tail.dur_ns == 1000
    assert all(s.dur_ns is not None for s in tr.spans)
    assert inner.start_ns >= outer.start_ns


def test_cross_scope_begin_end_and_finish_closes_open_spans():
    tr = trace_lib.Trace("request")
    h = tr.begin("queue_wait")
    assert tr.spans[h].dur_ns is None  # still open
    tr.end(h)
    assert tr.spans[h].dur_ns is not None
    h2 = tr.begin("admission")
    tr.finish(finish_reason="stop")
    assert tr.done and tr.spans[h2].dur_ns is not None
    assert tr.meta["finish_reason"] == "stop"
    assert tr.summary()["done"] is True


def test_chrome_export_shape():
    tr = trace_lib.Trace("request", label="x")
    with tr.span("prefill", tokens=5):
        pass
    tr.finish()
    tracer = trace_lib.Tracer()
    body = tracer.chrome_trace([tr])
    events = body["traceEvents"]
    assert events, body
    xs = [e for e in events if e.get("ph") == "X"]
    assert len(xs) == 1 and xs[0]["name"] == "prefill"
    # Chrome trace-event required keys; ts/dur in microseconds.
    assert {"name", "cat", "ph", "ts", "dur", "pid", "tid"} <= set(xs[0])
    assert xs[0]["args"] == {"tokens": 5}
    json.dumps(body)  # loadable JSON


def test_flight_recorder_bound_and_lookup():
    tracer = trace_lib.Tracer(capacity=3)
    traces = [tracer.start_trace("request", id=f"r{i}") for i in range(5)]
    kept = [t["id"] for t in tracer.snapshot()]
    assert kept == ["r4", "r3", "r2"]  # newest first, oldest evicted
    assert tracer.get("r0") is None
    assert tracer.get("r4") is traces[4]
    # In-flight traces are visible before finish().
    assert tracer.snapshot()[0]["done"] is False
    # capacity 0 clamps to 1 instead of crashing the first start_trace.
    t0 = trace_lib.Tracer(capacity=0)
    t0.start_trace("request", id="a")
    t0.start_trace("request", id="b")
    assert [t["id"] for t in t0.snapshot()] == ["b"]


def test_contextvar_propagation_and_noop():
    tr = trace_lib.Trace("request")
    # Outside activate(): helpers are no-ops, not errors.
    with trace_lib.span("ignored"):
        pass
    trace_lib.add_complete("ignored", trace_lib.now_ns())
    assert trace_lib.current() is None
    with trace_lib.activate(tr):
        assert trace_lib.current() is tr
        with trace_lib.span("inside"):
            pass
        trace_lib.add_complete("chunk", trace_lib.now_ns())
    assert trace_lib.current() is None
    assert [s.name for s in tr.spans] == ["inside", "chunk"]
    # Threads don't inherit another thread's active trace.
    seen = []
    t = threading.Thread(target=lambda: seen.append(trace_lib.current()))
    with trace_lib.activate(tr):
        t.start()
        t.join()
    assert seen == [None]


def test_jsonl_roundtrip(tmp_path):
    tracer = trace_lib.Tracer()
    tr = tracer.start_trace("request", id="rid1")
    tr.add_complete("decode_chunk", 1_000, 500)
    tr.add_complete("decode_chunk", 2_000, 700)
    tr.add_complete("emission", 3_000, 10)
    tr.finish()
    path = tmp_path / "flight.jsonl"
    assert tracer.write_jsonl(str(path)) == 1
    (rec,) = [json.loads(line) for line in path.read_text().splitlines()]
    assert rec["id"] == "rid1" and rec["done"]
    assert [(s["name"], s["start_ns"], s["dur_ns"]) for s in rec["spans"]] == [
        ("decode_chunk", 1_000, 500), ("decode_chunk", 2_000, 700),
        ("emission", 3_000, 10),
    ]


def test_watchdog_one_dump_per_stall():
    tracer = trace_lib.Tracer()
    tr = tracer.start_trace("request", id="stuck1")
    tr.begin("decode_chunk")
    out = io.StringIO()
    wd = trace_lib.StallWatchdog(
        tracer, 0.15, name="test", out=out
    ).start()
    try:
        # Inactive: a missing beat is not a stall.
        time.sleep(0.4)
        assert wd.dumps == 0
        # Active with no beats: exactly ONE dump, however long it stalls.
        wd.set_active(True)
        deadline = time.monotonic() + 5
        while wd.dumps == 0 and time.monotonic() < deadline:
            time.sleep(0.02)
        assert wd.dumps == 1
        time.sleep(0.5)
        assert wd.dumps == 1  # still one: re-armed only by a beat
        text = out.getvalue()
        assert "STALL WATCHDOG" in text
        assert "stuck1" in text  # flight-recorder tail is in the dump
        assert "MainThread" in text  # thread stacks are in the dump
        # A beat re-arms; the next stall dumps again.
        wd.beat()
        deadline = time.monotonic() + 5
        while wd.dumps == 1 and time.monotonic() < deadline:
            time.sleep(0.02)
        assert wd.dumps == 2
    finally:
        wd.stop()


def test_watchdog_beats_prevent_dumps():
    wd = trace_lib.StallWatchdog(None, 0.2, name="test", out=io.StringIO())
    wd.start()
    try:
        wd.set_active(True)
        for _ in range(8):
            time.sleep(0.05)
            wd.beat()
        assert wd.dumps == 0
    finally:
        wd.stop()


def test_now_ns_monotone_and_anchored():
    a = trace_lib.now_ns()
    b = trace_lib.now_ns()
    assert b >= a
    # Anchored to the wall clock (needed for the xplane join).
    assert abs(a - time.time_ns()) < 60 * 1_000_000_000


def test_span_handle_resolves_under_concurrent_appends():
    """Regression (oryxlint lock-discipline self-application): span()
    used to chase its handle into the span list OUTSIDE the lock while
    other threads append — it must yield the right span, and keep
    doing so with writers running."""
    tr = trace_lib.Trace("req")
    stop = threading.Event()

    def appender():
        while not stop.is_set():
            tr.add_complete("noise", trace_lib.now_ns(), 10)

    workers = [threading.Thread(target=appender) for _ in range(2)]
    for w in workers:
        w.start()
    try:
        for i in range(200):
            with tr.span("work", i=i) as sp:
                assert sp.name == "work"
                assert sp.args == {"i": i}
    finally:
        stop.set()
        for w in workers:
            w.join()
    tr.finish()
    names = {s.name for s in tr.spans}
    assert names == {"noise", "work"}
