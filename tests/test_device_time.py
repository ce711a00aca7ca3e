"""Continuous device-time attribution (utils/profiling.DeviceTimeSampler
+ utils/xplane busy-union helpers): synthetic-plane unit tests pinning
kind bucketing and the interval-union math, the sampling cadence
(0=off, every-Nth), the capture-failure degradation contract (a labeled
counter, never a crashed engine step), and a CPU smoke joining a real
jax.profiler capture to live timeline records with byte parity and the
one-dispatch invariant untouched."""

import threading

import pytest

import jax

from oryx_tpu import config as cfg_lib
from oryx_tpu.models import oryx
from oryx_tpu.serve.pipeline import OryxInference
from oryx_tpu.serve.scheduler import ContinuousScheduler
from oryx_tpu.utils import profiling, xplane
from oryx_tpu.utils.metrics import Registry, ServingMetrics
from oryx_tpu.utils.profiling import DeviceTimeSampler, \
    attribute_capture
from oryx_tpu.utils.xplane import Event, Line, Plane, busy_time_us


class FakeTokenizer:
    def encode(self, text, add_special_tokens=False):
        return [min(ord(c), 500) for c in text]

    def decode(self, ids, skip_special_tokens=True):
        return "".join(chr(i) for i in ids if 0 < i < 500)


@pytest.fixture(scope="module")
def pipe():
    cfg = cfg_lib.oryx_tiny()
    params = oryx.init_params(cfg, jax.random.key(0))
    return OryxInference(FakeTokenizer(), params, cfg)


# Epoch-scale anchor so planes read as wall-clock stamped (no
# alignment shift applies).
T0 = 1_700_000_000_000_000_000


def _plane(name, line_name, events, ts_ns=T0):
    """events: (offset_us, dur_us) pairs."""
    return Plane(name, [Line(
        line_name,
        [Event("op", int(d * 1e6), int(o * 1e6)) for o, d in events],
        timestamp_ns=ts_ns,
    )])


# ---------------------------------------------------------------------------
# Busy-union math
# ---------------------------------------------------------------------------


def test_union_counts_overlaps_once():
    # Nested + overlapping events: 0-100us and 10-50us and 90-150us
    # cover exactly 150us of wall time, not 200.
    planes = [_plane("/device:TPU:0", "XLA Ops",
                     [(0, 100), (10, 40), (90, 60)])]
    busy, total = busy_time_us(
        planes, T0, T0 + 1_000_000, plane_filter="TPU",
        line_filter="Ops",
    )
    assert busy == total == 150


def test_window_clipping_never_exceeds_window():
    # One event spanning a whole second; the 100ms window must clip.
    planes = [_plane("/device:TPU:0", "XLA Ops", [(0, 1_000_000)])]
    w0, w1 = T0 + 200_000_000, T0 + 300_000_000  # a 100ms window
    busy, total = busy_time_us(
        planes, w0, w1, plane_filter="TPU", line_filter="Ops",
    )
    assert busy == 100_000  # clipped to the window
    assert total == 1_000_000


def test_busiest_line_wins_not_the_sum():
    p = Plane("/host:CPU", [
        Line("thread 1", [Event("f", int(300e6), 0)], timestamp_ns=T0),
        Line("thread 2", [Event("g", int(10e6), 0)], timestamp_ns=T0),
    ])
    busy, total = busy_time_us([p], T0, T0 + 1_000_000_000)
    assert total == 300  # the busiest line, not 310


# ---------------------------------------------------------------------------
# Kind bucketing
# ---------------------------------------------------------------------------


def test_attribute_capture_kind_bucketing():
    # One TPU plane: 40us inside the ragged window, 25us inside the
    # prefill window, 10us outside both -> "other".
    planes = [_plane("/device:TPU:0", "XLA Ops",
                     [(100, 40), (300, 25), (900, 10)])]
    windows = [
        ("ragged", T0 + 90_000, T0 + 200_000),
        ("prefill", T0 + 290_000, T0 + 400_000),
    ]
    att = attribute_capture(planes, windows)
    assert att["source"] == "tpu_xla_ops"
    assert att["by_kind_us"] == {"ragged": 40, "prefill": 25}
    assert att["other_us"] == 10


def test_attribute_capture_host_fallback_excludes_modules():
    planes = [
        _plane("/host:CPU", "python threads", [(0, 50)]),
        _plane("/host:CPU", "XLA Modules", [(0, 500)]),
    ]
    att = attribute_capture(
        planes, [("decode", T0, T0 + 100_000)]
    )
    assert att["source"] == "host_fallback"
    assert att["by_kind_us"] == {"decode": 50}


def test_chrome_trace_shape():
    planes = [_plane("/device:TPU:0", "XLA Ops", [(0, 10), (20, 5)])]
    body = xplane.chrome_trace(planes)
    xs = [e for e in body["traceEvents"] if e["ph"] == "X"]
    assert len(xs) == 2
    for e in xs:
        assert all(k in e for k in ("name", "ts", "dur", "pid", "tid"))
    names = [e for e in body["traceEvents"] if e["ph"] == "M"]
    assert any(e["name"] == "process_name" for e in names)
    assert body["truncated"] is False


# ---------------------------------------------------------------------------
# Sampling cadence + failure degradation
# ---------------------------------------------------------------------------


def test_cadence_zero_is_off_and_every_nth_fires():
    s = DeviceTimeSampler(every=0)
    assert not any(s.tick() for _ in range(20))
    s = DeviceTimeSampler(every=3)
    fired = [i for i in range(1, 10) if s.tick()]
    assert fired == [3, 6, 9]


def test_capture_failure_degrades_to_labeled_counter(monkeypatch):
    reg = Registry(prefix="oryx_serving")
    s = DeviceTimeSampler(reg, every=1)

    def boom(*a, **k):
        raise RuntimeError("profiler unavailable")

    monkeypatch.setattr(profiling, "_start_trace", boom)
    assert s.tick()
    assert s.begin() is False  # the step proceeds unprofiled
    text = reg.render()
    assert ('oryx_profile_capture_errors_total{stage="start"} 1'
            in text)
    # A parse failure after a real start degrades the same way.
    monkeypatch.undo()
    assert s.begin() is True
    monkeypatch.setattr(
        xplane, "find_xplane_files", lambda d: []
    )
    assert s.end("decode", 0, 10) is None
    assert ('oryx_profile_capture_errors_total{stage="parse"} 1'
            in reg.render())
    assert s._dir is None  # temp state reclaimed


def test_abort_recovers_profiler_state():
    s = DeviceTimeSampler(every=1)
    assert s.begin()
    s.abort()
    assert s._dir is None
    # The process-global profiler is free again.
    assert s.begin()
    s.abort()


# ---------------------------------------------------------------------------
# CPU smoke: real capture joined to live timeline records
# ---------------------------------------------------------------------------


def _run(sched, reqs):
    handles = [sched.submit({"question": q}, cap) for q, cap in reqs]
    sched.start()
    out = [h.result(timeout=600)[0] for h in handles]
    sched.close()
    return out


def test_sampling_preserves_parity_and_feeds_timeline(pipe):
    """The acceptance bar: with --profile-sample-every armed, tokens
    and dispatch accounting are UNCHANGED (sampling observes, never
    participates), sampled timeline records carry device_us from a
    real capture, and the per-kind counters stay within their sampled
    wall windows."""
    reqs = [("hello there paged world", 8), ("what now then?", 6),
            ("tell me more", 7)]
    plain = ServingMetrics()
    sched = ContinuousScheduler(
        pipe, num_slots=2, page_size=16, chunk=4, max_ctx=512,
        prefill_chunk=32, ragged=True, metrics=plain, autostart=False,
    )
    baseline = _run(sched, reqs)
    armed = ServingMetrics()
    sched = ContinuousScheduler(
        pipe, num_slots=2, page_size=16, chunk=4, max_ctx=512,
        prefill_chunk=32, ragged=True, metrics=armed, autostart=False,
        profile_sample_every=2,
    )
    sampled = _run(sched, reqs)
    assert sampled == baseline  # byte parity
    for kind in ("ragged", "prefill", "decode", "spec"):
        fam_p = plain.registry.existing("dispatches_total")
        fam_a = armed.registry.existing("dispatches_total")
        assert fam_p.labels(kind=kind).value \
            == fam_a.labels(kind=kind).value, kind
    recs = sched.timeline.snapshot()
    dev = [r for r in recs if r["device_us"] is not None]
    assert dev, "no sampled step carried device_us"
    for r in dev:
        # In-window busy time can never exceed the step window.
        assert 0 <= r["device_us"] <= r["dur_s"] * 1e6 + 1
    text = armed.render()
    assert "oryx_device_time_seconds_total" in text
    assert "oryx_profile_sampled_wall_seconds_total" in text
    import re

    dev_by = dict(re.findall(
        r'^oryx_device_time_seconds_total\{kind="(\w+)"\} '
        r"([0-9.e+-]+)$", text, re.M))
    wall_by = dict(re.findall(
        r'^oryx_profile_sampled_wall_seconds_total\{kind="(\w+)"\} '
        r"([0-9.e+-]+)$", text, re.M))
    assert wall_by, "no sampled wall windows recorded"
    for kind, v in dev_by.items():
        if kind in wall_by:
            assert float(v) <= float(wall_by[kind]) * 1.01 + 1e-3


def test_on_demand_capture_finishes_early_on_idle(pipe):
    """An adopted capture whose traffic drains before the asked step
    count must finish EARLY with the windows collected so far —
    never leave the process-global profiler recording on an idle
    engine (which would wedge all later captures)."""
    sched = ContinuousScheduler(
        pipe, num_slots=2, page_size=16, chunk=4, max_ctx=512,
        autostart=False,
    )
    sched.start()
    result = {}

    def capture():
        result.update(sched.request_profile(50, timeout=60))

    t = threading.Thread(target=capture)
    t.start()
    sched.submit({"question": "short burst"}, 8).result(timeout=600)
    t.join(timeout=60)
    assert not t.is_alive(), "requester hung past the idle drain"
    assert 1 <= result["steps"] < 50, result["steps"]
    assert result.get("traceEvents")
    # The profiler is free again: a second capture works.
    result2 = {}
    t = threading.Thread(
        target=lambda: result2.update(
            sched.request_profile(2, timeout=60)
        )
    )
    t.start()
    sched.submit({"question": "more traffic"}, 8).result(timeout=600)
    t.join(timeout=60)
    assert result2.get("steps") == 2, result2.get("steps")
    sched.close()


def test_on_demand_request_profile(pipe):
    """scheduler.request_profile brackets the next K dispatches and
    returns a Chrome trace + per-kind split; an idle engine times
    out instead of hanging."""
    sched = ContinuousScheduler(
        pipe, num_slots=2, page_size=16, chunk=4, max_ctx=512,
        autostart=False,
    )
    sched.start()
    with pytest.raises(TimeoutError):
        sched.request_profile(2, timeout=0.5)  # idle: no dispatches
    result = {}

    def capture():
        result.update(sched.request_profile(3, timeout=120))

    t = threading.Thread(target=capture)
    t.start()
    handles = [
        sched.submit({"question": f"traffic {i}"}, 8) for i in range(3)
    ]
    for h in handles:
        h.result(timeout=600)
    t.join(timeout=120)
    assert not t.is_alive()
    assert result.get("steps") == 3
    assert result.get("traceEvents")
    assert isinstance(result.get("device_time_us"), dict)
    # The device's time by the program's layer names rides along; the
    # table is the device's own, so empty off a TPU.
    assert result["scope_seconds"] == {}
    sched.close()
