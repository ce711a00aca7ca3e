"""Metrics registry (utils/metrics.py): exposition well-formedness,
label escaping, histogram bucket math, concurrency, duplicate-family
rejection, collectors, and the TelemetryServer HTTP surface — the
backbone both ServingMetrics and the trainer exporter sit on."""

import json
import math
import re
import threading
import urllib.error
import urllib.request

import pytest

from oryx_tpu.utils.metrics import (
    PROMETHEUS_CONTENT_TYPE,
    Registry,
    ServingMetrics,
    TelemetryServer,
    register_device_memory_collector,
    register_process_collector,
)

_SAMPLE = re.compile(
    r"^([a-zA-Z_:][\w:]*)(\{[^}]*\})? (-?[\d.e+-]+|[+-]?inf|nan)$"
)


def parse_exposition(text: str) -> dict[str, float]:
    """Assert Prometheus text well-formedness; return sample map with
    labels folded into the key."""
    values = {}
    types: dict[str, str] = {}
    for line in text.splitlines():
        if not line:
            continue
        if line.startswith("#"):
            m = re.match(r"^# TYPE (\S+) (counter|gauge|histogram)$", line)
            assert m, line
            assert m.group(1) not in types, f"duplicate family {line!r}"
            types[m.group(1)] = m.group(2)
            continue
        m = _SAMPLE.match(line)
        assert m, f"malformed sample line: {line!r}"
        values[m.group(1) + (m.group(2) or "")] = float(m.group(3))
    return values


def test_counter_gauge_prefix_and_get():
    r = Registry(prefix="oryx_test")
    r.counter("reqs").inc()
    r.counter("reqs").inc(2.5)
    r.gauge("depth").set(7)
    assert r.get("reqs") == 3.5
    assert r.get("depth") == 7
    assert r.get("never_touched") == 0.0
    v = parse_exposition(r.render())
    assert v["oryx_test_reqs"] == 3.5
    assert v["oryx_test_depth"] == 7


def test_raw_name_skips_prefix():
    r = Registry(prefix="oryx_train")
    r.counter("oryx_anomaly_total", ("kind",), raw_name=True).labels(
        kind="nan_loss"
    ).inc()
    v = parse_exposition(r.render())
    assert v['oryx_anomaly_total{kind="nan_loss"}'] == 1


def test_negative_counter_increment_rejected():
    r = Registry()
    with pytest.raises(ValueError, match=">= 0"):
        r.counter("c").inc(-1)


def test_label_escaping():
    r = Registry(prefix="p")
    r.gauge("g", ("path",)).labels(path='a\\b"c\nd').set(1)
    text = r.render()
    assert 'path="a\\\\b\\"c\\nd"' in text
    # Escaped value stays on ONE line (the newline must not split it).
    assert len([l for l in text.splitlines() if l.startswith("p_g{")]) == 1


def test_label_names_must_match_declaration():
    r = Registry()
    fam = r.counter("c", ("kind",))
    with pytest.raises(ValueError, match="declares"):
        fam.labels(other="x")


def test_histogram_bucket_math():
    r = Registry(prefix="h")
    hist = r.histogram("lat", (0.1, 1.0, 10.0))
    for x in (0.05, 0.5, 0.5, 5.0, 50.0):
        hist.observe(x)
    v = parse_exposition(r.render())
    # Cumulative le-buckets; +Inf == total count; exact sum.
    assert v['h_lat_bucket{le="0.1"}'] == 1
    assert v['h_lat_bucket{le="1"}'] == 3
    assert v['h_lat_bucket{le="10"}'] == 4
    assert v['h_lat_bucket{le="+Inf"}'] == 5
    assert v["h_lat_count"] == 5
    assert v["h_lat_sum"] == pytest.approx(56.05)


def test_histogram_with_labels_renders_per_child():
    r = Registry()
    fam = r.histogram("lat", (1.0,), ("engine",))
    fam.labels(engine="a").observe(0.5)
    fam.labels(engine="b").observe(2.0)
    v = parse_exposition(r.render())
    assert v['lat_bucket{engine="a",le="1"}'] == 1
    assert v['lat_bucket{engine="b",le="1"}'] == 0
    assert v['lat_count{engine="a"}'] == 1
    assert v['lat_count{engine="b"}'] == 1


def test_duplicate_family_rejected():
    # The kind clash below is the POINT of the test (the runtime twin
    # of oryxlint's metric-name rule) — hence the suppressions.
    r = Registry()
    r.counter("x")  # oryxlint: disable=metric-name
    with pytest.raises(ValueError, match="re-declared"):
        r.gauge("x")  # oryxlint: disable=metric-name
    with pytest.raises(ValueError, match="re-declared"):
        r.counter("x", ("kind",))  # oryxlint: disable=metric-name
    # Identical re-declaration returns the same family.
    assert r.counter("x") is r.counter("x")  # oryxlint: disable=metric-name


def test_concurrent_increments_exact():
    r = Registry()
    c = r.counter("hits")
    fam = r.counter("by_kind", ("kind",))
    h = r.histogram("obs", (0.5,))
    N, T = 500, 8

    def work(i):
        for _ in range(N):
            c.inc()
            fam.labels(kind=f"k{i % 2}").inc()
            h.observe(0.1)

    threads = [threading.Thread(target=work, args=(i,)) for i in range(T)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    v = parse_exposition(r.render())
    assert v["hits"] == N * T
    assert v['by_kind{kind="k0"}'] + v['by_kind{kind="k1"}'] == N * T
    assert v["obs_count"] == N * T
    assert v['obs_bucket{le="0.5"}'] == N * T


def test_info_metric_replaces():
    r = Registry(prefix="s")
    r.info("build_info", {"revision": "abc", "engine": "continuous"})
    r.info("build_info", {"revision": "def", "engine": "continuous"})
    v = parse_exposition(r.render())
    assert v == {
        's_build_info{engine="continuous",revision="def"}': 1.0
    }
    # info() may replace only INFO families — clobbering a live
    # counter would violate the no-duplicate-family invariant. (The
    # deliberate kind clash is what's under test here.)
    r.counter("live_counter").inc()  # oryxlint: disable=metric-name
    with pytest.raises(ValueError, match="already registered"):
        r.info("live_counter", {"k": "v"})  # oryxlint: disable=metric-name
    assert r.get("live_counter") == 1


def test_get_on_histogram_and_labeled_is_zero():
    r = Registry()
    r.histogram("lat", (1.0,)).observe(0.5)
    r.counter("by_kind", ("kind",)).labels(kind="a").inc()
    assert r.get("lat") == 0.0  # no single scalar: convenience zero
    assert r.get("by_kind") == 0.0
    m = ServingMetrics()
    assert m.get("ttft_seconds") == 0.0  # pre-created histogram


def test_collectors_refresh_on_render_and_never_break_scrape():
    r = Registry()
    g = r.gauge("fresh")
    state = {"n": 0}

    def collect():
        state["n"] += 1
        g.set(state["n"])

    def broken():
        raise RuntimeError("boom")

    r.register_collector(collect)
    r.register_collector(broken)
    parse_exposition(r.render())
    v = parse_exposition(r.render())
    assert v["fresh"] == 2  # refreshed per render; broken one swallowed


def test_process_and_device_memory_collectors():
    r = Registry(prefix="t")
    register_process_collector(r)
    register_device_memory_collector(r)
    v = parse_exposition(r.render())
    assert v["t_process_cpu_seconds_total"] > 0
    assert v["t_process_resident_memory_bytes"] > 0
    assert v["t_process_threads"] >= 1
    assert "t_hbm_live_bytes" in v
    # Forced-host CPU backend: live_arrays is real, allocator stats 0.
    assert v["t_hbm_live_bytes"] >= 0


def test_device_memory_collector_rate_limited(monkeypatch):
    """`jax.live_arrays()` walks every live array, so the HBM
    collector caches for ~1s (monotonic): an aggressive scraper pays
    the walk at most once per TTL window, and ttl_s=0 disables the
    cache. Counting fake pins the contract."""
    import jax as jax_lib

    calls = {"n": 0}

    def counting_live_arrays():
        calls["n"] += 1
        return []

    monkeypatch.setattr(jax_lib, "live_arrays", counting_live_arrays)
    r = Registry(prefix="t")
    register_device_memory_collector(r, ttl_s=1000.0)
    for _ in range(5):
        r.render()
    assert calls["n"] == 1, calls  # cached inside the TTL window
    # Monotonic-clock based: past the TTL the walk refreshes.
    import time as time_lib

    r2 = Registry(prefix="t2")
    register_device_memory_collector(r2, ttl_s=0.05)
    calls["n"] = 0
    r2.render()
    r2.render()
    assert calls["n"] == 1, calls
    time_lib.sleep(0.06)
    r2.render()
    assert calls["n"] == 2, calls
    # ttl_s=0 disables the cache entirely.
    r3 = Registry(prefix="t3")
    register_device_memory_collector(r3, ttl_s=0)
    calls["n"] = 0
    for _ in range(3):
        r3.render()
    assert calls["n"] == 3, calls


def test_serving_metrics_compat_surface():
    """ServingMetrics is now a Registry client; the old call surface
    (inc/set_gauge/observe/get/render, creation-only buckets) must be
    byte-compatible for the scheduler and the endpoint gates."""
    m = ServingMetrics()
    m.inc("admitted")
    m.set_gauge("queue_depth", 2)
    m.observe("ttft_seconds", 0.3)
    m.observe("ttft_seconds", 0.3, buckets=(99.0,))  # ignored: exists
    m.set_info("build_info", {"revision": "r", "engine": "e", "model": "m"})
    assert m.get("admitted") == 1
    assert m.get("queue_depth") == 2
    text = m.render()
    v = parse_exposition(text)
    assert v["oryx_serving_admitted"] == 1
    # Both observations recorded into the ORIGINAL ladder (the second
    # call's bucket arg was ignored, not a new family).
    assert v['oryx_serving_ttft_seconds_bucket{le="0.5"}'] == 2
    assert v['oryx_serving_ttft_seconds_bucket{le="+Inf"}'] == 2
    # Pre-created ladders render from first touch.
    assert "oryx_serving_time_per_output_token_seconds_count" in v
    for line in text.splitlines():
        if line and not line.startswith("#"):
            assert line.startswith(("oryx_serving_", "oryx_anomaly_")), line


def test_telemetry_server_endpoints():
    r = Registry(prefix="oryx_train")
    r.gauge("loss").set(1.25)
    ready = {"ok": False}
    srv = TelemetryServer(
        r, port=0,
        ready_check=lambda: (ready["ok"], "ok" if ready["ok"] else "warming"),
    ).start()
    try:
        base = f"http://127.0.0.1:{srv.port}"
        with urllib.request.urlopen(base + "/metrics", timeout=10) as resp:
            assert resp.headers["Content-Type"] == PROMETHEUS_CONTENT_TYPE
            v = parse_exposition(resp.read().decode())
        assert v["oryx_train_loss"] == 1.25
        with urllib.request.urlopen(base + "/healthz", timeout=10) as resp:
            assert json.load(resp) == {"status": "ok"}
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(base + "/readyz", timeout=10)
        assert ei.value.code == 503
        assert json.load(ei.value) == {"ready": False, "reason": "warming"}
        ready["ok"] = True
        with urllib.request.urlopen(base + "/readyz", timeout=10) as resp:
            assert json.load(resp)["ready"] is True
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(base + "/nope", timeout=10)
        assert ei.value.code == 404
    finally:
        srv.close()


# ---------------------------------------------------------------------------
# Shared quantile helpers (histogram bucket interpolation — the one
# implementation loadgen and check_serving_endpoints both use)
# ---------------------------------------------------------------------------


def test_histogram_quantile_interpolation():
    from oryx_tpu.utils.metrics import histogram_quantile

    # Observations 0.5, 1.5, 1.5, 3.0 over bounds (1, 2, 4):
    # cumulative counts (1, 3, 4), total 4.
    bounds, counts, total = [1.0, 2.0, 4.0], [1, 3, 4], 4
    # p50: rank 2 inside (1, 2] between cum 1 and 3 -> 1.5 exactly.
    assert histogram_quantile(0.5, bounds, counts, total) == pytest.approx(1.5)
    # p100 lands at the top of the last bucket.
    assert histogram_quantile(1.0, bounds, counts, total) == pytest.approx(4.0)
    # p25: rank 1 is the full first bucket -> its upper bound.
    assert histogram_quantile(0.25, bounds, counts, total) == pytest.approx(1.0)
    # q=0 clamps to the lower edge of the first occupied bucket.
    assert histogram_quantile(0.0, bounds, counts, total) == pytest.approx(0.0)


def test_histogram_quantile_edges():
    from oryx_tpu.utils.metrics import histogram_quantile

    # Empty histogram -> NaN.
    assert math.isnan(histogram_quantile(0.5, [1.0], [0], 0))
    assert math.isnan(histogram_quantile(0.5, [], [], 0))
    # Observations past the last finite bound clamp to it (the
    # Prometheus convention): 3 of 4 obs overflowed the ladder.
    assert histogram_quantile(0.99, [1.0], [1], 4) == pytest.approx(1.0)
    with pytest.raises(ValueError):
        histogram_quantile(1.5, [1.0], [1], 1)


def test_parse_prom_histogram_roundtrip():
    """Render a real registry histogram, parse it back with the shared
    parser, and check the quantile is consistent with the samples."""
    from oryx_tpu.utils.metrics import (
        histogram_quantile,
        parse_prom_histogram,
    )

    reg = Registry(prefix="oryx_test")
    h = reg.histogram("lat_seconds", (0.1, 0.5, 1.0, 5.0))
    for v in (0.05, 0.2, 0.3, 0.7, 2.0, 9.0):
        h.observe(v)
    text = reg.render()
    parsed = parse_prom_histogram(text, "oryx_test_lat_seconds")
    assert parsed is not None
    bounds, counts, total, s = parsed
    assert bounds == [0.1, 0.5, 1.0, 5.0]
    assert counts == [1, 3, 4, 5]
    assert total == 6
    assert s == pytest.approx(12.25)
    p50 = histogram_quantile(0.5, bounds, counts, total)
    assert 0.1 <= p50 <= 0.5  # the median sample (0.3-ish bucket)
    # Absent family -> None, never a crash.
    assert parse_prom_histogram(text, "oryx_test_nope_seconds") is None


def test_sample_quantile_exact():
    from oryx_tpu.utils.metrics import sample_quantile

    assert math.isnan(sample_quantile([], 0.5))
    assert sample_quantile([3.0], 0.99) == 3.0
    vals = [4.0, 1.0, 3.0, 2.0]
    assert sample_quantile(vals, 0.5) == pytest.approx(2.5)
    assert sample_quantile(vals, 0.0) == 1.0
    assert sample_quantile(vals, 1.0) == 4.0
    with pytest.raises(ValueError):
        sample_quantile(vals, -0.1)


# ---------------------------------------------------------------------------
# Concurrent scrapes under write load (registry thread-safety + no
# torn exposition lines)
# ---------------------------------------------------------------------------


def _assert_histograms_consistent(text: str) -> None:
    """Within ONE exposition, every histogram's bucket counts must be
    cumulative non-decreasing and its +Inf bucket must equal its
    _count line — a torn render (counts snapshotted mid-observe)
    breaks one of these."""
    import collections

    buckets: dict[str, list[tuple[float, int]]] = collections.defaultdict(list)
    counts: dict[str, int] = {}
    for line in text.splitlines():
        m = re.match(r'^(\S+)_bucket\{le="([^"]+)"\} (\d+)$', line)
        if m:
            le = float("inf") if m.group(2) == "+Inf" else float(m.group(2))
            buckets[m.group(1)].append((le, int(m.group(3))))
            continue
        m = re.match(r"^(\S+)_count (\d+)$", line)
        if m:
            counts[m.group(1)] = int(m.group(2))
    assert buckets, "no histograms in exposition"
    for name, bs in buckets.items():
        cs = [c for _, c in sorted(bs)]
        assert cs == sorted(cs), f"{name}: non-cumulative buckets {bs}"
        assert cs[-1] == counts[name], (
            f"{name}: +Inf bucket {cs[-1]} != count {counts[name]}"
        )


def test_concurrent_scrapes_no_torn_lines():
    """Writers hammering counters/gauges/histograms (labeled children
    included) while readers render: every exposition parses line-clean
    (parse_exposition asserts per-line well-formedness and no
    duplicate TYPE), and every histogram is internally consistent."""
    import random as random_lib

    reg = Registry(prefix="oryx_test")
    c = reg.counter("ops_total")
    g = reg.gauge("depth")
    h = reg.histogram("lat_seconds", (0.1, 0.5, 1.0, 5.0))
    lbl = reg.counter("kinds_total", ("kind",))
    stop = threading.Event()
    failures: list[BaseException] = []

    def writer(seed: int) -> None:
        rng = random_lib.Random(seed)
        while not stop.is_set():
            c.inc()
            g.set(rng.random() * 100)
            h.observe(rng.random() * 10)
            lbl.labels(kind=f"k{rng.randrange(4)}").inc()

    def reader() -> None:
        try:
            for _ in range(40):
                text = reg.render()
                parse_exposition(text)
                _assert_histograms_consistent(text)
        except BaseException as e:  # surfaces through `failures`
            failures.append(e)

    writers = [
        threading.Thread(target=writer, args=(i,), daemon=True)
        for i in range(4)
    ]
    readers = [threading.Thread(target=reader) for _ in range(4)]
    for t in writers + readers:
        t.start()
    for t in readers:
        t.join(timeout=120)
    stop.set()
    for t in writers:
        t.join(timeout=10)
    assert not any(t.is_alive() for t in readers), "reader hung"
    assert not failures, failures[0]
