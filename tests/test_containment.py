"""Failure containment across the serving stack: per-request deadlines,
bounded-queue backpressure, the degraded-mode ladder, engine-crash
restart with deterministic replay, drain-on-shutdown, client-disconnect
cleanup, and allocator failure paths — every scenario ends with the
pool invariant (`check_invariant(holders)`) holding and zero leaked
pages or refcounts."""

import json
import socket
import threading
import time
import urllib.error
import urllib.request

import pytest

import jax

from oryx_tpu import config as cfg_lib
from oryx_tpu.models import oryx
from oryx_tpu.serve import api_server
from oryx_tpu.serve.pipeline import OryxInference
from oryx_tpu.serve.scheduler import (
    AdmissionRejected,
    ContinuousScheduler,
)
from oryx_tpu.utils import faults
from oryx_tpu.utils.anomaly import AnomalyMonitor, AnomalyThresholds
from oryx_tpu.utils.metrics import ServingMetrics


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


@pytest.fixture(autouse=True)
def _clean_faults():
    faults.reset()
    yield
    faults.reset()


def _wait(predicate, timeout=60.0, interval=0.02) -> bool:
    end = time.monotonic() + timeout
    while time.monotonic() < end:
        if predicate():
            return True
        time.sleep(interval)
    return False


# ---------------------------------------------------------------------------
# Deadlines
# ---------------------------------------------------------------------------


def test_deadline_cancels_mid_decode_and_frees_pages(pipe):
    """A request past its deadline is cancelled at the next step
    boundary — wherever it is — and its slot pages AND prefix-cache
    shares are provably returned (pool invariant with holders)."""
    metrics = ServingMetrics()
    sched = ContinuousScheduler(
        pipe, num_slots=2, page_size=16, chunk=4, max_ctx=512,
        metrics=metrics, autostart=False,
    )
    # max_new must keep prompt+decode inside max_ctx (the templated
    # prompt is ~119 tokens) or admission 400s before the deadline path
    # ever runs. Deadline expiry DURING decode must not depend on
    # machine speed: stall the first decode dispatch past the deadline
    # (the hung-dispatch scenario), so the cancel always lands with the
    # slot resident and pages held.
    faults.configure("decode_dispatch:delay=0.6,after=0")
    h = sched.submit({"question": "hello there"}, 300, timeout_s=0.3)
    sched.start()
    with pytest.raises(RuntimeError, match="deadline exceeded"):
        h.result(timeout=600)
    assert h.error_kind == "timeout"
    assert metrics.get("deadline_exceeded_total") == 1
    assert _wait(lambda: all(r is None for r in sched.slots))
    sched._check_pool_invariant()
    sched.close()


def test_deadline_expires_in_queue(pipe):
    """num_slots=1: the second request's deadline passes while it
    waits in the queue — it errors without ever holding pages."""
    sched = ContinuousScheduler(
        pipe, num_slots=1, page_size=16, chunk=4, max_ctx=512,
        autostart=False,
    )
    h_long = sched.submit({"question": "hello there"}, 64)
    h_queued = sched.submit({"question": "what now?"}, 4, timeout_s=0.005)
    sched.start()
    with pytest.raises(RuntimeError, match="deadline exceeded before"):
        h_queued.result(timeout=600)
    assert h_queued.error_kind == "timeout"
    reply, _, _ = h_long.result(timeout=600)  # unaffected neighbor
    assert reply == pipe.chat("hello there", max_new_tokens=64)
    sched._check_pool_invariant()
    sched.close()


# ---------------------------------------------------------------------------
# Bounded admission queue (backpressure)
# ---------------------------------------------------------------------------


def test_bounded_queue_rejects_with_retry_after(pipe):
    metrics = ServingMetrics()
    sched = ContinuousScheduler(
        pipe, num_slots=1, page_size=16, chunk=4, max_ctx=512,
        metrics=metrics, autostart=False, max_queue=2,
    )
    handles = [
        sched.submit({"question": f"q {i}"}, 3) for i in range(2)
    ]
    with pytest.raises(AdmissionRejected) as ei:
        sched.submit({"question": "one too many"}, 3)
    assert ei.value.reason == "backpressure"
    assert ei.value.retry_after_s >= 1.0
    # The rejection queued NOTHING: accepted requests all complete.
    sched.start()
    for i, h in enumerate(handles):
        reply, _, _ = h.result(timeout=600)
        assert reply == pipe.chat(f"q {i}", max_new_tokens=3)
    sched._check_pool_invariant()
    sched.close()
    text = metrics.render()
    assert ('oryx_serving_admission_rejected_total'
            '{reason="backpressure"} 1') in text


# ---------------------------------------------------------------------------
# Degraded-mode ladder
# ---------------------------------------------------------------------------


def test_degraded_ladder_escalates_and_decays(pipe):
    """SLO firings walk the ladder up (shed cache -> clamp -> shed
    load); quiet time walks it back down to 0."""
    metrics = ServingMetrics()
    anomaly = AnomalyMonitor(
        source="serve",
        thresholds=AnomalyThresholds(queue_depth_slo=1),
        registry=metrics.registry,
    )
    sched = ContinuousScheduler(
        pipe, num_slots=1, page_size=16, chunk=4, max_ctx=512,
        metrics=metrics, anomaly=anomaly, autostart=False,
        degraded_cooldown=0.3, degraded_clamp_tokens=2,
    )
    # Depth 2 > SLO 1 on the second submit: one queue_depth_slo event.
    h1 = sched.submit({"question": "hello there"}, 8)
    h2 = sched.submit({"question": "what now?"}, 8)
    assert anomaly.counts.get("queue_depth_slo") == 1
    sched.start()
    h1.result(timeout=600)
    r2, reason2, usage2 = h2.result(timeout=600)
    # The engine saw the firing before admitting h2: mode reached 1
    # (cache shed) — and can have climbed while the backlog drained.
    assert sched.degraded_mode >= 1
    assert metrics.get("degraded_mode") == sched.degraded_mode
    if sched.degraded_mode >= 2:
        assert usage2[1] <= 2  # clamp applied at admission
    # Quiet cooldowns decay it back to 0 even with no traffic at all
    # (mode 3 would otherwise latch: shedding load keeps the engine
    # idle, and an idle engine must still walk the ladder down).
    assert _wait(lambda: sched.degraded_mode == 0, timeout=30)
    assert metrics.get("degraded_mode") == 0
    sched._check_pool_invariant()
    sched.close()


def test_degraded_mode3_sheds_load(pipe):
    sched = ContinuousScheduler(
        pipe, num_slots=1, page_size=16, chunk=4, max_ctx=512,
        autostart=False, degraded_cooldown=3600.0,
    )
    sched._set_degraded(3)
    with pytest.raises(AdmissionRejected) as ei:
        sched.submit({"question": "hi"}, 2)
    assert ei.value.reason == "shed_load"
    sched.close()


# ---------------------------------------------------------------------------
# Engine crash -> restart with deterministic replay
# ---------------------------------------------------------------------------


def test_restart_replays_in_flight_requests(pipe):
    """Kill the engine thread mid-decode (injected crash); restart()
    must requeue the in-flight requests, rebuild the pool (invariant
    checked inside), and the replies must still be BYTE-identical to
    the solo pipeline — the client never learns the engine died."""
    metrics = ServingMetrics()
    sched = ContinuousScheduler(
        pipe, num_slots=2, page_size=16, chunk=4, max_ctx=512,
        metrics=metrics, autostart=False,
    )
    reqs = [("hello there", 12), ("tell me more", 9)]
    handles = [sched.submit({"question": q}, m) for q, m in reqs]
    # Die on the second engine step: both requests admitted and one
    # decode chunk harvested, so the replay actually has work to skip.
    faults.configure("engine_crash:after=1")
    sched.start()
    assert _wait(lambda: not sched.alive(), timeout=120), (
        "engine thread should have died on the injected crash"
    )
    assert faults.injected_count("engine_crash") == 1
    assert not any(h.done.is_set() for h in handles), (
        "no client may see an error from a crash the supervisor heals"
    )
    sched.restart()
    for (q, m), h in zip(reqs, handles):
        reply, _, _ = h.result(timeout=600)
        assert reply == pipe.chat(q, max_new_tokens=m), q
    assert sched.restarts == 1
    assert metrics.get("engine_restarts_total") == 1
    assert _wait(lambda: all(r is None for r in sched.slots))
    sched._check_pool_invariant()
    sched.close()


def test_engine_supervisor_restarts_dead_engine(pipe):
    """The api_server supervisor notices the death and performs the
    restart on its own."""
    sched = ContinuousScheduler(
        pipe, num_slots=2, page_size=16, chunk=4, max_ctx=512,
        autostart=False,
    )
    sup = api_server.EngineSupervisor(sched, poll_s=0.05)
    sup.start()
    h = sched.submit({"question": "hello there"}, 10)
    faults.configure("engine_crash:after=2")
    sched.start()
    reply, _, _ = h.result(timeout=600)
    assert reply == pipe.chat("hello there", max_new_tokens=10)
    assert sched.restarts == 1
    assert not sup.gave_up
    sched._check_pool_invariant()
    sup.stop()
    sched.close()


def test_supervisor_restart_clean_under_lock_sanitizer(pipe):
    """Sanitizer-interplay acceptance: the crash → supervisor-restart
    → replay cycle runs with the lock-order sanitizer and race
    detector ARMED, producing zero ordering violations, zero race
    findings, and — the re-entrancy contract — the restart path never
    re-acquires `scheduler._cond` re-entrantly (appendleft-per-request
    takes and releases it each time; a re-entrant hold would break
    Condition.wait's release semantics)."""
    from oryx_tpu.analysis.sanitizers import (
        lock_sanitizer,
        lock_sanitizer_armed,
        race_violations,
    )

    if lock_sanitizer_armed():
        # Already armed session-wide by the conftest fixture
        # (ORYX_LOCK_SANITIZER=1): don't nest armings.
        ctx = None
        from oryx_tpu.analysis.sanitizers import lock_stats

        san = type("S", (), {"stats": lock_stats()})
    else:
        ctx = lock_sanitizer(action="raise")
        san = ctx.__enter__()
    try:
        base_reentrant = dict(san.stats.reentrant)
        base_violations = len(san.stats.violations)
        sched = ContinuousScheduler(
            pipe, num_slots=2, page_size=16, chunk=4, max_ctx=512,
            autostart=False,
        )
        sup = api_server.EngineSupervisor(sched, poll_s=0.05)
        sup.start()
        h = sched.submit({"question": "hello there"}, 10)
        faults.configure("engine_crash:after=2")
        sched.start()
        reply, _, _ = h.result(timeout=600)
        assert reply == pipe.chat("hello there", max_new_tokens=10)
        assert sched.restarts == 1
        sup.stop()
        sched.close()
        assert san.stats.violations[base_violations:] == []
        assert not race_violations()
        assert san.stats.reentrant.get("scheduler._cond", 0) == \
            base_reentrant.get("scheduler._cond", 0), (
            "supervisor restart re-acquired scheduler._cond "
            "re-entrantly"
        )
        # The instrumented run actually exercised the lock: the
        # sanitizer saw real acquires, not a disarmed no-op.
        assert san.stats.acquires.get("scheduler._cond", 0) > 0
    finally:
        faults.reset()
        if ctx is not None:
            ctx.__exit__(None, None, None)


def test_supervisor_gives_up_on_crash_loop(pipe):
    """A systemically crashing engine must not restart forever: the
    supervisor gives up after its budget, leaves the replica
    not-ready for ejection, FAILS the stranded requests (a hung
    client is worse than a 503), and submit() rejects from then on."""
    sched = ContinuousScheduler(
        pipe, num_slots=1, page_size=16, chunk=4, max_ctx=512,
        autostart=False,
    )
    sup = api_server.EngineSupervisor(
        sched, poll_s=0.02, max_restarts=2, window_s=60.0
    )
    sup.start()
    h = sched.submit({"question": "doomed"}, 4)
    faults.configure("engine_crash:every=1,times=1000")  # crash loop
    sched.start()
    assert _wait(lambda: sup.gave_up, timeout=60)
    assert sched.restarts == 2  # the budget, not one more
    assert not sched.alive()
    # The doomed request was errored out, not left hanging forever...
    with pytest.raises(RuntimeError, match="supervisor gave up"):
        h.result(timeout=60)
    assert h.error_kind == "unavailable"
    # ...and new work is rejected at admission (503 material).
    with pytest.raises(AdmissionRejected) as ei:
        sched.submit({"question": "after give-up"}, 2)
    assert ei.value.reason == "engine_dead"
    sched._check_pool_invariant()
    sup.stop()
    sched.close()


def test_supervisor_is_alive_safe_after_exit(pipe):
    """Regression (found by the armed race detector): threading.Thread
    keeps a private `_stop()` METHOD that `is_alive()` calls once the
    thread has finished; EngineSupervisor shadowing it with an Event
    made every post-exit `is_alive()` raise TypeError."""
    sched = ContinuousScheduler(
        pipe, num_slots=1, page_size=16, chunk=4, max_ctx=512,
        autostart=False,
    )
    sup = api_server.EngineSupervisor(sched, poll_s=0.02)
    sup.start()
    sup.stop()
    sup.join(timeout=30)
    assert sup.is_alive() is False  # raised TypeError before the fix
    sched.close()


def test_dead_engine_without_supervisor_rejects_and_drains(pipe):
    """--no-supervisor: once the engine thread has died, submit() must
    reject instead of queueing requests whose handles can never
    complete, and drain() must fail the stranded ones out rather than
    reporting a clean drain over a dead loop."""
    sched = ContinuousScheduler(
        pipe, num_slots=1, page_size=16, chunk=4, max_ctx=512,
        autostart=False,
    )
    h = sched.submit({"question": "hello there"}, 4)
    faults.configure("engine_crash:after=0")
    sched.start()
    assert _wait(lambda: not sched.alive(), timeout=120)
    with pytest.raises(AdmissionRejected) as ei:
        sched.submit({"question": "too late"}, 2)
    assert ei.value.reason == "engine_dead"
    assert sched.drain(timeout=30) is True
    with pytest.raises(RuntimeError, match="engine stopped"):
        h.result(timeout=60)
    assert h.error_kind == "unavailable"
    sched._check_pool_invariant()
    sched.close()


# ---------------------------------------------------------------------------
# Drain-on-shutdown
# ---------------------------------------------------------------------------


def test_drain_finishes_residents_rejects_new(pipe):
    sched = ContinuousScheduler(
        pipe, num_slots=1, page_size=16, chunk=4, max_ctx=512,
        autostart=False,
    )
    h_res = sched.submit({"question": "hello there"}, 24)
    h_queued = sched.submit({"question": "never admitted"}, 4)
    sched.start()
    assert _wait(lambda: sched.slots[0] is not None, timeout=120)
    sched.begin_drain()
    # New work is rejected the moment drain starts...
    with pytest.raises(AdmissionRejected) as ei:
        sched.submit({"question": "too late"}, 2)
    assert ei.value.reason == "draining"
    # ...the queued-but-unadmitted request errors as unavailable...
    with pytest.raises(RuntimeError, match="draining"):
        h_queued.result(timeout=600)
    assert h_queued.error_kind == "unavailable"
    # ...and the RESIDENT decode still finishes, byte-exact.
    reply, _, _ = h_res.result(timeout=600)
    assert reply == pipe.chat("hello there", max_new_tokens=24)
    assert sched.drain(timeout=120) is True
    assert not sched.alive()
    sched._check_pool_invariant()


# ---------------------------------------------------------------------------
# Allocator failure paths (parametrized fault sites)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("spec", [
    "page_alloc_oom:after=0",        # very first allocation fails
    "page_alloc_oom:after=3",        # mid-splice/grow
    "page_alloc_oom:every=2",        # every other allocation
    "page_alloc_oom:p=0.4,seed=3",   # random schedule A
    "page_alloc_oom:p=0.4,seed=9",   # random schedule B
])
def test_allocator_failures_leave_refcounts_exact(pipe, spec):
    """PageAllocator exhaustion injected during _splice_and_grow, COW
    copies and growth: every request either completes (byte-exact) or
    errors cleanly, and `check_invariant(holders)` holds after — no
    leaked pages, no stale refcounts, with the prefix cache in play."""
    # 12 pages = 192 tokens: tight enough that two ~156-token prompts
    # can never be resident together (constant defer/evict pressure),
    # roomy enough that any SINGLE request genuinely fits — so every
    # failure below is the injector's doing, not geometry.
    sched = ContinuousScheduler(
        pipe, num_slots=2, page_size=16, chunk=4, max_ctx=256,
        num_pages=12, autostart=False,
    )
    faults.configure(spec)
    shared = "shared prefix for the cache to splice around! "
    handles = [
        sched.submit({"question": shared + f"q{i}"}, 4 + i % 3)
        for i in range(5)
    ]
    sched.start()
    completed = 0
    for h in handles:
        try:
            h.result(timeout=600)
        except RuntimeError:
            continue  # errored cleanly under injection — acceptable
        completed += 1
    faults.reset()  # stop injecting before the invariant probe
    assert _wait(
        lambda: all(r is None for r in sched.slots)
        and sched.queue_len() == 0
    )
    sched._check_pool_invariant()
    sched.close()
    if spec.endswith("after=0"):
        # A single transient failure is pure defer/evict territory:
        # every request must still complete.
        assert completed == 5


def test_cow_alloc_failure_falls_back_to_recompute(pipe):
    """The COW path's alloc failure (mid-page split) must fall back to
    recomputing the partial page — same reply, refcounts exact."""
    sched = ContinuousScheduler(
        pipe, num_slots=2, page_size=4, chunk=4, max_ctx=256,
        autostart=False,
    )
    q = "hello there friend"  # 18 tokens: partial last page at ps=4
    ref = pipe.chat(q, max_new_tokens=4)
    h1 = sched.submit({"question": q}, 4)
    sched.start()
    assert h1.result(timeout=600)[0] == ref
    # Second identical prompt hits the cache mid-page -> COW alloc;
    # inject exactly that allocation to fail.
    faults.configure("page_alloc_oom:after=0")
    h2 = sched.submit({"question": q}, 4)
    assert h2.result(timeout=600)[0] == ref
    faults.reset()
    assert _wait(lambda: all(r is None for r in sched.slots))
    sched._check_pool_invariant()
    sched.close()


# ---------------------------------------------------------------------------
# HTTP layer: 429/503/504, drain flip, disconnect mid-stream
# ---------------------------------------------------------------------------


def _post_raw(url, body):
    return urllib.request.Request(
        url + "/v1/chat/completions",
        data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json"},
    )


def _status_of(req):
    try:
        with urllib.request.urlopen(req, timeout=600) as r:
            return r.status, dict(r.headers), json.load(r)
    except urllib.error.HTTPError as e:
        return e.code, dict(e.headers), json.loads(e.read() or b"{}")


@pytest.fixture()
def server(pipe):
    """Per-test continuous server with tight containment knobs."""
    made = []

    def build(**kw):
        srv = api_server.build_server(
            pipe, port=0, engine="continuous", num_slots=1,
            page_size=16, decode_chunk=4, max_ctx=512, **kw,
        )
        threading.Thread(target=srv.serve_forever, daemon=True).start()
        made.append(srv)
        return srv, f"http://127.0.0.1:{srv.server_address[1]}"

    yield build
    for srv in made:
        if srv.supervisor is not None:
            srv.supervisor.stop()
        if srv.scheduler is not None:
            srv.scheduler.close()
        srv.shutdown()


def test_http_backpressure_429_with_retry_after(server):
    srv, url = server(max_queue=1)
    sched = srv.scheduler
    results = []

    def fire(i, max_tokens):
        results.append((i, _status_of(_post_raw(url, {
            "messages": [{"role": "user", "content": f"load {i}"}],
            "max_tokens": max_tokens,
        }))))

    # Occupy the single slot with a long decode, then queue one more:
    # the queue (cap 1) is now full DETERMINISTICALLY until the long
    # request finishes.
    t0 = threading.Thread(target=fire, args=(0, 64))
    t0.start()
    assert _wait(lambda: sched.slots[0] is not None, timeout=120)
    t1 = threading.Thread(target=fire, args=(1, 2))
    t1.start()
    assert _wait(lambda: sched.queue_len() >= 1, timeout=120)
    code, headers, body = _status_of(_post_raw(url, {
        "messages": [{"role": "user", "content": "over the cap"}],
        "max_tokens": 2,
    }))
    assert code == 429
    assert int(headers["Retry-After"]) >= 1
    assert body["error"]["type"] == "overloaded_error"
    assert body["error"]["reason"] == "backpressure"
    t0.join()
    t1.join()
    assert {c for _, (c, _, _) in results} == {200}
    assert 'reason="backpressure"} 1' in srv.metrics.render()
    assert _wait(lambda: all(r is None for r in sched.slots))
    sched._check_pool_invariant()


def test_http_deadline_maps_to_504(server):
    srv, url = server(request_timeout=0.01)
    code, _, body = _status_of(_post_raw(url, {
        "messages": [{"role": "user", "content": "too slow"}],
        "max_tokens": 300,
    }))
    assert code == 504
    assert body["error"]["type"] == "timeout_error"
    assert _wait(
        lambda: all(r is None for r in srv.scheduler.slots)
    )
    srv.scheduler._check_pool_invariant()


def test_http_drain_flips_readyz_and_rejects_posts(server):
    srv, url = server()

    def readyz():
        try:
            with urllib.request.urlopen(url + "/readyz", timeout=30) as r:
                return r.status, json.load(r)
        except urllib.error.HTTPError as e:
            return e.code, json.loads(e.read())

    assert readyz()[0] == 200
    srv.begin_drain()
    code, body = readyz()
    assert code == 503 and body["reason"] == "draining"
    code, headers, body = _status_of(_post_raw(url, {
        "messages": [{"role": "user", "content": "post-drain"}],
        "max_tokens": 2,
    }))
    assert code == 503
    assert body["error"]["type"] == "unavailable_error"
    assert headers.get("Retry-After")
    assert srv.scheduler.drain(timeout=120) is True


def test_client_disconnect_mid_stream_frees_everything(server):
    """The satellite regression: a socket that closes mid-decode must
    cancel the request, free its slot pages and prefix-cache shares,
    and leave the server serving."""
    srv, url = server()
    sched = srv.scheduler
    host, port = srv.server_address
    body = json.dumps({
        "messages": [{"role": "user", "content": "stream then die"}],
        "max_tokens": 300, "stream": True,
    }).encode()
    s = socket.create_connection((host, port), timeout=30)
    s.sendall(
        b"POST /v1/chat/completions HTTP/1.1\r\n"
        b"Host: x\r\nContent-Type: application/json\r\n"
        + f"Content-Length: {len(body)}\r\n\r\n".encode() + body
    )
    # Read a little SSE (the stream is live), then vanish mid-decode.
    assert s.recv(256)
    s.close()
    assert _wait(
        lambda: srv.metrics.get("cancelled") >= 1, timeout=120
    ), "disconnect never cancelled the request"
    assert _wait(lambda: all(r is None for r in sched.slots))
    sched._check_pool_invariant()
    # Still serving after the rude client:
    code, _, out = _status_of(_post_raw(url, {
        "messages": [{"role": "user", "content": "still alive?"}],
        "max_tokens": 3,
    }))
    assert code == 200


def test_cancel_mid_prefill_frees_pages(pipe):
    """Chunked prefill: a request whose client hangs up while its
    prompt is still prefilling must stop prefilling and release its
    pages (including spliced shares) at the next engine step."""
    metrics = ServingMetrics()
    sched = ContinuousScheduler(
        pipe, num_slots=1, page_size=16, chunk=4, max_ctx=512,
        metrics=metrics, autostart=False, prefill_chunk=8,
    )
    long_q = "a long prompt that needs several prefill chunks " * 4
    h = sched.submit({"question": long_q}, 8)
    sched.start()
    # Wait for PLACEMENT (pages held, prefill in flight), then vanish.
    assert _wait(lambda: sched.slots[0] is not None, timeout=120)
    h.cancelled = True
    assert _wait(
        lambda: metrics.get("cancelled") >= 1
        and all(r is None for r in sched.slots),
        timeout=120,
    )
    sched._check_pool_invariant()
    sched.close()
