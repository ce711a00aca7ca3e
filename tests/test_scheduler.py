"""Continuous-batching scheduler: slot freeing + admission at chunk
boundaries, FIFO no-starvation, page-pressure eviction with
deterministic replay, and the wasted-step microbench as a slow test."""

import re
import time

import numpy as np
import pytest

import jax

from oryx_tpu import config as cfg_lib
from oryx_tpu.models import oryx
from oryx_tpu.serve.pipeline import OryxInference
from oryx_tpu.serve.scheduler import ContinuousScheduler
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


def _run_all(sched, reqs):
    """Submit before starting (deterministic admission order), then
    collect every reply."""
    handles = [
        sched.submit({"question": q}, cap, sampling)
        for q, cap, sampling in reqs
    ]
    sched.start()
    results = [h.result(timeout=600) for h in handles]
    sched.close()
    return handles, results


def test_short_row_frees_slot_and_admits_within_chunk(pipe):
    """The headline continuous-batching behavior: with 2 slots and 3
    requests, the short row's finish must free its slot and the queued
    request must be admitted at that SAME chunk boundary — and every
    reply must equal the solo pipeline answer (greedy determinism across
    batch composition)."""
    metrics = ServingMetrics()
    sched = ContinuousScheduler(
        pipe, num_slots=2, page_size=16, chunk=4, max_ctx=512,
        metrics=metrics, autostart=False,
    )
    reqs = [("hello there", 3, None), ("what now?", 12, None),
            ("tell me more", 5, None)]
    handles, results = _run_all(sched, reqs)
    for (q, cap, _), (reply, reason, usage) in zip(reqs, results):
        assert reply == pipe.chat(q, max_new_tokens=cap), q
        assert reason == "length"  # tiny vocab never emits EOS
        assert usage[1] == cap
    # Request 3 waited for a slot, then entered at the chunk boundary
    # where request 1 finished (no full-batch drain in between). With
    # one chunk in flight the chunk enqueued BEFORE that finish was
    # read is harvested before request 3's first token is: one more
    # chunk counted at its admission, never two.
    finish_1 = handles[0].debug["finish_chunk"]
    admit_3 = handles[2].debug["admit_chunk"]
    assert admit_3 <= finish_1 + 1, (admit_3, finish_1)
    assert metrics.get("admitted") == 3
    assert metrics.get("completed") == 3
    assert metrics.get("decode_steps_wasted") < metrics.get(
        "decode_steps_total"
    )


def test_no_starvation_fifo(pipe):
    """More requests than slots: everyone completes, and admission
    follows submission order (the FIFO head is never jumped)."""
    sched = ContinuousScheduler(
        pipe, num_slots=2, page_size=16, chunk=4, max_ctx=512,
        autostart=False,
    )
    reqs = [(f"question number {i}", 4 + (i % 3), None) for i in range(6)]
    handles, results = _run_all(sched, reqs)
    for (q, cap, _), (reply, _, _) in zip(reqs, results):
        assert reply == pipe.chat(q, max_new_tokens=cap), q
    admit_order = [h.debug["admit_chunk"] for h in handles]
    assert admit_order == sorted(admit_order), admit_order


def test_mixed_sampling_configs_share_one_engine(pipe):
    """Greedy and sampled requests decode side by side (per-slot
    sampling state): the greedy rows still match pipe.chat exactly and
    a seeded sampled row is reproducible across runs."""
    reqs = [
        ("hello there", 5, None),
        ("what now?", 5, {"temperature": 0.9, "top_p": 0.9, "seed": 3}),
        ("tell me more", 5, None),
    ]
    replies = []
    for _ in range(2):
        sched = ContinuousScheduler(
            pipe, num_slots=2, page_size=16, chunk=4, max_ctx=512,
            autostart=False,
        )
        _, results = _run_all(sched, reqs)
        replies.append([r[0] for r in results])
    for i in (0, 2):
        assert replies[0][i] == pipe.chat(reqs[i][0], max_new_tokens=5)
    # Same seed, different batch timing possible -> same sampled reply.
    assert replies[0][1] == replies[1][1]


def test_eviction_requeues_and_replays(pipe):
    """Page pressure: a pool too small for both rows' growth evicts the
    YOUNGER slot, which re-queues, replays deterministically after the
    older finishes, and still returns the exact solo reply."""
    q1, q2 = "hello there", "tell me more"
    # Size the pool so both prompts admit, but the pool cannot hold both
    # rows' grown contexts: each row eventually needs pages_for(L + cap
    # + chunk) pages; give the pool one growth page only.
    chunk, ps = 4, 16
    ids1 = len(pipe._prepare_request({"question": q1})[0])
    ids2 = len(pipe._prepare_request({"question": q2})[0])
    import math

    admit1 = math.ceil((ids1 + chunk) / ps)
    admit2 = math.ceil((ids2 + chunk) / ps)
    cap = (admit1 * ps - ids1) + ps  # forces one extra page per row
    metrics = ServingMetrics()
    # prefix_cache off: the template prefix both prompts share would
    # otherwise be SPLICED (shared pages), dissolving the engineered
    # pressure — this test targets the eviction machinery itself
    # (tests/test_prefix_cache.py covers eviction WITH sharing).
    sched = ContinuousScheduler(
        pipe, num_slots=2, page_size=ps, chunk=chunk, max_ctx=512,
        num_pages=admit1 + admit2 + 1, metrics=metrics, autostart=False,
        prefix_cache=False,
    )
    handles, results = _run_all(
        sched, [(q1, cap, None), (q2, cap, None)]
    )
    assert metrics.get("evicted") >= 1
    for q, (reply, reason, usage) in zip((q1, q2), results):
        assert reply == pipe.chat(q, max_new_tokens=cap), q
        assert usage[1] == cap


def test_request_too_large_errors_cleanly(pipe):
    sched = ContinuousScheduler(
        pipe, num_slots=2, page_size=16, chunk=4, max_ctx=512,
        autostart=False,
    )
    h = sched.submit({"question": "hi"}, 2048)
    ok = sched.submit({"question": "hello there"}, 4)
    sched.start()
    with pytest.raises(RuntimeError, match="max_ctx"):
        h.result(timeout=600)
    # The oversized request must not wedge the queue behind it.
    reply, _, _ = ok.result(timeout=600)
    assert reply == pipe.chat("hello there", max_new_tokens=4)
    sched.close()


def test_request_traces_cover_lifecycle_and_eviction(pipe):
    """Flight-recorder span trees: every request records queue_wait ->
    admission -> prefill -> decode chunks -> emission; an evicted
    request additionally records the evicted event, a reopened
    queue_wait, and a replay prefill."""
    import math

    from oryx_tpu.utils import trace as trace_lib

    q1, q2 = "hello there", "tell me more"
    chunk, ps = 4, 16
    ids1 = len(pipe._prepare_request({"question": q1})[0])
    ids2 = len(pipe._prepare_request({"question": q2})[0])
    admit1 = math.ceil((ids1 + chunk) / ps)
    admit2 = math.ceil((ids2 + chunk) / ps)
    cap = (admit1 * ps - ids1) + ps
    metrics = ServingMetrics()
    tracer = trace_lib.Tracer()
    # prefix_cache off for the same reason as
    # test_eviction_requeues_and_replays: shared template pages would
    # dissolve the page pressure this test relies on.
    sched = ContinuousScheduler(
        pipe, num_slots=2, page_size=ps, chunk=chunk, max_ctx=512,
        num_pages=admit1 + admit2 + 1, metrics=metrics, autostart=False,
        tracer=tracer, prefix_cache=False,
    )
    handles, results = _run_all(
        sched, [(q1, cap, None), (q2, cap, None)]
    )
    assert metrics.get("evicted") >= 1
    for h, (reply, reason, usage) in zip(handles, results):
        tr = h.trace
        assert tr is tracer.get(h.request_id)
        assert tr.done
        assert tr.meta["finish_reason"] == reason
        assert tr.meta["completion_tokens"] == usage[1]
        names = [s.name for s in tr.spans]
        for want in ("queue_wait", "admission", "prefill",
                     "decode_chunk", "emission"):
            assert want in names, (want, names)
        assert all(s.dur_ns is not None for s in tr.spans)
    # The evicted request (the younger one) carries the eviction story.
    evicted = next(
        h.trace for h in handles
        if any(s.name == "evicted" for s in h.trace.spans)
    )
    names = [s.name for s in evicted.spans]
    assert names.count("queue_wait") >= 2  # submit + requeue
    prefills = [s for s in evicted.spans if s.name == "prefill"]
    assert len(prefills) >= 2
    assert prefills[-1].args["replay"] is True
    ev = next(s for s in evicted.spans if s.name == "evicted")
    assert ev.args["replay_tokens"] > 0


def test_forced_stall_triggers_exactly_one_watchdog_dump(pipe):
    """Acceptance: a test-injected stall (one decode chunk held past
    the deadline) produces exactly ONE watchdog dump, containing the
    thread stacks and the flight-recorder tail with the stuck
    request."""
    import io
    import time as time_lib

    sched = ContinuousScheduler(
        pipe, num_slots=2, page_size=16, chunk=4, max_ctx=512,
        autostart=False, stall_timeout=0.25,
    )
    out = io.StringIO()
    sched.watchdog.out = out
    orig = sched._step_chunk
    stalled = []

    def slow_chunk():
        if not stalled:
            stalled.append(1)
            time_lib.sleep(1.2)  # > 4x the deadline, no beat
        return orig()

    sched._step_chunk = slow_chunk
    h = sched.submit({"question": "hello there"}, 6)
    sched.start()
    reply, _, _ = h.result(timeout=600)
    assert reply == pipe.chat("hello there", max_new_tokens=6)
    # Allow the watchdog thread its final tick, then close.
    deadline = time_lib.monotonic() + 5
    while sched.watchdog.dumps == 0 and time_lib.monotonic() < deadline:
        time_lib.sleep(0.02)
    sched.close()
    assert sched.watchdog.dumps == 1, sched.watchdog.dumps
    text = out.getvalue()
    assert "STALL WATCHDOG" in text
    assert h.request_id in text  # recorder tail names the stuck request
    assert "slow_chunk" in text  # the stack shows where it hung


def test_cancel_in_queue_refreshes_queue_depth_gauge(pipe):
    """Regression: a request cancelled BEFORE admission popped the
    queue without refreshing the queue_depth gauge, pinning it one
    high until the next submit (found during the oryxlint
    self-application pass over the scheduler's guarded state)."""
    metrics = ServingMetrics()
    sched = ContinuousScheduler(
        pipe, num_slots=1, page_size=16, chunk=4, max_ctx=512,
        metrics=metrics, autostart=False,
    )
    h = sched.submit({"question": "never mind"}, 4)
    assert metrics.get("queue_depth") == 1
    h.cancelled = True
    sched._admit()  # engine loop body; thread never started
    assert metrics.get("queue_depth") == 0
    assert h.reply is None and not h.done.is_set()
    sched.close()


def test_cancel_drain_rearms_queue_depth_slo(pipe):
    """Regression: a backlog that empties via client cancels never fed
    the anomaly monitor, so the queue_depth_slo episode stayed disarmed
    and the NEXT backlog burst fired no event — the drain side must
    observe the depth, same as the engine-failure path."""
    from oryx_tpu.utils.anomaly import AnomalyMonitor, AnomalyThresholds

    monitor = AnomalyMonitor(
        source="serve", thresholds=AnomalyThresholds(queue_depth_slo=1)
    )
    sched = ContinuousScheduler(
        pipe, num_slots=1, page_size=16, chunk=4, max_ctx=512,
        metrics=ServingMetrics(), autostart=False, anomaly=monitor,
    )
    h1 = sched.submit({"question": "a"}, 4)
    h2 = sched.submit({"question": "b"}, 4)  # depth 2 > 1: fires
    assert monitor.counts.get("queue_depth_slo") == 1
    h1.cancelled = True
    h2.cancelled = True
    sched._admit()  # engine loop body; thread never started
    # The cancel drain observed depth 0 <= slo/2: episode re-armed,
    # so a second burst fires a second event.
    hc = sched.submit({"question": "c"}, 4)
    hd = sched.submit({"question": "d"}, 4)
    assert monitor.counts.get("queue_depth_slo") == 2
    hc.cancelled = True
    hd.cancelled = True
    sched._admit()  # drain + re-arm again
    # Same invariant on the admission-rejection pop: a burst of invalid
    # requests (prompt + max_tokens > max_ctx) fires the third event at
    # submit, drains through the except path, and must re-arm for the
    # fourth burst.
    h3 = sched.submit({"question": "e"}, 4096)
    h4 = sched.submit({"question": "f"}, 4096)
    assert monitor.counts.get("queue_depth_slo") == 3
    sched._admit()
    for h in (h3, h4):
        assert h.error_kind == "invalid_request"
    sched.submit({"question": "g"}, 4)
    sched.submit({"question": "h"}, 4)
    assert monitor.counts.get("queue_depth_slo") == 4
    sched.close()


def test_engine_error_drains_queue_and_resets_gauge(pipe, monkeypatch):
    """Regression: the engine-failure handler drained the queue without
    refreshing the queue_depth gauge — /metrics kept reporting the dead
    backlog until the next submit. Same every-pop-refreshes-the-gauge
    invariant as the pre-admission cancel path."""
    from oryx_tpu.serve import scheduler as sched_mod

    metrics = ServingMetrics()
    sched = ContinuousScheduler(
        pipe, num_slots=1, page_size=16, chunk=4, max_ctx=512,
        metrics=metrics, autostart=False,
    )

    def boom(*a, **k):
        raise RuntimeError("induced device failure")

    monkeypatch.setattr(sched_mod.generate_lib, "paged_prefill", boom)
    h1 = sched.submit({"question": "first"}, 4)
    h2 = sched.submit({"question": "queued behind"}, 4)
    sched.start()
    for h in (h1, h2):
        with pytest.raises(RuntimeError, match="induced device failure"):
            h.result(timeout=120)
    assert metrics.get("queue_depth") == 0
    sched.close()


def test_request_cost_ledger_complete_and_consistent(pipe):
    """Every finished request carries the full cost ledger (the
    capacity harness's acceptance bar): prefill + cached tokens
    partition the prompt, decode steps cover the decode, page-seconds
    and the span-derived wall times are positive and sane — and the
    look-alike second request shows its shared prefix as CACHED tokens
    (the TokenTrie splice visible in per-request cost)."""
    from oryx_tpu.utils.metrics import REQUEST_COST_KEYS

    metrics = ServingMetrics()
    sched = ContinuousScheduler(
        pipe, num_slots=2, page_size=16, chunk=4, max_ctx=512,
        metrics=metrics, autostart=False,
    )
    shared = "shared system preamble for the ledger test " * 2
    reqs = [(shared + "q one?", 4, None), (shared + "q two?", 4, None)]
    handles, results = _run_all(sched, reqs)
    for h, (reply, reason, usage) in zip(handles, results):
        cost = h.debug["cost"]
        assert set(REQUEST_COST_KEYS) <= set(cost), cost
        # Prompt tokens either came from the cache or were computed.
        assert cost["prefill_tokens"] + cost["cached_tokens"] == usage[0]
        assert cost["decode_steps"] >= 4  # at least one decode chunk
        assert cost["page_seconds"] > 0
        assert cost["prefill_s"] > 0
        assert cost["queue_s"] >= 0
        assert cost["decode_s"] > 0
        assert cost["e2e_s"] > 0
        # The ledger also lands in the trace meta (what
        # /debug/requests serves).
        assert h.trace.summary()["meta"]["cost"] == cost
    # First admission is cold; the second splices the shared prefix.
    assert handles[0].debug["cost"]["cached_tokens"] == 0
    assert handles[1].debug["cost"]["cached_tokens"] > 0
    # Aggregate histogram families observed one sample per request.
    text = metrics.render()
    import re

    for fam in ("request_prefill_tokens", "request_cached_tokens",
                "request_decode_steps", "request_page_seconds",
                "request_queue_seconds", "request_prefill_seconds",
                "request_decode_seconds", "request_e2e_seconds"):
        m = re.search(
            rf"^oryx_serving_{fam}_count (\d+)$", text, re.M
        )
        assert m and int(m.group(1)) == 2, fam


def test_cost_ledger_survives_eviction_replay(pipe):
    """An evicted-and-replayed request's ledger keeps accumulating:
    the replay re-pays prefill (prefill + cached tokens exceed one
    placement's prompt) and page-seconds never reset. The ledger
    reports what was SPENT, not what one placement used."""
    q1, q2 = "hello there", "tell me more"
    chunk, ps = 4, 16
    ids1 = len(pipe._prepare_request({"question": q1})[0])
    ids2 = len(pipe._prepare_request({"question": q2})[0])
    import math

    admit1 = math.ceil((ids1 + chunk) / ps)
    admit2 = math.ceil((ids2 + chunk) / ps)
    cap = (admit1 * ps - ids1) + ps
    metrics = ServingMetrics()
    sched = ContinuousScheduler(
        pipe, num_slots=2, page_size=ps, chunk=chunk, max_ctx=512,
        num_pages=admit1 + admit2 + 1, metrics=metrics, autostart=False,
        prefix_cache=False,
    )
    handles, results = _run_all(
        sched, [(q1, cap, None), (q2, cap, None)]
    )
    assert metrics.get("evicted") >= 1
    total_prefill = sum(
        h.debug["cost"]["prefill_tokens"] + h.debug["cost"]["cached_tokens"]
        for h in handles
    )
    # At least one request prefilled twice (eviction replay).
    assert total_prefill > ids1 + ids2
    for h in handles:
        assert h.debug["cost"]["page_seconds"] > 0


def test_cancelled_in_queue_gets_zero_cost_ledger(pipe):
    """Review fix: a request cancelled while still QUEUED finishes its
    trace as done-without-error, so the /debug/requests?state=done
    audit sees it — it must carry a (zero-resource) cost ledger like
    every other finished request."""
    import time as time_lib

    sched = ContinuousScheduler(
        pipe, num_slots=1, page_size=16, chunk=4, max_ctx=512,
        autostart=False,
    )
    h1 = sched.submit({"question": "hello there"}, 3)
    h2 = sched.submit({"question": "tell me more"}, 3)
    h2.cancelled = True  # client hung up while queued behind h1
    sched.start()
    assert h1.result(timeout=600)[0]
    for _ in range(200):  # the engine pops h2 at a later loop pass
        if h2.trace.done:
            break
        time_lib.sleep(0.05)
    sched.close()
    meta = h2.trace.summary()["meta"]
    assert meta.get("cancelled") is True
    cost = meta["cost"]
    assert cost["prefill_tokens"] == 0
    assert cost["cached_tokens"] == 0
    assert cost["decode_steps"] == 0
    assert cost["page_seconds"] == 0
    assert cost["queue_s"] >= 0 and cost["e2e_s"] >= 0
    assert h2.debug["cost"] == cost


def test_cancelled_in_queue_increments_cancelled_counter(pipe):
    """Regression for the queue-cancel undercount (oryxlint
    terminal-path obligation finding on scheduler.py `_cancel_queued`:
    `cancelled` undischarged): the pre-admission cancel path finalized
    the ledger and emitted the wide event but skipped
    `metrics.inc("cancelled")`, so the counter only saw the three
    slot-holding cancel paths and queue cancels undercounted. All four
    cancel exits now route through `_cancel_queued`/`_cancel_slot`,
    each carrying a machine-checked `# obligations:` set."""
    import time as time_lib

    metrics = ServingMetrics()
    sched = ContinuousScheduler(
        pipe, num_slots=1, page_size=16, chunk=4, max_ctx=512,
        metrics=metrics, autostart=False,
    )
    h1 = sched.submit({"question": "hello there"}, 3)
    h2 = sched.submit({"question": "tell me more"}, 3)
    h2.cancelled = True  # client hung up while queued behind h1
    sched.start()
    assert h1.result(timeout=600)[0]
    for _ in range(200):  # the engine pops h2 at a later loop pass
        if h2.trace.done:
            break
        time_lib.sleep(0.05)
    sched.close()
    assert metrics.get("cancelled") == 1


def test_queued_deadline_rejection_carries_cost_ledger(pipe):
    """Review fix: a request that dies while still QUEUED (deadline
    expired before admission) is a terminal path too — its ledger
    (zero resources, real queue wait) must land in the handle and the
    trace meta, so saturated-regime cost attribution covers the
    requests that never ran."""
    import time as time_lib

    sched = ContinuousScheduler(
        pipe, num_slots=1, page_size=16, chunk=4, max_ctx=512,
        autostart=False,
    )
    h = sched.submit({"question": "hello there"}, 3, timeout_s=0.01)
    time_lib.sleep(0.05)  # expire before the engine ever runs
    sched.start()
    with pytest.raises(RuntimeError):
        h.result(timeout=600)
    sched.close()
    assert h.error_kind == "timeout"
    cost = h.debug["cost"]
    assert cost["prefill_tokens"] == 0
    assert cost["page_seconds"] == 0
    assert cost["queue_s"] >= 0
    assert h.trace.summary()["meta"]["cost"] == cost


def test_page_seconds_accrual_is_refcount_weighted(pipe):
    """Review fix: a page shared by k holders charges each holder 1/k,
    so summed request_page_seconds never exceeds physical residency —
    without this, the better prefix sharing works, the more expensive
    the aggregate HBM currency would look."""
    import time as time_lib

    from oryx_tpu.serve.scheduler import RequestHandle, _Request

    sched = ContinuousScheduler(
        pipe, num_slots=2, page_size=16, chunk=4, max_ctx=512,
        autostart=False,
    )
    p_excl, p_shared = sched.allocator.alloc(2)
    sched.allocator.share([p_shared])  # second holder of p_shared
    def mk():
        r = _Request(
            request={}, max_new=1, sampling={},
            handle=RequestHandle(), submit_time=0.0, stops=[],
        )
        r.pages_t = time_lib.monotonic()
        return r

    ra, rb = mk(), mk()
    sched.slots[0], sched.slots[1] = ra, rb
    sched.bt[0, 0], sched.bt[0, 1] = p_excl, p_shared  # 1 + 1/2
    sched.bt[1, 0] = p_shared  # 1/2
    time_lib.sleep(0.1)
    sched._accrue_page_seconds(0)
    sched._accrue_page_seconds(1)
    a, b = ra.cost_page_seconds, rb.cost_page_seconds
    assert a > 0 and b > 0
    # A holds one exclusive page (weight 1) plus half the shared page;
    # B holds the other half: the ratio is 3 regardless of sleep
    # jitter (both accruals cover near-identical intervals).
    assert 2.5 < a / b < 3.5, (a, b)
    # Drop the fabricated holders so close() leaves a clean pool.
    sched.allocator.free([p_excl, p_shared, p_shared])
    sched.bt[:] = sched.allocator.sentinel
    sched.slots = [None, None]
    sched.close()


def test_stop_string_mid_chunk_not_billed_useful(pipe):
    """Bugfix pin: a slot that finishes mid-chunk on a stop STRING
    (detected host-side, so the token loop consumed the whole chunk)
    must re-bill the steps past the stop completion as wasted —
    without this, the wasted-step fraction under-counts exactly when
    stop strings end rows early."""
    import time as time_lib

    from oryx_tpu.serve.scheduler import RequestHandle, _Request

    metrics = ServingMetrics()
    sched = ContinuousScheduler(
        pipe, num_slots=1, page_size=16, chunk=8, max_ctx=512,
        metrics=metrics, autostart=False,
    )
    h = RequestHandle()
    tr = sched.tracer.start_trace("request")
    h.trace = tr
    h.request_id = tr.id
    req = _Request(
        request={}, max_new=100, sampling={}, handle=h,
        submit_time=time_lib.monotonic(), stops=["c"], trace=tr,
    )
    req.length = 4
    req.activated = True
    sched.slots[0] = req
    sched.lengths[0] = req.length
    # Device chunk decodes "abcde": the stop "c" completes at token 3;
    # tokens 4-5 did nothing for the client.
    useful = sched._advance(0, [ord(ch) for ch in "abcde"])
    assert h.done.is_set() and h.finish_reason == "stop"
    assert h.usage == (4, 3)
    assert useful == 3, f"steps past the stop billed useful: {useful}"

    # EOS consumed AFTER the stop completed: it is billed by the token
    # loop but never appended to `emitted` — the clamp must count it
    # wasted too (consumed-token space, not emitted-token space).
    h2 = RequestHandle()
    tr2 = sched.tracer.start_trace("request")
    h2.trace = tr2
    h2.request_id = tr2.id
    req2 = _Request(
        request={}, max_new=100, sampling={}, handle=h2,
        submit_time=time_lib.monotonic(), stops=["a"], trace=tr2,
    )
    req2.length = 4
    req2.activated = True
    sched.slots[0] = req2
    sched.lengths[0] = req2.length
    eos = sched.cfg.generation.eos_token_id
    useful = sched._advance(0, [ord("a"), ord("b"), eos, ord("d")])
    assert h2.done.is_set() and h2.finish_reason == "stop"
    assert h2.usage == (4, 1)
    assert useful == 1, f"EOS after the stop billed useful: {useful}"
    sched.close()


def _sorts_and_dispatches(metrics, kind):
    reg = metrics.registry
    return (
        reg.counter("sampler_sort_dispatches_total", ("kind",))
        .labels(kind=kind).value,
        reg.counter("dispatches_total", ("kind",)).labels(kind=kind).value,
    )


@pytest.mark.parametrize("mode", ["split", "block"])
def test_sampler_sort_counter_counts_the_dispatches_with_a_sampled_row(
    pipe, monkeypatch, mode
):
    """`sampler_sort_dispatches_total{kind=}` stays 0 over greedy
    requests, and over a mix it counts exactly the dispatches whose
    temperature argument held a value above 0: read off the arguments
    the device programs were given, which is what the sampler's
    conditional decides on."""
    from oryx_tpu.serve import scheduler as sched_lib

    if mode == "block":
        cfg = cfg_lib.sdar_tiny()
        pipe = OryxInference(
            FakeTokenizer(), oryx.init_params(cfg, jax.random.key(0)), cfg)
        kw = {"prefill_chunk": 32}
        step = ("block", "paged_block_step")
    else:
        kw = {"chunk": 4}
        step = ("decode", "paged_decode_chunk")
    # (program, position of its temperature argument)
    seen = {"prefill": [], step[0]: []}
    for kind, name, at in (("prefill", "paged_prefill", 8), (*step, 9)):
        real = getattr(sched_lib.generate_lib, name)

        def spy(*a, _real=real, _kind=kind, _at=at, **k):
            seen[_kind].append(bool(np.any(np.asarray(a[_at]) > 0)))
            return _real(*a, **k)

        monkeypatch.setattr(sched_lib.generate_lib, name, spy)

    def run(reqs):
        metrics = ServingMetrics()
        sched = ContinuousScheduler(
            pipe, num_slots=2, page_size=16, max_ctx=256, metrics=metrics,
            autostart=False, **kw,
        )
        for v in seen.values():
            v.clear()
        _run_all(sched, reqs)
        assert "sampler_sort_dispatches_total" in metrics.registry.render()
        return {kind: _sorts_and_dispatches(metrics, kind) for kind in seen}

    greedy = [("hello there, friend", 9, None), ("what now?", 6, None),
              ("tell me more, then", 5, {"temperature": 0.0})]
    for kind, (sorts, dispatches) in run(greedy).items():
        assert sorts == 0 and dispatches == len(seen[kind]) > 0
    mixed = [("hello there, friend", 14, None),
             ("what now? and then what, and why", 3,
              {"temperature": 0.9, "top_p": 0.9, "seed": 3}),
             ("tell me more, then", 5, None)]
    for kind, (sorts, dispatches) in run(mixed).items():
        assert dispatches == len(seen[kind])
        assert sorts == sum(seen[kind])
        assert 0 < sorts < dispatches


@pytest.mark.parametrize("mode", ["split", "ragged", "speculate", "audit"])
def test_released_prompt_embeds_are_gathered_again_for_a_replay(pipe, mode):
    """A text-only request's prompt embeds are released when its
    prefill ends (a live request would hold [1, bucket, H] twice over
    for nothing: its prompt is in the cache). An eviction replays the
    prefill and the auditor copies the embeds at the finish: both
    gather them again from the request's ids, under every step program
    that reads them (the split engine's `embeds` / `embeds_p`, the
    ragged and speculative engines' host copy `embeds_np`), and the
    replies stay the solo pipeline's, token for token."""
    import math

    q1, q2 = "hello there", "tell me more"
    chunk, ps = 4, 16
    ids1 = len(pipe._prepare_request({"question": q1})[0])
    ids2 = len(pipe._prepare_request({"question": q2})[0])
    admit1 = math.ceil((ids1 + chunk) / ps)
    admit2 = math.ceil((ids2 + chunk) / ps)
    cap = (admit1 * ps - ids1) + ps  # forces one extra page per row
    engine = {
        "split": {}, "audit": {"audit_sample_every": 1},
        # A prompt prefills inside one fused dispatch, so the pressure
        # comes once both rows are live.
        "ragged": {"ragged": True, "prefill_chunk": 32},
        "speculate": {"ragged": True, "prefill_chunk": 32, "speculate": 3},
    }[mode]
    metrics = ServingMetrics()
    sched = ContinuousScheduler(
        pipe, num_slots=2, page_size=ps, chunk=chunk, max_ctx=512,
        num_pages=admit1 + admit2 + 1, metrics=metrics, autostart=False,
        prefix_cache=False, **engine,
    )
    held, regathered = [], []
    activate, ensure = sched._activate, sched._ensure_embeds

    def activate_and_look(s, req, *rest):
        activate(s, req, *rest)
        held.append((req.embeds, req.embeds_p, req.embeds_np))

    def ensure_and_count(req, *base):
        was = req.embeds is None
        ensure(req, *base)
        assert req.embeds is not None
        if was:
            regathered.append(req.trace.id)

    sched._activate, sched._ensure_embeds = activate_and_look, ensure_and_count
    handles = [sched.submit({"question": q}, cap, None) for q in (q1, q2)]
    sched.start()
    results = [h.result(timeout=600) for h in handles]
    if mode == "audit":
        import time

        deadline = time.monotonic() + 120
        while (sum(sched.auditor.to_dict()["verdicts"].values()) < 2
               and time.monotonic() < deadline):
            time.sleep(0.05)
        verdicts = sched.auditor.to_dict()["verdicts"]
    sched.close()
    assert metrics.get("evicted") >= 1
    # Every activation (the replayed one too) left nothing held ...
    assert len(held) >= 3 and all(h == (None, None, None) for h in held)
    # ... and the replay (and each audited finish) gathered again.
    assert len(regathered) >= (3 if mode == "audit" else 1)
    for q, (reply, _, usage) in zip((q1, q2), results):
        assert reply == pipe.chat(q, max_new_tokens=cap), q
        assert usage[1] == cap
    if mode == "audit":
        assert verdicts["pass"] == 2 and not (
            verdicts["fail"] or verdicts["drift"])


# ---------------------------------------------------------------------------
# Block mode keeps one dispatch in flight (docs/DESIGN.md "Block
# diffusion"): block n+1 is enqueued before block n is read
# ---------------------------------------------------------------------------

# The block engine's own test file has the configuration, the reply's
# ids and the margin-aware comparison with the reference loop.
from test_block_engine import (  # noqa: E402
    _assert_tokens, _cfg as _block_cfg, _ids as _reply_ids,
)


class IdTokenizer:
    """`<id>` per token out. In, `<id>` is that id again and any other
    character its code point, so a reply re-encodes to its own ids (a
    history turn then hits the pages its first turn donated)."""

    def encode(self, text, add_special_tokens=False):
        return [
            int(m[1]) if m[1] else min(ord(m[0]), 500)
            for m in re.finditer(r"<(\d+)>|.", text, re.S)
        ]

    def decode(self, ids, skip_special_tokens=True):
        return "".join(f"<{int(i)}>" for i in ids)


@pytest.fixture(scope="module")
def block_params():
    """Four times the init's scale, behind the "plain" template (the
    user's text and a newline): at the init's 0.02 every reply is one
    token repeated whatever the prompt, and a slot that showed its
    previous owner's tokens would pass."""
    params = oryx.init_params(_block_cfg(), jax.random.key(0))
    return jax.tree.map(lambda x: x * 4 if x.ndim >= 2 else x, params)


def _block_pipe(params, **gen):
    return OryxInference(
        IdTokenizer(), params, _block_cfg(**gen), template="plain")


def _block_want(pipe, request, cap, eos=None):
    """The reference's cache-less loop on the request's own ids:
    ((tokens, margins), prompt length)."""
    from benchmark.reference import sdar_moe_ref as ref

    ids, *_ = pipe._prepare_request(request)
    gen = pipe.cfg.generation
    return ref.generate(
        pipe.params["llm"], pipe.cfg.llm, ids, cap,
        steps=gen.denoising_steps, remasking=gen.remasking,
        threshold=gen.confidence_threshold, eos=eos), len(ids)


def _serve_blocks(pipe, reqs, *, skip=(), hook=None, journal=None, **kw):
    """`reqs` (request dict, cap, sampling) through a block engine of
    two slots, submitted up front. The engine must end idle with
    nothing in flight and every page accounted for."""
    metrics = ServingMetrics()
    kw = {"num_slots": 2, "page_size": 16, "max_ctx": 256,
          "prefill_chunk": 32, **kw}
    sched = ContinuousScheduler(
        pipe, metrics=metrics, autostart=False, journal=journal, **kw)
    handles = [sched.submit(r, cap, s) for r, cap, s in reqs]
    if hook is not None:
        hook(sched, handles)
    sched.start()
    results = [
        None if i in skip else h.result(timeout=600)
        for i, h in enumerate(handles)
    ]
    deadline = time.monotonic() + 30
    while time.monotonic() < deadline and (
        sched._inflight is not None
        or any(r is not None for r in sched.slots)
    ):
        time.sleep(0.01)
    idle = sched._inflight is None and all(r is None for r in sched.slots)
    sched.close()
    assert idle, "the engine did not go idle with nothing in flight"
    sched._check_pool_invariant()
    return handles, results, metrics


def _dropped_by_rule(ends):
    """Slot-blocks the engine computes for nothing: a request that ends
    on something only the harvest knows (EOS, a stop) rides one block
    more, unless that block would have been past its max_tokens, which
    the host counts. `ends`: (prompt length, cap, new tokens up to and
    with the one that ended it, or None for a `length` finish)."""
    n = 0
    for length, cap, consumed in ends:
        if consumed is None:
            continue
        tail = length % 4
        n += -(-(tail + consumed) // 4) < -(-(tail + cap) // 4)
    return n


BLOCK_CASES = [
    "max_tokens_on_a_block_edge", "max_tokens_inside_a_block",
    "eos_inside_a_block", "stop_sequence", "cancel_with_a_block_in_flight",
    "eviction_under_a_small_pool", "slot_freed_one_block_earlier",
    "prefix_hit_on_pages_donated_with_a_block_in_flight",
]


@pytest.mark.parametrize("case", BLOCK_CASES)
def test_block_engine_with_a_block_in_flight_keeps_every_reply(
    block_params, case
):
    """Staggered requests through a two-slot block engine that enqueues
    block n+1 before it reads block n: every reply is the reference
    loop's, token for token, whatever ends a request and whoever takes
    its slot; `block_dispatches_ahead_total` and
    `block_rows_dropped_total` count what the case did."""
    pipe = _block_pipe(block_params)
    qs = ["hello there", "what now? " * 6, "tell me more!", "and a tail.."]
    kw, hook, skip, eos = {}, None, (), None
    sampled_solo = None
    if case.startswith("max_tokens"):
        lens = [len(pipe._prepare_request({"question": q})[0]) for q in qs]
        edge = case == "max_tokens_on_a_block_edge"
        # tail + cap a multiple of the block, or one past it.
        caps = [8 + (-(n + 8)) % 4 + (0 if edge else 1) for n in lens]
        reqs = [({"question": q}, c, None) for q, c in zip(qs, caps)]
    elif case == "eos_inside_a_block":
        (free, _), _ = _block_want(pipe, {"question": qs[0]}, 16)
        eos = free[5]  # the second block's second token, or earlier
        pipe = _block_pipe(block_params, eos_token_id=eos)
        reqs = [({"question": q}, 16, None) for q in qs[:3]]
    elif case == "stop_sequence":
        (free, _), _ = _block_want(pipe, {"question": qs[0]}, 16)
        reqs = [({"question": qs[0]}, 16, {"stop": [f"<{free[6]}>"]}),
                ({"question": qs[1]}, 9, None),
                ({"question": qs[2]}, 7, None)]
    elif case == "cancel_with_a_block_in_flight":
        reqs = [({"question": qs[0]}, 24, None),
                ({"question": qs[1]}, 40, None),
                ({"question": qs[2]}, 10, None)]
        skip = (1,)

        def hook(sched, handles):
            enqueue, rode = sched._enqueue_block, []

            def enqueue_then_hang_up(ahead):
                flight = enqueue(ahead)
                rode.extend(
                    s for s in (flight.riders if flight else ())
                    if sched.slots[s].handle is handles[1]
                )
                if len(rode) == 2:
                    # Request 1 rides this block and the one before it,
                    # which is read next: the hang-up is seen there.
                    assert ahead
                    handles[1].cancelled = True
                return flight

            sched._enqueue_block = enqueue_then_hang_up
    elif case == "eviction_under_a_small_pool":
        sampling = {"temperature": 0.8, "top_p": 0.9, "seed": 3}
        reqs = [({"question": "a" * 40}, 60, None),
                ({"question": "b" * 40}, 60, sampling)]
        _, ((sampled_solo, _, _),), _ = _serve_blocks(pipe, reqs[1:])
        # Both are admitted (3 pages each) and neither can finish (7)
        # without the other's pages.
        kw = {"num_pages": 10}
    elif case == "slot_freed_one_block_earlier":
        (free, _), n0 = _block_want(pipe, {"question": qs[0]}, 16)
        eos = free[5]
        pipe = _block_pipe(block_params, eos_token_id=eos)
        # The long request must not meet the EOS itself.
        (long, _), n1 = _block_want(pipe, {"question": qs[1]}, 40)
        assert eos not in long
        # The long one first: it is live when the short one ends, so
        # nothing drains and the third is placed at once.
        reqs = [({"question": qs[1]}, 40, None),
                ({"question": qs[0]}, 16, None),
                ({"question": qs[3]}, 12, None)]
        # Any two fit, all three do not: the third gets the pages the
        # short one gave back with a block still writing to them.
        pages = [-(-(n + c) // 16) + 1 for n, c in ((n0, 16), (n1, 40))]
        kw = {"num_pages": sum(pages) + 1, "prefix_cache": False}
        took_over = []

        def hook(sched, handles):
            harvest = sched._harvest_block

            def harvest_and_look(flight):
                took_over.extend(
                    s for s, seq in flight.riders.items()
                    if sched.slots[s] is not None
                    and sched.slots[s].admit_seq != seq
                )
                harvest(flight)

            sched._harvest_block = harvest_and_look
    else:
        q = "the same opening words, " * 2
        (free, _), n0 = _block_want(pipe, {"question": q}, 40)
        # An EOS late enough for the reply to fill a page of its own.
        at = next(
            i for i in range(len(free))
            if (n0 + i) // 16 > n0 // 16 and free.index(free[i]) == i
        )
        eos = free[at]
        pipe = _block_pipe(block_params, eos_token_id=eos)
        first = pipe.tokenizer.decode(free[:at])
        turn2 = {"question": "and then?", "history": [(q, first)]}
        ids0 = list(pipe._prepare_request({"question": q})[0])
        ids2 = list(pipe._prepare_request(turn2)[0])
        stream = ids0 + free[:at]
        assert ids2[:len(stream)] == stream
        (long, _), _ = _block_want(pipe, {"question": qs[1]}, 60)
        assert eos not in long
        reqs = [({"question": qs[1]}, 60, None),
                ({"question": q}, 40, None), (turn2, 9, None)]

    handles, results, metrics = _serve_blocks(
        pipe, reqs, skip=skip, hook=hook, **kw)
    ends = []
    for i, ((request, cap, sampling), res) in enumerate(zip(reqs, results)):
        if i in skip:
            assert not handles[i].done.is_set()
            continue
        reply, reason, usage = res
        if sampling and sampling.get("temperature"):
            assert reply == sampled_solo  # the same after an eviction
            continue
        (want, margins), n = _block_want(pipe, request, cap, eos=eos)
        consumed = None if len(want) == cap else len(want) + 1
        for stop in (sampling or {}).get("stop", ()):
            tok = int(stop.strip("<>"))
            if tok in want:
                want = want[:want.index(tok)]
                consumed = len(want) + 1
        _assert_tokens(_reply_ids(reply), want, margins)
        assert reason == ("length" if consumed is None else "stop")
        assert usage[0] == n
        ends.append((n, cap, consumed))
    ahead = metrics.get("block_dispatches_ahead_total")
    dropped = metrics.get("block_rows_dropped_total")
    reg = metrics.registry
    blocks = reg.counter("dispatches_total", ("kind",)).labels(
        kind="block").value
    assert 0 < ahead < blocks
    if case == "cancel_with_a_block_in_flight":
        assert metrics.get("cancelled") == 1 and dropped == 1
    elif case == "eviction_under_a_small_pool":
        # The block in flight is read before anyone is evicted.
        assert metrics.get("evicted") >= 1 and dropped == 0
    else:
        assert dropped == _dropped_by_rule(ends)
        assert (dropped > 0) == (case not in (
            "max_tokens_on_a_block_edge", "max_tokens_inside_a_block"))
    if case == "slot_freed_one_block_earlier":
        # A block was read whose slot had passed to the next request.
        assert took_over
    if case.startswith("prefix_hit"):
        # The second turn spliced the pages that hold the first reply.
        assert metrics.get("prefix_cache_hit_tokens_total") >= (
            len(stream) // 16 * 16) > n0


def test_block_n_plus_1_is_enqueued_before_block_n_is_read(
    block_params, monkeypatch
):
    """With two live slots the engine calls `paged_block_step` for
    block n+1 before it reads block n's result, reads every block it
    enqueued, in order, and ends with nothing in flight."""
    from oryx_tpu.serve import scheduler as sched_lib

    pipe = _block_pipe(block_params)
    log, made = [], []
    real = sched_lib.generate_lib.paged_block_step

    def spy(*a, **k):
        out = real(*a, **k)
        made.append(out[1])  # the block's tokens, still on the device
        log.append(("enqueue", len(made) - 1))
        return out

    monkeypatch.setattr(sched_lib.generate_lib, "paged_block_step", spy)

    def hook(sched, handles):
        harvest = sched._harvest_block

        def harvest_and_log(flight):
            log.append(("read", next(
                i for i, t in enumerate(made) if t is flight.toks)))
            harvest(flight)

        sched._harvest_block = harvest_and_log

    reqs = [({"question": "hello there"}, 21, None),
            ({"question": "what now?"}, 30, None)]
    _, _, metrics = _serve_blocks(pipe, reqs, hook=hook)
    enq = [x for kind, x in log if kind == "enqueue"]
    read = [x for kind, x in log if kind == "read"]
    assert read == enq and len(enq) >= 8  # every block, in order
    ahead = 0
    for n, x in enumerate(read):
        before = log[:log.index(("read", x))]
        n_made = sum(1 for kind, _ in before if kind == "enqueue")
        # Block n is read with block n+1 enqueued, while one was made.
        assert n_made == min(n + 2, len(enq))
        ahead += n_made == n + 2
    assert ahead >= len(enq) - 3
    assert metrics.get("block_dispatches_ahead_total") == ahead
    assert metrics.get("block_rows_dropped_total") == 0


def test_block_run_replays_from_its_journal(block_params, tmp_path):
    """The journal's `step` events advance once a harvested block, in
    enqueue order, so a block run that dropped rows and reused a slot
    replays cold to the same decision stream and the same replies."""
    import sys
    from pathlib import Path

    from oryx_tpu.serve import journal as journal_lib

    sys.path.insert(0, str(Path(__file__).resolve().parent.parent / "scripts"))
    import replay_journal as rj

    pipe = _block_pipe(block_params)
    (free, _), _ = _block_want(pipe, {"question": "hello there"}, 16)
    pipe = _block_pipe(block_params, eos_token_id=free[5])
    path = str(tmp_path / "journal.jsonl")
    journal = journal_lib.DecisionJournal(path)
    reqs = [({"question": "hello there"}, 16, None),
            ({"question": "what now? " * 6}, 30, None),
            ({"question": "and a tail.."}, 12,
             {"temperature": 0.8, "top_p": 0.9, "seed": 5})]
    try:
        _, _, metrics = _serve_blocks(pipe, reqs, journal=journal)
    finally:
        journal.close()
    assert metrics.get("block_rows_dropped_total") >= 1
    header, entries = journal_lib.read_journal(path)
    steps = [e for e in entries
             if e["kind"] == "step" and e["dispatch"] == "block"]
    assert len(steps) == metrics.registry.counter(
        "dispatches_total", ("kind",)).labels(kind="block").value
    res = rj.run_replay(header, entries, pipe=pipe, timeout_s=300)
    assert rj.first_divergence(entries, res["entries"]) is None
    matched, total, bad = rj.reply_match(entries, res["entries"])
    assert matched == total == 3, bad
    assert not res["feed_errors"] and not res["timed_out"]


# ---------------------------------------------------------------------------
# Block mode's deferred commit (docs/DESIGN.md "Block diffusion"): block
# n's K/V is written by commit lanes of block n+1's first forward, and a
# pending block belongs to the placement that generated it
# ---------------------------------------------------------------------------


def _commit_counts(metrics):
    how = metrics.registry.counter("diffusion_commits_total", ("how",))
    return (how.labels(how="fused").value, how.labels(how="dropped").value,
            metrics.get("diffusion_blocks_total"))


def _read_one_dispatch_at_a_time(sched):
    """The same engine with no block in flight behind a harvest: every
    dispatch is read before the next is enqueued."""
    def step():
        sched._drain_flight()
        sched._inflight = sched._enqueue_block(ahead=False)
        sched._drain_flight()

    sched._block_step = step


PENDING_CASES = [
    "eos_learned_late", "cancel", "eviction_and_replay", "forced_drains",
]


@pytest.mark.parametrize("case", PENDING_CASES)
def test_pending_blocks_leave_the_streams_of_a_serial_engine(
    block_params, case
):
    """Whatever ends a placement while its block is pending (an EOS
    learned a block late, a hang-up, an eviction and its replay) and
    wherever the engine drains, the replies are byte for byte those of
    the engine that reads one dispatch at a time, and once the engine
    is idle every slot-block was committed by a fused forward or
    dropped, never both, never neither."""
    pipe = _block_pipe(block_params)
    qs = ["hello there", "what now? " * 6, "tell me more!", "and a tail.."]
    kw, skip, cancel_after = {}, (), None
    reqs = [({"question": q}, cap, None)
            for q, cap in zip(qs, (16, 40, 10, 12))]
    if case == "eos_learned_late":
        (free, _), _ = _block_want(pipe, {"question": qs[0]}, 16)
        pipe = _block_pipe(block_params, eos_token_id=free[5])
    elif case == "cancel":
        skip, cancel_after = (1,), 2
    elif case == "eviction_and_replay":
        reqs = [({"question": "a" * 40}, 60, None),
                ({"question": "b" * 40}, 60,
                 {"temperature": 0.8, "top_p": 0.9, "seed": 3})]
        kw = {"num_pages": 10}

    def serve(serial):
        def hook(sched, handles):
            if serial:
                _read_one_dispatch_at_a_time(sched)
            enqueue, step, calls = sched._enqueue_block, sched._block_step, []
            rode = []

            def enqueue_and_hang_up(ahead):
                flight = enqueue(ahead)
                rode.extend(
                    s for s in (flight.riders if flight else ())
                    if sched.slots[s].handle is handles[1])
                if len(rode) == cancel_after:
                    handles[1].cancelled = True
                return flight

            def drain_every_third():
                calls.append(0)
                if len(calls) % 3 == 0:
                    sched._drain_flight()
                step()

            if cancel_after:
                sched._enqueue_block = enqueue_and_hang_up
            if case == "forced_drains":
                sched._block_step = drain_every_third

        _, results, metrics = _serve_blocks(
            pipe, reqs, skip=skip, hook=hook, **kw)
        fused, dropped, blocks = _commit_counts(metrics)
        assert fused + dropped == blocks and fused > 0 and dropped > 0
        return results, metrics

    piped, metrics = serve(serial=False)
    serial, _ = serve(serial=True)
    assert piped == serial
    assert sum(r is not None for r in piped) == len(reqs) - len(skip)
    assert metrics.get("block_dispatches_ahead_total") > 0
    if case == "cancel":
        assert metrics.get("cancelled") == 1
    if case == "eviction_and_replay":
        assert metrics.get("evicted") >= 1
    if case == "eos_learned_late":
        assert metrics.get("block_rows_dropped_total") >= 1


@pytest.mark.parametrize("stale", [False, True])
def test_a_refilled_slot_is_not_written_by_its_last_owners_pending_block(
    block_params, stale
):
    """A request ends on an EOS learned one block late; the next one
    takes its slot, is given the pages it freed (poisoned as they are
    freed) and is prefilled while a block is in flight. The prompt's
    K/V the newcomer prefilled is byte for byte there when it finishes:
    the old request's pending block, whose commit lanes would land on
    the newcomer's last prompt block, went with its placement. `stale`
    is the engine that forgets to clear it (the pending block kept as a
    flag of the slot): the same scenario must show the pages
    overwritten and the reply changed."""
    pipe = _block_pipe(block_params)
    qs = ["hello there", "what now? " * 6, "and a tail.."]
    (free, _), n0 = _block_want(pipe, {"question": qs[0]}, 16)
    eos = free[5]
    pipe = _block_pipe(block_params, eos_token_id=eos)
    (long, _), n1 = _block_want(pipe, {"question": qs[1]}, 40)
    assert eos not in long
    reqs = [({"question": qs[1]}, 40, None), ({"question": qs[0]}, 16, None),
            ({"question": qs[2]}, 12, None)]
    pages = [-(-(n + c) // 16) + 1 for n, c in ((n0, 16), (n1, 40))]
    seen = {"first_commit": [], "poisoned": 0}

    def hook(sched, handles):
        activate, finish, free_pages = (
            sched._activate_block, sched._finish, sched._free_slot_pages)
        enqueue = sched._enqueue_block

        def prompt_kv(s, req):
            head = req.length - req.length % 4
            pos = np.arange(head)
            held = sched.bt[s, pos // 16]
            return np.stack([
                np.asarray(sched.kv_pages[p])[:, held, pos % 16]
                for p in ("k", "v")])

        def activate_and_look(s, req):
            if stale and sched.blk_pending[s] >= 0:
                sched.blk_pending[s] = req.admit_seq
            activate(s, req)
            if req.handle is handles[2]:
                seen["slot"], seen["before"] = s, prompt_kv(s, req)

        def enqueue_and_look(ahead):
            first = [
                s for s, r in enumerate(sched.slots)
                if r is not None and r.handle is handles[2] and r.activated
                and "rode" not in seen]
            flight = enqueue(ahead)
            if first:
                seen["rode"] = True
                seen["first_commit"].append(flight.fused)
                seen["riders"] = len(flight.riders)
            return flight

        def finish_and_look(s, reason, completion):
            req = sched.slots[s]
            if req.handle is handles[2]:
                seen["after"] = prompt_kv(s, req)
            finish(s, reason, completion)

        def free_and_poison(s, owner=None):
            held = [int(p) for p in sched.bt[s] if p != sched._sentinel]
            free_pages(s, owner)
            if held and sched.slots[s].handle is handles[1]:
                seen["poisoned"] = len(held)
                sched.kv_pages = {
                    k: v.at[:, np.asarray(held)].set(1e4)
                    for k, v in sched.kv_pages.items()}

        sched._activate_block = activate_and_look
        sched._finish = finish_and_look
        sched._free_slot_pages = free_and_poison
        sched._enqueue_block = enqueue_and_look
        if stale:
            sched._drop_pending = lambda s: None

    _, results, metrics = _serve_blocks(
        pipe, reqs, hook=hook, num_pages=sum(pages) + 1, prefix_cache=False)
    assert seen["poisoned"] and "after" in seen
    (want, margins), _ = _block_want(pipe, reqs[2][0], 12, eos=eos)
    got = _reply_ids(results[2][0])
    if stale:
        # The newcomer's first ride committed somebody else's block.
        assert seen["first_commit"] == [seen["riders"]]
        assert not np.array_equal(seen["after"], seen["before"])
        assert got != want[:len(got)] or len(got) != len(want)
        return
    # Its first ride commits the long request's block and not its own.
    assert seen["first_commit"] == [seen["riders"] - 1]
    np.testing.assert_array_equal(seen["after"], seen["before"])
    _assert_tokens(got, want, margins)
    fused, dropped, blocks = _commit_counts(metrics)
    assert fused + dropped == blocks


def test_block_counters_after_the_deferred_commit(block_params):
    """No forward only commits, and the series says so at 0; a commit
    is fused or dropped; a slot-block of B masked positions costs its
    slot T forwards, not T + 1."""
    pipe = _block_pipe(block_params)
    reqs = []
    for q in ("hello there", "what now? " * 6, "tell me more!"):
        while len(pipe._prepare_request({"question": q})[0]) % 4:
            q += "!"
        reqs.append(({"question": q}, 16, None))  # 4 whole blocks each
    _, results, metrics = _serve_blocks(pipe, reqs)
    assert all(reason == "length" for _, reason, _ in results)
    reg = metrics.registry
    kinds = reg.counter("diffusion_forwards_total", ("kind",))
    text = reg.render()
    assert 'diffusion_forwards_total{kind="commit"} 0' in text
    assert 'diffusion_commits_total{how="fused"}' in text
    assert 'diffusion_commits_total{how="dropped"}' in text
    fused, dropped, blocks = _commit_counts(metrics)
    assert (fused, dropped, blocks) == (9, 3, 12)  # a request's last: dropped
    T = pipe.cfg.generation.denoising_steps
    assert metrics.get("decode_steps_useful") == blocks * T
    assert metrics.get("diffusion_tokens_unmasked_total") == blocks * 4
    dispatches = reg.counter("dispatches_total", ("kind",)).labels(
        kind="block").value
    assert kinds.labels(kind="denoise").value == dispatches * T
    assert metrics.get("decode_steps_total") == dispatches * T * 2
