"""Continuous-batching scheduler: slot freeing + admission at chunk
boundaries, FIFO no-starvation, page-pressure eviction with
deterministic replay, and the wasted-step microbench as a slow test."""

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
    # where request 1 finished (no full-batch drain in between).
    finish_1 = handles[0].debug["finish_chunk"]
    admit_3 = handles[2].debug["admit_chunk"]
    assert admit_3 <= finish_1, (admit_3, finish_1)
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
