"""The split engine keeps ONE decode chunk in flight (PR 47; docs/
DESIGN.md "One dispatch in flight"): chunk n+1 is enqueued before chunk
n is read. Over the four kinds of pool the split engine serves (per-head
K/V, a latent plane, K/V beside a recurrent state a slot, two planes
with a window table), greedy and seeded-sampled:

(a) every stream is token for token what the SERIAL engine serves (the
    same scheduler with every chunk read before the next is enqueued,
    the order before PR 47), whatever ends a request and whoever takes
    its slot;
(b) the enqueue of chunk n+1 precedes the read of chunk n
    (`decode_dispatches_ahead_total` = decode dispatches less the
    enqueues that found nothing unread), and idle, `drain()`,
    `close()`, a deadline, an eviction and a capture leave nothing
    unread;
(c) after a late stop with a chunk in flight the pool's invariant
    holds and no donated page covers a position at or past the length
    the device confirmed.

The order is hooked with the enqueue / harvest functions on the
instance (as the block tests do), never with a clock."""

from __future__ import annotations

import dataclasses
import time

import jax
import pytest

from oryx_tpu import config as cfg_lib
from oryx_tpu.models import oryx
from oryx_tpu.serve.pipeline import OryxInference
from oryx_tpu.serve.scheduler import ContinuousScheduler
from oryx_tpu.utils.metrics import ServingMetrics

PS, CHUNK = 8, 4
KINDS = {
    "plain": cfg_lib.oryx_tiny,
    "latent": cfg_lib.longcat_tiny,
    "recurrent": cfg_lib.jamba_tiny,
    "window": cfg_lib.smallthinker_tiny,
}
SAMPLED = {"temperature": 0.8, "top_p": 0.9, "seed": 11}
QS = ["hello there", "what now? " * 4, "tell me more!", "and a tail.."]


class IdTokenizer:
    """One id a character over 3..502, `<id>` per token out; a newline
    (the "plain" template's stop string) is the three ids 1, 2, 1,
    which seeded weights do not emit in a row."""

    def encode(self, text, add_special_tokens=False):
        return [t for c in text for t in (
            (1, 2, 1) if c == "\n" else (3 + (ord(c) * 7) % 500,))]

    def decode(self, ids, skip_special_tokens=True):
        return "".join(f"<{int(i)}>" for i in ids)


def _ids(reply):
    return [int(x) for x in reply.strip("<>").split("><")] if reply else []


@pytest.fixture(scope="module")
def pipes():
    """kind, eos -> pipeline; one set of weights a kind (x 4: at the
    init scale every greedy reply is one token repeated)."""
    params, made = {}, {}

    def get(kind, eos=None):
        if (kind, eos) not in made:
            cfg = KINDS[kind]()
            if kind not in params:
                p = oryx.init_params(cfg, jax.random.key(0))
                p["llm"] = jax.tree_util.tree_map(
                    lambda a: a * 4 if a.ndim >= 2 else a, p["llm"])
                params[kind] = p
            if eos is not None:
                cfg = dataclasses.replace(cfg, generation=dataclasses.replace(
                    cfg.generation, eos_token_id=int(eos)))
            made[kind, eos] = OryxInference(
                IdTokenizer(), params[kind], cfg, template="plain")
        return made[kind, eos]

    return get


def _engine(pipe, metrics=None, **kw):
    kw = {"num_slots": 2, "max_ctx": 256, "prefill_chunk": 16, **kw}
    return ContinuousScheduler(
        pipe, page_size=PS, chunk=CHUNK, autostart=False, metrics=metrics,
        **kw)


def make_serial(sched):
    """Every chunk is read before the next is enqueued: the order the
    split engine had before PR 47."""
    enqueue = sched._enqueue_chunk

    def enqueue_and_read(ahead):
        assert not ahead
        sched._inflight = enqueue(ahead)
        sched._drain_flight()
        return None

    sched._enqueue_chunk = enqueue_and_read


def _serve(pipe, reqs, *, serial=False, hook=None, skip=(), **kw):
    """`reqs` (question, cap, sampling) through a two-slot split engine,
    submitted up front. The engine must end idle with nothing unread
    and every page accounted for."""
    metrics = ServingMetrics()
    sched = _engine(pipe, metrics, **kw)
    handles = [sched.submit({"question": q}, cap, s) for q, cap, s in reqs]
    if serial:
        make_serial(sched)
    if hook is not None:
        hook(sched, handles)
    sched.start()
    results = [
        None if i in skip else h.result(timeout=600)
        for i, h in enumerate(handles)
    ]
    deadline = time.monotonic() + 30
    while time.monotonic() < deadline and (
        sched._inflight is not None or sched._first
        or any(r is not None for r in sched.slots)
    ):
        time.sleep(0.01)
    idle = sched._inflight is None and not sched._first and all(
        r is None for r in sched.slots)
    sched.close()
    assert idle, "the engine did not go idle with nothing unread"
    sched._check_pool_invariant()
    return handles, results, metrics, sched


_FREE = {}


def _free_stream(pipes, kind, q, sampling, n=16):
    """Request q's first n tokens with no EOS in the way, served alone
    by the serial engine."""
    key = (kind, q, bool(sampling))
    if key not in _FREE:
        _, ((reply, _, _),), _, _ = _serve(
            pipes(kind), [(q, n, sampling)], serial=True)
        _FREE[key] = _ids(reply)
        assert len(_FREE[key]) == n
    return _FREE[key]


def _first_at(stream, lo, hi):
    """A position in [lo, hi) whose token does not occur before it."""
    return next(i for i in range(lo, hi) if stream.index(stream[i]) == i)


CASES = [
    "eos_inside_a_chunk", "max_tokens_inside_a_chunk",
    "max_tokens_on_a_chunk_edge", "max_tokens_1", "first_token_is_eos",
    "stop_string_inside_a_chunk", "cancel_mid_decode",
    "eviction_and_replay", "slot_readmitted_behind_an_unread_chunk",
]


@pytest.mark.parametrize("mode", ["greedy", "sampled"])
@pytest.mark.parametrize("case", CASES)
@pytest.mark.parametrize("kind", list(KINDS))
def test_streams_with_a_chunk_in_flight_are_the_serial_engines(
    pipes, kind, case, mode
):
    sampling = dict(SAMPLED) if mode == "sampled" else None
    eos, skip, kw = None, (), {}
    # Chunk 1 emits the first token again and three more: a chunk's
    # edge is at 4, 8, 12 ... tokens.
    caps = [9, 14, 7, 6]
    stops = [None] * 4
    if case == "eos_inside_a_chunk":
        free = _free_stream(pipes, kind, QS[0], sampling)
        eos = free[_first_at(free, 5, 8)]  # inside the second chunk
        caps = [16, 14, 7, 6]
    elif case == "max_tokens_inside_a_chunk":
        caps = [6, 14, 7, 5]
    elif case == "max_tokens_on_a_chunk_edge":
        caps = [8, 12, 4, 8]
    elif case == "max_tokens_1":
        caps = [1, 9, 1, 5]
    elif case == "first_token_is_eos":
        eos = _free_stream(pipes, kind, QS[2], sampling)[0]
    elif case == "stop_string_inside_a_chunk":
        free = _free_stream(pipes, kind, QS[0], sampling)
        stops[0] = f"<{free[_first_at(free, 5, 7)]}>"
        caps = [16, 14, 7, 6]
    elif case == "cancel_mid_decode":
        caps = [24, 14, 7, 6]
        skip = (0,)
    elif case == "eviction_and_replay":
        caps = [20, 18, 5, 4]
    else:
        # The short request ends on an EOS only the harvest knows, with
        # the long one live beside it: nothing drains, and the third
        # takes the slot while the old lane's chunk is still unread.
        free = _free_stream(pipes, kind, QS[0], sampling)
        eos = free[_first_at(free, 5, 8)]
        caps = [16, 40, 7, 6]
    pipe = pipes(kind, eos)
    reqs = []
    for q, cap, stop in zip(QS, caps, stops):
        s = dict(sampling) if sampling else None
        if stop:
            s = {**(s or {}), "stop": [stop]}
        reqs.append((q, cap, s))
    if case == "slot_readmitted_behind_an_unread_chunk":
        reqs = [reqs[1], reqs[0], reqs[2], reqs[3]]
        # A short prompt at the queue's head: one prefill chunk, so it
        # rides the enqueue that follows the harvest that freed its slot.
        kw = {"prefix_cache": False} if kind in ("plain", "latent") else {}

    took_over, evicted_with = [], []

    def watch(sched, handles):
        harvest = sched._harvest_chunk

        def harvest_and_look(flight):
            took_over.extend(
                s for s, seq in flight.riders.items()
                if sched.slots[s] is not None
                and sched.slots[s].admit_seq != seq
            )
            harvest(flight)

        sched._harvest_chunk = harvest_and_look
        if case == "cancel_mid_decode":
            enqueue, rode = sched._enqueue_chunk, []

            def enqueue_then_hang_up(ahead):
                flight = enqueue(ahead)
                rode.extend(
                    s for s in (flight.riders if flight else ())
                    if sched.slots[s].handle is handles[0]
                )
                if len(rode) == 3:
                    handles[0].cancelled = True
                return flight

            sched._enqueue_chunk = enqueue_then_hang_up
        if case == "eviction_and_replay":
            grow, evict = sched._grow_slot, sched._evict

            def grow_short_once(s, tokens, req=None):
                # The oldest lane cannot grow while two lanes decode
                # with a chunk unread: the chunk is read, THEN the
                # youngest is evicted and replays.
                live = [r for r in sched.slots
                        if r is not None and r.activated]
                if (not evicted_with and req is None and len(live) == 2
                        and sched.slots[s].processed >= 5
                        and sched.slots[s] is min(
                            live, key=lambda r: r.admit_seq)):
                    return False
                return grow(s, tokens, req)

            def evict_and_note(s):
                evicted_with.append(sched._inflight is not None)
                evict(s)

            sched._grow_slot, sched._evict = grow_short_once, evict_and_note

    _, want, _, _ = _serve(pipe, reqs, serial=True, skip=skip,
                           hook=watch if skip else None, **kw)
    handles, got, metrics, _ = _serve(
        pipe, reqs, hook=watch, skip=skip, **kw)
    for i, (w, g) in enumerate(zip(want, got)):
        if i in skip:
            assert not handles[i].done.is_set()
            continue
        assert g == w, (i, g, w)
    reg = metrics.registry
    chunks = reg.counter("dispatches_total", ("kind",)).labels(
        kind="decode").value
    ahead = metrics.get("decode_dispatches_ahead_total")
    dropped = metrics.get("decode_rows_dropped_total")
    assert 0 < ahead < chunks
    reasons = [r[1] for i, r in enumerate(got) if i not in skip]
    if case in ("eos_inside_a_chunk", "first_token_is_eos",
                "stop_string_inside_a_chunk"):
        assert "stop" in reasons
    if case == "eos_inside_a_chunk":
        assert len(_ids(got[0][0])) in (5, 6, 7) and got[0][1] == "stop"
    if case == "first_token_is_eos":
        assert got[2] == ("", "stop", (got[2][2][0], 1))
    if case == "max_tokens_1":
        assert [len(_ids(got[i][0])) for i in (0, 2)] == [1, 1]
    if case.startswith("max_tokens"):
        # The host counts max_tokens: nobody rides a chunk for nothing.
        assert dropped == 0 and set(reasons) == {"length"}
    if case == "stop_string_inside_a_chunk":
        # Learned one chunk late: the lane's next chunk was enqueued.
        assert got[0][1] == "stop" and dropped >= 1
    if case == "cancel_mid_decode":
        assert metrics.get("cancelled") == 1 and dropped == 1
    if case == "eviction_and_replay":
        # The chunk in flight is read before anyone is evicted.
        assert metrics.get("evicted") >= 1 and evicted_with == [False]
    if case == "slot_readmitted_behind_an_unread_chunk":
        assert took_over and dropped >= len(took_over)


# ---- (b) the order, and what drains --------------------------------------


@pytest.mark.parametrize("kind", list(KINDS))
def test_chunk_n_plus_1_is_enqueued_before_chunk_n_is_read(pipes, kind):
    """One long request: every decode enqueue but the first finds the
    chunk before it unread, each harvest but the last has a chunk
    behind it, and the engine ends idle with nothing in flight."""
    order = []

    def hook(sched, handles):
        enqueue, harvest = sched._enqueue_chunk, sched._harvest_chunk

        def enq(ahead):
            flight = enqueue(ahead)
            order.append(("enqueue", ahead, flight is not None))
            return flight

        def har(flight):
            order.append(("harvest", sched._inflight is not None, True))
            harvest(flight)

        sched._enqueue_chunk, sched._harvest_chunk = enq, har

    _, ((reply, reason, _),), metrics, _ = _serve(
        pipes(kind), [(QS[0], 21, None)], hook=hook)
    assert len(_ids(reply)) == 21 and reason == "length"
    # 21 tokens: the first at activation, then chunks of 4 (the first
    # chunk emits the first token again): 6 chunks.
    enq = [o for o in order if o[0] == "enqueue" and o[2]]
    har = [o for o in order if o[0] == "harvest"]
    assert len(enq) == len(har) == 6
    assert [a for _, a, _ in enq] == [False] + [True] * 5
    assert [behind for _, behind, _ in har] == [True] * 5 + [False]
    kinds = [o[0] for o in order if o[2]]
    assert kinds[:3] == ["enqueue", "enqueue", "harvest"]
    assert metrics.get("decode_dispatches_ahead_total") == 5
    assert metrics.get("decode_rows_dropped_total") == 0
    assert metrics.get("decode_steps_total") == 6 * 2 * CHUNK


DRAINS = ["idle", "drain", "close", "deadline", "eviction", "capture"]


@pytest.mark.parametrize("how", DRAINS)
def test_rare_paths_leave_no_chunk_unread(pipes, how):
    """Each path that must see the engine as a step without a chunk in
    flight leaves it reads the chunk first: none is left in
    `_inflight`, and no first token unread."""
    pipe = pipes("plain")
    metrics = ServingMetrics()
    sched = _engine(pipe, metrics, **(
        {"profile_sample_every": 3} if how == "capture" else {}))
    seen = {"ahead": 0, "alone": 0}
    enqueue = sched._enqueue_chunk

    def enq(ahead):
        flight = enqueue(ahead)
        if flight is not None:
            seen["ahead" if ahead else "alone"] += 1
            if flight.captured:
                # A captured chunk is alone in its window.
                assert not ahead
        return flight

    sched._enqueue_chunk = enq
    h = sched.submit({"question": QS[0]}, 40, None)
    if how == "eviction":
        grow = sched._grow_slot
        fired = []

        def grow_short_once(s, tokens, req=None):
            # The older lane cannot grow until the younger is evicted;
            # the first refusal finds a chunk unread.
            live = [r for r in sched.slots if r is not None and r.activated]
            if (req is None and len(live) == 2
                    and not metrics.get("evicted")
                    and sched.slots[s] is min(
                        live, key=lambda r: r.admit_seq)
                    and (fired or sched._inflight is not None)):
                fired.append(sched._inflight is not None)
                return False
            return grow(s, tokens, req)

        sched._grow_slot = grow_short_once
        h2 = sched.submit({"question": QS[2]}, 30, None)
    sched.start()
    if how == "drain":
        while not metrics.get("decode_dispatches_ahead_total"):
            time.sleep(0.005)
        assert sched.drain(timeout=120)
        assert h.result(timeout=5)[1] == "length"
    elif how == "close":
        while not metrics.get("decode_dispatches_ahead_total"):
            time.sleep(0.005)
        sched.close()
        assert sched._inflight is None and not sched._first
        return
    elif how == "deadline":
        while not metrics.get("decode_dispatches_ahead_total"):
            time.sleep(0.005)
        # Past its deadline from now on: `_enforce_deadlines` reads
        # the chunk in flight before it sets the error.
        h_req = next(r for r in sched.slots if r is not None)
        h_req.deadline = time.monotonic() - 1
        with pytest.raises(RuntimeError, match="deadline exceeded"):
            h.result(timeout=120)
    else:
        assert h.result(timeout=600)[1] == "length"
        if how == "eviction":
            assert h2.result(timeout=600)[1] == "length"
            assert metrics.get("evicted") >= 1
            # read first (the refusal that found it), then evicted
            assert fired[0] and not fired[-1]
    deadline = time.monotonic() + 30
    while time.monotonic() < deadline and sched.alive() and (
        sched._inflight is not None
        or any(r is not None for r in sched.slots)
    ):
        time.sleep(0.01)
    assert sched._inflight is None and not sched._first
    assert all(r is None for r in sched.slots)
    sched.close()
    sched._check_pool_invariant()
    assert seen["ahead"] > 0
    assert metrics.get("decode_dispatches_ahead_total") == seen["ahead"]
    if how == "capture":
        assert seen["alone"] >= 2  # the first, and every third dispatch
        assert metrics.get("decode_rows_dropped_total") == 0


# ---- (c) pages behind a late stop ----------------------------------------


@pytest.mark.parametrize("kind", ["plain", "latent"])
def test_a_late_stop_donates_nothing_past_the_confirmed_length(pipes, kind):
    """A reply ended by a stop string is learned from the harvest of
    chunk n with chunk n+1 enqueued, which writes K/V past the length
    chunk n confirmed, into pages `_finish` frees. What `_finish`
    donates ends at or before that length, the pool's invariant holds
    there, and the next turn, which splices the donated pages, is
    served what the serial engine serves."""
    free = _free_stream(pipes, kind, "the same opening words, " * 2, None,
                        n=24)
    pipe = pipes(kind)
    q = "the same opening words, " * 2
    at = _first_at(free, 13, 19)
    first = pipe.tokenizer.decode(free[:at])
    stop = {"stop": [f"<{free[at]}>"]}
    reqs = [(QS[1], 40, None), (q, 24, stop), (q + first + "and then?", 9,
                                               None)]
    donated = []

    def hook(sched, handles):
        finish, donate = sched._finish, sched._donate_prefix

        def donate_and_look(s, req, tokens):
            donated.append((req.handle, tokens, int(sched.confirmed[s]),
                            int(sched.lengths[s]),
                            sched._inflight is not None))
            donate(s, req, tokens)

        def finish_and_check(s, reason, completion):
            finish(s, reason, completion)
            sched._check_pool_invariant()

        sched._donate_prefix, sched._finish = donate_and_look, finish_and_check

    _, want, _, _ = _serve(pipe, reqs, serial=True)
    handles, got, metrics, _ = _serve(pipe, reqs, hook=hook)
    assert got == want and got[1][1] == "stop"
    assert _ids(got[1][0]) == free[:at]
    at_finish = [d for d in donated if d[0] is handles[1]][-1]
    _, tokens, confirmed, advanced, inflight = at_finish
    assert inflight and tokens <= confirmed < advanced
    assert metrics.get("decode_rows_dropped_total") >= 1
    assert metrics.get("prefix_cache_hit_tokens_total") > 0
