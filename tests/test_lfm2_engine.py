"""LFM2 at `lfm2_tiny` (the ten-layer cut) through the continuous split
engine on the CPU: replies against the plain reference's greedy
continuation, the prefix cache ON over a conv state (a hit starts from
the page-edge snapshot and serves what the cold prompt serves, for pages
written by prefill and by decode; lanes that share a page; a page that
was evicted and reused), the conv_* counters, and what the engine still
refuses, by name."""

import dataclasses

import numpy as np
import pytest

import jax

from benchmark.reference import lfm2_ref as ref
from oryx_tpu import config as cfg_lib
from oryx_tpu.models import oryx
from oryx_tpu.serve.pipeline import OryxInference
from oryx_tpu.serve.scheduler import ContinuousScheduler
from oryx_tpu.utils.metrics import ServingMetrics

from test_lfm2 import REFUSAL, scaled, sizes_of

PS = 16


BASE = 0x4E00  # an emitted id is ONE character, which encodes back to it


class IdTokenizer:
    """One id a character in, one character a token out, and a reply
    re-sent as history has the ids the engine emitted: so the next turn
    of a session hits the pages its reply's decode steps filled."""

    def encode(self, text, add_special_tokens=False):
        return [ord(c) - BASE if ord(c) >= BASE else 3 + (ord(c) * 7) % 490
                for c in text]

    def decode(self, ids, skip_special_tokens=True):
        return "".join(chr(BASE + int(i)) for i in ids)


def _ids(reply):
    return [ord(c) - BASE for c in reply]


@pytest.fixture(scope="module")
def pipe():
    cfg = cfg_lib.lfm2_tiny()
    cfg = dataclasses.replace(
        cfg, llm=dataclasses.replace(cfg.llm, num_layers=10))
    params = oryx.init_params(cfg, jax.random.key(0))
    params["llm"] = scaled(params["llm"])
    return OryxInference(IdTokenizer(), params, cfg, template="plain")


def _want(pipe, question, cap):
    ids, *_ = pipe._prepare_request({"question": question})
    seq = [int(t) for t in ids]
    sz = sizes_of(pipe.cfg.llm)
    for _ in range(cap):
        row = np.asarray(ref.logits(
            pipe.params["llm"], sz, np.asarray(seq, np.int32),
            rows=[len(seq) - 1]))[0]
        seq.append(int(row.argmax()))
    return seq[len(ids):], len(ids)


def _engine(pipe, metrics=None, **kw):
    return ContinuousScheduler(pipe, **{
        "num_slots": 2, "page_size": PS, "max_ctx": 256, "prefill_chunk": 24,
        "chunk": 4, "autostart": False, "metrics": metrics, **kw})


def _ask(sched, question, cap):
    return sched.submit({"question": question}, cap, None).result(timeout=600)


QUESTIONS = [("hello there, how are you doing today my friend?", 9),
             ("abc" * 20, 7), ("zzz tell me a story", 12), ("q" * 33, 5)]
SYSTEM = "you are a tool-calling agent; the tools are: search, run, read. "


def test_engine_serves_four_requests_on_two_slots_with_the_counters(pipe):
    """Four requests over two slots (each slot is reused, a request
    prefills in one while the other decodes): every reply is the
    reference's greedy continuation, the prefix cache is ON, and the
    conv_* counters say what ran."""
    metrics = ServingMetrics()
    sched = _engine(pipe, metrics)
    assert sched.prefix_cache is not None and sched.conv_state
    sched.start()
    handles = [sched.submit({"question": q}, cap, None)
               for q, cap in QUESTIONS]
    results = [h.result(timeout=600) for h in handles]
    sched.close()
    prompt = 0
    for (q, cap), (reply, reason, usage) in zip(QUESTIONS, results):
        want, n = _want(pipe, q, cap)
        prompt += n
        assert reason == "length" and usage == (n, cap)
        assert _ids(reply) == want
    assert metrics.get("conv_prefill_tokens_total") == prompt
    assert metrics.get("conv_state_resets_total") == len(QUESTIONS)
    assert metrics.get("conv_state_handovers_total") == 0
    steps = metrics.get("conv_decode_lane_steps_total")
    out = sum(cap for _, cap in QUESTIONS)
    assert out - len(QUESTIONS) <= steps <= 4 * (out // 4 + len(QUESTIONS))
    assert metrics.get("decode_kv_tokens_total") > steps
    assert metrics.get("moe_experts_hit_total") > 0
    assert metrics.get("conv_edge_writes_total") >= sum(
        _want(pipe, q, 0)[1] // PS for q, _ in QUESTIONS)
    llm = pipe.cfg.llm
    assert metrics.get("conv_state_bytes") == 2 * llm.state_bytes_per_slot(4)
    assert metrics.get("conv_edge_bytes") == (
        sched.num_pages * llm.state_bytes_per_slot(4))
    assert llm.state_bytes_per_slot(4) == 8 * 2 * llm.hidden_size * 4
    assert not metrics.get("ssm_state_bytes")


@pytest.mark.parametrize("kind", ["prefilled", "decoded", "inside_a_page"])
def test_a_prompt_served_after_a_hit_is_the_prompt_served_cold(pipe, kind):
    """`prefilled`: the same 70-token prompt twice, the second a hit on
    four pages its twin's prefill wrote. `decoded`: a session's second
    turn re-sends the first turn and the engine's own reply, whose
    pages decode steps filled. `inside_a_page`: a prompt that ends
    inside a cached page prefills that page's tokens again (a hit is
    cut back to the page edge, nothing is copied). Each is served by a
    fresh engine too, cold, and the replies are the same and the
    reference's."""
    first = SYSTEM + "what is in the working directory?"
    cap = 40 if kind == "decoded" else 6
    metrics = ServingMetrics()
    sched = _engine(pipe, metrics)
    sched.start()
    reply1, _, (n1, _) = _ask(sched, first, cap)
    if kind == "prefilled":
        second = first
    elif kind == "inside_a_page":
        second = first[:len(first) - 5]  # ends inside the fourth page
    else:
        second = None
    if second is not None:
        request = {"question": second}
    else:
        # The history as the engine served it: ids, not text.
        request = {"question": "and what is in the file named notes?",
                   "history": [(first, reply1)]}
    if kind == "decoded":
        ids, *_ = pipe._prepare_request(request)
        assert len(ids) > n1 + cap - 1
    hits0 = metrics.get("prefix_cache_hit_tokens_total")
    got = sched.submit(request, 8, None).result(timeout=600)
    hit = metrics.get("prefix_cache_hit_tokens_total") - hits0
    sched.close()
    cold = _engine(pipe)
    cold.start()
    want = cold.submit(request, 8, None).result(timeout=600)
    cold.close()
    assert got == want
    assert hit > 0 and hit % PS == 0
    assert metrics.get("conv_state_handovers_total") == 1
    assert metrics.get("conv_state_resets_total") == 1
    if kind == "prefilled":
        assert hit == (n1 - 1) // PS * PS
        assert _ids(got[0]) == _want(pipe, second, 8)[0]
    if kind == "inside_a_page":
        n2 = n1 - 5
        assert hit == (n2 - 1) // PS * PS < n2 - 1
        assert _ids(got[0]) == _want(pipe, second, 8)[0]
    if kind == "decoded":
        # The hit reaches past the first prompt into pages that decode
        # steps filled.
        assert hit >= (n1 + PS) // PS * PS
    sched._check_pool_invariant()


def test_two_lanes_that_share_a_prefix_start_alike_and_stay_apart(pipe):
    """A shared 64-token head (four pages) is cached; two requests that
    extend it differently are served at once, both from the same
    snapshot, while the other decodes beside: each reply is the
    reference's."""
    head = (SYSTEM * 2)[:62]
    sched = _engine(pipe, ServingMetrics())
    sched.start()
    _ask(sched, head, 2)
    tails = [(head + " list the files", 10), (head + " print the date!", 10)]
    handles = [sched.submit({"question": q}, cap, None) for q, cap in tails]
    results = [h.result(timeout=600) for h in handles]
    assert sched.metrics.get("conv_state_handovers_total") == 2
    sched.close()
    for (q, cap), (reply, reason, _) in zip(tails, results):
        assert _ids(reply) == _want(pipe, q, cap)[0]
    assert results[0][0] != results[1][0]


def test_a_page_that_was_evicted_and_reused_hands_no_stale_edge_over(pipe):
    """A pool of 12 pages: the first prompt's pages are cached, evicted
    and taken by another prompt, whose own pages are then cached; the
    first prompt again is a miss (or a hit on what it wrote anew) and
    is served as a fresh engine serves it."""
    metrics = ServingMetrics()
    sched = _engine(pipe, metrics, num_slots=1, num_pages=12, max_ctx=128)
    a = SYSTEM + "alpha alpha alpha alpha alpha alpha alpha"
    b = "an unrelated prompt " * 5
    sched.start()
    first = _ask(sched, a, 6)
    cache = sched.prefix_cache
    assert cache.pages > 0
    cache.evict(cache.evictable_pages())
    assert cache.pages == 0
    _ask(sched, b, 6)  # takes the freed pages, leaves its own snapshots
    again = _ask(sched, a, 6)
    half = _ask(sched, a[:len(a) // 2] + " beta gamma delta", 6)
    sched.close()
    assert again == first
    assert _ids(first[0]) == _want(pipe, a, 6)[0]
    assert _ids(half[0]) == _want(
        pipe, a[:len(a) // 2] + " beta gamma delta", 6)[0]
    assert metrics.get("prefix_cache_evicted_pages_total") > 0


def test_eviction_and_replay_reproduce_the_stream(pipe):
    """A request evicted mid-decode re-prefills (from its own cached
    pages' snapshot where the cache has them, else from zeros) and
    streams the same tokens, each once."""
    sched = _engine(pipe)
    q, cap = SYSTEM + "tell me about short convolutions please", 20
    want, _ = _want(pipe, q, cap)
    evicted = []
    step = sched._step_chunk

    def evict_once():
        step()
        if not evicted and sched.slots[0] is not None \
                and sched.slots[0].activated:
            evicted.append(sched.slots[0].processed)
            sched._evict(0)

    sched._step_chunk = evict_once
    sched.start()
    reply, reason, usage = _ask(sched, q, cap)
    sched.close()
    assert evicted and reason == "length"
    assert _ids(reply) == want


@pytest.mark.parametrize("kw", [
    {"ragged": True}, {"ragged": True, "speculate": 2},
    {"kv_dtype": "int8"},
    {"host_cache_bytes": 1 << 20}, {"audit_sample_every": 4},
])
def test_the_engine_refuses_what_is_not_built_for_a_state(pipe, kw):
    with pytest.raises(ValueError, match=REFUSAL):
        _engine(pipe, **kw)


@pytest.mark.parametrize("option", [
    {"numerics_every": 1}, {"prefill_chunk": None}, {"prefix_cache": False},
])
def test_the_engine_serves_what_it_does_not_refuse(
        pipe, option, serves_like_the_default):
    serves_like_the_default(
        lambda **kw: _engine(pipe, **kw), option, QUESTIONS[0][0], 8)


def test_a_mamba_hybrid_keeps_its_cache_off_and_its_refusal():
    cfg = cfg_lib.jamba_tiny()
    params = oryx.init_params(cfg, jax.random.key(0))
    jam = OryxInference(IdTokenizer(), params, cfg, template="plain")
    sched = _engine(jam, prefill_chunk=16)
    assert sched.prefix_cache is None and not sched.conv_state
    with pytest.raises(ValueError, match=REFUSAL):
        sched._build_prefix_cache()
