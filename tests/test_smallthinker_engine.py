"""SmallThinker-class decoder through the continuous split engine at
`config.smallthinker_tiny()` (window 32): what `ContinuousScheduler`
serves against benchmark/reference/smallthinker_ref.py, with the window
plane's pages released on the way, poisoned, and never read again;
eviction and replay past the window; page pressure in the window
plane; what the engine refuses. Served tokens are held to the
reference by teacher forcing: ONE reference forward over prompt +
served tokens, whose argmax at every row is the token served there
(float32 at "highest" on both sides, weights x 4: no near-ties)."""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import smallthinker_ref as ref
from oryx_tpu import config as cfg_lib
from oryx_tpu.models import oryx
from oryx_tpu.ops import paged_kv
from oryx_tpu.serve.pipeline import OryxInference
from oryx_tpu.serve.scheduler import ContinuousScheduler
from oryx_tpu.utils.metrics import ServingMetrics
from tests.test_smallthinker import keys_of

PS, CHUNK, PF = 8, 4, 16
REFUSAL = "window layers"


class IdTokenizer:
    """One id a character over 3..502; a newline (the "plain"
    template's stop string) is the three ids 1, 2, 1, which seeded
    weights do not emit in a row, so every reply runs to its cap."""

    def encode(self, text, add_special_tokens=False):
        return [t for c in text for t in (
            (1, 2, 1) if c == "\n" else (3 + (ord(c) * 7) % 500,))]

    def decode(self, ids, skip_special_tokens=True):
        return "".join(f"<{int(i)}>" for i in ids)


def _ids(reply):
    return [int(x) for x in reply.strip("<>").split("><")] if reply else []


@pytest.fixture(scope="module")
def pipe():
    cfg = cfg_lib.smallthinker_tiny()
    params = oryx.init_params(cfg, jax.random.key(0))
    params["llm"] = jax.tree_util.tree_map(
        lambda a: a * 4 if a.ndim >= 2 else a, params["llm"])
    return OryxInference(IdTokenizer(), params, cfg, template="plain")


def served_is_the_references(pipe, question, reply):
    ids, *_ = pipe._prepare_request({"question": question})
    ids, out = [int(t) for t in ids], _ids(reply)
    rows = np.asarray(ref.logits(
        pipe.params["llm"], ref.sizes_from_keys(keys_of(pipe.cfg.llm)),
        np.asarray(ids + out[:-1], np.int32),
        rows=list(range(len(ids) - 1, len(ids) + len(out) - 1))))
    assert list(rows.argmax(-1)) == out
    return len(ids)


def _engine(pipe, metrics=None, **kw):
    kw = {"num_slots": 2, "max_ctx": 256, **kw}
    return ContinuousScheduler(
        pipe, page_size=PS, chunk=CHUNK, prefill_chunk=PF, autostart=False,
        metrics=metrics, **kw)


def poison_released_pages(sched):
    """Every window page given back is overwritten with NaN at once
    (and zeroed when handed out again, as a fresh pool's pages are), so
    that a read of a freed page would show in what is served. Returns
    the list the freed pages are logged to."""
    plane, log = sched.wplane, []

    def fill(pages, value):
        idx = jnp.asarray(pages)
        sched.kv_pages = dict(sched.kv_pages, **{
            n: sched.kv_pages[n].at[:, idx].set(value)
            for n in paged_kv.WINDOW_PLANES})

    advance, alloc = plane.advance, plane.allocator.alloc

    def advance_and_poison(s, at, owner=None):
        freed = advance(s, at, owner=owner)
        if freed:
            fill(freed, jnp.nan)
            log.extend(freed)
        return freed

    def alloc_zeroed(n, owner=None):
        pages = alloc(n, owner=owner)
        if pages:
            fill(pages, 0.0)
        return pages

    plane.advance, plane.allocator.alloc = advance_and_poison, alloc_zeroed
    return log


def hold_the_page_rule(sched):
    """After any dispatch: no live lane holds a window page wholly older
    than n - W - chunk, asserted from the plane's own tables and
    allocator; and the two planes' invariants."""
    W = sched.cfg.llm.sliding_window
    seen = []

    def check():
        plane = sched.wplane
        for s, req in enumerate(sched.slots):
            held = plane.held(s)
            if req is None:
                assert not held
                continue
            n = int(sched.lengths[s]) if req.activated else req.prefill_pos
            assert int(plane.base[s]) + PS > n - W - max(PF, CHUNK)
            assert len(held) <= plane.tables.shape[1]
            seen.append(len(held))
        plane.check_invariant()

    for name in ("_step_chunk", "_advance_prefill"):
        step = getattr(sched, name)

        def hooked(*a, _step=step):
            _step(*a)
            check()

        setattr(sched, name, hooked)
    return seen


LONG = "the quick brown fox jumps over the lazy dog and runs far away " * 2
QUESTIONS = [("hello there, how are you?", 9),  # never reaches the window
             ("abc" * 9, 20),                    # crosses it during decode
             (LONG[:90], 75),                    # passes it in a chunk, > 2 W on
             ("q" * 33, 5)]


def test_engine_serves_through_both_planes_with_the_counters(pipe):
    metrics = ServingMetrics()
    sched = _engine(pipe, metrics)
    assert sched.prefix_cache is None and sched.windowed
    freed = poison_released_pages(sched)
    held = hold_the_page_rule(sched)
    sched.start()
    handles = [sched.submit({"question": q}, cap, None)
               for q, cap in QUESTIONS]
    results = [h.result(timeout=900) for h in handles]
    events = [sp["args"] for h in handles
              for sp in h.trace.to_dict()["spans"]
              if sp["name"] == "window_release"]
    sched._check_pool_invariant()
    snap = sched.pool_snapshot()
    sched.close()
    W, pairs, wpairs = pipe.cfg.llm.sliding_window, 0, 0
    for (q, cap), (reply, reason, usage) in zip(QUESTIONS, results):
        n = served_is_the_references(pipe, q, reply)
        assert reason == "length" and usage == (n, cap)
        pairs += n * (n + 1) // 2
        wpairs += sum(min(p + 1, W) for p in range(n))
    assert freed and held and max(held) <= sched.wplane.tables.shape[1]
    assert metrics.get("kv_window_pages_released_total") == len(freed)
    assert sum(e["pages"] for e in events) == len(freed)
    assert metrics.get("prefill_attn_pairs_total") == pairs
    assert metrics.get("prefill_window_attn_pairs_total") == wpairs < pairs
    assert 0 < metrics.get("decode_window_kv_tokens_total") \
        < metrics.get("decode_kv_tokens_total")
    assert metrics.get("moe_experts_hit_total") > 0
    assert metrics.get("moe_expert_rows_max_total") \
        >= metrics.get("moe_expert_rows_mean_total") > 0
    win = snap["window_plane"]
    assert win["num_free"] == win["num_pages"] and snap["num_free"] == \
        snap["num_pages"]  # every page of both planes came back
    assert snap["kv_pool_bytes"] == sum(
        x.size * x.dtype.itemsize for x in jax.tree.leaves(
            sched._new_pool()))


def test_pallas_serves_what_xla_serves(pipe):
    replies = {}
    for impl in ("xla", "pallas"):
        served = OryxInference(
            IdTokenizer(), pipe.params,
            dataclasses.replace(pipe.cfg, attn_impl=impl), template="plain")
        sched = _engine(served)
        sched.start()
        handles = [sched.submit({"question": q}, cap, None)
                   for q, cap in ((LONG[:50], 30), ("w" * 21, 3))]
        replies[impl] = [h.result(timeout=900)[0] for h in handles]
        sched.close()
    assert replies["pallas"] == replies["xla"]
    served_is_the_references(pipe, LONG[:50], replies["pallas"][0])


def test_eviction_and_replay_past_the_window(pipe):
    """A lane evicted after it has given pages back replays from token
    0 through both planes and streams the same tokens, each once."""
    sched = _engine(pipe)
    poison_released_pages(sched)
    q, cap = LONG[:60], 40
    evicted = []
    step = sched._step_chunk

    def evict_once():
        step()
        if not evicted and sched.slots[0] is not None \
                and sched.slots[0].activated and sched.wplane.base[0] > 0:
            evicted.append(int(sched.wplane.base[0]))
            sched._evict(0)
            assert not sched.wplane.held(0) and sched.wplane.base[0] == 0

    sched._step_chunk = evict_once
    sched.start()
    reply, reason, _ = sched.submit(
        {"question": q}, cap, None).result(timeout=900)
    sched._check_pool_invariant()
    sched.close()
    assert evicted and reason == "length"
    served_is_the_references(pipe, q, reply)


def test_page_pressure_in_both_planes_evicts_the_youngest(pipe):
    """A window plane of 12 pages under three lanes that each come to
    hold 5-6 as they decode (the global plane has room): all three are
    admitted, the plane runs short, the youngest lane is evicted from
    BOTH planes and replays, and what is served is still the
    reference's."""
    metrics = ServingMetrics()
    sched = _engine(pipe, metrics, num_slots=3, num_pages=54)
    assert sched.num_window_pages == 12
    poison_released_pages(sched)
    hold_the_page_rule(sched)
    sched.start()
    asks = [("ab", 60), ("xy", 55), ("hello", 50)]  # 2 pages each at first
    handles = [sched.submit({"question": q}, cap, None) for q, cap in asks]
    results = [h.result(timeout=900) for h in handles]
    sched._check_pool_invariant()
    sched.close()
    for (q, cap), (reply, reason, _) in zip(asks, results):
        assert reason == "length"
        served_is_the_references(pipe, q, reply)
    assert metrics.get("evicted") > 0


def test_out_of_pages_names_the_plane():
    plane = paged_kv.WindowPlane(4, 8, 2, 6, 32)
    assert plane.grow(0, 30) and not plane.grow(1, 10)
    with pytest.raises(paged_kv.OutOfPagesError, match="window plane"):
        plane.allocator.alloc(1)
    assert plane.advance(0, 45) == [0] and plane.base[0] == 8
    assert plane.grow(1, 8)
    plane.release(0)
    plane.check_invariant()
    assert paged_kv.window_table_pages(4096, 1024, 64) == 81


@pytest.mark.parametrize("kw", [
    {"ragged": True}, {"ragged": True, "speculate": 2},
    {"kv_dtype": "int8"},
    {"host_cache_bytes": 1 << 20}, {"audit_sample_every": 4},
    {"prefill_chunk": None},
])
def test_the_engine_refuses_what_is_not_built_for_two_planes(pipe, kw):
    with pytest.raises(ValueError, match=REFUSAL):
        ContinuousScheduler(pipe, **{
            "num_slots": 2, "page_size": PS, "max_ctx": 256,
            "prefill_chunk": PF, "autostart": False, **kw})


@pytest.mark.parametrize("option", [{"numerics_every": 1}])
def test_the_engine_serves_what_it_does_not_refuse(
        pipe, option, serves_like_the_default):
    """The probe reads the decode chunk's logits, whatever made them: a
    reply long enough that the window plane gives pages back."""
    serves_like_the_default(
        lambda **kw: _engine(pipe, **kw), option,
        "a question of some length, longer than a window", 12)
