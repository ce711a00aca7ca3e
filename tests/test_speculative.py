"""Speculative decoding on the ragged paged engine: self-drafted
multi-token steps, verified in ONE dispatch (ROADMAP item "speculative
decoding"; docs/DESIGN.md "Speculative decoding").

Three layers of proof, all against the machinery speculation rides on:

  * unit level — the `NgramDrafter` prompt-lookup proposer is a
    deterministic function of the context; `spec_verify_rows` accepts
    exactly the greedy argmax prefix for temperature==0, and for
    temperature>0 its emitted-token marginal is EXACTLY the truncated
    target distribution (point-mass rejection sampling, checked
    empirically against the analytic distribution).
  * op level — `spec_lane_metadata` routes a slot's 1+k verify lanes
    through the SAME packed (segment, position) contract as the ragged
    kernel's prefill-suffix lanes.
  * engine level — `ContinuousScheduler(speculate=k)` replies are
    BYTE-identical to the plain ragged engine and the solo pipeline
    across mixed lengths, page-boundary prompts, prefix-cache COW
    splices, eviction replay, and a tp=2 mesh, while
    oryx_serving_dispatches_total shows kind="spec" ONLY; rejected
    drafts (page boundaries included) leak zero pages; stop strings
    spanning a multi-token accept truncate and bill exactly; and
    temperature>0 runs are seed-deterministic and replay-stable.
"""

import math

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from oryx_tpu import config as cfg_lib
from oryx_tpu.models import generate as gen_lib
from oryx_tpu.models import oryx
from oryx_tpu.ops import paged_kv
from oryx_tpu.serve.pipeline import OryxInference
from oryx_tpu.serve.scheduler import ContinuousScheduler
from oryx_tpu.utils.metrics import ServingMetrics


class FakeTokenizer:
    def encode(self, text, add_special_tokens=False):
        return [min(ord(c), 500) for c in text]

    def decode(self, ids, skip_special_tokens=True):
        return "".join(chr(i) for i in ids if 0 < i < 500)


# ---------------------------------------------------------------------------
# Drafter unit level
# ---------------------------------------------------------------------------


def test_ngram_drafter_prompt_lookup():
    d = gen_lib.NgramDrafter(max_ngram=3, min_ngram=1)
    # Periodic context: the suffix 3-gram (8, 9, 7) recurs; the drafter
    # must propose the tokens that FOLLOWED its most recent earlier
    # occurrence.
    ctx = [5, 8, 9, 7, 1, 2, 3, 8, 9, 7]
    assert d.propose(ctx, 4) == [1, 2, 3, 8]
    assert d.propose(ctx, 2) == [1, 2]


def test_ngram_drafter_most_recent_occurrence_wins():
    d = gen_lib.NgramDrafter(max_ngram=2, min_ngram=1)
    # The pair (1, 2) occurs twice before the suffix; the MOST RECENT
    # one (followed by 9) must win over the older one (followed by 4).
    ctx = [1, 2, 4, 0, 1, 2, 9, 3, 1, 2]
    assert d.propose(ctx, 1) == [9]


def test_ngram_drafter_no_match_and_validation():
    d = gen_lib.NgramDrafter()
    assert d.propose([1, 2, 3, 4], 4) == []  # nothing repeats
    assert d.propose([1], 4) == []  # too short
    assert d.propose([1, 1, 1], 0) == []  # k=0
    with pytest.raises(ValueError):
        gen_lib.NgramDrafter(max_ngram=1, min_ngram=2)


def test_ngram_drafter_window_bounds_lookup():
    """The lookup window bounds per-step host cost: matches outside
    the declared tail are invisible (deterministically — replay sees
    the same tail at the same confirmed position)."""
    ctx = [1, 2, 9, 0, 0, 0, 0, 1, 2]
    bounded = gen_lib.NgramDrafter(max_ngram=2, min_ngram=2, window=6)
    assert bounded.propose(ctx, 3) == []  # match lies outside the tail
    unbounded = gen_lib.NgramDrafter(max_ngram=2, min_ngram=2,
                                     window=None)
    assert unbounded.propose(ctx, 1) == [9]
    with pytest.raises(ValueError):
        gen_lib.NgramDrafter(max_ngram=3, window=3)


def test_ngram_drafter_deterministic():
    d = gen_lib.NgramDrafter()
    rng = np.random.default_rng(0)
    ctx = rng.integers(0, 5, size=200)
    assert d.propose(ctx, 8) == d.propose(list(ctx), 8)


# The trained drafter (`NeuralDrafter`, --draft-model): a host drafter
# behind the same seam.
VOCAB = cfg_lib.oryx_tiny().llm.vocab_size


def test_neural_drafter_deterministic():
    d = gen_lib.NeuralDrafter.init(VOCAB, dim=8, window=8, seed=0)
    ctx = [5, 8, 9, 7, 1, 2, 3, 8, 9, 7, 11, 4]
    a = d.propose(ctx, 4)
    assert len(a) == 4 and all(isinstance(t, int) for t in a)
    assert a == d.propose(list(ctx), 4)
    # The window bounds what the proposer can see: contexts identical
    # on the declared tail propose identically.
    assert d.propose([99] * 6 + ctx[-8:], 4) == d.propose(ctx, 4)


def test_neural_drafter_save_load_roundtrip(tmp_path):
    d = gen_lib.NeuralDrafter.init(VOCAB, dim=8, window=8, seed=1)
    path = str(tmp_path / "draft.npz")
    d.save(path)
    d2 = gen_lib.NeuralDrafter.load(path)
    assert d2.window == d.window
    assert d2.source == path
    ctx = list(range(40, 60))
    assert d2.propose(ctx, 5) == d.propose(ctx, 5)
    np.testing.assert_array_equal(d.params["embed"], d2.params["embed"])


def test_neural_drafter_from_spec(tmp_path):
    V = VOCAB
    d = gen_lib.NeuralDrafter.from_spec(f"init:{V}:8:8:7")
    assert d.source == f"init:{V}:8:8:7"
    same = gen_lib.NeuralDrafter.init(V, dim=8, window=8, seed=7)
    ctx = [3, 1, 4, 1, 5, 9, 2, 6]
    assert d.propose(ctx, 4) == same.propose(ctx, 4)
    path = str(tmp_path / "d.npz")
    d.save(path)
    assert gen_lib.NeuralDrafter.from_spec(path).propose(ctx, 4) \
        == d.propose(ctx, 4)
    with pytest.raises(ValueError, match="init:"):
        gen_lib.NeuralDrafter.from_spec("init:100:8")


def test_neural_drafter_validation():
    ok = dict(
        embed=np.zeros((10, 4), np.float32),
        proj=np.zeros((4, 10), np.float32),
    )
    gen_lib.NeuralDrafter(ok, window=4)
    with pytest.raises(ValueError):
        gen_lib.NeuralDrafter(ok, window=0)
    with pytest.raises(ValueError):
        gen_lib.NeuralDrafter(
            dict(embed=np.zeros((10, 4), np.float32),
                 proj=np.zeros((5, 10), np.float32)),
            window=4,
        )


def test_fit_neural_drafter_learns_and_validates():
    # A deterministic repeating stream: the decayed-bag predictor can
    # drive CE down on it, and fitting must be reproducible.
    streams = [[1, 2, 3, 1, 2, 3, 1, 2, 3, 1, 2, 3]] * 4
    d, losses = gen_lib.fit_neural_drafter(
        streams, vocab_size=8, dim=8, window=4, epochs=30, seed=0,
    )
    assert losses[-1] < losses[0]
    assert d.source.startswith("fit:")
    d2, losses2 = gen_lib.fit_neural_drafter(
        streams, vocab_size=8, dim=8, window=4, epochs=30, seed=0,
    )
    assert losses == losses2
    assert d.propose([1, 2, 3, 1], 3) == d2.propose([1, 2, 3, 1], 3)
    with pytest.raises(ValueError):
        gen_lib.fit_neural_drafter([[5]], vocab_size=8)

# ---------------------------------------------------------------------------
# Op level: spec lanes are just more (segment, position) packed rows
# ---------------------------------------------------------------------------


def test_spec_lane_metadata_routing():
    lengths = jnp.asarray([5, 17, 0], jnp.int32)
    seg, pos = paged_kv.spec_lane_metadata(lengths, 2)
    np.testing.assert_array_equal(
        np.asarray(seg), [0, 0, 0, 1, 1, 1, 2, 2, 2]
    )
    np.testing.assert_array_equal(
        np.asarray(pos), [5, 6, 7, 17, 18, 19, 0, 1, 2]
    )


def test_spec_lanes_write_like_sequential_steps():
    """1+k verify lanes of one slot land K/V exactly where 1+k
    sequential single-token writes would — the packed writer needs no
    notion of 'draft'."""
    rng = np.random.default_rng(0)
    Hk, D, ps, P = 2, 16, 8, 8
    alloc = paged_kv.PageAllocator(P, ps)
    bt = np.full((2, 3), alloc.sentinel, np.int32)
    bt[1, :2] = alloc.alloc(2)
    pool = rng.standard_normal((P, ps, Hk, D)).astype(np.float32)
    new = rng.standard_normal((3, Hk, D)).astype(np.float32)
    start = 6  # lane 1 crosses the page boundary at 8
    seg, pos = paged_kv.spec_lane_metadata(
        jnp.asarray([0, start], jnp.int32), 2
    )
    packed = paged_kv.write_pages_packed(
        jnp.asarray(pool), jnp.asarray(new), jnp.asarray(bt),
        seg[3:], pos[3:],
    )
    seq = jnp.asarray(pool)
    for j in range(3):
        seq = paged_kv.write_pages(
            seq, jnp.asarray(new[j][None, None]), jnp.asarray(bt[1:2]),
            jnp.asarray([start + j], np.int32),
        )
    np.testing.assert_array_equal(np.asarray(packed), np.asarray(seq))


# ---------------------------------------------------------------------------
# Verification math: greedy exactness + rejection-sampling correctness
# ---------------------------------------------------------------------------


def _verify(lg, tok, drafts, dlen, keys, temp, eos=0, top_p=None,
            top_k=None):
    S = lg.shape[0]
    return gen_lib.spec_verify_rows(
        jnp.asarray(lg), jnp.asarray(tok, jnp.int32),
        jnp.asarray(drafts, jnp.int32), jnp.asarray(dlen, jnp.int32),
        keys,
        temperature=jnp.full((S,), temp, jnp.float32),
        top_p=jnp.full((S,), 1.0 if top_p is None else top_p,
                       jnp.float32),
        top_k=jnp.zeros((S,), jnp.int32) if top_k is None
        else jnp.full((S,), top_k, jnp.int32),
        eos=eos,
    )


def test_spec_verify_greedy_longest_prefix():
    V, k = 7, 3
    # argmax targets per lane: [2, 4, 1, 5]
    lg = np.full((1, k + 1, V), -5.0, np.float32)
    for j, t in enumerate([2, 4, 1, 5]):
        lg[0, j, t] = 5.0
    keys = jax.random.split(jax.random.key(0), 1)
    # Full match: all 3 accepted, bonus = lane-3 argmax.
    acc, cand, _ = _verify(lg, [9], [[2, 4, 1]], [3], keys, 0.0)
    assert (int(acc[0]), int(cand[0])) == (3, 5)
    # Mismatch at lane 1: accept 1, bonus = lane-1 argmax (the token
    # sequential decode would have produced there). Key reuse across
    # these calls is the point: each verifies a different proposal
    # against the SAME frozen sampling state.
    acc, cand, _ = _verify(lg, [9], [[2, 9, 1]], [3], keys, 0.0)  # oryxlint: disable=key-linearity
    assert (int(acc[0]), int(cand[0])) == (1, 4)
    # draft_len masks trailing lanes even when they would match.
    acc, cand, _ = _verify(lg, [9], [[2, 4, 1]], [1], keys, 0.0)  # oryxlint: disable=key-linearity
    assert (int(acc[0]), int(cand[0])) == (1, 4)
    # Zero proposals degenerate to the plain decode step.
    acc, cand, _ = _verify(lg, [9], [[0, 0, 0]], [0], keys, 0.0)  # oryxlint: disable=key-linearity
    assert (int(acc[0]), int(cand[0])) == (0, 2)


def test_spec_verify_eos_truncation():
    V, k, eos = 7, 3, 6
    lg = np.full((1, k + 1, V), -5.0, np.float32)
    for j, t in enumerate([2, eos, 1, 5]):
        lg[0, j, t] = 5.0
    keys = jax.random.split(jax.random.key(1), 1)
    # Accepted EOS at lane 1 truncates the span INCLUSIVE of the eos
    # (the host must see it to finish the row); lane 2's match never
    # counts.
    acc, _, _ = _verify(lg, [9], [[2, eos, 1]], [3], keys, 0.0, eos=eos)
    assert int(acc[0]) == 2
    # A fed EOS accepts nothing at all (same keys: same frozen sampling
    # state, different fed token — that contrast is the assertion).
    acc, _, _ = _verify(lg, [eos], [[2, eos, 1]], [3], keys, 0.0,  # oryxlint: disable=key-linearity
                        eos=eos)
    assert int(acc[0]) == 0


def _emitted_marginal(lg_row, draft, n, temp, top_p=1.0, top_k=0,
                      seed=0):
    """Empirical marginal of the token emitted AT THE DRAFT POSITION
    (draft if accepted, else the residual resample) over n seeds."""
    V = lg_row.shape[-1]
    lg = np.broadcast_to(lg_row, (n, 2, V)).copy()
    keys = jax.random.split(jax.random.key(seed), n)
    acc, cand, _ = gen_lib.spec_verify_rows(
        jnp.asarray(lg), jnp.zeros((n,), jnp.int32),
        jnp.full((n, 1), draft, jnp.int32), jnp.ones((n,), jnp.int32),
        keys,
        temperature=jnp.full((n,), temp, jnp.float32),
        top_p=jnp.full((n,), top_p, jnp.float32),
        top_k=jnp.full((n,), top_k, jnp.int32),
        eos=-1,
    )
    acc, cand = np.asarray(acc), np.asarray(cand)
    emitted = np.where(acc == 1, draft, cand)
    return np.bincount(emitted, minlength=V) / n


def test_spec_verify_rejection_sampling_distribution():
    """The whole temperature>0 correctness claim: with a point-mass
    proposal, accept-with-p(d) + residual-resample must leave the
    emitted token distributed EXACTLY as the truncated target — for a
    likely draft, an unlikely draft, and under top-k truncation."""
    rng = np.random.default_rng(3)
    V, n = 8, 4000
    logits = rng.standard_normal((1, 2, V)).astype(np.float32) * 1.5
    for temp, top_k, draft, seed in (
        (1.0, 0, int(np.argmax(logits[0, 0])), 0),  # likely draft
        (1.0, 0, int(np.argmin(logits[0, 0])), 1),  # unlikely draft
        (0.7, 5, int(np.argmax(logits[0, 0])), 2),  # truncated target
    ):
        l_t, _ = gen_lib.truncate_logits_rows(
            jnp.asarray(logits[:, 0]),
            temperature=jnp.full((1,), temp, jnp.float32),
            top_p=jnp.ones((1,), jnp.float32),
            top_k=jnp.full((1,), top_k, jnp.int32),
        )
        target = np.asarray(jax.nn.softmax(l_t, axis=-1))[0]
        emp = _emitted_marginal(
            logits[0], draft, n, temp, top_k=top_k, seed=seed
        )
        tv = 0.5 * np.abs(emp - target).sum()
        assert tv < 0.04, (temp, top_k, draft, tv, emp, target)


# ---------------------------------------------------------------------------
# Engine level
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def pipe():
    cfg = cfg_lib.oryx_tiny()
    params = oryx.init_params(cfg, jax.random.key(0))
    return OryxInference(FakeTokenizer(), params, cfg)


def _run(pipe, reqs, *, speculate=0, sampling=None, **kw):
    metrics = ServingMetrics()
    defaults = dict(
        num_slots=2, page_size=16, chunk=4, max_ctx=512,
        prefill_chunk=8, ragged=True,
    )
    defaults.update(kw)
    sched = ContinuousScheduler(
        pipe, metrics=metrics, autostart=False, speculate=speculate,
        **defaults,
    )
    handles = [
        sched.submit({"question": q}, cap, sampling=sampling)
        for q, cap in reqs
    ]
    sched.start()
    results = [h.result(timeout=600) for h in handles]
    sched._check_pool_invariant()
    sched.close()
    return results, metrics, handles


def _dispatches(metrics, kind):
    fam = metrics.registry.counter("dispatches_total", ("kind",))
    return fam.labels(kind=kind).value


def test_speculate_requires_ragged(pipe):
    with pytest.raises(ValueError, match="ragged"):
        ContinuousScheduler(
            pipe, autostart=False, prefill_chunk=8, speculate=2
        )
    with pytest.raises(ValueError, match="non-negative"):
        ContinuousScheduler(
            pipe, autostart=False, prefill_chunk=8, ragged=True,
            speculate=-1,
        )


def test_spec_parity_mixed_lengths_one_dispatch(pipe):
    """The headline: mixed prompt lengths through the speculative
    engine — replies byte-identical to the plain ragged engine and the
    solo pipeline, with kind="spec" the ONLY dispatch kind paid and
    the draft economics counters ticking."""
    reqs = [
        ("hi", 5),
        ("what is going on with all of this, tell me now please", 8),
        ("tell me more", 6),
    ]
    ragg, _, _ = _run(pipe, reqs)
    spec, sm, _ = _run(pipe, reqs, speculate=3)
    for (q, cap), a, b in zip(reqs, ragg, spec):
        assert a == b, q
        assert b[0] == pipe.chat(q, max_new_tokens=cap), q
    assert _dispatches(sm, "spec") > 0
    for kind in ("ragged", "prefill", "decode"):
        assert _dispatches(sm, kind) == 0, kind
    assert sm.get("draft_proposed_total") > 0
    text = sm.render()
    assert "oryx_serving_accepted_tokens_per_step_bucket" in text
    assert "oryx_serving_draft_accepted_total" in text


def test_spec_parity_page_boundary_prompt(pipe):
    ps = 16
    q = "hello"
    n = len(pipe._prepare_request({"question": q})[0])
    q = q + " " + "a" * ((-n - 1) % ps)  # pad ids to a page multiple
    assert len(pipe._prepare_request({"question": q})[0]) % ps == 0
    ragg, _, _ = _run(pipe, [(q, 6)], page_size=ps)
    spec, _, _ = _run(pipe, [(q, 6)], speculate=4, page_size=ps)
    assert ragg[0] == spec[0]
    assert spec[0][0] == pipe.chat(q, max_new_tokens=6)


def test_spec_parity_prefix_cache_partial_page_cow(pipe):
    reqs = [
        ("hello there", 5),
        ("hello there friend", 5),
        ("hello there again, why?", 4),
    ]
    spec, sm, _ = _run(pipe, reqs, speculate=3)
    for (q, cap), r in zip(reqs, spec):
        assert r[0] == pipe.chat(q, max_new_tokens=cap), q
    assert sm.get("prefix_cache_hit_tokens_total") > 0


def test_spec_parity_eviction_replay(pipe):
    """Page pressure evicts the younger slot mid-decode; replay
    re-drafts from the DEVICE-confirmed stream and re-derives the same
    accept pattern — both replies byte-identical to the solo
    pipeline."""
    q1, q2 = "hello there", "tell me more"
    ps, k = 16, 3
    ids1 = len(pipe._prepare_request({"question": q1})[0])
    ids2 = len(pipe._prepare_request({"question": q2})[0])
    win = 1 + k
    admit1 = math.ceil((ids1 + win) / ps)
    admit2 = math.ceil((ids2 + win) / ps)
    cap = (admit1 * ps - ids1) + ps  # forces one extra page per row
    spec, sm, _ = _run(
        pipe, [(q1, cap), (q2, cap)], speculate=k, page_size=ps,
        num_pages=admit1 + admit2 + 1, prefix_cache=False,
    )
    assert sm.get("evicted") >= 1
    for q, (reply, _, usage) in zip((q1, q2), spec):
        assert reply == pipe.chat(q, max_new_tokens=cap), q


def test_spec_parity_tp2_mesh():
    if jax.device_count() < 2:
        pytest.skip("needs multiple (CPU) devices")
    from oryx_tpu.config import MeshConfig
    from oryx_tpu.parallel.mesh import build_mesh

    mesh = build_mesh(MeshConfig(tp=2), devices=jax.devices()[:2])
    cfg = cfg_lib.oryx_tiny()
    params = oryx.init_params(cfg, jax.random.key(0))
    ref_pipe = OryxInference(FakeTokenizer(), params, cfg)
    tp_pipe = OryxInference(
        FakeTokenizer(), params, cfg, mesh=mesh, sharding_mode="tp"
    )
    reqs = [("hello there", 5), ("hello there friend", 5)]
    spec, sm, _ = _run(tp_pipe, reqs, speculate=3)
    for (q, cap), r in zip(reqs, spec):
        assert r[0] == ref_pipe.chat(q, max_new_tokens=cap), q
    assert _dispatches(sm, "spec") > 0


def test_spec_zero_recompiles_across_mixes(pipe):
    """Static-shape claim for the spec program: after warmup compiles
    the two shape classes (prefill lanes present/absent), a different
    live-slot mix with different accept patterns compiles NOTHING —
    drafts and draft_len are traced operands."""
    from oryx_tpu.analysis.sanitizers import recompile_watchdog

    metrics = ServingMetrics()
    sched = ContinuousScheduler(
        pipe, num_slots=3, page_size=16, chunk=4, max_ctx=512,
        metrics=metrics, autostart=False, prefill_chunk=8,
        ragged=True, speculate=3, prefix_cache=False,
    )
    warm = [
        sched.submit({"question": "warm up the two shape classes"}, 6),
        sched.submit({"question": "warm the second slot too"}, 3),
    ]
    sched.start()
    for h in warm:
        h.result(timeout=600)
    with recompile_watchdog(budget=1, action="record") as stats:
        hs = [
            sched.submit({"question": q}, cap)
            for q, cap in [
                ("a totally different mix of lengths now", 7),
                ("short", 2),
                ("and a third request to stagger the finishes", 5),
                ("plus one more that queues behind them all", 4),
            ]
        ]
        for h in hs:
            h.result(timeout=600)
    sched.close()
    assert not stats.counts, (
        f"varying live-slot/draft mixes recompiled: {stats.counts}"
    )


# ---------------------------------------------------------------------------
# Rollback, stops across accept boundaries, ledger, sampling
# ---------------------------------------------------------------------------


class FixedDrafter(gen_lib.Drafter):
    """Always proposes the same token — on a greedy stream this is
    (almost) always rejected, making every step pay k dead lanes:
    the rollback-churn worst case."""

    def __init__(self, token: int, k: int):
        self.token, self.k = token, k

    def propose(self, context, k):
        return [self.token] * min(k, self.k)


class OracleDrafter(gen_lib.Drafter):
    """Proposes the request's KNOWN future tokens (a recorded reference
    stream), keyed by how many reply tokens the context already holds —
    a stand-in for a perfect draft model that also proves the Drafter
    interface is genuinely pluggable. Deterministic by construction."""

    def __init__(self, prompt_len: int, stream: list[int]):
        self.prompt_len = prompt_len
        self.stream = stream

    def propose(self, context, k):
        done = len(context) - self.prompt_len  # confirmed + fed token
        return self.stream[done: done + k]


class TapDrafter(gen_lib.Drafter):
    """Proposes nothing but records the longest context it was shown —
    a pure observer; the engine then behaves exactly like the plain
    one-token path while the tap captures the reply token stream."""

    def __init__(self):
        self.longest: list[int] = []

    def propose(self, context, k):
        ctx = [int(x) for x in context]
        if len(ctx) > len(self.longest):
            self.longest = ctx
        return []


def test_spec_rejected_drafts_at_page_boundary_leak_nothing(pipe):
    """All-reject worst case with the draft window straddling a page
    boundary every few steps: the pool invariant must hold mid-run and
    after, and replies stay byte-identical (rejected lanes write dead
    bytes past cur_len that the next real token overwrites)."""
    ps = 8
    q = "hello there friend"
    cap = 3 * ps  # decode crosses several page boundaries
    metrics = ServingMetrics()
    sched = ContinuousScheduler(
        pipe, num_slots=2, page_size=ps, chunk=4, max_ctx=512,
        prefill_chunk=8, ragged=True, speculate=5,
        drafter=FixedDrafter(token=7, k=5),
        metrics=metrics, autostart=False, prefix_cache=False,
    )
    h = sched.submit({"question": q}, cap)
    sched.start()
    reply = h.result(timeout=600)[0]
    sched._check_pool_invariant()
    held = sum(
        1 for p in range(sched.allocator.num_pages)
        if sched.allocator.refcount(p) > 0
    )
    assert held == 0, f"{held} pages still held after finish"
    sched.close()
    assert reply == pipe.chat(q, max_new_tokens=cap)


def test_spec_stop_string_across_accept_boundary(pipe):
    """Satellite regression: a stop string completing MID-accepted-span
    (and one spanning the boundary between two steps) must truncate the
    reply at the match and bill only tokens through it — byte- and
    usage-identical to the non-speculative engine."""
    q = "tell me a long story please"
    cap = 24
    ref = pipe.chat(q, max_new_tokens=cap)
    assert len(ref) >= 6, ref
    ids = len(pipe._prepare_request({"question": q})[0])
    # Record the greedy reply's token stream with a pure-observer
    # drafter (the engine behaves exactly like the one-token path).
    tap = TapDrafter()
    _run(pipe, [(q, cap)], speculate=1, drafter=tap)
    stream = tap.longest[ids:]
    assert len(stream) >= 6
    # A stop string strictly inside the reply: with an oracle drafter
    # and k=4 the accepted span covers it mid-span.
    stop = ref[2:5]
    for speculate, drafter in (
        (0, None), (4, OracleDrafter(ids, stream)),
    ):
        results, _, _ = _run(
            pipe, [(q, cap)], speculate=speculate,
            sampling={"stop": [stop]},
            **({"drafter": drafter} if drafter else {}),
        )
        if speculate == 0:
            expect = results[0]
        else:
            assert results[0] == expect, (
                "stop handling diverged across a multi-token accept"
            )
    reply, reason, usage = expect
    assert stop not in reply
    assert reason == "stop"
    assert usage[1] <= len(ref)


def test_spec_cost_ledger_steps_vs_tokens(pipe):
    """The satellite billing split: decode_steps bills device verify
    lanes (rejected drafts are paid compute), decode_tokens bills
    client progress — under speculation steps strictly exceed tokens
    for an all-reject drafter, and tokens equals the completion."""
    q, cap = "tell me more", 6
    results, sm, handles = _run(
        pipe, [(q, cap)], speculate=4,
        drafter=FixedDrafter(token=7, k=4),
    )
    cost = handles[0].debug["cost"]
    assert cost["decode_tokens"] == results[0][2][1] == cap
    assert cost["decode_steps"] > cost["decode_tokens"]
    assert "request_decode_tokens" in sm.render()
    # Plain ragged mode keeps the legacy equality steps >= tokens with
    # both keys present (schema is mode-independent).
    _, _, h2 = _run(pipe, [(q, cap)])
    c2 = h2[0].debug["cost"]
    assert c2["decode_tokens"] == cap
    assert c2["decode_steps"] >= c2["decode_tokens"]


def test_spec_sampled_deterministic_and_replay_stable(pipe):
    """temperature>0 under speculation: the same seed gives the same
    bytes run-to-run, and an eviction replay mid-stream re-derives the
    SAME reply as an eviction-free run (the drafter proposing from the
    device-confirmed stream is what makes this hold)."""
    q1, q2 = "hello there", "tell me more"
    ps, k = 16, 3
    sampling = {"temperature": 0.8, "top_p": 0.9, "seed": 12}
    ids1 = len(pipe._prepare_request({"question": q1})[0])
    ids2 = len(pipe._prepare_request({"question": q2})[0])
    win = 1 + k
    admit1 = math.ceil((ids1 + win) / ps)
    admit2 = math.ceil((ids2 + win) / ps)
    cap = (admit1 * ps - ids1) + ps
    kw = dict(
        speculate=k, page_size=ps, sampling=sampling,
        prefix_cache=False,
    )
    tight, tm, _ = _run(
        pipe, [(q1, cap), (q2, cap)],
        num_pages=admit1 + admit2 + 1, **kw,
    )
    assert tm.get("evicted") >= 1
    roomy, rm, _ = _run(pipe, [(q1, cap), (q2, cap)], **kw)
    assert rm.get("evicted") == 0
    assert tight == roomy
    again, _, _ = _run(pipe, [(q1, cap), (q2, cap)], **kw)
    assert roomy == again
