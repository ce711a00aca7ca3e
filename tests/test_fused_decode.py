"""Fused K-step decode megastep (--fuse-steps; docs/DESIGN.md "Fused
multi-step decode"): ONE device dispatch runs K logical engine steps —
sampling, packed KV writes, EOS/stop-window detection all device-side —
and the host harvests once per megastep, billing and journaling K
logical steps from column slices of the harvested outputs.

Three layers of proof, mirroring test_speculative.py:

  * unit level — the `NeuralDrafter` draft model is a deterministic
    function of (weights, context); save/load and the init:V:D:W:SEED
    spec rebuild bit-identical proposers; the HOST `propose()` and the
    DEVICE `neural_draft_propose` chain produce the same bits (the
    property that lets speculation ride the fused scan).
  * engine level — `ContinuousScheduler(fuse_steps=K)` replies are
    BYTE-identical to the K=1 engine and the solo pipeline across
    greedy and seeded sampling, mixed lengths, mid-megastep stop
    strings, eviction replay, int8 KV, spec rollback and a tp=2 mesh;
    billing is per LOGICAL step (stop-point clamped); adaptive K
    ("auto") crosses its ladder rungs with ZERO recompiles after
    warmup.
  * journal level — a megastep journals K step entries stamped
    (fused_k, fused_j); fused captures replay byte-exact (the journaled
    fuse plan is re-applied, not re-derived); a K=1 replay of a fused
    capture diverges with the `dispatch` field NAMED in the
    first-divergence report.
"""

import math
import sys
from pathlib import Path

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from oryx_tpu import config as cfg_lib
from oryx_tpu.models import generate as gen_lib
from oryx_tpu.models import oryx
from oryx_tpu.serve import journal as journal_lib
from oryx_tpu.serve.api_server import build_server
from oryx_tpu.serve.pipeline import OryxInference
from oryx_tpu.serve.scheduler import ContinuousScheduler
from oryx_tpu.utils.metrics import ServingMetrics

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT / "scripts"))

import replay_journal as rj  # noqa: E402


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


def _vocab(pipe):
    return pipe.cfg.llm.vocab_size


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


# ---------------------------------------------------------------------------
# NeuralDrafter unit level
# ---------------------------------------------------------------------------


def test_neural_drafter_deterministic(pipe):
    d = gen_lib.NeuralDrafter.init(_vocab(pipe), dim=8, window=8, seed=0)
    ctx = [5, 8, 9, 7, 1, 2, 3, 8, 9, 7, 11, 4]
    a = d.propose(ctx, 4)
    assert len(a) == 4 and all(isinstance(t, int) for t in a)
    assert a == d.propose(list(ctx), 4)
    # The window bounds what the proposer can see: contexts identical
    # on the declared tail propose identically.
    assert d.propose([99] * 6 + ctx[-8:], 4) == d.propose(ctx, 4)


def test_neural_drafter_save_load_roundtrip(pipe, tmp_path):
    d = gen_lib.NeuralDrafter.init(_vocab(pipe), dim=8, window=8, seed=1)
    path = str(tmp_path / "draft.npz")
    d.save(path)
    d2 = gen_lib.NeuralDrafter.load(path)
    assert d2.window == d.window
    assert d2.source == path
    ctx = list(range(40, 60))
    assert d2.propose(ctx, 5) == d.propose(ctx, 5)
    np.testing.assert_array_equal(d.params["embed"], d2.params["embed"])


def test_neural_drafter_from_spec(pipe, tmp_path):
    V = _vocab(pipe)
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


def test_neural_drafter_host_device_bit_identical(pipe):
    """The property the fused spec scan rests on: the device chain
    (`neural_draft_propose`, right-aligned window + shift-in fed token)
    proposes the SAME bits as the host `propose()` on the equivalent
    context — so --fuse-steps 1 vs K spec runs share accept patterns."""
    V = _vocab(pipe)
    d = gen_lib.NeuralDrafter.init(V, dim=8, window=8, seed=2)
    ctx_list = [7, 3, 9, 12, 5, 5, 2]
    fed = 31
    host = d.propose(ctx_list + [fed], 4)
    CW = d.window
    ctx = np.zeros((1, CW), np.int32)
    tail = np.asarray(ctx_list[-CW:], np.int32)
    ctx[0, CW - len(tail):] = tail
    drafts, dlen = gen_lib.neural_draft_propose(
        d.device_params(), jnp.asarray(ctx),
        jnp.asarray([len(tail)], jnp.int32),
        jnp.asarray([fed], jnp.int32), 4,
    )
    assert int(dlen[0]) == 4
    assert [int(t) for t in np.asarray(drafts)[0]] == host


# ---------------------------------------------------------------------------
# Flag validation (scheduler + server + CLI)
# ---------------------------------------------------------------------------


def test_fuse_steps_validation(pipe):
    for bad in (0, -2, "bogus", 2.5):
        with pytest.raises(ValueError, match="fuse_steps"):
            ContinuousScheduler(
                pipe, autostart=False, prefill_chunk=8, ragged=True,
                fuse_steps=bad,
            )
    with pytest.raises(ValueError, match="ragged"):
        ContinuousScheduler(
            pipe, autostart=False, prefill_chunk=8, fuse_steps=4
        )
    # Host-side drafters cannot ride the fused scan: speculation under
    # fuse_steps>1 demands the device params/apply contract.
    with pytest.raises(ValueError, match="NeuralDrafter"):
        ContinuousScheduler(
            pipe, autostart=False, prefill_chunk=8, ragged=True,
            speculate=2, fuse_steps=4,
        )


def test_build_server_fuse_flag_pairing(pipe):
    base = dict(engine="continuous", prefill_chunk=8)
    with pytest.raises(ValueError, match="ragged"):
        build_server(pipe, fuse_steps=4, **base)
    with pytest.raises(ValueError, match="draft-model"):
        build_server(pipe, fuse_steps=4, ragged=True, speculate=2,
                     **base)
    with pytest.raises(ValueError, match="speculate"):
        build_server(pipe, ragged=True, draft_model="init:512:8:8:0",
                     **base)


def test_cli_fuse_flag_validation():
    from oryx_tpu.serve import api_server

    base = ["--model-path", "x", "--engine", "continuous",
            "--prefill-chunk", "8"]
    for extra in (
        ["--fuse-steps", "0"],
        ["--fuse-steps", "nope"],
        ["--fuse-steps", "4"],  # no --ragged
        ["--ragged", "--fuse-steps", "4", "--speculate", "2"],
        ["--ragged", "--speculate", "0", "--draft-model", "d.npz"],
    ):
        with pytest.raises(SystemExit):
            api_server.main(base + extra)


# ---------------------------------------------------------------------------
# Engine level: byte parity vs the K=1 engine and the solo pipeline
# ---------------------------------------------------------------------------


def test_fused_parity_greedy_mixed_lengths(pipe):
    """The headline: mixed prompt lengths, --fuse-steps 4 — replies
    byte-identical to the K=1 ragged engine and the solo pipeline, with
    kind="fused" dispatches actually paid and the fused_k gauge +
    harvest counter exported."""
    reqs = [
        ("hi", 24),
        ("what is going on with all of this, tell me now please", 32),
    ]
    base, bm, _ = _run(pipe, reqs)
    fused, fm, _ = _run(pipe, reqs, fuse_steps=4)
    for (q, cap), a, b in zip(reqs, base, fused):
        assert a == b, q
        assert b[0] == pipe.chat(q, max_new_tokens=cap), q
    assert _dispatches(fm, "fused") > 0
    # The whole point: K steps per harvest -> strictly fewer host syncs
    # than the K=1 engine paid for the same tokens.
    assert fm.get("harvest_total") < bm.get("harvest_total")
    text = fm.render()
    assert "oryx_serving_fused_k" in text
    assert "oryx_serving_harvest_total" in text


def test_fused_parity_seeded_sampling(pipe):
    """temperature>0: the fused scan consumes the per-row RNG chain in
    the same order as K sequential dispatches, so seeded sampling is
    bit-identical — and run-to-run stable."""
    reqs = [("hello there", 20), ("tell me more", 24)]
    sampling = {"temperature": 0.8, "top_p": 0.9, "seed": 12}
    base, _, _ = _run(pipe, reqs, sampling=sampling)
    fused, fm, _ = _run(pipe, reqs, sampling=sampling, fuse_steps=4)
    assert base == fused
    assert _dispatches(fm, "fused") > 0
    again, _, _ = _run(pipe, reqs, sampling=sampling, fuse_steps=4)
    assert fused == again


def test_fused_parity_mid_megastep_stop_string(pipe):
    """A custom stop string completing MID-megastep: the host truncates
    at the logical step that matched, discards the device's overshoot
    columns, and bills only through the stop — byte- and usage-
    identical to the K=1 engine."""
    q, cap = "tell me a long story please", 24
    ref = pipe.chat(q, max_new_tokens=cap)
    assert len(ref) >= 6, ref
    stop = ref[2:5]
    base, _, bh = _run(pipe, [(q, cap)], sampling={"stop": [stop]})
    fused, fm, fh = _run(
        pipe, [(q, cap)], sampling={"stop": [stop]}, fuse_steps=4
    )
    assert base == fused
    assert _dispatches(fm, "fused") > 0
    reply, reason, usage = fused[0]
    assert reason == "stop" and stop not in reply
    assert usage[1] < cap  # clamped at the stop point, not the horizon
    # Billing keys match exactly (peak_pages may legitimately sit one
    # higher under the megastep's pre-ensured K-window horizon).
    for k in ("prefill_tokens", "cached_tokens", "decode_steps",
              "decode_tokens"):
        assert bh[0].debug["cost"][k] == fh[0].debug["cost"][k], k


def test_fused_parity_eviction_replay(pipe):
    """Page pressure under the K-step horizon: capacity for the full
    megastep is ensured BEFORE the scan (the device cannot grow tables
    mid-flight), eviction re-queues the victim, and the replayed
    request still lands byte-identical to the solo pipeline."""
    q1, q2 = "hello there", "tell me more"
    ps, chunk = 16, 4
    ids1 = len(pipe._prepare_request({"question": q1})[0])
    ids2 = len(pipe._prepare_request({"question": q2})[0])
    admit1 = math.ceil((ids1 + chunk) / ps)
    admit2 = math.ceil((ids2 + chunk) / ps)
    cap = (admit1 * ps - ids1) + ps
    assert cap >= 16  # big enough that K=4 megasteps actually fire
    fused, fm, _ = _run(
        pipe, [(q1, cap), (q2, cap)], fuse_steps=4, page_size=ps,
        num_pages=admit1 + admit2 + 1, prefix_cache=False,
    )
    assert fm.get("evicted") >= 1
    for q, (reply, _, _) in zip((q1, q2), fused):
        assert reply == pipe.chat(q, max_new_tokens=cap), q


def test_fused_parity_int8_kv(pipe):
    reqs = [("hello there", 20), ("what now?", 24)]
    base, _, _ = _run(pipe, reqs, kv_dtype="int8")
    fused, fm, _ = _run(pipe, reqs, kv_dtype="int8", fuse_steps=4)
    assert base == fused
    assert _dispatches(fm, "fused") > 0


def test_fused_parity_tp2_mesh():
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
    reqs = [("hello there", 20), ("hello there friend", 20)]
    fused, fm, _ = _run(tp_pipe, reqs, fuse_steps=4)
    for (q, cap), r in zip(reqs, fused):
        assert r[0] == ref_pipe.chat(q, max_new_tokens=cap), q
    assert _dispatches(fm, "fused") > 0


def test_fused_spec_parity_and_rollback(pipe):
    """Speculation INSIDE the fused scan: the device draft chain
    proposes, the packed verify forward judges, rejection rolls back —
    all without a host round-trip — and the replies are byte-identical
    to the K=1 speculative engine and the solo pipeline. A random-init
    draft model rejects nearly everything, so this is also the
    rollback-churn worst case."""
    V = _vocab(pipe)
    reqs = [("hello there", 20), ("tell me more about that", 24)]
    mk = lambda: gen_lib.NeuralDrafter.init(V, dim=8, window=8, seed=0)
    base, _, _ = _run(pipe, reqs, speculate=3, drafter=mk())
    fused, fm, _ = _run(
        pipe, reqs, speculate=3, drafter=mk(), fuse_steps=4
    )
    for (q, cap), a, b in zip(reqs, base, fused):
        assert a == b, q
        assert b[0] == pipe.chat(q, max_new_tokens=cap), q
    assert _dispatches(fm, "fused_spec") > 0
    assert fm.get("draft_proposed_total") > 0


def test_fused_billing_per_logical_step(pipe):
    """Satellite billing contract: the megastep bills K logical steps
    — decode_steps / decode_tokens / prefill / cached all land exactly
    as the K=1 engine's ledger, including a row that finishes before
    the megastep's horizon (its overshoot columns are free)."""
    reqs = [("hello there", 17), ("tell me more", 26)]  # off-rung caps
    base, bm, bh = _run(pipe, reqs)
    fused, fm, fh = _run(pipe, reqs, fuse_steps=4)
    assert base == fused
    keys = ("prefill_tokens", "cached_tokens", "decode_steps",
            "decode_tokens")
    for a, b in zip(bh, fh):
        for k in keys:
            assert a.debug["cost"][k] == b.debug["cost"][k], k
    for series in ("decode_steps_total", "decode_steps_useful",
                   "decode_steps_wasted"):
        assert bm.get(series) == fm.get(series), series


def test_fused_small_budget_never_engages(pipe):
    """The remaining-budget clamp: when no live row has K windows of
    max_new left, the engine stays on K=1 dispatches (no megastep ever
    overruns a row's budget by more than one window — the same max_ctx
    exposure as the sequential engine)."""
    reqs = [("hi", 5), ("tell me more", 6)]
    base, _, _ = _run(pipe, reqs)
    fused, fm, _ = _run(pipe, reqs, fuse_steps=16)
    assert base == fused
    assert _dispatches(fm, "fused") == 0
    assert _dispatches(fm, "ragged") > 0


def test_fused_k_gauge_tracks_selection(pipe):
    """oryx_serving_fused_k is the live K decision: a run whose budget
    supports megasteps shows the rung on the gauge during them and 1 on
    the sequential tail."""
    metrics = ServingMetrics()
    sched = ContinuousScheduler(
        pipe, metrics=metrics, autostart=False, num_slots=2,
        page_size=16, chunk=4, max_ctx=512, prefill_chunk=8,
        ragged=True, fuse_steps=4,
    )
    seen = set()
    orig = sched._fused_megastep

    def spy(k_steps):
        seen.add(k_steps)
        return orig(k_steps)

    sched._fused_megastep = spy
    h = sched.submit({"question": "hello there"}, 20)
    sched.start()
    h.result(timeout=600)
    sched.close()
    assert seen == {4}
    # The gauge ends on the tail's K=1 (budget exhausted), having
    # passed through 4 during the megasteps.
    assert metrics.get("fused_k") == 1.0


def test_fused_auto_adaptive_zero_recompiles(pipe):
    """--fuse-steps auto crosses its whole ladder — K=16 solo, K=4
    shared, K=1 tails and admission steps — and after warmup compiles
    NOTHING: every rung is a static shape class, and adaptive selection
    only switches between already-compiled programs."""
    from oryx_tpu.analysis.sanitizers import recompile_watchdog

    metrics = ServingMetrics()
    sched = ContinuousScheduler(
        pipe, metrics=metrics, autostart=False, num_slots=2,
        page_size=16, chunk=4, max_ctx=512, prefill_chunk=8,
        ragged=True, fuse_steps="auto", prefix_cache=False,
    )
    # Warmup: a shared phase (K=4), a solo phase long enough for K=16,
    # and off-rung tails (K=1) — plus the prefill shape classes.
    warm = [
        sched.submit({"question": "warm up the big solo rung"}, 90),
        sched.submit({"question": "short neighbor"}, 20),
    ]
    sched.start()
    for h in warm:
        h.result(timeout=600)
    with recompile_watchdog(budget=1, action="record") as stats:
        hs = [
            sched.submit({"question": q}, cap)
            for q, cap in [
                ("a different mix of lengths this time", 70),
                ("another short one", 10),
                ("and a third that queues behind them", 30),
            ]
        ]
        for h in hs:
            h.result(timeout=600)
    sched.close()
    assert _dispatches(metrics, "fused") > 0
    assert not stats.counts, (
        f"adaptive-K transitions recompiled: {stats.counts}"
    )


# ---------------------------------------------------------------------------
# Journal level: K entries per megastep, byte-exact replay, named
# divergence
# ---------------------------------------------------------------------------


def _capture(pipe, tmp_path, reqs, **kw):
    path = str(tmp_path / "journal.jsonl")
    j = journal_lib.DecisionJournal(path)
    defaults = dict(
        num_slots=2, page_size=16, chunk=4, max_ctx=512,
        prefill_chunk=8, ragged=True,
    )
    defaults.update(kw)
    sched = ContinuousScheduler(
        pipe, autostart=False, journal=j, **defaults,
    )
    handles = [
        sched.submit({"question": q}, cap, sampling)
        for q, cap, sampling in reqs
    ]
    sched.start()
    results = [h.result(timeout=600) for h in handles]
    sched.close()
    j.close()
    return path, results


def _replay_byte_exact(path, pipe):
    header, entries = journal_lib.read_journal(path)
    res = rj.run_replay(header, entries, pipe=pipe, timeout_s=300)
    div = rj.first_divergence(entries, res["entries"])
    assert div is None, f"replay diverged: {div}"
    matched, total, bad = rj.reply_match(entries, res["entries"])
    assert matched == total and total > 0, bad
    assert not res["feed_errors"] and not res["timed_out"]
    assert not res["gave_up"]
    return header, entries


def test_fused_journal_k_entries_per_megastep(pipe, tmp_path):
    """Satellite: ONE device dispatch, K journal entries — each logical
    step stamped (fused_k, fused_j) with a contiguous step clock, so
    replay can reconstruct the fuse plan and per-step triage (accepted
    tokens, live slots, free pages) keeps its K=1 meaning."""
    path, _ = _capture(pipe, tmp_path, [("hello there", 20, None)],
                       fuse_steps=4)
    header, entries = journal_lib.read_journal(path)
    assert header["config"]["fuse_steps"] == 4
    fused = [e for e in entries
             if e["kind"] == "step" and e.get("fused_j") is not None]
    assert fused, "no megastep entries journaled"
    assert all(e["dispatch"] == "fused" and e["fused_k"] == 4
               for e in fused)
    starts = [e for e in fused if e["fused_j"] == 0]
    assert starts
    by_step = {e["step"]: e for e in fused}
    for e in starts:
        for j in range(4):
            assert by_step[e["step"] + j]["fused_j"] == j
    # K=1 dispatches never carry the megastep fields.
    plain = [e for e in entries
             if e["kind"] == "step" and e.get("fused_j") is None]
    assert all(e.get("fused_k") is None for e in plain)


def test_fused_replay_byte_exact(pipe, tmp_path):
    path, _ = _capture(
        pipe, tmp_path,
        [("hello there", 20, None), ("tell me more", 24, None)],
        fuse_steps=4,
    )
    header, entries = _replay_byte_exact(path, pipe)
    assert any(e.get("dispatch") == "fused" for e in entries)


def test_fused_auto_replay_uses_journaled_plan(pipe, tmp_path):
    """Adaptive K reads queue depth — wall-clock-coupled state replay
    does not have. The journaled (fused_k, fused_j) stamps ARE the
    plan: replay re-applies them instead of re-deriving, and the
    capture reproduces byte-exact across rung transitions."""
    path, _ = _capture(
        pipe, tmp_path,
        [("hello there is a lot to say", 90, None),
         ("short one", 10, None)],
        fuse_steps="auto", prefix_cache=False, prefill_chunk=64,
    )
    header, entries = _replay_byte_exact(path, pipe)
    assert header["config"]["fuse_steps"] == "auto"
    rungs = {e["fused_k"] for e in entries
             if e["kind"] == "step" and e.get("fused_j") == 0}
    assert rungs, "auto never fused"


def test_fused_spec_replay_byte_exact(pipe, tmp_path):
    """The header's draft_model spec rebuilds the IDENTICAL proposer
    (init:V:D:W:SEED is a complete recipe), so a fused speculative
    capture — device drafting included — replays byte-exact."""
    V = _vocab(pipe)
    drafter = gen_lib.NeuralDrafter.init(V, dim=8, window=8, seed=0)
    path, _ = _capture(
        pipe, tmp_path,
        [("hello there", 20, None), ("tell me more", 20, None)],
        fuse_steps=4, speculate=3, drafter=drafter,
    )
    header, entries = _replay_byte_exact(path, pipe)
    assert header["config"]["draft_model"] == f"init:{V}:8:8:0"
    assert any(e.get("dispatch") == "fused_spec" for e in entries)


def test_k1_replay_of_fused_capture_names_divergence(pipe, tmp_path):
    """Satellite contract: replaying a fused capture with fuse_steps
    overridden to 1 must NOT silently pass — the first megastep's
    journal entry diverges on the `dispatch` field BY NAME (fused vs
    ragged), which is the triage breadcrumb the runbook documents."""
    path, _ = _capture(pipe, tmp_path, [("hello there", 20, None)],
                       fuse_steps=4)
    header, entries = journal_lib.read_journal(path)
    res = rj.run_replay(
        header, entries, pipe=pipe, overrides={"fuse_steps": 1},
        timeout_s=300,
    )
    div = rj.first_divergence(entries, res["entries"])
    assert div is not None, "K=1 replay of a fused capture matched"
    assert div["kind"] == "step" and div["field"] == "dispatch"
    assert div["live"] == "fused" and div["replay"] == "ragged"
    # The un-fused counterfactual still produces the same BYTES — only
    # the decision stream differs.
    matched, total, bad = rj.reply_match(entries, res["entries"])
    assert matched == total, bad


def test_replay_geometry_includes_fuse_steps():
    assert "fuse_steps" in rj.GEOMETRY_KEYS
    assert "fuse_steps" in rj.OVERRIDE_KEYS
