"""Engine flight recorder: decision-journal schema + ring + rotation,
the shared rolling-sink regression, and byte-exact offline replay
(scripts/replay_journal.py) across the engine's hard modes — eviction
replay, supervisor restart, speculative decoding, int8 KV, host-spill
reload, prefix-cache COW splices, and a tp=2 mesh — plus the
first-divergence report contract and the observe-never-perturb
(armed == unarmed) guarantee."""

import json
import sys
from pathlib import Path

import pytest

import jax

from oryx_tpu import config as cfg_lib
from oryx_tpu.models import oryx
from oryx_tpu.serve import journal as journal_lib
from oryx_tpu.serve.api_server import EngineSupervisor
from oryx_tpu.serve.pipeline import OryxInference
from oryx_tpu.serve.scheduler import ContinuousScheduler
from oryx_tpu.utils import faults
from oryx_tpu.utils.metrics import ServingMetrics
from oryx_tpu.utils.request_log import RequestLog
from oryx_tpu.utils.rolling_sink import RollingSink

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


# ---------------------------------------------------------------------------
# Schema + ring + file (no engine)
# ---------------------------------------------------------------------------


def test_build_journal_event_rejects_undeclared_fields():
    ev = journal_lib.build_journal_event(kind="step", dispatch="decode")
    assert ev["schema"] == journal_lib.JOURNAL_SCHEMA
    # Deliberately undeclared fields, passed as splats: the static
    # metric-name check (rightly) flags literal bad kwargs at any
    # build_journal_event call site — the runtime rejection is what
    # this test pins.
    with pytest.raises(ValueError, match="undeclared"):
        journal_lib.build_journal_event(**{"kind": "step",
                                           "not_a_field": 1})
    with pytest.raises(ValueError, match="undeclared"):
        journal_lib.build_journal_event(**{"BadCase": "x"})


def test_journal_ring_counts_and_debug_shape(tmp_path):
    path = str(tmp_path / "j.jsonl")
    j = journal_lib.DecisionJournal(path, keep=3)
    j.stamp_header(num_slots=2, seed=0)
    j.seal_header()
    for i in range(5):
        seq = j.append(journal_lib.build_journal_event(
            kind="step", step=i, dispatch="decode",
        ))
        assert seq == i
    body = j.to_dict()
    assert body["armed"] is True
    assert body["total"] == 5
    assert body["counts_by_kind"] == {"step": 5}
    assert body["header"]["config"]["num_slots"] == 2
    # keep=3 bounds the ring, newest first; the file holds all 5.
    assert [e["step"] for e in body["entries"]] == [4, 3, 2]
    j.close()
    header, entries = journal_lib.read_journal(path)
    assert header["config"]["seed"] == 0
    assert [e["step"] for e in entries] == [0, 1, 2, 3, 4]
    # Disarmed body: same shape, armed=false (the /debug/journal
    # contract for servers booted without --journal).
    d = journal_lib.DISARMED.to_dict()
    assert d["armed"] is False and d["entries"] == []
    assert set(d) == set(body)


def test_journal_rotation_preserves_header(tmp_path):
    """The size cap rolls to .1 exactly once and every generation
    re-carries the header line, so read_journal can always rebuild."""
    path = str(tmp_path / "j.jsonl")
    j = journal_lib.DecisionJournal(path, max_bytes=600)
    j.stamp_header(num_slots=1)
    j.seal_header()
    n = 40
    for i in range(n):
        j.append(journal_lib.build_journal_event(
            kind="step", step=i, dispatch="decode",
        ))
    j.close()
    assert (tmp_path / "j.jsonl.1").exists()
    # Both generations start with the header line.
    for p in (tmp_path / "j.jsonl", tmp_path / "j.jsonl.1"):
        first = json.loads(p.read_text().splitlines()[0])
        assert first["kind"] == "header"
    header, entries = journal_lib.read_journal(path)
    assert header["config"]["num_slots"] == 1
    # One generation of history: the newest entries survive, in order,
    # with no seq gaps inside the retained window.
    steps = [e["step"] for e in entries]
    assert steps == list(range(steps[0], n))
    assert steps[-1] == n - 1


def test_rolling_sink_shared_semantics(tmp_path):
    """The one rotation implementation (utils/rolling_sink.py) behind
    events.jsonl / requests.jsonl / the journal: rotate-after-crossing
    write, single .1 generation, optional prologue re-written at the
    top of each generation, loud write-after-close."""
    path = str(tmp_path / "s.jsonl")
    sink = RollingSink(path, max_bytes=120)
    sink.set_prologue('{"kind": "header"}')
    for i in range(20):
        sink.write(json.dumps({"i": i}))
    live = Path(path).read_text().splitlines()
    rolled = Path(path + ".1").read_text().splitlines()
    assert live[0] == '{"kind": "header"}'
    assert rolled[0] == '{"kind": "header"}'
    # Continuous coverage across the roll: rolled tail + live body.
    seen = [json.loads(x)["i"] for x in rolled[1:] + live[1:]]
    assert seen == list(range(seen[0], 20))
    sink.close()
    with pytest.raises(ValueError, match="closed"):
        sink.write("{}")


# ---------------------------------------------------------------------------
# Live capture -> offline replay (the tentpole loop)
# ---------------------------------------------------------------------------


def _capture(pipe, tmp_path, reqs, *, supervisor=False, faults_spec=None,
             request_log=None, **kw):
    """One journaled live run: submit everything up front (deterministic
    arrival), run to completion, close. Returns (path, results)."""
    path = str(tmp_path / "journal.jsonl")
    j = journal_lib.DecisionJournal(path)
    if faults_spec:
        j.stamp_header(faults_spec=faults_spec)
        faults.configure(faults_spec)
    sup = None
    try:
        sched = ContinuousScheduler(
            pipe, autostart=False, journal=j, request_log=request_log,
            **kw,
        )
        handles = [
            sched.submit({"question": q}, cap, sampling)
            for q, cap, sampling in reqs
        ]
        sched.start()
        if supervisor:
            sup = EngineSupervisor(sched, poll_s=0.05)
            sup.start()
        results = [h.result(timeout=600) for h in handles]
    finally:
        if sup is not None:
            sup.stop()
        sched.close()
        j.close()
        faults.configure(None)
    return path, results


def _replay_byte_exact(path, pipe):
    """Replay the journal cold and assert the full tentpole contract:
    no first divergence, every reply fingerprint identical, cost
    ledgers equal (part of the finish entries), clean run."""
    header, entries = journal_lib.read_journal(path)
    res = rj.run_replay(header, entries, pipe=pipe, timeout_s=300)
    div = rj.first_divergence(entries, res["entries"])
    assert div is None, f"replay diverged: {div}"
    matched, total, bad = rj.reply_match(entries, res["entries"])
    assert total == len(
        [e for e in entries if e["kind"] == "finish"]
    ) and matched == total, bad
    assert not res["feed_errors"] and not res["timed_out"]
    assert not res["gave_up"]
    return entries, res["entries"]


def test_replay_eviction(pipe, tmp_path):
    """Page pressure evicts the younger slot mid-decode; the journal
    records the victim choice and the replay re-derives it — byte-
    identical replies through the re-queue and replay."""
    import math

    q1, q2 = "hello there", "tell me more"
    chunk, ps = 4, 16
    ids1 = len(pipe._prepare_request({"question": q1})[0])
    ids2 = len(pipe._prepare_request({"question": q2})[0])
    admit1 = math.ceil((ids1 + chunk) / ps)
    admit2 = math.ceil((ids2 + chunk) / ps)
    cap = (admit1 * ps - ids1) + ps
    metrics = ServingMetrics()
    path, _ = _capture(
        pipe, tmp_path, [(q1, cap, None), (q2, cap, None)],
        num_slots=2, page_size=ps, chunk=chunk, max_ctx=512,
        num_pages=admit1 + admit2 + 1, prefix_cache=False,
        metrics=metrics,
    )
    assert metrics.get("evicted") >= 1
    entries, _ = _replay_byte_exact(path, pipe)
    assert any(e["kind"] == "evict" for e in entries)
    # Eviction re-admission is journaled as a second admit with the
    # already-confirmed tokens to replay.
    readmits = [
        e for e in entries
        if e["kind"] == "admit" and e.get("replay_tokens")
    ]
    assert readmits


def test_replay_supervisor_restart(pipe, tmp_path):
    """A seeded engine crash mid-run: the live supervisor restarts the
    engine and restart-replays the in-flight requests; offline replay
    reproduces the crash at the same hit, the restart, and the same
    final bytes."""
    path, results = _capture(
        pipe, tmp_path,
        [("hello there", 10, None), ("what now then", 10, None)],
        num_slots=2, page_size=16, chunk=4, max_ctx=512,
        supervisor=True, faults_spec="engine_crash:after=2",
    )
    assert all(r[1] == "length" for r in results)
    entries, _ = _replay_byte_exact(path, pipe)
    assert any(e["kind"] == "fault" and e["site"] == "engine_crash"
               for e in entries)
    assert any(e["kind"] == "restart" for e in entries)


def test_replay_speculative(pipe, tmp_path):
    """Speculative decoding (fused ragged verify lanes): per-step
    accept counts are journaled and the replay re-derives the same
    accept pattern."""
    path, _ = _capture(
        pipe, tmp_path,
        [("hello there", 8, None),
         ("tell me more about that", 8, None)],
        num_slots=2, page_size=16, chunk=4, max_ctx=512,
        prefill_chunk=32, ragged=True, speculate=2,
    )
    entries, _ = _replay_byte_exact(path, pipe)
    steps = [e for e in entries if e["kind"] == "step"]
    assert steps and all(e["dispatch"] in ("spec", "ragged")
                         for e in steps)
    assert any((e.get("accepted_tokens") or 0) > 1 for e in steps)


def test_replay_int8_kv(pipe, tmp_path):
    """int8 KV pool: quantize-on-write / dequant-in-walk is
    deterministic, so the journal replays byte-exact under it too."""
    path, _ = _capture(
        pipe, tmp_path,
        [("hello there", 8, None), ("what now?", 8, None)],
        num_slots=2, page_size=16, chunk=4, max_ctx=512,
        kv_dtype="int8",
    )
    entries, _ = _replay_byte_exact(path, pipe)
    header, _ = journal_lib.read_journal(path)
    assert header["config"]["kv_dtype"] == "int8"


def test_replay_prefix_cache_cow(pipe, tmp_path):
    """Prefix-cache hit with a COW tail: a page-aligned prompt re-sent
    matches whole, clamps to L-1, and the mid-page write copies the
    shared page — the splice entry (shared pages, COW copies) replays
    decision-for-decision and the spliced request's bytes still
    match."""
    ps = 16
    base = ("You are a meticulous multimodal assistant. Always answer "
            "with care and keep replies short. Describe it")
    L = len(pipe._prepare_request({"question": base})[0])
    q = base + "x" * ((-L) % ps)  # pad until the prompt is page-aligned
    path, _ = _capture(
        pipe, tmp_path, [(q, 6, None), (q, 6, None)],
        num_slots=1, page_size=ps, chunk=4, max_ctx=512,
    )
    entries, _ = _replay_byte_exact(path, pipe)
    splices = [e for e in entries if e["kind"] == "splice"]
    assert splices and any(e.get("cow_pages") for e in splices)
    assert any(e.get("spliced_tokens", 0) > 0 for e in splices)


def test_replay_host_spill_reload(pipe, tmp_path):
    """Host-RAM spill driven ORGANICALLY by pool pressure (a decision
    the journal records): a donated prefix spills to host when a later
    request's growth reclaims its pages, then a look-alike reloads it
    — splice carries host_reload_pages and the replay re-derives the
    spill and the reload."""
    import math

    ps, chunk = 8, 4
    pA = "spill tier prompt " * 3
    pB = "completely different filler text " * 3
    idsA = len(pipe._prepare_request({"question": pA})[0])
    idsB = len(pipe._prepare_request({"question": pB})[0])
    capA, capB = 6, 6
    pagesA = math.ceil((idsA + capA + chunk) / ps)
    pagesB = math.ceil((idsB + capB + chunk) / ps)
    # Pool sized so B's growth must reclaim A's donated cache pages
    # (shortfall -> prefix_cache.evict -> host spill), then A's rerun
    # reloads from the host tier.
    path, _ = _capture(
        pipe, tmp_path,
        [(pA, capA, None), (pB, capB, None), (pA, capA, None)],
        num_slots=1, page_size=ps, chunk=chunk, max_ctx=256,
        num_pages=max(pagesA, pagesB) + 2,
        host_cache_bytes=1 << 24,
    )
    entries, _ = _replay_byte_exact(path, pipe)
    splices = [e for e in entries if e["kind"] == "splice"]
    assert any((e.get("host_reload_pages") or 0) > 0 for e in splices), (
        "scenario did not exercise the host reload path: "
        f"{splices}"
    )


def test_replay_tp2_mesh(tmp_path):
    """tp=2 mesh pipeline: the journal is pipeline-agnostic — replay
    against the same meshed pipe reproduces the bytes."""
    if jax.device_count() < 2:
        pytest.skip("needs multiple (CPU) devices")
    from oryx_tpu.config import MeshConfig
    from oryx_tpu.parallel.mesh import build_mesh

    mesh = build_mesh(MeshConfig(tp=2), devices=jax.devices()[:2])
    cfg = cfg_lib.oryx_tiny()
    params = oryx.init_params(cfg, jax.random.key(0))
    tp_pipe = OryxInference(
        FakeTokenizer(), params, cfg, mesh=mesh, sharding_mode="tp"
    )
    path, _ = _capture(
        tp_pipe, tmp_path,
        [("hello there", 5, None), ("hello there friend", 5, None)],
        num_slots=2, page_size=16, chunk=4, max_ctx=512,
    )
    _replay_byte_exact(path, tp_pipe)


# ---------------------------------------------------------------------------
# Divergence report + what-if + never-perturb
# ---------------------------------------------------------------------------


def test_first_divergence_report_shape(pipe, tmp_path):
    """An injected mid-stream tamper yields exactly the triage tuple
    the runbook documents: index, seq, kind, field, both values."""
    path, _ = _capture(
        pipe, tmp_path, [("hello there", 5, None)],
        num_slots=1, page_size=16, chunk=4, max_ctx=512,
    )
    header, entries = journal_lib.read_journal(path)
    tampered = [dict(e) for e in entries]
    victim = next(e for e in tampered if e["kind"] == "step")
    victim["free_pages"] = (victim["free_pages"] or 0) + 7
    div = rj.first_divergence(entries, tampered)
    assert div is not None
    assert set(div) == {"index", "seq", "kind", "field", "live",
                        "replay"}
    assert div["kind"] == "step" and div["field"] == "free_pages"
    assert div["replay"] == div["live"] + 7
    assert div["seq"] == victim["seq"]
    # A truncated stream reports the missing side.
    div2 = rj.first_divergence(entries, entries[:-1])
    assert div2 is not None and div2["field"] == "<missing>"
    # Identity replays clean.
    assert rj.first_divergence(entries, entries) is None


def test_whatif_rows_and_report_schema(pipe, tmp_path):
    """--override replays the identical workload under altered flags
    and the diff table/report validates against its schema."""
    path, _ = _capture(
        pipe, tmp_path,
        [("hello there", 6, None), ("hello there again", 6, None)],
        num_slots=2, page_size=16, chunk=4, max_ctx=512,
    )
    header, entries = journal_lib.read_journal(path)
    res = rj.run_replay(
        header, entries, pipe=pipe,
        overrides={"prefix_cache": False}, timeout_s=300,
    )
    rows = rj.whatif_rows(entries, res["entries"])
    report = {
        "bench": "replay_whatif", "schema": rj.WHATIF_SCHEMA,
        "journal": path, "overrides": {"prefix_cache": False},
        "baseline": rj.summarize(entries),
        "current": rj.summarize(res["entries"]),
        "rows": rows,
    }
    assert rj.validate_whatif_report(report) == []
    by_series = {r["series"]: r for r in rows}
    # Same workload either way...
    assert (by_series["requests_finished"]["baseline"]
            == by_series["requests_finished"]["current"] == 2)
    # ...but no cache means no splices in the counterfactual.
    assert by_series["spliced_tokens"]["current"] == 0
    bad = rj.validate_whatif_report({"rows": [{}]})
    assert any("missing" in p for p in bad)


def test_journal_observes_never_perturbs(pipe, tmp_path):
    """Armed vs unarmed runs of the same workload: byte-identical
    replies and identical dispatch counts — journaling is read-only on
    the decision path."""
    reqs = [("hello there", 6, None), ("what now?", 6, None)]
    kw = dict(num_slots=2, page_size=16, chunk=4, max_ctx=512)
    m_armed = ServingMetrics()
    path, armed = _capture(
        pipe, tmp_path, reqs, metrics=m_armed, **kw
    )
    m_plain = ServingMetrics()
    sched = ContinuousScheduler(
        pipe, autostart=False, metrics=m_plain, **kw
    )
    handles = [
        sched.submit({"question": q}, cap, s) for q, cap, s in reqs
    ]
    sched.start()
    plain = [h.result(timeout=600) for h in handles]
    sched.close()
    assert [r[0] for r in armed] == [r[0] for r in plain]
    assert sched.journal is None
    for series in ("decode_steps_total", "prefill_tokens_total"):
        assert m_armed.get(series) == m_plain.get(series), series


def test_journal_seq_joins_wide_events(pipe, tmp_path):
    """Satellite contract: every terminal wide event carries the
    journal_seq of its submit entry (the ledger <-> journal join key);
    disarmed runs carry None."""
    rlog = RequestLog(None, keep=16)
    path, _ = _capture(
        pipe, tmp_path, [("hello there", 5, None)],
        num_slots=1, page_size=16, chunk=4, max_ctx=512,
        request_log=rlog,
    )
    header, entries = journal_lib.read_journal(path)
    submit = next(e for e in entries if e["kind"] == "submit")
    ev = rlog.snapshot(1)[0]
    assert ev["journal_seq"] == submit["seq"]
    assert ev["request_id"] == submit["request_id"]


# ---------------------------------------------------------------------------
# Journals from a tree that still had --fuse-steps (before PR 48)
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def default_journal(pipe, tmp_path_factory):
    path, _ = _capture(
        pipe, tmp_path_factory.mktemp("default_journal"),
        [("hello there", 6, None), ("what now then", 5, None)],
        num_slots=2, page_size=16, chunk=4, max_ctx=512,
    )
    return journal_lib.read_journal(path)


def _with_header(header, **config):
    return {**header, "config": {**header["config"], **config}}


def _stamp_first_step(entries):
    steps = [i for i, e in enumerate(entries) if e["kind"] == "step"]
    out = [dict(e) for e in entries]
    out[steps[0]].update(fused_k=4, fused_j=0)
    return out


@pytest.mark.parametrize("edit_header, edit_entries", [
    (lambda h: _with_header(h, fuse_steps=4), list),
    (lambda h: _with_header(h, fuse_steps="auto"), list),
    (lambda h: _with_header(h, fuse_steps=1), _stamp_first_step),
], ids=["header-4", "header-auto", "entry-fused_k"])
def test_replay_refuses_a_journal_recorded_with_fuse_steps(
        pipe, default_journal, edit_header, edit_entries):
    """A journal is input from outside the program: one recorded under
    the K-step megastep is refused by name, never replayed into a
    divergence nobody can explain."""
    header, entries = default_journal
    with pytest.raises(ValueError, match="--fuse-steps.*no longer has"):
        rj.run_replay(
            edit_header(header), edit_entries(entries), pipe=pipe)


def test_replay_of_a_header_that_says_fuse_steps_1(pipe, default_journal):
    """Every journal the parent wrote by default says `fuse_steps: 1`
    in its header: it replays byte-exact, and the key is not a
    constructor argument any more."""
    header, entries = default_journal
    assert "fuse_steps" not in header["config"]
    assert "fuse_steps" not in rj.GEOMETRY_KEYS
    res = rj.run_replay(
        _with_header(header, fuse_steps=1), entries, pipe=pipe,
        timeout_s=300)
    assert rj.first_divergence(entries, res["entries"]) is None
    matched, total, bad = rj.reply_match(entries, res["entries"])
    assert matched == total == 2, bad
