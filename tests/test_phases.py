"""The host's time, named (PR 24): the engine and trainer loops' phases
(utils/profiling.PhaseClock) as exclusive seconds in /metrics and as
host events in a profiler capture, read back with the benchmark's own
trace reader; and `prefill_s`, which now is the `admission` span.
PR 35: the seconds in which the device waited for the engine
(`engine_starved_seconds_total`), `harvest` ending where the device
drains (`copy_out`), a request-less stretch as ONE `idle` event, and
stalls (`engine_stall_seconds_total`)."""

import dataclasses
import statistics
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark import trace as bench_trace
from oryx_tpu import config as cfg_lib
from oryx_tpu.models import oryx
from oryx_tpu.serve.pipeline import OryxInference
from oryx_tpu.serve.scheduler import (
    ENGINE_PHASES, STALL_SECONDS, ContinuousScheduler,
)
from oryx_tpu.train.trainer import Trainer
from oryx_tpu.utils import faults
from oryx_tpu.utils.metrics import ServingMetrics
from oryx_tpu.utils.profiling import PhaseClock
from test_scheduler import FakeTokenizer
from test_trainer_modes import _batch as train_batch
from test_trainer_modes import _cfg as train_cfg

TRAIN_PHASES = ("data", "h2d", "dispatch", "sync", "log")
ENGINES = {
    "split": dict(prefill_chunk=16),
    "ragged": dict(prefill_chunk=16, ragged=True),
}


@pytest.fixture(scope="module")
def pipe():
    cfg = cfg_lib.oryx_tiny()
    params = oryx.init_params(cfg, jax.random.key(0))
    return OryxInference(FakeTokenizer(), params, cfg)


def requests():
    """Short and long prompts (the long one takes several prefill
    chunks of 16) and one image, so every engine phase has work."""
    img = np.random.default_rng(7).integers(
        0, 255, size=(40, 56, 3), dtype=np.uint8
    )
    return [
        ({"question": "hello there"}, 6),
        ({"question": "tell me more about " + "this and that " * 5}, 9),
        ({"question": "what is this?", "images": [img]}, 5),
        ({"question": "what now?"}, 7),
    ]


def new_engine(pipe, engine: str, metrics, **kw):
    return ContinuousScheduler(
        pipe, num_slots=2, page_size=16, chunk=4, max_ctx=512,
        metrics=metrics, autostart=False, **{**ENGINES[engine], **kw},
    )


def by_phase(metrics, family: str) -> dict:
    fam = metrics.registry.existing(family)
    return {p: fam.labels(phase=p).value for p in ENGINE_PHASES}


def run_engine(pipe, engine: str, *, idle_s: float = 0.0,
               idle_first_s: float = 0.0):
    """One scheduler's life: submit, start, collect, close (with
    `idle_first_s` the loop starts that long before the first submit).
    Returns (replies, phase seconds, the loop's wall seconds, handles,
    the metrics)."""
    metrics = ServingMetrics()
    sched = new_engine(pipe, engine, metrics)
    t0 = time.perf_counter()
    if idle_first_s:
        sched.start()
        time.sleep(idle_first_s)
    handles = [sched.submit(req, cap, None) for req, cap in requests()]
    if not idle_first_s:
        t0 = time.perf_counter()
        sched.start()
    replies = [h.result(timeout=600) for h in handles]
    time.sleep(idle_s)
    sched.close()  # joins the engine thread: its last phase is billed
    wall = time.perf_counter() - t0
    seconds = by_phase(metrics, "engine_phase_seconds_total")
    return replies, seconds, wall, handles, metrics


# ---- the primitive -------------------------------------------------------


def test_phase_seconds_are_exclusive_and_add_up():
    got = {}
    t0 = time.perf_counter()
    clock = PhaseClock(
        "oryx.test", lambda n, s: got.__setitem__(n, got.get(n, 0) + s),
        base="rest",
    )
    with clock.phase("outer"):
        time.sleep(0.02)
        with clock.phase("inner", "blocked"):
            time.sleep(0.03)
        time.sleep(0.01)
    with clock.phase("last", "dispatch"):
        pass
    wall = time.perf_counter() - t0
    # a nested phase's seconds are not counted again in its parent ...
    assert got["inner"] == pytest.approx(0.03, abs=0.008)
    assert got["outer"] == pytest.approx(0.03, abs=0.008)
    # ... and with the base phase everything adds up to the wall time
    assert set(got) == {"rest", "outer", "inner", "last"}
    assert sum(got.values()) == pytest.approx(wall, abs=1e-3)


def test_a_phase_with_no_capture_running_costs_microseconds():
    clock = PhaseClock("oryx.test", lambda n, s: None, base="rest")
    cost = []
    for _ in range(1000):
        t = time.perf_counter()
        with clock.phase("x"):
            pass
        cost.append(time.perf_counter() - t)
    assert statistics.median(cost) < 50e-6


def test_starved_seconds_are_those_under_the_host_event():
    """What `starved` is handed adds up to the time `<prefix>.host` was
    open: from a top-level blocked phase's return to the end of the
    next dispatch phase, and to the entry of a blocked phase that comes
    first; a "wait" opens nothing. The recorded seconds still add up
    to the wall time."""
    got, starved = {}, {}

    def into(d):
        return lambda n, s: d.__setitem__(n, d.get(n, 0) + s)

    t0 = time.perf_counter()
    clock = PhaseClock("oryx.test", into(got), base="rest",
                       starved=into(starved))
    time.sleep(0.01)  # nothing has drained yet: not starved
    with clock.phase("wait_a", "blocked"):
        time.sleep(0.01)
    time.sleep(0.02)  # rest, starved
    with clock.phase("work"):
        time.sleep(0.01)  # starved
        with clock.phase("inner"):
            time.sleep(0.01)  # starved, and not again in its parent
    with clock.phase("enqueue", "dispatch"):
        time.sleep(0.02)  # starved to its end
    time.sleep(0.01)  # the device has work: not starved
    with clock.phase("wait_b", "wait"):
        time.sleep(0.01)
    time.sleep(0.01)  # a "wait" returned: still not starved
    with clock.phase("wait_c", "blocked"):
        time.sleep(0.01)
    time.sleep(0.02)  # starved, up to the next blocked phase's entry
    with clock.phase("wait_d", "blocked"):
        time.sleep(0.01)
    wall = time.perf_counter() - t0
    assert sum(got.values()) == pytest.approx(wall, abs=1e-3)
    # whole stretches, so the very seconds the phase was billed
    assert set(starved) == {"rest", "work", "inner", "enqueue"}
    assert all(starved[p] == got[p] for p in ("work", "inner", "enqueue"))
    # of `rest`, the two stretches after a wait's return (a sleep never
    # ends early), and not the three with work queued or none drained
    assert 0.039 <= starved["rest"] <= got["rest"] - 0.029


def test_a_held_phase_is_entered_once_and_billed_at_every_call():
    calls = []
    clock = PhaseClock("oryx.test", lambda n, s: calls.append((n, s)),
                       base="rest")
    clock.release()  # nothing held: nothing happens
    assert calls == []
    for _ in range(3):
        clock.hold("quiet")
        time.sleep(0.01)
    clock.release()
    with clock.phase("work"):
        pass
    names = [n for n, _ in calls]
    assert names == ["rest", "quiet", "quiet", "quiet", "rest", "work"]
    assert all(s >= 0.009 for n, s in calls if n == "quiet")
    clock.close()


# ---- (a) the counters are exhaustive -------------------------------------


@pytest.fixture(scope="module")
def plain_runs(pipe):
    return {e: run_engine(pipe, e, idle_s=0.5) for e in ENGINES}


@pytest.mark.parametrize("engine", list(ENGINES))
def test_engine_phase_seconds_add_up_to_the_loops_wall_time(
    plain_runs, engine
):
    _, seconds, wall, _, _ = plain_runs[engine]
    # every phase of the table ran on this path (idle: the wait before
    # close; prompt_prep / embed: the image; first_token: each admission)
    assert all(seconds[p] > 0 for p in ENGINE_PHASES), seconds
    assert sum(seconds.values()) == pytest.approx(wall, rel=0.02)


@pytest.mark.parametrize("engine", list(ENGINES))
def test_starved_seconds_stay_inside_their_phase_and_no_wait_has_any(
    plain_runs, engine
):
    _, seconds, _, _, metrics = plain_runs[engine]
    starved = by_phase(metrics, "engine_starved_seconds_total")
    assert all(starved[p] <= seconds[p] + 1e-9 for p in ENGINE_PHASES)
    # the device has work, or nobody asks for any, while the host waits
    assert [starved[p] for p in ("harvest", "first_token", "idle")] == [
        0.0, 0.0, 0.0]
    # ... and between a blocked wait's return and the next enqueue it
    # has none. The split engine keeps a chunk in flight (PR 47): its
    # decode enqueue follows a harvest that left the device with work,
    # so only what follows a DRAIN's harvest is starved there.
    after = ("emit", "copy_out") if engine == "split" else (
        "emit", "housekeeping", "decode")
    assert all(starved[p] > 0 for p in after), starved
    assert sum(starved.values()) < sum(seconds.values()) - seconds["idle"]


@pytest.mark.parametrize("engine", list(ENGINES))
def test_copy_out_is_counted_and_starved_after_every_harvest(
    plain_runs, engine
):
    _, seconds, _, _, metrics = plain_runs[engine]
    starved = by_phase(metrics, "engine_starved_seconds_total")
    if engine == "split":
        # PR 47: the copies behind a harvest with a chunk enqueued
        # behind it are not the device's wait; a drain's are.
        assert 0 < starved["copy_out"] < seconds["copy_out"]
        assert metrics.get("decode_dispatches_ahead_total") > 0
    else:
        assert 0 < starved["copy_out"] == pytest.approx(
            seconds["copy_out"], rel=1e-6)
    assert metrics.get("harvest_total") > 0


def test_a_chunk_in_flight_starves_neither_its_harvest_nor_the_emit(pipe):
    """PR 47: with chunk n+1 enqueued behind it the harvest of chunk n
    is a wait for a device that has work when it returns (`PhaseClock`
    kind "wait"): none of the seconds of `harvest`, `copy_out`, `emit`
    and `first_token` that pass while a chunk is in flight are billed
    as starved. The harvest that drains the engine is `blocked` as
    before: it is not starved itself, and what follows it is."""
    metrics = ServingMetrics()
    sched = new_engine(pipe, "split", metrics)
    seen = {"phase": [], "starved": []}
    names = ("harvest", "copy_out", "emit", "first_token")
    for kind, billed in (("phase", sched._phase_seconds),
                         ("starved", sched._starved_seconds)):
        for name in names:
            def spy(s, _real=billed[name], _to=seen[kind], _name=name):
                _to.append((_name, sched._inflight is not None))
                _real(s)
            billed[name] = spy
    handles = [sched.submit(req, cap, None) for req, cap in requests()]
    sched.start()
    for h in handles:
        h.result(timeout=600)
    sched.close()
    assert metrics.get("decode_dispatches_ahead_total") >= 4
    flying = {n for n, inflight in seen["phase"] if inflight}
    assert flying == set(names)
    assert not [n for n, inflight in seen["starved"] if inflight]
    drained = {n for n, inflight in seen["starved"] if not inflight}
    assert {"copy_out", "emit"} <= drained
    assert not {"harvest", "first_token"} & drained
    fam = metrics.registry.existing("engine_starved_seconds_total")
    assert fam.labels(phase="harvest").value == 0
    assert fam.labels(phase="first_token").value == 0
    assert fam.labels(phase="copy_out").value > 0


def _device_outputs(sched):
    """A dispatch's outputs as the harvest is handed them."""
    n, rng = sched.num_slots, np.random.default_rng(3)
    tok = jnp.asarray(rng.integers(0, 99, n), jnp.int32)
    lengths = jnp.asarray(rng.integers(1, 99, n), jnp.int32)
    finished = jnp.asarray([True, False])
    recent = jnp.asarray(rng.integers(0, 99, (n, 4)), jnp.int32)
    toks = jnp.asarray(rng.integers(0, 99, (n, 4)), jnp.int32)
    fin = jnp.asarray(rng.integers(0, 2, (n, 4)).astype(bool))
    return tok, lengths, finished, recent, toks, fin


@pytest.mark.parametrize("which", ["chunk", "spec"])
def test_the_split_harvest_returns_what_the_one_phase_harvest_did(
    pipe, which
):
    metrics = ServingMetrics()
    sched = new_engine(pipe, "split", metrics)
    tok, lengths, finished, recent, toks, fin = _device_outputs(sched)
    try:
        if which == "chunk":
            out = sched._read_chunk(
                tok, lengths, finished, recent, toks, fin)
            want_out = (toks, fin)
            state = {"tok": tok, "lengths": lengths,
                     "finished": finished, "recent": recent}
        else:
            n_new, acc = lengths, tok
            out = sched._harvest_spec(
                tok, lengths, finished, toks, n_new, acc)
            want_out = (toks, n_new, acc)
            state = {"tok": tok, "lengths": lengths, "finished": finished}
    finally:
        sched.close()
    assert len(out) == len(want_out)
    for got, want in zip(out, want_out):
        np.testing.assert_array_equal(got, np.asarray(want))
    for name, want in state.items():
        got = getattr(sched, name)
        np.testing.assert_array_equal(got, np.asarray(want))
        assert got.dtype == np.asarray(want).dtype
        assert got.flags.writeable  # the engine's own copy
    seconds = by_phase(metrics, "engine_phase_seconds_total")
    assert seconds["harvest"] > 0 and seconds["copy_out"] > 0
    assert metrics.get("harvest_total") == 1


# ---- (a2) stalls ---------------------------------------------------------


@pytest.mark.parametrize("delay,stalled", [(0.7, True), (0.2, False)])
def test_one_stretch_over_the_limit_is_a_stall(
    pipe, plain_runs, delay, stalled
):
    """A decode dispatch held up by `delay` s (the fault point sits in
    the loop's base phase, housekeeping): counted under that phase and
    marked on the resident request's trace when it is over
    STALL_SECONDS, and not otherwise."""
    assert 0.2 < STALL_SECONDS < 0.7
    metrics = ServingMetrics()
    sched = new_engine(pipe, "split", metrics)
    sched.start()
    try:
        # first, whatever this process has yet to compile
        sched.submit({"question": "hello there"}, 6, None).result(
            timeout=600)
        before = by_phase(metrics, "engine_stall_seconds_total")
        faults.configure(f"decode_dispatch:delay={delay},times=1")
        h = sched.submit({"question": "what now?"}, 7, None)
        h.result(timeout=600)
    finally:
        faults.reset()
        sched.close()
    after = by_phase(metrics, "engine_stall_seconds_total")
    added = after["housekeeping"] - before["housekeeping"]
    marks = [s for s in h.trace.spans if s.name == "engine_stall"]
    if not stalled:
        assert added == 0 and not marks
        return
    assert delay <= added < delay + 1.0
    (mark,) = marks
    assert mark.args["phase"] == "housekeeping"
    assert mark.args["seconds"] == pytest.approx(added)
    assert mark.args["last_dispatch"] in ("prefill", "decode")
    seconds = by_phase(metrics, "engine_phase_seconds_total")
    assert all(after[p] <= seconds[p] + 1e-9 for p in ENGINE_PHASES)
    assert after["idle"] == 0  # however long nobody asks


# ---- (b) the phases are in a profiler capture ----------------------------


@pytest.fixture(scope="module")
def captured(pipe, tmp_path_factory):
    """ONE capture on the CPU around a split-engine run and three
    trainer steps, read back with the benchmark's reader."""
    tmp = tmp_path_factory.mktemp("phases")
    cfg = train_cfg(tmp, "ckpt")
    cfg = dataclasses.replace(
        cfg, train=dataclasses.replace(cfg.train, num_train_steps=3)
    )
    batch = train_batch(cfg)
    trainer = Trainer(cfg, sharding_mode="fsdp")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0  # as the benchmark captures
    jax.profiler.start_trace(str(tmp / "trace"), profiler_options=opts)
    try:
        replies, *_ = run_engine(pipe, "split")
        trainer.fit(iter([batch] * 3), num_steps=3, resume=False,
                    prefetch=0)
    finally:
        jax.profiler.stop_trace()
        trainer.close()
    planes = bench_trace.parse_xspace(
        bench_trace.find_xplane_files(str(tmp / "trace"))[-1]
    )
    stats = next(p.stats for p in planes if "profile_start_time" in p.stats)
    length_ps = 1000 * (
        stats["profile_stop_time"] - stats["profile_start_time"]
    )
    spans = [s for s in bench_trace.host_span_list(planes)
             if s[2].startswith("oryx.")]
    return {"replies": replies, "spans": spans, "length_ps": length_ps}


def test_a_request_less_stretch_is_one_idle_event(pipe, tmp_path, captured):
    """The loop wakes every 0.1 s while nobody asks; a capture holds
    the stretch before the first request as ONE `oryx.engine.idle`
    event with no `oryx.engine.host` inside it, and the counter has its
    seconds."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        *_, metrics = run_engine(pipe, "split", idle_first_s=0.35)
    finally:
        jax.profiler.stop_trace()
    planes = bench_trace.parse_xspace(
        bench_trace.find_xplane_files(str(tmp_path))[-1]
    )
    spans = bench_trace.host_span_list(planes)
    idles = sorted(s for s in spans if s[2] == "oryx.engine.idle")
    first = idles[0]
    assert (first[1] - first[0]) / 1e12 >= 0.3
    # one event before the first dispatch, one after the last reply
    enqueues = [s for s in spans if s[2] in (
        "oryx.engine.prefill", "oryx.engine.decode")]
    assert [s for s in idles if s[0] < min(e[0] for e in enqueues)] == [
        first]
    assert len(idles) <= 2
    hosts = [s for s in spans if s[2] == "oryx.engine.host"]
    assert hosts
    assert not any(s < first[1] and e > first[0] for s, e, _ in hosts)
    # a gap the length of the stretch is named by it
    assert bench_trace._covering_span(
        spans, first[0], first[1]) == "oryx.engine.idle"
    idle_s = by_phase(metrics, "engine_phase_seconds_total")["idle"]
    assert idle_s >= (first[1] - first[0]) / 1e12 - 0.01


def _nested(spans) -> bool:
    """Any two events of one thread are disjoint or one holds the
    other."""
    spans = sorted(spans, key=lambda s: (s[0], -s[1]))
    stack = []
    for s, e, _ in spans:
        while stack and stack[-1] <= s:
            stack.pop()
        if stack and e > stack[-1]:
            return False
        stack.append(e)
    return True


@pytest.mark.parametrize("prefix,phases", [
    ("oryx.engine.", tuple(p for p in ENGINE_PHASES if p != "idle")
     + ("host",)),
    ("oryx.train.", TRAIN_PHASES[:-1] + ("host",)),  # log has no block
])
def test_a_capture_holds_every_phase_inside_its_bounds_and_nested(
    captured, prefix, phases
):
    mine = [s for s in captured["spans"] if s[2].startswith(prefix)]
    assert {prefix + p for p in phases} <= {name for _, _, name in mine}
    assert all(0 <= s < e <= captured["length_ps"] for s, e, _ in mine)
    assert _nested(mine)


def test_a_gap_between_two_dispatches_is_labelled_with_an_engine_phase(
    captured
):
    spans = captured["spans"]
    harvests = sorted(s for s in spans if s[2] == "oryx.engine.harvest")
    enqueues = sorted(s for s in spans if s[2] in (
        "oryx.engine.prefill", "oryx.engine.decode"))
    assert len(harvests) >= 2
    labels = []
    for _, h_end, _ in harvests:
        # the device would idle from the harvest's return to the next
        # enqueue: made of emit, housekeeping, admit ... each too short
        # to cover it alone. (With a chunk in flight, PR 47, the last
        # harvests come in a row, with no enqueue after them; and only
        # a harvest that DRAINED the device is followed by a `host`
        # event.)
        later = [e for s, e, _ in enqueues if s >= h_end]
        if later:
            labels.append(
                bench_trace._covering_span(spans, h_end, min(later)))
    assert all(label.startswith("oryx.engine.") for label in labels), labels
    # A harvest that drained the device is followed at once by the
    # `host` event (the first request ends while the second, four
    # prefill chunks long, is still admitted: nobody rides a next
    # chunk); one with a chunk enqueued behind it opens none.
    hosts = [s for s in spans if s[2] == "oryx.engine.host"]
    drained = [h for h in harvests
               if any(0 <= hs - h[1] < 2e8 for hs, _, _ in hosts)]
    assert 0 < len(drained) < len(harvests), (len(drained), len(harvests))


# ---- (d) tracing does not change a token ---------------------------------


def test_replies_are_token_identical_with_a_capture_running(
    pipe, plain_runs, captured
):
    plain = plain_runs["split"][0]
    assert captured["replies"] == plain
    for (req, cap), (reply, _, usage) in zip(requests(), plain):
        assert usage[1] == cap
        if "images" not in req:
            assert reply == pipe.chat(req["question"], max_new_tokens=cap)


# ---- (c) prefill_s is the admission span ---------------------------------


def _long_request_cost(pipe, *, resident: bool):
    """prefill_s of a 4-chunk prompt, admitted alone or behind a
    resident stream whose decode chunks (each 50 ms slower by an
    injected delay) run between its prefill chunks."""
    metrics = ServingMetrics()
    sched = ContinuousScheduler(
        pipe, num_slots=2, page_size=16, chunk=4, max_ctx=512,
        metrics=metrics, autostart=False, prefill_chunk=16,
        prefix_cache=False,
    )
    handles = []
    if resident:
        handles.append(sched.submit({"question": "hi"}, 40, None))
    long_q = "tell me more about " + "this and that " * 3
    handles.append(sched.submit({"question": long_q}, 2, None))
    faults.configure("decode_dispatch:delay=0.05")
    try:
        sched.start()
        for h in handles:
            h.result(timeout=600)
    finally:
        faults.reset()
        sched.close()
    return handles[-1], handles[-1].trace, metrics


def test_prefill_s_is_queue_head_to_first_token(pipe):
    h, tr, metrics = _long_request_cost(pipe, resident=False)
    by = tr.span_seconds()
    chunks = [s for s in tr.spans if s.name == "prefill"]
    assert len(chunks) >= 3
    cost = h.debug["cost"]
    # the ledger and the histogram read the admission span ...
    assert cost["prefill_s"] == pytest.approx(by["admission"], abs=2e-6)
    hist = metrics.registry.existing("request_prefill_seconds").labels()
    assert (hist.total, hist.sum) == (1, cost["prefill_s"])
    # ... which holds every prefill enqueue and ends with the first token
    assert cost["prefill_s"] >= by["prefill"]
    assert cost["prefill_s"] <= h.debug["ttft_s"]
    (adm,) = [s for s in tr.spans if s.name == "admission"]
    assert all(
        adm.start_ns <= c.start_ns
        and c.start_ns + c.dur_ns <= adm.start_ns + adm.dur_ns
        for c in chunks
    )

    # a decode chunk between two of its chunks makes it longer: the
    # enqueue-timed `prefill` spans could not see that
    h2, tr2, _ = _long_request_cost(pipe, resident=True)
    between = len([s for s in tr2.spans if s.name == "prefill"]) - 1
    assert h2.debug["cost"]["prefill_s"] >= (
        cost["prefill_s"] + 0.05 * between * 0.9
    )
    assert tr2.span_seconds()["prefill"] < 0.05 * between
