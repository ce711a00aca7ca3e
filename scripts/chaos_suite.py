"""Chaos suite: every named fault scenario against a LIVE tiny server,
asserting the invariant triad after each one.

Scenarios (one armed `utils/faults.py` spec each, fully deterministic):

  * ``page_alloc_oom``    injected pool exhaustion during a concurrent
                          shared-prefix burst — defer/evict absorbs it;
                          every request still answers 200.
  * ``engine_crash``      engine-thread death mid-decode — the
                          EngineSupervisor restarts with deterministic
                          replay; the client's reply is byte-identical
                          to the solo pipeline and /readyz recovers.
  * ``journaled_crash``   the same engine-thread death with the
                          decision journal armed (--journal): the
                          fault firing and supervisor restart land in
                          the journal, and scripts/replay_journal.py
                          replays the file offline bit-for-bit —
                          decision-for-decision equal, reply
                          fingerprints identical.
  * ``hung_dispatch``     a decode dispatch stalls past the
                          per-request deadline — the request converts
                          into a clean 504, pages freed.
  * ``client_disconnect`` the SSE write path raises BrokenPipeError
                          (the dropped-socket code path) — the request
                          cancels, pages and cache shares freed.
  * ``spec_drift``        an oracle drafter degrades mid-run into
                          proposing garbage — the spec_accept_collapse
                          detector fires EXACTLY ONE event for the
                          whole episode, replies stay byte-identical
                          (rejected drafts are dead lanes), zero leaks.
  * ``checkpoint_save``   injected save failures — bounded
                          exponential-backoff retry lands the
                          checkpoint; the schedule is pinned (no
                          wall-clock sleeps).

The invariant triad, asserted after EVERY serving scenario:

  1. pool `check_invariant(holders)` holds — every page free or
     exactly accounted to its holders (slots + prefix cache);
  2. zero leaked pages/refcounts — with all slots idle, free pages +
     cache-held pages == the whole pool;
  3. the server RETURNS TO SERVING — /readyz 200, a fresh completion
     answers 200, and `oryx_faults_injected_total{site=}` in /metrics
     reconciles exactly against the injection schedule's own count.

Exit 0 = all scenarios contained; nonzero prints the failing scenario.
Wired into scripts/check_tier1.sh. See docs/OBSERVABILITY.md "Failure
playbook" for what each scenario looks like in production telemetry.
"""

from __future__ import annotations

import json
import os
import re
import sys
import threading
import time

sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)

os.environ.setdefault("JAX_PLATFORMS", "cpu")
# A chaos run must never inherit ambient fault specs on top of the
# per-scenario ones this script arms itself.
os.environ.pop("ORYX_FAULTS", None)


class _Tokenizer:
    def encode(self, text, add_special_tokens=False):
        return [min(ord(c), 500) for c in text]

    def decode(self, ids, skip_special_tokens=True):
        return "".join(chr(i) for i in ids if 0 < i < 500)


def fail(msg: str) -> None:
    print(f"FAIL: {msg}", file=sys.stderr)
    raise SystemExit(1)


def wait_for(predicate, timeout=120.0, what="condition") -> None:
    end = time.monotonic() + timeout
    while time.monotonic() < end:
        if predicate():
            return
        time.sleep(0.05)
    fail(f"timed out waiting for {what}")


class Harness:
    """One tiny in-process server per scenario: build, run the
    scenario body, assert the triad, tear down."""

    def __init__(self, pipe):
        self.pipe = pipe

    def boot(self, faults_spec: str, **server_kw):
        from oryx_tpu.serve import api_server

        srv = api_server.build_server(
            self.pipe, port=0, engine="continuous", num_slots=2,
            page_size=16, decode_chunk=4, max_ctx=512,
            faults_spec=faults_spec, **server_kw,
        )
        threading.Thread(target=srv.serve_forever, daemon=True).start()
        return srv, f"http://127.0.0.1:{srv.server_address[1]}"

    def teardown(self, srv) -> None:
        from oryx_tpu.utils import faults

        faults.reset()
        if srv.supervisor is not None:
            srv.supervisor.stop()
        if srv.scheduler is not None:
            srv.scheduler.close()
        srv.shutdown()

    # -- HTTP helpers (utils/retry.urlopen_json: rides out the engine
    # -- restart window instead of failing on one refused connect) ----

    def get(self, url: str, **kw):
        from oryx_tpu.utils.retry import urlopen_json

        return urlopen_json(url, **kw)

    def post_chat(self, base: str, content: str, max_tokens: int,
                  timeout: float = 600.0):
        return self.get(
            base + "/v1/chat/completions",
            data=json.dumps({
                "messages": [{"role": "user", "content": content}],
                "max_tokens": max_tokens,
            }).encode(),
            headers={"Content-Type": "application/json"},
            timeout=timeout,
        )

    # -- the triad -----------------------------------------------------

    def assert_triad(self, srv, base: str, scenario: str,
                    sites: list[str]) -> None:
        from oryx_tpu.utils import faults

        sched = srv.scheduler
        wait_for(
            lambda: all(r is None for r in sched.slots)
            and sched.queue_len() == 0,
            what=f"[{scenario}] slots+queue to empty",
        )
        # 1. Pool invariant: every page free or exactly accounted.
        sched._check_pool_invariant()
        # 2. Zero leaks: with no residents, only the prefix cache may
        #    hold pages. (The cache is engine-thread-owned; this read
        #    is legal because the wait above proved quiescence — say
        #    so to the armed race detector instead of tripping it.)
        from oryx_tpu.analysis.sanitizers import race_exempt

        with race_exempt("zero-leak check after quiesce"):
            cache_pages = (
                len(sched.prefix_cache.held_pages())
                if sched.prefix_cache is not None else 0
            )
        if sched.allocator.num_free + cache_pages != sched.num_pages:
            fail(f"[{scenario}] leaked pages: free "
                 f"{sched.allocator.num_free} + cache {cache_pages} "
                 f"!= pool {sched.num_pages}")
        # 3a. Back to serving: /readyz 200 and a real completion works.
        status, body, _ = self.get(base + "/readyz", timeout=30)
        if status != 200 or body.get("ready") is not True:
            fail(f"[{scenario}] /readyz after the scenario: want "
                 f"200/true, got {status} {body}")
        status, body, _ = self.post_chat(base, "post-chaos probe", 3)
        if status != 200:
            fail(f"[{scenario}] post-scenario completion: want 200, "
                 f"got {status} {body}")
        # 3b. Metric reconciliation: what /metrics says happened is
        #     exactly what the armed schedule says it injected.
        import urllib.request

        with urllib.request.urlopen(base + "/metrics", timeout=30) as r:
            if r.status != 200:
                fail(f"[{scenario}] /metrics scrape: want 200, got "
                     f"{r.status}")
            text = r.read().decode()
        total = 0
        for site in sites:
            m = re.search(
                rf'^oryx_faults_injected_total\{{site="{site}"\}} '
                rf"([0-9.e+-]+)$", text, re.M,
            )
            metric = float(m.group(1)) if m else 0.0
            count = faults.injected_count(site)
            if metric != count:
                fail(f"[{scenario}] oryx_faults_injected_total"
                     f'{{site="{site}"}} is {metric}, injector '
                     f"counted {count}")
            total += count
        print(f"  [{scenario}] contained: invariant holds, 0 leaks, "
              f"/readyz 200, {total} fault(s) injected and accounted")


# ---------------------------------------------------------------------
# Scenarios
# ---------------------------------------------------------------------


def scenario_page_alloc_oom(h: Harness) -> None:
    """Injected pool exhaustion on a deterministic schedule while a
    shared-prefix burst runs: allocation failure is a scheduling
    signal (defer / evict / COW-fallback) — every request answers."""
    srv, base = h.boot("page_alloc_oom:every=3,times=6")
    try:
        sysprompt = "shared prefix for the chaos burst to splice! "
        results: list[tuple[int, object]] = []

        def one(i: int) -> None:
            status, body, _ = h.post_chat(
                base, sysprompt + f"q{i}", 3 + i % 2
            )
            results.append((status, body))

        threads = [
            threading.Thread(target=one, args=(i,)) for i in range(4)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        bad = [r for r in results if r[0] != 200]
        if bad:
            fail(f"[page_alloc_oom] burst requests failed under "
                 f"injected OOM: {bad}")
        h.assert_triad(srv, base, "page_alloc_oom", ["page_alloc_oom"])
        # Forensics: every injected OOM must have left one bounded
        # record in the ring (pool summary reconciled at capture
        # time, top-K residents named), and the post-incident pool
        # map must reconcile — the capacity incident is diagnosable
        # AFTER the fact from /debug/oom alone.
        from oryx_tpu.utils import faults

        injected = faults.injected_count("page_alloc_oom")
        import urllib.request as _url

        with _url.urlopen(base + "/metrics", timeout=30) as r:
            mtext = r.read().decode()
        m = re.search(
            r'^oryx_serving_oom_forensics_total\{trigger="oom"\} '
            r"([0-9.e+-]+)$", mtext, re.M,
        )
        raised = float(m.group(1)) if m else 0.0
        # Every injected raise captures exactly one trigger="oom"
        # record (genuine free-list-shortfall episodes capture their
        # own trigger="pool_pressure" records and are not counted
        # against the injector).
        if raised != injected:
            fail(f"[page_alloc_oom] {raised:g} trigger=oom forensic "
                 f"record(s), injector counted {injected}")
        status, recs, _ = h.get(base + "/debug/oom?n=64", timeout=30)
        if status != 200 or recs.get("total", 0) < injected:
            fail(f"[page_alloc_oom] /debug/oom holds "
                 f"{recs.get('total')} record(s), want >= {injected}")
        for rec in recs.get("records") or []:
            if not rec.get("top_requests"):
                fail(f"[page_alloc_oom] forensic record "
                     f"#{rec.get('index')} has an empty top-K")
            if not (rec.get("pool") or {}).get("reconciled"):
                fail(f"[page_alloc_oom] forensic record "
                     f"#{rec.get('index')} captured an unreconciled "
                     f"pool: {rec.get('pool')}")
        status, pages, _ = h.get(
            base + "/debug/pages?format=summary", timeout=30
        )
        s = pages.get("summary") or {}
        if status != 200 or not s.get("reconciled") \
                or s.get("slot") != 0:
            fail(f"[page_alloc_oom] post-incident /debug/pages does "
                 f"not reconcile: {s}")
        print(f"  [page_alloc_oom] forensics: {injected} injected "
              f"OOM(s) -> {injected} trigger=oom record(s) "
              f"({recs.get('total')} total), pool map reconciled")
    finally:
        h.teardown(srv)


def scenario_engine_crash(h: Harness) -> None:
    """Engine-thread death mid-flight: the supervisor restarts the
    loop, the in-flight request replays deterministically, and the
    client's reply is byte-identical to the solo pipeline."""
    q, m = "hello there chaos", 10
    ref = h.pipe.chat(q, max_new_tokens=m)
    srv, base = h.boot("engine_crash:after=2")
    try:
        status, body, _ = h.post_chat(base, q, m)
        if status != 200:
            fail(f"[engine_crash] request through the crash: want "
                 f"200, got {status} {body}")
        reply = body["choices"][0]["message"]["content"]
        if reply != ref:
            fail(f"[engine_crash] replayed reply {reply!r} != solo "
                 f"pipeline {ref!r} — replay was not deterministic")
        wait_for(lambda: srv.scheduler.restarts >= 1, timeout=30,
                 what="[engine_crash] supervisor restart")
        if srv.metrics.get("engine_restarts_total") < 1:
            fail("[engine_crash] engine_restarts_total never moved")
        h.assert_triad(srv, base, "engine_crash", ["engine_crash"])
    finally:
        h.teardown(srv)


def scenario_journaled_crash(h: Harness) -> None:
    """The flight-recorder contract under chaos: a crash mid-burst is
    JOURNALED (--journal armed; fault firing + supervisor restart
    entries in the stream), and the journal file replays offline
    bit-for-bit — fault, restart and every decision reproduced, reply
    fingerprints identical (docs/OBSERVABILITY.md "Incident replay")."""
    import tempfile

    from oryx_tpu.serve import journal as journal_lib

    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    import replay_journal as rj

    jpath = os.path.join(tempfile.mkdtemp(), "journal.jsonl")
    srv, base = h.boot("engine_crash:after=3", journal_path=jpath)
    try:
        for i in range(3):
            status, body, _ = h.post_chat(
                base, f"journal me through the crash q{i}", 4 + i % 3
            )
            if status != 200:
                fail(f"[journaled_crash] request {i} through the "
                     f"crash: want 200, got {status} {body}")
        wait_for(lambda: srv.scheduler.restarts >= 1, timeout=30,
                 what="[journaled_crash] supervisor restart")
        h.assert_triad(srv, base, "journaled_crash", ["engine_crash"])
        # Quiesce the live engine, then replay the file offline.
        if srv.supervisor is not None:
            srv.supervisor.stop()
        srv.scheduler.close()
        header, entries = journal_lib.read_journal(jpath)
        kinds = {e.get("kind") for e in entries}
        if "fault" not in kinds or "restart" not in kinds:
            fail(f"[journaled_crash] the crash did not journal: kinds "
                 f"{sorted(kinds)} lack fault/restart")
        res = rj.run_replay(header, entries, pipe=h.pipe)
        if res["feed_errors"] or res["timed_out"] or res["gave_up"]:
            fail(f"[journaled_crash] offline replay did not run "
                 f"clean: feed_errors={res['feed_errors']} "
                 f"timed_out={res['timed_out']} gave_up={res['gave_up']}")
        div = rj.first_divergence(entries, res["entries"])
        if div is not None:
            fail(f"[journaled_crash] offline replay diverged from the "
                 f"live journal: {div}")
        matched, total, bad = rj.reply_match(entries, res["entries"])
        if matched != total or total < 3:
            fail(f"[journaled_crash] replayed reply fingerprints: "
                 f"{matched}/{total} matched (divergent ids {bad})")
        print(f"  [journaled_crash] replayed: crash + restart "
              f"journaled ({len(entries)} entries), offline replay "
              f"decision-for-decision equal, {matched}/{total} reply "
              "fingerprints identical")
    finally:
        h.teardown(srv)


def scenario_hung_dispatch(h: Harness) -> None:
    """The FIRST decode dispatch stalls past the per-request deadline:
    the next step boundary converts the hang into a clean 504 and
    frees the slot's pages."""
    srv, base = h.boot(
        "decode_dispatch:delay=2.0,after=0", request_timeout=0.75,
    )
    try:
        status, body, _ = h.post_chat(base, "about to hang", 64)
        if status != 504:
            fail(f"[hung_dispatch] want 504 from the deadline, got "
                 f"{status} {body}")
        if body["error"]["type"] != "timeout_error":
            fail(f"[hung_dispatch] error type {body['error']} is not "
                 "timeout_error")
        if srv.metrics.get("deadline_exceeded_total") < 1:
            fail("[hung_dispatch] deadline_exceeded_total never moved")
        # The post-scenario probe in the triad must NOT inherit the
        # deadline that 504s everything — lift it (server default for
        # new requests only; the scenario's own request already ran).
        srv.scheduler.request_timeout = None
        h.assert_triad(srv, base, "hung_dispatch", ["decode_dispatch"])
    finally:
        h.teardown(srv)


def scenario_client_disconnect(h: Harness) -> None:
    """The SSE write path raises BrokenPipeError (the exact dropped-
    socket code path): the request cancels and its pages and
    prefix-cache shares come back."""
    import urllib.error
    import urllib.request

    srv, base = h.boot("client_disconnect:after=0")
    try:
        req = urllib.request.Request(
            base + "/v1/chat/completions",
            data=json.dumps({
                "messages": [
                    {"role": "user", "content": "stream then vanish"}
                ],
                "max_tokens": 200, "stream": True,
            }).encode(),
            headers={"Content-Type": "application/json"},
        )
        # The injected BrokenPipeError kills the response mid-stream;
        # whatever the client sees (truncated body, reset) is fine —
        # the assertion is server-side.
        # fault-boundary: the client half of an injected disconnect
        try:
            with urllib.request.urlopen(req, timeout=600) as r:
                r.read()
        except (OSError, urllib.error.URLError):
            pass
        wait_for(lambda: srv.metrics.get("cancelled") >= 1,
                 what="[client_disconnect] cancellation")
        h.assert_triad(
            srv, base, "client_disconnect", ["client_disconnect"]
        )
    finally:
        h.teardown(srv)


def scenario_spec_drift(h: Harness) -> None:
    """Speculation drift guard (ISSUE 14 satellite): an ORACLE drafter
    (proposes the request's known future — accept rate k+1) degrades
    mid-run into proposing garbage (accept rate collapses to 1.0).
    The spec_accept_collapse detector — default-armed whenever
    --speculate is set — must fire EXACTLY ONE event for the whole
    degraded phase (one page per episode, not one per dispatch), and
    the engine must stay healthy: every reply byte-identical to the
    solo pipeline, pool invariant intact, zero leaks."""
    from oryx_tpu.models import generate as gen_lib
    from oryx_tpu.serve.scheduler import ContinuousScheduler
    from oryx_tpu.utils.anomaly import AnomalyMonitor

    q, cap = "tell me a long story please", 40
    ref = h.pipe.chat(q, max_new_tokens=cap)
    ids = len(h.pipe._prepare_request({"question": q})[0])

    class Tap(gen_lib.Drafter):
        def __init__(self):
            self.longest: list[int] = []

        def propose(self, context, k):
            ctx = [int(x) for x in context]
            if len(ctx) > len(self.longest):
                self.longest = ctx
            return []

    # Record the greedy reply's token stream with a pure-observer
    # drafter (the engine then behaves exactly like the plain path).
    tap = Tap()
    sched = ContinuousScheduler(
        h.pipe, num_slots=2, page_size=16, chunk=4, max_ctx=512,
        prefill_chunk=8, ragged=True, speculate=1, drafter=tap,
        autostart=False, prefix_cache=False,
    )
    hd = sched.submit({"question": q}, cap)
    sched.start()
    if hd.result(timeout=600)[0] != ref:
        fail("[spec_drift] tap run diverged from the solo pipeline")
    sched.close()
    stream = tap.longest[ids:]

    class DegradableOracle(gen_lib.Drafter):
        """Perfect drafts until degrade(); garbage after."""

        def __init__(self, prompt_len: int, stream: list[int]):
            self.prompt_len = prompt_len
            self.stream = stream
            self.degraded = False

        def degrade(self):
            self.degraded = True

        def propose(self, context, k):
            if self.degraded:
                return [7] * k  # (almost) always rejected on greedy
            done = len(context) - self.prompt_len
            return self.stream[done: done + k]

    oracle = DegradableOracle(ids, stream)
    monitor = AnomalyMonitor(source="serve")
    sched = ContinuousScheduler(
        h.pipe, num_slots=2, page_size=16, chunk=4, max_ctx=512,
        prefill_chunk=8, ragged=True, speculate=3, drafter=oracle,
        anomaly=monitor, autostart=False, prefix_cache=False,
    )
    sched.start()
    try:
        # Healthy phase: enough spec dispatches to build the rolling
        # baseline (min_window) at the oracle's high accept rate.
        for _ in range(2):
            hd = sched.submit({"question": q}, cap)
            if hd.result(timeout=600)[0] != ref:
                fail("[spec_drift] healthy-phase reply diverged")
        if monitor.counts.get("spec_accept_collapse", 0):
            fail("[spec_drift] detector fired during the HEALTHY phase")
        # Mid-run degradation: the drafter starts proposing garbage.
        oracle.degrade()
        for _ in range(2):
            hd = sched.submit({"question": q}, cap)
            if hd.result(timeout=600)[0] != ref:
                fail("[spec_drift] degraded-phase reply diverged — "
                     "rejected drafts must not corrupt the stream")
        fired = monitor.counts.get("spec_accept_collapse", 0)
        if fired != 1:
            fail(f"[spec_drift] spec_accept_collapse fired {fired} "
                 "time(s) across the degraded phase, want exactly 1 "
                 "(one event per episode)")
        sched._check_pool_invariant()
        held = sum(
            1 for p in range(sched.allocator.num_pages)
            if sched.allocator.refcount(p) > 0
        )
        if held:
            fail(f"[spec_drift] {held} page(s) still held after the "
                 "degraded phase drained")
    finally:
        sched.close()
        monitor.close()
    print("  [spec_drift] contained: oracle degraded mid-run -> "
          "exactly 1 spec_accept_collapse event, replies "
          "byte-identical, 0 leaks")


def scenario_host_spill_upload(h: Harness) -> None:
    """Host spill-tier re-upload failure (site `host_spill_upload`,
    one injected raise) on an int8 pool with the host tier armed: a
    prompt is served cold, its cached prefix is force-spilled to host
    RAM, and the SAME prompt is re-sent — the injected upload failure
    must degrade the reload to a cold recompute (byte-identical 200
    reply, never a crash), with the pool invariant and zero leaks
    after the incident; a third send proves the tier recovered."""
    srv, base = h.boot(
        "host_spill_upload:times=1",
        kv_dtype="int8", host_cache_bytes=1 << 24, prefill_chunk=32,
    )
    try:
        prompt = "host tier chaos shared prefix " * 4
        status, cold, _ = h.post_chat(base, prompt, 6)
        if status != 200:
            fail(f"[host_spill_upload] cold request: {status} {cold}")
        cold_text = cold["choices"][0]["message"]["content"]
        sched = srv.scheduler
        wait_for(
            lambda: all(r is None for r in sched.slots)
            and sched.queue_len() == 0,
            what="[host_spill_upload] quiesce before the forced spill",
        )
        from oryx_tpu.analysis.sanitizers import race_exempt

        with race_exempt("forced cache spill: engine quiesced by the "
                         "wait above"):
            cache = sched.prefix_cache
            cache.evict(cache.evictable_pages())
            spilled = cache.spilled_pages
        if not spilled:
            fail("[host_spill_upload] forced eviction spilled nothing "
                 "(tier not armed?)")
        # Re-send: the reload attempt hits the injected failure and
        # must fall back to a cold recompute of the whole prefix.
        status, warm, _ = h.post_chat(base, prompt, 6)
        if status != 200:
            fail(f"[host_spill_upload] re-send under injected upload "
                 f"failure: {status} {warm}")
        warm_text = warm["choices"][0]["message"]["content"]
        if warm_text != cold_text:
            fail("[host_spill_upload] degraded (cold-recompute) reply "
                 f"diverged: {warm_text!r} != {cold_text!r}")
        # Third send: the fault schedule is exhausted and the cold
        # recompute re-donated the prefix — a normal cached hit.
        status, third, _ = h.post_chat(base, prompt, 6)
        if status != 200 or (
            third["choices"][0]["message"]["content"] != cold_text
        ):
            fail(f"[host_spill_upload] post-incident send: {status} "
                 f"{third}")
        h.assert_triad(
            srv, base, "host_spill_upload", ["host_spill_upload"]
        )
    finally:
        h.teardown(srv)


def scenario_checkpoint_save(h: Harness) -> None:
    """Two injected save failures: bounded backoff retries land the
    checkpoint on the third attempt, schedule pinned (no wall-clock
    sleeps), and the fault metric reconciles in the bound registry."""
    import tempfile

    import numpy as np

    from oryx_tpu.utils import faults
    from oryx_tpu.utils.checkpoint import CheckpointManager
    from oryx_tpu.utils.metrics import Registry
    from oryx_tpu.utils.retry import BackoffPolicy

    faults.configure("checkpoint_save:times=2")
    reg = Registry()  # raw-named family only; no prefix needed
    faults.bind_registry(reg)
    slept: list[float] = []
    with tempfile.TemporaryDirectory() as d:
        mgr = CheckpointManager(
            os.path.join(d, "ck"),
            save_retry=BackoffPolicy(retries=3, base_s=0.5,
                                     factor=2.0, jitter=0.0),
            sleep=slept.append,
        )
        try:
            state = {"x": np.arange(16, dtype=np.float32)}
            if mgr.save(1, state) is not True:
                fail("[checkpoint_save] save did not land")
            mgr.wait()
            if mgr.latest_step() != 1:
                fail("[checkpoint_save] latest_step != 1 after "
                     "retried save")
            restored = mgr.restore(None)
            if not np.array_equal(np.asarray(restored["x"]),
                                  state["x"]):
                fail("[checkpoint_save] restored state differs")
        finally:
            mgr.close()
    if slept != [0.5, 1.0]:
        fail(f"[checkpoint_save] backoff schedule {slept} != "
             "[0.5, 1.0] — retry policy drifted")
    m = re.search(
        r'^oryx_faults_injected_total\{site="checkpoint_save"\} '
        r"([0-9.e+-]+)$", reg.render(), re.M,
    )
    metric = float(m.group(1)) if m else 0.0
    if metric != 2 or faults.injected_count("checkpoint_save") != 2:
        fail(f"[checkpoint_save] injected-count mismatch: metric "
             f"{metric}, counter "
             f"{faults.injected_count('checkpoint_save')}, want 2")
    faults.reset()
    print("  [checkpoint_save] contained: 2 injected failures, "
          "pinned backoff [0.5, 1.0], checkpoint landed + restored, "
          "2 fault(s) accounted")


def main() -> None:
    import jax

    from oryx_tpu import config as cfg_lib
    from oryx_tpu.analysis import sanitizers
    from oryx_tpu.models import oryx
    from oryx_tpu.serve.pipeline import OryxInference

    # ORYX_LOCK_SANITIZER=1 (how check_tier1.sh runs this): every
    # scenario — crash, restart, hung dispatch, disconnect — executes
    # with instrumented locks and the guarded-field race detector
    # armed, and the suite fails on ANY recorded ordering violation,
    # race, or re-entrant scheduler._cond acquire. Chaos is exactly
    # when lock ordering bugs surface: restart/drain/fail_inflight are
    # the rarely-trodden paths.
    san_armed = sanitizers.maybe_arm_from_env()
    if san_armed:
        print("lock sanitizer ARMED for this chaos run "
              "(ordering violations raise at the faulty acquire)")

    t0 = time.monotonic()
    cfg = cfg_lib.oryx_tiny()
    params = oryx.init_params(cfg, jax.random.key(0))
    pipe = OryxInference(_Tokenizer(), params, cfg)
    h = Harness(pipe)
    print("chaos suite: 8 scenarios against a live tiny server")
    for scenario in (
        scenario_page_alloc_oom,
        scenario_engine_crash,
        scenario_journaled_crash,
        scenario_hung_dispatch,
        scenario_client_disconnect,
        scenario_spec_drift,
        scenario_host_spill_upload,
        scenario_checkpoint_save,
    ):
        scenario(h)
    if san_armed:
        stats = sanitizers.lock_stats()
        if stats.violations:
            fail("lock-order sanitizer recorded violations during the "
                 f"chaos run: {stats.violations}")
        races = sanitizers.race_violations()
        if races:
            fail(f"race detector recorded violations: {races}")
        reentrant = stats.reentrant.get("scheduler._cond", 0)
        if reentrant:
            fail(f"scheduler._cond was re-acquired re-entrantly "
                 f"{reentrant} time(s) — the supervisor restart path "
                 "must take and release it per request")
        if not stats.acquires.get("scheduler._cond"):
            fail("sanitizer armed but saw no scheduler._cond acquires "
                 "— instrumentation did not take effect")
        print(f"  lock sanitizer: 0 violations, 0 races, 0 re-entrant "
              f"_cond acquires across "
              f"{sum(stats.acquires.values())} instrumented acquires")
    print(f"chaos suite OK: every fault contained, every pool "
          f"invariant held ({time.monotonic() - t0:.0f}s)")


if __name__ == "__main__":
    main()
