"""CI well-formedness gate for the serving observability surface.

Runs one battery of endpoint checks against a serving TARGET — a bare
replica (api_server) or the prefix-affinity router (serve/router.py)
fronting several — detected from the target's own /metrics:

  * GET /healthz — 200 liveness;
  * GET /readyz — 200 with ready:true while the target can serve (the
    load-balancer probe that replaces spending a real completion);
  * GET /metrics — exact Prometheus content type
    (`text/plain; version=0.0.4`), every metric name carries the
    target's prefix (`oryx_serving_` on a replica, `oryx_router_` on
    the router; the cross-source `oryx_anomaly_` family is the one
    deliberate exception), the build_info gauge is present with
    revision + engine labels. Replicas must expose the HBM gauges;
    the router instead must expose `/metrics/aggregate` where every
    replica sample line carries an injected `replica=` label
    (including the HBM gauges, per backend);
  * GET /debug/requests — valid JSON, the request we sent is recorded
    (the router merges its replicas' flight recorders); ?limit=
    bounds the response, ?state=done returns only finished requests
    and every one carries a COMPLETE per-request cost ledger
    (utils/metrics.REQUEST_COST_KEYS), a bogus state is a 400;
  * GET /debug/trace?id= — valid Chrome trace JSON covering
    queue_wait/prefill/decode_chunk (the router locates the replica
    that served the id);
  * a latency histogram read back through the SHARED quantile helpers
    (utils/metrics.parse_prom_histogram + histogram_quantile — the
    same math scripts/loadgen.py reports with): finite, positive,
    ordered p50 <= p99. Replica: `oryx_serving_ttft_seconds`; router:
    `oryx_router_upstream_ttfb_seconds`;
  * prefix cache under a shared-prefix burst — hit/miss counters,
    entries/pages gauges, eviction counter and the prefill chunk-size
    histogram present and well-formed, and hit_tokens actually moved
    (summed across replicas through the aggregation endpoint when the
    target is the router).

Modes:

    # self-boot a tiny CPU replica (the default; wired into
    # scripts/check_tier1.sh)
    python scripts/check_serving_endpoints.py

    # the same gate against any live target — a bare replica or a
    # router front-end
    python scripts/check_serving_endpoints.py --base-url http://host:port

    # 2-replica router smoke: boots two tiny replicas + a router,
    # runs the full gate against the ROUTER, then asserts prefix
    # AFFINITY — the shared-prefix burst must land on one replica
    # (its oryx_serving_prefix_cache_hit_tokens_total dominates)
    python scripts/check_serving_endpoints.py --router-smoke

Exit 0 = all good; nonzero prints what broke.
"""

from __future__ import annotations

import argparse
import json
import os
import re
import sys
import threading
import urllib.error
import urllib.request

sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)

os.environ.setdefault("JAX_PLATFORMS", "cpu")


class _Tokenizer:
    def encode(self, text, add_special_tokens=False):
        return [min(ord(c), 500) for c in text]

    def decode(self, ids, skip_special_tokens=True):
        return "".join(chr(i) for i in ids if 0 < i < 500)


def fail(msg: str) -> None:
    print(f"FAIL: {msg}", file=sys.stderr)
    raise SystemExit(1)


def _get(base: str, path: str, timeout: float = 30.0):
    return urllib.request.urlopen(base + path, timeout=timeout)


def boot_tiny_server(replica_id: str | None = None):
    """One tiny-geometry continuous-engine CPU replica; returns the
    (unstarted threads aside) live server."""
    import jax

    from oryx_tpu import config as cfg_lib
    from oryx_tpu.models import oryx
    from oryx_tpu.serve import api_server
    from oryx_tpu.serve.pipeline import OryxInference

    cfg = cfg_lib.oryx_tiny()
    params = oryx.init_params(cfg, jax.random.key(0))
    pipe = OryxInference(_Tokenizer(), params, cfg)
    srv = api_server.build_server(
        pipe, port=0, engine="continuous", num_slots=2, page_size=16,
        decode_chunk=4, max_ctx=512, prefill_chunk=32,
        replica_id=replica_id,
    )
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    return srv


def _base_of(srv) -> str:
    return f"http://127.0.0.1:{srv.server_address[1]}"


SYSMSG = ("You are a careful assistant. Study the context and "
          "answer briefly. " * 2)


def _completion(base: str, messages, max_tokens: int = 4,
                request_id: str | None = None) -> str:
    headers = {"Content-Type": "application/json"}
    if request_id:
        headers["X-Request-Id"] = request_id
    req = urllib.request.Request(
        base + "/v1/chat/completions",
        data=json.dumps({
            "messages": messages, "max_tokens": max_tokens,
        }).encode(),
        headers=headers,
    )
    with urllib.request.urlopen(req, timeout=300) as r:
        rid = r.headers.get("X-Request-Id")
        json.load(r)
    return rid


def _labeled_total(text: str, family: str) -> float:
    """Sum of a family's samples across any labels (the aggregated
    multi-replica view)."""
    total = 0.0
    for m in re.finditer(
        rf"^{re.escape(family)}(?:\{{[^}}]*\}})? ([0-9.e+-]+)$",
        text, re.M,
    ):
        total += float(m.group(1))
    return total


def run_checks(base: str) -> str:
    """The full endpoint battery against `base`; returns the detected
    target kind ("replica" | "router")."""
    with _get(base, "/metrics") as r:
        ctype = r.headers.get("Content-Type")
        metrics_text = r.read().decode()
    if ctype != "text/plain; version=0.0.4":
        fail(f"/metrics content type {ctype!r}, want the Prometheus "
             "text exposition type")
    kind = (
        "router" if "oryx_router_build_info" in metrics_text
        else "replica"
    )
    prefixes = (
        ("oryx_router_", "oryx_anomaly_") if kind == "router"
        # oryx_pool_/oryx_page_ are the page-pool observatory's raw
        # families, oryx_device_time_/oryx_profile_ the device-time
        # attributor's, oryx_audit_/oryx_numerics_ the output-quality
        # observatory's — raw-named like oryx_anomaly_ because their
        # semantics are engine-independent.
        # oryx_cache_ is the prefix cache's host spill tier
        # (raw-named: tier semantics are engine-independent too).
        else ("oryx_serving_", "oryx_anomaly_", "oryx_pool_",
              "oryx_page_", "oryx_device_time_", "oryx_profile_",
              "oryx_audit_", "oryx_numerics_", "oryx_cache_")
    )
    info_family = (
        "oryx_router_build_info" if kind == "router"
        else "oryx_serving_build_info"
    )

    with _get(base, "/healthz") as r:
        if json.load(r) != {"status": "ok"}:
            fail("/healthz body is not {status: ok}")
    with _get(base, "/readyz") as r:
        ready = json.load(r)
        if r.status != 200 or ready.get("ready") is not True:
            fail(f"/readyz on a live target: want 200/true, "
                 f"got {r.status} {ready}")

    # Client-supplied request ids are honored END-TO-END (through the
    # router too): the response must echo the id, and it keys the
    # trace lookups below.
    rid = _completion(
        base, [{"role": "user", "content": "hello there"}],
        request_id="endpoint-check-1",
    )
    if rid != "endpoint-check-1":
        fail("client-supplied X-Request-Id was not honored "
             f"(sent endpoint-check-1, got {rid!r})")

    # Prefix/build_info checks run against the BOOT-time scrape (those
    # families exist before any traffic); the latency-histogram check
    # below re-scrapes after the burst for its samples.
    bad = [
        line for line in metrics_text.splitlines()
        if line and not line.startswith("#")
        and not line.startswith(prefixes)
    ]
    if bad:
        fail(f"unprefixed metric names for a {kind}: {bad[:5]}")
    if not re.search(
        rf'^{info_family}\{{[^}}]*engine="[^"]+"[^}}]*\}} 1$',
        metrics_text, re.M,
    ) or 'revision="' not in metrics_text:
        fail(f"{info_family} gauge with engine+revision labels "
             "missing from /metrics")
    if kind == "replica":
        if "oryx_serving_hbm_live_bytes" not in metrics_text:
            fail("device-memory gauge oryx_serving_hbm_live_bytes "
                 "missing from /metrics")
        # Output-quality & numerics families: pre-registered so the
        # ladders render (at zero) on an UNARMED default boot — the
        # dashboard row must exist before the first audit/probe.
        for verdict in ("pass", "drift", "fail"):
            if not re.search(
                rf'^oryx_audit_total\{{verdict="{verdict}"\}} ',
                metrics_text, re.M,
            ):
                fail(f"oryx_audit_total{{verdict=\"{verdict}\"}} not "
                     "pre-registered on an unarmed boot")
        for fam in (
            "oryx_audit_sampled_total",
            "oryx_audit_dropped_total",
            "oryx_audit_pending",
            "oryx_audit_replayed_tokens_total",
            "oryx_numerics_logits_finite_frac",
            "oryx_numerics_logits_absmax",
            "oryx_numerics_logits_rms",
            "oryx_numerics_logits_entropy",
            "oryx_numerics_logits_top1_margin",
            "oryx_numerics_samples_total",
        ):
            if not re.search(rf"^{fam} ", metrics_text, re.M):
                fail(f"{fam} not pre-registered on an unarmed boot")
        for fam in ("oryx_audit_logit_max_abs_diff", "oryx_audit_kl"):
            if not re.search(
                rf'^{fam}_bucket\{{le="\+Inf"\}} ', metrics_text, re.M
            ):
                fail(f"{fam} histogram ladder not pre-registered")
        # Host spill-tier families (prefix-cache host-RAM tier) and
        # the pool's wire-format label: pre-registered at zero so the
        # capacity dashboard renders before the first spill, and the
        # kv_dtype provenance is scrapeable from boot.
        for fam in (
            "oryx_cache_spilled_pages",
            "oryx_cache_host_bytes",
            "oryx_cache_reload_hit_total",
            "oryx_cache_reload_upload_total",
        ):
            if not re.search(rf"^{fam} ", metrics_text, re.M):
                fail(f"{fam} not pre-registered on boot")
        if not re.search(
            r'^oryx_pool_kv_dtype\{kv_dtype="(bf16|int8)"\} 1$',
            metrics_text, re.M,
        ):
            fail("oryx_pool_kv_dtype{kv_dtype=} build-info gauge "
                 "missing from /metrics")
    else:
        # The router has no HBM of its own; the fleet's shows through
        # the aggregation endpoint, every sample line replica-labeled.
        with _get(base, "/metrics/aggregate") as r:
            agg = r.read().decode()
        if not re.search(
            r'^oryx_serving_hbm_live_bytes\{[^}]*replica="[^"]+"',
            agg, re.M,
        ):
            fail("/metrics/aggregate missing replica-labeled "
                 "oryx_serving_hbm_live_bytes")
        unlabeled = [
            line for line in agg.splitlines()
            if line and not line.startswith("#")
            and line.startswith("oryx_serving_")
            and 'replica="' not in line
        ]
        if unlabeled:
            fail("aggregated replica samples missing the replica= "
                 f"label: {unlabeled[:5]}")

    with _get(base, "/debug/requests") as r:
        recorder = json.load(r)
    ids = [e.get("id") for e in recorder.get("requests", [])]
    if rid not in ids:
        fail(f"/debug/requests does not list request {rid} (got {ids})")

    with _get(base, f"/debug/trace?id={rid}") as r:
        tracejs = json.load(r)
    names = {e.get("name") for e in tracejs.get("traceEvents", [])}
    wanted = ["queue_wait", "prefill", "decode_chunk"]
    if kind == "router":
        # The acceptance bar for fleet tracing: ONE merged trace with
        # router spans AND the owning replica's engine spans, loadable
        # as Chrome trace JSON.
        wanted += ["route_decide", "upstream_ttfb"]
        if tracejs.get("merged") is not True:
            fail("/debug/trace through the router is not a merged "
                 f"trace (merged={tracejs.get('merged')!r})")
    for want in wanted:
        if want not in names:
            fail(f"/debug/trace missing span {want!r} (got "
                 f"{sorted(names)})")
    for ev in tracejs.get("traceEvents", []):
        if ev.get("ph") == "X" and not all(
            k in ev for k in ("name", "ts", "dur", "pid", "tid")
        ):
            fail(f"/debug/trace event not Chrome-trace shaped: {ev}")

    # Shared-prefix burst: several requests with one long system
    # prompt must light up the prefix-cache metric family (and, on a
    # router target, the affinity machinery keeps them on one
    # replica — asserted separately by --router-smoke).
    for i in range(3):
        _completion(base, [
            {"role": "system", "content": SYSMSG},
            {"role": "user", "content": f"question {i}?"},
        ], max_tokens=3)
    with _get(base, "/metrics") as r:
        metrics_text = r.read().decode()
    if kind == "router":
        with _get(base, "/metrics/aggregate") as r:
            cache_text = r.read().decode()
    else:
        cache_text = metrics_text
    for fam in (
        "oryx_serving_prefix_cache_hit_tokens_total",
        "oryx_serving_prefix_cache_miss_tokens_total",
        "oryx_serving_prefix_cache_evicted_pages_total",
        "oryx_serving_prefix_cache_entries",
        "oryx_serving_prefix_cache_pages",
        "oryx_serving_prefill_tokens_total",
    ):
        if not re.search(
            rf"^{fam}(?:\{{[^}}]*\}})? ([0-9.e+-]+)$", cache_text, re.M
        ):
            fail(f"prefix-cache metric {fam} missing or malformed "
                 "after the shared-prefix burst")
    if not re.search(
        r'^oryx_serving_prefill_chunk_tokens_bucket\{[^}]*le="\+Inf"[^}]*\} '
        r"[1-9]", cache_text, re.M,
    ):
        fail("prefill chunk-size histogram did not record any dispatch")
    hit = _labeled_total(
        cache_text, "oryx_serving_prefix_cache_hit_tokens_total"
    )
    if hit <= 0:
        fail("shared-prefix burst produced zero "
             "prefix_cache_hit_tokens_total — the cache never hit")

    # Latency quantiles through the SHARED bucket-interpolation
    # helpers (the loadgen report uses the same math): the histogram
    # must parse and produce finite, ordered quantiles. A replica's
    # own TTFT ladder, or the router's upstream-TTFB ladder.
    from oryx_tpu.utils.metrics import (
        REQUEST_COST_KEYS,
        histogram_quantile,
        parse_prom_histogram,
    )

    lat_family = (
        "oryx_router_upstream_ttfb_seconds" if kind == "router"
        else "oryx_serving_ttft_seconds"
    )
    hist = parse_prom_histogram(metrics_text, lat_family)
    if hist is None:
        fail(f"{lat_family} histogram missing")
    bounds, counts, total, _ = hist
    if total < 4:
        fail(f"{lat_family} recorded {total} < 4 requests")
    p50 = histogram_quantile(0.5, bounds, counts, total)
    p99 = histogram_quantile(0.99, bounds, counts, total)
    if not (0 < p50 <= p99):
        fail(f"{lat_family} quantiles malformed: p50={p50} p99={p99}")
    if kind == "replica" and not re.search(
        r"^oryx_serving_request_page_seconds_count [1-9]",
        metrics_text, re.M,
    ):
        fail("oryx_serving_request_page_seconds histogram did not "
             "record any finished request")

    # /debug/requests filters: ?limit= bounds the response,
    # ?state=done shows only finished requests — each carrying a
    # complete cost ledger — and a bogus state is a 400 (propagated
    # through the router's merge).
    with _get(base, "/debug/requests?limit=1") as r:
        lim = json.load(r)
    if len(lim["requests"]) != 1 or lim["returned"] != 1:
        fail(f"/debug/requests?limit=1 returned "
             f"{len(lim['requests'])} entries")
    if lim["total"] < 4:
        fail(f"/debug/requests?limit=1 total={lim['total']}, "
             "want >= 4 (the burst flowed through the recorder)")
    with _get(base, "/debug/requests?state=done") as r:
        done = json.load(r)
    if not done["requests"]:
        fail("/debug/requests?state=done is empty after the burst")
    for rec in done["requests"]:
        if not rec["done"]:
            fail(f"?state=done returned in-flight request {rec['id']}")
        cost = (rec.get("meta") or {}).get("cost")
        missing = [
            k for k in REQUEST_COST_KEYS
            if not isinstance(cost, dict) or k not in cost
        ]
        if missing:
            fail(f"finished request {rec['id']} cost ledger "
                 f"missing {missing}")
    try:
        with _get(base, "/debug/requests?state=bogus") as r:
            fail("/debug/requests?state=bogus did not 400")
    except urllib.error.HTTPError as e:
        if e.code != 400:
            fail(f"/debug/requests?state=bogus -> {e.code}, want 400")
        e.close()

    # Wide-event export: one JSONL line per terminal request, every
    # field drawn from the declared schema registry.
    from oryx_tpu.utils.metrics import REQUEST_EVENT_KEYS

    with _get(base, "/debug/requests?format=jsonl") as r:
        if r.headers.get("Content-Type") != "application/x-ndjson":
            fail("?format=jsonl content type is "
                 f"{r.headers.get('Content-Type')!r}")
        lines = [ln for ln in r.read().decode().splitlines() if ln]
    if len(lines) < 4:
        fail(f"?format=jsonl returned {len(lines)} events, want >= 4 "
             "(the burst reached terminal states)")
    from oryx_tpu.utils.metrics import OOM_EVENT_KEYS

    seen_ids = set()
    for ln in lines:
        try:
            ev = json.loads(ln)
        except ValueError:
            fail(f"?format=jsonl line is not JSON: {ln[:80]!r}")
        # The sink carries two declared schemas, dispatched on `kind`:
        # request events (no kind) and oom_pressure events.
        schema = (
            OOM_EVENT_KEYS if ev.get("kind") == "oom_pressure"
            else REQUEST_EVENT_KEYS
        )
        extra = set(ev) - set(schema)
        if extra:
            fail(f"wide event carries undeclared fields {sorted(extra)}")
        if ev.get("kind") == "oom_pressure":
            continue
        if not ev.get("request_id") or "status" not in ev:
            fail(f"wide event missing identity/outcome: {ev}")
        seen_ids.add(ev["request_id"])
    if rid not in seen_ids:
        fail(f"wide-event log does not contain request {rid}")

    # Step timeline: per-step records, and (replica) dispatch-kind
    # counts that reconcile EXACTLY with the dispatches_total counters
    # — both cumulative since boot, scraped with the engine quiesced.
    with _get(base, "/debug/timeline?n=16") as r:
        tl = json.load(r)
    if kind == "replica":
        if not tl.get("records"):
            fail("/debug/timeline returned no records after the burst")
        counts = tl.get("counts_by_kind") or {}
        if tl.get("total_steps") != sum(counts.values()):
            fail(f"timeline total_steps {tl.get('total_steps')} != "
                 f"sum of counts_by_kind {counts}")
        with _get(base, "/metrics") as r:
            mtext = r.read().decode()
        for k, v in counts.items():
            m = re.search(
                rf'^oryx_serving_dispatches_total\{{kind="{k}"\}} '
                rf"([0-9.e+-]+)$", mtext, re.M,
            )
            if not m or float(m.group(1)) != v:
                fail(f"timeline kind {k!r}={v} does not reconcile "
                     "with oryx_serving_dispatches_total "
                     f"({m.group(1) if m else 'absent'})")
    else:
        reps = tl.get("replicas") or {}
        if not reps:
            fail("router /debug/timeline returned no replicas")
        served = [
            r for r in reps.values()
            if isinstance(r.get("records"), list) and r["records"]
        ]
        if not served:
            fail(f"no replica timeline carries records: {tl}")

    # Page-pool observatory: on the quiesced target the ownership map
    # must reconcile exactly (free + slot + cache + shared == pool,
    # the allocator-invariant partition) and the summary must equal
    # the oryx_pool_* gauges from a scrape of the same quiesced state.
    with _get(base, "/debug/pages") as r:
        pm = json.load(r)
    if kind == "replica":
        s = pm.get("summary") or {}
        if not s.get("reconciled") or (
            s["free"] + s["slot"] + s["cache"] + s["shared"]
            != pm["num_pages"]
        ):
            fail(f"/debug/pages does not reconcile with the pool "
                 f"partition: {s}")
        if len(pm.get("pages") or []) != pm["num_pages"]:
            fail("/debug/pages is not one record per page "
                 f"({len(pm.get('pages') or [])} of {pm['num_pages']})")
        for rec in pm["pages"]:
            if rec["state"] not in ("free", "slot", "cache", "shared"):
                fail(f"unknown page state in the ownership map: {rec}")
            if (rec["state"] == "free") != (rec["refcount"] == 0):
                fail(f"page state/refcount mismatch: {rec}")
        with _get(base, "/metrics") as r:
            ptext = r.read().decode()
        for gname, key in (
            ("oryx_pool_free_pages", "free"),
            ("oryx_pool_slot_pages", "slot"),
            ("oryx_pool_cache_pages", "cache"),
            ("oryx_pool_shared_pages", "shared"),
            ("oryx_pool_size_pages", "num_pages"),
        ):
            m = re.search(rf"^{gname} ([0-9.e+-]+)$", ptext, re.M)
            want = s[key] if key != "num_pages" else pm["num_pages"]
            if not m or float(m.group(1)) != want:
                fail(f"{gname} ({m.group(1) if m else 'absent'}) does "
                     f"not equal the /debug/pages summary's {want}")
        if not re.search(
            r"^oryx_page_lifetime_seconds_count [1-9]", ptext, re.M
        ):
            fail("oryx_page_lifetime_seconds recorded no freed pages "
                 "after the burst (the free-time observer never fired)")
    else:
        reps = pm.get("replicas") or {}
        if not reps:
            fail("router /debug/pages returned no replicas")
        for rid, body in reps.items():
            if not (body.get("summary") or {}).get("reconciled"):
                fail(f"replica {rid} page map does not reconcile: "
                     f"{body}")
        # The forensic merge answers fleet-wide too (rings empty on a
        # healthy fleet).
        with _get(base, "/debug/oom") as r:
            om = json.load(r)
        if set(om.get("replicas") or {}) != set(reps):
            fail(f"router /debug/oom replicas {sorted(om)} do not "
                 f"match /debug/pages {sorted(reps)}")
    # Output-quality observatory surface: /debug/audit answers on an
    # UNARMED target (empty ring, zero verdicts that reconcile with the
    # zero counters); the router merges it per replica.
    with _get(base, "/debug/audit") as r:
        au = json.load(r)
    if kind == "replica":
        verdicts = au.get("verdicts") or {}
        if au.get("total") != sum(verdicts.values()):
            fail(f"/debug/audit total {au.get('total')} != sum of "
                 f"verdicts {verdicts}")
        with _get(base, "/metrics") as r:
            atext = r.read().decode()
        for verdict, want in verdicts.items():
            m = re.search(
                rf'^oryx_audit_total\{{verdict="{verdict}"\}} '
                rf"([0-9.e+-]+)$", atext, re.M,
            )
            if not m or float(m.group(1)) != want:
                fail(f"/debug/audit verdict {verdict!r}={want} does "
                     "not reconcile with oryx_audit_total "
                     f"({m.group(1) if m else 'absent'})")
    else:
        if not au.get("replicas"):
            fail("router /debug/audit returned no replicas")
    return kind


def _shutdown_replica(srv) -> None:
    if srv.scheduler is not None:
        srv.scheduler.close()
    srv.shutdown()


def run_oom_forensic_check() -> None:
    """Boot a fresh tiny replica with ONE injected page_alloc_oom
    armed (every=2,times=1: the second allocator call fails — by then
    the first streaming request is resident, so the capture names it)
    and assert the forensic contract: both requests still answer 200,
    exactly one /debug/oom record exists, its top-K is non-empty, the
    oom_pressure wide event rides the request log, and the post-
    incident page map reconciles."""
    import threading as threading_lib

    from oryx_tpu.serve import api_server
    from oryx_tpu.serve.pipeline import OryxInference
    from oryx_tpu import config as cfg_lib
    from oryx_tpu.models import oryx as oryx_lib
    import jax

    cfg = cfg_lib.oryx_tiny()
    params = oryx_lib.init_params(cfg, jax.random.key(0))
    pipe = OryxInference(_Tokenizer(), params, cfg)
    srv = api_server.build_server(
        pipe, port=0, engine="continuous", num_slots=2, page_size=16,
        decode_chunk=4, max_ctx=512, prefill_chunk=32,
        faults_spec="page_alloc_oom:every=2,times=1",
    )
    threading_lib.Thread(target=srv.serve_forever, daemon=True).start()
    base = _base_of(srv)
    try:
        codes: list[int] = []

        def one(i: int, tokens: int) -> None:
            try:
                _completion(
                    base,
                    [{"role": "user",
                      "content": f"oom burst request {i} with a "
                      "longer prompt to prefill and decode"}],
                    max_tokens=tokens,
                )
                codes.append(200)
            except urllib.error.HTTPError as e:
                codes.append(e.code)
                e.close()

        threads = [
            threading.Thread(target=one, args=(i, t))
            for i, t in ((0, 64), (1, 8))
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        if codes != [200, 200]:
            fail(f"injected-OOM burst did not answer 200/200: {codes}")
        with _get(base, "/debug/oom?n=64") as r:
            oom = json.load(r)
        raised = [
            rec for rec in oom.get("records") or []
            if rec.get("trigger") == "oom"
        ]
        if len(raised) != 1:
            fail(f"injected page_alloc_oom produced {len(raised)} "
                 f"trigger=oom /debug/oom record(s), want exactly 1 "
                 f"(ring: {oom.get('total')})")
        rec = raised[0]
        if not rec.get("top_requests"):
            fail(f"forensic record has an empty top-K: {rec}")
        if not (rec.get("pool") or {}).get("reconciled"):
            fail(f"forensic record captured an unreconciled pool: "
                 f"{rec.get('pool')}")
        with _get(base, "/debug/requests?format=jsonl") as r:
            events = [json.loads(ln) for ln in
                      r.read().decode().splitlines() if ln]
        ooms = [e for e in events if e.get("kind") == "oom_pressure"
                and e.get("trigger") == "oom"]
        if len(ooms) != 1 \
                or ooms[0].get("forensic_index") != rec.get("index"):
            fail(f"expected one trigger=oom wide event joined to "
                 f"forensic #{rec.get('index')}, got {ooms}")
        with _get(base, "/debug/pages?format=summary") as r:
            s = json.load(r)["summary"]
        if not s.get("reconciled") or s.get("slot") != 0:
            fail(f"post-incident /debug/pages does not reconcile: {s}")
        print("oom forensic check OK: 200/200 under one injected "
              "OOM, 1 forensic record (non-empty top-K), wide event "
              "joined, pool reconciled")
    finally:
        from oryx_tpu.utils import faults

        faults.reset()
        _shutdown_replica(srv)


def run_audit_check() -> None:
    """The output-quality observatory gate (ISSUE 14): the SAME
    sequential greedy burst against an ARMED (--audit-sample-every 1)
    and an UNARMED tiny replica, gating:

      * every sampled request audits verdict=pass on the fp path —
        zero fail, zero drift;
      * the /debug/audit ring/verdict counts reconcile EXACTLY with
        oryx_audit_total{verdict=};
      * every kind="audit" wide event validates against the declared
        schema (utils.metrics.AUDIT_EVENT_KEYS) and joins the ring by
        audit_index;
      * the auditor observes, never perturbs: live-traffic reply bytes
        AND oryx_serving_dispatches_total{kind=} are identical between
        the armed and unarmed runs (sequential requests — the dispatch
        schedule is deterministic).
    """
    import jax

    from oryx_tpu import config as cfg_lib
    from oryx_tpu.models import oryx as oryx_lib
    from oryx_tpu.serve import api_server
    from oryx_tpu.serve.pipeline import OryxInference
    from oryx_tpu.utils.metrics import AUDIT_EVENT_KEYS

    cfg = cfg_lib.oryx_tiny()
    params = oryx_lib.init_params(cfg, jax.random.key(0))

    bursts = [
        ("hello there, audit me", 6),
        ("a different question now", 4),
        ("hello there, audit me", 6),  # repeat: splice path audited too
        ("one more to finish the burst", 5),
    ]

    def boot(audit_every: int):
        pipe = OryxInference(_Tokenizer(), params, cfg)
        srv = api_server.build_server(
            pipe, port=0, engine="continuous", num_slots=2,
            page_size=16, decode_chunk=4, max_ctx=512, prefill_chunk=32,
            audit_sample_every=audit_every,
        )
        threading.Thread(target=srv.serve_forever, daemon=True).start()
        return srv

    def drive(srv) -> tuple[list[str], dict[str, float]]:
        base = _base_of(srv)
        replies = []
        for i, (q, toks) in enumerate(bursts):
            req = urllib.request.Request(
                base + "/v1/chat/completions",
                data=json.dumps({
                    "messages": [{"role": "user", "content": q}],
                    "max_tokens": toks,
                }).encode(),
                headers={"Content-Type": "application/json"},
            )
            with urllib.request.urlopen(req, timeout=300) as r:
                body = json.load(r)
            replies.append(body["choices"][0]["message"]["content"])
        with _get(base, "/metrics") as r:
            text = r.read().decode()
        dispatches = {
            m.group(1): float(m.group(2))
            for m in re.finditer(
                r'^oryx_serving_dispatches_total\{kind="([^"]+)"\} '
                r"([0-9.e+-]+)$", text, re.M,
            )
        }
        return replies, dispatches

    armed = boot(1)
    plain = boot(0)
    try:
        armed_replies, armed_disp = drive(armed)
        base = _base_of(armed)
        # Drain the audit backlog: replays run at engine idle points,
        # so after the last reply they complete within a poll window.
        import time as time_lib

        deadline = time_lib.monotonic() + 120
        while time_lib.monotonic() < deadline:
            with _get(base, "/debug/audit?n=64") as r:
                au = json.load(r)
            if au.get("pending") == 0 and au.get("total", 0) >= len(
                bursts
            ):
                break
            time_lib.sleep(0.1)
        if au.get("pending") != 0:
            fail(f"audit backlog never drained: {au.get('pending')} "
                 "pending after the burst")
        verdicts = au.get("verdicts") or {}
        if verdicts.get("fail") or verdicts.get("drift"):
            fail(f"non-pass audit verdict(s) on the fp path: "
                 f"{verdicts} (records: {au.get('records')})")
        if au.get("total") != len(bursts) \
                or verdicts.get("pass") != len(bursts):
            fail(f"expected {len(bursts)} pass audits, got total="
                 f"{au.get('total')} verdicts={verdicts}")
        # Ring <-> counter reconciliation on the quiesced replica.
        with _get(base, "/metrics") as r:
            atext = r.read().decode()
        for verdict, want in verdicts.items():
            m = re.search(
                rf'^oryx_audit_total\{{verdict="{verdict}"\}} '
                rf"([0-9.e+-]+)$", atext, re.M,
            )
            if not m or float(m.group(1)) != want:
                fail(f"oryx_audit_total verdict {verdict!r} "
                     f"({m.group(1) if m else 'absent'}) does not "
                     f"reconcile with /debug/audit's {want}")
        if not re.search(
            r"^oryx_audit_logit_max_abs_diff_count [1-9]", atext, re.M
        ):
            fail("oryx_audit_logit_max_abs_diff recorded no samples "
                 "over an armed burst")
        # Every audit's wide event validates and joins the ring.
        with _get(base, "/debug/requests?format=jsonl") as r:
            events = [json.loads(ln) for ln in
                      r.read().decode().splitlines() if ln]
        audits = [e for e in events if e.get("kind") == "audit"]
        if len(audits) != len(bursts):
            fail(f"{len(audits)} kind=audit wide event(s), want "
                 f"{len(bursts)}")
        indices = {rec["index"] for rec in au.get("records") or []}
        for ev in audits:
            extra = set(ev) - set(AUDIT_EVENT_KEYS)
            if extra:
                fail(f"audit wide event carries undeclared fields "
                     f"{sorted(extra)}")
            if ev.get("verdict") != "pass":
                fail(f"audit wide event is not a pass: {ev}")
            if ev.get("audit_index") not in indices:
                fail(f"audit wide event index {ev.get('audit_index')} "
                     "does not join the /debug/audit ring")
        # Never-perturb A/B: byte parity + identical dispatch schedule
        # against the unarmed twin.
        plain_replies, plain_disp = drive(plain)
        if armed_replies != plain_replies:
            fail("armed vs unarmed replies diverged — the auditor "
                 f"perturbed live traffic: {armed_replies} vs "
                 f"{plain_replies}")
        if armed_disp != plain_disp:
            fail("armed vs unarmed dispatch counters diverged — the "
                 f"auditor perturbed the engine: {armed_disp} vs "
                 f"{plain_disp}")
        print(f"audit smoke OK: {len(bursts)}/{len(bursts)} audits "
              "pass, ring==counters, wide events schema-valid and "
              "joined, armed==unarmed byte parity and dispatch "
              f"schedule ({armed_disp})")
    finally:
        _shutdown_replica(armed)
        _shutdown_replica(plain)


def run_journal_check() -> None:
    """The engine flight-recorder gate (ISSUE 18): the SAME sequential
    greedy burst against a --journal ARMED and an unarmed tiny
    replica, gating:

      * /debug/journal is well-formed and reconciled: armed=true, the
        sealed header carries the scheduler geometry, counts_by_kind
        sums to total, one submit and one finish entry per request
        (the unarmed twin answers the same shape with armed=false);
      * the journal FILE replays offline byte-exactly
        (scripts/replay_journal.py as a library): first_divergence is
        None over the replayed decision stream, every finish entry's
        reply/token fingerprints match, and the deterministic cost
        ledgers are equal — the capture -> replay contract of
        docs/OBSERVABILITY.md "Incident replay";
      * the journal observes, never perturbs: live-traffic reply
        bytes AND oryx_serving_dispatches_total{kind=} are identical
        between the armed and unarmed runs.
    """
    import tempfile

    import jax

    from oryx_tpu import config as cfg_lib
    from oryx_tpu.models import oryx as oryx_lib
    from oryx_tpu.serve import api_server
    from oryx_tpu.serve import journal as journal_lib
    from oryx_tpu.serve.pipeline import OryxInference

    import replay_journal as rj

    cfg = cfg_lib.oryx_tiny()
    params = oryx_lib.init_params(cfg, jax.random.key(0))
    pipe = OryxInference(_Tokenizer(), params, cfg)
    jpath = os.path.join(tempfile.mkdtemp(), "journal.jsonl")

    bursts = [
        ("hello there, journal me", 6),
        ("a different question now", 4),
        ("hello there, journal me", 6),  # repeat: splice path journaled
        ("one more to finish the burst", 5),
    ]

    def boot(path):
        srv = api_server.build_server(
            pipe, port=0, engine="continuous", num_slots=2,
            page_size=16, decode_chunk=4, max_ctx=512, prefill_chunk=32,
            journal_path=path,
        )
        threading.Thread(target=srv.serve_forever, daemon=True).start()
        return srv

    def drive(srv) -> tuple[list[str], dict[str, float]]:
        base = _base_of(srv)
        replies = []
        for q, toks in bursts:
            req = urllib.request.Request(
                base + "/v1/chat/completions",
                data=json.dumps({
                    "messages": [{"role": "user", "content": q}],
                    "max_tokens": toks,
                }).encode(),
                headers={"Content-Type": "application/json"},
            )
            with urllib.request.urlopen(req, timeout=300) as r:
                body = json.load(r)
            replies.append(body["choices"][0]["message"]["content"])
        with _get(base, "/metrics") as r:
            text = r.read().decode()
        dispatches = {
            m.group(1): float(m.group(2))
            for m in re.finditer(
                r'^oryx_serving_dispatches_total\{kind="([^"]+)"\} '
                r"([0-9.e+-]+)$", text, re.M,
            )
        }
        return replies, dispatches

    armed = boot(jpath)
    plain = boot(None)
    try:
        armed_replies, armed_disp = drive(armed)
        with _get(_base_of(armed), "/debug/journal?n=512") as r:
            ring = json.load(r)
        if not ring.get("armed") or ring.get("path") != jpath:
            fail(f"/debug/journal on the armed replica is not armed "
                 f"at {jpath}: {ring.get('armed')}/{ring.get('path')}")
        counts = ring.get("counts_by_kind") or {}
        if sum(counts.values()) != ring.get("total"):
            fail(f"/debug/journal counts_by_kind {counts} does not "
                 f"sum to total {ring.get('total')}")
        if counts.get("submit") != len(bursts) \
                or counts.get("finish") != len(bursts):
            fail(f"expected {len(bursts)} submit and finish entries, "
                 f"got {counts}")
        hdr_cfg = (ring.get("header") or {}).get("config") or {}
        for key in ("num_slots", "page_size", "seed"):
            if key not in hdr_cfg:
                fail(f"journal header config is missing {key!r}: "
                     f"{sorted(hdr_cfg)}")
        with _get(_base_of(plain), "/debug/journal") as r:
            off = json.load(r)
        if off.get("armed") or off.get("total") or off.get("entries"):
            fail(f"unarmed replica's /debug/journal is not the "
                 f"disarmed shape: {off}")
        # Quiesce the armed engine (close() joins the thread and
        # detaches the journal's fault observer; the sink flushed
        # every line already), then replay the FILE offline.
        armed.scheduler.close()
        header, entries = journal_lib.read_journal(jpath)
        res = rj.run_replay(header, entries, pipe=pipe)
        if res["feed_errors"] or res["timed_out"] or res["gave_up"]:
            fail(f"offline replay did not run clean: "
                 f"feed_errors={res['feed_errors']} "
                 f"timed_out={res['timed_out']} gave_up={res['gave_up']}")
        div = rj.first_divergence(entries, res["entries"])
        if div is not None:
            fail(f"offline replay diverged from the live journal: "
                 f"{div}")
        matched, total_fp, bad = rj.reply_match(entries, res["entries"])
        if matched != total_fp or total_fp != len(bursts):
            fail(f"replayed reply fingerprints: {matched}/{total_fp} "
                 f"matched (want {len(bursts)}/{len(bursts)}; "
                 f"divergent ids {bad})")
        # Never-perturb A/B against the unarmed twin.
        plain_replies, plain_disp = drive(plain)
        if armed_replies != plain_replies:
            fail("armed vs unarmed replies diverged — the journal "
                 f"perturbed live traffic: {armed_replies} vs "
                 f"{plain_replies}")
        if armed_disp != plain_disp:
            fail("armed vs unarmed dispatch counters diverged — the "
                 f"journal perturbed the engine: {armed_disp} vs "
                 f"{plain_disp}")
        print(f"journal smoke OK: {len(bursts)} requests journaled "
              f"({sum(counts.values())} entries), offline replay "
              f"byte-identical ({matched}/{total_fp} replies, "
              "decision-for-decision equal), armed==unarmed byte "
              f"parity and dispatch schedule ({armed_disp})")
    finally:
        _shutdown_replica(armed)
        _shutdown_replica(plain)


def run_router_smoke() -> None:
    """Two tiny replicas + a router: the full gate against the ROUTER,
    then the affinity assertion — the shared-prefix burst must
    concentrate on one replica (its prefix_cache_hit_tokens_total
    dominates the fleet total)."""
    from oryx_tpu.serve.router import build_router

    reps = [boot_tiny_server(replica_id=f"r{i}") for i in range(2)]
    rsrv = build_router(
        [(f"r{i}", _base_of(s)) for i, s in enumerate(reps)],
        port=0, poll_s=0.1,
    )
    threading.Thread(target=rsrv.serve_forever, daemon=True).start()
    try:
        kind = run_checks(_base_of(rsrv))
        if kind != "router":
            fail(f"router smoke detected target kind {kind!r}")
        hits = []
        for i, s in enumerate(reps):
            with _get(_base_of(s), "/metrics") as r:
                text = r.read().decode()
            m = re.search(
                r"^oryx_serving_prefix_cache_hit_tokens_total "
                r"([0-9.e+-]+)$", text, re.M,
            )
            hits.append(float(m.group(1)) if m else 0.0)
        total = sum(hits)
        if total <= 0:
            fail("router smoke: no prefix-cache hits anywhere — "
                 f"affinity routed nothing usefully (hits={hits})")
        if max(hits) < 0.8 * total:
            fail("router smoke: shared-prefix burst did not "
                 f"concentrate on one replica (hit tokens {hits}; "
                 "want one replica >= 80% of the total)")
        with _get(_base_of(rsrv), "/metrics") as r:
            rt = r.read().decode()
        m = re.search(
            r"^oryx_router_affinity_hit_rate ([0-9.e+-]+)$", rt, re.M
        )
        if not m or float(m.group(1)) <= 0:
            fail("oryx_router_affinity_hit_rate did not move")
        print(f"router smoke OK: hit tokens per replica {hits}, "
              f"affinity_hit_rate={m.group(1)}")
    finally:
        rsrv.stop_prober()  # before the replicas go: no eject noise
        for s in reps:
            _shutdown_replica(s)
        rsrv.shutdown()


def main() -> None:
    ap = argparse.ArgumentParser(
        description="serving endpoint well-formedness gate "
        "(see module docstring)"
    )
    ap.add_argument(
        "--base-url", default=None,
        help="live target (replica or router); omitted = boot a tiny "
        "CPU replica in-process",
    )
    ap.add_argument(
        "--router-smoke", action="store_true",
        help="boot 2 tiny replicas + a router, run the gate against "
        "the router, and assert shared-prefix affinity dominance",
    )
    ap.add_argument(
        "--audit-smoke", action="store_true",
        help="boot an --audit-sample-every 1 replica and an unarmed "
        "twin, run the same sequential burst against both, and gate "
        "all-pass verdicts, ring<->counter reconciliation, audit "
        "wide-event schema, and armed==unarmed byte parity + "
        "dispatch schedule (the auditor observes, never perturbs)",
    )
    ap.add_argument(
        "--journal-smoke", action="store_true",
        help="boot a --journal armed replica and an unarmed twin, run "
        "the same sequential burst against both, replay the journal "
        "file offline byte-exactly (scripts/replay_journal.py), and "
        "gate armed==unarmed byte parity + dispatch schedule (the "
        "journal observes, never perturbs)",
    )
    args = ap.parse_args()
    if args.journal_smoke:
        if args.base_url:
            ap.error("--journal-smoke self-boots; drop --base-url")
        run_journal_check()
        return
    if args.router_smoke:
        if args.base_url:
            ap.error("--router-smoke self-boots; drop --base-url")
        run_router_smoke()
        return
    if args.audit_smoke:
        if args.base_url:
            ap.error("--audit-smoke self-boots; drop --base-url")
        run_audit_check()
        return

    srv = None
    base = args.base_url
    try:
        if base is None:
            srv = boot_tiny_server()
            base = _base_of(srv)
        kind = run_checks(base)
    finally:
        if srv is not None:
            _shutdown_replica(srv)
    if args.base_url is None:
        # Self-boot only (the fault registry is process-global and the
        # scenario needs its own deterministic injection schedule).
        run_oom_forensic_check()
    print(f"serving endpoints OK ({kind}): /healthz + /readyz + "
          "/metrics (content-type, prefix, build_info"
          + (", aggregate replica labels" if kind == "router"
             else ", hbm gauges")
          + ") + /debug/requests (+ limit/state filters, cost ledger, "
          "wide-event jsonl) + /debug/trace"
          + (" (merged router+replica)" if kind == "router" else "")
          + " + /debug/timeline (dispatch-kind reconciliation) + "
          "/debug/pages (ownership-map reconciliation vs the "
          "oryx_pool_* gauges) + "
          "honored X-Request-Id + prefix-cache family under a "
          "shared-prefix burst + latency quantiles via the shared "
          "histogram helper")


if __name__ == "__main__":
    main()
