#!/usr/bin/env python
"""Open-loop load/SLO capacity harness for the serving stack.

Drives a live server at a controlled OFFERED load — seeded Poisson
arrivals that do NOT wait for completions (open loop: a saturated
server keeps receiving work at the offered rate, exactly the regime
where closed-loop benchmarks lie) — across a sweep of rates, and
reports what capacity actually is:

  * client-measured p50/p95/p99 TTFT and per-token latency per stage
    (streaming SSE requests; TTFT = first content delta);
  * goodput: tokens/s from requests that completed WITHIN the SLO,
    vs offered load — the curve whose flattening is saturation;
  * the saturation knee: the highest offered load whose stage still
    met the SLO for >= --knee-good-frac of its requests (every stage
    past it is saturated);
  * error breakdown (429 backpressure / 503 unavailable / 504
    deadline / transport);
  * per-stage deltas of the server's own SLO anomaly detectors
    (oryx_anomaly_total{kind="ttft_slo"|"queue_depth_slo"}) — the
    pass/fail gate: ZERO firings at or below the knee;
  * per-request cost attribution from the scheduler's ledger (final
    SSE metadata): prefill vs prefix-cache-spliced tokens, decode
    steps, and page-seconds (pages-held x time, the HBM currency).

Workload shape: prompt and output lengths are drawn per-request from
small mixed distributions, and --shared-prefix-frac of requests carry
one of --shared-prefix-count long shared system prompts so the sweep
exercises the TokenTrie prefix cache like real traffic does.

Everything client-side is stdlib (urllib + threading + random); the
histogram math comes from the shared helpers in oryx_tpu.utils.metrics
(the same bucket interpolation scripts/check_serving_endpoints.py
uses).

    # against a live server
    python scripts/loadgen.py --base-url http://127.0.0.1:8000 \
        --rates 1,2,4,8,16 --duration 30 --slo-ttft 2.0 --gate

    # CI smoke: boots a tiny CPU server in-process, short sweep,
    # SLO-detector gate + report schema check + cost-ledger audit
    JAX_PLATFORMS=cpu python scripts/loadgen.py --smoke

Writes BENCH_loadgen.json (see docs/OBSERVABILITY.md "Capacity & load
testing" for how to read the knee and the goodput curve).
"""

from __future__ import annotations

import argparse
import json
import os
import random
import re
import sys
import threading
import time
import urllib.error
import urllib.request

sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)

ANOMALY_KINDS = (
    "ttft_slo", "queue_depth_slo",
    # Output-quality sentinels (ISSUE 14): zero firings at/below the
    # knee is part of the gate — a drifting audit or collapsing accept
    # rate under healthy load is a correctness regression, not noise.
    "audit_drift", "spec_accept_collapse",
)

WORDS = (
    "capacity goodput latency saturation paged prefill decode cache "
    "page token slot queue chunk splice replay admit evict serve"
).split()


# ---------------------------------------------------------------------------
# Workload synthesis (all draws from one seeded Random -> the schedule
# and every request body are reproducible)
# ---------------------------------------------------------------------------


def poisson_arrivals(rng: random.Random, rate: float,
                     duration: float) -> list[float]:
    """Open-loop arrival offsets in [0, duration): exponential
    inter-arrival times at `rate` req/s. Always at least one arrival
    (a stage that sends nothing measures nothing)."""
    out: list[float] = []
    t = rng.expovariate(rate)
    while t < duration:
        out.append(t)
        t += rng.expovariate(rate)
    return out or [0.0]


def filler_text(rng: random.Random, chars: int) -> str:
    words = []
    n = 0
    while n < chars:
        w = rng.choice(WORDS)
        words.append(w)
        n += len(w) + 1
    return " ".join(words)[:chars]


def build_body(rng: random.Random, cfg: dict) -> dict:
    """One request body: sampled prompt/output lengths, a shared
    system prefix with probability shared_prefix_frac (exercises the
    prefix cache), streaming with usage so the client can count tokens
    and read the final cost metadata."""
    messages = []
    if cfg["shared_prefixes"] and rng.random() < cfg["shared_prefix_frac"]:
        messages.append({
            "role": "system",
            "content": rng.choice(cfg["shared_prefixes"]),
        })
    chars = rng.choice(cfg["prompt_chars_choices"])
    messages.append({
        "role": "user",
        "content": f"q{rng.randrange(1_000_000)}: "
                   + filler_text(rng, chars),
    })
    return {
        "messages": messages,
        "max_tokens": rng.choice(cfg["max_tokens_choices"]),
        "stream": True,
        "stream_options": {"include_usage": True},
    }


# ---------------------------------------------------------------------------
# SSE client
# ---------------------------------------------------------------------------


def send_stream(base: str, body: dict, timeout: float) -> dict:
    """POST one streaming completion; returns the client-side record:
    status, ttft_s (first content delta), per_token_s, completion
    token count (from the usage chunk), the server's cost ledger
    (from the final chunk's "oryx" metadata) and an error class."""
    rec: dict = {
        "status": None, "ok": False, "ttft_s": None, "per_token_s": None,
        "e2e_s": None, "tokens": 0, "cost": None, "error": None,
        # Router-mode attribution (zero/absent against a bare replica):
        # how many times the router retried this request onto another
        # replica, which replica finally served it, and whether an
        # error was ROUTER-generated (no healthy replica / draining)
        # rather than a backend's own answer.
        "router_retries": 0, "replica": None,
    }
    data = json.dumps(body).encode()
    req = urllib.request.Request(
        base + "/v1/chat/completions", data=data,
        headers={"Content-Type": "application/json"},
    )
    t0 = time.monotonic()
    t_first = t_last = None
    finished = False
    try:
        with urllib.request.urlopen(req, timeout=timeout) as r:
            rec["status"] = r.status
            rec["router_retries"] = int(
                r.headers.get("X-Oryx-Router-Retries") or 0
            )
            rec["replica"] = r.headers.get("X-Oryx-Router-Replica")
            for raw in r:
                line = raw.decode("utf-8", "replace").strip()
                if not line.startswith("data: "):
                    continue
                payload = line[len("data: "):]
                if payload == "[DONE]":
                    break
                obj = json.loads(payload)
                if "error" in obj:
                    rec["error"] = "stream_error"
                    break
                now = time.monotonic()
                choices = obj.get("choices") or []
                if choices:
                    if choices[0].get("delta", {}).get("content"):
                        if t_first is None:
                            t_first = now
                            rec["ttft_s"] = now - t0
                        t_last = now
                    if choices[0].get("finish_reason"):
                        finished = True
                if obj.get("usage"):
                    rec["tokens"] = int(
                        obj["usage"].get("completion_tokens", 0)
                    )
                if isinstance(obj.get("oryx"), dict):
                    rec["cost"] = obj["oryx"].get("cost")
    except urllib.error.HTTPError as e:
        rec["status"] = e.code
        hdrs = e.headers or {}
        rec["router_retries"] = int(
            hdrs.get("X-Oryx-Router-Retries") or 0
        )
        # A 503 the ROUTER generated (fleet exhausted / router drain)
        # is a different incident from a backend's own 503 forwarded
        # through — the X-Oryx-Router-Error tag splits them.
        if e.code == 503 and hdrs.get("X-Oryx-Router-Error"):
            rec["error"] = "router_503"
        else:
            rec["error"] = str(e.code)
        e.close()
        rec["e2e_s"] = time.monotonic() - t0
        return rec
    except Exception:
        rec["error"] = "transport"
        rec["e2e_s"] = time.monotonic() - t0
        return rec
    rec["e2e_s"] = time.monotonic() - t0
    rec["ok"] = rec["error"] is None and finished
    if (
        rec["ok"] and rec["tokens"] > 1
        and t_first is not None and t_last is not None and t_last > t_first
    ):
        rec["per_token_s"] = (t_last - t_first) / (rec["tokens"] - 1)
    return rec


# ---------------------------------------------------------------------------
# Server-side scrapes
# ---------------------------------------------------------------------------


def scrape_metrics(base: str, timeout: float = 30.0) -> str:
    with urllib.request.urlopen(base + "/metrics", timeout=timeout) as r:
        return r.read().decode()


def build_info_labels(text: str, family: str) -> dict[str, str]:
    """Labels of an info gauge (build_info) from a text exposition —
    the target's self-declared identity (engine, revision, replica),
    stamped into the report so scripts/bench_compare.py can refuse
    cross-config comparisons instead of producing a noisy diff."""
    m = re.search(rf"^{re.escape(family)}\{{([^}}]*)\}} 1$", text, re.M)
    if not m:
        return {}
    return dict(re.findall(r'(\w+)="([^"]*)"', m.group(1)))


def fetch_timeline(base: str, n: int = 24, timeout: float = 30.0) -> dict:
    """One replica's /debug/timeline snapshot (utils/timeline.py): the
    per-stage flight-data-recorder embed — reading the records at the
    knee stage replaces guessing engine state from counter deltas. A
    target without the endpoint (an old server) degrades to an error
    entry, never a failed stage."""
    try:
        with urllib.request.urlopen(
            base + f"/debug/timeline?n={n}", timeout=timeout
        ) as r:
            body = json.load(r)
        return {
            "total_steps": body.get("total_steps"),
            "counts_by_kind": body.get("counts_by_kind"),
            "records": body.get("records"),
        }
    except Exception as e:
        return {"error": f"{type(e).__name__}: {e}"}


def fetch_pages_summary(base: str, timeout: float = 30.0) -> dict:
    """One target's /debug/pages?format=summary body (the page-pool
    observatory). Targets without the endpoint (an old server)
    degrade to an error entry, never a failed stage."""
    try:
        with urllib.request.urlopen(
            base + "/debug/pages?format=summary", timeout=timeout
        ) as r:
            return json.load(r)
    except Exception as e:
        return {"error": f"{type(e).__name__}: {e}"}


def _kind_counter_values(text: str, family: str) -> dict[str, float]:
    """{kind: value} of a kind-labeled counter family in a text
    exposition (the oryx_device_time_seconds_total shape)."""
    out: dict[str, float] = {}
    for m in re.finditer(
        rf'^{re.escape(family)}\{{kind="([^"]+)"\}} ([0-9.eE+-]+)$',
        text, re.M,
    ):
        out[m.group(1)] = float(m.group(2))
    return out


def memory_block(m0: str, m1: str, pages: dict,
                 timeline: dict) -> dict:
    """One stage's `memory` record: pool geometry + end-of-stage
    occupancy/fragmentation (from the observatory summary), peak
    occupancy over the stage (min free_pages across the stage's
    timeline records, floored by the boot-wide watermark), page
    lifetime/idle quantiles from the oryx_page_lifetime_seconds
    histogram DELTA across the stage, and the sampled device-time
    split (per-kind busy seconds vs the sampled wall window —
    busy <= wall per kind by construction, the gate's sanity bar).
    This block is what ROADMAP item 3's memory-economics PR will gate
    its halving claim on (scripts/bench_compare.py memory class)."""
    from oryx_tpu.utils.metrics import histogram_quantile, \
        parse_prom_histogram

    summary = pages.get("summary") or {}
    block: dict = {
        "pool": {
            "num_pages": pages.get("num_pages"),
            "page_size": pages.get("page_size"),
            "kv_dtype": pages.get("kv_dtype"),
            "kv_pool_bytes": pages.get("kv_pool_bytes"),
        },
        "end": {
            k: summary.get(k)
            for k in ("free", "slot", "cache", "shared",
                      "fragmentation_ratio", "reconciled")
        },
        "peak_pages_in_use": summary.get("peak_pages_in_use"),
    }
    if "error" in pages:
        block["error"] = pages["error"]
    num_pages = pages.get("num_pages")
    frees = [
        rec.get("free_pages")
        for rec in (timeline.get("records") or [])
        if isinstance(rec, dict) and rec.get("free_pages") is not None
    ]
    if num_pages is not None and frees:
        block["stage_peak_pages_in_use"] = num_pages - min(frees)
    kv_bytes = pages.get("kv_pool_bytes")
    peak = block.get(
        "stage_peak_pages_in_use", block.get("peak_pages_in_use")
    )
    if kv_bytes and num_pages and peak is not None:
        # Peak occupancy in HBM BYTES: pages x (pool bytes / pages) —
        # the row that halves under --kv-dtype int8 while the page
        # count stays put (pages are token-granular).
        block["stage_peak_kv_bytes"] = int(peak * kv_bytes / num_pages)
    for name, fam in (
        ("page_lifetime_s", "oryx_page_lifetime_seconds"),
        ("page_idle_s", "oryx_page_idle_seconds"),
    ):
        h0 = parse_prom_histogram(m0, fam)
        h1 = parse_prom_histogram(m1, fam)
        if h0 is None or h1 is None or h0[0] != h1[0]:
            block[name] = {"count": 0, "p50": None, "p95": None}
            continue
        counts = [b - a for a, b in zip(h0[1], h1[1])]
        total = h1[2] - h0[2]
        q = {}
        for p in (0.5, 0.95):
            v = histogram_quantile(p, h1[0], counts, total)
            q[f"p{int(p * 100)}"] = None if v != v else round(v, 6)
        block[name] = {"count": total, **q}
    dev0 = _kind_counter_values(m0, "oryx_device_time_seconds_total")
    dev1 = _kind_counter_values(m1, "oryx_device_time_seconds_total")
    wall0 = _kind_counter_values(
        m0, "oryx_profile_sampled_wall_seconds_total"
    )
    wall1 = _kind_counter_values(
        m1, "oryx_profile_sampled_wall_seconds_total"
    )
    block["device_time_s"] = {
        k: round(dev1[k] - dev0.get(k, 0.0), 6) for k in sorted(dev1)
    }
    block["sampled_wall_s"] = {
        k: round(wall1[k] - wall0.get(k, 0.0), 6) for k in sorted(wall1)
    }
    # Host-tier rows (the prefix cache's host-RAM spill plane): end-of
    # -stage residency plus the stage's reload economics — hits are
    # requests whose splice crossed into spilled blocks, uploads the
    # pages brought back. hit rate = uploaded pages per hit (how much
    # spilled prefix each hit recovered on average is uploads/hits;
    # the fraction of hits that recovered ANYTHING device-side is what
    # the closed-loop gate asserts via the counters themselves).
    rh = _counter_value(m1, "oryx_cache_reload_hit_total") \
        - _counter_value(m0, "oryx_cache_reload_hit_total")
    ru = _counter_value(m1, "oryx_cache_reload_upload_total") \
        - _counter_value(m0, "oryx_cache_reload_upload_total")
    block["host_tier"] = {
        "spilled_pages": _counter_value(m1, "oryx_cache_spilled_pages"),
        "host_bytes": _counter_value(m1, "oryx_cache_host_bytes"),
        "reload_hits": rh,
        "reload_uploads": ru,
        "reload_pages_per_hit": round(ru / rh, 4) if rh else None,
    }
    return block


def anomaly_counts(text: str) -> dict[str, float]:
    out = {}
    for kind in ANOMALY_KINDS:
        m = re.search(
            rf'^oryx_anomaly_total\{{kind="{kind}"\}} ([0-9.e+-]+)$',
            text, re.M,
        )
        out[kind] = float(m.group(1)) if m else 0.0
    return out


def server_hist_quantiles(
    m0: str, m1: str, family: str, qs: tuple[float, ...] = (0.5, 0.99)
) -> dict[str, float | None]:
    """Windowed quantiles of a server histogram across one stage: the
    element-wise DELTA of two cumulative scrapes is itself a valid
    cumulative histogram, fed to the shared bucket-interpolation
    helper."""
    from oryx_tpu.utils.metrics import histogram_quantile, \
        parse_prom_histogram

    h0, h1 = parse_prom_histogram(m0, family), parse_prom_histogram(m1, family)
    out: dict[str, float | None] = {}
    if h0 is None or h1 is None or h0[0] != h1[0]:
        return {f"p{int(q * 100)}": None for q in qs}
    bounds = h1[0]
    counts = [b - a for a, b in zip(h0[1], h1[1])]
    total = h1[2] - h0[2]
    for q in qs:
        v = histogram_quantile(q, bounds, counts, total)
        out[f"p{int(q * 100)}"] = None if v != v else round(v, 6)
    return out


def speculation_block(scrape_pairs: list[tuple[str, str]]) -> dict:
    """Per-stage speculation report from server scrape deltas (one
    (before, after) pair per backend; a fleet sums across replicas):
    accepted-tokens-per-step MEAN from the
    oryx_serving_accepted_tokens_per_step histogram's sum/count delta
    (the docs/OBSERVABILITY.md headline — >1 means speculation is
    converting drafts into latency), plus the raw draft economics.
    `active` stays False (and the mean None) on a non-speculative
    engine, so the block is schema-stable either way."""
    from oryx_tpu.utils.metrics import parse_prom_histogram

    fam = "oryx_serving_accepted_tokens_per_step"
    d_sum = d_cnt = prop = acc = 0.0
    for m0, m1 in scrape_pairs:
        h0 = parse_prom_histogram(m0, fam)
        h1 = parse_prom_histogram(m1, fam)
        if h0 is not None and h1 is not None:
            d_sum += h1[3] - h0[3]
            d_cnt += h1[2] - h0[2]
        for name, ref in (
            ("oryx_serving_draft_proposed_total", "prop"),
            ("oryx_serving_draft_accepted_total", "acc"),
        ):
            d = _counter_value(m1, name) - _counter_value(m0, name)
            if ref == "prop":
                prop += d
            else:
                acc += d
    return {
        "active": d_cnt > 0,
        "accepted_tokens_per_step": (
            round(d_sum / d_cnt, 4) if d_cnt > 0 else None
        ),
        "draft_proposed": prop,
        "draft_accepted": acc,
        "draft_accept_rate": round(acc / prop, 4) if prop > 0 else None,
    }


def audit_block(scrape_pairs: list[tuple[str, str]]) -> dict:
    """Per-stage output-audit report from server scrape deltas (one
    (before, after) pair per backend; a fleet sums across replicas):
    sampled/pass/drift/fail counts from oryx_audit_total{verdict=} and
    the derived pass_rate — bench_compare treats it as an EXACT-class
    metric (any non-pass on the fp path is a regression, not noise).
    Schema-stable with auditing off: zero counts, pass_rate None."""

    def verdict_value(text: str, verdict: str) -> float:
        m = re.search(
            rf'^oryx_audit_total\{{verdict="{verdict}"\}} '
            rf"([0-9.eE+-]+)$", text, re.M,
        )
        return float(m.group(1)) if m else 0.0

    out = {"sampled": 0.0, "pass": 0.0, "drift": 0.0, "fail": 0.0}
    for m0, m1 in scrape_pairs:
        out["sampled"] += (
            _counter_value(m1, "oryx_audit_sampled_total")
            - _counter_value(m0, "oryx_audit_sampled_total")
        )
        for v in ("pass", "drift", "fail"):
            out[v] += verdict_value(m1, v) - verdict_value(m0, v)
    done = out["pass"] + out["drift"] + out["fail"]
    out["pass_rate"] = round(out["pass"] / done, 4) if done else None
    return out


# ---------------------------------------------------------------------------
# Stage runner + aggregation
# ---------------------------------------------------------------------------


def _dist(values: list[float]) -> dict:
    from oryx_tpu.utils.metrics import sample_quantile

    if not values:
        return {"n": 0, "p50": None, "p95": None, "p99": None,
                "mean": None, "max": None}
    return {
        "n": len(values),
        "p50": round(sample_quantile(values, 0.5), 6),
        "p95": round(sample_quantile(values, 0.95), 6),
        "p99": round(sample_quantile(values, 0.99), 6),
        "mean": round(sum(values) / len(values), 6),
        "max": round(max(values), 6),
    }


def _counter_value(text: str, family: str) -> float:
    """Value of one unlabeled counter/gauge/sum sample, 0 if absent."""
    m = re.search(
        rf"^{re.escape(family)} ([0-9.eE+-]+)$", text, re.M
    )
    return float(m.group(1)) if m else 0.0


def replica_stage_split(r0: dict[str, str],
                        r1: dict[str, str]) -> dict[str, dict]:
    """Per-replica goodput attribution for one stage: the delta of
    each replica's own counters between the stage's two direct
    scrapes — completions served, prefix-cache hit tokens (the
    affinity payoff), and decode steps (the request_decode_steps
    histogram's sum, the device-work share)."""
    out: dict[str, dict] = {}
    total_completed = 0.0
    for rid in sorted(r1):
        completed = (
            _counter_value(r1[rid], "oryx_serving_completed")
            - _counter_value(r0.get(rid, ""), "oryx_serving_completed")
        )
        out[rid] = {
            "completed": completed,
            "prefix_hit_tokens": (
                _counter_value(
                    r1[rid], "oryx_serving_prefix_cache_hit_tokens_total"
                ) - _counter_value(
                    r0.get(rid, ""),
                    "oryx_serving_prefix_cache_hit_tokens_total",
                )
            ),
            "decode_steps": (
                _counter_value(
                    r1[rid], "oryx_serving_request_decode_steps_sum"
                ) - _counter_value(
                    r0.get(rid, ""),
                    "oryx_serving_request_decode_steps_sum",
                )
            ),
        }
        total_completed += completed
    for rid, row in out.items():
        row["completed_share"] = round(
            row["completed"] / total_completed, 4
        ) if total_completed > 0 else None
    return out


def aggregate_stage(rate: float, duration: float, results: list[dict],
                    hung: int, m0: str, m1: str, slo_ttft: float,
                    slo_per_token: float | None,
                    replica_scrapes: tuple[dict, dict] | None = None,
                    router: bool = False) -> dict:
    """One stage's record for the report. Goodput divides by the
    ARRIVAL window (`duration`), not the drain: open-loop capacity is
    tokens served per second of offered-load time. A hung request
    (worker still blocked past the drain, so it never appended a
    record) counts in `sent` and against `slo_good_frac` — offered
    traffic that never completed is the OPPOSITE of healthy and must
    not inflate the fraction the knee is found on."""
    ok = [r for r in results if r["ok"]]
    good = [
        r for r in ok
        if r["ttft_s"] is not None and r["ttft_s"] <= slo_ttft
        and (
            slo_per_token is None or r["per_token_s"] is None
            or r["per_token_s"] <= slo_per_token
        )
    ]
    errors = {"429": 0, "503": 0, "504": 0, "other_http": 0,
              "transport": 0, "stream_error": 0,
              "harness_inflight_cap": 0, "router_503": 0}
    for r in results:
        e = r["error"]
        if e is None:
            continue
        if e in ("429", "503", "504", "router_503"):
            # router_503 = the ROUTER answered (no healthy replica /
            # router drain), distinct from a backend 503 forwarded
            # through — conflating them would blame backends for a
            # routing-tier outage.
            errors[e] += 1
        elif e in ("transport", "stream_error", "harness_inflight_cap"):
            # harness_inflight_cap is a HARNESS-side shed, not a
            # server response — bucketing it as HTTP would blame the
            # server for load the generator never sent.
            errors[e] += 1
        else:
            errors["other_http"] += 1
    if replica_scrapes is not None:
        # Router target: the SLO detectors live on the replicas, not
        # the router — the stage's anomaly delta is the fleet sum of
        # each replica's own scrape pair.
        r0s, r1s = replica_scrapes
        anomalies = {
            k: sum(
                anomaly_counts(r1s[rid]).get(k, 0.0)
                - anomaly_counts(r0s.get(rid, "")).get(k, 0.0)
                for rid in r1s
            )
            for k in ANOMALY_KINDS
        }
    else:
        a0, a1 = anomaly_counts(m0), anomaly_counts(m1)
        anomalies = {k: a1[k] - a0.get(k, 0.0) for k in ANOMALY_KINDS}
    costs = [r["cost"] for r in results if r["cost"]]
    prefill = sum(c["prefill_tokens"] for c in costs)
    cached = sum(c["cached_tokens"] for c in costs)
    page_s = sum(c["page_seconds"] for c in costs)
    goodput = sum(r["tokens"] for r in good) / duration
    sent = len(results) + hung
    router_block = None
    if router:
        # Affinity across THIS stage: the delta of the router's own
        # hit/miss counters between its two scrapes.
        d_hits = (
            _counter_value(m1, "oryx_router_affinity_hits_total")
            - _counter_value(m0, "oryx_router_affinity_hits_total")
        )
        d_miss = (
            _counter_value(m1, "oryx_router_affinity_misses_total")
            - _counter_value(m0, "oryx_router_affinity_misses_total")
        )
        router_block = {
            "retries": sum(r.get("router_retries") or 0 for r in results),
            "router_503": errors["router_503"],
            "affinity": {
                "hits": d_hits,
                "misses": d_miss,
                "hit_rate": round(d_hits / (d_hits + d_miss), 4)
                if d_hits + d_miss > 0 else None,
            },
            "per_replica": replica_stage_split(*replica_scrapes)
            if replica_scrapes is not None else {},
        }
    out = {
        "offered_rps": rate,
        "sent": sent,
        "ok": len(ok),
        "good": len(good),
        "hung": hung,
        "slo_good_frac": round(len(good) / max(1, sent), 4),
        "goodput_tps": round(goodput, 3),
        "completed_tps": round(
            sum(r["tokens"] for r in ok) / duration, 3
        ),
        "ttft_s": _dist([
            r["ttft_s"] for r in results if r["ttft_s"] is not None
        ]),
        "per_token_s": _dist([
            r["per_token_s"] for r in results
            if r["per_token_s"] is not None
        ]),
        "server_ttft_s": server_hist_quantiles(
            m0, m1,
            "oryx_router_upstream_ttfb_seconds" if router
            else "oryx_serving_ttft_seconds",
        ),
        "errors": errors,
        "anomalies": anomalies,
        "speculation": speculation_block(
            [(replica_scrapes[0].get(rid, ""), replica_scrapes[1][rid])
             for rid in replica_scrapes[1]]
            if replica_scrapes is not None else [(m0, m1)]
        ),
        "audit": audit_block(
            [(replica_scrapes[0].get(rid, ""), replica_scrapes[1][rid])
             for rid in replica_scrapes[1]]
            if replica_scrapes is not None else [(m0, m1)]
        ),
        "cost": {
            "requests_with_cost": len(costs),
            "prefill_tokens": prefill,
            "cached_tokens": cached,
            "cache_hit_frac": round(
                cached / max(1, prefill + cached), 4
            ),
            "decode_steps": sum(c["decode_steps"] for c in costs),
            "decode_tokens": sum(
                c.get("decode_tokens", 0) for c in costs
            ),
            "page_seconds": round(page_s, 3),
            "mean_page_seconds": round(page_s / max(1, len(costs)), 6),
            "goodput_tokens_per_page_second": round(
                goodput * duration / page_s, 3
            ) if page_s > 0 else None,
        },
    }
    if router_block is not None:
        out["router"] = router_block
    return out


def run_stage(base: str, rate: float, cfg: dict,
              rng: random.Random,
              carryover: list | None = None,
              replicas: dict[str, str] | None = None,
              router: bool = False) -> dict:
    """Run one open-loop stage at `rate` req/s: the dispatcher sleeps
    to each pre-drawn arrival time and fires a daemon thread per
    request — completions never gate arrivals. A bounded in-flight cap
    (way above anything a healthy stage reaches) keeps a wedged server
    from accumulating threads without limit; capped sends are recorded
    as harness errors, never silently dropped. `carryover` is the
    cross-stage straggler registry: threads still blocked from EARLIER
    stages count against the cap too (pass the same list to every
    stage of a sweep), otherwise a wedged server accumulates up to
    max_inflight threads PER STAGE."""
    duration = cfg["duration"]
    arrivals = poisson_arrivals(rng, rate, duration)
    bodies = [build_body(rng, cfg) for _ in arrivals]
    results: list[dict] = []
    lock = threading.Lock()
    threads: list[threading.Thread] = []
    carry = carryover if carryover is not None else []
    carry[:] = [t for t in carry if t.is_alive()]

    def worker(body: dict) -> None:
        rec = send_stream(base, body, cfg["request_timeout"])
        with lock:
            results.append(rec)

    m0 = scrape_metrics(base)
    r0 = {
        rid: scrape_metrics(u) for rid, u in (replicas or {}).items()
    }
    t0 = time.monotonic()
    for off, body in zip(arrivals, bodies):
        delay = t0 + off - time.monotonic()
        if delay > 0:
            time.sleep(delay)
        live = sum(t.is_alive() for t in threads) + sum(
            t.is_alive() for t in carry
        )
        if live >= cfg["max_inflight"]:
            with lock:
                results.append({
                    "status": None, "ok": False, "ttft_s": None,
                    "per_token_s": None, "e2e_s": None, "tokens": 0,
                    "cost": None, "error": "harness_inflight_cap",
                    "router_retries": 0, "replica": None,
                })
            continue
        t = threading.Thread(target=worker, args=(body,), daemon=True)
        t.start()
        threads.append(t)
    deadline = time.monotonic() + cfg["drain_s"]
    for t in threads:
        t.join(timeout=max(0.0, deadline - time.monotonic()))
    hung = sum(t.is_alive() for t in threads)
    carry.extend(t for t in threads if t.is_alive())
    m1 = scrape_metrics(base)
    r1 = {
        rid: scrape_metrics(u) for rid, u in (replicas or {}).items()
    }
    with lock:
        # Snapshot: hung daemon workers may still append after the
        # drain; aggregation must see one consistent list.
        snapshot = list(results)
    st = aggregate_stage(
        rate, duration, snapshot, hung, m0, m1,
        cfg["slo_ttft"], cfg["slo_per_token"],
        replica_scrapes=(r0, r1) if replicas else None,
        router=router,
    )
    # Engine step-timeline snapshot at stage end: what the engine(s)
    # were actually doing as this offered load drained — per replica
    # behind a router (the router has no engine loop of its own). The
    # memory block rides the same per-target split (pool + page
    # lifetimes + device-time split live on the engines).
    if replicas:
        st["timeline"] = {
            rid: fetch_timeline(u) for rid, u in replicas.items()
        }
        st["memory"] = {
            rid: memory_block(
                r0.get(rid, ""), r1[rid], fetch_pages_summary(u),
                st["timeline"].get(rid) or {},
            )
            for rid, u in replicas.items()
        }
    else:
        st["timeline"] = fetch_timeline(base, n=256)
        st["memory"] = memory_block(
            m0, m1, fetch_pages_summary(base), st["timeline"]
        )
    return st


# ---------------------------------------------------------------------------
# Knee + report schema + gate
# ---------------------------------------------------------------------------


def find_knee(stages: list[dict], good_frac: float = 0.9) -> dict | None:
    """The saturation knee: the highest offered load whose stage still
    met the SLO for >= good_frac of its requests, with every
    lower-load stage healthy too (prefix property — a sick low-load
    stage caps the knee below it). None = saturated at the lowest
    offered load."""
    knee = None
    for i, st in enumerate(stages):
        if st["sent"] > 0 and st["slo_good_frac"] >= good_frac:
            knee = i
        else:
            break
    if knee is None:
        return None
    st = stages[knee]
    return {
        "index": knee,
        "offered_rps": st["offered_rps"],
        "goodput_tps": st["goodput_tps"],
        "saturated": knee < len(stages) - 1,
    }


_STAGE_KEYS = (
    "offered_rps", "sent", "ok", "good", "slo_good_frac", "goodput_tps",
    "completed_tps", "ttft_s", "per_token_s", "server_ttft_s", "errors",
    "anomalies", "speculation", "audit", "cost", "timeline", "memory",
)


def _stage_memory_blocks(st: dict) -> list[dict]:
    """The stage's memory blocks — one for a single target, one per
    replica behind a router (error entries excluded)."""
    mem = st.get("memory")
    if not isinstance(mem, dict):
        return []
    if "pool" in mem:
        return [mem]
    return [
        b for b in mem.values() if isinstance(b, dict) and "pool" in b
    ]


def validate_report(report: dict) -> list[str]:
    """Schema well-formedness: the shape downstream tooling (CI gates,
    dashboards diffing BENCH_loadgen.json across PRs) depends on.
    Returns problems, [] when clean."""
    probs = []
    for k in ("bench", "config", "stages", "knee", "gate"):
        if k not in report:
            probs.append(f"missing top-level key {k!r}")
    if report.get("bench") != "loadgen":
        probs.append("bench != 'loadgen'")
    stages = report.get("stages") or []
    if not stages:
        probs.append("no stages")
    for i, st in enumerate(stages):
        for k in _STAGE_KEYS:
            if k not in st:
                probs.append(f"stage {i} missing {k!r}")
        for k in ("p50", "p95", "p99"):
            if k not in (st.get("ttft_s") or {}):
                probs.append(f"stage {i} ttft_s missing {k!r}")
            if k not in (st.get("per_token_s") or {}):
                probs.append(f"stage {i} per_token_s missing {k!r}")
        for k in ANOMALY_KINDS:
            if k not in (st.get("anomalies") or {}):
                probs.append(f"stage {i} anomalies missing {k!r}")
        for k in ("429", "503", "504", "transport"):
            if k not in (st.get("errors") or {}):
                probs.append(f"stage {i} errors missing {k!r}")
    knee = report.get("knee")
    if knee is not None and not isinstance(knee, dict):
        probs.append("knee is neither null nor an object")
    if isinstance(knee, dict):
        for k in ("index", "offered_rps", "goodput_tps", "saturated"):
            if k not in knee:
                probs.append(f"knee missing {k!r}")
    return probs


def check_cost_ledger(base: str) -> list[str]:
    """Every finished request in the flight recorder must carry a
    COMPLETE cost ledger (the acceptance bar for the per-request
    attribution path). The key list is the scheduler's own contract
    (utils/metrics.REQUEST_COST_KEYS) — one source of truth."""
    from oryx_tpu.utils.metrics import REQUEST_COST_KEYS

    with urllib.request.urlopen(
        base + "/debug/requests?state=done", timeout=30
    ) as r:
        body = json.load(r)
    if body.get("engine") not in ("continuous", "router"):
        # One clear reason beats N "missing every key" lines. The
        # router's merged recorder carries its replicas' ledgers.
        return [
            "cost-ledger audit requires a scheduler engine or a "
            f"router (server reports engine={body.get('engine')!r})"
        ]
    reqs = body.get("requests", [])
    if not reqs:
        return ["no finished requests in /debug/requests?state=done"]
    probs = []
    for rec in reqs:
        cost = (rec.get("meta") or {}).get("cost")
        missing = [
            k for k in REQUEST_COST_KEYS
            if not isinstance(cost, dict) or k not in cost
        ]
        if missing:
            probs.append(
                f"request {rec.get('id')}: cost ledger missing {missing}"
            )
    return probs


def evaluate_gate(report: dict, *, ledger_problems: list[str],
                  require_affinity: float | None = None,
                  vs_single: bool = False,
                  check_memory: bool = False) -> dict:
    """Pass/fail: schema valid, a knee exists, and ZERO SLO-detector
    firings (and zero hung/transport casualties) at or below it.
    Router sweeps add: the sweep-wide affinity hit rate must exceed
    `require_affinity` (the shared-prefix mix must actually land hot),
    and with `vs_single` the knee must sit at STRICTLY higher offered
    load than the recorded single-replica baseline's. `check_memory`
    (self-booted targets) adds the memory-observatory bars: zero
    leaked pages after the sweep drains (the end-of-stage snapshot's
    free + cache must cover the pool with no slot/shared residue),
    nonzero page-lifetime samples across the sweep, and — when the
    device-time sampler is armed — a per-kind split that stays within
    its sampled wall windows."""
    reasons = list(validate_report(report))
    reasons += ledger_problems
    if check_memory:
        for rid, a in (report.get("memory_audit") or {}).items():
            if a.get("leaked"):
                reasons.append(
                    f"leaked pages on {rid} after drain: "
                    f"slot={a.get('slot')} shared={a.get('shared')} "
                    f"free={a.get('free')} cache={a.get('cache')} of "
                    f"{a.get('num_pages')} (want slot=shared=0, "
                    "free+cache==pool)"
                )
        blocks = [
            b for st in report.get("stages", [])
            for b in _stage_memory_blocks(st)
        ]
        if not blocks:
            reasons.append(
                "no memory block on any stage (the /debug/pages "
                "observatory never answered)"
            )
        lifetime = sum(
            (b.get("page_lifetime_s") or {}).get("count") or 0
            for b in blocks
        )
        if blocks and lifetime <= 0:
            reasons.append(
                "zero page-lifetime samples across the sweep (the "
                "allocator's free-time observatory hook never fired)"
            )
        for st in report.get("stages", []):
            for b in _stage_memory_blocks(st):
                dev = b.get("device_time_s") or {}
                wall = b.get("sampled_wall_s") or {}
                for k, v in dev.items():
                    w = wall.get(k)
                    if w is not None and v > w * 1.1 + 0.05:
                        reasons.append(
                            f"device-time split kind {k!r} "
                            f"({v:.3f}s) exceeds its sampled wall "
                            f"window ({w:.3f}s) at offered "
                            f"{st['offered_rps']:g} rps"
                        )
        if (report.get("config") or {}).get("profile_sample_every"):
            if not any(b.get("sampled_wall_s") for b in blocks):
                reasons.append(
                    "device-time sampler armed but no sampled wall "
                    "windows recorded across the sweep"
                )
    knee = report.get("knee")
    if require_affinity is not None:
        hits = sum(
            (st.get("router") or {}).get("affinity", {}).get("hits") or 0
            for st in report.get("stages", [])
        )
        misses = sum(
            (st.get("router") or {}).get("affinity", {}).get("misses") or 0
            for st in report.get("stages", [])
        )
        rate = hits / (hits + misses) if hits + misses > 0 else 0.0
        report["affinity_hit_rate"] = round(rate, 4)
        if rate <= require_affinity:
            reasons.append(
                f"affinity hit rate {rate:.3f} <= {require_affinity} "
                "on the shared-prefix mix (routing is not preserving "
                "cache locality)"
            )
    if vs_single:
        single = (report.get("single_baseline") or {}).get("knee")
        if single is None:
            reasons.append(
                "--gate-vs-single: no single-replica baseline knee "
                "available to compare against"
            )
        elif knee is None or knee["offered_rps"] <= single["offered_rps"]:
            got = None if knee is None else knee["offered_rps"]
            reasons.append(
                f"router knee at offered {got} rps is not strictly "
                f"above the single-replica knee at "
                f"{single['offered_rps']} rps"
            )
    if knee is None:
        reasons.append(
            "saturated at the lowest offered load (no knee found)"
        )
    else:
        for st in report["stages"][: knee["index"] + 1]:
            fired = sum(st["anomalies"].values())
            if fired:
                reasons.append(
                    f"{fired:g} SLO-detector firing(s) at offered "
                    f"{st['offered_rps']:g} rps (at/below the knee)"
                )
            capped = st["errors"].get("harness_inflight_cap", 0)
            if st["hung"] or st["errors"]["transport"] or capped:
                reasons.append(
                    f"{st['hung']} hung / "
                    f"{st['errors']['transport']} transport-failed / "
                    f"{capped} harness-capped request(s) at offered "
                    f"{st['offered_rps']:g} rps (at/below the knee)"
                )
    return {"passed": not reasons, "reasons": reasons}


# ---------------------------------------------------------------------------
# Self-boot tiny server (smoke / no --base-url)
# ---------------------------------------------------------------------------


class _CharTokenizer:
    def encode(self, text, add_special_tokens=False):
        return [min(ord(c), 500) for c in text]

    def decode(self, ids, skip_special_tokens=True):
        return "".join(chr(i) for i in ids if 0 < i < 500)


def boot_tiny_server(args, *, replica_id: str | None = None,
                     params=None, cfg=None,
                     profile_sample_every: int | None = None,
                     journal_path: str | None = None):
    """In-process tiny-geometry continuous-engine server with the SLO
    detectors ARMED (they are the gate). Returns (srv, base_url).
    profile_sample_every overrides the CLI value (the fleet boot
    disables sampling per replica — jax's profiler is process-global
    and N in-process engines would contend for it)."""
    import jax

    from oryx_tpu import config as cfg_lib
    from oryx_tpu.models import oryx
    from oryx_tpu.serve import api_server
    from oryx_tpu.serve.pipeline import OryxInference

    if cfg is None:
        cfg = cfg_lib.oryx_tiny()
    if params is None:
        params = oryx.init_params(cfg, jax.random.key(0))
    pipe = OryxInference(_CharTokenizer(), params, cfg)
    speculate = getattr(args, "speculate", 0)
    if profile_sample_every is None:
        profile_sample_every = getattr(args, "profile_sample_every", 0)
    srv = api_server.build_server(
        pipe, port=0, engine="continuous", num_slots=2, page_size=16,
        decode_chunk=4, max_ctx=512, prefill_chunk=32,
        ragged=bool(speculate), speculate=speculate,
        kv_dtype=getattr(args, "kv_dtype", "bf16"),
        host_cache_bytes=getattr(args, "host_cache_bytes", 0),
        profile_sample_every=profile_sample_every,
        ttft_slo=args.server_ttft_slo,
        queue_depth_slo=args.server_queue_depth_slo,
        replica_id=replica_id,
        journal_path=journal_path,
    )
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    return srv, f"http://127.0.0.1:{srv.server_address[1]}"


def boot_tiny_fleet(args, n: int):
    """N tiny replicas (shared tiny params — one compile, n engines)
    behind a prefix-affinity router. Returns (replica_srvs, router_srv,
    router_base, {rid: replica_base})."""
    import jax

    from oryx_tpu import config as cfg_lib
    from oryx_tpu.models import oryx
    from oryx_tpu.serve.router import build_router

    cfg = cfg_lib.oryx_tiny()
    params = oryx.init_params(cfg, jax.random.key(0))
    servers, bases = [], {}
    for i in range(n):
        srv, base = boot_tiny_server(
            args, replica_id=f"r{i}", params=params, cfg=cfg,
            profile_sample_every=0,
        )
        servers.append(srv)
        bases[f"r{i}"] = base
    rsrv = build_router(
        sorted(bases.items()), port=0, poll_s=0.2,
    )
    threading.Thread(target=rsrv.serve_forever, daemon=True).start()
    return (
        servers, rsrv,
        f"http://127.0.0.1:{rsrv.server_address[1]}", bases,
    )


def warmup(base: str, cfg: dict, rng: random.Random) -> None:
    """Compile the prefill buckets the sweep will hit BEFORE measuring
    — first-touch XLA compiles belong to deployment, not to the
    latency distribution a capacity claim rests on."""
    seen = set()
    for shared in (False, True):
        for chars in cfg["prompt_chars_choices"]:
            key = (shared, chars)
            if key in seen:
                continue
            seen.add(key)
            body = {
                "messages": (
                    [{"role": "system",
                      "content": cfg["shared_prefixes"][0]}]
                    if shared and cfg["shared_prefixes"] else []
                ) + [{
                    "role": "user",
                    "content": "warmup: " + filler_text(rng, chars),
                }],
                "max_tokens": max(cfg["max_tokens_choices"]),
                "stream": True,
                "stream_options": {"include_usage": True},
            }
            send_stream(base, body, cfg["request_timeout"])


# ---------------------------------------------------------------------------
# main
# ---------------------------------------------------------------------------


def run(argv=None) -> dict:
    ap = argparse.ArgumentParser(
        description="open-loop load/SLO capacity harness "
        "(see module docstring)"
    )
    ap.add_argument("--base-url", default=None,
                    help="target server; omitted = boot a tiny CPU "
                    "server in-process")
    ap.add_argument("--rates", default="1,2,4,8",
                    help="comma-separated offered loads (req/s), "
                    "swept in order")
    ap.add_argument("--duration", type=float, default=15.0,
                    help="arrival window per stage (s)")
    ap.add_argument("--drain-s", type=float, default=60.0,
                    help="max wait for stragglers after each stage")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--max-tokens-choices", default="8,16,32")
    ap.add_argument("--prompt-chars-choices", default="48,128")
    ap.add_argument("--shared-prefix-frac", type=float, default=0.5,
                    help="fraction of requests carrying a shared "
                    "system prompt (exercises the prefix cache)")
    ap.add_argument("--shared-prefix-count", type=int, default=2)
    ap.add_argument("--shared-prefix-chars", type=int, default=200)
    ap.add_argument("--slo-ttft", type=float, default=30.0,
                    help="client goodput SLO: TTFT bound (s)")
    ap.add_argument("--slo-per-token", type=float, default=None,
                    help="client goodput SLO: per-token latency bound")
    ap.add_argument("--server-ttft-slo", type=float, default=30.0,
                    help="self-boot server's --ttft-slo (detector arm)")
    ap.add_argument("--server-queue-depth-slo", type=int, default=16,
                    help="self-boot server's --queue-depth-slo")
    ap.add_argument("--knee-good-frac", type=float, default=0.9,
                    help="a stage below the knee must meet the SLO for "
                    "at least this request fraction")
    ap.add_argument("--speculate", type=int, default=0, metavar="K",
                    help="self-booted server only: serve with the "
                    "speculative ragged engine (--ragged --speculate K "
                    "semantics); the per-stage speculation block then "
                    "reports accepted-tokens/step and draft economics")
    ap.add_argument("--kv-dtype", choices=["bf16", "int8"],
                    default="bf16",
                    help="self-booted server: paged KV pool storage "
                    "format (int8 = quantized pages with per-page "
                    "scales — ~2x resident KV tokens per page budget). "
                    "Stamped into the report's provenance; "
                    "bench_compare REFUSES cross-dtype diffs.")
    ap.add_argument("--host-cache-bytes", type=int, default=0,
                    help="self-booted server: host-RAM prefix-cache "
                    "spill tier budget in bytes (0 = off); the "
                    "per-stage memory block then carries host-tier "
                    "rows (spilled pages, reload hit economics)")
    ap.add_argument("--journal", default=None, metavar="PATH",
                    help="single self-booted server only: arm the "
                    "engine decision journal at PATH (serve/journal.py) "
                    "— the sweep's decision stream lands as a "
                    "replayable artifact (scripts/replay_journal.py) "
                    "and the journal provenance (armed, path, entry "
                    "count) is stamped into the report's config block")
    ap.add_argument("--profile-sample-every", type=int, default=0,
                    metavar="N",
                    help="self-booted server only: arm the sampled "
                    "device-time attributor (every N engine steps one "
                    "dispatch is profiled; feeds the per-stage memory "
                    "block's device-time split). Router fleets keep it "
                    "off per replica — jax's profiler is "
                    "process-global")
    ap.add_argument("--request-timeout", type=float, default=300.0)
    ap.add_argument("--max-inflight", type=int, default=256)
    ap.add_argument("--out", default="BENCH_loadgen.json",
                    help="report path ('' disables). The default "
                    "deliberately refreshes the tracked artifact: "
                    "every PR's gate re-runs the same seeded sweep "
                    "and commits the new capacity point, which is the "
                    "regression-diff workflow (docs/OBSERVABILITY.md)")
    ap.add_argument("--gate", action="store_true",
                    help="exit nonzero when the gate fails (implied "
                    "by --smoke)")
    ap.add_argument("--smoke", action="store_true",
                    help="CI mode: tiny self-boot server, short sweep, "
                    "hard gate + schema + cost-ledger audit")
    ap.add_argument("--router", type=int, default=0, metavar="N",
                    help="multi-replica mode: boot N tiny replicas "
                    "behind a prefix-affinity router (serve/router.py) "
                    "and sweep THROUGH the router; the report gains "
                    "per-stage per-replica goodput splits, affinity "
                    "hit rate, and router-level retry/503 "
                    "classification (self-boot only)")
    ap.add_argument("--gate-vs-single", action="store_true",
                    help="router mode: fail the gate unless the "
                    "router sweep's knee sits at STRICTLY higher "
                    "offered load than the single-replica knee "
                    "recorded in the pre-existing --out report "
                    "(meaningful on multi-core hosts; N replicas on "
                    "one core share it)")
    args = ap.parse_args(argv)
    if args.router and args.base_url:
        ap.error("--router self-boots a fleet; drop --base-url")
    if args.gate_vs_single and not args.router:
        ap.error("--gate-vs-single only applies to --router sweeps")
    if args.journal and (args.router or args.base_url):
        # One journal file per scheduler: a fleet would collide on the
        # path, and a remote target's journal lives on its own disk.
        ap.error("--journal applies to the single self-booted server")
    if args.smoke:
        args.base_url = None
        args.rates = "1,4"
        args.duration = 5.0
        args.drain_s = 60.0
        args.max_tokens_choices = "4,6"
        args.prompt_chars_choices = "32,64"
        args.gate = True
        if not args.router:
            # The smoke's committed artifact must carry a real
            # device-time split (the memory block's acceptance bar);
            # every 5th engine step is cheap on the tiny geometry.
            args.profile_sample_every = args.profile_sample_every or 5
        if args.router:
            # The router smoke is the AFFINITY gate: emphasize the
            # shared-prefix mix so the >0.5 hit-rate bar measures
            # routing quality, not the unique-prompt fraction (a
            # fully-unique request can never affinity-hit).
            args.shared_prefix_frac = 0.75

    rates = [float(r) for r in args.rates.split(",") if r.strip()]
    rng = random.Random(args.seed)
    shared_rng = random.Random(args.seed + 1)
    cfg = {
        "duration": args.duration,
        "drain_s": args.drain_s,
        "request_timeout": args.request_timeout,
        "max_inflight": args.max_inflight,
        "slo_ttft": args.slo_ttft,
        "slo_per_token": args.slo_per_token,
        "max_tokens_choices": [
            int(x) for x in args.max_tokens_choices.split(",")
        ],
        "prompt_chars_choices": [
            int(x) for x in args.prompt_chars_choices.split(",")
        ],
        "shared_prefix_frac": args.shared_prefix_frac,
        "shared_prefixes": [
            filler_text(shared_rng, args.shared_prefix_chars)
            for _ in range(args.shared_prefix_count)
        ],
    }

    srv = None
    fleet: list = []
    rsrv = None
    replica_bases: dict[str, str] | None = None
    base = args.base_url
    self_booted = base is None
    # Router mode compares against the PRIOR single-replica report at
    # --out (the same seeded sweep the single smoke just wrote): its
    # knee becomes the baseline the multi-replica knee must beat.
    single_baseline = None
    if args.router and args.out and os.path.exists(args.out):
        try:
            with open(args.out) as f:
                prior = json.load(f)
            if not (prior.get("config") or {}).get("router_replicas"):
                single_baseline = {
                    "knee": prior.get("knee"),
                    "rates_rps": (prior.get("config") or {}).get(
                        "rates_rps"
                    ),
                }
        except (OSError, ValueError):
            single_baseline = None
    try:
        if args.router:
            fleet, rsrv, base, replica_bases = boot_tiny_fleet(
                args, args.router
            )
        elif self_booted:
            srv, base = boot_tiny_server(args, journal_path=args.journal)
        warmup(base, cfg, random.Random(args.seed + 2))
        if replica_bases:
            # The affinity router concentrates the warmup on one
            # replica; touch every OTHER engine once directly so no
            # replica meets its first request mid-measurement. (The
            # XLA programs are already compiled — tiny replicas share
            # one process-wide jit cache — this warms each engine
            # thread's first-admission path.)
            for rb in replica_bases.values():
                send_stream(rb, {
                    "messages": [{"role": "user", "content": "warm"}],
                    "max_tokens": 2, "stream": True,
                }, cfg["request_timeout"])
        stages = []
        stragglers: list = []  # live threads from earlier stages
        for rate in rates:
            print(f"stage: offered {rate:g} req/s for "
                  f"{args.duration:g}s ...", file=sys.stderr)
            st = run_stage(
                base, rate, cfg, rng, carryover=stragglers,
                replicas=replica_bases, router=bool(args.router),
            )
            print(
                f"  sent={st['sent']} ok={st['ok']} "
                f"good_frac={st['slo_good_frac']} "
                f"goodput={st['goodput_tps']} tok/s "
                f"ttft_p99={st['ttft_s']['p99']}", file=sys.stderr,
            )
            stages.append(st)
        knee = find_knee(stages, args.knee_good_frac)
        # Provenance stamps (scripts/bench_compare.py refuses
        # comparisons across any of these): the git revision this run
        # measured, the backend class (a cpu self-boot is a labeled
        # cpu_proxy run — never comparable against a TPU baseline),
        # the target's own
        # build_info identity, and the engine flags in effect.
        import jax

        from oryx_tpu.serve.api_server import _git_revision

        scrape = scrape_metrics(base)
        server_build = (
            build_info_labels(scrape, "oryx_serving_build_info")
            or build_info_labels(scrape, "oryx_router_build_info")
        )
        # Pool-geometry provenance: the memory blocks are only
        # comparable across runs serving from the SAME pool shape —
        # scripts/bench_compare.py refuses a drifted geometry instead
        # of diffing page counts across different pools.
        pool_probe = fetch_pages_summary(
            next(iter(replica_bases.values())) if replica_bases
            else base
        )
        pool_geom = {
            "num_pages": pool_probe.get("num_pages"),
            "page_size": pool_probe.get("page_size"),
            # Device bytes of the whole KV pool (codes + scales on a
            # quantized pool): pages are token-granular and
            # dtype-blind, so THIS is the unit --kv-dtype int8
            # halves at identical geometry-in-tokens.
            "kv_pool_bytes": pool_probe.get("kv_pool_bytes"),
        }
        # End-of-sweep zero-leak audit (self-booted targets only —
        # a remote server's quiescence is unknowable from here): with
        # every stage drained, no slot may still hold pages and the
        # free list plus the prefix cache's references must cover the
        # whole pool.
        # Decision-journal provenance: when --journal armed the flight
        # recorder, the sweep's decision stream is itself an artifact
        # (scripts/replay_journal.py replays it offline) — record
        # where it landed and how many decisions it carries so the
        # capacity number stays re-derivable. Unarmed/remote/router
        # runs stamp armed=false / null honestly.
        journal_prov = None
        if not args.base_url and not args.router:
            try:
                with urllib.request.urlopen(
                    base + "/debug/journal?n=0", timeout=30
                ) as r:
                    jbody = json.load(r)
                journal_prov = {
                    "armed": bool(jbody.get("armed")),
                    "path": jbody.get("path"),
                    "entries": jbody.get("total"),
                }
            except Exception as e:
                journal_prov = {"error": f"{type(e).__name__}: {e}"}
        memory_audit = None
        if not args.base_url:
            memory_audit = {}
            targets = replica_bases or {"self": base}
            for rid, b in sorted(targets.items()):
                s = fetch_pages_summary(b).get("summary") or {}
                memory_audit[rid] = {
                    **{k: s.get(k) for k in (
                        "num_pages", "free", "slot", "cache", "shared",
                        "reconciled",
                    )},
                    "leaked": not (
                        s.get("reconciled")
                        and s.get("slot") == 0
                        and s.get("shared") == 0
                        and (s.get("free", 0) + s.get("cache", 0)
                             == s.get("num_pages"))
                    ),
                }
        if args.base_url:
            backend = "remote"
            # A remote target's engine flags are unknowable from the
            # client side — stamping the harness's own (unused) flags
            # would let bench_compare diff across a server config
            # change instead of refusing. Null = honestly unknown;
            # server_build carries what the target self-declares.
            speculate = ragged = None
        else:
            backend = jax.default_backend()
            if backend != "tpu":
                backend = "cpu_proxy"
            speculate = args.speculate or 0
            ragged = bool(args.speculate)
        report = {
            "bench": "loadgen",
            "config": {
                "gated": bool(args.gate),
                "git_rev": _git_revision(),
                "backend": backend,
                "server_build": server_build,
                "engine": {
                    "engine": server_build.get("engine"),
                    "ragged": ragged,
                    "speculate": speculate,
                    "router_replicas": args.router or None,
                },
                "base_url": args.base_url or (
                    f"self-boot router x{args.router} (cpu)"
                    if args.router else "self-boot tiny (cpu)"
                ),
                "rates_rps": rates,
                "duration_s": args.duration,
                "seed": args.seed,
                "slo_ttft_s": args.slo_ttft,
                "slo_per_token_s": args.slo_per_token,
                "knee_good_frac": args.knee_good_frac,
                "max_tokens_choices": cfg["max_tokens_choices"],
                "prompt_chars_choices": cfg["prompt_chars_choices"],
                "shared_prefix_frac": args.shared_prefix_frac,
                "shared_prefix_chars": args.shared_prefix_chars,
                "smoke": args.smoke,
                "router_replicas": args.router or None,
                "pool": pool_geom,
                # KV-pool wire format + host-tier geometry provenance:
                # page counts from pools storing different bytes per
                # token are category errors (a remote target's format
                # is unknowable from here -> null, like the engine
                # flags above).
                "kv_dtype": (
                    None if args.base_url else args.kv_dtype
                ),
                "host_cache_bytes": (
                    None if args.base_url else args.host_cache_bytes
                ),
                # The EFFECTIVE cadence: router fleets boot every
                # replica with sampling off (jax's profiler is
                # process-global), so stamping the CLI value would
                # false-fail the armed-but-no-windows gate bar and
                # mis-key bench_compare's provenance refusal.
                "profile_sample_every": (
                    None if args.base_url
                    else 0 if args.router
                    else args.profile_sample_every
                ),
                # Flight-recorder provenance (NOT a comparability key:
                # journaling observes, never perturbs — CI-gated).
                "journal": journal_prov,
            },
            "stages": stages,
            "knee": knee,
            "gate": {},
            "memory_audit": memory_audit,
        }
        if args.router and single_baseline is not None:
            report["single_baseline"] = single_baseline
        # Cost-ledger audit rides the same server session (the flight
        # recorder still holds the sweep's requests; the router merges
        # its replicas').
        ledger_problems = check_cost_ledger(base)
        report["gate"] = evaluate_gate(
            report, ledger_problems=ledger_problems,
            require_affinity=0.5
            if args.router and args.shared_prefix_frac >= 0.5 else None,
            vs_single=args.gate_vs_single,
            check_memory=not args.base_url,
        )
    finally:
        if rsrv is not None:
            rsrv.stop_prober()
        for s in fleet:
            if s.scheduler is not None:
                s.scheduler.close()
            s.shutdown()
        if rsrv is not None:
            rsrv.shutdown()
        if srv is not None:
            if srv.scheduler is not None:
                srv.scheduler.close()
            srv.shutdown()
    if args.out:
        with open(args.out, "w") as f:
            json.dump(report, f, indent=2)
    return report


def main(argv=None) -> None:
    report = run(argv)
    print(json.dumps(report, indent=2))
    gate = report["gate"]
    if report["config"]["gated"] and not gate["passed"]:
        for r in gate["reasons"]:
            print(f"FAIL: {r}", file=sys.stderr)
        raise SystemExit(1)


if __name__ == "__main__":
    main()
