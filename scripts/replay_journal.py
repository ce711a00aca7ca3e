#!/usr/bin/env python3
"""Offline incident replay for the engine decision journal.

Reads a journal captured with ``--journal PATH`` (the flight recorder,
oryx_tpu/serve/journal.py), rebuilds a COLD scheduler from the header's
flags/seed/pool geometry, feeds the journaled admission stream at its
recorded step gates, and asserts the incident reproduces bit-for-bit:

  * byte-identical reply tokens per request (the finish entries'
    reply/token fingerprints),
  * decision-for-decision stream equality over REPLAYED_KINDS
    (admit/splice/evict/step/fault/restart/finish),
  * cost-ledger equality (the DETERMINISTIC_COST_KEYS subset).

On mismatch it prints a first-divergence report — seq, decision kind,
the first differing field, both values — and exits 2. By contract
(docs/OBSERVABILITY.md "Incident replay") submit arrival, admission-
control rejects and degraded transitions are timing-coupled and NOT
compared; live cancellations and deadline expiries are likewise
load-coupled and will legitimately diverge.

What-if mode: ``--override k=v,...`` replays the IDENTICAL workload
under altered flags (kv_dtype, prefill_chunk, speculate,
host_cache_bytes, ...) and emits a bench_compare-style cost/goodput
diff table instead of asserting equality — a counterfactual ("would
int8 KV have avoided the eviction storm?") from one captured window.

Usage::

    python scripts/replay_journal.py /tmp/journal.jsonl
    python scripts/replay_journal.py /tmp/journal.jsonl \
        --override kv_dtype=int8,prefill_chunk=16 --out whatif.json

The default pipeline is the tiny self-test model every smoke harness
uses (oryx_tiny + the ord tokenizer — chaos_suite, loadgen, the test
suite); pass --model-path/--shard to replay a journal captured against
a real checkpoint. The pipeline must match the capturing server or the
reply fingerprints cannot reproduce.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from collections import deque
from pathlib import Path
from typing import Any

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "scripts"))

import bench_compare  # noqa: E402
from oryx_tpu.serve import journal as journal_lib  # noqa: E402
from oryx_tpu.utils import faults  # noqa: E402

# Per-entry fields excluded from the decision-for-decision comparison:
# `seq` is a global counter shared with the non-replayed kinds (submit/
# reject/degraded interleave differently by contract) and `ts_unix_s`
# is wall clock.
VOLATILE_FIELDS = ("seq", "ts_unix_s")

# Header-config keys that are ContinuousScheduler constructor kwargs,
# in constructor spelling — the cold-rebuild set, and (plus faults_spec)
# the --override whitelist.
GEOMETRY_KEYS = (
    "num_slots", "page_size", "chunk", "max_ctx", "num_pages", "seed",
    "prefill_chunk", "prefix_cache", "ragged", "speculate", "kv_dtype",
    "host_cache_bytes", "degraded_clamp_tokens",
)
OVERRIDE_KEYS = GEOMETRY_KEYS + ("faults_spec",)

WHATIF_SCHEMA = 1
WHATIF_ROW_KEYS = (
    "series", "baseline", "current", "direction", "rel_tol", "verdict",
    "note",
)


class _CharTokenizer:
    """Byte-compatible with chaos_suite._Tokenizer / loadgen
    ._CharTokenizer / the test suite's FakeTokenizer: replaying a
    journal captured by any of them reproduces the exact token ids."""

    def encode(self, text, add_special_tokens=False):
        return [min(ord(c), 500) for c in text]

    def decode(self, ids, skip_special_tokens=True):
        return "".join(chr(i) for i in ids if 0 < i < 500)


def build_tiny_pipe():
    import jax

    from oryx_tpu import config as cfg_lib
    from oryx_tpu.models import oryx
    from oryx_tpu.serve.pipeline import OryxInference

    cfg = cfg_lib.oryx_tiny()
    params = oryx.init_params(cfg, jax.random.key(0))
    return OryxInference(_CharTokenizer(), params, cfg)


# ---------------------------------------------------------------------------
# Workload plan
# ---------------------------------------------------------------------------


def plan_feed(entries: list[dict[str, Any]]
              ) -> tuple[list[dict[str, Any]], list[tuple[str, str]]]:
    """The feed plan: one item per replayable submit, in arrival order.

    Each item carries the journaled payload/sampling/streaming, the
    EFFECTIVE max_new (the first admit entry's budget — the degraded
    clamp applied at the live queue head — falling back to the
    requested value), and `feed_step`: the engine step at which the
    live run first admitted it (validation rejects never admit; they
    gate on their finish step). Returns (plan, skipped) where skipped
    lists (request_id, reason) for submits replay cannot carry.
    """
    rejected = {
        e.get("request_id") for e in entries if e["kind"] == "reject"
    }
    first_admit: dict[str, dict[str, Any]] = {}
    finish_step: dict[str, int] = {}
    for e in entries:
        rid = e.get("request_id")
        if e["kind"] == "admit" and rid not in first_admit:
            first_admit[rid] = e
        elif e["kind"] == "finish" and rid not in finish_step:
            finish_step[rid] = int(e.get("step") or 0)
    submits = sorted(
        (e for e in entries if e["kind"] == "submit"),
        key=lambda e: e["arrival_seq"],
    )
    plan: list[dict[str, Any]] = []
    skipped: list[tuple[str, str]] = []
    for e in submits:
        rid = e["request_id"]
        if rid in rejected:
            skipped.append(
                (rid, "admission-control reject (timing-coupled, "
                      "excluded by contract)")
            )
            continue
        if e.get("prompt") is None:
            # Non-JSON payloads journal a fingerprint only (see
            # _journal_submit): the workload cannot be rebuilt.
            raise ValueError(
                f"request {rid} journaled a prompt fingerprint, not a "
                "payload (programmatic non-JSON submit): this journal "
                "is not replayable"
            )
        admit = first_admit.get(rid)
        if admit is None and rid not in finish_step:
            skipped.append(
                (rid, "no admit or finish entry (capture ended "
                      "mid-flight or the journal rotated past it)")
            )
            continue
        plan.append({
            "request_id": rid,
            "prompt": e["prompt"],
            "sampling": e.get("sampling") or {},
            "max_new": int(
                admit["max_new"] if admit is not None else e["max_new"]
            ),
            "streaming": bool(e.get("streaming")),
            "feed_step": int(
                admit["step"] if admit is not None else finish_step[rid]
            ),
        })
    return plan, skipped


# ---------------------------------------------------------------------------
# Replay run
# ---------------------------------------------------------------------------


def run_replay(header: dict[str, Any], entries: list[dict[str, Any]], *,
               pipe=None, overrides: dict[str, Any] | None = None,
               timeout_s: float = 300.0) -> dict[str, Any]:
    """Cold-rebuild the scheduler the header describes (plus override
    deltas), replay the journaled admission stream, and return
    {"entries": replay journal entries, "skipped", "feed_errors",
    "timed_out", "gave_up"}.

    The feeder runs on the engine thread at the top of every loop
    iteration (scheduler.replay_feeder): it submits pending requests
    once `steps_run` reaches their recorded gate — or, under overrides
    that finish the resident work in fewer steps, once the engine is
    fully idle (the anti-hang fallback; in faithful replay an idle
    engine has by construction already reached the next gate, because
    the step clock only advances on dispatches).
    """
    from oryx_tpu.serve.api_server import EngineSupervisor
    from oryx_tpu.serve.scheduler import ContinuousScheduler

    cfg = dict(header.get("config") or {})
    # A journal is input from outside the program: one recorded under
    # the K-step megastep (a header with fuse_steps other than 1, or
    # step entries stamped fused_k) would replay into a divergence with
    # no name, so it is refused by name.
    if cfg.get("fuse_steps", 1) != 1 or any(
        "fused_k" in e for e in entries if e.get("kind") == "step"
    ):
        raise ValueError(
            "this journal was recorded with --fuse-steps "
            f"(fuse_steps={cfg.get('fuse_steps', 1)!r} in its header, or "
            "fused_k on its step entries), which this tree no longer "
            "has; replay it on the tree that recorded it"
        )
    if overrides:
        cfg.update(overrides)
    plan, skipped = plan_feed(entries)
    if pipe is None:
        pipe = build_tiny_pipe()
    kw = {k: cfg[k] for k in GEOMETRY_KEYS if k in cfg}
    # The draft model is part of the recorded machine: its source spec
    # (an init:V:D:W:SEED string or a checkpoint path) is stamped in the
    # header, and device-side speculation replays bit-for-bit only with
    # the same weights.
    drafter = None
    if cfg.get("draft_model"):
        from oryx_tpu.models import generate as generate_lib

        drafter = generate_lib.NeuralDrafter.from_spec(cfg["draft_model"])
        kw["drafter"] = drafter
    journal = journal_lib.DecisionJournal(
        None, keep=max(4096, 4 * len(entries) + 8 * len(plan)),
    )
    # The seeded fault schedule is part of the recorded configuration:
    # arm it before construction so hit counts start from zero exactly
    # as the live process's did.
    faults.configure(cfg.get("faults_spec") or None)
    sched = ContinuousScheduler(
        pipe, autostart=False, journal=journal,
        engine_label=str(cfg.get("engine") or "continuous"),
        replica_id=cfg.get("replica"),
        # No max_queue / timeouts / SLO watchers: admission control,
        # deadlines and the degraded ladder are timing-coupled and
        # excluded from replay by contract.
        **kw,
    )

    pending = deque(plan)
    handles: dict[str, Any] = {}
    feed_errors: list[tuple[str, str]] = []

    def feeder(s) -> None:
        while pending:
            item = pending[0]
            if s.steps_run < item["feed_step"]:
                idle = s.queue_len() == 0 and all(
                    r is None for r in s.slots
                )
                if not idle:
                    return
            pending.popleft()
            try:
                handles[item["request_id"]] = s.submit(
                    item["prompt"], item["max_new"], item["sampling"],
                    streaming=item["streaming"],
                    request_id=item["request_id"],
                )
            except Exception as e:  # AdmissionRejected under overrides
                feed_errors.append(
                    (item["request_id"], f"{type(e).__name__}: {e}")
                )

    sched.replay_feeder = feeder
    sched.start()
    # The supervisor is part of the recorded machine: a journaled
    # engine_crash fault must revive and restart-replay exactly as the
    # live supervisor did. Tight poll — replay has no SLO to protect.
    sup = EngineSupervisor(sched, poll_s=0.05)
    sup.start()
    timed_out = False
    try:
        deadline = time.monotonic() + timeout_s
        while pending or not all(
            h.done.is_set() for h in handles.values()
        ):
            if sup.gave_up:
                break
            if time.monotonic() > deadline:
                timed_out = True
                break
            time.sleep(0.02)
    finally:
        gave_up = sup.gave_up
        sup.stop()
        sched.close()
        faults.configure(None)
    return {
        "entries": journal.snapshot(),
        "skipped": skipped,
        "feed_errors": feed_errors,
        "timed_out": timed_out,
        "gave_up": gave_up,
    }


# ---------------------------------------------------------------------------
# Comparison
# ---------------------------------------------------------------------------


def replayed_stream(entries: list[dict[str, Any]]
                    ) -> list[tuple[int | None, dict[str, Any]]]:
    """The comparison view of a journal: REPLAYED_KINDS only, volatile
    fields dropped, as (original seq, cleaned entry) pairs (the seq
    rides along for the divergence report only)."""
    out = []
    for e in entries:
        if e.get("kind") not in journal_lib.REPLAYED_KINDS:
            continue
        clean = {k: v for k, v in e.items() if k not in VOLATILE_FIELDS}
        out.append((e.get("seq"), clean))
    return out


def first_divergence(live_entries: list[dict[str, Any]],
                     replay_entries: list[dict[str, Any]]
                     ) -> dict[str, Any] | None:
    """None when the two decision streams are equal; else the first
    point of divergence: {index (into the replayed stream), seq (the
    LIVE journal's), kind, field, live, replay}. A stream ending early
    reports field "<missing>" with the absent side None."""
    live = replayed_stream(live_entries)
    rep = replayed_stream(replay_entries)
    for i in range(min(len(live), len(rep))):
        lseq, a = live[i]
        _, b = rep[i]
        if a == b:
            continue
        if a.get("kind") != b.get("kind"):
            field = "kind"
        else:
            field = next(
                k for k in sorted(set(a) | set(b))
                if a.get(k) != b.get(k)
            )
        return {
            "index": i, "seq": lseq, "kind": a.get("kind"),
            "field": field, "live": a.get(field), "replay": b.get(field),
        }
    if len(live) != len(rep):
        i = min(len(live), len(rep))
        seq, e = (live[i] if len(live) > len(rep) else rep[i])
        return {
            "index": i,
            "seq": seq if len(live) > len(rep) else None,
            "kind": e.get("kind"), "field": "<missing>",
            "live": e if len(live) > len(rep) else None,
            "replay": e if len(rep) > len(live) else None,
        }
    return None


def _finishes(entries: list[dict[str, Any]]) -> dict[str, dict[str, Any]]:
    return {
        e["request_id"]: e for e in entries if e["kind"] == "finish"
    }


def reply_match(live_entries: list[dict[str, Any]],
                replay_entries: list[dict[str, Any]]
                ) -> tuple[int, int, list[str]]:
    """(matched, total, mismatched request ids) over the live finish
    entries' reply-bytes + token-stream fingerprints."""
    live, rep = _finishes(live_entries), _finishes(replay_entries)
    bad = [
        rid for rid, e in live.items()
        if (r := rep.get(rid)) is None
        or r.get("reply_sha256") != e.get("reply_sha256")
        or r.get("tokens_sha256") != e.get("tokens_sha256")
    ]
    return len(live) - len(bad), len(live), sorted(bad)


# ---------------------------------------------------------------------------
# What-if diffing
# ---------------------------------------------------------------------------


def summarize(entries: list[dict[str, Any]]) -> dict[str, Any]:
    """Aggregate cost/goodput view of one journal — the quantities the
    what-if diff table compares."""
    fin = [e for e in entries if e["kind"] == "finish"]
    steps = [e for e in entries if e["kind"] == "step"]
    cost = {
        k: sum((e.get("cost") or {}).get(k) or 0 for e in fin)
        for k in journal_lib.DETERMINISTIC_COST_KEYS
    }
    dispatches = len(steps)
    return {
        "requests_finished": len(fin),
        "requests_ok": sum(1 for e in fin if e.get("status") == "ok"),
        "completion_tokens": sum(
            e.get("completion_tokens") or 0 for e in fin
        ),
        **{f"{k}_total": v for k, v in cost.items()},
        "peak_pages_max": max(
            ((e.get("cost") or {}).get("peak_pages") or 0 for e in fin),
            default=0,
        ),
        "dispatches": dispatches,
        "evictions": sum(1 for e in entries if e["kind"] == "evict"),
        "splices": sum(1 for e in entries if e["kind"] == "splice"),
        "spliced_tokens": sum(
            e.get("spliced_tokens") or 0
            for e in entries if e["kind"] == "splice"
        ),
        "faults": sum(1 for e in entries if e["kind"] == "fault"),
        "restarts": sum(1 for e in entries if e["kind"] == "restart"),
        "tokens_per_dispatch": (
            cost["decode_tokens"] / dispatches if dispatches else 0.0
        ),
        "accepted_per_dispatch": (
            sum(e.get("accepted_tokens") or 0 for e in steps)
            / dispatches if dispatches else 0.0
        ),
    }


# (series, direction, rel_tol): the diff table's shape. Goodput rows
# judge "higher is better", resource rows "lower", workload-identity
# rows are informational (the what-if replays the same requests, but
# overrides may legitimately change completion under faults).
_WHATIF_SERIES = (
    ("requests_finished", "info", 0.0),
    ("requests_ok", "info", 0.0),
    ("completion_tokens", "info", 0.0),
    ("decode_tokens_total", "info", 0.0),
    ("prefill_tokens_total", "info", 0.0),
    ("cached_tokens_total", "higher", 0.05),
    ("spliced_tokens", "higher", 0.05),
    ("decode_steps_total", "lower", 0.05),
    ("dispatches", "lower", 0.05),
    ("tokens_per_dispatch", "higher", 0.05),
    ("accepted_per_dispatch", "higher", 0.05),
    ("peak_pages_max", "lower", 0.05),
    ("evictions", "lower", 0.0),
    ("splices", "info", 0.0),
    ("faults", "info", 0.0),
    ("restarts", "lower", 0.0),
)


def whatif_rows(live_entries: list[dict[str, Any]],
                replay_entries: list[dict[str, Any]]
                ) -> list[dict[str, Any]]:
    """bench_compare-idiom rows (baseline = the live journal, current =
    the overridden replay), judged with bench_compare's own verdict
    logic so "improved"/"regression" mean exactly what the perf gates
    mean."""
    base, cur = summarize(live_entries), summarize(replay_entries)
    matched, total, _ = reply_match(live_entries, replay_entries)
    rows = []
    for series, direction, tol in _WHATIF_SERIES:
        row = bench_compare._judge(bench_compare.Row(
            series=series, baseline=base[series], current=cur[series],
            direction=direction, rel_tol=tol,
        ))
        rows.append(vars(row))
    rows.append(vars(bench_compare.Row(
        series="reply_bytes_identical",
        baseline=f"{total}/{total}", current=f"{matched}/{total}",
        direction="info", rel_tol=0.0, verdict="info",
        note="overrides may legally change sampling numerics",
    )))
    return rows


def validate_whatif_report(report: dict[str, Any]) -> list[str]:
    """Schema check for the --out what-if report; [] when valid."""
    problems = []
    for key in ("bench", "schema", "journal", "overrides", "rows",
                "baseline", "current"):
        if key not in report:
            problems.append(f"missing key {key!r}")
    if report.get("bench") != "replay_whatif":
        problems.append("bench != 'replay_whatif'")
    if report.get("schema") != WHATIF_SCHEMA:
        problems.append(f"schema != {WHATIF_SCHEMA}")
    for i, row in enumerate(report.get("rows") or []):
        missing = [k for k in WHATIF_ROW_KEYS if k not in row]
        if missing:
            problems.append(f"row {i} missing {missing}")
    if not report.get("rows"):
        problems.append("empty rows")
    return problems


def print_diff_table(rows: list[dict[str, Any]]) -> None:
    w = 58

    def fmt(v):
        if isinstance(v, bool):
            return str(v)
        if isinstance(v, (int, float)):
            return f"{v:g}"
        return "-" if v is None else str(v)

    print(f"{'series':<{w}} {'baseline':>12} {'current':>12} "
          f"{'tol':>6}  verdict")
    print("-" * (w + 42))
    for r in rows:
        print(f"{r['series'][:w]:<{w}} {fmt(r['baseline']):>12} "
              f"{fmt(r['current']):>12} {r['rel_tol']:>6g}  "
              f"{r['verdict'].upper()}"
              + (f" ({r['note']})" if r.get("note") else ""))


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------


def parse_overrides(spec: str, base: dict[str, Any]) -> dict[str, Any]:
    """`k=v,k=v` against the OVERRIDE_KEYS whitelist, coercing each
    value to the header field's type (the header is the source of truth
    for what e.g. prefill_chunk *is*)."""
    out: dict[str, Any] = {}
    for part in spec.split(","):
        part = part.strip()
        if not part:
            continue
        key, eq, val = part.partition("=")
        key, val = key.strip(), val.strip()
        if not eq or key not in OVERRIDE_KEYS:
            raise SystemExit(
                f"unknown override {key!r} (allowed: "
                + ", ".join(OVERRIDE_KEYS) + ")"
            )
        out[key] = _coerce(val, base.get(key))
    return out


def _coerce(val: str, current: Any) -> Any:
    low = val.lower()
    if low in ("none", "null", ""):
        return None
    if isinstance(current, bool) or low in ("true", "false"):
        return low in ("1", "true", "yes", "on")
    try:
        return int(val)
    except ValueError:
        return val


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter,
    )
    ap.add_argument("journal", help="journal file from --journal PATH "
                    "(a rotated PATH.1 sibling is merged automatically)")
    ap.add_argument("--override", default=None, metavar="K=V[,K=V...]",
                    help="what-if mode: replay under altered flags "
                    "and diff cost/goodput instead of asserting "
                    "equality (keys: " + ", ".join(OVERRIDE_KEYS) + ")")
    ap.add_argument("--model-path", default=None,
                    help="replay against a real checkpoint "
                    "(default: the tiny self-test pipeline)")
    ap.add_argument("--shard", default=None,
                    help="shard spec for --model-path (e.g. tp=8)")
    ap.add_argument("--timeout", type=float, default=300.0,
                    help="replay wall-clock budget in seconds")
    ap.add_argument("--out", default=None,
                    help="write the replay/what-if report JSON here")
    args = ap.parse_args(argv)

    header, entries = journal_lib.read_journal(args.journal)
    cfg = header.get("config") or {}
    print(f"journal: {args.journal}")
    print(f"  schema {header.get('schema')}  model "
          f"{cfg.get('model')!r}  engine {cfg.get('engine')!r}  "
          f"entries {len(entries)}")
    print("  geometry: " + " ".join(
        f"{k}={cfg.get(k)}" for k in GEOMETRY_KEYS if k in cfg
    ))
    if cfg.get("faults_spec"):
        print(f"  faults: {cfg['faults_spec']}")

    pipe = None
    if args.model_path:
        from oryx_tpu.serve.builder import load_pipeline

        pipe = load_pipeline(args.model_path, shard=args.shard)

    overrides = (
        parse_overrides(args.override, cfg) if args.override else None
    )
    if overrides:
        print("  overrides: " + " ".join(
            f"{k}={v}" for k, v in overrides.items()
        ))
    result = run_replay(
        header, entries, pipe=pipe, overrides=overrides,
        timeout_s=args.timeout,
    )
    for rid, why in result["skipped"]:
        print(f"  skipped {rid}: {why}")
    for rid, err in result["feed_errors"]:
        print(f"  feed error {rid}: {err}")
    if result["timed_out"]:
        print(f"REPLAY TIMED OUT after {args.timeout:g}s", file=sys.stderr)
    if result["gave_up"]:
        print("REPLAY SUPERVISOR GAVE UP (crash loop)", file=sys.stderr)

    if overrides:
        rows = whatif_rows(entries, result["entries"])
        print()
        print_diff_table(rows)
        report = {
            "bench": "replay_whatif", "schema": WHATIF_SCHEMA,
            "journal": str(args.journal), "overrides": overrides,
            "baseline": summarize(entries),
            "current": summarize(result["entries"]),
            "rows": rows,
            "skipped": result["skipped"],
            "feed_errors": result["feed_errors"],
        }
        problems = validate_whatif_report(report)
        if problems:
            print("INTERNAL: invalid what-if report: "
                  + "; ".join(problems), file=sys.stderr)
            return 2
        if args.out:
            Path(args.out).write_text(json.dumps(report, indent=2))
            print(f"\nwrote {args.out}")
        return 1 if (result["timed_out"] or result["gave_up"]) else 0

    div = first_divergence(entries, result["entries"])
    matched, total, bad = reply_match(entries, result["entries"])
    n_live = len(replayed_stream(entries))
    print(f"\nreplayed decisions: {n_live} live vs "
          f"{len(replayed_stream(result['entries']))} replay")
    print(f"reply bytes identical: {matched}/{total}"
          + (f"  (mismatched: {', '.join(bad)})" if bad else ""))
    if args.out:
        Path(args.out).write_text(json.dumps({
            "bench": "replay_faithful", "schema": WHATIF_SCHEMA,
            "journal": str(args.journal),
            "replies_matched": matched, "replies_total": total,
            "divergence": div,
            "skipped": result["skipped"],
            "feed_errors": result["feed_errors"],
        }, indent=2))
        print(f"wrote {args.out}")
    if div is not None:
        print("\nFIRST DIVERGENCE:", file=sys.stderr)
        for k in ("index", "seq", "kind", "field", "live", "replay"):
            print(f"  {k:>7}: {div[k]!r}", file=sys.stderr)
        return 2
    if result["timed_out"] or result["gave_up"] or result["feed_errors"]:
        return 2
    print("\nREPLAY OK: byte-identical replies, "
          "decision-for-decision equal, cost ledgers equal")
    return 0


if __name__ == "__main__":
    sys.exit(main())
