#!/usr/bin/env python
"""Bench regression sentinel: diff the current bench artifacts against
committed baselines with noise-aware, per-metric-class tolerances.

The serving benches (`BENCH_loadgen.json` from scripts/loadgen.py,
`BENCH_paged_attention.json` from scripts/bench_paged_attention.py)
are regenerated by every CI run — they are the capacity/perf claims
this repo makes — but until this script nothing DIFFED them: a knee
that moved left or an accepted-tokens/step that collapsed sailed
through review as an innocuous JSON churn. This script is the gate:

  * every compared series belongs to a METRIC CLASS with its own
    tolerance — structural invariants (dispatches/step == 1.0, byte
    parity) tolerate nothing; workload-deterministic counters
    (accepted/step on the seeded fixture) tolerate a little;
    wall-clock-derived numbers (goodput, tokens/page-second) tolerate
    a lot, because shared CI cores are noisy;
  * comparisons are REFUSED — with the mismatched field named — when
    the two runs are not comparable: a cpu_proxy-labeled run never
    diffs against a TPU baseline, and a changed sweep config (rates,
    seed, workload mix, engine flags) is config drift to resolve, not
    a perf delta to report;
  * ``--gate`` (wired into scripts/check_tier1.sh after the loadgen
    smoke) exits nonzero on any regression or refusal, naming the
    offending series.

Baselines live in ``baselines/`` and are refreshed deliberately with
``--update-baselines`` when a perf change is REAL and accepted — the
same ratchet discipline as the tier-1 dots baseline.

    python scripts/bench_compare.py                # verdict table
    python scripts/bench_compare.py --gate         # CI gate
    python scripts/bench_compare.py --update-baselines
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import shutil
import sys
from typing import Any

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# (current artifact, committed baseline) pairs the sentinel watches.
PAIRS = (
    ("BENCH_loadgen.json", os.path.join("baselines", "BENCH_loadgen.json")),
    ("BENCH_paged_attention.json",
     os.path.join("baselines", "BENCH_paged_attention.json")),
)

# Metric-class tolerances (relative, on the "worse" direction only —
# improvements never fail). Chosen against the observed noise of the
# artifact, not wishes: structural invariants are exact; the seeded
# accepted/step fixture is token-deterministic (tolerance covers a
# fixture tweak riding along); anything divided by wall time on a
# shared CI core gets the wide band.
TOL_EXACT = 0.0        # dispatches/step, byte parity
TOL_DETERMINISTIC = 0.25   # accepted tokens/step, knee offered load*
TOL_WALL_CLOCK = 0.5   # goodput, tokens/page-second
# *knee offered load uses its own band: the ISSUE-12 acceptance bar is
# that a 20% knee regression is DETECTED, so the tolerance sits below.
TOL_KNEE = 0.1


@dataclasses.dataclass
class Row:
    """One compared series for the verdict table."""

    series: str
    baseline: Any
    current: Any
    direction: str  # "higher" | "lower" | "equal" | "info"
    rel_tol: float
    verdict: str = "ok"  # ok | improved | regression | info
    note: str = ""


def _judge(row: Row) -> Row:
    """Fill `verdict` from the values + direction + tolerance."""
    b, c = row.baseline, row.current
    if row.direction == "info" or b is None or c is None:
        row.verdict = "info"
        return row
    if row.direction == "equal":
        row.verdict = "ok" if c == b else "regression"
        return row
    b, c = float(b), float(c)
    if b == 0.0:
        # No baseline signal: only a strict worsening from zero in the
        # "lower is better" sense can regress.
        worse = c > 0 if row.direction == "lower" else False
        row.verdict = "regression" if worse else "ok"
        return row
    change = (c - b) / abs(b)
    if row.direction == "lower":
        change = -change
    # change > 0 = improved, < 0 = worse.
    if change < -row.rel_tol:
        row.verdict = "regression"
    elif change > row.rel_tol:
        row.verdict = "improved"
    else:
        row.verdict = "ok"
    return row


# ---------------------------------------------------------------------------
# Comparability (the refusal path)
# ---------------------------------------------------------------------------


def _dig(d: dict, path: str) -> Any:
    cur: Any = d
    for part in path.split("."):
        if not isinstance(cur, dict):
            return None
        cur = cur.get(part)
    return cur


def refuse_reason(current: dict, baseline: dict,
                  keys: tuple[str, ...],
                  backend_key: str) -> str | None:
    """None when the two runs are comparable; else one clear sentence
    naming what differs. The backend label gets its own wording — a
    cpu_proxy number against a TPU number is not a noisy diff, it is a
    category error."""
    cb = _dig(current, backend_key)
    bb = _dig(baseline, backend_key)
    if cb != bb:
        return (
            f"backend mismatch: refusing to diff a {cb!r} run against "
            f"a {bb!r} baseline (regenerate the baseline on the same "
            "backend class)"
        )
    drift = [
        k for k in keys if _dig(current, k) != _dig(baseline, k)
    ]
    if drift:
        return (
            "config drift on " + ", ".join(drift) + ": the runs are "
            "not comparable (re-record the baseline if the new config "
            "is deliberate)"
        )
    return None


# ---------------------------------------------------------------------------
# BENCH_loadgen.json
# ---------------------------------------------------------------------------

_LOADGEN_CONFIG_KEYS = (
    "config.rates_rps", "config.duration_s", "config.seed",
    "config.slo_ttft_s", "config.knee_good_frac",
    "config.max_tokens_choices", "config.prompt_chars_choices",
    "config.shared_prefix_frac", "config.router_replicas",
    "config.engine.engine", "config.engine.speculate",
    # Pool-geometry + sampler provenance: page counts from different
    # pool shapes (or device-time splits at different sampling
    # cadences) are category errors, not perf deltas — drift here is
    # a REFUSAL naming the field.
    "config.pool.num_pages", "config.pool.page_size",
    "config.profile_sample_every",
    # KV wire format + host-tier geometry: a bf16 run against an int8
    # baseline (or tiered against untiered) stores different bytes
    # per resident token — peak_pages/page-seconds diffs across that
    # line are the CLAIM a memory-economics PR makes deliberately,
    # never a regression signal; refuse with the field named.
    "config.kv_dtype", "config.host_cache_bytes",
)


def compare_loadgen(
    current: dict, baseline: dict
) -> tuple[list[Row], str | None]:
    """(rows, refusal). Gated series: the saturation knee (offered
    load), its goodput, and the capacity-economics headline
    goodput_tokens_per_page_second at the knee stage."""
    refusal = refuse_reason(
        current, baseline, _LOADGEN_CONFIG_KEYS, "config.backend"
    )
    if refusal:
        return [], refusal
    rows: list[Row] = []
    kb, kc = baseline.get("knee"), current.get("knee")
    if kb is None:
        rows.append(Row(
            "loadgen knee", None, None, "info", 0.0,
            note="baseline has no knee: nothing to gate",
        ))
        return [_judge(r) for r in rows], None
    if kc is None:
        rows.append(_judge(Row(
            "loadgen knee.offered_rps", kb.get("offered_rps"), 0.0,
            "higher", TOL_KNEE,
            note="current run saturated at the lowest offered load",
        )))
        return rows, None
    rows.append(Row(
        "loadgen knee.offered_rps",
        kb.get("offered_rps"), kc.get("offered_rps"),
        "higher", TOL_KNEE,
    ))
    rows.append(Row(
        "loadgen knee.goodput_tps",
        kb.get("goodput_tps"), kc.get("goodput_tps"),
        "higher", TOL_WALL_CLOCK,
    ))

    def knee_stage_cost(rep: dict, knee: dict) -> Any:
        stages = rep.get("stages") or []
        i = knee.get("index")
        if isinstance(i, int) and 0 <= i < len(stages):
            return (stages[i].get("cost") or {}).get(
                "goodput_tokens_per_page_second"
            )
        return None

    rows.append(Row(
        "loadgen knee-stage goodput_tokens_per_page_second",
        knee_stage_cost(baseline, kb), knee_stage_cost(current, kc),
        "higher", TOL_WALL_CLOCK,
    ))

    def spec_peak(rep: dict) -> Any:
        """Best accepted-tokens/step across the sweep's stages (peak,
        not mean: low-load stages dilute the signal). 0.0 stages count
        — a collapsed baseline must still gate, not vanish."""
        vals = [
            (st.get("speculation") or {}).get("accepted_tokens_per_step")
            for st in rep.get("stages") or []
        ]
        vals = [v for v in vals if v is not None]
        return max(vals) if vals else None

    sb, sc = spec_peak(baseline), spec_peak(current)
    if sb is not None:
        rows.append(Row(
            "loadgen speculation accepted_tokens_per_step",
            sb, sc if sc is not None else 0.0,
            "higher", TOL_DETERMINISTIC,
        ))

    def audit_pass_rate(rep: dict) -> Any:
        """Sweep-wide audit pass rate (sum across stages — the knee
        stage alone can have too few samples to be meaningful). None
        when auditing was off for the whole sweep."""
        done = passed = 0.0
        for st in rep.get("stages") or []:
            a = st.get("audit") or {}
            passed += a.get("pass") or 0
            done += sum(a.get(k) or 0 for k in ("pass", "drift", "fail"))
        return passed / done if done else None

    # Audit pass rate is an EXACT-class metric: on the fp path every
    # sampled request must replay byte-identical, so any non-pass in a
    # run whose baseline was clean is a correctness regression — never
    # inside a noise band.
    ab, ac = audit_pass_rate(baseline), audit_pass_rate(current)
    if ab is not None:
        rows.append(Row(
            "loadgen audit pass_rate",
            ab, ac if ac is not None else 0.0,
            "equal", TOL_EXACT,
        ))

    # Memory class (the item-3 gate surface): peak pool occupancy at
    # the knee stage is workload-deterministic (seeded sweep, same
    # pool) — a quantized-KV PR should HALVE it; page lifetimes and
    # the sampled device-time split divide by wall time, so they get
    # the wide band. Geometry drift never reaches these rows (the
    # config refusal above fires first).
    def knee_stage_memory(rep: dict, knee: dict) -> dict:
        stages = rep.get("stages") or []
        i = knee.get("index")
        if isinstance(i, int) and 0 <= i < len(stages):
            mem = stages[i].get("memory")
            if isinstance(mem, dict) and "pool" in mem:
                return mem
        return {}

    mb = knee_stage_memory(baseline, kb)
    mc = knee_stage_memory(current, kc)
    if mb:
        rows.append(Row(
            "loadgen knee-stage memory peak_pages_in_use",
            mb.get("stage_peak_pages_in_use",
                   mb.get("peak_pages_in_use")),
            mc.get("stage_peak_pages_in_use",
                   mc.get("peak_pages_in_use")),
            "lower", TOL_DETERMINISTIC,
        ))
        rows.append(Row(
            "loadgen knee-stage page_lifetime_s.p95",
            (mb.get("page_lifetime_s") or {}).get("p95"),
            (mc.get("page_lifetime_s") or {}).get("p95"),
            "lower", TOL_WALL_CLOCK,
        ))

        def dev_total(mem: dict):
            d = mem.get("device_time_s") or {}
            return round(sum(d.values()), 6) if d else None

        rows.append(Row(
            "loadgen knee-stage device_time_s (per-kind sum)",
            dev_total(mb), dev_total(mc), "lower", TOL_WALL_CLOCK,
        ))
        rows.append(Row(
            "loadgen knee-stage fragmentation_ratio",
            (mb.get("end") or {}).get("fragmentation_ratio"),
            (mc.get("end") or {}).get("fragmentation_ratio"),
            "info", 0.0,
        ))
        ht_b = mb.get("host_tier") or {}
        ht_c = mc.get("host_tier") or {}
        if ht_b or ht_c:
            # Host-tier rows: residency and reload economics are
            # workload-shaped, not pass/fail thresholds — info class,
            # visible in the verdict table so a tier that silently
            # stopped spilling (or reloading) shows up in review.
            rows.append(Row(
                "loadgen knee-stage host_tier spilled_pages",
                ht_b.get("spilled_pages"), ht_c.get("spilled_pages"),
                "info", 0.0,
            ))
            rows.append(Row(
                "loadgen knee-stage host_tier reload_hits",
                ht_b.get("reload_hits"), ht_c.get("reload_hits"),
                "info", 0.0,
            ))
            rows.append(Row(
                "loadgen knee-stage host_tier reload_pages_per_hit",
                ht_b.get("reload_pages_per_hit"),
                ht_c.get("reload_pages_per_hit"),
                "info", 0.0,
            ))
    return [_judge(r) for r in rows], None


# ---------------------------------------------------------------------------
# BENCH_paged_attention.json
# ---------------------------------------------------------------------------

_PAGED_CONFIG_KEYS = (
    "geometry.num_slots", "geometry.page_size", "geometry.chunk",
    "geometry.max_new",
)


def compare_paged(
    current: dict, baseline: dict
) -> tuple[list[Row], str | None]:
    """(rows, refusal). Gated series: ragged dispatches/step (the
    one-dispatch invariant), byte parity everywhere, and the seeded
    speculation fixture's accepted tokens/step."""
    refusal = refuse_reason(
        current, baseline, _PAGED_CONFIG_KEYS, "backend"
    )
    if refusal:
        return [], refusal
    rows: list[Row] = []

    def max_ragged_dps(rep: dict) -> float | None:
        vals = [
            (c.get("ragged") or {}).get("dispatches_per_step")
            for c in rep.get("cells") or []
        ]
        vals = [v for v in vals if v is not None]
        return max(vals) if vals else None

    rows.append(Row(
        "paged_attention max ragged dispatches_per_step",
        max_ragged_dps(baseline), max_ragged_dps(current),
        "lower", TOL_EXACT,
        note="the one-dispatch-per-step invariant",
    ))

    def parity(rep: dict) -> bool:
        cells_ok = all(
            c.get("replies_bit_identical") for c in rep.get("cells") or []
        )
        spec = rep.get("speculation") or {}
        spec_ok = spec.get("replies_bit_identical", True)
        return bool(cells_ok and spec_ok)

    rows.append(Row(
        "paged_attention replies_bit_identical (all cells + spec)",
        parity(baseline), parity(current), "equal", TOL_EXACT,
    ))
    sb = _dig(baseline, "speculation.spec.accepted_tokens_per_step")
    sc = _dig(current, "speculation.spec.accepted_tokens_per_step")
    if sb is not None:
        rows.append(Row(
            "paged_attention speculation accepted_tokens_per_step",
            sb, sc if sc is not None else 0.0,
            "higher", TOL_DETERMINISTIC,
        ))
    # Informational only: wall-clock ratio of the two engines (shared
    # CI cores make this a weather report, not a gate).
    def mean_ratio(rep: dict) -> float | None:
        ratios = []
        for c in rep.get("cells") or []:
            s = (c.get("split") or {}).get("decode_steps_per_s")
            r = (c.get("ragged") or {}).get("decode_steps_per_s")
            if s and r:
                ratios.append(r / s)
        return round(sum(ratios) / len(ratios), 4) if ratios else None

    rows.append(Row(
        "paged_attention mean ragged/split decode_steps_per_s",
        mean_ratio(baseline), mean_ratio(current), "info", 0.0,
    ))
    return [_judge(r) for r in rows], None


# ---------------------------------------------------------------------------
# Driver
# ---------------------------------------------------------------------------

_COMPARATORS = {
    "BENCH_loadgen.json": compare_loadgen,
    "BENCH_paged_attention.json": compare_paged,
}


def _load(path: str) -> dict | None:
    try:
        with open(path) as f:
            return json.load(f)
    except (OSError, ValueError):
        return None


def compare_all(root: str = REPO) -> dict[str, Any]:
    """Run every registered comparison; returns the machine-readable
    result ({file: {rows|refusal|skipped}}, plus the overall lists the
    gate decides on)."""
    out: dict[str, Any] = {"files": {}, "regressions": [], "refusals": []}
    for cur_name, base_rel in PAIRS:
        cur = _load(os.path.join(root, cur_name))
        base = _load(os.path.join(root, base_rel))
        entry: dict[str, Any] = {}
        if cur is None or base is None:
            entry["skipped"] = (
                f"missing {'current ' + cur_name if cur is None else ''}"
                f"{'baseline ' + base_rel if base is None else ''}".strip()
                + " (record one with --update-baselines)"
            )
        else:
            rows, refusal = _COMPARATORS[cur_name](cur, base)
            if refusal:
                entry["refusal"] = refusal
                out["refusals"].append(f"{cur_name}: {refusal}")
            entry["rows"] = [dataclasses.asdict(r) for r in rows]
            out["regressions"] += [
                f"{cur_name}: {r.series} "
                f"(baseline {r.baseline!r} -> current {r.current!r}, "
                f"tol {r.rel_tol:g})"
                for r in rows if r.verdict == "regression"
            ]
        out["files"][cur_name] = entry
    return out


def print_table(result: dict[str, Any]) -> None:
    w = 58
    print(f"{'series':<{w}} {'baseline':>12} {'current':>12} "
          f"{'tol':>6}  verdict")
    print("-" * (w + 42))
    for fname, entry in result["files"].items():
        if "skipped" in entry:
            print(f"{fname:<{w}} {'-':>12} {'-':>12} {'-':>6}  "
                  f"SKIPPED: {entry['skipped']}")
            continue
        if "refusal" in entry:
            print(f"{fname:<{w}} {'-':>12} {'-':>12} {'-':>6}  "
                  f"REFUSED: {entry['refusal']}")
            continue
        for r in entry["rows"]:
            def fmt(v):
                if isinstance(v, bool):
                    return str(v)
                if isinstance(v, (int, float)) and v is not None:
                    return f"{v:g}"
                return "-" if v is None else str(v)
            series = r["series"][:w]
            print(f"{series:<{w}} {fmt(r['baseline']):>12} "
                  f"{fmt(r['current']):>12} {r['rel_tol']:>6g}  "
                  f"{r['verdict'].upper()}"
                  + (f" ({r['note']})" if r.get("note") else ""))


def update_baselines(root: str = REPO) -> None:
    os.makedirs(os.path.join(root, "baselines"), exist_ok=True)
    for cur_name, base_rel in PAIRS:
        src = os.path.join(root, cur_name)
        if os.path.exists(src):
            shutil.copyfile(src, os.path.join(root, base_rel))
            print(f"baseline {base_rel} <- {cur_name}")
        else:
            print(f"skip {cur_name}: not present")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(
        description="bench regression sentinel (see module docstring)"
    )
    ap.add_argument("--root", default=REPO,
                    help="repo root holding the BENCH_*.json artifacts "
                    "and baselines/")
    ap.add_argument("--gate", action="store_true",
                    help="exit 1 on any regression, 2 on a refused "
                    "(non-comparable) pair — wired into check_tier1.sh")
    ap.add_argument("--update-baselines", action="store_true",
                    help="copy the current artifacts over baselines/ "
                    "(deliberate perf-ratchet move; commit the result)")
    ap.add_argument("--json-out", default=None,
                    help="also write the machine-readable result here")
    args = ap.parse_args(argv)
    if args.update_baselines:
        update_baselines(args.root)
        return
    result = compare_all(args.root)
    print_table(result)
    if args.json_out:
        with open(args.json_out, "w") as f:
            json.dump(result, f, indent=2)
    for line in result["refusals"]:
        print(f"REFUSED: {line}", file=sys.stderr)
    for line in result["regressions"]:
        print(f"REGRESSION: {line}", file=sys.stderr)
    if args.gate:
        if result["regressions"]:
            raise SystemExit(1)
        if result["refusals"]:
            raise SystemExit(2)
    print("bench_compare: "
          + ("clean vs baselines" if not (result["regressions"]
                                          or result["refusals"])
             else "problems found (see stderr)"))


if __name__ == "__main__":
    main()
