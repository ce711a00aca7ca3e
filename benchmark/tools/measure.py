"""Run one cell several times in one call and work out the spreads the
bounds are set from (the contract's rule: sets of runs with the same
seeds in both sets; per metric the wider of the two sets' spreads,
spread = IQR / median by statistics.quantiles(n=4)).

    python benchmark/tools/measure.py --workload <cell> [--sets 2] [--runs 6]
        [--seconds S] [--trace-run N] [--base-seed N]

`--trace-run N` adds N traced runs, one on each of the first N seeds
(`--sets 0 --runs 6 --trace-run 6`: the device instrument alone, with
`busy_s`, `window_s` and `host_window_s` of every slice).
Writes every last line and the summary to chiprun_out/measure.<cell>.json.
Each run is a fresh process, as in the driver's check.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, ROOT)

from benchmark import stats  # noqa: E402


def one(cell, seed, seconds, trace, extra, keep_dir=None):
    cmd = [sys.executable, os.path.join(BENCH, "run.py"), "--workload", cell,
           "--seed", str(seed), "--trace", str(trace)] + extra
    if seconds is not None:
        cmd += ["--seconds", str(seconds)]
    t0 = time.monotonic()
    p = subprocess.run(cmd, capture_output=True, text=True, cwd=ROOT)
    wall = time.monotonic() - t0
    lines = [ln for ln in p.stdout.splitlines() if ln.strip()]
    try:
        last = json.loads(lines[-1]) if p.returncode == 0 else None
    except (ValueError, IndexError):
        last = None
    info = None
    if last is not None and len(lines) > 1:
        try:
            info = json.loads(lines[-2]).get("info")
        except ValueError:
            pass
    src = os.path.join(BENCH, "out", cell, f"run.seed{seed}.trace{trace}.json")
    if keep_dir and os.path.exists(src):
        os.makedirs(keep_dir, exist_ok=True)
        shutil.copy(src, keep_dir)
    return {"seed": seed, "trace": trace, "rc": p.returncode, "wall_s": wall,
            "last": last, "info": info,
            "stderr_tail": p.stderr[-1500:] if last is None else ""}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(allow_abbrev=False)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--sets", type=int, default=2)
    ap.add_argument("--runs", type=int, default=6)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace-run", type=int, default=0)
    ap.add_argument("--base-seed", type=int, default=2147480000)
    ap.add_argument("--tag", default="")
    args, extra = ap.parse_known_args(argv)
    out_dir = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    seeds = [args.base_seed + 104729 * i for i in range(args.runs)]
    record = {"cell": args.workload, "sets": [], "traced": []}
    path = os.path.join(out_dir, f"measure.{args.workload}{args.tag}.json")
    for s in range(args.sets):
        runs = []
        for seed in seeds:
            r = one(args.workload, seed, args.seconds, 0, extra, os.path.join(
                out_dir, "runs", args.workload + args.tag, f"set{s}"))
            runs.append(r)
            vals = {k: v["value"] for k, v in (r["last"] or {}).get(
                "metrics", {}).items()}
            print(json.dumps({
                "set": s, "seed": seed, "rc": r["rc"],
                "wall_s": round(r["wall_s"], 1),
                "correct": (r["last"] or {}).get("correct"),
                "problems": (r["last"] or {}).get("problems"),
                "failed": (r["last"] or {}).get("failed"),
                "attempted": (r["last"] or {}).get("attempted"),
                "mem": (r["last"] or {}).get("device", {}).get(
                    "memory_peak_bytes"),
                **vals, "err": r["stderr_tail"][-600:],
            }), flush=True)
            with open(path, "w") as f:
                json.dump({**record, "partial": runs}, f)
        record["sets"].append(runs)
    for seed in seeds[:args.trace_run]:
        r = one(args.workload, seed, args.seconds, 1, extra,
                os.path.join(out_dir, "runs", args.workload + args.tag))
        record["traced"].append(r)
        last = r["last"] or {}
        dev = last.get("device", {})
        print(json.dumps({
            "traced": seed, "rc": r["rc"], "wall_s": round(r["wall_s"], 1),
            "correct": last.get("correct"), "problems": last.get("problems"),
            **{k: dev.get(k) for k in ("busy_s", "window_s", "host_window_s",
                                       "memory_peak_bytes")},
            "metrics": {k: v["value"]
                        for k, v in last.get("metrics", {}).items()},
            "breakdown": last.get("breakdown"),
            "err": r["stderr_tail"][-600:],
        }), flush=True)
        with open(path, "w") as f:
            json.dump(record, f)
    summary = {}
    names = set()
    for runs in record["sets"]:
        for r in runs:
            names |= set((r["last"] or {}).get("metrics", {}))
    for name in sorted(names):
        per_set = []
        for runs in record["sets"]:
            vals = [r["last"]["metrics"][name]["value"] for r in runs
                    if r["last"] and name in r["last"]["metrics"]]
            if name == "setup_s":
                vals = vals[1:] if runs is record["sets"][0] else vals
            if len(vals) >= 2:
                per_set.append({
                    "median": stats.median(vals), "iqr_share":
                    stats.iqr_share(vals) if len(vals) >= 2 else None,
                    "min": min(vals), "max": max(vals), "n": len(vals)})
        summary[name] = per_set
    record["summary"] = summary
    with open(path, "w") as f:
        json.dump(record, f)
    print(json.dumps({"summary": summary}, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
