"""The benchmark's one command.

    python benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Finds everything by name: the cell and its configuration in
BENCHMARK.json, `workloads/<cell>.json` (runner kind + traffic
parameters), `configs/<config>.json` (sizes + runtime layout),
`runners/<kind>.py`, and for each per-layer metric of the cell
`layer_metrics/<metric>.py`. Adding a cell, a configuration or a
per-layer metric is adding files and entries; nothing here is edited.

The LAST stdout line is the contract's JSON object. With `--trace 0`
its metrics are the cell's end-to-end metrics, with `--trace 1` its
per-layer metrics. `--rehearse 1` runs the same code on the CPU with
each file's `rehearse` overrides (tiny model, short traffic) and is for
tests only: it prints the object with `"rehearsal": true` and the CPU's
device record, and is never a measurement.
"""

from __future__ import annotations

import argparse
import atexit
import importlib
import json
import math
import os
import signal
import sys
import time

T_START = time.monotonic()
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)


def load_json(*parts) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def deep_merge(base: dict, over: dict) -> dict:
    out = dict(base)
    for k, v in over.items():
        if isinstance(v, dict) and isinstance(out.get(k), dict):
            out[k] = deep_merge(out[k], v)
        else:
            out[k] = v
    return out


def resolve(d: dict, rehearse: bool) -> dict:
    """A file as it is run: its `rehearse` block applied (or dropped)."""
    d = dict(d)
    over = d.pop("rehearse", {})
    return deep_merge(d, over) if rehearse else d


def metrics_for(manifest: dict, kind: str, cell: str) -> list[dict]:
    return [
        m for m in manifest[kind]
        if "workloads" not in m or cell in m["workloads"]
    ]


def end_on_signals() -> None:
    """SIGTERM, SIGINT and SIGHUP end the run as an exit does: whatever
    child holds the chip is killed with its group FIRST (it may be deep
    in a comparison that nobody will read), then SystemExit unwinds the
    runner through its `finally`. The `atexit` covers every other way
    out. (SIGKILL cannot be caught: the child ends itself then,
    runners/lifeline.py.)"""
    from benchmark.runners import serve

    def end(signum, frame):
        serve.kill_live()
        raise SystemExit(128 + signum)

    for sig in (signal.SIGTERM, signal.SIGINT, signal.SIGHUP):
        signal.signal(sig, end)
    atexit.register(serve.kill_live)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", type=int, choices=(0, 1), default=0)
    ap.add_argument("--set", action="append", default=[],
                    help="experiment only (never a measurement of the "
                    "cell as committed): override a configuration key, "
                    "e.g. layout.ragged=true")
    args = ap.parse_args(argv)

    manifest = load_json(ROOT, "BENCHMARK.json")
    cells = {w["name"]: w for w in manifest["workloads"]}
    if args.workload not in cells:
        raise SystemExit(f"unknown workload {args.workload!r}; "
                         f"BENCHMARK.json has {sorted(cells)}")
    cell = cells[args.workload]
    rehearse = bool(args.rehearse)
    wl = resolve(load_json(HERE, "workloads", cell["name"] + ".json"),
                 rehearse)
    configs = {c["name"]: c for c in manifest["configs"]}
    conf = resolve(load_json(ROOT, configs[cell["config"]]["file"]), rehearse)
    for kv in args.set:
        key, val = kv.split("=", 1)
        d = conf
        *head, last = key.split(".")
        for k in head:
            d = d.setdefault(k, {})
        d[last] = json.loads(val)
    if wl["config"] != cell["config"]:
        raise SystemExit(f"{cell['name']}: workload file names config "
                         f"{wl['config']!r}, BENCHMARK.json {cell['config']!r}")
    seconds = args.seconds if args.seconds is not None else (
        manifest["run_seconds"])
    if rehearse and "seconds" in wl:
        seconds = min(seconds, wl["seconds"])
    out_dir = os.path.join(HERE, "out", cell["name"])
    os.makedirs(out_dir, exist_ok=True)

    end_on_signals()
    runner = importlib.import_module("benchmark.runners." + wl["runner"])
    ctx = {
        "workload": wl, "config": conf, "seed": args.seed,
        "seconds": float(seconds), "trace": bool(args.trace),
        "rehearse": rehearse, "chips": cell["chips"], "out_dir": out_dir,
        "t_start": T_START, "setup_timeout": 1100.0, "cell": cell["name"],
    }
    run = runner.run(ctx)
    run["cell"], run["seed"], run["seconds"] = cell["name"], args.seed, seconds
    run["config"], run["workload"] = conf, wl

    correct = bool(run["correct"])
    metrics = {}
    if args.trace:
        from benchmark import metric_files

        for m in metrics_for(manifest, "per_layer", cell["name"]):
            # A reader that finds nothing to read returns None and the
            # metric is left out; one that raises is a fault of the run.
            try:
                v = metric_files.load(m["name"]).read(run)
            except Exception as e:  # fault-boundary: any reader, any fault
                correct = False
                run["problems"].append(f"{m['name']}: {e!r}")
                continue
            if v is not None and math.isfinite(v):
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        for m in metrics_for(manifest, "end_to_end", cell["name"]):
            v = run["end_to_end"].get(m["name"])
            if v is None or not math.isfinite(v):
                correct = False
                run["problems"].append(f"{m['name']} is {v}")
                v = 1e18
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}

    line = {
        "correct": correct, "attempted": run["attempted"],
        "failed": run["failed"], "metrics": metrics, "device": run["device"],
    }
    if args.trace and run.get("trace"):
        from benchmark import trace as trace_lib

        line["breakdown"] = trace_lib.breakdown(run["trace"])
        # beside device.window_s (the device planes' own extent): the
        # host's stamp of the same slice, for whoever wants both clocks
        line["device"]["host_window_s"] = run["trace"].get("host_window_s")
    if rehearse:
        line["rehearsal"] = True
    if args.set:
        line["overrides"] = args.set
    line["problems"] = run["problems"]
    tag = f"seed{args.seed}.trace{args.trace}"
    with open(os.path.join(out_dir, f"run.{tag}.json"), "w") as f:
        json.dump({k: v for k, v in run.items()}, f, default=str)
    info = {k: run.get(k) for k in ("requests", "setup", "compiles_in_window",
                                    "phases")}
    info["wall_s"] = time.monotonic() - T_START  # process start to here
    if run.get("train"):
        info["train"] = {k: v for k, v in run["train"].items()
                         if not isinstance(v, list) or len(v) <= 8}
    print(json.dumps({"info": info}, default=str))
    print(json.dumps(line))
    return 0


if __name__ == "__main__":
    sys.exit(main())
