"""Find the knee of an open-loop serve cell: one server (one set-up),
stages at rising fixed rates with the cell's own traffic, the queue
drained between stages.

    python benchmark/tools/sweep.py --workload oryx-7b.chat \\
        --rates 1,1.5,2,2.5,3 --stage-seconds 30 [--set layout.ragged=true]

A rate is SUSTAINED when, at the stage's end, no request is waiting
outside a slot (in flight <= num_slots and the scheduler's queue_depth
gauge <= num_slots) and the requests completed are >= 0.97 of those due
before the last `--tail` seconds (those sent later are still decoding
at any rate). The knee is the highest sustained rate below the first
one that is not. The cell's rate is then written into its workload
file by hand, at about 0.8 of the knee. Writes
chiprun_out/sweep.<cell><tag>.json.
"""

from __future__ import annotations

import argparse
import json
import os
import random
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, ROOT)

from benchmark import loadgen, stats, traffic  # noqa: E402
from benchmark.run import load_json, resolve  # noqa: E402
from benchmark.runners import serve  # noqa: E402


def set_path(d: dict, dotted: str, value) -> None:
    *head, last = dotted.split(".")
    for k in head:
        d = d.setdefault(k, {})
    d[last] = value


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--stage-seconds", type=float, default=30.0)
    ap.add_argument("--tail", type=float, default=6.0)
    ap.add_argument("--seed", type=int, default=2147481111)
    ap.add_argument("--rehearse", type=int, default=0)
    ap.add_argument("--set", action="append", default=[],
                    help="config override, e.g. layout.ragged=true")
    ap.add_argument("--tag", default="")
    args = ap.parse_args(argv)
    manifest = load_json(ROOT, "BENCHMARK.json")
    cell = next(w for w in manifest["workloads"] if w["name"] == args.workload)
    wl = resolve(load_json(BENCH, "workloads", cell["name"] + ".json"),
                 bool(args.rehearse))
    conf_file = next(c["file"] for c in manifest["configs"]
                     if c["name"] == cell["config"])
    conf = resolve(load_json(ROOT, conf_file), bool(args.rehearse))
    for kv in args.set:
        k, v = kv.split("=", 1)
        set_path(conf, k, json.loads(v))
    out_dir = os.path.join(BENCH, "out", "sweep." + cell["name"])
    os.makedirs(out_dir, exist_ok=True)
    child = serve.Child(conf, args.seed, cell["chips"], bool(args.rehearse),
                        os.path.join(out_dir, "trace"),
                        os.path.join(out_dir, "serve_child.log"))
    p = wl["traffic"]
    slots = conf["layout"]["num_slots"]
    stages = []
    try:
        dev = child.wait_for("device", 600)
        port = child.wait_for("ready", 1500)["port"]
        for payload, want in loadgen.encode_bodies(
                traffic.warmup_bodies(p, dev["embed_buckets"], args.seed)):
            r = loadgen.send_stream("127.0.0.1", port, payload,
                                    time.monotonic(), 900.0, want)
            if not r["ok"]:
                raise SystemExit(f"sweep: warm-up request failed: {r}")
        serve.warm_copy_on_write(port, conf["layout"]["page_size"], args.seed)
        for i, rate in enumerate(float(x) for x in args.rates.split(",")):
            n = max(1, int(round(rate * args.stage_seconds)))
            sessions = traffic.build_sessions(p, args.seed + i, n)
            items = loadgen.encode_bodies(serve.interleave(
                sessions, p.get("concurrent_sessions", 24)))
            offsets = traffic.arrival_offsets(
                dict(p["arrivals"], rate=rate), len(items),
                random.Random(p.get("order_seed", 0)))
            before = serve.scrape(port)
            res = loadgen.run_open_loop(
                "127.0.0.1", port, items, offsets, args.stage_seconds,
                workers=p.get("workers", 128))
            after = serve.scrape(port)
            red = serve.reduce_requests(
                res, first_token_limit_s=p.get("first_token_limit_s"))
            due_early = sum(1 for o in res["issued"]
                            if o < args.stage_seconds - args.tail)
            ttft, tpot = red.pop("ttft_ms"), red.pop("tpot_ms")
            red.pop("lateness_ms")
            inflight = red["inflight_at_end"]
            st = {
                "rate": rate, "sent": len(res["issued"]),
                "completed": red["completed"], "failed": red["failed"],
                "due_before_tail": due_early, "inflight_at_end": inflight,
                "queue_depth_at_end": after.get("queue_depth"),
                "ttft_p50_ms": stats.percentile(ttft, 50),
                "ttft_p90_ms": stats.percentile(ttft, 90),
                "tpot_p50_ms": stats.percentile(tpot, 50),
                "tpot_p90_ms": stats.percentile(tpot, 90),
                "tok_s": red["serve_tok_s"],
                "decode_util": (
                    (after["decode_steps_useful"] - before["decode_steps_useful"])
                    / max(1.0, after["decode_steps_total"]
                          - before["decode_steps_total"])),
            }
            st["sustained"] = bool(
                st["failed"] == 0 and inflight <= slots
                and (st["queue_depth_at_end"] or 0) <= slots
                and st["completed"] >= 0.97 * due_early)
            stages.append(st)
            print(json.dumps(st), flush=True)
            # drain before the next stage
            t_end = time.monotonic() + 120
            while time.monotonic() < t_end:
                m = serve.scrape(port)
                if not m.get("queue_depth") and not m.get("slot_occupancy"):
                    break
                time.sleep(0.5)
    finally:
        child.stop()
    knee = None
    for st in stages:
        if not st["sustained"]:
            break
        knee = st["rate"]
    out = {"cell": cell["name"], "overrides": args.set, "stages": stages,
           "knee": knee}
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(
            ROOT, "chiprun_out", f"sweep.{cell['name']}{args.tag}.json"),
            "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps({"knee": knee, "overrides": args.set}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
