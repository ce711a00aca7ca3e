"""Parent side of a block-diffusion serve cell: a closed loop over a
text-only model that generates by diffusion over blocks. The shape of
runners/serve.py's `run` (child holds the chip, traffic made meanwhile,
every shape warmed, window, scrape, reduce) with the child that builds
a model without a vision tower and checks it against the block
reference (`serve_blockdiff_child.py`). Never imports jax."""

from __future__ import annotations

import os
import random
import shutil
import threading
import time

from benchmark import loadgen, traffic
from benchmark.runners import serve


class Child(serve.Child):
    script = "serve_blockdiff_child.py"


def warm_copy_on_write(port: int, page_size: int, seed: int) -> None:
    """Compile the prefix cache's copy-on-write program before the
    window. In block mode a splice ends on a block edge at or before
    the prompt's last whole block, so it lands mid-page only when the
    cache holds the page in which the prompt ends: the same prompt sent
    twice, with a reply long enough that the first one's finish donates
    that page (prompt + reply, whole pages)."""
    text = traffic.text_of(random.Random(seed ^ 0xC0), page_size + 7)
    body = traffic.chat_body([{"role": "user", "content": text}], page_size)
    (payload, want), = loadgen.encode_bodies([body])
    for _ in range(2):
        r = loadgen.send_stream("127.0.0.1", port, payload,
                                time.monotonic(), 900.0, want)
        if not r["ok"]:
            raise SystemExit(f"serve_blockdiff: warm-up request failed: {r}")


def run(ctx: dict) -> dict:
    wl, conf = ctx["workload"], ctx["config"]
    seconds, seed = ctx["seconds"], ctx["seed"]
    p = wl["traffic"]
    trace_dir = os.path.join(ctx["out_dir"], "trace")
    shutil.rmtree(trace_dir, ignore_errors=True)
    child = Child(conf, seed, ctx["chips"], ctx["rehearse"], trace_dir,
                  os.path.join(ctx["out_dir"], "serve_child.log"))
    ph = serve.Phases(ctx["t_start"], child.log)
    try:
        # Traffic is made while the child initialises and compiles.
        clients = p["clients"]
        n = int(clients * seconds * p.get("max_requests_per_client_s", 1.0))
        sessions = traffic.build_sessions(p, seed, n)
        per_client = [[] for _ in range(clients)]
        for i, s in enumerate(sessions):
            per_client[i % clients].extend(s)
        client_items = [
            loadgen.encode_bodies(traffic.rotated(c, seed))
            for c in per_client
        ]
        dev = child.wait_for("device", 600)
        ph.mark("device")
        warm = loadgen.encode_bodies(
            traffic.warmup_bodies(p, dev["embed_buckets"], seed)
        )
        ready = child.wait_for("ready", ctx["setup_timeout"])
        port = ready["port"]
        ph.mark("ready")
        check = next(e for e in child.events if e["event"] == "logit_check")
        ph.seconds["comparison_in_ready"] = check["seconds"]

        t_w = time.monotonic()
        for payload, want in warm:
            r = loadgen.send_stream("127.0.0.1", port, payload,
                                    time.monotonic(), 900.0, want)
            if not r["ok"]:
                raise SystemExit(
                    f"serve_blockdiff: warm-up request failed: {r}")
        if conf["layout"].get("prefix_cache", True):
            warm_copy_on_write(port, conf["layout"]["page_size"], seed)
        burst = [warm[i % len(warm)] for i in range(
            min(4, conf["layout"]["num_slots"]))]
        loadgen.run_closed_loop(
            "127.0.0.1", port, [[b] for b in burst], 600.0, until_done=True
        )
        warm_s = time.monotonic() - t_w
        ph.mark("warmup")

        child.tell("arm", "armed")
        before = serve.scrape(port)
        setup_s = time.monotonic() - ctx["t_start"]
        ph.mark("arm")
        tracer, slice_ = None, {}
        if ctx["trace"]:
            tracer = threading.Thread(
                target=serve._trace_slice, daemon=True,
                args=(child, port, seconds, wl.get("trace_seconds", 3.0),
                      slice_),
            )
            tracer.start()
        res = loadgen.run_closed_loop(
            "127.0.0.1", port, client_items, seconds,
            start_gap_s=p.get("start_gap_s", 0.0),
        )
        after = serve.scrape(port)
        if tracer is not None:
            tracer.join()
        ph.mark("window")
        end = child.tell("disarm", "disarmed", 300.0)
        ph.mark("disarm")
    finally:
        child.stop()
    ph.mark("stop")
    red = serve.reduce_requests(
        res, first_token_limit_s=p.get("first_token_limit_s"))
    delta = {k: after.get(k, 0.0) - before.get(k, 0.0) for k in after}
    compiles = end.get("compiles")
    raw = {"ttft_ms": red.pop("ttft_ms"), "tpot_ms": red.pop("tpot_ms")}
    lateness = red.pop("lateness_ms")
    problems = []
    if not check["ok"]:
        failed = [k for k, v in check.get("passed", {}).items() if not v]
        problems.append(f"logit check failed: {', '.join(failed)}")
    if compiles:
        problems.append(f"{compiles} compiles inside the window: "
                        f"{end.get('compile_counts')}")
    if red["failed"]:
        problems.append(f"{red['failed']} requests failed: {red['errors']}")
    if red["completed"] == 0:
        problems.append("no request completed")
    if res.get("exhausted_clients"):
        problems.append("a client ran out of requests before the window "
                        "ended: raise max_requests_per_client_s")
    device = dict(dev["device"], memory_peak_bytes=end["memory_peak_bytes"])
    tr = end.get("trace") or {}
    if tr:
        tr["slice_counters"] = slice_.get("counters", {})
        device["busy_s"], device["window_s"] = tr["busy_s"], tr["window_s"]
    return {
        "correct": not problems, "problems": problems,
        "attempted": red["attempted"], "failed": red["failed"],
        "end_to_end": {
            "ttft_p90_ms": red["ttft_p90_ms"],
            "tpot_p90_ms": red["tpot_p90_ms"],
            "serve_tok_s": red["serve_tok_s"], "setup_s": setup_s,
        },  # the manifest says which of these a cell reports
        "device": device,
        "requests": red, "requests_raw": raw, "lateness_ms": lateness,
        "counters": delta, "trace": tr, "logit_check": check,
        "setup": {
            "events": [e for e in child.events
                       if e["event"] in ("device", "init", "logit_check",
                                         "ready")],
            "warmup_s": warm_s,
        },
        "compiles_in_window": compiles,
        "phases": ph.seconds,
    }
