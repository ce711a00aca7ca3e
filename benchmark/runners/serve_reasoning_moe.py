"""Parent side of the agent-reasoning cell of a Mamba-2 hybrid with
latent experts: a closed loop of single-turn requests (a task with its
context, a few thousand streamed tokens of reasoning) over a text-only
hybrid whose slots hold a recurrent state beside their pages.
runners/serve_reasoning.py's `run` and its `client_lists` (sessions
dealt to clients in turn, client i of n starting i/n of the way through
its list AT EVERY SEED), with one more problem a run can have, as
runners/serve_agent.py: every request of this mix runs to its
`max_tokens` (the child's tokenizer keeps the template's stop out of
reach), so one that ends on `stop` makes the run incorrect. The child
is `serve_reasoning_moe_holder.py` (its configuration keys, its
comparison: correctness_nemotron.py; NOT named `*_child.py`, for the
reason serve_agent.py gives). No prefix cache, so no copy-on-write
program to warm. `correct` is decided AFTER the window, on what it
served. Nothing of it is inside `setup_s`.

Never imports jax."""

from __future__ import annotations

import os
import shutil
import threading
import time

from benchmark import loadgen, traffic
from benchmark.runners import serve, serve_reasoning


class Child(serve.Child):
    script = "serve_reasoning_moe_holder.py"


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
        client_items = [
            loadgen.encode_bodies(c)
            for c in serve_reasoning.client_lists(p, seed, seconds)]
        dev = child.wait_for("device", 600)
        ph.mark("device")
        warm = loadgen.encode_bodies(
            traffic.warmup_bodies(p, dev["embed_buckets"], seed))
        ready = child.wait_for("ready", ctx["setup_timeout"])
        port = ready["port"]
        ph.mark("ready")

        t_w = time.monotonic()
        for payload, want in warm:
            r = loadgen.send_stream("127.0.0.1", port, payload,
                                    time.monotonic(), 900.0, want)
            if not r["ok"]:
                raise SystemExit(
                    f"serve_reasoning_moe: warm-up request failed: {r}")
        burst = [warm[i % len(warm)] for i in range(
            min(4, conf["layout"]["num_slots"]))]
        loadgen.run_closed_loop(
            "127.0.0.1", port, [[b] for b in burst], 600.0, until_done=True
        )
        warm_s = time.monotonic() - t_w
        ph.mark("warmup")

        child.tell("arm", "armed")
        scraped = serve.scrape(port)
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
        # The comparison comes after the window, on what it served.
        check = child.check_after_window()
        ph.mark("comparison")
    finally:
        child.stop()
    ph.mark("stop")
    red = serve.reduce_requests(
        res, first_token_limit_s=p.get("first_token_limit_s"))
    delta = {k: after.get(k, 0.0) - scraped.get(k, 0.0) for k in after}
    compiles = end.get("compiles")
    raw = {"ttft_ms": red.pop("ttft_ms"), "tpot_ms": red.pop("tpot_ms")}
    lateness = red.pop("lateness_ms")
    problems = serve.check_problems(check, p.get("check_sample_kinds", ()))
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
    stopped = [r for r in res["records"] if r.get("finish") == "stop"]
    if stopped:
        # Every request of this mix runs to its max_tokens (the child's
        # tokenizer keeps the template's stop out of reach): one that
        # stops sooner sends its client on early, and the seed has
        # changed the work.
        problems.append(
            f"{len(stopped)} requests ended before their max_tokens "
            f"(finish_reason stop, {stopped[0]['tokens']} of "
            f"{stopped[0]['want_tokens']} tokens the first): the window's "
            "work depends on the seed")
    device = dict(dev["device"], memory_peak_bytes=end["memory_peak_bytes"])
    tr = end.get("trace") or {}
    if tr:
        tr["slice_counters"] = slice_.get("counters", {})
        device["busy_s"], device["window_s"] = tr["busy_s"], tr["window_s"]
    return {
        "correct": not problems, "problems": problems,
        "attempted": red["attempted"], "failed": red["failed"],
        "end_to_end": {
            "serve_tok_s": red["serve_tok_s"], "setup_s": setup_s,
        },
        "device": device,
        "requests": red, "requests_raw": raw, "lateness_ms": lateness,
        "counters": delta, "trace": tr, "logit_check": check,
        "setup": {
            "events": [e for e in child.events
                       if e["event"] in ("device", "init", "ready")],
            "warmup_s": warm_s,
            # Not set-up: here because run.py's info line carries this
            # block, and the comparison's numbers belong on it.
            "check_after_window": check,
        },
        "compiles_in_window": compiles,
        "phases": ph.seconds,
    }
