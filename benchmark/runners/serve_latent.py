"""Parent side of a latent-attention serve cell: a closed loop of
multi-turn sessions over a text-only model whose cache is a paged
latent. The shape of runners/serve_blockdiff.py's `run` (child holds
the chip, traffic made meanwhile, every shape warmed, window, scrape,
reduce), with its own order of requests (`client_lists`) and three
things the cell's traffic file asks for by key:

  - the child (`serve_latent_child.py`: its configuration keys, its
    tokenizer, its comparison);
  - `session_tag_chars`: a seed-made tag at the head of every session,
    which the child's tokenizer starts its hash from;
  - `warm_previous_turn`: a client whose first request is a later turn
    of a session first sends, in set-up, the turn before it (cut to 8
    tokens out), so that the window opens on sessions in progress, each
    with its history in the prefix cache up to its last user turn, as
    every later turn of the window finds it.

Never imports jax."""

from __future__ import annotations

import os
import random
import shutil
import string
import threading
import time

from benchmark import loadgen, traffic
from benchmark.runners import serve, serve_blockdiff

_ALPHABET = string.ascii_letters + string.digits


class Child(serve.Child):
    script = "serve_latent_child.py"


def tagged_sessions(params: dict, seed: int, n_requests: int) -> list[list]:
    """traffic.build_sessions, then the first `session_tag_chars`
    characters of every session's first user turn overwritten, in every
    request that re-sends it, with a tag made from (seed, session):
    lengths stay as the generator drew them, and no two sessions open
    alike."""
    sessions = traffic.build_sessions(params, seed, n_requests)
    n = params.get("session_tag_chars", 0)
    for i, session in enumerate(sessions):
        rng = random.Random(seed * 1_000_003 + i)
        tag = "".join(rng.choice(_ALPHABET) for _ in range(n))
        for body in session:
            first = body["messages"][0]
            first["content"] = tag + first["content"][n:]
    return sessions


def client_lists(params: dict, seed: int, seconds: float):
    """(what each client sends in the window, what it sends before it).
    Sessions are dealt to clients in turn, as the harness deals them,
    and client i of n starts i/n of the way through its list: at any
    instant the clients of a server are spread over the turns of their
    sessions, and so is every window here. The generator draws the same
    lengths at every seed and a 50 s window serves 4 of a client's ~25
    requests, so a rotation by the seed, which the other closed-loop
    cells have, picks another sixth of the lengths each run:
    `serve_tok_s` then follows the sample's tokens out per prefill
    chunk (spread 4.2 % over 200 seeds by the lengths alone, 4.5-6.7 %
    on the chip; PERF.md section 6, PR 31). Here the seed makes the
    words, the tags and the weights, as in the open-loop cells. Before
    the window (`warm_previous_turn`): where a client's first request
    re-sends a history, the request before it in its list, which is
    that session's turn before, with 8 tokens out."""
    clients = params["clients"]
    n = int(clients * seconds * params.get("max_requests_per_client_s", 1.0))
    per_client = [[] for _ in range(clients)]
    for i, s in enumerate(tagged_sessions(params, seed, n)):
        per_client[i % clients].extend(s)
    starts = [i * len(c) // clients for i, c in enumerate(per_client)]
    window = [traffic.rotated(c, k) for c, k in zip(per_client, starts)]
    before = []
    if params.get("warm_previous_turn"):
        for c, k in zip(per_client, starts):
            if len(c[k]["messages"]) > 1:
                before.append(dict(c[k - 1], max_tokens=8))
    return window, before


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
        window, before = client_lists(p, seed, seconds)
        client_items = [loadgen.encode_bodies(c) for c in window]
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
                raise SystemExit(f"serve_latent: warm-up request failed: {r}")
        if conf["layout"].get("prefix_cache", True):
            serve_blockdiff.warm_copy_on_write(
                port, conf["layout"]["page_size"], seed)
        burst = [warm[i % len(warm)] for i in range(
            min(4, conf["layout"]["num_slots"]))]
        loadgen.run_closed_loop(
            "127.0.0.1", port, [[b] for b in burst], 600.0, until_done=True
        )
        ph.mark("warmup")
        # Sessions in progress: their histories, after every shape.
        res = loadgen.run_closed_loop(
            "127.0.0.1", port,
            [[b] for b in loadgen.encode_bodies(before)], 900.0,
            until_done=True, start_gap_s=p.get("start_gap_s", 0.0),
        )
        bad = [r for r in res["records"] if not r.get("ok")]
        if bad:
            raise SystemExit(f"serve_latent: a history failed: {bad[0]}")
        warm_s = time.monotonic() - t_w
        ph.mark("histories")

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
    finally:
        child.stop()
    ph.mark("stop")
    red = serve.reduce_requests(
        res, first_token_limit_s=p.get("first_token_limit_s"))
    delta = {k: after.get(k, 0.0) - scraped.get(k, 0.0) for k in after}
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
            "warmup_s": warm_s, "histories_sent": len(before),
        },
        "compiles_in_window": compiles,
        "phases": ph.seconds,
    }
