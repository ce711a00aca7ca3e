"""Parent side of a long-context agent-session cell: a closed loop of
sessions, each ONE long context (a repository, a log, a set of papers)
at the head of its first turn and MANY turns that re-send it, over a
text-only latent-attention model with learned sparse attention. The
shape of runners/serve_docqa.py's `run` (child holds the chip, traffic
made meanwhile, every shape warmed, histories sent, window, scrape,
reduce, the comparison after the window), whose `Child`, warm-up and
client order it uses as they are, with its own session builder:

  - `long_sessions`: as `serve_docqa.doc_sessions` (lengths the
    mid-quantiles of the stated distributions in one shuffled order, the
    same at every seed; the seed makes the words and the tags), but a
    session ENDS before `max_session_tokens` positions instead of
    failing there: a context of 49k tokens has room for five turns, one
    of 13k for all twelve.
  - the child is `serve_longctx_child.py` (its configuration keys, its
    comparison: correctness_glm5.py, a tokenizer under which no emitted
    id is the template's stop).
  - a request that ends before its `max_tokens` makes the run
    incorrect: the work of a window is the same at every seed.

Never imports jax."""

from __future__ import annotations

import os
import random
import shutil
import threading
import time

from benchmark import loadgen, traffic
from benchmark.runners import serve, serve_blockdiff, serve_docqa


class Child(serve.Child):
    script = "serve_longctx_child.py"


def long_sessions(params: dict, seed: int) -> list[list]:
    """`clients * sessions_per_client` sessions, each a list of request
    bodies to be sent in order: turn 0 is the tag, the context and the
    first user turn; every later request re-sends it and the history
    (seed-made assistant text of the asked length) and appends one user
    or tool turn. A session stops before the turn that would pass
    `max_session_tokens`."""
    n_sessions = params["clients"] * params["sessions_per_client"]
    cycle = params["turns"]
    n_requests = sum(cycle[i % len(cycle)] for i in range(n_sessions))
    order = random.Random(params.get("order_seed", 0))
    ctx_len = traffic.shuffled(
        traffic.quantile_values(params["context_tokens"], n_sessions), order)
    turn_len = traffic.shuffled(
        traffic.quantile_values(params["user_tokens"], n_requests), order)
    out_len = traffic.shuffled(
        traffic.quantile_values(params["max_tokens"], n_requests), order)
    rng = random.Random(seed)  # words
    tag_chars = params.get("session_tag_chars", 0)
    limit = params["max_session_tokens"]
    sessions, made = [], 0
    for i in range(n_sessions):
        tag_rng = random.Random(seed * 1_000_003 + i)
        tag = "".join(tag_rng.choice(serve_docqa._ALPHABET)
                      for _ in range(tag_chars))
        context = tag + traffic.text_of(rng, ctx_len[i] - tag_chars)
        session, history = [], []
        for t in range(cycle[i % len(cycle)]):
            q = traffic.text_of(rng, turn_len[made])
            turn = context + "\n" + q if t == 0 else q
            msgs = history + [{"role": "user", "content": turn}]
            total = sum(len(m["content"]) + 1 for m in msgs) + out_len[made]
            made += 1
            if total > limit:
                if not session:
                    raise ValueError(
                        f"session {i}'s first turn would reach {total} "
                        f"positions, over max_session_tokens {limit}")
                break
            session.append(traffic.chat_body(msgs, out_len[made - 1]))
            history = msgs + [{
                "role": "assistant",
                "content": traffic.text_of(rng, out_len[made - 1]),
            }]
        sessions.append(session)
    return sessions


def client_lists(params: dict, seed: int):
    """(what each client sends in the window, what it sends before it):
    `serve_docqa.client_lists` over this cell's sessions."""
    clients = params["clients"]
    per_client = [[] for _ in range(clients)]
    for i, s in enumerate(long_sessions(params, seed)):
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
        window, before = client_lists(p, seed)
        client_items = [loadgen.encode_bodies(c) for c in window]
        dev = child.wait_for("device", 600)
        ph.mark("device")
        warm = loadgen.encode_bodies(serve_docqa.warmup_bodies(
            {**p, "question_tokens": p["user_tokens"]},
            dev["embed_buckets"], seed))
        ready = child.wait_for("ready", ctx["setup_timeout"])
        port = ready["port"]
        ph.mark("ready")

        t_w = time.monotonic()
        for payload, want in warm:
            r = loadgen.send_stream("127.0.0.1", port, payload,
                                    time.monotonic(), 900.0, want)
            if not r["ok"]:
                raise SystemExit(f"serve_longctx: warm-up request failed: {r}")
        serve_blockdiff.warm_copy_on_write(
            port, conf["layout"]["page_size"], seed)
        burst = [warm[i % len(warm)] for i in range(
            min(4, conf["layout"]["num_slots"]))]
        loadgen.run_closed_loop(
            "127.0.0.1", port, [[b] for b in burst], 600.0, until_done=True
        )
        ph.mark("warmup")
        # Sessions in progress: their contexts, after every shape.
        res = loadgen.run_closed_loop(
            "127.0.0.1", port,
            [[b] for b in loadgen.encode_bodies(before)], 900.0,
            until_done=True, start_gap_s=p.get("start_gap_s", 0.0),
        )
        bad = [r for r in res["records"] if not r.get("ok")]
        if bad:
            raise SystemExit(f"serve_longctx: a history failed: {bad[0]}")
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
    problems = serve.check_problems(
        check, p.get("check_sample_kinds", ()))
    if compiles:
        problems.append(f"{compiles} compiles inside the window: "
                        f"{end.get('compile_counts')}")
    if red["failed"]:
        problems.append(f"{red['failed']} requests failed: {red['errors']}")
    if red["completed"] == 0:
        problems.append("no request completed")
    if res.get("exhausted_clients"):
        problems.append("a client ran out of requests before the window "
                        "ended: raise sessions_per_client")
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
            "warmup_s": warm_s, "histories_sent": len(before),
            # Not set-up: here because run.py's info line carries this
            # block, and the comparison's numbers belong on it.
            "check_after_window": check,
        },
        "compiles_in_window": compiles,
        "phases": ph.seconds,
    }
