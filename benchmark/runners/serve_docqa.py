"""Parent side of a long-document question-answering cell: a closed
loop of sessions, each ONE long document and a few questions about it,
over a text-only latent-attention model whose cache is a paged latent.
The shape of runners/serve_latent.py's `run` (child holds the chip,
traffic made meanwhile, every shape warmed, histories sent, window,
scrape, reduce) with its own session builder, because the general
generator (`traffic.build_sessions`) has no key for a long first turn:

  - `doc_sessions`: a session's first user turn is a seed-made tag, a
    document of `document_tokens` and the first question; every later
    request re-sends it and the history (seed-made assistant text of the
    asked length, as the harness does) and appends one question. So
    request 1 of a session prefills the whole document cold, in the
    engine's chunks, and every later one prefills a few hundred tokens
    against a cached latent prefix of the document's length.
  - `client_lists`: sessions are dealt to clients in turn, client i of n
    starts i/n of the way through its list AT EVERY SEED (the seed makes
    the words, the tags and the weights, never the order: serve_latent's
    docstring says why), and a client that opens on a later question
    first sends, in set-up, the request before it with 8 tokens out, so
    that the window opens on sessions in progress with their documents
    in the prefix cache.
  - the child is `serve_docqa_child.py` (its configuration keys, its
    comparison).
  - `correct` is decided AFTER the window, on what it served: the
    `stop` that ends the child is answered with a `logit_check` event,
    the comparison of a sample of the requests the engine finished in
    the window (their streamed greedy tokens) with the plain reference
    (correctness_mistral4.py). Nothing of it is inside `setup_s`.

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
    script = "serve_docqa_child.py"


def doc_sessions(params: dict, seed: int) -> list[list]:
    """`clients * sessions_per_client` sessions, each a list of request
    bodies to be sent in order. Lengths are the mid-quantiles of the
    stated distributions in the one order `order_seed` shuffles them
    into, the same at every seed; the seed makes the words and the
    session's tag (the first `session_tag_chars` characters of the
    document, which the child's tokenizer starts its hash from)."""
    n_sessions = params["clients"] * params["sessions_per_client"]
    cycle = params["questions"]
    n_requests = sum(cycle[i % len(cycle)] for i in range(n_sessions))
    order = random.Random(params.get("order_seed", 0))
    doc_len = traffic.shuffled(
        traffic.quantile_values(params["document_tokens"], n_sessions), order)
    q_len = traffic.shuffled(
        traffic.quantile_values(params["question_tokens"], n_requests), order)
    out_len = traffic.shuffled(
        traffic.quantile_values(params["max_tokens"], n_requests), order)
    rng = random.Random(seed)  # words
    tag_chars = params.get("session_tag_chars", 0)
    limit = params.get("max_session_tokens", 1 << 30)
    sessions, made = [], 0
    for i in range(n_sessions):
        tag_rng = random.Random(seed * 1_000_003 + i)
        tag = "".join(tag_rng.choice(_ALPHABET) for _ in range(tag_chars))
        doc = tag + traffic.text_of(rng, doc_len[i] - tag_chars)
        session, history = [], []
        for t in range(cycle[i % len(cycle)]):
            q = traffic.text_of(rng, q_len[made])
            # The document is the head of the first user turn.
            turn = doc + "\n" + q if t == 0 else q
            msgs = history + [{"role": "user", "content": turn}]
            total = sum(len(m["content"]) + 1 for m in msgs) + out_len[made]
            if total > limit:
                raise ValueError(
                    f"session {i} would reach {total} positions, over "
                    f"max_session_tokens {limit}")
            session.append(traffic.chat_body(msgs, out_len[made]))
            history = msgs + [{
                "role": "assistant",
                "content": traffic.text_of(rng, out_len[made]),
            }]
            made += 1
        sessions.append(session)
    return sessions


def client_lists(params: dict, seed: int):
    """(what each client sends in the window, what it sends before it):
    see the module's docstring."""
    clients = params["clients"]
    per_client = [[] for _ in range(clients)]
    for i, s in enumerate(doc_sessions(params, seed)):
        per_client[i % clients].extend(s)
    starts = [i * len(c) // clients for i, c in enumerate(per_client)]
    window = [traffic.rotated(c, k) for c, k in zip(per_client, starts)]
    before = []
    if params.get("warm_previous_turn"):
        for c, k in zip(per_client, starts):
            if len(c[k]["messages"]) > 1:
                before.append(dict(c[k - 1], max_tokens=8))
    return window, before


def warmup_bodies(params: dict, buckets, seed: int) -> list[dict]:
    """One prompt inside each embed bucket up to the longest session
    (`traffic.warmup_bodies` on the keys it reads): the longest passes
    through every block-table width a prefill chunk is dispatched
    with."""
    return traffic.warmup_bodies(
        {"max_session_tokens": params["max_session_tokens"],
         "user_tokens": params["question_tokens"]}, buckets, seed)


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
        warm = loadgen.encode_bodies(
            warmup_bodies(p, dev["embed_buckets"], seed))
        ready = child.wait_for("ready", ctx["setup_timeout"])
        port = ready["port"]
        ph.mark("ready")

        t_w = time.monotonic()
        for payload, want in warm:
            r = loadgen.send_stream("127.0.0.1", port, payload,
                                    time.monotonic(), 900.0, want)
            if not r["ok"]:
                raise SystemExit(f"serve_docqa: warm-up request failed: {r}")
        if conf["layout"].get("prefix_cache", True):
            serve_blockdiff.warm_copy_on_write(
                port, conf["layout"]["page_size"], seed)
        burst = [warm[i % len(warm)] for i in range(
            min(4, conf["layout"]["num_slots"]))]
        loadgen.run_closed_loop(
            "127.0.0.1", port, [[b] for b in burst], 600.0, until_done=True
        )
        ph.mark("warmup")
        # Sessions in progress: their documents, after every shape.
        res = loadgen.run_closed_loop(
            "127.0.0.1", port,
            [[b] for b in loadgen.encode_bodies(before)], 900.0,
            until_done=True, start_gap_s=p.get("start_gap_s", 0.0),
        )
        bad = [r for r in res["records"] if not r.get("ok")]
        if bad:
            raise SystemExit(f"serve_docqa: a history failed: {bad[0]}")
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
        # The comparison comes after the window, on what it served:
        # the child closes its server, samples the requests the engine
        # finished and holds their streamed tokens to the reference.
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
                       if e["event"] in ("device", "init", "ready")],
            "warmup_s": warm_s, "histories_sent": len(before),
            # Not set-up: here because run.py's info line carries this
            # block, and the comparison's numbers belong on it.
            "check_after_window": check,
        },
        "compiles_in_window": compiles,
        "phases": ph.seconds,
    }
