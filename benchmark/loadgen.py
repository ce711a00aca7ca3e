"""The load generator: open-loop and closed-loop clients over real HTTP
(SSE streams), timed on this process's monotonic clock.

Runs in the parent process, which never imports jax: the client threads
share no GIL with the engine. Open loop: a request is timed from the
instant it was DUE, not from when a (possibly late) thread sent it, so
a stall shows as waiting in every later request; `lateness` (sent - due)
says whether the generator itself kept up.
"""

from __future__ import annotations

import http.client
import json
import queue
import re
import threading
import time

TOKEN_RE = re.compile(r"<\d+>")


def send_stream(host: str, port: int, payload: bytes, t_ref: float,
                timeout: float, want_tokens: int, rec: dict | None = None) -> dict:
    """POST one streaming chat completion. Times are seconds on
    time.monotonic(); `t_ref` is the instant TTFT counts from. A `rec`
    passed in is filled IN PLACE as the stream arrives, so the caller
    can read a request that is still in flight when its window ends."""
    rec = {} if rec is None else rec
    rec.update({
        "ok": False, "status": None, "error": None, "tokens": 0,
        "tokens_after_first": 0, "t_ref": t_ref, "t_sent": time.monotonic(),
        "t_first": None, "t_last": None, "t_done": None,
        "want_tokens": want_tokens, "finish": None, "prompt_tokens": None,
    })
    conn = http.client.HTTPConnection(host, port, timeout=timeout)
    try:
        conn.request(
            "POST", "/v1/chat/completions", body=payload,
            headers={"Content-Type": "application/json"},
        )
        resp = conn.getresponse()
        rec["status"] = resp.status
        if resp.status != 200:
            rec["error"] = f"http_{resp.status}"
            resp.read()
            return rec
        usage_tokens = None
        while True:
            raw = resp.readline()
            if not raw:
                break
            if not raw.startswith(b"data: "):
                continue
            data = raw[6:].strip()
            if data == b"[DONE]":
                break
            obj = json.loads(data)
            if "error" in obj:
                rec["error"] = "stream_error"
                break
            now = time.monotonic()
            for ch in obj.get("choices") or []:
                text = (ch.get("delta") or {}).get("content")
                if text:
                    n = len(TOKEN_RE.findall(text))
                    if rec["t_first"] is None:
                        rec["t_first"] = now
                    else:
                        rec["tokens_after_first"] += n
                    rec["tokens"] += n
                    rec["t_last"] = now
                if ch.get("finish_reason"):
                    rec["finish"] = ch["finish_reason"]
            if obj.get("usage"):
                usage_tokens = obj["usage"].get("completion_tokens")
                rec["prompt_tokens"] = obj["usage"].get("prompt_tokens")
        rec["t_done"] = time.monotonic()
        if usage_tokens is not None:
            rec["usage_tokens"] = int(usage_tokens)
        # Answered in full: as many tokens as asked ("length"), or the
        # model's own earlier stop — never a cut stream or a mislabel.
        n = rec.get("usage_tokens", rec["tokens"])
        rec["ok"] = rec["error"] is None and rec["t_first"] is not None and (
            (rec["finish"] == "length" and n == want_tokens)
            or (rec["finish"] == "stop" and 0 < n <= want_tokens)
        )
        if not rec["ok"] and rec["error"] is None:
            rec["error"] = f"finish={rec['finish']} tokens={n}/{want_tokens}"
    except Exception as e:  # a refused, reset or timed-out connection
        rec["error"] = type(e).__name__
    finally:
        conn.close()
    return rec


def encode_bodies(bodies: list[dict]) -> list[tuple[bytes, int]]:
    return [(json.dumps(b).encode(), int(b["max_tokens"])) for b in bodies]


def run_open_loop(host, port, items, offsets, seconds: float, *,
                  workers: int = 96, timeout: float = 120.0) -> dict:
    """Send items[i] at t0 + offsets[i] regardless of completions. At
    `seconds` the window ends; the records returned are a snapshot of
    every request issued, finished or still in flight (`t_done` None)."""
    jobs: queue.Queue = queue.Queue()
    records: list[dict] = []
    t0 = time.monotonic() + 0.05

    def worker():
        while True:
            job = jobs.get()
            if job is None:
                return
            (payload, want), due, rec = job
            send_stream(host, port, payload, due, timeout, want, rec)

    threads = [threading.Thread(target=worker, daemon=True)
               for _ in range(workers)]
    for t in threads:
        t.start()
    issued: list[float] = []
    for item, off in zip(items, offsets):
        if off >= seconds:
            break
        due = t0 + off
        delay = due - time.monotonic()
        if delay > 0:
            time.sleep(delay)
        rec = {"due": off, "t_ref": due, "t_sent": None, "t_first": None,
               "t_done": None, "ok": False, "tokens": 0,
               "tokens_after_first": 0, "prompt_tokens": None}
        records.append(rec)
        jobs.put((item, due, rec))
        issued.append(off)
    unsent = jobs.qsize()
    rest = t0 + seconds - time.monotonic()
    if rest > 0:
        time.sleep(rest)
    t_end = time.monotonic()
    done = [dict(r) for r in records]  # a snapshot: threads write on
    for _ in threads:
        jobs.put(None)
    return {"records": done, "t0": t0, "t_end": t_end, "issued": issued,
            "unsent_at_end": unsent}


def run_closed_loop(host, port, client_items, seconds: float, *,
                    timeout: float = 120.0, until_done: bool = False,
                    start_gap_s: float = 0.0) -> dict:
    """One thread per client; each sends its next request when the
    previous one completes, until the window ends (`until_done`: until
    every client has sent all it has, for warm-up). Client i sends its
    first request `i * start_gap_s` into the window: sixteen first
    requests in the same instant reach the engine in whatever order
    the server's threads finish decoding them, another one each run."""
    records: list[dict] = []
    lock = threading.Lock()
    t0 = time.monotonic() + 0.05
    t_stop = t0 + seconds

    def client(i, items):
        time.sleep(max(0.0, t0 + i * start_gap_s - time.monotonic()))
        for payload, want in items:
            now = time.monotonic()
            if now >= t_stop:
                return
            rec = {"due": now - t0}
            with lock:
                records.append(rec)
            send_stream(host, port, payload, now,
                        seconds if until_done else timeout, want, rec)
        with lock:
            records.append({"exhausted": True})

    threads = [threading.Thread(target=client, args=(i, it), daemon=True)
               for i, it in enumerate(client_items)]
    for t in threads:
        t.start()
    if until_done:
        for t in threads:
            t.join(seconds)
    else:
        time.sleep(max(0.0, t_stop - time.monotonic()))
    t_end = time.monotonic()
    with lock:
        done = [dict(r) for r in records]
    return {"records": [r for r in done if "exhausted" not in r],
            "t0": t0, "t_end": t_end,
            "exhausted_clients": sum(1 for r in done if "exhausted" in r)}
