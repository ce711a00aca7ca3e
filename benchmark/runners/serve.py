"""Parent side of a serve cell: starts the child that holds the chip,
makes the traffic, warms the cell's shapes, drives the window, scrapes
the counters, and reduces what came back. Never imports jax."""

from __future__ import annotations

import contextlib
import json
import math
import os
import random
import re
import shutil
import signal
import subprocess
import sys
import threading
import time
import urllib.request

from benchmark import loadgen, stats, traffic

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)

# How long a parent waits for the comparison that follows `stop` in the
# cells that decide `correct` after their window: three times the
# slowest healthy one, which is mistral-small-4.doc-qa's on a machine
# that has compiled none of the reference's programs, 270 s (glm-5.
# long-sessions 221-234 s, reasoning 143 s, mixed-queue 88 s; 63 s and
# under once compiled; my chip runs, PR 55, PERF.md section 6).
CHECK_TIMEOUT_S = 810.0
KILL_WAIT_S = 60.0

# The children this process has started and not yet seen dead. A module
# list, because what reads it is the process's own end (`kill_live`, from
# run.py's signal handlers and `atexit`), which has no object to ask.
LIVE: list["Child"] = []


def kill_live() -> None:
    for child in list(LIVE):
        child.kill()


class ChildSilent(SystemExit):
    """The child did not say what was waited for, and has been killed."""


class Child:
    """The process that holds the chip, and the ONE place that starts
    and ends one. It gets a session (so a process group) of its own:
    `kill` signals the group and returns when nothing of it is alive. It
    is told this process's pid and dies with it (`lifeline.
    tie_to_parent`), whatever this process dies of. A runner names its
    own child script in a subclass and changes nothing else."""

    script = "serve_child.py"

    def __init__(self, conf: dict, seed: int, chips: int, rehearse: bool,
                 trace_dir: str, log_path: str):
        env = dict(os.environ)
        env["PYTHONPATH"] = ROOT + os.pathsep + env.get("PYTHONPATH", "")
        self.log = open(log_path, "w")
        self.events: list[dict] = []
        self.asked_to_stop = False
        self.proc = subprocess.Popen(
            [sys.executable, os.path.join(HERE, self.script),
             "--config", json.dumps(conf), "--seed", str(seed),
             "--chips", str(chips), "--rehearse", str(int(rehearse)),
             "--trace-dir", trace_dir, "--parent-pid", str(os.getpid())],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE, stderr=self.log,
            text=True, cwd=ROOT, env=env, start_new_session=True,
        )
        self.pgid = self.proc.pid  # a session leader leads its group
        LIVE.append(self)

    def wait_for(self, event: str, timeout: float) -> dict:
        """Next `event` line from the child (other stdout is logged)."""
        deadline = time.monotonic() + timeout
        box: list = []

        def read():
            for line in self.proc.stdout:
                try:
                    obj = json.loads(line)
                except ValueError:
                    obj = None
                if not isinstance(obj, dict) or "event" not in obj:
                    self.log.write(line)
                    continue
                self.events.append(obj)
                if obj["event"] == event:
                    box.append(obj)
                    return
            box.append(None)

        t = threading.Thread(target=read, daemon=True)
        t.start()
        t.join(max(0.0, deadline - time.monotonic()))
        if not box or box[0] is None:
            how = (f"within {timeout:.0f} s" if not box
                   else f"(exit {self.proc.poll()})")
            self.kill()
            raise ChildSilent(
                f"serve child: no {event!r} {how}; see {self.log.name}")
        return box[0]

    def tell(self, cmd: str, event: str, timeout: float = 120.0) -> dict:
        self.asked_to_stop |= cmd == "stop"
        self.proc.stdin.write(cmd + "\n")
        self.proc.stdin.flush()
        return self.wait_for(event, timeout)

    def check_after_window(self, timeout: float = CHECK_TIMEOUT_S) -> dict:
        """`stop` after `disarm`: the child closes its server and holds
        what the window served to the reference. A comparison that has
        not ended in `timeout` seconds is a run that is not correct,
        with a problem that says so; the child is dead by then."""
        try:
            return self.tell("stop", "logit_check", timeout)
        except ChildSilent as e:
            return {"ok": False, "problem": f"no comparison: {e}"}

    def stop(self) -> None:
        """Ask, wait 60 s, kill. On the way out of a fault (an exception
        in flight) nobody will read an answer: kill at once."""
        if self.proc.poll() is None and sys.exc_info()[0] is None:
            try:
                if not self.asked_to_stop:
                    self.proc.stdin.write("stop\n")
                    self.proc.stdin.flush()
                self.proc.wait(timeout=60)
            except (OSError, ValueError, subprocess.TimeoutExpired):
                # fault-boundary: a child that is gone or deaf is killed
                # below, which is all that stopping it asks for
                self.log.write("serve child did not stop on request\n")
        self.kill()

    def kill(self) -> None:
        """SIGKILL to the child's group; returns when the child is
        reaped and no process of the group is left alive."""
        if self not in LIVE:
            return
        deadline = time.monotonic() + KILL_WAIT_S
        while group_alive(self.pgid):
            with contextlib.suppress(ProcessLookupError, PermissionError):
                os.killpg(self.pgid, signal.SIGKILL)
            self.proc.poll()  # reaps the child once it is dead
            if time.monotonic() > deadline:
                self.log.write(f"group {self.pgid} outlived SIGKILL by "
                               f"{KILL_WAIT_S:.0f} s\n")
                break
            time.sleep(0.02)
        self.proc.wait()
        with contextlib.suppress(ValueError):  # a signal's kill_live got here first
            LIVE.remove(self)
        self.log.flush()


def group_alive(pgid: int) -> bool:
    """Whether any process of the group is alive. A zombie is not: it
    holds no chip and no port, and whoever inherits it reaps it."""
    try:
        pids = [d for d in os.listdir("/proc") if d.isdigit()]
    except OSError:  # no /proc: ask the kernel, which counts zombies too
        with contextlib.suppress(ProcessLookupError):
            os.killpg(pgid, 0)
            return True
        return False
    for pid in pids:
        try:
            with open(f"/proc/{pid}/stat") as f:
                # pid (comm) state ppid pgrp ...: comm may hold spaces
                state, _, pgrp = f.read().rsplit(")", 1)[1].split()[:3]
        except (OSError, ValueError):
            continue  # gone while we looked
        if int(pgrp) == pgid and state not in "ZX":
            return True
    return False


class Phases:
    """Wall seconds of a run's phases, for run.py's info line: `mark`
    gives the named phase the time since the mark before it (the first
    one: since the process started), so the phases add up to the run as
    far as the last mark. Each mark is also a line `phase <name>
    <seconds>` in the child's log, the moment it is made."""

    def __init__(self, t_start: float, log):
        self._last = t_start
        self._log = log
        self.seconds: dict[str, float] = {}

    def mark(self, name: str) -> None:
        now = time.monotonic()
        self.seconds[name] = now - self._last
        self._last = now
        # as it happens, for whoever watches the run
        self._log.write(f"phase {name} {self.seconds[name]:.3f}\n")
        self._log.flush()


def check_problems(check: dict, want_kinds) -> list[str]:
    """What a comparison after the window (`Child.check_after_window`)
    leaves to say: that it never came, which clauses failed, which kinds
    of request the window finished none of."""
    if check.get("problem"):
        return [check["problem"]]
    problems = []
    if not check["ok"]:
        failed = [k for k, v in check.get("passed", {}).items() if not v]
        problems.append(f"the served tokens' check failed: {', '.join(failed)}")
    kinds = {w["kind"] for w in check.get("sample", [])}
    if not set(want_kinds) <= kinds:
        problems.append(f"the window finished no "
                        f"{sorted(set(want_kinds) - kinds)} request to compare")
    return problems


_SAMPLE = re.compile(r"^([a-zA-Z_:][\w:]*)(\{[^}]*\})?\s+([0-9.eE+-]+|NaN)$")


def scrape(port: int) -> dict:
    """GET /metrics -> {family: sum over label sets} plus every
    labelled sample under its full `family{labels}` name."""
    with urllib.request.urlopen(
        f"http://127.0.0.1:{port}/metrics", timeout=30
    ) as r:
        text = r.read().decode()
    out: dict[str, float] = {}
    for line in text.splitlines():
        m = _SAMPLE.match(line)
        if not m:
            continue
        fam, labels, val = m.group(1), m.group(2) or "", float(m.group(3))
        fam = fam.removeprefix("oryx_serving_")
        if fam.endswith("_bucket"):
            continue  # histograms: the _sum and _count samples suffice
        out[fam] = out.get(fam, 0.0) + val
        if labels:
            out[fam + labels] = val
    return out


# A request still decoding when the window ends has a time per token
# once it has streamed this many tokens after the first.
MIN_TOKENS_FOR_TPOT = 16


def reduce_requests(res: dict, *, first_token_limit_s: float | None) -> dict:
    """Window records -> the numbers the metrics read, over EVERY
    request issued inside the window, open loop or closed.

    Time to first token: a request whose first token arrived counts
    with that time; one still waiting when the window ends counts with
    the wait it has had so far (window end - the instant it counts
    from), a lower bound — so a stall in the window's last seconds
    moves the tail like any other. One that has waited longer than the
    mix's `first_token_limit_s` (where it states one) is counted as
    failed instead. TPOT is over requests that finished, or streamed
    MIN_TOKENS_FOR_TPOT tokens after the first, inside the window.
    Failed requests rank as +inf in both tails. Tokens per second are
    all tokens streamed inside the window over its length, whether
    their request finished or not."""
    t0, t_end = res["t0"], res["t_end"]
    recs = res["records"]
    done = [r for r in recs if r.get("t_done") is not None]
    ok = [r for r in done if r["ok"]]
    failed = [r for r in done if not r["ok"]]
    flying = [r for r in recs if r.get("t_done") is None]
    started = [r for r in flying if r.get("t_first") is not None]
    waiting = [r for r in flying if r.get("t_first") is None]
    limit = math.inf if first_token_limit_s is None else first_token_limit_s
    overdue = [r for r in waiting if t_end - r["t_ref"] > limit]
    n_failed = len(failed) + len(overdue)
    ttft = [(r["t_first"] - r["t_ref"]) * 1e3 for r in ok + started] + [
        (t_end - r["t_ref"]) * 1e3 for r in waiting
        if t_end - r["t_ref"] <= limit
    ]
    tpot = [
        (r["t_last"] - r["t_first"]) / r["tokens_after_first"] * 1e3
        for r in ok + started
        if r["tokens_after_first"] >= (1 if r in ok else MIN_TOKENS_FOR_TPOT)
    ]
    lateness = [(r["t_sent"] - r["t_ref"]) * 1e3 for r in recs
                if r.get("t_sent") is not None]
    tokens = sum(r["tokens"] for r in ok + started)
    return {
        "attempted": len(done) + len(overdue), "failed": n_failed,
        "completed": len(ok), "first_token_only": len(started),
        "waiting_at_end": len(waiting), "overdue": len(overdue),
        "window_s": t_end - t0, "ttft_n": len(ttft), "tpot_n": len(tpot),
        "ttft_ms": ttft, "tpot_ms": tpot, "lateness_ms": lateness,
        "completion_tokens": tokens,
        "prompt_tokens": sum(r["prompt_tokens"] or 0 for r in ok),
        "inflight_at_end": len(flying),
        "errors": sorted({str(r.get("error")) for r in failed})[:8],
        "ttft_p50_ms": stats.percentile(ttft, 50, failed=n_failed),
        "ttft_p90_ms": stats.quantile_hd(ttft, 90, failed=n_failed),
        "tpot_p50_ms": stats.percentile(tpot, 50, failed=n_failed),
        "tpot_p90_ms": stats.quantile_hd(tpot, 90, failed=n_failed),
        "serve_tok_s": tokens / (t_end - t0),
    }


def warm_copy_on_write(port: int, page_size: int, seed: int) -> None:
    """Compile the prefix cache's copy-on-write program before the
    window. It runs when a prompt is matched WHOLE (the cache must
    leave one token to prefill, which then lands mid-page), and cached
    blocks are whole pages, so that takes a prompt of an exact multiple
    of the page size sent twice. The template's own token count is read
    from a probe's usage, then the prompt is padded to the boundary."""
    def send(text):
        body = traffic.chat_body([{"role": "user", "content": text}], 4)
        (payload, want), = loadgen.encode_bodies([body])
        r = loadgen.send_stream("127.0.0.1", port, payload,
                                time.monotonic(), 900.0, want)
        if not r["ok"]:
            raise SystemExit(f"serve: warm-up request failed: {r}")
        return r["prompt_tokens"]

    text = traffic.text_of(random.Random(seed ^ 0xC0), 40)
    text += "x" * ((-send(text)) % page_size)
    send(text)
    send(text)


def run(ctx: dict, *, open_loop: bool) -> dict:
    """ctx: workload, config (resolved), seed, seconds, trace, rehearse,
    chips, out_dir, t_start (process start on time.monotonic())."""
    wl, conf = ctx["workload"], ctx["config"]
    seconds, seed = ctx["seconds"], ctx["seed"]
    p = wl["traffic"]
    trace_dir = os.path.join(ctx["out_dir"], "trace")
    shutil.rmtree(trace_dir, ignore_errors=True)
    child = Child(conf, seed, ctx["chips"], ctx["rehearse"], trace_dir,
                  os.path.join(ctx["out_dir"], "serve_child.log"))
    ph = Phases(ctx["t_start"], child.log)
    try:
        # Traffic is made while the child initialises and compiles.
        if open_loop:
            rate = p["arrivals"]["rate"]
            n = max(1, int(round(rate * seconds)))
            sessions = traffic.build_sessions(p, seed, n)
            order = interleave(sessions, p.get("concurrent_sessions", 24))
            base = traffic.arrival_offsets(
                p["arrivals"], len(order),
                random.Random(p.get("order_seed", 0)),
            )
            # Every seed gets the one fixed schedule, unrotated: the
            # seed makes the words and the weights (traffic.py says why).
            items = loadgen.encode_bodies(order)
            offsets = base
        else:
            clients = p["clients"]
            n = int(clients * seconds * p.get("max_requests_per_client_s", 1.0))
            sessions = traffic.build_sessions(p, seed, n)
            per_client = [[] for _ in range(clients)]
            for i, s in enumerate(sessions):
                per_client[i % clients].extend(s)
            # The seed rotates each client's list. With the start gap a
            # seed repeats itself to 0.001 % and six seeds spread 3 %:
            # a steady spread to set a bound from. Unrotated, every
            # seed does the same work and runs differ by a decode chunk
            # (0.6 %) or, now and then, a request (3.7 %): an IQR of
            # six such runs reads anything from 0 to 4 % (PERF.md
            # section 6, PR 23; section 7 keeps the question open).
            client_items = [
                loadgen.encode_bodies(traffic.rotated(c, seed))
                for c in per_client
            ]
        dev = child.wait_for("device", 600)
        ph.mark("device")
        # The embed widths a prompt can be padded to are the program's
        # own (each a small compiled program): the child reads them.
        warm = loadgen.encode_bodies(
            traffic.warmup_bodies(p, dev["embed_buckets"], seed)
        )
        ready = child.wait_for("ready", ctx["setup_timeout"])
        port = ready["port"]
        ph.mark("ready")
        check = next(e for e in child.events if e["event"] == "logit_check")
        ph.seconds["comparison_in_ready"] = check["seconds"]

        # Warm-up: every shape once, then a few at once so that the
        # engine has admitted into a running batch.
        t_w = time.monotonic()
        for payload, want in warm:
            r = loadgen.send_stream("127.0.0.1", port, payload,
                                    time.monotonic(), 900.0, want)
            if not r["ok"]:
                raise SystemExit(f"serve: warm-up request failed: {r}")
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
        before = scrape(port)
        setup_s = time.monotonic() - ctx["t_start"]
        ph.mark("arm")
        tracer, slice_ = None, {}
        if ctx["trace"]:
            tracer = threading.Thread(
                target=_trace_slice, daemon=True,
                args=(child, port, seconds, wl.get("trace_seconds", 3.0),
                      slice_),
            )
            tracer.start()
        if open_loop:
            res = loadgen.run_open_loop(
                "127.0.0.1", port, items, offsets, seconds,
                workers=p.get("workers", 128),
            )
        else:
            res = loadgen.run_closed_loop(
                "127.0.0.1", port, client_items, seconds,
                start_gap_s=p.get("start_gap_s", 0.0),
            )
        after = scrape(port)
        if tracer is not None:
            tracer.join()
        ph.mark("window")
        end = child.tell("disarm", "disarmed", 300.0)
        ph.mark("disarm")
    finally:
        child.stop()
    ph.mark("stop")
    red = reduce_requests(
        res, first_token_limit_s=p.get("first_token_limit_s"))
    delta = {k: after.get(k, 0.0) - before.get(k, 0.0) for k in after}
    compiles = end.get("compiles")
    raw = {"ttft_ms": red.pop("ttft_ms"), "tpot_ms": red.pop("tpot_ms")}
    lateness = red.pop("lateness_ms")
    problems = []
    if not check["ok"]:
        problems.append("logit check failed")
    if compiles:
        problems.append(f"{compiles} compiles inside the window: "
                        f"{end.get('compile_counts')}")
    if red["failed"]:
        problems.append(
            f"{red['failed']} requests failed ({red['overdue']} of them "
            f"waited over the limit for a first token): {red['errors']}")
    if red["completed"] == 0:
        problems.append("no request completed")
    if not open_loop and res.get("exhausted_clients"):
        problems.append("a client ran out of requests before the window "
                        "ended: raise max_requests_per_client_s")
    device = dict(dev["device"], memory_peak_bytes=end["memory_peak_bytes"])
    tr = end.get("trace") or {}
    if tr:
        tr["slice_counters"] = slice_.get("counters", {})
        tr["slice_kv_bytes"] = slice_kv_bytes(res, slice_, conf)
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


def _trace_slice(child: Child, port: int, seconds: float, trace_s: float,
                 out: dict) -> None:
    """Trace a slice in the middle of the window; scrape the counters
    at both ends of it so that trace times have counts to divide by."""
    time.sleep(max(0.0, seconds * 0.4))
    child.tell("trace_start", "trace_started")
    out["t_start"], c0 = time.monotonic(), scrape(port)
    time.sleep(min(trace_s, seconds * 0.4))
    out["t_stop"], c1 = time.monotonic(), scrape(port)
    child.tell("trace_stop", "trace_stopped", 300.0)
    out["counters"] = {k: c1.get(k, 0.0) - c0.get(k, 0.0) for k in c1}


def slice_kv_bytes(res: dict, slice_: dict, conf: dict) -> float:
    """Bytes of KV pages the decode steps inside the traced slice had
    to read, from the client-side records: a request decoding from
    t_first to t_last makes one step per token after the first, each
    reading its whole context (prompt + what it has generated)."""
    from benchmark import costs

    if "t_start" not in slice_:
        return 0.0
    a, b = slice_["t_start"], slice_["t_stop"]
    lay, total = conf["layout"], 0.0
    for r in res["records"]:
        if not r.get("t_first") or not r.get("tokens_after_first"):
            continue
        span = r["t_last"] - r["t_first"]
        ov = min(b, r["t_last"]) - max(a, r["t_first"])
        if span <= 0 or ov <= 0:
            continue
        steps = r["tokens_after_first"] * ov / span
        ctx_len = (r["prompt_tokens"] or 0) + r["tokens"] / 2
        total += steps * costs.paged_kv_bytes(
            [ctx_len], hk=conf["num_key_value_heads"], d=conf["head_dim"],
            page_size=lay["page_size"], layers=conf["num_hidden_layers"],
        )
    return total


def interleave(sessions: list[list], concurrent: int) -> list:
    """Open-loop order: groups of `concurrent` sessions advance a turn
    at a time, so a session's next turn is due well after its last one
    was (the history it re-sends is then in the prefix cache)."""
    out = []
    for g in range(0, len(sessions), concurrent):
        group = sessions[g:g + concurrent]
        for t in range(max(len(s) for s in group)):
            out.extend(s[t] for s in group if t < len(s))
    return out
