"""OpenAI-compatible chat API server over the continuous-batching engine.

Beyond-parity serving front-end (the reference ships only a CLI/Gradio
demo; SURVEY.md §2 "Inference example / demo"): an HTTP endpoint speaking
the `/v1/chat/completions` schema so existing OpenAI-client tooling
points at an Oryx-TPU model unchanged. Stdlib-only (http.server) — no
web-framework dependency.

  POST /v1/chat/completions
    {"model": "...", "messages": [{"role": "user", "content": ...}],
     "max_tokens": 64, "stream": false}
  GET /v1/models
  GET /healthz          (liveness: the process is up)
  GET /readyz           (readiness: engine loop alive + un-stalled;
                         503 with a reason otherwise)
  GET /metrics          (Prometheus text format, build_info gauge,
                         HBM gauges, oryx_anomaly_total on SLO breach)
  GET /debug/requests   (flight recorder: last N requests, in-flight
                         too; ?limit=K bounds the response, ?state=
                         active|done|error filters — both built to stay
                         usable mid load-sweep; finished entries carry
                         the per-request cost ledger in meta.cost;
                         ?format=jsonl exports the wide-event log —
                         one canonical JSON line per terminal request,
                         schema utils.metrics.REQUEST_EVENT_KEYS)
  GET /debug/trace?id=  (one request's span tree as Chrome trace JSON —
                         loads in Perfetto; id from the X-Request-Id
                         header every response carries. Client-supplied
                         X-Request-Id values are honored end-to-end —
                         sanitized, minted on absence/collision — and a
                         router-propagated X-Oryx-Trace header adopts
                         the fleet-wide id + records the parent span)
  GET /debug/timeline   (the engine flight data recorder: ?n= newest
                         per-step records — dispatch kind/rows/wall
                         time, live slots, accepted tokens, queue
                         depth, free pages, degraded mode, sampled
                         device_us — plus cumulative dispatch-kind
                         counts that reconcile with
                         oryx_serving_dispatches_total)
  GET /debug/pages      (page-pool observatory: the live ownership map
                         — per page free/slot/cache/shared, refcount,
                         owner tags, tenancy age — ?format=summary for
                         just the derived counts/fragmentation, which
                         reconcile with the oryx_pool_* gauges on a
                         quiesced engine)
  GET /debug/oom        (OOM forensics: ?n= newest memory-pressure
                         records — pool summary, top-K residents with
                         ledgers, cache LRU tail, timeline tail —
                         captured at every OutOfPagesError and
                         degraded-mode escalation)
  GET /debug/audit      (output-quality observatory: ?n= newest audit
                         records — verdict, first-divergence position,
                         per-position logit max-abs-diff/KL, top-k
                         logit table, both token streams' tails — plus
                         monotone verdict counts that reconcile
                         exactly with oryx_audit_total{verdict=} and
                         the pending/dropped sampler view. Armed with
                         --audit-sample-every N; the ring and counters
                         render empty/zero when off)
  GET /debug/profile    (on-demand device-time capture: bracket the
                         next ?steps=K dispatches in one jax.profiler
                         capture; returns a Perfetto-loadable Chrome
                         trace + per-kind device-time split. 503 on an
                         idle engine)

Content may be a plain string or OpenAI content-part lists; image parts
(`{"type": "image_url", "image_url": {"url": "data:image/...;base64,..."
| "file:///path" | "/path"}}`) attach media to the turn. Multi-turn
history maps onto the conversation template; media bind to the FIRST
user turn (as everywhere in this framework) and are rejected elsewhere
with a 400. `temperature`, `top_p`, `stop` and `seed` are honored per
request (per slot: differing requests share one resident batch); `n > 1`
and `logprobs` are rejected with a 400 rather than silently ignored.

One request path: every request (streaming and not) goes from the HTTP
handler through `Engine.submit` into the continuous-batching scheduler
(serve/scheduler.py): a fixed slot array decoding over a paged KV cache,
with admission and retirement at chunk boundaries. `GET /metrics`
(Prometheus text format) reports queue depth, slot occupancy,
admitted/evicted counts and TTFT / per-token latency histograms.

    python -m oryx_tpu.serve.api_server --model-path models/oryx7b-sft \
        [--shard tp=8] [--port 8000]
"""

from __future__ import annotations

import argparse
import base64
import io
import json
import os
import subprocess
import threading
import time
import urllib.parse
import uuid
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Any

import numpy as np

from oryx_tpu.analysis import sanitizers
from oryx_tpu.serve import journal as journal_lib
from oryx_tpu.utils import faults
from oryx_tpu.utils import trace as trace_lib


def _git_revision() -> str:
    """Best-effort build identity for the build_info metric: git HEAD
    of the source tree, or ORYX_GIT_REV when deployed from an export."""
    if rev := os.environ.get("ORYX_GIT_REV"):
        return rev
    try:
        return subprocess.run(
            ["git", "rev-parse", "--short", "HEAD"],
            cwd=os.path.dirname(os.path.abspath(__file__)),
            capture_output=True, text=True, timeout=5,
        ).stdout.strip() or "unknown"
    except Exception:
        return "unknown"


def _decode_image(url: str, *, allow_local_files: bool) -> np.ndarray:
    """data: URI (base64) or — when explicitly allowed — a file
    path/URI → HWC uint8 array. Local paths are opt-in: a network
    client must not be able to make the server open arbitrary files."""
    if url.startswith("data:"):
        from PIL import Image

        b64 = url.split(",", 1)[1]
        img = Image.open(io.BytesIO(base64.b64decode(b64)))
        return np.asarray(img.convert("RGB"))
    if not allow_local_files:
        raise ValueError(
            "image_url must be a data: URI (local file paths require "
            "--allow-local-files)"
        )
    from oryx_tpu.data import media

    path = url[len("file://"):] if url.startswith("file://") else url
    return media.load_image(path)


def parse_messages(
    messages: list[dict[str, Any]],
    *,
    allow_local_files: bool = False,
) -> tuple[str, list[tuple[str, str]], list[np.ndarray]]:
    """OpenAI messages → (current question, (user, assistant) history,
    images). The last message must be a user turn; system messages are
    folded into the next user text (the conversation template carries
    its own system prompt)."""
    turns: list[tuple[str, str | None]] = []
    images: list[np.ndarray] = []
    pending_system = ""
    for m in messages:
        role, content = m.get("role"), m.get("content", "")
        if role == "developer":  # OpenAI's modern alias for system
            role = "system"
        if role in ("tool", "function"):
            raise ValueError(
                "tool/function messages are not supported "
                "(this model has no tool-calling)"
            )
        if role not in ("system", "user", "assistant"):
            raise ValueError(f"unsupported message role {role!r}")
        text_parts: list[str] = []
        msg_images: list[np.ndarray] = []
        if isinstance(content, str):
            text_parts.append(content)
        else:
            for part in content:
                if part.get("type") == "text":
                    text_parts.append(part.get("text", ""))
                elif part.get("type") == "image_url":
                    msg_images.append(_decode_image(
                        part["image_url"]["url"],
                        allow_local_files=allow_local_files,
                    ))
        if msg_images:
            # The conversation template binds media to the FIRST user
            # turn; accepting them elsewhere would silently re-pin them
            # (diverging from OpenAI's attach-to-carrier semantics), so
            # reject instead.
            if role != "user":
                raise ValueError(
                    f"image parts are only supported on user messages "
                    f"(got {role!r})"
                )
            if turns:
                raise ValueError(
                    "images must attach to the FIRST user message: this "
                    "model binds all media to the conversation's opening "
                    "turn"
                )
            images.extend(msg_images)
        text = "\n".join(t for t in text_parts if t)
        if role == "system":
            # Multiple system messages concatenate (never overwrite).
            pending_system = (
                f"{pending_system}\n{text}" if pending_system else text
            )
        elif role == "user":
            if pending_system:
                text = f"{pending_system}\n{text}" if text else pending_system
                pending_system = ""
            turns.append((text, None))
        elif role == "assistant":
            if not turns or turns[-1][1] is not None:
                raise ValueError("assistant message without a user turn")
            turns[-1] = (turns[-1][0], text)
    if pending_system:
        raise ValueError("system message must precede a user turn")
    if not turns or turns[-1][1] is not None:
        raise ValueError("the last message must be from the user")
    question = turns[-1][0]
    history = turns[:-1]
    if any(a is None for _, a in history):
        raise ValueError("history user turns must alternate with assistant")
    return question, history, images


class EngineSupervisor(threading.Thread):
    """Watches the continuous scheduler's engine thread and restarts
    it after a crash: `scheduler.restart()` requeues every in-flight
    request for deterministic replay, rebuilds the page pool (invariant
    checked), and /readyz flips 503 -> 200 around the window. Bounded:
    more than `max_restarts` deaths inside `window_s` means the failure
    is systemic — the supervisor gives up and leaves /readyz at 503 so
    a load balancer ejects the replica instead of feeding a crash
    loop."""

    def __init__(self, scheduler, *, poll_s: float = 0.25,
                 max_restarts: int = 5, window_s: float = 60.0):
        super().__init__(daemon=True, name="engine-supervisor")
        self.scheduler = scheduler
        # The scheduler queues through an engine-death window only
        # while someone is committed to reviving it; submit() rejects
        # on a dead engine otherwise. (set_supervised takes _cond —
        # the flag is read by submit under the same lock.)
        scheduler.set_supervised(True)
        self.poll_s = poll_s
        self.max_restarts = max_restarts
        self.window_s = window_s
        # Written by this thread at give-up, read by /readyz handler
        # threads: an Event, not a bare bool.
        self._gave_up = threading.Event()
        # NOT named `_stop`: threading.Thread has a private _stop()
        # METHOD that is_alive() calls internally — shadowing it with
        # an Event makes is_alive() raise TypeError once the thread
        # finishes (latent since PR 6; surfaced by the armed race
        # detector calling is_alive() on prior accessor threads).
        self._halt = threading.Event()
        # Only the supervisor thread prunes/appends the restart
        # window after construction.
        self._restart_times: list[float] = []  # thread-owned: supervisor

    @property
    def gave_up(self) -> bool:
        return self._gave_up.is_set()

    def stop(self) -> None:
        self.scheduler.set_supervised(False)
        self._halt.set()

    def run(self) -> None:
        while not self._halt.wait(self.poll_s):
            s = self.scheduler
            if s.stopping:
                return  # deliberate shutdown/drain: nothing to revive
            if s.alive() or self.gave_up:
                continue
            now = time.monotonic()
            self._restart_times = [
                t for t in self._restart_times
                if now - t < self.window_s
            ]
            if len(self._restart_times) >= self.max_restarts:
                # Systemic failure: stop reviving, stop accepting
                # (submit rejects once `supervised` clears), and fail
                # every stranded request — a hung client is worse
                # than a 503.
                self._gave_up.set()
                s.set_supervised(False)
                try:
                    s.fail_inflight(
                        "engine dead (supervisor gave up after "
                        f"{self.max_restarts} restarts in "
                        f"{self.window_s:g}s)"
                    )
                # fault-boundary: a failing cleanup must not kill the
                # supervisor before it reaches its give-up endpoint
                except Exception:
                    import traceback

                    traceback.print_exc()
                continue
            self._restart_times.append(now)
            try:
                s.restart()
            # A restart that itself crashes (pool rebuild failed?)
            # counts against the budget and is retried next poll —
            # the supervisor must outlive it to reach its bounded
            # give-up endpoint.
            # fault-boundary: failed restart retried next poll
            except Exception:
                import traceback

                traceback.print_exc()


def _parse_sampling(req: dict[str, Any]) -> dict[str, Any]:
    """Validate OpenAI sampling fields → the per-request sampling dict
    `Engine.submit` takes. Unsupported values raise (→ 400) instead of
    being silently ignored."""
    if int(req.get("n", 1)) != 1:
        raise ValueError("n > 1 is not supported")
    if req.get("logprobs"):
        raise ValueError("logprobs is not supported")
    out: dict[str, Any] = {}
    # temperature/top_p become STATIC jit arguments downstream (one
    # compiled decode per distinct value) — quantize to 2 decimals so a
    # client sweeping arbitrary floats can't force unbounded recompiles.
    if (t := req.get("temperature")) is not None:
        t = float(t)
        if not 0.0 <= t <= 2.0:
            raise ValueError(f"temperature must be in [0, 2], got {t}")
        out["temperature"] = round(t, 2)
    if (p := req.get("top_p")) is not None:
        p = float(p)
        if not 0.0 < p <= 1.0:
            raise ValueError(f"top_p must be in (0, 1], got {p}")
        out["top_p"] = round(p, 2)
    if (stop := req.get("stop")) is not None:
        if isinstance(stop, str):
            stop = [stop]
        if not (
            isinstance(stop, list)
            and all(isinstance(s, str) for s in stop)
            and len(stop) <= 8
        ):
            raise ValueError("stop must be a string or <=8 strings")
        out["stop"] = [s for s in stop if s]
    if (seed := req.get("seed")) is not None:
        out["seed"] = int(seed)
    return out


def _completion_body(
    model: str, reply: str, finish_reason: str = "stop",
    usage: tuple[int, int] | None = None,
    request_id: str | None = None,
) -> dict[str, Any]:
    body = {
        # The completion id embeds the server-side request id, so a
        # client log line can be joined to /debug/trace without the
        # header plumbing.
        "id": f"chatcmpl-{request_id or uuid.uuid4().hex[:24]}",
        "object": "chat.completion",
        "created": int(time.time()),
        "model": model,
        "choices": [{
            "index": 0,
            "message": {"role": "assistant", "content": reply},
            "finish_reason": finish_reason,
        }],
    }
    if usage is not None:
        prompt, completion = usage
        body["usage"] = {
            "prompt_tokens": prompt,
            "completion_tokens": completion,
            "total_tokens": prompt + completion,
        }
    return body


def _chunk_body(
    model: str, cid: str, delta: str | None, finish_reason: str = "stop",
    *,
    usage_field: bool = False,
    usage: dict[str, int] | None = None,
) -> dict[str, Any]:
    """One chat.completion.chunk. usage_field=True adds the "usage" key
    per OpenAI's stream_options.include_usage contract: null on every
    delta chunk, totals on the FINAL chunk (which carries empty choices —
    pass usage with delta=None and it replaces the finish chunk's
    choice)."""
    choice: dict[str, Any] = {"index": 0, "delta": {}, "finish_reason": None}
    if delta is None:
        choice["finish_reason"] = finish_reason
    else:
        choice["delta"] = {"content": delta}
    choices = [] if usage else [choice]
    body: dict[str, Any] = {
        "id": cid, "object": "chat.completion.chunk",
        "created": int(time.time()), "model": model, "choices": choices,
    }
    if usage_field:
        body["usage"] = usage
    return body


def build_server(
    pipe,
    *,
    model_name: str = "oryx-tpu",
    host: str = "127.0.0.1",
    port: int = 8000,
    allow_local_files: bool = False,
    max_tokens_limit: int = 2048,
    engine: str = "continuous",
    num_slots: int = 4,
    page_size: int = 64,
    decode_chunk: int = 8,
    max_ctx: int = 2048,
    prefill_chunk: int | None = None,
    prefix_cache: bool = True,
    ragged: bool = False,
    speculate: int = 0,
    draft_model: str | None = None,
    kv_dtype: str = "bf16",
    host_cache_bytes: int = 0,
    audit_tol_maxdiff: float | None = None,
    audit_tol_kl: float | None = None,
    profile_sample_every: int = 0,
    audit_sample_every: int = 0,
    numerics_every: int = 0,
    stall_timeout: float | None = None,
    flight_recorder_size: int = 256,
    ttft_slo: float | None = None,
    queue_depth_slo: int | None = None,
    events_path: str | None = None,
    max_queue: int | None = 256,
    request_timeout: float | None = None,
    degraded_cooldown: float = 30.0,
    supervise: bool = True,
    faults_spec: str | None = None,
    replica_id: str | None = None,
    requests_log_path: str | None = None,
    requests_log_max_bytes: int = 16 * 1024 * 1024,
    journal_path: str | None = None,
    journal_max_bytes: int = 64 * 1024 * 1024,
) -> ThreadingHTTPServer:
    """Construct (not start) the HTTP server around a pipeline.

    engine: a name in the Engine registry (serve/engine.py).
    "continuous" routes EVERYTHING — streaming and not — through the
    continuous-batching scheduler (serve/scheduler.py): a fixed slot
    array over a paged KV cache, admission at chunk boundaries, per-slot
    sampling; "sharded" is the same scheduler with a tensor-parallel
    mesh REQUIRED (KV pool heads-sharded over tp).
    Every engine exports GET /metrics; GET /readyz reports the
    engine's own readiness() (loop alive, un-stalled, not draining) so
    load balancers never have to probe with real completions.

    replica_id: this backend's identity in a multi-replica deployment
    — lands as the `replica` label on build_info so the router's
    aggregated scrape (serve/router.py /metrics/aggregate) can
    distinguish backends even before it injects its own labels.

    ttft_slo / queue_depth_slo arm the serving anomaly detectors
    (utils/anomaly.py): breaches increment oryx_anomaly_total{kind=}
    and, with events_path, append structured JSONL events.

    Failure containment (docs/OBSERVABILITY.md
    "Failure playbook"): max_queue bounds admission (full -> 429 +
    Retry-After), request_timeout deadlines every request (-> 504),
    the SLO detectors drive a degraded-mode ladder (gauge
    oryx_serving_degraded_mode), an EngineSupervisor restarts a dead
    engine thread with deterministic request replay, and
    `srv.begin_drain()` (SIGTERM in main()) flips /readyz to 503,
    stops admission and finishes resident decodes. faults_spec arms
    the deterministic fault-injection registry (utils/faults.py) —
    chaos testing only, never in production config.
    """
    from oryx_tpu.serve import engine as engine_lib
    from oryx_tpu.utils.anomaly import AnomalyMonitor, AnomalyThresholds
    from oryx_tpu.utils.metrics import ServingMetrics
    from oryx_tpu.utils.request_log import RequestLog

    if faults_spec:
        faults.configure(faults_spec)

    if speculate and not ragged:
        # Fail fast: drafts are extra lanes of the fused ragged
        # dispatch — accepting the flag without --ragged would promise
        # multi-token steps that never happen.
        raise ValueError(
            "--speculate requires --ragged (draft tokens ride the "
            "fused packed dispatch as extra verify lanes)"
        )
    if draft_model and not speculate:
        raise ValueError(
            "--draft-model requires --speculate (the draft model "
            "proposes speculative tokens; without a verify lane count "
            "it would never be consulted)"
        )
    # $ORYX_LOCK_SANITIZER=1 arms the lock-order sanitizer + race
    # detector for this server (chaos/test runs). Armed BEFORE the
    # metrics registry and scheduler are built so every named lock
    # they create is instrumented; the registry is bound right after
    # so the oryx_lock_{wait,hold}_seconds histograms flush into
    # /metrics.
    sanitizers.maybe_arm_from_env()
    metrics = ServingMetrics()
    build_labels = {
        "revision": _git_revision(), "engine": engine,
        "model": model_name,
    }
    if replica_id:
        # Multi-replica identity: the router's aggregated scrape keys
        # backends on this label (and stamps its own replica= on every
        # series it re-exports).
        build_labels["replica"] = replica_id
    metrics.set_info("build_info", build_labels)
    if faults.armed():
        faults.bind_registry(metrics.registry)
    sanitizers.bind_lock_metrics(metrics.registry)
    anomaly = AnomalyMonitor(
        source="serve",
        thresholds=AnomalyThresholds(
            ttft_slo_s=ttft_slo, queue_depth_slo=queue_depth_slo,
        ),
        events_path=events_path,
        registry=metrics.registry,
    )
    # One flight recorder for the whole server: the last
    # `flight_recorder_size` requests — in-flight and finished — served
    # by GET /debug/requests, with per-request span trees (queue-wait →
    # prefill → decode chunks → emission) at GET /debug/trace?id=.
    tracer = trace_lib.Tracer(flight_recorder_size)
    # Drain state shared across handler threads: set once by
    # begin_drain(), read by /readyz and every POST.
    draining = threading.Event()
    # Wide-event request log (utils/request_log.py): one JSONL event per
    # terminal request, in-memory always (the
    # /debug/requests?format=jsonl export), on disk when --requests-log
    # names a path (size-capped rotation).
    request_log = RequestLog(
        requests_log_path, max_bytes=requests_log_max_bytes
    )
    # Decision journal (serve/journal.py): the engine flight recorder
    # scripts/replay_journal.py replays offline. The server stamps the
    # workload-level identity here; the scheduler stamps its effective
    # geometry and seals the header. None when --journal was not given —
    # every instrumentation site in the scheduler then costs one
    # attribute check.
    journal = None
    if journal_path:
        journal = journal_lib.DecisionJournal(
            journal_path, max_bytes=journal_max_bytes
        )
        journal.stamp_header(
            model=model_name, faults_spec=faults_spec or None,
            max_tokens_limit=max_tokens_limit,
        )
    # Trained draft model (models/generate.NeuralDrafter): a checkpoint
    # path or an "init:V:D:W:SEED" spec. Replaces the default n-gram
    # drafter. Its `source` string lands in the journal header
    # (draft_model) so replay rebuilds the identical proposer.
    drafter = None
    if draft_model:
        from oryx_tpu.models import generate as generate_lib

        drafter = generate_lib.NeuralDrafter.from_spec(draft_model)
    # Engine registry (serve/engine.py): "continuous", "sharded", and
    # whatever later shapes register — all drop-in behind this server
    # and the supervisor through the Engine protocol.
    scheduler = engine_lib.create_engine(
        engine, pipe, num_slots=num_slots, page_size=page_size,
        chunk=decode_chunk, max_ctx=max_ctx, metrics=metrics,
        tracer=tracer, stall_timeout=stall_timeout, anomaly=anomaly,
        prefill_chunk=prefill_chunk, prefix_cache=prefix_cache,
        ragged=ragged, speculate=speculate,
        drafter=drafter,
        kv_dtype=kv_dtype, host_cache_bytes=host_cache_bytes,
        audit_tol_maxdiff=audit_tol_maxdiff,
        audit_tol_kl=audit_tol_kl,
        profile_sample_every=profile_sample_every,
        audit_sample_every=audit_sample_every,
        numerics_every=numerics_every,
        max_queue=max_queue, request_timeout=request_timeout,
        degraded_cooldown=degraded_cooldown,
        request_log=request_log, engine_label=engine,
        replica_id=replica_id, journal=journal,
    )
    supervisor = None
    if supervise:
        supervisor = EngineSupervisor(scheduler)
        supervisor.start()

    def _ready() -> tuple[bool, str]:
        """Readiness = the engine loop is genuinely able to make
        progress. The engine's own readiness() (Engine protocol)
        answers for drain/death/stall; the server layers on the two
        things only it knows — a server-level drain begun before the
        engine saw it, and a supervisor that gave up reviving. A load
        balancer probing this never has to spend a real completion;
        routers eject a draining or crash-looping replica on it."""
        if draining.is_set():
            return False, "draining"
        if (
            not scheduler.alive()
            and supervisor is not None and supervisor.gave_up
        ):
            return False, (
                "engine dead (supervisor gave up after "
                f"{supervisor.max_restarts} restarts in "
                f"{supervisor.window_s:g}s)"
            )
        return scheduler.readiness()

    class Handler(BaseHTTPRequestHandler):
        def log_message(self, *a):  # quiet access log
            pass

        def _ring_debug(self, ring, *, default_n: int) -> None:
            """Shared shape of the ring-backed debug endpoints
            (/debug/timeline, /debug/oom, /debug/audit,
            /debug/journal): ONE ?n= contract, engine label + the
            ring's to_dict(n) body — so the views can never drift on
            parsing or error semantics."""
            q = urllib.parse.parse_qs(
                urllib.parse.urlsplit(self.path).query
            )
            try:
                n = int((q.get("n") or [str(default_n)])[0])
                if n < 0:
                    raise ValueError
            except ValueError:
                self._json(400, {
                    "error": "n must be a non-negative integer",
                })
                return
            body = {"engine": engine}
            body.update(ring.to_dict(n or None))
            self._json(200, body)

        def _json(self, code: int, body: dict[str, Any],
                  request_id: str | None = None,
                  extra_headers: dict[str, str] | None = None) -> None:
            data = json.dumps(body).encode()
            self.send_response(code)
            self.send_header("Content-Type", "application/json")
            self.send_header("Content-Length", str(len(data)))
            if request_id:
                self.send_header("X-Request-Id", request_id)
            for k, v in (extra_headers or {}).items():
                self.send_header(k, v)
            self.end_headers()
            self.wfile.write(data)

        def do_GET(self):
            if self.path == "/healthz":
                self._json(200, {"status": "ok"})
            elif self.path == "/readyz":
                ready, reason = _ready()
                self._json(
                    200 if ready else 503,
                    {"ready": ready, "reason": reason},
                )
            elif self.path.split("?", 1)[0] == "/debug/requests":
                # Flight recorder: newest-first summaries of the last N
                # requests (in-flight included). ?limit= bounds the
                # response and ?state=active|done|error filters — a
                # load sweep pushes hundreds of requests through the
                # recorder and the consumer usually wants "the failed
                # ones" or "the last K", not the whole ring.
                q = urllib.parse.parse_qs(
                    urllib.parse.urlsplit(self.path).query
                )
                fmt = (q.get("format") or [""])[0]
                if fmt not in ("", "json", "jsonl"):
                    self._json(400, {
                        "error": f"unknown format {fmt!r} (json|jsonl)",
                    })
                    return
                # One ?limit= contract for both formats.
                try:
                    limit = int((q.get("limit") or ["0"])[0])
                    if limit < 0:
                        raise ValueError
                except ValueError:
                    self._json(400, {
                        "error": "limit must be a non-negative integer",
                    })
                    return
                if fmt == "jsonl":
                    # Wide-event export: the canonical one-line-per-
                    # terminal-request log (utils/request_log.py),
                    # schema REQUEST_EVENT_KEYS. ?limit= bounds it.
                    data = scheduler.request_log.export_jsonl(
                        limit or None
                    ).encode()
                    self.send_response(200)
                    self.send_header(
                        "Content-Type", "application/x-ndjson"
                    )
                    self.send_header("Content-Length", str(len(data)))
                    self.end_headers()
                    self.wfile.write(data)
                    return
                state = (q.get("state") or [""])[0]
                if state not in ("", "all", "active", "done", "error"):
                    self._json(400, {
                        "error": f"unknown state {state!r} "
                        "(active|done|error|all)",
                    })
                    return
                reqs = tracer.snapshot()
                if state == "active":
                    reqs = [r for r in reqs if not r["done"]]
                elif state == "done":
                    reqs = [
                        r for r in reqs
                        if r["done"] and "error" not in r["meta"]
                    ]
                elif state == "error":
                    reqs = [r for r in reqs if "error" in r["meta"]]
                total = len(reqs)
                if limit:
                    reqs = reqs[:limit]
                self._json(200, {
                    "engine": engine,
                    "total": total,
                    "returned": len(reqs),
                    "requests": reqs,
                })
            elif self.path.split("?", 1)[0] == "/debug/timeline":
                # The engine flight data recorder (utils/timeline.py):
                # newest-first per-step records plus cumulative
                # dispatch-kind counts that reconcile against
                # oryx_serving_dispatches_total.
                self._ring_debug(scheduler.timeline, default_n=64)
            elif self.path.split("?", 1)[0] == "/debug/pages":
                # Page-pool observatory (utils/pagemap.py): the live
                # ownership map — per page free/slot/cache/shared,
                # refcount, owner tags, tenancy age — plus the derived
                # summary whose state counts must reconcile with the
                # oryx_pool_* gauges on a quiesced engine.
                q = urllib.parse.parse_qs(
                    urllib.parse.urlsplit(self.path).query
                )
                fmt = (q.get("format") or ["json"])[0]
                if fmt not in ("json", "summary"):
                    self._json(400, {
                        "error": f"unknown format {fmt!r} "
                        "(json|summary)",
                    })
                    return
                snap = scheduler.pool_snapshot()
                body = {
                    "engine": engine,
                    "num_pages": snap["num_pages"],
                    "page_size": snap["page_size"],
                    # Wire format + device byte cost of the pool: what
                    # turns page counts into the HBM bytes the
                    # --kv-dtype lever actually halves.
                    "kv_dtype": snap.get("kv_dtype"),
                    "kv_pool_bytes": snap.get("kv_pool_bytes"),
                    "summary": snap["summary"],
                }
                if fmt == "json":
                    body["pages"] = snap["pages"]
                self._json(200, body)
            elif self.path.split("?", 1)[0] == "/debug/oom":
                # OOM forensics (utils/forensics.py): the bounded ring
                # of memory-pressure incident records — pool summary,
                # top-K residents with ledgers, cache LRU tail,
                # timeline tail — captured at every OutOfPagesError
                # and degraded-mode escalation.
                self._ring_debug(scheduler.forensics, default_n=16)
            elif self.path.split("?", 1)[0] == "/debug/audit":
                # Output-quality observatory (serve/audit.py): the
                # bounded ring of shadow-parity audit records plus the
                # monotone verdict counts /debug consumers reconcile
                # against oryx_audit_total{verdict=}.
                self._ring_debug(scheduler.auditor, default_n=16)
            elif self.path.split("?", 1)[0] == "/debug/journal":
                # Decision journal (serve/journal.py): the engine
                # flight recorder's bounded ring — header + newest-
                # first entries + per-kind counts. Disarmed replicas
                # serve the same body shape with armed=false.
                self._ring_debug(
                    scheduler.journal or journal_lib.DISARMED,
                    default_n=64,
                )
            elif self.path.split("?", 1)[0] == "/debug/profile":
                # On-demand device-time capture: bracket the next
                # ?steps=K engine dispatches in one jax.profiler
                # capture and return the Perfetto-loadable Chrome
                # trace + per-kind device-time attribution. Needs live
                # traffic — an idle engine answers 503.
                q = urllib.parse.parse_qs(
                    urllib.parse.urlsplit(self.path).query
                )
                try:
                    steps = int((q.get("steps") or ["4"])[0])
                    if not 1 <= steps <= 256:
                        raise ValueError
                except ValueError:
                    self._json(400, {
                        "error": "steps must be an integer in "
                        "[1, 256]",
                    })
                    return
                try:
                    timeout = float((q.get("timeout") or ["30"])[0])
                except ValueError:
                    self._json(400, {"error": "timeout must be a "
                                     "number"})
                    return
                try:
                    result = scheduler.request_profile(
                        steps, timeout=max(1.0, min(timeout, 300.0))
                    )
                except TimeoutError as e:
                    self._json(503, {"error": str(e)},
                               extra_headers={"Retry-After": "1"})
                    return
                except RuntimeError as e:
                    self._json(503, {"error": str(e)})
                    return
                result["engine"] = engine
                self._json(200, result)
            elif self.path.startswith("/debug/trace"):
                q = urllib.parse.parse_qs(
                    urllib.parse.urlsplit(self.path).query
                )
                rid = (q.get("id") or [""])[0]
                if not rid:
                    self._json(400, {"error": "missing ?id=<request id>"})
                    return
                tr = tracer.get(rid)
                if tr is None:
                    self._json(404, {
                        "error": f"no trace for id {rid!r} (the flight "
                        "recorder keeps the last "
                        f"{tracer.capacity} requests)"
                    })
                    return
                # Chrome trace-event JSON: loads directly in Perfetto /
                # chrome://tracing; also carries the raw summary.
                body = tracer.chrome_trace([tr])
                body["request"] = tr.summary()
                self._json(200, body, request_id=rid)
            elif self.path == "/metrics":
                data = metrics.render().encode()
                self.send_response(200)
                self.send_header(
                    "Content-Type", "text/plain; version=0.0.4"
                )
                self.send_header("Content-Length", str(len(data)))
                self.end_headers()
                self.wfile.write(data)
            elif self.path == "/v1/models":
                self._json(200, {
                    "object": "list",
                    "data": [{
                        "id": model_name, "object": "model",
                        "owned_by": "oryx-tpu",
                    }],
                })
            else:
                self._json(404, {"error": "not found"})

        def do_POST(self):
            if self.path != "/v1/chat/completions":
                self._json(404, {"error": "not found"})
                return
            if draining.is_set():
                # Drain contract: after SIGTERM no new completion work
                # is accepted; in-flight requests still finish. The
                # router saw /readyz flip already — this is the
                # stragglers' answer.
                self._json(503, {"error": {
                    "message": "server is draining (shutting down)",
                    "type": "unavailable_error",
                }}, extra_headers={"Retry-After": "1"})
                return
            try:
                n = int(self.headers.get("Content-Length", 0))
                req = json.loads(self.rfile.read(n))
                question, history, images = parse_messages(
                    req["messages"], allow_local_files=allow_local_files
                )
                if images and pipe.cfg.vision is None:
                    from oryx_tpu.serve.pipeline import TEXT_ONLY_MESSAGE

                    raise ValueError(TEXT_ONLY_MESSAGE)
                raw_max = req.get(
                    "max_tokens", req.get("max_completion_tokens")
                )
                if raw_max is None:
                    max_new = pipe.cfg.generation.max_new_tokens
                else:
                    max_new = int(raw_max)
                    if max_new < 1:
                        raise ValueError(
                            f"max_tokens must be >= 1, got {max_new}"
                        )
                    # A request holds its slot and its pages for up to
                    # max_new steps — an unbounded client value is a
                    # denial of service.
                    if max_new > max_tokens_limit:
                        raise ValueError(
                            f"max_tokens must be <= {max_tokens_limit}, "
                            f"got {max_new}"
                        )
                sampling = _parse_sampling(req)
                if (so := req.get("stream_options")) is not None:
                    # Unsupported values raise (-> 400), never silently
                    # no-op — same policy as _parse_sampling.
                    if not req.get("stream"):
                        raise ValueError(
                            "stream_options requires stream: true"
                        )
                    if not isinstance(so, dict) or set(so) - {
                        "include_usage"
                    }:
                        raise ValueError(
                            "stream_options supports only include_usage"
                        )
            except Exception as e:
                self._json(400, {"error": {
                    "message": f"{type(e).__name__}: {e}",
                    "type": "invalid_request_error",
                }})
                return

            # Request identity, honored end-to-end: a sanitized client
            # X-Request-Id becomes the trace id (responses echo it, so
            # client logs join /debug/trace without extra plumbing); a
            # router-propagated X-Oryx-Trace header (`rid;parent-span`)
            # wins over both — the router already honored the client's
            # id, and its rid is what keys the merged fleet trace.
            # Unsafe or colliding ids fall back to minting.
            rid_pref = trace_lib.sanitize_request_id(
                self.headers.get("X-Request-Id")
            )
            routed = False
            router_parent: int | None = None
            if xt := self.headers.get("X-Oryx-Trace"):
                t_rid, _, t_parent = xt.partition(";")
                if t_rid := trace_lib.sanitize_request_id(t_rid):
                    rid_pref = t_rid
                    routed = True
                    try:
                        router_parent = int(t_parent)
                    except ValueError:
                        router_parent = None

            is_video = bool(req.get("video")) and len(images) > 1
            request_dict = {
                "question": question, "images": images,
                "is_video": is_video, "history": history,
            }
            self._submit(
                req, request_dict, max_new, sampling,
                request_id=rid_pref, routed=routed,
                router_parent=router_parent,
            )

        def _submit(self, req, request_dict, max_new, sampling,
                    request_id=None, routed=False,
                    router_parent=None) -> None:
            """Hand one validated request to the engine and answer it.
            The scheduler thread owns the device; this handler thread
            only drains the handle's event queue, so a slow client
            never blocks decode (a dead one flips `cancelled` and the
            slot frees at the next harvest)."""
            from oryx_tpu.serve.scheduler import AdmissionRejected

            try:
                handle = scheduler.submit(
                    request_dict, max_new, sampling,
                    streaming=bool(req.get("stream")),
                    request_id=request_id, routed=routed,
                )
            except AdmissionRejected as e:
                # Backpressure / shed-load -> 429, draining -> 503;
                # both carry Retry-After so well-behaved clients back
                # off instead of hammering a saturated replica.
                code = (503 if e.reason in ("draining", "engine_dead")
                        else 429)
                self._json(code, {"error": {
                    "message": str(e),
                    "type": "overloaded_error" if code == 429
                    else "unavailable_error",
                    "reason": e.reason,
                }}, extra_headers={
                    "Retry-After": str(max(1, round(e.retry_after_s))),
                })
                return
            rid = handle.request_id
            if routed:
                # Mark the trace as router-originated and remember the
                # router's parent span index: the router's merged
                # /debug/trace?id= view nests this replica's spans
                # under it, and offline consumers can tell routed from
                # direct traffic.
                handle.trace.annotate(
                    routed=True, router_parent_span=router_parent
                )
            if not req.get("stream"):
                handle.done.wait()
                if handle.error is not None:
                    # error_kind -> status: the scheduler classified
                    # the failure; this is just the HTTP spelling.
                    if handle.error_kind == "invalid_request":
                        self._json(400, {"error": {
                            "message": handle.error,
                            "type": "invalid_request_error",
                        }}, request_id=rid)
                    elif handle.error_kind == "timeout":
                        self._json(504, {"error": {
                            "message": handle.error,
                            "type": "timeout_error",
                        }}, request_id=rid)
                    elif handle.error_kind == "unavailable":
                        self._json(503, {"error": {
                            "message": handle.error,
                            "type": "unavailable_error",
                        }}, request_id=rid,
                            extra_headers={"Retry-After": "1"})
                    else:
                        self._json(
                            500, {"error": {"message": handle.error}},
                            request_id=rid,
                        )
                else:
                    body = _completion_body(
                        model_name, handle.reply, handle.finish_reason,
                        usage=handle.usage, request_id=rid,
                    )
                    # Per-request cost ledger (extra key; OpenAI
                    # clients ignore unknown fields): what this
                    # completion actually cost the engine.
                    cost = handle.debug.get("cost")
                    if cost is not None:
                        body["oryx"] = {"cost": cost}
                    self._json(200, body, request_id=rid)
                return
            want_usage = bool(
                (req.get("stream_options") or {}).get("include_usage")
            )
            cid = f"chatcmpl-{rid}"
            try:
                self.send_response(200)
                self.send_header("Content-Type", "text/event-stream")
                self.send_header("Cache-Control", "no-cache")
                self.send_header("X-Request-Id", rid)
                self.end_headers()
                usage: tuple[int, int] | None = None
                errored = False
                while True:
                    kind, *payload = handle.events.get()
                    if kind == "delta":
                        self._sse(_chunk_body(
                            model_name, cid, payload[0],
                            usage_field=want_usage,
                        ))
                    elif kind == "error":
                        # Terminal: no usage chunk, no [DONE] — an
                        # errored stream must not look like a normal
                        # completion to OpenAI-style clients.
                        self._sse({"error": {"message": payload[0]}})
                        errored = True
                        break
                    else:  # ("end", reason, usage)
                        usage = payload[1]
                        fin = _chunk_body(
                            model_name, cid, None, payload[0],
                            usage_field=want_usage,
                        )
                        # Final SSE metadata: the request's cost ledger
                        # rides the finish chunk (the scheduler set it
                        # in debug before queueing the end event), so a
                        # streaming client — loadgen included — gets
                        # per-request cost without a /debug round-trip.
                        cost = handle.debug.get("cost")
                        if cost is not None:
                            fin["oryx"] = {"cost": cost}
                        self._sse(fin)
                        break
                if errored:
                    return
                if want_usage:
                    p, c = usage or (0, 0)
                    self._sse(_chunk_body(
                        model_name, cid, None,
                        usage_field=True,
                        usage={
                            "prompt_tokens": p,
                            "completion_tokens": c,
                            "total_tokens": p + c,
                        },
                    ))
                self.wfile.write(b"data: [DONE]\n\n")
                self.wfile.flush()
            except (BrokenPipeError, ConnectionResetError, OSError):
                handle.cancelled = True

        def _sse(self, body: dict[str, Any]) -> None:
            # Chaos site: mid-stream client disconnect — raising
            # BrokenPipeError here takes the exact code path a dropped
            # socket takes, so the suite can prove cancellation frees
            # the slot's pages and prefix-cache shares.
            faults.fault_point("client_disconnect", exc=BrokenPipeError)
            self.wfile.write(f"data: {json.dumps(body)}\n\n".encode())
            self.wfile.flush()

    srv = ThreadingHTTPServer((host, port), Handler)
    srv.metrics = metrics
    srv.scheduler = scheduler
    srv.tracer = tracer
    srv.anomaly = anomaly
    srv.supervisor = supervisor
    srv.request_log = scheduler.request_log
    srv.timeline = scheduler.timeline
    srv.forensics = scheduler.forensics
    srv.auditor = scheduler.auditor
    srv.journal = scheduler.journal

    def begin_drain() -> None:
        """Drain-on-shutdown, step 1: /readyz flips 503 NOW (router
        health ejection), POSTs answer 503 + Retry-After, and the
        continuous engine stops admission and finishes resident
        decodes. Callers then `scheduler.drain()` and shutdown()."""
        draining.set()
        scheduler.begin_drain()

    srv.begin_drain = begin_drain
    return srv


def main(argv: list[str] | None = None) -> None:
    ap = argparse.ArgumentParser(description="Oryx-TPU OpenAI-style server")
    ap.add_argument("--model-path", required=True)
    ap.add_argument("--tokenizer-path", default=None)
    ap.add_argument("--model-name", default="oryx-tpu")
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--port", type=int, default=8000)
    from oryx_tpu.serve.engine import engine_names

    ap.add_argument(
        "--engine", choices=engine_names(), default="continuous",
        help="serving engine: the continuous-batching scheduler over a "
        "paged KV cache (admission at chunk boundaries, per-slot "
        "sampling, GET /metrics occupancy), or sharded — the same "
        "scheduler with a tensor-parallel mesh required (--shard tp=N; "
        "KV pool sharded along heads)",
    )
    ap.add_argument(
        "--replica-id", default=None,
        help="this backend's identity behind serve/router.py: lands as "
        "the replica label on build_info so aggregated scrapes "
        "distinguish backends",
    )
    ap.add_argument(
        "--num-slots", type=int, default=4,
        help="continuous engine: decode slot array size",
    )
    ap.add_argument(
        "--page-size", type=int, default=64,
        help="continuous engine: KV page size in tokens",
    )
    ap.add_argument(
        "--decode-chunk", type=int, default=8,
        help="continuous engine: decode steps per compiled dispatch "
        "(admission latency is bounded by one chunk)",
    )
    ap.add_argument(
        "--max-ctx", type=int, default=2048,
        help="continuous engine: per-request context ceiling "
        "(prompt + max_tokens; sizes the per-slot block table)",
    )
    ap.add_argument(
        "--prefill-chunk", type=int, default=512,
        help="continuous engine: admission prefills at most this many "
        "prompt tokens per engine step, interleaved with resident "
        "decode chunks (bounds decode latency under long-prompt "
        "admission; 0 = prefill each prompt in one dispatch)",
    )
    ap.add_argument(
        "--ragged", action="store_true",
        help="continuous engine: fuse chunked prefill and decode into "
        "ONE ragged paged-attention dispatch per engine step (a packed "
        "query buffer mixing every live slot's decode token with the "
        "admitting prompt's suffix chunk; requires --prefill-chunk). "
        "Greedy outputs are bit-identical to the split path.",
    )
    ap.add_argument(
        "--speculate", type=int, default=0, metavar="K",
        help="continuous engine: speculative decoding — every live "
        "slot self-drafts K tokens per step (n-gram prompt lookup "
        "against its own context; no second model) and the whole "
        "fleet's drafts verify as extra lanes of the ONE fused "
        "dispatch, so a slot advances 1..K+1 tokens per sequential "
        "step. Greedy outputs stay byte-identical; temperature>0 uses "
        "rejection sampling (distribution-exact). Requires --ragged.",
    )
    ap.add_argument(
        "--draft-model", default=None, metavar="PATH|init:V:D:W:SEED",
        help="continuous engine: trained draft model for speculative "
        "decoding (models/generate.NeuralDrafter) replacing the "
        "default n-gram drafter — an .npz checkpoint path (see "
        "generate.fit_neural_drafter) or an init:V:D:W:SEED spec for "
        "a random init. Requires --speculate",
    )
    ap.add_argument(
        "--kv-dtype", choices=["bf16", "int8"], default="bf16",
        help="continuous engine: paged KV pool storage format. bf16 = "
        "dense pages in the compute dtype (byte-exact). int8 = "
        "quantized pages with per-page scale blocks — quantize on "
        "page write, dequantize in the kernel's page walk — roughly "
        "doubling resident KV tokens per HBM byte; replies drift "
        "within the audit plane's roundtrip-derived tolerances "
        "(--audit-tol-maxdiff/--audit-tol-kl) instead of matching the "
        "bf16 pool bit-for-bit",
    )
    ap.add_argument(
        "--host-cache-bytes", type=int, default=0,
        help="continuous engine: host-RAM prefix-cache spill tier "
        "budget in bytes (0 = off). LRU-evicted cache pages spill to "
        "host RAM instead of dying; a hit on a spilled prefix "
        "re-uploads its pages ahead of the suffix prefill — cache "
        "capacity becomes host-bounded, not HBM-bounded",
    )
    ap.add_argument(
        "--audit-tol-maxdiff", type=float, default=None,
        help="output auditor: logit max-abs-diff above which a "
        "production-vs-reference drift is a FAIL verdict (default "
        "derives from utils/quant.roundtrip_error_stats on "
        "--kv-dtype; drift at or below it — but above the pass "
        "tolerance — is the `drift` verdict)",
    )
    ap.add_argument(
        "--audit-tol-kl", type=float, default=None,
        help="output auditor: per-position KL above which drift is a "
        "FAIL verdict (default derives from roundtrip_error_stats on "
        "--kv-dtype)",
    )
    ap.add_argument(
        "--profile-sample-every", type=int, default=0, metavar="N",
        help="continuous engine: every N engine steps, bracket ONE "
        "dispatch in a jax.profiler capture and attribute its device "
        "busy time to oryx_device_time_seconds_total{kind=} + the "
        "step's /debug/timeline record (0 = off; sampling never "
        "alters tokens or adds a dispatch, and a failed capture only "
        "increments oryx_profile_capture_errors_total). "
        "GET /debug/profile?steps=K serves on-demand captures either "
        "way",
    )
    ap.add_argument(
        "--audit-sample-every", type=int, default=0, metavar="N",
        help="continuous engine: audit every Nth FINISHED request — "
        "replay it cold through the split XLA reference path at an "
        "idle point of the engine loop and compare greedy byte parity "
        "+ logit drift at sampled positions; verdicts land in "
        "oryx_audit_total{verdict=}, the record ring at "
        "GET /debug/audit, and kind=\"audit\" wide events (0 = off; "
        "audits never perturb live traffic — see "
        "docs/OBSERVABILITY.md \"Output quality & numerics\")",
    )
    ap.add_argument(
        "--numerics-every", type=int, default=0, metavar="N",
        help="continuous engine: every N engine steps the dispatch "
        "carries the in-dispatch logit probe (finite fraction, "
        "absmax, rms, entropy, top-1 margin -> oryx_numerics_* "
        "gauges + the entropy_collapse/absmax_explosion sentinels); "
        "a static program twin — zero extra dispatches, tokens "
        "bit-identical (0 = off; not supported with --speculate — "
        "the verify step carries no probe)",
    )
    ap.add_argument(
        "--no-prefix-cache", action="store_true",
        help="continuous engine: disable the shared-prefix KV cache "
        "(copy-on-write paged pool reuse of repeated system/media "
        "prefixes across requests)",
    )
    ap.add_argument(
        "--stall-timeout", type=float, default=120.0,
        help="continuous engine: dump all thread stacks + the request "
        "flight recorder to stderr when no decode chunk completes for "
        "this many seconds (0 disables the watchdog)",
    )
    ap.add_argument(
        "--flight-recorder-size", type=int, default=256,
        help="how many recent requests GET /debug/requests retains "
        "(span trees at GET /debug/trace?id=)",
    )
    ap.add_argument(
        "--ttft-slo", type=float, default=None,
        help="fire an oryx_anomaly_total{kind=\"ttft_slo\"} event when "
        "a request's time-to-first-token exceeds this many seconds",
    )
    ap.add_argument(
        "--queue-depth-slo", type=int, default=None,
        help="fire an oryx_anomaly_total{kind=\"queue_depth_slo\"} "
        "event when the admission queue exceeds this depth",
    )
    ap.add_argument(
        "--events-path", default=None,
        help="append structured anomaly events as JSONL here "
        "(see docs/OBSERVABILITY.md for the schema)",
    )
    ap.add_argument(
        "--requests-log", default=None, metavar="PATH",
        help="continuous engine: append one wide JSONL event per "
        "terminal request here (size-capped, rolls to PATH.1; schema "
        "utils.metrics.REQUEST_EVENT_KEYS). The in-memory ring behind "
        "/debug/requests?format=jsonl is always on",
    )
    ap.add_argument(
        "--journal", default=None, metavar="PATH",
        help="continuous engine: arm the decision journal — append one "
        "JSONL entry per engine dispatch and scheduling decision here "
        "(size-capped, rolls to PATH.1, header re-written per "
        "generation; schema utils.metrics.JOURNAL_EVENT_KEYS). "
        "scripts/replay_journal.py replays the file offline "
        "byte-for-byte; GET /debug/journal serves the in-memory ring",
    )
    ap.add_argument(
        "--max-queue", type=int, default=256,
        help="continuous engine: bound on the admission queue; beyond "
        "it new requests get 429 + Retry-After instead of unbounded "
        "queueing (0 = unbounded)",
    )
    ap.add_argument(
        "--request-timeout", type=float, default=None,
        help="continuous engine: per-request deadline in seconds — a "
        "request past it is cancelled (pages and cache shares freed) "
        "and answered 504 wherever it was (queued, prefilling, "
        "decoding)",
    )
    ap.add_argument(
        "--no-supervisor", action="store_true",
        help="continuous engine: disable the engine supervisor that "
        "restarts a dead engine thread with deterministic request "
        "replay",
    )
    ap.add_argument(
        "--drain-timeout", type=float, default=60.0,
        help="seconds to wait for resident decodes to finish after "
        "SIGTERM before exiting anyway",
    )
    ap.add_argument(
        "--faults", default=None, metavar="SPEC",
        help="arm deterministic fault injection (utils/faults.py), "
        "e.g. 'page_alloc_oom:p=0.05,seed=7;engine_crash:after=40' — "
        "chaos testing only ($ORYX_FAULTS also works)",
    )
    ap.add_argument(
        "--allow-local-files", action="store_true",
        help="let image_url reference server-local file paths (off by "
        "default: any network client could read arbitrary images)",
    )
    ap.add_argument(
        "--max-tokens-limit", type=int, default=2048,
        help="reject requests asking for more than this many new tokens "
        "(a request holds its slot and pages for that long)",
    )
    ap.add_argument(
        "--shard", default=None, metavar="MODE=N",
        help="multi-chip serving (tp=N | fsdp=N over all visible devices)",
    )
    ap.add_argument(
        "--quantize", default=None, choices=["int8"],
        help="weight-only int8 for single-chip serving (halves weight "
        "HBM; mutually exclusive with --shard)",
    )
    args = ap.parse_args(argv)
    from oryx_tpu.utils.compile_cache import configure_compile_cache

    configure_compile_cache()
    if args.quantize and args.shard:
        ap.error("--quantize is single-chip serving; drop --shard")
    if args.engine == "sharded" and not args.shard:
        ap.error("--engine sharded requires --shard tp=N")
    if args.ragged and not args.prefill_chunk:
        ap.error("--ragged requires a nonzero --prefill-chunk")
    if args.speculate and not args.ragged:
        ap.error("--speculate requires --ragged (drafts are extra "
                 "lanes of the fused dispatch)")
    if args.speculate < 0:
        ap.error("--speculate must be >= 0")
    if args.draft_model and not args.speculate:
        ap.error("--draft-model requires --speculate")

    from oryx_tpu.parallel.mesh import parse_shard_arg
    from oryx_tpu.serve.builder import load_pipeline

    try:
        mesh, mode = parse_shard_arg(args.shard)
    except ValueError as e:
        ap.error(str(e))
    pipe = load_pipeline(
        args.model_path, tokenizer_path=args.tokenizer_path,
        mesh=mesh, sharding_mode=mode, quantize=args.quantize,
    )
    srv = build_server(
        pipe, model_name=args.model_name, host=args.host, port=args.port,
        allow_local_files=args.allow_local_files,
        max_tokens_limit=args.max_tokens_limit,
        engine=args.engine, num_slots=args.num_slots,
        page_size=args.page_size, decode_chunk=args.decode_chunk,
        max_ctx=args.max_ctx,
        prefill_chunk=args.prefill_chunk or None,
        prefix_cache=not args.no_prefix_cache,
        ragged=args.ragged,
        speculate=args.speculate,
        draft_model=args.draft_model,
        kv_dtype=args.kv_dtype,
        host_cache_bytes=args.host_cache_bytes,
        audit_tol_maxdiff=args.audit_tol_maxdiff,
        audit_tol_kl=args.audit_tol_kl,
        profile_sample_every=args.profile_sample_every,
        audit_sample_every=args.audit_sample_every,
        numerics_every=args.numerics_every,
        stall_timeout=args.stall_timeout or None,
        flight_recorder_size=args.flight_recorder_size,
        ttft_slo=args.ttft_slo,
        queue_depth_slo=args.queue_depth_slo,
        events_path=args.events_path,
        max_queue=args.max_queue or None,
        request_timeout=args.request_timeout,
        supervise=not args.no_supervisor,
        faults_spec=args.faults or os.environ.get("ORYX_FAULTS"),
        replica_id=args.replica_id,
        requests_log_path=args.requests_log,
        journal_path=args.journal,
    )

    def _drain_and_exit() -> None:
        print("SIGTERM: draining (admission stopped, /readyz now 503)")
        srv.begin_drain()
        drained = srv.scheduler.drain(timeout=args.drain_timeout)
        print("drain complete" if drained
              else f"drain timed out after {args.drain_timeout:g}s")
        srv.shutdown()

    def _on_sigterm(signum, frame):
        # serve_forever() owns this thread; drain from a helper so the
        # signal handler returns immediately.
        threading.Thread(target=_drain_and_exit, daemon=True).start()

    import signal

    signal.signal(signal.SIGTERM, _on_sigterm)
    print(f"serving {args.model_name} on http://{args.host}:{args.port}")
    srv.serve_forever()


if __name__ == "__main__":
    main()
