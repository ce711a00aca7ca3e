"""Continuous-batching scheduler over the paged decode path.

A slot array + admission queue:

  * The device runs ONE compiled program shape forever —
    `paged_decode_chunk` over `num_slots` rows, `chunk` tokens per
    dispatch. Which request owns a slot is host-side state (block
    tables, lengths, per-slot sampling arrays) edited between chunks.
  * A request is admitted the moment a slot AND enough KV pages are
    free: its prompt prefills into its own pages (`paged_prefill`, the
    pipeline's prompt prep — text or multimodal — feeds it), and it
    starts decoding at the next chunk, mid-flight of everyone else.
  * A finished row's pages return to the free list at the chunk
    boundary and the head of the queue takes the slot — so decode
    throughput tracks OCCUPANCY of the slot array instead of the p100
    of a fixed batch.
  * Per-slot sampling state (temperature/top_p/top_k as traced arrays,
    per-slot PRNG keys) means mixed sampling configs share one program
    and a row's sample stream never depends on its neighbors — which is
    also what makes EVICTION sound: when the page pool runs dry, the
    youngest slot is evicted and re-queued, and its deterministic
    replay (same key, same prompt) re-emits the same tokens, which the
    scheduler skips (`_Request.replay`) so the client stream never
    stutters or duplicates.

EOS is detected on device (the chunk program freezes finished rows);
stop STRINGS and per-row max_tokens are enforced host-side at harvest,
with the same trim/stable-prefix text rules as `chat_stream` — a
request's reply through this engine is byte-identical to `pipe.chat`.

Ragged fused path (`ragged=True`, docs/DESIGN.md "Ragged paged
attention"): `_prefill_step` + `_step_chunk` fuse into `_ragged_step`
— ONE device dispatch per engine step runs a packed query buffer
mixing every live slot's decode token with up to `prefill_chunk`
suffix tokens of the one admitting prompt
(models/generate.paged_ragged_step; per-token (segment, position)
routing through ops/paged_kv.write_pages_packed /
ragged_paged_attention). The dispatch shape is STATIC (two compiled
shape classes: prefill lanes present/absent, selected by host state);
greedy and seeded outputs stay byte-identical to the split path, and
oryx_serving_dispatches_total{kind=} is the observable proof.

Speculative decoding (`speculate=k`, requires ragged; docs/DESIGN.md
"Speculative decoding"): the fused step becomes ONE packed verify
forward (`generate.paged_spec_step`) where every live slot rides 1+k
lanes — its fed token plus k tokens proposed host-side by a `Drafter`
(default `generate.NgramDrafter`, prompt-lookup against the request's
own confirmed stream; no second model) — and advances 1..k+1 tokens
per sequential step. Greedy replies stay byte-identical (accept ==
argmax match); temperature>0 is rejection-sampled against the same
truncated distribution the plain sampler draws from. Rollback is free:
lanes only ever write the slot's exclusively-owned pages (the
COW-at-splice invariant), so rejected drafts are dead bytes past
cur_len, never held pages. Billing splits honestly into device steps
(verify lanes, rejected ones included) vs client tokens — see
`_finish_dispatch` and the accepted_tokens_per_step histogram.

Prefix cache + chunked prefill (serve/prefix_cache.py): admission looks
up the longest page-aligned cached prefix of the prompt's token ids and
SPLICES those pages into the new slot's block table — full pages shared
(refcounted), a partially-consumed page copy-on-written — so only the
unseen suffix is prefilled. The suffix prefills in bounded
`prefill_chunk`-token dispatches interleaved with everyone else's
decode chunks, so one long prompt never stalls resident streams for its
whole prefill. A request donates its full-page prompt prefix to the
cache the moment its prefill completes (concurrent look-alikes hit
immediately) and its prompt+reply prefix when it finishes; under pool
pressure, cache-only pages are LRU-evicted BEFORE any live request is.
Replies stay bit-identical to the cold path: valid-slot KV does not
depend on chunk grouping, and splicing reuses KV a cold prefill would
have recomputed bit-equal.

Metrics (utils/metrics.ServingMetrics): queue depth, slot occupancy,
admitted/evicted/completed counts, TTFT and per-token latency
histograms, wasted vs useful decode steps, prefix-cache hit/miss
tokens + entries/pages/evictions, prefill tokens and chunk sizes.

Cost ledger: every request accumulates what it actually COST — prefill
tokens computed vs tokens spliced from the prefix cache, device decode
steps (replays included), queue/prefill/decode wall time from its own
spans, and a pages-held x time integral (page-seconds, the HBM
currency; refcount-weighted so shared prefix pages split their cost
among their holders). The ledger is finalized on every terminal path into
handle.debug["cost"] + the trace meta (so /debug/requests and the
final SSE chunk carry it) and into the oryx_serving_request_*
histogram families; scripts/loadgen.py turns the aggregate into
capacity claims (docs/OBSERVABILITY.md "Capacity & load testing").

Failure containment (docs/DESIGN.md "Failure containment"):

  * Bounded admission: `max_queue` caps the queue; `submit` raises
    `AdmissionRejected` (the API server answers 429 + Retry-After)
    instead of letting a backlog grow without bound.
  * Per-request deadlines: `request_timeout` (or per-call `timeout_s`)
    cancels a request wherever it is — queued, prefilling, or decoding
    — freeing its slot pages and prefix-cache shares exactly (the
    chaos suite asserts `check_invariant` after every induced
    timeout). The API server maps the "timeout" error kind to 504.
  * Degraded-mode ladder: serving SLO anomalies (ttft_slo /
    queue_depth_slo) escalate `degraded_mode` 0→3 — 1 sheds the prefix
    cache, 2 clamps max_tokens, 3 sheds load (submit rejects) — and
    quiet periods of `degraded_cooldown` seconds walk it back down.
  * Crash recovery: `restart()` (driven by the API server's engine
    supervisor) requeues every in-flight request for deterministic
    eviction-style replay, rebuilds the page pool, verifies the pool
    invariant, and restarts the engine thread — clients ride through
    an engine-thread death without an error.
  * Drain-on-shutdown: `begin_drain()` stops admission (new submits
    rejected, queue errored with "draining"), finishes resident
    decodes, then exits the loop; /readyz flips 503 at drain start.
"""

from __future__ import annotations

import dataclasses
import json
import logging
import queue
import threading
import time
from collections import deque
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from oryx_tpu.analysis.sanitizers import (
    hot_dispatch,
    named_lock,
    race_exempt,
)
from oryx_tpu.models import generate as generate_lib
from oryx_tpu.models import oryx, qwen2
from oryx_tpu.ops import paged_kv
from oryx_tpu.ops.packing import round_up_bucket
from oryx_tpu.serve import audit as audit_lib
from oryx_tpu.serve import journal as journal_lib
from oryx_tpu.serve import pipeline as pipeline_lib
from oryx_tpu.serve.prefix_cache import PagedPrefixCache
from oryx_tpu.utils import faults
from oryx_tpu.utils import forensics as forensics_lib
from oryx_tpu.utils import numerics as numerics_lib
from oryx_tpu.utils import pagemap
from oryx_tpu.utils import profiling as profiling_lib
from oryx_tpu.utils import request_log as request_log_lib
from oryx_tpu.utils import trace as trace_lib
from oryx_tpu.utils.anomaly import AnomalyMonitor
from oryx_tpu.utils.timeline import StepTimeline
from oryx_tpu.utils.metrics import (
    DISPATCH_ROWS_BUCKETS,
    PAGE_SECONDS_BUCKETS,
    PREFILL_CHUNK_BUCKETS,
    REQUEST_SECONDS_BUCKETS,
    REQUEST_TOKEN_BUCKETS,
    SPEC_ACCEPT_BUCKETS,
    ServingMetrics,
    TTFT_BUCKETS,
)

# Every line carries the request id — grep one id end-to-end across
# queue/admission/eviction/finish (same id as X-Request-Id and
# /debug/trace).
_LOG = logging.getLogger("oryx.serve.scheduler")

def _unsupported_for_block(mode: str) -> str:
    """The one refusal of a mode that is not built for block mode (a
    model that generates by diffusion over blocks, docs/DESIGN.md "Block
    diffusion")."""
    return (
        f"block mode (block_length > 0): {mode} is not built for the "
        "block step (a dispatch denoises one block a slot, "
        "`paged_block_step`, and commits it in the next); it serves "
        "through the continuous split engine with chunked prefill and a "
        "bf16 pool only"
    )


# The options an engine may be asked for that some cache kind has no
# engine for: (option, "is it set", the words a refusal names it by).
# In the order they are looked at.
_ENGINE_OPTIONS = (
    ("ragged", bool, "ragged=True"),
    ("speculate", bool, "speculate"),
    ("kv_dtype", lambda v: v != "bf16", "kv_dtype={!r} (a quantized KV pool)"),
    ("host_cache_bytes", bool, "host_cache_bytes (the host spill tier)"),
    ("audit_sample_every", bool, "audit_sample_every (the output auditor's "
     "replay: a one-token decode step over one paged pool of its own)"),
    ("numerics_every", bool, "numerics_every (the numerics probe)"),
    ("prefill_chunk", lambda v: v is None, "an unchunked prefill "
     "(prefill_chunk=None; set prefill_chunk)"),
    ("mesh", lambda v: v is not None,
     "the tensor-parallel engine (--engine sharded)"),
)

# What each cache kind's engine is NOT built for. A row a kind: the
# `LLMConfig` attribute that is truthy for a model of the kind, the
# kind's sentence, and the options above it refuses. Every kind serves
# through the split engine's two programs (block mode: `paged_prefill`
# and `paged_block_step`) and a bf16 pool; a new kind adds a row. What a
# row leaves out is served and held to the default engine's bytes by
# `test_the_engine_serves_what_it_does_not_refuse` in the kind's tests.
_CACHE_KINDS = (
    ("block_length", _unsupported_for_block, frozenset({
        "ragged", "speculate", "kv_dtype", "audit_sample_every",
        "numerics_every", "prefill_chunk", "mesh"})),
    ("latent", qwen2.unsupported_for_latent, frozenset({
        "ragged", "speculate", "kv_dtype", "mesh"})),
    ("recurrent", qwen2.unsupported_for_recurrent, frozenset({
        "ragged", "speculate", "kv_dtype", "host_cache_bytes",
        "audit_sample_every", "mesh"})),
    ("windowed", qwen2.unsupported_for_window, frozenset({
        "ragged", "speculate", "kv_dtype", "host_cache_bytes",
        "audit_sample_every", "prefill_chunk", "mesh"})),
)

# Positions below which the split prefill keeps ONE block-table width
# (`prefill_table_buckets`).
PREFILL_TABLE_MIN = 8192


def prefill_table_buckets(max_pages: int, page_size: int) -> tuple[int, ...]:
    """Block-table widths, in pages, that `paged_prefill` is compiled
    for: powers of two of positions from PREFILL_TABLE_MIN up to the
    table's own width, which is always the last. A table of
    PREFILL_TABLE_MIN positions or fewer has one width, its own."""
    out, n = [], PREFILL_TABLE_MIN
    while n < max_pages * page_size:
        out.append(-(-n // page_size))
        n *= 2
    return tuple(out) + (max_pages,)


# The engine loop's phases (utils/profiling.PhaseClock): the labels of
# oryx_serving_engine_phase_seconds_total{phase=} and, prefixed
# `oryx.engine.`, the host events a profiler capture holds. Exclusive
# seconds: every instant of the loop is in exactly one. The host WAITS
# in idle (no request to serve), first_token (the tok0 read in
# _activate) and harvest (a dispatch's FIRST output read, which ends
# where the device drains); it works in the rest, copy_out (the
# dispatch's further outputs, copied with the device drained) among
# them (docs/OBSERVABILITY.md "Engine phases").
ENGINE_PHASES = (
    "idle", "housekeeping", "admit", "prompt_prep", "embed", "prefill",
    "first_token", "decode", "harvest", "copy_out", "emit",
)
# A block-diffusion engine (cfg.llm.block_length > 0) dispatches
# `paged_block_step` under `denoise` in place of `decode`, and has no
# first-token read: a block's tokens all arrive with its harvest.
BLOCK_ENGINE_PHASES = tuple(
    "denoise" if p == "decode" else p
    for p in ENGINE_PHASES if p != "first_token"
)

# One uninterrupted stretch of one phase, `idle` apart, that lasts
# longer than this is a stall: engine_stall_seconds_total{phase=}, and
# an `engine_stall` event on the trace of every resident request. The
# longest normal stretch of any benchmark cell is the harvest of a
# 107 ms decode dispatch (PERF.md section 5); a program's first
# execution in set-up takes seconds, and so does a stall.
STALL_SECONDS = 0.5


class AdmissionRejected(RuntimeError):
    """submit() refused the request without queueing it: backpressure
    (bounded queue full), shed_load (degraded mode 3), or draining
    (shutdown in progress). Carries the Retry-After hint the HTTP
    layer forwards (429 for load, 503 for drain)."""

    def __init__(self, message: str, *, reason: str,
                 retry_after_s: float = 1.0):
        super().__init__(message)
        self.reason = reason
        self.retry_after_s = retry_after_s


class RequestHandle:
    """Consumer side of a scheduled request.

    `events` carries ("delta", text), ("end", finish_reason, usage) or
    ("error", message) — at most one terminal event. `result()` blocks
    for the terminal event and returns the assembled reply. Setting
    `cancelled` (client hung up) releases the slot at the next harvest.
    """

    def __init__(self) -> None:
        self.events: queue.SimpleQueue[tuple] = queue.SimpleQueue()
        self.done = threading.Event()
        self.reply: str | None = None
        self.finish_reason: str = "stop"
        self.usage: tuple[int, int] | None = None
        self.error: str | None = None
        # HTTP mapping for `error`: "invalid_request" = rejected at
        # admission (400), "timeout" = per-request deadline exceeded
        # (504), "unavailable" = draining/restarting (503),
        # "server_error" = anything else (500).
        self.error_kind: str = "server_error"
        self.cancelled = False
        # Streaming consumers read text deltas off `events`; plain ones
        # only wait on `done` (set by submit(streaming=...)).
        self.streaming = False
        self.debug: dict[str, Any] = {}
        # Observability: the id the API server returns as X-Request-Id
        # and the span tree /debug/trace?id= serves.
        self.request_id: str = ""
        self.trace: trace_lib.Trace | None = None

    def result(self, timeout: float | None = None):
        """(reply, finish_reason, usage) or raises RuntimeError."""
        if not self.done.wait(timeout):
            raise TimeoutError("request did not complete in time")
        if self.error is not None:
            raise RuntimeError(self.error)
        return self.reply, self.finish_reason, self.usage


@dataclasses.dataclass
class _Request:
    request: dict[str, Any]
    max_new: int
    sampling: dict[str, Any]
    handle: RequestHandle
    submit_time: float
    stops: list[str]
    # Absolute monotonic deadline (None = no deadline): enforced in
    # the queue, during chunked prefill, and at every harvest — a
    # request past it frees its pages/refcounts and errors with the
    # "timeout" kind (HTTP 504).
    deadline: float | None = None
    # Filled at first admission; cached so an evicted request never
    # re-runs the host-side prompt/media prep.
    embeds: Any = None
    # Prompt token that `embeds[:, 0]` belongs to: 0, or on the chunked
    # split path the end of the spliced prefix at the gather
    # (scheduler._ensure_embeds).
    embeds_base: int = 0
    length: int = 0
    key0: Any = None
    # Ragged mode: host copy of `embeds` made once at first prefill, so
    # each fused dispatch's fixed-shape prefill window is a free numpy
    # slice (the dispatch operand shape never depends on prompt length).
    embeds_np: Any = None  # thread-owned: engine
    # Ragged mode: the admission-constant prefill operands (slot, len,
    # active flag, key0, sampling scalars), built once per PLACEMENT at
    # _place — only the window and its offset change per fused step.
    pf_consts: Any = None  # thread-owned: engine
    # Prefix-cache key: the prompt's token ids for text-only requests
    # (token ids == logical KV stream). None = uncacheable (multimodal
    # prompts key visual slots positionally; they bypass the cache).
    cache_tokens: Any = None
    # Admission prefill state: logical KV tokens already in place for
    # this placement (spliced cached prefix + prefilled chunks), the
    # spliced count, and whether the slot has started decoding.
    prefill_pos: int = 0
    spliced: int = 0
    activated: bool = False
    ttft_done: bool = False
    embeds_p: Any = None  # chunk-padded embeds (see pad_embeds_for_chunks)
    # Host text state (survives eviction: replay re-derives the same
    # tokens and `replay` skips re-processing them).
    emitted: list[int] = dataclasses.field(default_factory=list)
    # The reply's text as it goes (what was sent, and up to which token
    # the text is decided): `_advance` pays for a chunk's tokens, never
    # for `emitted` whole.
    text: pipeline_lib.ReplyText = dataclasses.field(
        default_factory=pipeline_lib.ReplyText
    )
    processed: int = 0  # tokens consumed from the device stream
    replay: int = 0  # tokens to skip after an eviction re-admission
    admit_seq: int = -1  # admission order (eviction picks the youngest)
    # Seq of this request's journal `submit` entry (None = journal
    # disarmed): the join key between the wide event / trace meta and
    # the decision journal (serve/journal.py).
    journal_seq: int | None = None
    # Replay re-admissions this request paid (eviction + supervisor
    # restart), surfaced in its wide event — the per-request spelling
    # of the fleet's eviction pressure.
    evictions: int = 0  # thread-owned: engine
    # The request arrived through the front-end router (X-Oryx-Trace
    # present): stamped into the wide event so fleet traffic can be
    # split routed-vs-direct offline.
    routed: bool = False
    # Cost ledger (docs/OBSERVABILITY.md "Capacity & load testing"):
    # per-request resource attribution, accumulated ACROSS placements
    # (an evicted request's replay re-pays prefill — that cost was
    # really spent). prefill tokens actually computed, tokens spliced
    # from the prefix cache, device decode steps the row consumed
    # (replay steps included: eviction overhead is still cost), and
    # the pages-held x wall-time integral in page-seconds. Wall-time
    # phase attribution comes from the trace spans at finalization.
    # thread-owned: engine — after submit() hands the request to the
    # queue, only the engine thread accumulates cost (the HTTP side
    # reads the finalized dict in handle.debug["cost"], never these);
    # the supervisor/drain paths touch them only once the engine
    # thread is dead (the race detector's handoff rule).
    # decode_steps counts DEVICE work (scan steps, or verify lanes in
    # speculative mode — rejected drafts are paid compute); decode
    # _tokens counts what the CLIENT got (completion-progress tokens).
    # They were equal before speculation; recording both keeps goodput
    # and page-seconds attribution honest when one dispatch advances a
    # slot by several tokens (or burns rejected lanes).
    cost_prefill_tokens: int = 0  # thread-owned: engine
    cost_cached_tokens: int = 0  # thread-owned: engine
    cost_decode_steps: int = 0  # thread-owned: engine
    cost_decode_tokens: int = 0  # thread-owned: engine
    cost_page_seconds: float = 0.0  # thread-owned: engine
    pages_t: float = 0.0  # last accrual (0 = never held) # thread-owned: engine
    # HBM high-water mark: most pages held at once (sampled at every
    # accrual point — grow/free/chunk/finalize) and the page-seconds
    # the request had paid when it got there; both land in the cost
    # ledger + wide event as peak_pages / peak_page_seconds.
    peak_pages: int = 0  # thread-owned: engine
    peak_page_seconds: float = 0.0  # thread-owned: engine
    # Span handles into `trace` for regions that outlive one method:
    # queue_wait opens at submit (and again at eviction), admission
    # opens when the request reaches the queue head. -1 = not open.
    trace: trace_lib.Trace | None = None
    qw_span: int = -1
    adm_span: int = -1

    @property
    def text_done(self) -> str:
        """The reply text the client has so far."""
        return self.text.done


class _CountedDecode:
    """`tokenizer.decode` for the emit phase, with every token it is
    handed counted (`emit_decoded_tokens_total`: beside
    `decode_steps_useful` it says how many tokens the host decodes for
    one it emits)."""

    def __init__(self, tokenizer, counter):
        self.tokenizer = tokenizer
        self.counter = counter

    def decode(self, ids, **kw) -> str:
        self.counter.inc(len(ids))
        return self.tokenizer.decode(ids, **kw)


def _handed(a: np.ndarray):
    """A host array as a dispatch is handed it: a COPY, because the
    host changes its arrays while the dispatch is in flight, and on the
    CPU `jnp.asarray` shares the numpy buffer."""
    return jnp.asarray(a.copy())


@dataclasses.dataclass
class _Flight:
    """What one enqueued decode dispatch (a `paged_block_step`, or the
    split engine's `paged_decode_chunk`) was given, kept until its
    harvest: by then the host's arrays have moved on (the next dispatch
    is enqueued, slots were freed and filled), so the harvest reads the
    outputs against this record."""
    toks: Any  # [S, B] / [S, chunk] on the device
    # slot -> admit_seq of the request it rode for: a row whose slot
    # holds another placement at the harvest is dropped.
    riders: dict[int, int]
    temp: np.ndarray  # the temperatures as handed
    t0_ns: int  # enqueue (trace_lib.now_ns)
    sampled: bool  # a periodic device-time sample brackets it
    captured: bool  # a capture brackets it: read at once, alone
    # A block:
    counts: dict[str, Any] | None = None  # paged_block_step's counts
    fused: int = 0  # riders whose pending block its first forward commits
    known: np.ndarray | None = None  # blk_known as handed
    # A decode chunk:
    lengths: Any = None  # [S] on the device, as the chunk leaves them
    finished: Any = None  # [S] on the device
    # The host's edits the chunk ran with (generate.overlay_lanes): the
    # lengths it started from are these over the chunk before's.
    edited: np.ndarray | None = None
    ran_lengths: np.ndarray | None = None
    nstats: Any = None  # the numerics probe's accumulator, if armed
    share: Any = None  # generate.SHARE_STATS sums, if the config has them
    # Statistics of the prefill chunks enqueued before it: ready when
    # it is.
    held: list = dataclasses.field(default_factory=list)


class ContinuousScheduler:
    """Slot map + admission queue + paged KV pool around one pipeline.

    Non-streaming callers wait on the handle submit() returns;
    streaming consumers drain RequestHandle.events.
    """

    def __init__(
        self,
        pipe,
        *,
        num_slots: int = 4,
        page_size: int = 64,
        chunk: int = 8,
        max_ctx: int = 2048,
        num_pages: int | None = None,
        metrics: ServingMetrics | None = None,
        seed: int = 0,
        autostart: bool = True,
        tracer: trace_lib.Tracer | None = None,
        stall_timeout: float | None = None,
        anomaly: AnomalyMonitor | None = None,
        prefill_chunk: int | None = None,
        prefix_cache: bool = True,
        max_queue: int | None = None,
        request_timeout: float | None = None,
        degraded_cooldown: float = 30.0,
        degraded_clamp_tokens: int = 64,
        ragged: bool = False,
        speculate: int = 0,
        drafter=None,
        timeline: StepTimeline | None = None,
        request_log: request_log_lib.RequestLog | None = None,
        engine_label: str = "continuous",
        replica_id: str | None = None,
        profile_sample_every: int = 0,
        forensics: forensics_lib.ForensicRing | None = None,
        audit_sample_every: int = 0,
        numerics_every: int = 0,
        kv_dtype: str = "bf16",
        host_cache_bytes: int = 0,
        audit_tol_maxdiff: float | None = None,
        audit_tol_kl: float | None = None,
        journal: journal_lib.DecisionJournal | None = None,
    ):
        # Pool-geometry validation up front: a bad flag should be one
        # actionable ValueError at construction, never a mid-decode
        # OutOfPagesError or a silent reshape surprise.
        for name, v in (
            ("num_slots", num_slots), ("page_size", page_size),
            ("chunk", chunk), ("max_ctx", max_ctx),
        ):
            if not isinstance(v, int) or v < 1:
                raise ValueError(
                    f"{name} must be a positive integer, got {v!r}"
                )
        if num_pages is not None and (
            not isinstance(num_pages, int) or num_pages < 1
        ):
            raise ValueError(
                f"num_pages must be a positive integer, got {num_pages!r}"
            )
        if prefill_chunk is not None and (
            not isinstance(prefill_chunk, int) or prefill_chunk < 1
        ):
            raise ValueError(
                "prefill_chunk must be a positive integer or None, "
                f"got {prefill_chunk!r}"
            )
        if max_ctx % page_size:
            raise ValueError(f"{max_ctx=} not a multiple of {page_size=}")
        if ragged and prefill_chunk is None:
            raise ValueError(
                "ragged=True fuses chunked prefill into the decode "
                "dispatch; set prefill_chunk (the per-step prompt "
                "budget that sizes the packed buffer's prefill lanes)"
            )
        if not isinstance(speculate, int) or speculate < 0:
            raise ValueError(
                f"speculate must be a non-negative integer (draft "
                f"tokens per slot per step), got {speculate!r}"
            )
        if speculate and not ragged:
            raise ValueError(
                "speculate requires ragged=True: drafts are extra "
                "packed lanes of the fused ragged dispatch (the split "
                "engine has no packed buffer to extend)"
            )
        # What this model's cache kind has no engine for is refused
        # here, by name, never silently run (`_CACHE_KINDS`).
        llm = pipe.cfg.llm
        given = dict(
            ragged=ragged, speculate=speculate, kv_dtype=kv_dtype,
            host_cache_bytes=host_cache_bytes,
            audit_sample_every=audit_sample_every,
            numerics_every=numerics_every, prefill_chunk=prefill_chunk,
            mesh=getattr(pipe, "mesh", None),
        )
        for kind, sentence, refused in _CACHE_KINDS:
            if not getattr(llm, kind):
                continue
            for option, is_set, words in _ENGINE_OPTIONS:
                if option in refused and is_set(given[option]):
                    raise ValueError(
                        sentence(words.format(given[option])))
        # Block mode (docs/DESIGN.md "Block diffusion"): on iff the
        # model generates by diffusion over blocks. The split engine's
        # admission and chunked prefill stay; the decode chunk's program
        # is `paged_block_step`, and a dispatch advances a slot by one
        # block.
        self.block = int(llm.block_length)
        if self.block:
            gen = pipe.cfg.generation
            for name, v in (
                ("page_size", page_size), ("prefill_chunk", prefill_chunk),
                ("max_ctx", max_ctx),
            ):
                if v % self.block:
                    raise ValueError(
                        f"block mode: {name}={v} is not a multiple of "
                        f"block_length={self.block} (a cached page, a "
                        "prefill chunk and the context must each end "
                        "on a block edge)"
                    )
            if (gen.denoising_steps or self.block) > self.block:
                raise ValueError(
                    f"denoising_steps={gen.denoising_steps} exceeds "
                    f"block_length={self.block}: a step fixes at least "
                    "one position"
                )
        # A recurrent state a slot and a window plane's re-based table
        # are each carried by the split engine's two programs alone. The
        # prefix cache of a Mamba hybrid or a window model is
        # constructed OFF, with the refusal's words in the log: a hit
        # hands over pages and no [d_state, d_inner] state (Mamba-2: 4
        # MB a layer, 21 MB a snapshot of the benchmark's cut), or would
        # have to hand over the window plane's pages as they stood at
        # the hit's last token. A gated short convolution's state is a
        # snapshot a page (`paged_kv.CONV_EDGE`), which a hit that ends
        # on a page edge hands over (`_splice_and_grow`): its cache
        # stays on.
        self.recurrent = bool(llm.recurrent)
        self.conv_state = llm.state_kind == "conv"
        # A Mamba-2 mixer's prefill chunk (0: no such mixer).
        self._ssd_chunk = (
            llm.mamba_chunk_size if llm.state_kind == "mamba2" else 0)
        self.windowed = bool(llm.windowed)
        self.indexed = bool(llm.indexed)
        for on, sentence in (
            (self.recurrent and not self.conv_state,
             qwen2.unsupported_for_recurrent),
            (self.windowed, qwen2.unsupported_for_window),
        ):
            if on and prefix_cache:
                _LOG.info("prefix cache off: %s",
                          sentence("prefix-cache splicing"))
                prefix_cache = False
        # Optional SLO watcher (utils/anomaly.py): TTFT and queue-depth
        # breaches fire oryx_anomaly_total{kind=} + events.jsonl.
        self.anomaly = anomaly
        self.pipe = pipe
        self.cfg = pipe.cfg
        self.num_slots = num_slots
        self.page_size = page_size
        self.chunk = chunk
        self.max_ctx = max_ctx
        self.max_pages = max_ctx // page_size
        self.num_pages = num_pages or num_slots * self.max_pages
        if self.num_pages * page_size < max_ctx:
            _LOG.warning(
                "page pool (%d pages x %d tokens = %d) cannot hold one "
                "max_ctx=%d request; prompts near the context ceiling "
                "will be rejected at admission (raise --num-pages or "
                "lower --max-ctx)",
                self.num_pages, page_size, self.num_pages * page_size,
                max_ctx,
            )
        self.prefill_chunk = prefill_chunk
        # Block-table widths (pages) a split prefill chunk is dispatched
        # with: the narrowest that covers what the chunk reads and
        # writes, so a chunk costs what its live prefix costs and not
        # what max_ctx would. Powers of two of positions from
        # PREFILL_TABLE_MIN up, the last the table's own width; a
        # context of PREFILL_TABLE_MIN or less keeps ONE program, the
        # whole table. One compiled `paged_prefill` a width: a warm-up
        # has to reach each (a prompt near max_ctx passes through all).
        self.table_buckets = prefill_table_buckets(
            self.max_pages, page_size)
        # The chunked split path holds a text-only prompt's embeds from
        # its first uncached token on (`_ensure_embeds`): the cached
        # prefix is never prefilled. (The ragged step and the single
        # -shot prefill index the whole prompt.)
        self._suffix_embeds = prefill_chunk is not None and not ragged
        # Ragged mode (docs/DESIGN.md "Ragged paged attention"): one
        # fused dispatch per engine step — `chunk` packed forwards,
        # each carrying every decode slot (1 token) plus `pf_width`
        # prefill-suffix tokens of the one admitting slot, so a
        # dispatch advances the admission by ~prefill_chunk tokens
        # while residents decode `chunk` tokens. Two compiled shape
        # classes total (prefill lanes present / absent), both static.
        self.ragged = bool(ragged)
        # The split engine (and block mode, by its own pair of
        # functions) keeps one decode dispatch in flight; the ragged
        # and speculative steps read every dispatch before the next.
        self._ahead = not (self.block or self.ragged)
        self.pf_width = (
            -(-prefill_chunk // chunk) if ragged else 0
        )
        # Speculative decoding (docs/DESIGN.md "Speculative decoding"):
        # k>0 makes the fused step a SINGLE packed verify forward —
        # every live slot contributes 1+k lanes (fed token + k
        # self-drafted continuations, proposed host-side between
        # dispatches) and advances 1..k+1 tokens per sequential step.
        # The per-step decode window is then 1+k tokens (capacity
        # growth, splice feasibility), not `chunk`.
        self.speculate = int(speculate)
        self.drafter = None
        if self.speculate:
            self.drafter = (
                drafter if drafter is not None
                else generate_lib.NgramDrafter()
            )
        self._win = (1 + self.speculate) if self.speculate else chunk
        if self.block:
            self._win = self.block  # a dispatch writes one block a slot
        if ragged and not self.speculate and prefill_chunk % chunk:
            # The prefill lanes advance chunk*pf_width tokens per fused
            # step — ceil-rounding silently raises the configured
            # per-step admission budget, so say so once. (The spec
            # step is a single forward of exactly prefill_chunk lanes;
            # no rounding there.)
            _LOG.warning(
                "ragged: prefill_chunk=%d is not a multiple of "
                "chunk=%d; the fused step advances admission by %d "
                "tokens per step (rounded up)",
                prefill_chunk, chunk, self.pf_width * chunk,
            )
        # KV pool storage format (docs/DESIGN.md "KV quantization &
        # cache tiering"): "bf16" = dense pages in the compute dtype
        # (today's byte-exact path); "int8" = quantized pool with
        # per-page scale blocks — quantize on page write, dequantize
        # in the page walk — roughly doubling resident KV tokens per
        # HBM byte. The audit plane's drift tolerances gate the
        # numerics cost continuously.
        if kv_dtype not in ("bf16", "int8"):
            raise ValueError(
                f"kv_dtype must be 'bf16' or 'int8', got {kv_dtype!r}"
            )
        self.kv_dtype = kv_dtype
        if not isinstance(host_cache_bytes, int) or host_cache_bytes < 0:
            raise ValueError(
                "host_cache_bytes must be a non-negative integer "
                f"(0 = host spill tier off), got {host_cache_bytes!r}"
            )
        self.host_cache_bytes = host_cache_bytes
        self.metrics = metrics or ServingMetrics()
        # Pre-register the prefix-cache + prefill families so the full
        # ladder renders (at zero) from the first scrape.
        reg = self.metrics.registry
        reg.counter("prefix_cache_hit_tokens_total")
        reg.counter("prefix_cache_miss_tokens_total")
        reg.counter("prefix_cache_evicted_pages_total")
        reg.gauge("prefix_cache_entries")
        reg.gauge("prefix_cache_pages")
        # Host spill-tier families, pre-registered at zero whether or
        # not the tier is armed (ladders must render before the first
        # spill), plus the pool's wire format as a build-info label.
        reg.gauge("oryx_cache_spilled_pages", raw_name=True)
        reg.gauge("oryx_cache_host_bytes", raw_name=True)
        reg.counter("oryx_cache_reload_hit_total", raw_name=True)
        reg.counter("oryx_cache_reload_upload_total", raw_name=True)
        reg.info(
            "oryx_pool_kv_dtype", {"kv_dtype": kv_dtype}, raw_name=True
        )
        reg.counter("prefill_tokens_total")
        # What the split prefill's attention needs against what it is
        # handed, per chunk (`_advance_prefill`): query-key pairs of the
        # chunk's causal attention, the positions its keys span (the
        # live prefix, once a chunk), and dispatched rows x the
        # positions of the block table the program ran with.
        reg.counter("prefill_attn_pairs_total")
        if pipe.cfg.llm.indexed:
            # Learned sparse attention (docs/OBSERVABILITY.md "Learned
            # sparse attention"), a cache layer each: the pairs a
            # chunk's indexer has to score (a query that sees more keys
            # than index_topk scores them all) and the pairs its
            # attention reads (min(visible, index_topk) a query), beside
            # prefill_attn_pairs_total, which counts every causal pair;
            # the key tiles its masked attention visits (one call of
            # `_attend_tile`, or of the kernel `_dsa_attend`, a tile);
            # and the latent rows the decode rows read,
            # min(length, index_topk) a row, beside
            # decode_kv_tokens_total, the index keys they scored.
            reg.counter("prefill_index_pairs_total")
            reg.counter("prefill_selected_pairs_total")
            reg.counter("prefill_masked_tiles_total")
            reg.counter("decode_selected_tokens_total")
        reg.counter("prefill_live_positions_total")
        reg.counter("prefill_table_positions_total")
        reg.histogram("prefill_chunk_tokens", PREFILL_CHUNK_BUCKETS)
        # Dispatch accounting: how many device dispatches each engine
        # step pays (the ragged path's whole claim is kind="ragged"
        # only, one per step) and the packed-buffer occupancy each one
        # carried (docs/OBSERVABILITY.md).
        reg.counter("dispatches_total", ("kind",))
        # ... and how many of them held a row with temperature > 0: the
        # dispatches whose sampler took its sorting branch
        # (generate.sample_token_rows decides on the same array).
        reg.counter("sampler_sort_dispatches_total", ("kind",))
        reg.histogram("dispatch_rows", DISPATCH_ROWS_BUCKETS)
        # How many times the host read a dispatch's outputs.
        reg.counter("harvest_total")
        # Speculation accounting: tokens a slot advanced per engine
        # step (sum/count mean is THE speculation headline — the
        # accepted-tokens/step gate) plus raw draft economics
        # (proposed vs accepted = the drafter's hit rate).
        reg.histogram("accepted_tokens_per_step", SPEC_ACCEPT_BUCKETS)
        reg.counter("draft_proposed_total")
        reg.counter("draft_accepted_total")
        # Containment families, pre-registered so dashboards render
        # them at zero before the first incident.
        reg.counter("admission_rejected_total", ("reason",))
        reg.counter("deadline_exceeded_total")
        reg.counter("engine_restarts_total")
        reg.gauge("degraded_mode")
        # Per-request cost-ledger families: the aggregate view of the
        # ledger every terminal request carries in /debug/requests and
        # its final SSE metadata (scripts/loadgen.py divides these by
        # goodput for tokens-per-page-second capacity claims).
        reg.histogram("request_prefill_tokens", REQUEST_TOKEN_BUCKETS)
        reg.histogram("request_cached_tokens", REQUEST_TOKEN_BUCKETS)
        reg.histogram("request_decode_steps", REQUEST_TOKEN_BUCKETS)
        reg.histogram("request_decode_tokens", REQUEST_TOKEN_BUCKETS)
        reg.histogram("request_page_seconds", PAGE_SECONDS_BUCKETS)
        reg.histogram("request_queue_seconds", REQUEST_SECONDS_BUCKETS)
        reg.histogram("request_prefill_seconds", REQUEST_SECONDS_BUCKETS)
        reg.histogram("request_decode_seconds", REQUEST_SECONDS_BUCKETS)
        reg.histogram("request_e2e_seconds", REQUEST_SECONDS_BUCKETS)
        reg.histogram("request_peak_pages", REQUEST_TOKEN_BUCKETS)
        # Memory-pressure forensics: one counter per captured incident
        # (the chaos suite reconciles it against the injection
        # schedule) backing the bounded ring /debug/oom serves.
        reg.counter("oom_forensics_total", ("trigger",))
        # Tokens the emit phase hands to `tokenizer.decode` (over
        # decode_steps_useful: the tokens decoded for one emitted, a
        # small constant whatever the reply's length).
        self._emit_decode = _CountedDecode(
            pipe.tokenizer,
            reg.counter("emit_decoded_tokens_total").labels(),
        )
        if self.block:
            # Block-diffusion and expert-layer accounting, per block
            # dispatch (docs/OBSERVABILITY.md "Block diffusion"). The
            # moe_* families count the block step's forwards only (the
            # prefill program returns no counts) and stay 0 on a dense
            # model. rows_max / rows_mean over a window is the expert
            # imbalance; experts_hit sizes the weight bytes a forward
            # had to read.
            reg.counter("diffusion_blocks_total")
            reg.counter("diffusion_tokens_unmasked_total")
            # A block's commit rides the next block's first forward
            # (DESIGN.md "Block diffusion"), or never happens: no
            # forward only commits, and the series says so at 0.
            reg.counter("diffusion_forwards_total", ("kind",)).labels(
                kind="commit")
            for how in ("fused", "dropped"):
                reg.counter("diffusion_commits_total", ("how",)).labels(
                    how=how)
            # One dispatch in flight (DESIGN.md, the section of that name): blocks
            # enqueued while another was unread (against dispatches_
            # total{kind="block"}: the share that hid the host), and
            # slot-blocks computed for a placement that had ended.
            reg.counter("block_dispatches_ahead_total")
            reg.counter("block_rows_dropped_total")
            reg.counter("moe_rows_routed_total")
            reg.counter("moe_expert_rows_max_total")
            reg.counter("moe_expert_rows_mean_total")
            reg.counter("moe_experts_hit_total")
        elif self._ahead:
            # The split engine's twins (DESIGN.md "One dispatch in
            # flight"), against dispatches_total{kind="decode"}.
            reg.counter("decode_dispatches_ahead_total")
            reg.counter("decode_rows_dropped_total")
        # A model whose expert layer holds a share of the routed experts
        # or has zero-compute experts: per decode dispatch, from the
        # routing the step returns (generate.SHARE_STATS; docs/
        # OBSERVABILITY.md "Expert share"). rows_max / rows_mean count
        # the HELD experts' rows.
        whole_stats = bool(
            (self.windowed or self.recurrent) and pipe.cfg.llm.num_experts)
        self.share_stats = bool(
            pipe.cfg.llm.experts_held or pipe.cfg.llm.zero_experts
            or whole_stats)
        if pipe.cfg.llm.n_shared_experts:
            # Rows the shared expert computed, a layer-forward each.
            reg.counter("moe_shared_rows_total")
        # Prefill chunks count their held experts too (`paged_prefill`'s
        # held_stats), on the single latent block alone: the shortcut-
        # connected double layer's prefill is the program its accepted
        # cell was measured on, and one more output is another program.
        self.prefill_held_stats = bool(
            (pipe.cfg.llm.experts_held
             and not pipe.cfg.llm.shortcut_double_layer) or whole_stats)
        # A device array a chunk, until a read that waits anyway: the
        # decode chunk's harvest or a prompt's first token, a round
        # later at most (`_drain_prefill_held`).
        self._prefill_held: list = []  # thread-owned: engine
        if self.prefill_held_stats:
            reg.counter("moe_prefill_pairs_total")
            reg.counter("moe_prefill_held_rows_total")
            reg.counter("moe_prefill_held_experts_hit_total")
            reg.counter("moe_prefill_held_expert_slots_total")
        if self.share_stats:
            reg.counter("moe_pairs_total")
            reg.counter("moe_zero_pairs_total")
            reg.counter("moe_held_experts_hit_total")
            reg.counter("moe_held_expert_slots_total")
            reg.counter("moe_expert_rows_max_total")
            reg.counter("moe_expert_rows_mean_total")
            reg.counter("decode_kv_tokens_total")
        if self.recurrent:
            # The recurrent state's accounting (docs/OBSERVABILITY.md
            # "Recurrent state", "Gated short convolution"), under the
            # state kind's prefix (`ssm_` a Mamba hybrid's, `conv_` a
            # gated short convolution's): prompt tokens the prefill ran
            # over, live lanes x steps of the decode update and the
            # cached tokens their attention read (both from the lengths
            # a chunk ran with and came back with), sequences that
            # started from a zero state, and the planes' bytes. A conv
            # state beside: sequences that started from a page-edge
            # snapshot, snapshots written, and the snapshots' bytes.
            self._state = "conv" if self.conv_state else "ssm"
            reg.counter("decode_kv_tokens_total")
            width = jnp.dtype(oryx.compute_dtype(pipe.cfg)).itemsize
            state_bytes = num_slots * llm.state_bytes_per_slot(width)
            if not self.conv_state:
                reg.counter("ssm_prefill_tokens_total")
                reg.counter("ssm_decode_lane_steps_total")
                reg.counter("ssm_state_resets_total")
                reg.gauge("ssm_state_bytes").set(state_bytes)
                if self._ssd_chunk:
                    # Chunks of mamba_chunk_size tokens a prefill
                    # dispatch's REAL tokens fill, a mixer: what the
                    # chunked scan's matrix products ran over.
                    reg.counter("ssd_prefill_chunks_total")
            else:
                reg.counter("conv_prefill_tokens_total")
                reg.counter("conv_decode_lane_steps_total")
                reg.counter("conv_state_resets_total")
                reg.gauge("conv_state_bytes").set(state_bytes)
                reg.counter("conv_state_handovers_total")
                reg.counter("conv_edge_writes_total")
                reg.gauge("conv_edge_bytes").set(
                    self.num_pages * llm.state_bytes_per_slot(width))
            if whole_stats:  # the block step's name, as a window model
                reg.counter("moe_experts_hit_total")
        self.wplane = None
        if self.windowed:
            # Both planes' accounting (docs/OBSERVABILITY.md "Window
            # layers"): what a window layer's decode rows and prefill
            # chunks could see, beside decode_kv_tokens_total and
            # prefill_attn_pairs_total, which count what a GLOBAL layer
            # sees; the window pages given back; the pages each plane
            # holds. moe_* (held = every expert) from decode dispatches
            # and prefill chunks, under the block step's names.
            reg.counter("decode_window_kv_tokens_total")
            reg.counter("prefill_window_attn_pairs_total")
            reg.counter("kv_window_pages_released_total")
            reg.counter("moe_experts_hit_total")
            live = reg.gauge("kv_pages_live", ("plane",))
            self._pages_live = {
                p: live.labels(plane=p).set for p in ("global", "window")}
            # The longest dispatch a lane rides: a prefill chunk with
            # its padding, or a decode chunk.
            self._wtable_pages = paged_kv.window_table_pages(
                pipe.cfg.llm.sliding_window, max(prefill_chunk, chunk),
                page_size)
            # As many pages a slot as its table is wide, times the share
            # of a full pool that num_pages asks for of the global one.
            self.num_window_pages = max(self._wtable_pages, -(
                -num_slots * self._wtable_pages * self.num_pages
                // (num_slots * self.max_pages)))
            self.wplane = self._new_window_plane()
        self.allocator = self._new_allocator()
        # Page-pool observatory (utils/pagemap.py): oryx_pool_* gauges
        # refreshed at scrape time + the free-time page-lifetime/idle
        # histograms the allocator feeds through its observer hook.
        # Constructed once (families may not be re-declared); every
        # pool rebuild re-attaches the fresh allocator.
        self.pool_observatory = pagemap.PoolObservatory(
            reg, lambda: self.allocator
        )
        self.pool_observatory.attach(self.allocator)
        # OOM forensic ring (utils/forensics.py): every OutOfPagesError
        # and degraded-mode escalation captures a bounded record,
        # served at GET /debug/oom.
        self.forensics = forensics or forensics_lib.ForensicRing()
        # Continuous device-time attribution (utils/profiling.py):
        # every `profile_sample_every` engine steps ONE dispatch is
        # bracketed in a jax.profiler capture and its device busy time
        # lands on oryx_device_time_seconds_total{kind=} + the step's
        # timeline record (device_us). 0 = periodic sampling off; the
        # sampler still serves on-demand /debug/profile captures.
        if not isinstance(profile_sample_every, int) \
                or profile_sample_every < 0:
            raise ValueError(
                "profile_sample_every must be a non-negative integer "
                f"(steps between samples; 0 = off), got "
                f"{profile_sample_every!r}"
            )
        self.profiler = profiling_lib.DeviceTimeSampler(
            reg, every=profile_sample_every
        )
        # On-demand capture coordination: HTTP threads park a request
        # here (request_profile); the engine loop adopts it at the next
        # step and completes it over the asked number of dispatches.
        self._profile_pending = None  # guarded-by: _cond
        self._profile_active = None  # thread-owned: engine
        # Pool-pressure episode arming: the REAL capacity path (free
        # list short, eviction pending) retries every engine step
        # while a head waits — capture ONE forensic per episode
        # (armed at the first failed grow/splice, cleared by the next
        # successful allocation), not one per step.
        self._oom_episode = False  # thread-owned: engine
        self.prefix_cache = (
            self._build_prefix_cache() if prefix_cache else None
        )
        self.kv_pages = self._new_pool()
        S = num_slots
        self._sentinel = self.allocator.sentinel
        self.bt = np.full((S, self.max_pages), self._sentinel, np.int32)
        self.tok = np.zeros((S,), np.int32)
        self.lengths = np.zeros((S,), np.int32)
        self.finished = np.ones((S,), bool)  # empty slots ride as finished
        self.temp = np.zeros((S,), np.float32)
        self.top_p = np.ones((S,), np.float32)
        self.top_k = np.zeros((S,), np.int32)
        self.stop_sequences = pipe.stop_sequences  # template stop (device)
        stop_L = (
            0 if self.stop_sequences is None else self.stop_sequences.shape[1]
        )
        self.recent = np.full((S, stop_L), -2, np.int32)
        self.keys = jax.random.split(jax.random.key(seed), S)
        # Block mode: the slot's open block (its known tokens first; the
        # device fills the rest with the mask id) and how many are known.
        self.blk = np.zeros((S, max(self.block, 1)), np.int32)
        self.blk_known = np.zeros((S,), np.int32)
        # The slot's PENDING block: generated by the last dispatch, its
        # K/V not yet in the pages; the next dispatch's first forward
        # commits it. It belongs to a placement (the admit_seq here, -1
        # for none), and its tokens are slot s's row of the last
        # dispatch's output, which never leaves the device for this.
        self.blk_pending = np.full((S,), -1, np.int64)
        self._pending_toks = None  # thread-owned: engine
        # The decode dispatch enqueued and not yet read (a block, or the
        # split engine's chunk: at most one between two engine steps),
        # and when the last harvest returned: the device begins a
        # dispatch enqueued ahead about then.
        self._inflight: _Flight | None = None  # thread-owned: engine
        self._read_ns = 0  # thread-owned: engine
        # The split engine's lane state lives ON THE DEVICE between
        # chunks (tok, lengths, finished, recent as the last enqueued
        # chunk leaves them; `keys` beside them), so chunk n+1 is
        # enqueued before chunk n is read. The host's own arrays above
        # say where the chunk in flight WILL leave a live lane
        # (`lengths` advanced at the enqueue: exact for a lane that is
        # live at the next one, an upper bound for one that meets EOS);
        # `_edited` marks the lanes it changed since the last enqueue,
        # which `generate.overlay_lanes` lays over the device's.
        self._lanes = None  # thread-owned: engine
        self._edited = np.zeros((S,), bool)
        # What the device has CONFIRMED: the last harvested chunk's
        # lengths for every lane (what the next one to be read ran
        # with), and for a slot's own placement the length its K/V is
        # known to reach (what `_finish` may donate).
        self._seen_lengths = np.zeros((S,), np.int32)
        self.confirmed = np.zeros((S,), np.int32)
        # slot -> (request, tok0 on the device): first tokens seated in
        # the next chunk and not yet read by the host.
        self._first: dict[int, tuple] = {}  # thread-owned: engine
        self._ragged_blanks = None
        if self.ragged:
            # The pure-decode shape class's constant prefill operands,
            # built ONCE: _ragged_step is hot-path and would otherwise
            # pay ~8 fresh host->device constants per steady-state
            # step. (The dummy key only feeds the discarded
            # pf_key_next; any fixed key is correct.)
            self._ragged_blanks = (
                jnp.zeros((1, 0, self.cfg.llm.hidden_size),
                          oryx.compute_dtype(self.cfg)),
                jnp.asarray(0, jnp.int32),
                jnp.asarray(0, jnp.int32),
                jnp.asarray(0, jnp.int32),
                jnp.asarray(False),
                jax.random.split(jax.random.key(0), 1),
                jnp.zeros((1,), np.float32),
                jnp.ones((1,), np.float32),
                jnp.zeros((1,), np.int32),
            )
        # `slots`/`bt`/`lengths`/... are engine-thread-only; the ONLY
        # state shared with the HTTP submit threads is the queue and
        # the shutdown flag, and oryxlint enforces that every touch of
        # them happens under the condition's lock.
        self.slots: list[_Request | None] = [None] * S
        self._queue: deque[_Request] = deque()  # guarded-by: _cond
        self._cond = named_lock("scheduler._cond", kind="condition")
        self._shutdown = False  # guarded-by: _cond
        self._draining = False  # guarded-by: _cond
        self._admit_seq = 0
        self.chunks_run = 0
        # Failure-containment knobs. max_queue bounds admission
        # (backpressure -> AdmissionRejected -> HTTP 429);
        # request_timeout is the default per-request deadline.
        self.max_queue = max_queue
        self.request_timeout = request_timeout
        # Degraded-mode ladder (0 normal, 1 shed prefix cache, 2 clamp
        # max_tokens, 3 shed load), escalated by serving SLO anomaly
        # firings and walked back after `degraded_cooldown` quiet
        # seconds. The mode is read by submit() (HTTP threads) and
        # written by the engine thread, both under _cond.
        self.degraded_cooldown = degraded_cooldown
        self.degraded_clamp_tokens = degraded_clamp_tokens
        self._degraded = 0  # guarded-by: _cond
        self._slo_fired_seen = 0
        self._degraded_changed = time.monotonic()
        self._cache_shed = False  # engine-thread-only
        self.restarts = 0
        # Dead-engine admission guard: once the loop has STARTED, a
        # dead thread with nobody to revive it (no EngineSupervisor —
        # which calls set_supervised(True) — or one that gave up and
        # cleared it) must reject new work instead of queueing
        # requests whose handles can never complete. Written by the
        # supervisor's thread, read by submit(): under _cond on both
        # sides.
        self._started = False
        self.supervised = False  # guarded-by: _cond
        # Flight recorder of the last N requests (shared with the API
        # server's /debug endpoints when it passes its own tracer) plus
        # an optional stall watchdog: no decode chunk completing within
        # stall_timeout while slots are live dumps every thread stack +
        # the recorder tail to stderr, once per stall.
        self.tracer = tracer or trace_lib.Tracer()
        # Step timeline (utils/timeline.py): one fixed-shape record per
        # device dispatch, written lock-free from the engine thread and
        # served at GET /debug/timeline — the engine's flight data
        # recorder. Always on: a disabled recorder during the incident
        # it exists for would be the wrong default, and the disarmed
        # cost is one dict build per dispatch.
        self.timeline = timeline or StepTimeline()
        # Where the engine thread's time goes (ENGINE_PHASES). The
        # children are made here so every phase renders from the first
        # scrape and a phase boundary costs one locked add.
        # Beside them, per phase: the seconds of it in which the
        # device had nothing queued (`oryx.engine.host` was open), and
        # the seconds of its stretches over STALL_SECONDS.
        phases = BLOCK_ENGINE_PHASES if self.block else ENGINE_PHASES

        def per_phase(fam) -> dict:
            return {p: fam.labels(phase=p).inc for p in phases}

        reg = self.metrics.registry
        self._phase_seconds = per_phase(
            reg.counter("engine_phase_seconds_total", ("phase",))
        )
        self._starved_seconds = per_phase(
            reg.counter("engine_starved_seconds_total", ("phase",))
        )
        self._stall_seconds = per_phase(
            reg.counter("engine_stall_seconds_total", ("phase",))
        )
        self._last_dispatch = ""  # thread-owned: engine
        self._phases = self._new_phase_clock()
        # Wide-event request log (utils/request_log.py): one canonical
        # JSONL event per terminal request, merging the cost ledger,
        # span wall-times, outcome and routing identity. engine_label/
        # replica_id are this engine's identity fields in those events.
        self.request_log = request_log or request_log_lib.RequestLog()
        self.engine_label = engine_label
        self.replica_id = replica_id
        # Decision journal (serve/journal.py): the deterministic flight
        # recorder. None = disarmed, and every instrumentation site is
        # a single attribute check (the observe-never-perturb contract
        # check_tier1.sh gates byte-for-byte). The scheduler stamps its
        # EFFECTIVE geometry — num_pages resolved, clamp knobs — so
        # scripts/replay_journal.py can rebuild this exact scheduler
        # cold from the header alone.
        self.journal = journal
        # Dispatch counter gating journal entries and replay feeding:
        # unlike chunks_run (decode chunks only), this advances at
        # EVERY recorded dispatch, so split-mode prefill-only
        # iterations can't alias two loop turns onto one gate value.
        self.steps_run = 0  # thread-owned: engine
        # Replay feeding hook (scripts/replay_journal.py): called at
        # the top of every engine-loop iteration; None in live serving.
        self.replay_feeder = None  # thread-owned: engine
        if self.journal is not None:
            self.journal.stamp_header(
                num_slots=num_slots, page_size=page_size, chunk=chunk,
                max_ctx=max_ctx, num_pages=self.num_pages, seed=seed,
                prefill_chunk=prefill_chunk,
                prefix_cache=bool(prefix_cache),
                ragged=self.ragged, speculate=self.speculate,
                draft_model=getattr(self.drafter, "source", None),
                kv_dtype=kv_dtype, host_cache_bytes=host_cache_bytes,
                max_queue=max_queue,
                degraded_clamp_tokens=degraded_clamp_tokens,
                engine=engine_label, replica=replica_id,
            )
            self.journal.seal_header()
            # Fault firings reach the journal through the module-level
            # observer hook (utils/faults.py) — the seeded schedule
            # makes the (site, count) stream reproducible, which is
            # what lets replay assert fault-for-fault equality.
            faults.add_observer(self._journal_fault)
        # Output auditor (serve/audit.py): shadow-parity replays of
        # every Nth finished request, run on THIS thread at idle
        # points only. Constructed unconditionally so the oryx_audit_*
        # ladders render (at zero) even when sampling is off.
        self.auditor = audit_lib.OutputAuditor(
            pipe, page_size=page_size, max_ctx=max_ctx,
            sample_every=audit_sample_every, metrics=self.metrics,
            request_log=self.request_log, anomaly=self.anomaly,
            engine_label=engine_label, replica_id=replica_id,
            kv_dtype=kv_dtype,
            fail_abs_tol=audit_tol_maxdiff, fail_kl_tol=audit_tol_kl,
        )
        # Numerics sentinels (utils/numerics.py): every
        # `numerics_every` engine steps the dispatch carries the logit
        # -stat probe (a static-flag twin of the same program — extra
        # scalar outputs, zero extra dispatches). 0 = off; the gauges
        # are pre-registered either way.
        if not isinstance(numerics_every, int) or numerics_every < 0:
            raise ValueError(
                "numerics_every must be a non-negative integer (steps "
                f"between probe samples; 0 = off), got {numerics_every!r}"
            )
        if numerics_every and self.speculate:
            # Fail fast instead of arming a probe that never samples:
            # every decode dispatch in speculative mode is a
            # paged_spec_step, which does not carry the numerics
            # outputs (yet) — accepting the flag would leave the
            # oryx_numerics_* gauges silently frozen at zero.
            raise ValueError(
                "numerics_every is not supported with speculate>0: the "
                "speculative verify step carries no numerics probe — "
                "drop --numerics-every or --speculate"
            )
        self.numerics_every = numerics_every
        # Literal declarations (the greppable source of truth is
        # numerics_lib.NUMERICS_GAUGES; tests assert the two agree).
        self._numerics_gauges = {
            "finite_frac": reg.gauge(
                "oryx_numerics_logits_finite_frac", raw_name=True
            ),
            "absmax": reg.gauge(
                "oryx_numerics_logits_absmax", raw_name=True
            ),
            "rms": reg.gauge("oryx_numerics_logits_rms", raw_name=True),
            "entropy": reg.gauge(
                "oryx_numerics_logits_entropy", raw_name=True
            ),
            "top1_margin": reg.gauge(
                "oryx_numerics_logits_top1_margin", raw_name=True
            ),
        }
        self._numerics_samples = reg.counter(
            "oryx_numerics_samples_total", raw_name=True
        )
        self.watchdog: trace_lib.StallWatchdog | None = None
        if stall_timeout is not None:
            self.watchdog = trace_lib.StallWatchdog(
                self.tracer, stall_timeout, name="continuous-scheduler"
            ).start()
        # The thread NAME is part of the concurrency model
        # (oryx_tpu/concurrency.py): `# thread-owned: engine` state
        # belongs to it, and the race detector's reports name it.
        self._thread = threading.Thread(
            target=self._run, name="oryx-engine", daemon=True
        )
        if autostart:
            self._thread.start()

    # ---- public API (the Engine protocol surface, serve/engine.py) -------

    def _pool_kv_dtype(self) -> str | None:
        """init_paged_kv_cache's kv_dtype spelling of the flag value
        (None = dense pages in the compute dtype)."""
        return None if self.kv_dtype == "bf16" else self.kv_dtype

    def _new_pool(self):
        """A zeroed pool, placed: the pages and, for a model with
        state-space layers, the per-slot state planes."""
        return self._place_kv(qwen2.init_paged_kv_cache(
            self.cfg.llm,
            (self.num_pages, self.num_window_pages) if self.windowed
            else self.num_pages,
            self.page_size,
            dtype=oryx.compute_dtype(self.cfg),
            kv_dtype=self._pool_kv_dtype(),
            **({"num_slots": self.num_slots} if self.recurrent else {}),
        ))

    def _new_allocator(self) -> paged_kv.PageAllocator:
        """The free list over `num_pages`: every layer's pages, or the
        GLOBAL plane's where window layers have one of their own."""
        return paged_kv.PageAllocator(
            self.num_pages, self.page_size,
            **({"plane": "global"} if self.windowed else {}))

    def _new_window_plane(self) -> paged_kv.WindowPlane:
        return paged_kv.WindowPlane(
            self.num_window_pages, self.page_size, self.num_slots,
            self._wtable_pages, self.cfg.llm.sliding_window)

    def _build_prefix_cache(self) -> PagedPrefixCache:
        """The prefix cache over the CURRENT allocator, host spill
        tier wired when --host-cache-bytes asked for one. The spill
        callbacks read/write `self.kv_pages` at call time (the pool's
        identity changes at every donated dispatch), and upload runs
        under the pipe's mesh scope so a heads-sharded pool re-places
        the page correctly."""
        if self.recurrent and not self.conv_state:
            raise ValueError(qwen2.unsupported_for_recurrent(
                "prefix-cache splicing"))
        if self.windowed:
            raise ValueError(qwen2.unsupported_for_window(
                "prefix-cache splicing"))
        return PagedPrefixCache(
            self.allocator, metrics=self.metrics,
            host_cache_bytes=self.host_cache_bytes,
            spill_fetch=self._spill_fetch,
            spill_upload=self._spill_upload,
        )

    def _spill_fetch(self, page: int):
        """Device -> host byte copy of one pool page (engine thread;
        the prefix cache's spill_fetch callback)."""
        blob = paged_kv.fetch_page(self.kv_pages, int(page))
        return blob, paged_kv.host_blob_bytes(blob)

    def _spill_upload(self, blob, page: int) -> None:
        """Host -> device byte copy into a freshly allocated pool page
        (engine thread; the prefix cache's spill_upload callback).
        Donates and reassigns the pool like every other device edit."""
        with self.pipe._mesh_scope():
            self.kv_pages = paged_kv.upload_page(
                self.kv_pages, jnp.asarray(int(page), jnp.int32), blob
            )

    def _place_kv(self, kv_pages):
        """Tensor-parallel placement of the paged pool: KV heads
        sharded over the pipe mesh's tp axis (a no-op off-mesh, on an
        fsdp-only mesh, or when heads don't divide). Every dispatch
        already runs under `pipe._mesh_scope()`, so with the pool AND
        the params placed, GSPMD partitions paged prefill/decode by
        heads — each shard runs its own heads bit-identically to the
        single-device path, and only o_proj's contraction crosses
        shards. Applied at construction and every `_reset_pool`."""
        mesh = getattr(self.pipe, "mesh", None)
        if mesh is None:
            return kv_pages
        from oryx_tpu.parallel.sharding import shard_paged_kv

        return shard_paged_kv(
            kv_pages, mesh, num_kv_heads=self.cfg.llm.num_kv_heads
        )

    def readiness(self) -> tuple[bool, str]:
        """(ready, reason): this engine can make progress — not
        draining, loop thread alive, and (when a watchdog is armed) no
        in-flight stall. The /readyz signal routers eject on."""
        if self.draining:
            return False, "draining"
        if not self.alive():
            return False, "scheduler loop dead"
        wd = self.watchdog
        if wd is not None and wd.stalled():
            return False, (
                f"scheduler stalled (no decode beat in {wd.deadline_s:g}s)"
            )
        return True, "ok"

    def cancel(self, handle: RequestHandle) -> None:
        """Cancel a submitted request wherever it lives; the engine
        loop frees its slot/pages at the next harvest or prefill step
        (same path a client disconnect takes)."""
        handle.cancelled = True

    def stop(self) -> None:
        """Engine-protocol spelling of close(): stop the loop without
        waiting for resident requests (drain() is the graceful twin)."""
        self.close()

    def set_supervised(self, value: bool) -> None:
        """EngineSupervisor attach/detach. Under _cond like every other
        reader/writer of the flag: a race between the supervisor's
        give-up and a submit() would otherwise queue a request nobody
        will ever complete."""
        with self._cond:
            self.supervised = value

    def queue_len(self) -> int:
        """Admission-queue depth, under the lock (tests and debug
        endpoints must not peek at `_queue` bare — the race detector
        enforces exactly that when armed)."""
        with self._cond:
            return len(self._queue)

    def request_profile(self, steps: int, timeout: float = 60.0
                        ) -> dict[str, Any]:
        """On-demand device-time capture (the GET /debug/profile
        entry point, any thread): park a request for the engine loop,
        which brackets its next `steps` dispatches in one
        jax.profiler capture and returns the Perfetto-loadable Chrome
        trace + per-kind device-time attribution. Raises TimeoutError
        when the engine ran no dispatches in time (an idle engine
        cannot be profiled — send it traffic first) and RuntimeError
        when a capture is already in flight or the capture failed."""
        if not isinstance(steps, int) or steps < 1:
            raise ValueError(f"steps must be a positive integer, "
                             f"got {steps!r}")
        holder: dict[str, Any] = {
            "steps": steps, "done": threading.Event(), "result": None,
        }
        with self._cond:
            if self._profile_pending is not None:
                raise RuntimeError(
                    "a profile capture is already queued"
                )
            self._profile_pending = holder
            self._cond.notify()
        if not holder["done"].wait(timeout):
            with self._cond:
                # Safe check-then-act: the guard for this clear is the
                # IDENTITY re-check on this line, under this lock
                # acquisition (only OUR holder is ever removed); the
                # earlier emptiness check going stale is harmless —
                # an adopted holder simply isn't pending any more.
                if self._profile_pending is holder:
                    self._profile_pending = None  # oryxlint: disable=atomicity
            raise TimeoutError(
                f"no completed profile capture within {timeout:g}s "
                "(engine idle, or a capture already in flight — "
                "profiling needs live dispatches)"
            )
        result = holder["result"]
        if isinstance(result, dict) and "error" in result:
            raise RuntimeError(result["error"])
        return result

    def start(self) -> None:
        if not self._thread.is_alive():
            self._started = True
            self._thread.start()

    def submit(
        self,
        request: dict[str, Any],
        max_new: int,
        sampling: dict[str, Any] | None = None,
        *,
        streaming: bool = False,
        timeout_s: float | None = None,
        request_id: str | None = None,
        routed: bool = False,
    ) -> RequestHandle:
        """Queue one request; raises AdmissionRejected (without
        queueing anything) when draining, shedding load (degraded mode
        3), or the bounded queue is full. timeout_s overrides the
        scheduler-wide request_timeout deadline for this request.

        request_id: a client-supplied X-Request-Id to honor as the
        trace id (already sanitized by the HTTP layer); the tracer
        atomically replaces it with a minted id when it collides with
        a trace the flight recorder still holds — an id must name ONE
        request.
        routed: the request came through the front-end router (stamped
        into the wide event)."""
        sampling = sampling or {}
        h = RequestHandle()
        h.streaming = streaming
        stops = (
            [self.pipe.conv.stop_str] if self.pipe.conv.stop_str else []
        ) + [s for s in (sampling.get("stop") or []) if s]
        tr = self.tracer.start_trace(
            "request", label=f"chat max_new={max_new}", id=request_id,
        )
        h.request_id = tr.id
        h.trace = tr
        h.debug["request_id"] = tr.id
        now = time.monotonic()
        eff_timeout = (
            timeout_s if timeout_s is not None else self.request_timeout
        )
        req = _Request(
            request=request, max_new=max_new, sampling=sampling,
            handle=h, submit_time=now, stops=stops, trace=tr,
            deadline=(now + eff_timeout) if eff_timeout else None,
            routed=routed,
        )
        req.qw_span = tr.begin("queue_wait")
        if self.journal is not None:
            # Journal the arrival BEFORE the admission-control verdict:
            # the submit entry is the replayable workload record
            # (arrival order + payload + requested knobs), whatever
            # happens to the request next. journal_seq joins the wide
            # event / /debug/requests meta back to this entry.
            req.journal_seq = self._journal_submit(req)
            tr.annotate(journal_seq=req.journal_seq)
        with self._cond:
            # Admission-control checks and the append are one atomic
            # section: two racing submits can never both squeeze into
            # the last queue slot.
            reject = None
            if self._shutdown or self._draining:
                reject = ("draining", "server is draining; not "
                          "accepting new requests", 1.0)
            elif (
                self._started and not self._thread.is_alive()
                and not self.supervised
            ):
                # Permanently dead engine (no supervisor, or it gave
                # up): queueing would hang the client forever — the
                # deadline enforcer lives in the dead loop too.
                reject = ("engine_dead", "engine is not running and "
                          "nothing will restart it", 5.0)
            elif self._degraded >= 3:
                reject = ("shed_load", "server is shedding load "
                          "(degraded mode 3); retry shortly", 2.0)
            elif (
                self.max_queue is not None
                and len(self._queue) >= self.max_queue
            ):
                # Retry-After scales with how deep the backlog runs
                # relative to serving capacity — a rough token-bucket
                # hint, not a promise.
                retry = min(
                    30.0, 1.0 + len(self._queue) / max(1, self.num_slots)
                )
                reject = ("backpressure",
                          f"admission queue full ({len(self._queue)} "
                          f">= {self.max_queue})", retry)
            if reject is None:
                self._queue.append(req)
                depth = len(self._queue)
                self.metrics.set_gauge("queue_depth", depth)
                self._cond.notify()
        if reject is not None:
            reason, msg, retry_after = reject
            self.metrics.inc(
                "admission_rejected_total", labels={"reason": reason}
            )
            if self.journal is not None:
                # Excluded from replay comparison by contract
                # (REPLAYED_KINDS): admission control is load/timing-
                # coupled, so a replayed run legitimately admits what
                # the live run shed.
                self.journal.append(journal_lib.build_journal_event(
                    kind="reject", request_id=tr.id, reason=reason,
                ))
            cost = self._finalize_cost(None, req, observe=False)
            tr.finish(error=msg, rejected=reason, cost=cost)
            self._emit_request_event(
                req, status="rejected", error_kind=reason
            )
            _LOG.info("request %s rejected (%s)", tr.id, reason)
            raise AdmissionRejected(
                msg, reason=reason, retry_after_s=retry_after
            )
        _LOG.info("request %s queued (max_new=%d)", tr.id, max_new)
        if self.anomaly is not None:
            self.anomaly.observe_queue_depth(depth)
        return h

    def close(self) -> None:
        with self._cond:
            self._shutdown = True
            self._cond.notify()
        if self._thread.is_alive():
            self._thread.join(timeout=30)
        if self.watchdog is not None:
            self.watchdog.stop()
        if self.journal is not None:
            # Detach the process-global fault observer (the journal
            # itself is closed by its owner — build_server, or the
            # replay harness).
            faults.remove_observer(self._journal_fault)

    def begin_drain(self) -> None:
        """Start drain-on-shutdown: admission stops NOW (new submits
        rejected, queued-but-unadmitted requests errored with
        "draining"), resident requests — decoding or mid-prefill —
        run to completion, then the engine loop exits. /readyz flips
        503 the moment this is called (the `draining` property)."""
        with self._cond:
            if self._draining:
                return
            self._draining = True
            self._cond.notify()
        _LOG.info("drain started: admission stopped, finishing "
                  "resident requests")

    def drain(self, timeout: float | None = 60.0) -> bool:
        """begin_drain() + wait for the engine loop to finish resident
        work and exit; returns whether it fully drained in time."""
        self.begin_drain()
        if self._thread.is_alive():
            self._thread.join(timeout=timeout)
        drained = not self._thread.is_alive()
        if drained:
            with self._cond:
                stranded = bool(self._queue) or any(
                    r is not None for r in self.slots
                )
            if stranded:
                # The engine died before (or without) running the
                # drain path: its queue-flush and resident-finish
                # logic never ran, and nothing ever will complete
                # these handles. Error them out now so clients get a
                # retriable 503 instead of a connection reset at
                # shutdown.
                self.fail_inflight("server draining with engine stopped")
        if drained and self.watchdog is not None:
            self.watchdog.stop()
        return drained

    # obligations: _reset_pool
    def fail_inflight(self, msg: str, *, kind: str = "unavailable"
                      ) -> None:
        """Error out EVERY queued and resident request and rebuild the
        pool. Only for the engine-is-dead-and-staying-dead endgames
        (supervisor give-up, drain of a dead engine): with the loop
        stopped nothing else will ever complete these handles, and
        this is what turns "hang forever" into a retriable 503. Must
        not be called while the engine loop is running."""
        with self._cond:
            dropped = list(self._queue)
            self._queue.clear()
            self.metrics.set_gauge("queue_depth", 0)
        for r in dropped:
            self._reject_queued(r, msg, kind=kind)
        if dropped and self.anomaly is not None:
            self.anomaly.observe_queue_depth(0)
        for s, req in enumerate(self.slots):
            if req is not None:
                self._finish_error(s, msg, kind=kind)
        # The dead loop may have left the donated pool consumed;
        # rebuild (clears every slot, asserts check_invariant).
        self._reset_pool()

    @property
    def draining(self) -> bool:
        with self._cond:
            return self._draining

    def alive(self) -> bool:
        """Engine loop thread is running (the /readyz signal)."""
        return self._thread.is_alive()

    @property
    def stopping(self) -> bool:
        """close() or drain() in progress — the supervisor must not
        restart a deliberately stopped engine."""
        with self._cond:
            return self._shutdown or self._draining

    @property
    def degraded_mode(self) -> int:
        with self._cond:
            return self._degraded

    def restart(self) -> None:
        """Recover from engine-thread death (the supervisor's entry
        point): requeue every in-flight request at the FRONT for
        deterministic replay (same machinery as eviction: same key0,
        same prompt, `processed` tokens skipped on re-emission),
        rebuild the consumed page pool, verify the pool invariant, and
        start a fresh engine thread. No client sees an error."""
        if self._thread.is_alive():
            return
        live = sorted(
            ((req.admit_seq, s, req)
             for s, req in enumerate(self.slots) if req is not None),
            reverse=True,
        )
        for _, s, req in live:  # youngest first -> oldest ends at head
            # The pool rebuild below frees these pages without
            # _clear_slot: bank the page-seconds integral now so the
            # ledger doesn't lose the pre-crash residency.
            self._accrue_page_seconds(s)
            req.replay = req.processed
            req.evictions += 1
            req.activated = False
            req.spliced = 0
            req.prefill_pos = 0
            req.trace.event(
                "engine_restart_replay", slot=s,
                replay_tokens=req.processed,
            )
            self._requeue_spans(req)
            with self._cond:
                self._queue.appendleft(req)
        with self._cond:
            self.metrics.set_gauge("queue_depth", len(self._queue))
        # The dead dispatch may have consumed the donated pool; rebuild
        # (this clears every slot and asserts check_invariant). Any
        # capture the dead thread left running is discarded too.
        self._abort_profile()
        self._reset_pool()
        self.restarts += 1
        self.metrics.inc("engine_restarts_total")
        if self.journal is not None:
            # Supervisor thread, engine dead: steps_run is quiescent
            # and safe to read here — the restart's position in the
            # step stream is exactly what replay reproduces.
            self.journal.append(journal_lib.build_journal_event(
                kind="restart", step=self.steps_run,
                restarts=self.restarts, requeued=len(live),
            ))
        _LOG.warning(
            "engine thread restarted (#%d): %d request(s) requeued "
            "for replay", self.restarts, len(live),
        )
        self._thread = threading.Thread(
            target=self._run, name="oryx-engine", daemon=True
        )
        self._thread.start()

    # ---- slot bookkeeping ------------------------------------------------

    def _reset_pool(self) -> None:
        """Fresh page pool + allocator + prefix cache + empty slot state
        (used after a device-step failure invalidated the donated pool).
        Callers have already errored-out every in-flight request."""
        self.allocator = self._new_allocator()
        if self.windowed:
            self.wplane = self._new_window_plane()
        # A fresh allocator starts with observer=None: re-attach so
        # page-lifetime telemetry keeps flowing after the rebuild.
        self.pool_observatory.attach(self.allocator)
        if self.prefix_cache is not None:
            # The old cache indexed pages of the CONSUMED pool; rebuild
            # it over the fresh allocator (the host tier restarts empty
            # too: its blobs are still valid KV bytes, but re-seeding
            # them into a fresh trie buys little against the complexity
            # of a partial-trust tier after a crash).
            self.prefix_cache = self._build_prefix_cache()
        self.kv_pages = self._new_pool()
        self.bt[:] = self._sentinel
        self._oom_episode = False
        self.slots = [None] * self.num_slots
        self._prefill_held = []
        self.finished[:] = True
        self.lengths[:] = 0
        self.tok[:] = 0
        self.recent[:] = -2
        self.blk[:] = 0
        self.blk_known[:] = 0
        self.blk_pending[:] = -1
        self._pending_toks = None  # a failed dispatch's output is lost
        self._inflight = None  # its pool is gone
        self._lanes = None  # built again from the host's arrays
        self._edited[:] = False
        self._first.clear()
        self._check_pool_invariant()

    def _check_pool_invariant(self) -> None:
        """Every page is either free or exactly accounted to its holders
        (slot block tables + the prefix cache); raises RuntimeError with
        the offending page on leak/double-hold. Cheap enough to call
        from tests after any workload. Callers assert quiescence by
        contract (tests between bursts, the engine between chunks), so
        the cross-thread reads of engine-owned structures here are
        declared exempt to the armed race detector."""
        with race_exempt("pool-invariant check: caller asserts quiescence"):
            holders = [
                [int(p) for p in self.bt[s] if p != self._sentinel]
                for s in range(self.num_slots)
            ]
            if self.prefix_cache is not None:
                holders.append(self.prefix_cache.held_pages())
            self.allocator.check_invariant(holders)
            if self.wplane is not None:
                self.wplane.check_invariant()

    def pool_snapshot(self) -> dict[str, Any]:
        """The live page-ownership map + derived summary — the
        GET /debug/pages body (utils/pagemap.summarize over
        PageAllocator.snapshot). Thread contract: engine-owned state
        read best-effort from debug threads; exact on a quiesced
        engine, which is how the reconciliation gate
        (scripts/check_serving_endpoints.py) reads it — declared to
        the armed race detector like the pool-invariant check."""
        with race_exempt("pool snapshot: debug read, quiesced by "
                         "contract"):
            snap = self.allocator.snapshot()
            # Force-refresh the oryx_pool_* gauges from the same
            # moment, so a scrape right after this snapshot agrees
            # with it (the collector is otherwise TTL-cached).
            self.pool_observatory.collect(force=True)
        # Wire-format provenance + the pool's device byte cost
        # (metadata only — leaf shapes, no device sync): what turns
        # "peak pages" into "peak KV bytes" downstream, the unit the
        # int8 pool actually halves (pages are token-granular and
        # dtype-blind). Read off the LIVE pool, not the flag, so the
        # report can never disagree with what is actually resident
        # (a dense pool reports its real dtype, e.g. "float32").
        snap["kv_dtype"] = paged_kv.kv_pool_dtype(self.kv_pages)
        snap["kv_pool_bytes"] = int(sum(
            leaf.size * leaf.dtype.itemsize
            for leaf in jax.tree_util.tree_leaves(self.kv_pages)
        ))
        snap["summary"] = pagemap.summarize(snap)
        if self.wplane is not None:
            # The window layers' plane: its own ownership map, each
            # slot's table base, the same summary.
            with race_exempt("pool snapshot: debug read, quiesced by "
                             "contract"):
                win = self.wplane.allocator.snapshot()
                win["base"] = [int(b) for b in self.wplane.base]
            win["summary"] = pagemap.summarize(win)
            snap["window_plane"] = win
        return snap

    def _capture_oom(self, trigger: str, detail: str, *,
                     asking: tuple | None = None) -> None:
        """Forensic capture at a memory-pressure moment (engine thread
        only; docs/OBSERVABILITY.md "Memory & device time"): pool
        summary, top-K residents by pages held with their in-flight
        ledgers, the prefix cache's LRU tail, and the engine timeline
        tail land in the bounded ring (/debug/oom), plus one flat
        oom_pressure wide event through the request-log sink so
        requests.jsonl carries the greppable one-liner. `asking` =
        (slot, request, pages_needed) — the allocation that failed,
        which at admission time is not yet a resident but is exactly
        the request an operator wants named."""
        summary = pagemap.summarize(self.allocator.snapshot())
        residents = []
        for s, req in enumerate(self.slots):
            if req is None:
                continue
            residents.append(self._forensic_request(s, req))
        if asking is not None:
            s, req, need = asking
            if req is not None and all(
                r["request_id"] != req.trace.id for r in residents
            ):
                ent = self._forensic_request(s, req)
                ent["asking_pages"] = int(need)
                residents.append(ent)
        residents.sort(key=lambda r: -r["pages"])
        residents = residents[:forensics_lib.TOP_K]
        cache = None
        cache_lru = []
        if self.prefix_cache is not None:
            cache = {
                "entries": self.prefix_cache.entries,
                "pages": self.prefix_cache.pages,
                "evictable_pages": self.prefix_cache.evictable_pages(),
                # Host spill tier at the incident: what eviction can
                # still bank (vs drop) and how much budget remains.
                "spilled_pages": self.prefix_cache.spilled_pages,
                "host_bytes": self.prefix_cache.host_bytes,
            }
            leaves = sorted(
                self.prefix_cache.trie.leaves(), key=lambda n: n.stamp
            )
            for node in leaves[:forensics_lib.TOP_K]:
                depth = 0
                walk = node
                while walk is not None and walk.parent is not None:
                    depth += 1
                    walk = walk.parent
                cache_lru.append({
                    "leaf_page": node.payload,
                    "depth_pages": depth,
                    "lru_stamp": node.stamp,
                    "refcount": self.allocator.refcount(node.payload),
                })
        record = {
            "kind": "oom_pressure",
            "trigger": trigger,
            "detail": detail,
            "engine": self.engine_label,
            "replica": self.replica_id,
            "degraded_mode": int(self.metrics.get("degraded_mode")),
            "queue_depth": int(self.metrics.get("queue_depth")),
            "live_slots": sum(
                1 for r in self.slots if r is not None
            ),
            "pool": summary,
            "top_requests": residents,
            "cache": cache,
            "cache_lru": cache_lru,
            "timeline_tail": self.timeline.snapshot(16),
        }
        idx = self.forensics.append(record)
        self.metrics.inc(
            "oom_forensics_total", labels={"trigger": trigger}
        )
        self.request_log.append(request_log_lib.build_oom_event(
            trigger=trigger,
            detail=detail,
            engine=self.engine_label,
            replica=self.replica_id,
            degraded_mode=record["degraded_mode"],
            queue_depth=record["queue_depth"],
            live_slots=record["live_slots"],
            free_pages=summary["free"],
            slot_pages=summary["slot"],
            cache_pages=summary["cache"],
            shared_pages=summary["shared"],
            fragmentation_ratio=summary["fragmentation_ratio"],
            top_request_id=(
                residents[0]["request_id"] if residents else None
            ),
            top_request_pages=(
                residents[0]["pages"] if residents else 0
            ),
            forensic_index=idx,
        ))
        _LOG.warning(
            "memory-pressure forensic #%d captured (%s: %s; free=%d "
            "slot=%d cache=%d shared=%d)", idx, trigger, detail,
            summary["free"], summary["slot"], summary["cache"],
            summary["shared"],
        )

    def _forensic_request(self, s: int, req: _Request) -> dict[str, Any]:
        """One resident's line in a forensic record: identity, pages
        held, and the in-flight half of its cost ledger (the finalized
        ledger lands in its wide event later — this is the live view
        at the incident)."""
        return {
            "request_id": req.trace.id,
            "slot": s,
            "pages": self._held(s),
            "prompt_tokens": req.length,
            "emitted_tokens": len(req.emitted),
            "spliced_tokens": req.spliced,
            "activated": req.activated,
            "evictions": req.evictions,
            "cost": {
                "prefill_tokens": req.cost_prefill_tokens,
                "cached_tokens": req.cost_cached_tokens,
                "decode_steps": req.cost_decode_steps,
                "decode_tokens": req.cost_decode_tokens,
                "page_seconds": round(req.cost_page_seconds, 6),
                "peak_pages": req.peak_pages,
            },
        }

    def _held(self, s: int) -> int:
        return int((self.bt[s] != self._sentinel).sum())

    def _accrue_page_seconds(self, s: int) -> None:
        """Advance slot s's pages-held x time integral up to now,
        REFCOUNT-WEIGHTED: a page shared by k holders charges each
        holder 1/k (the prefix cache's own reference is a holder too),
        so request_page_seconds summed across requests never exceeds
        physical page-seconds — full-charging shared pages would make
        the aggregate HBM currency look MORE expensive the better
        prefix sharing works, inverting the metric. Runs before every
        page-count change (grow / free), once per decode chunk (so
        refcount samples stay fresh as neighbors splice/release), and
        at finalization."""
        req = self.slots[s]
        if req is None or not req.pages_t:
            return
        now = time.monotonic()
        # No walk of the table in Python, however wide it is, and no
        # numpy on the row either: beside 64 streams' handler threads
        # the boolean index, gather and sum of a 64-entry row cost
        # 192 us a lane on the chip's host where this costs 12 and the
        # walk cost 29 (PERF.md section 5, PR 46).
        row = self.bt[s].tolist()
        held = len(row) - row.count(self._sentinel)
        weight = self.allocator.charge(row)
        req.cost_page_seconds += weight * (now - req.pages_t)
        req.pages_t = now
        if held > req.peak_pages:
            # HBM high-water mark: accrual runs before every page-count
            # change AND at finalization (pages still held), so the
            # peak is sampled at worst one accrual late and always
            # covers the final held count.
            req.peak_pages = held
            req.peak_page_seconds = req.cost_page_seconds

    def _finalize_cost(self, s: int | None, req: _Request,
                       observe: bool = True) -> dict[str, Any]:
        """Close the per-request cost ledger on a terminal path
        (finish, error, cancel — BEFORE the slot's pages are freed;
        s=None for a request that never held a slot, e.g. cancelled in
        queue — its ledger is real too, just all-zero resources): final
        page-seconds accrual, queue/prefill/decode wall time from the
        request's own spans, aggregate histograms. The dict lands in
        handle.debug["cost"] (the API server forwards it as final SSE
        metadata) and in the trace meta (/debug/requests)."""
        if s is not None:
            self._accrue_page_seconds(s)
        by = req.trace.span_seconds()
        cost = {
            "prefill_tokens": req.cost_prefill_tokens,
            "cached_tokens": req.cost_cached_tokens,
            "decode_steps": req.cost_decode_steps,
            "decode_tokens": req.cost_decode_tokens,
            "page_seconds": round(req.cost_page_seconds, 6),
            "queue_s": round(by.get("queue_wait", 0.0), 6),
            # Queue head -> first token (the `admission` spans): prompt
            # prep, the wait for pages, every prefill chunk and what
            # ran between them. The `prefill` spans time an enqueue.
            "prefill_s": round(by.get("admission", 0.0), 6),
            "decode_s": round(by.get("decode_chunk", 0.0), 6),
            "e2e_s": round(time.monotonic() - req.submit_time, 6),
            "peak_pages": req.peak_pages,
            "peak_page_seconds": round(req.peak_page_seconds, 6),
        }
        req.handle.debug["cost"] = cost
        if not observe:
            # Submit-time rejections (429/503, never queued) keep
            # their ledger for /debug, but must not flood the
            # aggregate histograms with all-zero samples — a retry
            # storm would drive every request_* distribution to the
            # bottom bucket exactly when the overload view matters.
            return cost
        m = self.metrics
        m.observe("request_prefill_tokens", cost["prefill_tokens"])
        m.observe("request_cached_tokens", cost["cached_tokens"])
        m.observe("request_decode_steps", cost["decode_steps"])
        m.observe("request_decode_tokens", cost["decode_tokens"])
        m.observe("request_page_seconds", cost["page_seconds"])
        m.observe("request_queue_seconds", cost["queue_s"])
        m.observe("request_prefill_seconds", cost["prefill_s"])
        m.observe("request_decode_seconds", cost["decode_s"])
        m.observe("request_e2e_seconds", cost["e2e_s"])
        m.observe("request_peak_pages", cost["peak_pages"])
        return cost

    def _emit_request_event(self, req: _Request, *, status: str,
                            error_kind: str | None = None) -> None:
        """Append the request's wide event (utils/request_log.py) —
        called on EVERY terminal path, right after the trace closes, so
        the event merges the finalized cost ledger, the span-derived
        wall times already inside it, the outcome, and this engine's
        identity. One request, one line — the offline twin of the
        oryx_serving_request_* histograms."""
        h = req.handle
        cost = h.debug.get("cost") or {}
        aps = None
        if self.speculate and cost.get("decode_steps"):
            # decode_steps bills 1+k verify lanes per spec dispatch, so
            # steps/(1+k) recovers the dispatch count and tokens-per-
            # dispatch is the per-request speculation yield.
            dispatches = cost["decode_steps"] / (1 + self.speculate)
            if dispatches:
                aps = round(
                    cost.get("decode_tokens", 0) / dispatches, 4
                )
        usage = h.usage or (req.length, len(req.emitted))
        self.request_log.append(request_log_lib.build_request_event(
            request_id=req.trace.id,
            engine=self.engine_label,
            replica=self.replica_id,
            routed=req.routed,
            status=status,
            error_kind=error_kind,
            finish_reason=h.finish_reason if status == "ok" else None,
            prompt_tokens=usage[0],
            completion_tokens=usage[1],
            streaming=h.streaming,
            evictions=req.evictions,
            accepted_tokens_per_step=aps,
            journal_seq=req.journal_seq,
            **cost,
        ))
        if self.journal is not None and status != "rejected":
            # Terminal journal entry (submit-time rejections already
            # wrote their own `reject` entry — a finish here would leak
            # a timing-coupled decision into the replayed stream). The
            # reply fingerprints are THE byte-exactness oracle replay
            # asserts against; the cost subset is the deterministic
            # half of the ledger (journal_lib.DETERMINISTIC_COST_KEYS).
            self.journal.append(journal_lib.build_journal_event(
                kind="finish",
                step=self._journal_step(),
                request_id=req.trace.id,
                status=status,
                finish_reason=h.finish_reason if status == "ok" else None,
                error_kind=error_kind,
                completion_tokens=len(req.emitted),
                reply_sha256=journal_lib.fingerprint_text(req.text_done),
                tokens_sha256=journal_lib.fingerprint_tokens(req.emitted),
                cost={
                    k: cost.get(k, 0)
                    for k in journal_lib.DETERMINISTIC_COST_KEYS
                },
            ))

    # ---- decision journal (serve/journal.py) -----------------------------

    def _journal_step(self) -> int | None:
        """`steps_run` when journaling FROM the engine thread, else
        None: the counter is engine-thread-owned, and entries written
        from HTTP/supervisor threads (submit rejections, fail_inflight,
        off-engine fault sites) are timing-coupled anyway — replay
        feeds on the step gates of engine-thread entries only."""
        if threading.current_thread() is self._thread:
            return self.steps_run
        return None

    def _journal_submit(self, req: _Request) -> int:
        """One `submit` entry: the replayable workload record. A
        JSON-serializable request dict (every HTTP request is one) is
        journaled VERBATIM as the payload; anything else — e.g. raw
        array embeds handed to submit() programmatically — journals a
        fingerprint only and is flagged unreplayable by its absence."""
        try:
            canon = json.dumps(req.request, sort_keys=True)
        except (TypeError, ValueError):
            prompt = None
            sha = journal_lib.fingerprint_text(repr(req.request))
        else:
            prompt = req.request
            sha = journal_lib.fingerprint_text(canon)
        return self.journal.append(journal_lib.build_journal_event(
            kind="submit",
            request_id=req.trace.id,
            arrival_seq=self.journal.next_arrival(),
            prompt=prompt,
            prompt_sha256=sha,
            sampling=req.sampling,
            max_new=req.max_new,
            streaming=req.handle.streaming,
        ))

    def _journal_fault(self, site: str, fired: int) -> None:
        """utils/faults.py observer hook: one entry per fault-point
        firing, any thread (the journal lock is a leaf). Registered at
        construction when the journal is armed, detached in close()."""
        if self.journal is not None:
            self.journal.append(journal_lib.build_journal_event(
                kind="fault", step=self._journal_step(),
                site=site, fires=fired,
            ))

    @staticmethod
    def _owner_tag(req: _Request | None) -> str | None:
        """The ownership-map stamp for a request's page references
        (PageAllocator owner tags; "cache" is the prefix cache's)."""
        return None if req is None else f"req:{req.trace.id}"

    def _free_slot_pages(self, s: int, owner: str | None = None) -> None:
        pages = [int(p) for p in self.bt[s] if p != self._sentinel]
        if pages:
            self.allocator.free(
                pages, owner=owner or self._owner_tag(self.slots[s])
            )
        self.bt[s] = self._sentinel
        if self.wplane is not None:
            self.wplane.release(
                s, owner=owner or self._owner_tag(self.slots[s]))

    def _clear_slot(self, s: int) -> None:
        # Last accrual point while the occupant still holds its pages
        # (eviction keeps accumulating on the same ledger after
        # re-admission; terminal paths have already finalized).
        self._accrue_page_seconds(s)
        self._free_slot_pages(s)
        self.slots[s] = None
        self.finished[s] = True
        self.lengths[s] = 0
        self.tok[s] = 0
        self.temp[s] = 0.0
        self.top_p[s] = 1.0
        self.top_k[s] = 0
        self.recent[s] = -2
        self.blk[s] = 0
        self.blk_known[s] = 0
        self._edited[s] = True
        self._first.pop(s, None)  # a first token nobody will read
        self._drop_pending(s)

    def _drop_pending(self, s: int) -> None:
        """Slot s's pending block will never be committed: its request
        ended (nothing reads a last block's K/V) or lost the slot, and
        its pages may be another's by the next enqueue."""
        if self.blk_pending[s] >= 0:
            self.blk_pending[s] = -1
            self.metrics.inc("diffusion_commits_total",
                             labels={"how": "dropped"})

    def _grow_slot(self, s: int, tokens: int,
                   req: _Request | None = None) -> bool:
        """Extend slot s's block table to cover `tokens` logical slots;
        False when the free list can't satisfy it. The ask is clamped to
        max_ctx (the table is max_pages wide; near the context ceiling
        the final chunk's overshoot steps self-confine to the row's own
        discarded tail). `req` is the ownership-map stamp (defaults to
        the slot's occupant — admission passes the not-yet-placed
        request explicitly)."""
        tokens = min(tokens, self.max_ctx)
        need = self.allocator.pages_for(tokens) - self._held(s)
        if req is None:
            req = self.slots[s]
        if self.wplane is not None and not self._grow_window(s, tokens, req):
            return False
        if need <= 0:
            return True
        # Page count is about to change: bank the integral at the OLD
        # held count first, or the grown pages would be backdated.
        self._accrue_page_seconds(s)
        if need > self.allocator.num_free and self.prefix_cache is not None:
            # Cached pages go before live requests: reclaim cache-only
            # (refcount-1) entries, LRU first, before reporting
            # pressure to the eviction machinery — but only when
            # eviction can actually cover the shortfall. Draining the
            # cache for a grow that fails anyway would cost look-alike
            # requests their splices for nothing.
            shortfall = need - self.allocator.num_free
            if self.prefix_cache.evictable_pages() >= shortfall:
                self.prefix_cache.evict(shortfall)
        if need > self.allocator.num_free:
            # THE real capacity-OOM path (no exception: deferral and
            # eviction absorb it) — the incident /debug/oom exists to
            # diagnose. One capture per pressure episode.
            if not self._oom_episode:
                self._oom_episode = True
                self._capture_oom(
                    "pool_pressure",
                    f"free-list shortfall: need {need} page(s), "
                    f"{self.allocator.num_free} free",
                    asking=(s, req, need),
                )
            return False
        held = self._held(s)
        try:
            pages = self.allocator.alloc(need, owner=self._owner_tag(req))
        except paged_kv.OutOfPagesError as e:
            # Free-list said yes but alloc refused (injected OOM, or a
            # racing holder): report "can't grow" so the normal
            # eviction/defer machinery handles it — an allocation
            # failure is a scheduling signal, never a crash. alloc is
            # all-or-nothing, so nothing is held on this path. The
            # moment IS a forensic: capture the pool state while the
            # pressure that caused it is still live.
            self._capture_oom(
                "oom", f"{type(e).__name__}: {e}",
                asking=(s, req, need),
            )
            return False
        self.bt[s, held: held + need] = pages
        self._oom_episode = False  # pressure episode over: pages flowed
        return True

    def _window_args(self, rows) -> dict:
        """The window plane's table and base of slots `rows`, as the two
        step programs take them; nothing for a model without window
        layers."""
        if self.wplane is None:
            return {}
        # Host COPIES (numpy's own: `jnp.array` of a view was still
        # read late): `advance` shifts a table's row in place before
        # the next dispatch, while this one may not have read its
        # operands yet.
        return {"window_tables": jnp.asarray(self.wplane.tables[rows].copy()),
                "window_base": jnp.asarray(self.wplane.base[rows].copy())}

    def _grow_window(self, s: int, tokens: int, req: _Request) -> bool:
        """The window plane's part of `_grow_slot`, at a dispatch
        boundary: the lane's next dispatch has its first query at the
        position it stands at (its prefill offset, or its length once
        it decodes), so first every window page wholly older than that
        query's window goes back to the plane's allocator, the table
        is shifted and the base moved (`WindowPlane.advance`); then the
        table is grown to `tokens`, as far as its width reaches (a long
        prompt's later chunks grow it again). False, nothing taken,
        when the plane's free list is short: the caller defers or
        evicts, as for the global plane."""
        at = int(self.lengths[s]) if req.activated else req.prefill_pos
        wp, tag = self.wplane, self._owner_tag(req)
        if not req.activated and at == 0 and wp.allocator.num_free < min(
                wp.tables.shape[1], wp.allocator.pages_for(tokens)):
            return False  # its later chunks would not fit: not admitted
        # What the NEXT dispatch writes; a long prompt's later chunks
        # ask again.
        tokens = min(tokens, at + max(self.prefill_chunk, self._win)
                     + self._win)
        freed = wp.advance(s, at, owner=tag)
        if freed:
            self.metrics.inc("kv_window_pages_released_total", len(freed))
            req.trace.event("window_release", slot=s, pages=len(freed),
                            base=int(wp.base[s]))
        if not wp.grow(s, tokens, owner=tag):
            if not self._oom_episode:
                self._oom_episode = True
                need = wp.need(s, tokens)
                self._capture_oom(
                    "pool_pressure",
                    f"window plane shortfall: need {need} page(s), "
                    f"{wp.allocator.num_free} free",
                    asking=(s, req, need),
                )
            return False
        return True

    # ---- scheduling loop -------------------------------------------------

    def _new_phase_clock(self) -> profiling_lib.PhaseClock:
        return profiling_lib.PhaseClock(
            "oryx.engine", self._bill_phase, base="housekeeping",
            starved=lambda name, seconds: self._starved_seconds[name](
                seconds
            ),
        )

    def _bill_phase(self, name: str, seconds: float) -> None:
        """One uninterrupted stretch of a phase has ended. One over
        STALL_SECONDS is counted, and marked on the trace of every
        request that sat in a slot through it."""
        self._phase_seconds[name](seconds)
        if seconds > STALL_SECONDS and name != "idle":
            self._stall_seconds[name](seconds)
            for req in self.slots:
                if req is not None:
                    req.trace.event(
                        "engine_stall", phase=name, seconds=seconds,
                        last_dispatch=self._last_dispatch,
                    )

    def _phase(self, name: str, kind: str = "host"):
        """`with self._phase(...)`: the engine thread is in this phase
        (ENGINE_PHASES) until the block ends; loop time outside every
        block is housekeeping."""
        if kind == "dispatch":
            self._last_dispatch = name
        return self._phases.phase(name, kind)

    def _run(self) -> None:
        # A restarted loop runs on a new thread: its own clock.
        self._phases = self._new_phase_clock()
        try:
            self._loop()
        finally:
            self._phases.close()  # a loop that ends idle bills it

    def _loop(self) -> None:
        while True:
            if self.replay_feeder is not None:
                # Offline replay (scripts/replay_journal.py): feed the
                # journaled admission stream at its recorded step gates
                # before this iteration examines the queue. Live
                # serving never sets the hook — the branch costs one
                # attribute check.
                self.replay_feeder(self)
            drain_drop: list[_Request] = []
            with self._cond:
                shutdown = self._shutdown
            if shutdown:
                # close(): the dispatch in flight is read, not left.
                self._drain_flight()
                return
            with self._cond:
                if self._draining and self._queue:
                    # Drain: admission is over — queued-but-unadmitted
                    # requests hold no pages; error them out so their
                    # clients retry against another replica.
                    while self._queue:
                        drain_drop.append(self._queue.popleft())
                    self.metrics.set_gauge("queue_depth", 0)
                idle = not self._queue and all(
                    r is None for r in self.slots
                )
                drain_exit = idle and self._draining
            for r in drain_drop:
                self._reject_queued(
                    r, "server draining: request not admitted",
                    kind="unavailable",
                )
            if drain_drop and self.anomaly is not None:
                self.anomaly.observe_queue_depth(0)
            if drain_exit:
                _LOG.info("drain complete: engine loop exiting")
                return
            if idle:
                if self._profile_active is not None:
                    # Traffic drained mid-capture: close the capture
                    # NOW with the windows collected so far (an idle
                    # loop would otherwise leave the process-global
                    # profiler recording forever and every later
                    # capture failing at start — and the requester
                    # hanging to its timeout for steps that will
                    # never come).
                    act, self._profile_active = (
                        self._profile_active, None
                    )
                    holder = act["holder"]
                    if act["windows"]:
                        holder["result"] = self.profiler.finish_capture(
                            act["windows"]
                        )
                    else:
                        self.profiler.abort()
                        holder["result"] = {
                            "error": "engine went idle before any "
                            "dispatch was captured (profiling needs "
                            "live traffic)",
                        }
                    holder["done"].set()
                # The degraded ladder must keep decaying while idle —
                # mode 3 sheds load, so "no traffic" is exactly when
                # it has to walk itself back down (called OUTSIDE the
                # cond block: it takes the lock itself).
                self._update_degraded()
                if self.watchdog is not None:
                    self.watchdog.set_active(False)
                # ONE `idle` phase from the first empty wait to the
                # first request, however often the loop wakes in it.
                self._phases.hold("idle")
                if self.auditor.pending():
                    # Idle quiesce point: run ONE queued shadow-
                    # parity replay, then re-check for live work —
                    # an arrival never waits behind a second
                    # replay, and a replay can never interleave
                    # with a live dispatch (the never-perturb
                    # contract, serve/audit.py).
                    self.auditor.run_one()
                    continue
                with self._cond:
                    if not self._queue and not self._shutdown:
                        self._cond.wait(timeout=0.1)
                continue
            self._phases.release()
            if self.watchdog is not None:
                self.watchdog.set_active(True)
            # Chaos site: engine-thread DEATH (outside the containment
            # try below, so the exception escapes _run and the thread
            # dies — exactly what the API server's supervisor exists
            # to catch and restart).
            faults.fault_point("engine_crash")
            # Adopt a parked /debug/profile request only when there is
            # work to dispatch (an idle engine would leave the
            # profiler running against nothing until the requester's
            # timeout).
            with self._cond:
                take = (
                    self._profile_pending
                    if self._profile_active is None else None
                )
                if take is not None:
                    self._profile_pending = None
            if take is not None:
                self._adopt_profile(take)
            try:
                with self._phase("housekeeping"):
                    self._update_degraded()
                    self._enforce_deadlines()
                self._admit()
                if self.ragged:
                    # Fused path: prefill lanes and decode lanes ride
                    # ONE dispatch (docs/DESIGN.md "Ragged paged
                    # attention").
                    self._ragged_step()
                else:
                    # Chunked admission interleaves with decode: each
                    # engine step advances the in-flight admission by at
                    # most one prefill chunk, then runs one decode chunk
                    # for the resident streams — a long prompt never
                    # stalls decode for more than one prefill dispatch.
                    # (Unchunked prefills completed inside _admit; this
                    # is a no-op.)
                    self._prefill_step()
                    if any(
                        r is not None and r.activated for r in self.slots
                    ):
                        with self._phase("housekeeping"):
                            self._ensure_capacity()
                        if self.block:
                            self._block_step()
                        else:
                            self._step_chunk()
            except Exception as e:  # surface to every in-flight client
                msg = f"{type(e).__name__}: {e}"
                for s, req in enumerate(self.slots):
                    if req is not None:
                        self._finish_error(s, msg)
                with self._cond:
                    # obligations: _finalize_cost, _emit_request_event
                    while self._queue:
                        r = self._queue.popleft()
                        cost = self._finalize_cost(None, r)
                        r.handle.error = msg
                        r.handle.events.put(("error", msg))
                        r.handle.done.set()
                        if r.trace is not None:
                            r.trace.finish(error=msg, cost=cost)
                        self._emit_request_event(
                            r, status="error", error_kind="server_error"
                        )
                    # Every pop refreshes the gauge (same invariant as
                    # the cancel path): after the drain /metrics must
                    # say empty, and the drain-side observation lets a
                    # queue_depth_slo episode re-arm.
                    self.metrics.set_gauge("queue_depth", 0)
                if self.anomaly is not None:
                    self.anomaly.observe_queue_depth(0)
                # The failed dispatch may have CONSUMED the donated page
                # pool (donate_argnames=kv_pages): rebuild it so the
                # engine keeps serving new traffic instead of erroring
                # forever on a deleted array. A capture straddling the
                # failure is discarded the same way.
                self._abort_profile()
                self._reset_pool()

    # obligations: _finalize_cost, _emit_request_event
    def _reject_queued(
        self, req: _Request, msg: str, *, kind: str = "server_error"
    ) -> None:
        """Error out a request that was ALREADY popped from the queue
        and never placed (holds no pages). Still a terminal path: the
        ledger (zero resources, real queue_s) is finalized — in the
        saturated regime most requests end HERE, and cost attribution
        that omits them would claim saturation is cheap."""
        cost = self._finalize_cost(None, req)
        req.handle.error = msg
        req.handle.error_kind = kind
        req.handle.events.put(("error", msg))
        req.handle.done.set()
        req.trace.finish(error=msg, cost=cost)
        self._emit_request_event(req, status="error", error_kind=kind)
        _LOG.info("request %s dropped: %s", req.trace.id, msg)

    # obligations: cancelled, _finalize_cost, _emit_request_event
    def _cancel_queued(self, req: _Request) -> None:
        """Terminal path for a client hang-up BEFORE admission (the
        request holds no slot, no pages): ledger finalized with zero
        resources but real queue_s, trace closed, wide event emitted,
        and the `cancelled` counter advanced — this path used to skip
        the counter while the three slot-holding cancel paths bumped
        it, so queue cancels undercounted (found by the terminal-path
        obligations annotation, finding scheduler.py `_cancel_queued`
        / cancelled)."""
        self.metrics.inc("cancelled")
        cost = self._finalize_cost(None, req)
        req.trace.finish(cancelled=True, cost=cost)
        self._emit_request_event(req, status="cancelled")
        _LOG.info("request %s cancelled in queue", req.trace.id)

    # obligations: cancelled, _finalize_cost, _clear_slot, _emit_request_event
    def _cancel_slot(self, s: int, req: _Request, where: str) -> None:
        """Terminal path for a client hang-up while holding slot `s`
        (mid-prefill or mid-decode): the slot's pages — including
        spliced prefix-cache shares — return NOW, before any further
        dispatch. One body for the three call sites so the obligation
        set is declared (and machine-checked) once."""
        self.metrics.inc("cancelled")
        cost = self._finalize_cost(s, req)
        self._clear_slot(s)
        req.trace.finish(cancelled=True, cost=cost)
        self._emit_request_event(req, status="cancelled")
        _LOG.info("request %s cancelled %s", req.trace.id, where)

    def _enforce_deadlines(self) -> None:
        """Cancel every request past its deadline, wherever it lives:
        queued (no pages held), mid-prefill, or decoding (slot pages +
        prefix-cache shares freed via _clear_slot). Runs once per
        engine step — a hung dispatch therefore converts into a clean
        504 at the next step boundary."""
        now = time.monotonic()
        expired: list[_Request] = []
        with self._cond:
            if self._queue and any(
                r.deadline is not None and now > r.deadline
                for r in self._queue
            ):
                keep: deque[_Request] = deque()
                for r in self._queue:
                    if r.deadline is not None and now > r.deadline:
                        expired.append(r)
                    else:
                        keep.append(r)
                self._queue = keep
                depth = len(keep)
                self.metrics.set_gauge("queue_depth", depth)
            else:
                depth = None
        for r in expired:
            self.metrics.inc("deadline_exceeded_total")
            self._reject_queued(
                r, "deadline exceeded before admission", kind="timeout"
            )
        if depth is not None and self.anomaly is not None:
            self.anomaly.observe_queue_depth(depth)
        for s, req in enumerate(self.slots):
            if req is None or req.deadline is None or now <= req.deadline:
                continue
            if self._inflight is not None:
                # The dispatch in flight is read first, and may have
                # ended the request by itself.
                self._drain_flight()
                if self.slots[s] is not req:
                    continue
            self.metrics.inc("deadline_exceeded_total")
            self._finish_error(
                s,
                f"deadline exceeded after {now - req.submit_time:.2f}s "
                f"({len(req.emitted)} tokens emitted)",
                kind="timeout",
            )

    def _update_degraded(self) -> None:
        """Degraded-mode ladder: each NEW serving-SLO anomaly firing
        escalates one level (1 shed prefix cache, 2 clamp max_tokens,
        3 shed load); `degraded_cooldown` quiet seconds de-escalate
        one level. Exported as the `degraded_mode` gauge."""
        if self.anomaly is None:
            return
        fired = sum(
            self.anomaly.counts.get(k, 0)
            for k in ("ttft_slo", "queue_depth_slo")
        )
        now = time.monotonic()
        with self._cond:
            mode = self._degraded
        if fired > self._slo_fired_seen:
            self._slo_fired_seen = fired
            self._degraded_changed = now
            if mode < 3:
                self._set_degraded(mode + 1)
        elif mode > 0 and now - self._degraded_changed \
                >= self.degraded_cooldown:
            self._degraded_changed = now
            self._set_degraded(mode - 1)

    def _set_degraded(self, mode: int) -> None:
        with self._cond:
            prev, self._degraded = self._degraded, mode
        self.metrics.set_gauge("degraded_mode", mode)
        _LOG.warning(
            "degraded mode %d -> %d (%s)", prev, mode,
            ["normal", "prefix cache shed", "max_tokens clamped",
             "shedding load"][mode],
        )
        if self.journal is not None:
            # Journaled, NOT replayed (REPLAYED_KINDS): the ladder is
            # wall-clock-driven; its decision effect is the clamped
            # max_new the admit entries carry.
            self.journal.append(journal_lib.build_journal_event(
                kind="degraded", step=self._journal_step(), mode=mode,
            ))
        if mode > prev:
            # An escalation is a capacity incident in progress: capture
            # the same forensic record an OOM gets, while the pressure
            # that drove the SLO breach is still visible in the pool.
            self._capture_oom(
                "degraded_escalation",
                f"degraded mode {prev} -> {mode}",
            )
        if mode >= 1 and not self._cache_shed:
            # Shed the prefix cache: free its pages for live requests
            # and stop feeding it until the ladder fully clears.
            self._cache_shed = True
            if self.prefix_cache is not None:
                self.prefix_cache.clear()
        elif mode == 0:
            self._cache_shed = False

    def _admit(self) -> None:
        while True:
            with self._phase("admit"):
                placed = self._admit_next()
            if placed is None:
                break
            if placed and self.prefill_chunk is None:
                # Unchunked: complete the (single-dispatch) prefill now,
                # so the slot activates — and donates its prompt pages —
                # before the next queue head is examined. A burst of
                # look-alike requests therefore admits cold exactly
                # once; the rest splice.
                self._advance_prefill(*placed)

    def _admit_next(self) -> tuple | None:
        """Examine the queue head once. None: admission stops for this
        engine step (a chunked prefill in flight, no free slot, an
        empty queue, or a head that does not fit yet); (): the head
        was dropped (cancelled or rejected) and the next one is due;
        (slot, request): the head was placed."""
        gen = self.cfg.generation
        if self.replay_feeder is not None:
            # Replay feeding re-checks its step gates HERE as well
            # as at the loop top: an unchunked prefill dispatches
            # inside this while (advancing steps_run mid-
            # iteration), and the live run may have admitted the
            # next queued request immediately after it — the
            # feeder must be able to inject that request between
            # two admissions, not one engine iteration later.
            self.replay_feeder(self)
        if any(r is not None and not r.activated for r in self.slots):
            # A chunked prefill is in flight: the engine-step budget
            # for prompt work is ONE prefill chunk, so no further
            # admission until it activates (its donation then lands
            # before the next look-alike's lookup).
            return None
        free = [s for s, r in enumerate(self.slots) if r is None]
        if not free:
            return None
        with self._cond:
            if not self._queue:
                return None
            req = self._queue[0]
        if req.handle.cancelled:
            with self._cond:
                # Safe check-then-act: the engine thread is the
                # queue's ONLY consumer (submit appends at the
                # tail; restart appendlefts only once this thread
                # is dead), so the head peeked above cannot have
                # changed.
                self._queue.popleft()  # oryxlint: disable=atomicity
                depth = len(self._queue)
                # Every pop must refresh the gauge: without this a
                # pre-admission cancel left queue_depth one high
                # until the next submit.
                self.metrics.set_gauge("queue_depth", depth)
            if self.anomaly is not None:
                # Drain-side observation, same invariant as the
                # engine-failure drain: a backlog that empties via
                # client cancels must re-arm the queue_depth_slo
                # episode, or the next burst fires no event.
                self.anomaly.observe_queue_depth(depth)
            # A cancelled-in-queue request still gets a ledger
            # (zero resources, real queue_s): its trace lands in
            # /debug/requests?state=done, and the every-finished-
            # request-has-a-complete-ledger audit must hold there
            # too.
            self._cancel_queued(req)
            return ()
        if not req.length:
            # The request reached the queue head: queue_wait ends,
            # admission (prompt prep + validation + the wait for
            # pages + prefill) begins.
            req.trace.end(req.qw_span)
            req.qw_span = -1
            req.adm_span = req.trace.begin("admission")
            try:
                with req.trace.span("prompt_prep"):
                    with self._phase("prompt_prep"):
                        ids, imgs, factors, caps = (
                            self.pipe._prepare_request(req.request)
                        )
                    # Patch packing, staging and the enqueue of
                    # mm_embeds (or of the embedding gather); nothing
                    # here waits for the device.
                    # Text-only prompts key the prefix cache by
                    # token ids (ids == the logical KV stream);
                    # multimodal streams key visual slots
                    # positionally and bypass it.
                    req.cache_tokens = (
                        None if imgs else np.asarray(ids, np.int64)
                    )
                    if self._suffix_embeds and not imgs:
                        # Gathered at `_place`, from the first token
                        # the prefix cache does not hold.
                        req.length = len(ids)
                    else:
                        with self._phase("embed"), self.pipe._mesh_scope():
                            req.embeds, req.length = (
                                self.pipe._prompt_embeds(
                                    self.cfg, ids, imgs, factors, caps
                                )
                            )
                s_ = req.sampling
                req.temp = float(
                    s_.get("temperature", gen.temperature) or 0.0
                )
                req.topp = float(s_.get("top_p", gen.top_p) or 1.0)
                req.topk = int(s_.get("top_k", gen.top_k) or 0)
                req.key0 = jax.random.key(int(s_.get("seed") or 0))
                with self._cond:
                    mode = self._degraded
                if (
                    mode >= 2
                    and req.max_new > self.degraded_clamp_tokens
                ):
                    # Degraded mode 2: cap the decode budget so the
                    # backlog turns over faster; the client sees a
                    # "length" finish and the clamp in debug.
                    req.max_new = self.degraded_clamp_tokens
                    req.handle.debug["clamped_max_tokens"] = (
                        self.degraded_clamp_tokens
                    )
                if req.length + req.max_new > self.max_ctx:
                    raise ValueError(
                        f"prompt ({req.length}) + max_tokens "
                        f"({req.max_new}) exceeds max_ctx {self.max_ctx}"
                    )
                need = self.allocator.pages_for(
                    req.length + self._win
                )
                if need > self.num_pages:
                    raise ValueError(
                        f"prompt needs {need} KV pages but the whole "
                        f"pool holds {self.num_pages} (raise "
                        "--num-pages, or lower the prompt length / "
                        "--max-ctx)"
                    )
            except Exception as e:
                with self._cond:
                    # Single-consumer head pop (see the cancel
                    # branch above).
                    self._queue.popleft()  # oryxlint: disable=atomicity
                    depth = len(self._queue)
                    self.metrics.set_gauge("queue_depth", depth)
                if self.anomaly is not None:
                    # Same drain-side invariant as the cancel and
                    # engine-failure pops: a backlog emptied by
                    # rejections must re-arm the queue_depth_slo
                    # episode.
                    self.anomaly.observe_queue_depth(depth)
                msg = f"{type(e).__name__}: {e}"
                cost = self._finalize_cost(None, req)
                req.handle.error = msg
                if isinstance(e, ValueError):
                    req.handle.error_kind = "invalid_request"
                req.handle.events.put(("error", msg))
                req.handle.done.set()
                req.trace.finish(error=msg, cost=cost)
                self._emit_request_event(
                    req, status="error",
                    error_kind=req.handle.error_kind,
                )
                _LOG.info(
                    "request %s rejected at admission: %s",
                    req.trace.id, msg,
                )
                return ()
        s = free[0]
        # Splice the cached prefix and take pages for the prompt
        # plus the first chunk's writes. FIFO head-of-line: if the
        # head doesn't fit, nobody jumps it (that is the
        # no-starvation guarantee).
        if not self._splice_and_grow(s, req):
            return None
        with self._cond:
            # Single-consumer head pop (see the cancel branch).
            self._queue.popleft()  # oryxlint: disable=atomicity
            depth = len(self._queue)
            self.metrics.set_gauge("queue_depth", depth)
        if self.anomaly is not None:
            # Drain-side observations re-arm the hysteresis: with
            # submit-only feeding, the detector would only ever see
            # depths >= 1 and a queue_depth_slo of 1 could never
            # re-arm after its first firing.
            self.anomaly.observe_queue_depth(depth)
        self._place(s, req)
        return s, req

    def _splice_and_grow(self, s: int, req: _Request) -> bool:
        """Splice the longest cached prefix of `req`'s prompt into slot
        s's block table — full pages SHARED (refcounted, immutable), a
        partially-consumed last page COPY-ON-WRITTEN — then grow the
        table to cover prompt + one decode chunk. Returns False, with
        nothing held, when the pool cannot satisfy it (the FIFO head
        then waits). At least one suffix token always remains to
        prefill: the admission needs the next-token logit."""
        ps = self.page_size
        # Page-seconds accrual starts the moment this placement can
        # hold pages (held is 0 until the splice/grow below succeeds,
        # so a False return leaves the integral untouched).
        req.pages_t = time.monotonic()
        spliced = 0
        cow_pages = 0
        host_reloaded = 0
        matched, pages, host_nodes = 0, [], []
        cache_on = (
            self.prefix_cache is not None
            and req.cache_tokens is not None
            and not self._cache_shed  # degraded >= 1: no splicing
        )
        if cache_on:
            matched, pages, host_nodes = (
                self.prefix_cache.lookup_tiered(req.cache_tokens)
            )
        limit = max(req.length - 1, 0)
        if self.block:
            # Block mode prefills whole blocks only (the prompt's tail
            # opens the first generated block) and needs no logit from
            # the prefill, so all of them may come from the cache; a
            # splice ends on a block edge.
            limit = req.length - req.length % self.block
            matched -= matched % self.block
        use = min(matched, limit)
        if self.conv_state:
            # A hit ends where a snapshot of the state lies: on a page
            # edge. A prompt that ends inside a matched page prefills
            # that page's tokens again (no copy-on-write here).
            use -= use % ps
        full = use // ps
        # Feasibility screen BEFORE any share or COW device copy: the
        # fresh pages needed beyond the spliced prefix must be coverable
        # by the free list plus genuinely evictable cache pages —
        # otherwise a head that cannot fit would pay a futile full-page
        # device copy every engine step while it waits.
        total_need = self.allocator.pages_for(
            min(req.length + self._win, self.max_ctx)
        )
        avail = self.allocator.num_free
        if self.prefix_cache is not None:
            avail += self.prefix_cache.evictable_pages(
                exclude=[int(p) for p in pages[:full]]
            )
        if total_need - full > avail:
            # Admission-side twin of the _grow_slot shortfall: the
            # head cannot fit even with every evictable cache page —
            # same one-capture-per-episode forensic contract.
            if not self._oom_episode:
                self._oom_episode = True
                self._capture_oom(
                    "pool_pressure",
                    f"admission shortfall: prompt needs "
                    f"{total_need - full} fresh page(s), "
                    f"{avail} coverable",
                    asking=(s, req, total_need - full),
                )
            return False
        if cache_on and host_nodes and full == len(pages):
            # Host-tier hit: the prompt's cached prefix continues past
            # the device-resident blocks into spilled entries — reload
            # them onto fresh pages AHEAD of the suffix prefill, so
            # the splice (and the suffix-only prefill bill) covers
            # them too. Reload needs one free page per block; let the
            # LRU arbitrate hot-vs-cold when the free list is short
            # (evicting a cold entry — which itself spills — to bring
            # a hot one back is exactly the tier working). Every
            # failure mode (no page, failed upload) just shortens the
            # match: the remaining suffix recomputes cold.
            n_host = min(len(host_nodes), limit // ps - full)
            if n_host > 0:
                short = n_host - self.allocator.num_free
                keep = [int(p) for p in pages[:full]]
                if short > 0 and self.prefix_cache.evictable_pages(
                    exclude=keep
                ) >= short:
                    # The matched device prefix is still refcount-1
                    # (nothing shared yet) — exclude it or this round
                    # could evict the pages the splice shares below.
                    self.prefix_cache.evict(short, exclude=keep)
                reloaded = self.prefix_cache.reload(
                    req.cache_tokens, host_nodes[:n_host]
                )
                if reloaded:
                    host_reloaded = len(reloaded)
                    pages = pages + reloaded
                    matched = len(pages) * ps  # a block edge: ps % B == 0
                    use = min(matched, limit)
                    full = use // ps
        if cache_on:
            if full:
                share = [int(p) for p in pages[:full]]
                self.allocator.share(share, owner=self._owner_tag(req))
                self.bt[s, :full] = share
            if use - full * ps > 0:
                # The suffix prefill starts MID-page: the cache (and
                # possibly other slots) still read this page, so the
                # writer gets its own copy (COW) — or, when no page is
                # free for the copy, simply recomputes the partial page.
                try:
                    cow = self.allocator.alloc(
                        1, owner=self._owner_tag(req)
                    )[0]
                except paged_kv.OutOfPagesError as e:
                    self._capture_oom(
                        "oom", f"COW alloc: {type(e).__name__}: {e}",
                        asking=(s, req, 1),
                    )
                    use = full * ps
                else:
                    self.kv_pages = paged_kv.copy_pages(
                        self.kv_pages,
                        jnp.asarray(int(pages[full]), jnp.int32),
                        jnp.asarray(cow, jnp.int32),
                    )
                    self.bt[s, full] = cow
                    cow_pages = 1
            spliced = use
        req.spliced = spliced
        req.prefill_pos = spliced
        if not self._grow_slot(s, req.length + self._win, req=req):
            self._free_slot_pages(s, owner=self._owner_tag(req))
            req.spliced = 0
            req.prefill_pos = 0
            return False
        if self.conv_state and spliced:
            # The state after the hit's last token: the snapshot its
            # last page keeps, into the slot's rows, ahead of the
            # suffix's first chunk.
            self.kv_pages = paged_kv.handover_state(
                self.kv_pages,
                jnp.asarray(int(self.bt[s, full - 1]), jnp.int32),
                jnp.asarray(s, jnp.int32))
            self.metrics.inc("conv_state_handovers_total")
        self.metrics.inc("prefix_cache_hit_tokens_total", spliced)
        self.metrics.inc(
            "prefix_cache_miss_tokens_total", req.length - spliced
        )
        req.cost_cached_tokens += spliced
        if self.journal is not None and (spliced or host_reloaded):
            # Cache-hit decision record (misses are implied by an admit
            # entry with spliced_tokens=0 — journaling every miss would
            # double the stream for no replay signal).
            self.journal.append(journal_lib.build_journal_event(
                kind="splice", step=self.steps_run,
                request_id=req.trace.id, slot=s,
                spliced_tokens=spliced,
                shared_pages=full,
                cow_pages=cow_pages,
                host_reload_pages=host_reloaded,
            ))
        return True

    def _ensure_embeds(self, req: _Request, base: int = 0) -> None:
        """Gather a text-only prompt's embeds from token `base` on where
        they are not held: `_activate` released them (an eviction or a
        restart replays the prefill; the auditor copies the whole prompt
        at the finish), the chunked split path has not gathered them yet
        (`_suffix_embeds`: base is the spliced prefix's end, so a
        prompt's cached part is never held), or what is held starts
        past `base` (a replay that found less in the cache)."""
        if req.embeds is None or req.embeds_base > base:
            with self._phase("embed"), self.pipe._mesh_scope():
                req.embeds, _ = self.pipe._prompt_embeds(
                    self.cfg, req.cache_tokens[base:].tolist(),
                    None, None, None,
                )
            req.embeds_base = base
            req.embeds_p = req.embeds_np = None

    def _place(self, s: int, req: _Request) -> None:
        """Claim slot s for `req` (pages already spliced+grown) and
        start its prefill. The slot stays `finished` on device — decode
        chunks skip it — until `_activate` flips it live; the prefill
        itself advances chunk-by-chunk in `_prefill_step`."""
        # The "admission" span opened at the queue head stays open
        # until the first token is read (_read_first_tokens): it holds
        # the prompt prep, the wait for pages, every prefill chunk and
        # whatever ran between them. A re-admission after eviction
        # closes the reopened "queue_wait" and opens an admission span
        # of its own around the replayed prefill.
        if req.qw_span >= 0:
            req.trace.end(req.qw_span)
            req.qw_span = -1
        if req.adm_span < 0:
            req.adm_span = req.trace.begin("admission", replay=True)
        suffix_only = self._suffix_embeds and req.cache_tokens is not None
        self._ensure_embeds(req, req.spliced if suffix_only else 0)
        self.slots[s] = req
        req.activated = False
        self.finished[s] = True
        self.lengths[s] = 0
        self.tok[s] = 0
        if self.ragged:
            if req.embeds_np is None:
                # One host copy per admission (NOT per step): every
                # fused dispatch's prefill window is then a free numpy
                # slice of it, and the dispatch operand keeps its fixed
                # [1, chunk*pf_width, H] shape for any prompt length.
                req.embeds_np = np.asarray(req.embeds)
            # Admission-constant dispatch operands, built once per
            # placement (the slot can change across evictions, so per
            # PLACEMENT, not per request): the hot fused step then
            # ships only the window and its offset.
            req.pf_consts = (
                jnp.asarray(s, jnp.int32),
                jnp.asarray(req.length, jnp.int32),
                jnp.asarray(True),
                req.key0[np.newaxis],
                jnp.asarray([req.temp], np.float32),
                jnp.asarray([req.topp], np.float32),
                jnp.asarray([req.topk], np.int32),
            )
        # Eviction ordering needs an age the moment pages are held.
        req.admit_seq = self._admit_seq
        self._admit_seq += 1
        if self.journal is not None:
            # max_new here is the EFFECTIVE budget (degraded clamp
            # already applied at the queue head): replay re-submits
            # with this value, so the wall-clock-driven ladder never
            # has to replay — its decision effect is captured here.
            self.journal.append(journal_lib.build_journal_event(
                kind="admit", step=self.steps_run,
                request_id=req.trace.id, slot=s,
                admit_seq=req.admit_seq, prompt_len=req.length,
                max_new=req.max_new, replay_tokens=req.replay,
                spliced_tokens=req.spliced,
            ))
        _LOG.info(
            "request %s %s slot=%d prompt=%d cached=%d", req.trace.id,
            "re-admitted" if req.replay else "admitted", s, req.length,
            req.spliced,
        )

    def _prefill_step(self) -> None:
        """Advance every admitting slot by at most one prefill chunk
        (prefill_chunk=None: the whole remaining suffix in one
        dispatch); slots whose prefill completes activate and join the
        next decode chunk."""
        for s, req in enumerate(self.slots):
            if req is None or req.activated:
                continue
            if req.handle.cancelled:
                # Client hung up mid-admission: the prefill must stop
                # HERE, not run the rest of the prompt — and the slot's
                # pages (including spliced prefix-cache shares) return
                # now. Same invariant as the mid-decode cancel in
                # _advance.
                self._cancel_slot(s, req, "mid-prefill")
                continue
            self._advance_prefill(s, req)

    def _advance_prefill(self, s: int, req: _Request) -> None:
        # Chaos site: prefill dispatch failure/stall. A raise here is
        # contained by _run's catch-all (requests errored, pool reset).
        faults.fault_point("prefill_dispatch")
        hot_dispatch("scheduler._advance_prefill")
        # Block mode prefills the prompt's whole blocks; its tail opens
        # the first generated block (_activate).
        L = req.length - (req.length % self.block if self.block else 0)
        if req.prefill_pos >= L:
            # Nothing (left) to prefill: a prompt shorter than a block,
            # or whole blocks all spliced from the prefix cache.
            self._activate(s, req, None, None)
            return
        # The phase and the request's `prefill` span both end when the
        # ENQUEUE returns: they mark a dispatch, not the device's work.
        # The wait for this prompt's first token is `first_token`
        # (_read_first_tokens); queue head -> first token is the
        # request's `admission` span, which the ledger's prefill_s reads.
        with self._phase("prefill", "dispatch"):
            B1 = np.newaxis
            off = req.prefill_pos
            if self.prefill_chunk is None and off == 0:
                # Cold single-shot: the original full-embeds program.
                emb, end = req.embeds, L
            elif self.prefill_chunk is None:
                # Cached suffix in one dispatch, bucketed so it shares the
                # cold path's compiled prefill shapes.
                width = round_up_bucket(L - off)
                emb = generate_lib.slice_embeds(
                    generate_lib.pad_embeds_for_chunks(req.embeds, width),
                    jnp.asarray(off, jnp.int32), width=width,
                )
                end = L
            else:
                width = self.prefill_chunk
                if req.embeds_p is None:
                    req.embeds_p = generate_lib.pad_embeds_for_chunks(
                        req.embeds, width
                    )
                    if req.cache_tokens is not None:
                        # The chunks read the padded copy alone; a text
                        # prompt's gather comes again if anything asks
                        # (`_ensure_embeds`): [1, 32768, H] once, not
                        # twice, while a document prefills.
                        req.embeds = None
                emb = generate_lib.slice_embeds(
                    req.embeds_p,
                    jnp.asarray(off - req.embeds_base, jnp.int32),
                    width=width,
                )
                end = min(off + width, L)
            # The narrowest table that holds every position the chunk
            # reads or writes (its padding is written too).
            if self.wplane is not None and not self._grow_slot(
                    s, end + (self._win if end >= L else 0), req=req):
                # The window plane cannot cover this chunk: the lane
                # (the youngest: no other is admitted while it
                # prefills) gives its pages back and waits at the
                # queue's head.
                self._evict(s)
                return
            reach = -(-(off + emb.shape[1]) // self.page_size)
            table = next(
                (b for b in self.table_buckets if b >= reach),
                self.max_pages,
            )
            pf = req.trace.begin(
                "prefill", slot=s, start=off, tokens=end - off,
                cached=req.spliced > 0, replay=req.replay > 0,
                table_positions=table * self.page_size,
            )
            sampled = self._profile_dispatch_begin()
            t0 = time.monotonic()
            t0_ns = trace_lib.now_ns()
            with self.pipe._mesh_scope():
                kv, tok0, key, *held = generate_lib.paged_prefill(
                    self.pipe.params["llm"], self.cfg.llm,
                    emb,
                    jnp.asarray([end], np.int32),
                    jnp.asarray(self.bt[s, :table][B1]),
                    self.kv_pages,
                    jnp.asarray([off], np.int32),
                    req.key0[B1],
                    jnp.asarray([req.temp], np.float32),
                    jnp.asarray([req.topp], np.float32),
                    jnp.asarray([req.topk], np.int32),
                    attn_impl=self.cfg.attn_impl,
                    compute_dtype=oryx.compute_dtype(self.cfg),
                    **({"held_stats": True} if self.prefill_held_stats
                       else {}),
                    # The slot's state: zeroed by the chunk that starts
                    # at 0, carried by every other.
                    **({"slots": jnp.asarray([s], np.int32)}
                       if self.recurrent else {}),
                    **self._window_args(slice(s, s + 1)),
                )
            req.trace.end(pf)
        self.kv_pages = kv
        self._prefill_held += held  # read later: a dispatch waits for nothing
        req.prefill_pos = end
        req.cost_prefill_tokens += end - off
        self.metrics.inc("prefill_tokens_total", end - off)
        if self.recurrent:
            self.metrics.inc(f"{self._state}_prefill_tokens_total", end - off)
            if self._ssd_chunk:
                self.metrics.inc(
                    "ssd_prefill_chunks_total",
                    -(-(end - off) // self._ssd_chunk))
            if off == 0:
                self.metrics.inc(f"{self._state}_state_resets_total")
            if self.conv_state:
                # A snapshot a page whose last token the chunk held.
                ps = self.page_size
                self.metrics.inc(
                    "conv_edge_writes_total", end // ps - off // ps)
        # Token p attends positions 0..p: the chunk's causal pairs.
        self.metrics.inc(
            "prefill_attn_pairs_total", (end - off) * (off + end + 1) // 2)
        if self.indexed:
            # ... of which the indexer scores a query's p + 1 where they
            # are more than it keeps, and attention reads what it keeps.
            k = self.cfg.llm.index_topk
            seen = np.arange(off, end, dtype=np.int64) + 1
            self.metrics.inc("prefill_index_pairs_total",
                             int(seen[seen > k].sum()))
            self.metrics.inc("prefill_selected_pairs_total",
                             int(np.minimum(seen, k).sum()))
            # ... a tile of keys at a time, every tile up to the chunk's
            # last position (a chunk under k skips the selection alone).
            self.metrics.inc("prefill_masked_tiles_total",
                             -(-end // qwen2.SPARSE_TILE_TOKENS))
        if self.windowed:
            # ... of which a window layer's query at p sees min(p + 1, W).
            W = self.cfg.llm.sliding_window
            p = np.arange(off, end, dtype=np.int64)
            self.metrics.inc("prefill_window_attn_pairs_total",
                             int(np.minimum(p + 1, W).sum()))
        self.metrics.inc("prefill_live_positions_total", end)
        self.metrics.inc(
            "prefill_table_positions_total",
            emb.shape[1] * table * self.page_size,
        )
        if self.cfg.llm.n_shared_experts:
            self.metrics.inc(
                "moe_shared_rows_total",
                (end - off) * self.cfg.llm.num_layers,
            )
        self.metrics.observe(
            "prefill_chunk_tokens", end - off,
            buckets=PREFILL_CHUNK_BUCKETS,
        )
        self._count_dispatch("prefill", end - off, req.temp)
        # Split-path prefill dispatches are engine steps too: record
        # them so timeline dispatch-kind counts reconcile with
        # oryx_serving_dispatches_total on every engine mode.
        self._timeline_record(
            dur_s=time.monotonic() - t0, kind="prefill",
            rows=end - off, accepted=0,
            device_us=self._profile_dispatch_end(
                sampled, "prefill", t0_ns
            ),
        )
        if self.watchdog is not None:
            # A completed prefill chunk is progress too — without this,
            # a burst of admissions (each possibly a compile) could
            # out-wait the deadline with the engine perfectly healthy.
            self.watchdog.beat()
        if end >= L:
            # Intermediate chunks' sampled token/key are discarded; the
            # final chunk's are the single-shot values (every chunk was
            # seeded with the request's own key0).
            self._activate(s, req, tok0, key)

    def _activate(self, s: int, req: _Request, tok0, key) -> None:
        """Prefill complete: mark slot s live for the next decode chunk.
        The slot's key is (re)seeded from the REQUEST's advanced key — a
        slot must never inherit a previous occupant's RNG state (that
        would make sampled streams depend on scheduling history, and
        break eviction replay)."""
        req.activated = True
        if req.cache_tokens is not None:
            # A text-only prompt's embeds are a gather of its ids, and
            # the prompt is in the cache now: a live request would
            # otherwise hold [1, bucket, H] twice over (100 + 113 MB at
            # a 6k prompt 6144 wide, 2.3 GB over a dozen live slots; my
            # chip run, PR 31). `_place` gathers them again if an
            # eviction or a restart replays the prefill.
            req.embeds = req.embeds_p = req.embeds_np = None
        if self.block:
            self._activate_block(s, req)
            return
        with self._phase("emit"):
            self.lengths[s] = self.confirmed[s] = req.length
            # A max_tokens=1 request ends on its first token: it never
            # occupies a chunk.
            self.finished[s] = self._ahead and req.max_new <= 1
            self.temp[s] = req.temp
            self.top_p[s] = req.topp
            self.top_k[s] = req.topk
            self.recent[s] = -2
            self.metrics.inc("admitted")
            # The prompt's pages are written by programs already
            # enqueued, and whoever splices them is enqueued later: a
            # look-alike in this same admission round hits at once.
            self._donate_prefix(s, req, req.length)
            self._first[s] = (req, tok0)
            if self._ahead:
                # The first token goes into the next chunk's `tok` on
                # the device; the host reads it once that chunk is
                # enqueued behind the prefill (`_read_first_tokens`).
                tok, *rest = self._lane_state()
                with self.pipe._mesh_scope():
                    tok, self.keys = generate_lib.seat_first_token(
                        tok, self.keys, jnp.asarray(s, jnp.int32), tok0, key)
                self._lanes = (tok, *rest)
                self._edited[s] = True
            else:
                self.keys = self.keys.at[s].set(key[0])
            self._occupancy_gauge()
        if not self._ahead:
            # The ragged and speculative steps read every dispatch
            # before the next: the token is there.
            self._read_first_tokens()

    def _lane_state(self) -> tuple:
        """The split engine's lane state on the device (tok, lengths,
        finished, recent): the last enqueued chunk's outputs, or the
        host's arrays before the first one and after a pool reset."""
        if self._lanes is None:
            self._lanes = tuple(
                jnp.asarray(a.copy()) for a in
                (self.tok, self.lengths, self.finished, self.recent))
            self._seen_lengths = self.lengths.copy()
            self._edited[:] = False
        return self._lanes

    # hot-path
    def _read_first_tokens(self) -> None:
        """Read the first tokens of the prompts activated since the
        last call: observe TTFT, end the admission span, emit. The engine's second point of waiting (the
        first is the harvest): the prompt's last prefill chunk, and
        every dispatch enqueued before it, must finish before the read
        returns. In the split engine the chunk the lane joined is
        enqueued behind it by then, so the device has work when the
        wait returns (`wait`, not `blocked`) and the token is NOT a
        chunk late."""
        for s in list(self._first):
            req, tok0 = self._first.pop(s)
            with self._phase(
                "first_token",
                "blocked" if self._inflight is None else "wait",
            ):
                first = int(np.asarray(tok0)[0])  # oryxlint: disable=host-sync
            self.tok[s] = first
            self._drain_prefill_held()  # ready: the first token was read
            if req.adm_span >= 0:
                req.trace.end(req.adm_span)
                req.adm_span = -1
            with self._phase("emit"):
                self._observe_ttft(req)
                # tok0 is this slot's first generated token. The chunk
                # program re-emits it as its first output (the scan
                # step emits the token it was FED, dense-path
                # semantics), so one extra replay skip keeps the stream
                # exactly-once.
                self._advance(s, [first])
                if self.slots[s] is not None:
                    req.replay += 1

    def _observe_ttft(self, req: _Request) -> None:
        """The request's first token exists: observe its TTFT once."""
        if req.ttft_done:
            return
        req.ttft_done = True
        ttft = time.monotonic() - req.submit_time
        self.metrics.observe("ttft_seconds", ttft, buckets=TTFT_BUCKETS)
        req.handle.debug["ttft_s"] = ttft
        if self.anomaly is not None:
            self.anomaly.observe_ttft(ttft, request_id=req.trace.id)
        req.handle.debug["admit_chunk"] = self.chunks_run

    def _activate_block(self, s: int, req: _Request) -> None:
        """Block mode's activation: the prompt's whole blocks are in the
        cache (prefilled or spliced) and its tail `length % B` opens the
        slot's first block. Nothing is read from the device: the first
        tokens arrive with the first block's harvest, which is also
        where TTFT is observed and the admission span ends. The slot's
        key is the request's own key0 whatever was prefilled or
        spliced, so a sampled stream replays the same after an
        eviction."""
        B = self.block
        with self._phase("emit"):
            tail = req.length % B
            self.lengths[s] = req.length - tail
            self.blk[s] = 0
            if tail:
                self.blk[s, :tail] = req.cache_tokens[req.length - tail:]
            self.blk_known[s] = tail
            self.finished[s] = False
            self.temp[s] = req.temp
            self.top_p[s] = req.topp
            self.top_k[s] = req.topk
            self.keys = self.keys.at[s].set(req.key0)
            self.metrics.inc("admitted")
            self._donate_prefix(s, req, req.length)
            self._occupancy_gauge()

    def _donate_prefix(self, s: int, req: _Request, tokens: int) -> None:
        """Index the full-page prefix of slot s's first `tokens` logical
        slots into the prefix cache (the cache takes its own page
        references, so the entry outlives the slot). Called at
        activation with the prompt — concurrent look-alikes hit
        immediately — and at finish with prompt + reply."""
        if (
            self.prefix_cache is None or req.cache_tokens is None
            or self._cache_shed
        ):
            return
        stream = req.cache_tokens
        if tokens > req.length:
            stream = np.concatenate([
                stream, np.asarray(req.emitted, np.int64),
            ])
        full = min(
            min(tokens, len(stream)) // self.page_size, self._held(s)
        )
        if full:
            self.prefix_cache.insert(
                stream[: full * self.page_size],
                [int(p) for p in self.bt[s, :full]],
            )

    def _ensure_capacity(self) -> None:
        """Every live slot must own pages for lengths + one dispatch
        window (`_win`) before the next dispatch; under page pressure,
        preempt YOUNGER slots only — a slot with no younger victim
        preempts ITSELF (vLLM-style), so the oldest request always
        makes progress and eviction can never ping-pong two slots at
        the same growth point forever."""
        order = sorted(
            (s for s, r in enumerate(self.slots) if r is not None),
            key=lambda s: self.slots[s].admit_seq,
        )
        for s in order:
            if self.slots[s] is None or self.finished[s]:
                continue  # freed or evicted by an earlier iteration
            while not self._grow_slot(s, int(self.lengths[s]) + self._win):
                if self._inflight is not None:
                    # Page pressure: read the dispatch in flight before
                    # anyone is evicted (its finishes may free the
                    # pages; a victim's replay count holds its tokens),
                    # then look again.
                    self._drain_flight()
                    return self._ensure_capacity()
                me = self.slots[s].admit_seq
                younger = [
                    v for v in order
                    if self.slots[v] is not None
                    and self.slots[v].admit_seq > me
                ]
                if younger:
                    self._evict(
                        max(younger, key=lambda v: self.slots[v].admit_seq)
                    )
                elif any(
                    self.slots[v] is not None for v in order if v != s
                ):
                    self._evict(s)  # wait for the older slots' pages
                    break
                else:
                    self._finish_error(
                        s, "page pool exhausted for a single request"
                    )
                    break

    @staticmethod
    def _requeue_spans(req: _Request) -> None:
        """A slot holder goes back to the queue head (eviction, engine
        restart): an admission it was still in ends here, and it waits
        again."""
        if req.adm_span >= 0:
            req.trace.end(req.adm_span)
            req.adm_span = -1
        req.qw_span = req.trace.begin("queue_wait", requeued=True)

    # obligations: _clear_slot, queue_depth, evicted
    def _evict(self, s: int) -> None:
        """Free slot s and requeue its request at the FRONT; replay
        (same key0, same prompt) re-derives its stream deterministically
        and `processed` tokens are skipped on re-admission."""
        req = self.slots[s]
        req.replay = req.processed
        req.evictions += 1
        req.activated = False
        req.spliced = 0
        req.prefill_pos = 0
        self._clear_slot(s)
        req.trace.event("evicted", slot=s, replay_tokens=req.processed)
        self._requeue_spans(req)
        if self.journal is not None:
            self.journal.append(journal_lib.build_journal_event(
                kind="evict", step=self.steps_run, slot=s,
                victim_request_id=req.trace.id,
                admit_seq=req.admit_seq,
                replay_tokens=req.processed,
            ))
        _LOG.info(
            "request %s evicted from slot %d (replay %d tokens)",
            req.trace.id, s, req.processed,
        )
        with self._cond:
            self._queue.appendleft(req)
            self.metrics.set_gauge("queue_depth", len(self._queue))
        self.metrics.inc("evicted")
        self._occupancy_gauge()

    # ---- device-time sampling (utils/profiling.DeviceTimeSampler) --------

    def _abort_profile(self) -> None:
        """Containment: a failed dispatch (or engine restart) may have
        left a capture — periodic or on-demand — straddling the
        failure. Stop and discard it so the process-global profiler
        stays usable, and answer any waiting /debug/profile requester
        with an error instead of a hang."""
        self.profiler.abort()
        act, self._profile_active = self._profile_active, None
        if act is not None:
            act["holder"]["result"] = {
                "error": "engine step failed during the capture",
            }
            act["holder"]["done"].set()

    def _adopt_profile(self, holder: dict[str, Any]) -> None:
        """Engine thread: begin an on-demand capture spanning the next
        `steps` dispatches. A profiler that cannot start answers the
        requester immediately (counted error, engine untouched)."""
        if self.profiler.begin():
            self._profile_active = {
                "holder": holder,
                "left": int(holder["steps"]),
                "windows": [],
            }
        else:
            holder["result"] = {
                "error": "profiler start failed (see "
                "oryx_profile_capture_errors_total)",
            }
            holder["done"].set()

    def _profile_dispatch_begin(self) -> bool:
        """Immediately before a dispatch: True when THIS dispatch is a
        periodic device-time sample (capture started). The step
        counter advances every dispatch; steps inside an on-demand
        capture are never double-captured (jax's profiler is
        process-global) — their windows are recorded in
        _profile_dispatch_end instead."""
        due = self.profiler.tick()
        if self._profile_active is not None:
            return False
        return due and self.profiler.begin()

    def _profile_dispatch_end(self, sampled: bool, kind: str,
                              t0_ns: int) -> int | None:
        """After the dispatch's harvest sync: close a periodic sample
        (returns the window's device microseconds for the timeline
        record) or advance the on-demand capture by one window,
        finishing it — and answering the requester — when the asked
        step count is reached."""
        t1_ns = trace_lib.now_ns()
        act = self._profile_active
        if act is not None:
            act["windows"].append((kind, t0_ns, t1_ns))
            act["left"] -= 1
            if act["left"] <= 0:
                self._profile_active = None
                holder = act["holder"]
                holder["result"] = self.profiler.finish_capture(
                    act["windows"]
                )
                holder["done"].set()
            return None
        if sampled:
            return self.profiler.end(kind, t0_ns, t1_ns)
        return None

    # hot-path
    def _step_chunk(self) -> None:
        """The split engine's decode step: enqueue ONE
        `paged_decode_chunk` for the resident streams, THEN read the
        chunk enqueued a step earlier (`_step_ahead`)."""
        # Chaos site: decode dispatch failure (raise -> every in-flight
        # request errors, pool resets, serving continues) or hang
        # (delay= -> the stall watchdog and per-request deadlines are
        # what bound it).
        faults.fault_point("decode_dispatch")
        # Armed sanitizer: a decode dispatch entered while ANY lock is
        # held would serialize submit()/scrapes/debug reads on device
        # latency — the runtime twin of the static hot-path rule.
        hot_dispatch("scheduler._step_chunk")
        self._step_ahead()

    # hot-path
    def _block_step(self) -> None:
        """Block mode's engine step: enqueue ONE `paged_block_step`, in
        which every live slot generates its open block by diffusion
        (T denoising forwards of num_slots x B lanes, the first with B
        more lanes a slot that commit the block before), THEN read the
        block enqueued a step earlier (`_step_ahead`)."""
        faults.fault_point("decode_dispatch")
        hot_dispatch("scheduler._block_step")
        self._step_ahead()

    # hot-path
    def _step_ahead(self) -> None:
        """One decode dispatch is always in flight (docs/DESIGN.md "One
        dispatch in flight"): dispatch n+1 is enqueued before dispatch
        n is read, so the harvest, `emit` and the next round's
        housekeeping, admission and prefill enqueue run while the
        device works. What the host changes in a slot takes effect at
        the next enqueue; a harvest drops rows whose placement has
        ended; rare paths drain first. The enqueue / harvest pair is
        the mode's (`_enqueue_block` / `_harvest_block`,
        `_enqueue_chunk` / `_harvest_chunk`), looked up at the call so
        that a test can hook either on the instance."""
        enqueue, harvest = (
            (self._enqueue_block, self._harvest_block) if self.block
            else (self._enqueue_chunk, self._harvest_chunk)
        )
        prev, self._inflight = self._inflight, None
        if prev is not None and (
            self._profile_active is not None or self.profiler.due_next()
        ):
            # A capture's window holds its own dispatch alone.
            harvest(prev)
            prev = None
        self._inflight = enqueue(ahead=prev is not None)
        if prev is not None:
            harvest(prev)
        self._read_first_tokens()
        if self._inflight is not None and (
            self._inflight.captured
            or not any(r is not None and r.activated for r in self.slots)
        ):
            # Nobody is left to ride a next dispatch (every rider ended
            # on an EOS, a stop or a cancel): the engine goes idle
            # with nothing in flight.
            self._drain_flight()

    # hot-path
    def _enqueue_chunk(self, ahead: bool) -> _Flight | None:
        """Enqueue one decode chunk for every lane that rides it, on
        the lane state the chunk before left on the device with the
        host's edits laid over it, and move the host's state to where
        the chunk will leave a live lane: lengths a chunk further, and
        a lane whose request reaches max_tokens inside this chunk off
        the next one (the host counts; EOS, a stop string and a cancel
        are learned from the harvest, one chunk late). None when no
        lane rides."""
        riders = {
            s: r.admit_seq for s, r in enumerate(self.slots)
            if r is not None and r.activated and not self.finished[s]
        }
        if not riders:
            return None
        tok, lengths, finished, recent = self._lane_state()
        # Copies of its own (`_handed`): the record keeps them.
        edited, ran_lengths = self._edited.copy(), self.lengths.copy()
        temp = self.temp.copy()

        with self._phase("decode", "dispatch"):
            sampled = self._profile_dispatch_begin()
            numer = self._numerics_due()
            t0_ns = trace_lib.now_ns()
            with self.pipe._mesh_scope():
                lengths, finished, recent = generate_lib.overlay_lanes(
                    lengths, finished, recent, jnp.asarray(edited),
                    jnp.asarray(ran_lengths), _handed(self.finished),
                )
                out = generate_lib.paged_decode_chunk(
                    self.pipe.params["llm"], self.cfg.llm, self.kv_pages,
                    _handed(self.bt),
                    tok, lengths, finished, recent,
                    self.keys,
                    jnp.asarray(temp),
                    _handed(self.top_p),
                    _handed(self.top_k),
                    self.stop_sequences,
                    chunk=self.chunk,
                    eos=self.cfg.generation.eos_token_id,
                    attn_impl=self.cfg.attn_impl,
                    compute_dtype=oryx.compute_dtype(self.cfg),
                    numerics=numer,
                    **self._window_args(slice(None)),
                )
        (self.kv_pages, tok, lengths, finished, recent, self.keys,
         toks) = out[:7]
        self._lanes = (tok, lengths, finished, recent)
        self._edited[:] = False
        for s in riders:
            self.lengths[s] += self.chunk  # the device's, while it lives
            req = self.slots[s]
            if int(self.lengths[s]) - req.length >= req.max_new:
                self.finished[s] = self._edited[s] = True  # its last chunk
        held, self._prefill_held = self._prefill_held, []
        if ahead:
            self.metrics.inc("decode_dispatches_ahead_total")
        return _Flight(
            toks=toks, riders=riders, temp=temp, t0_ns=t0_ns,
            sampled=sampled,
            captured=sampled or self._profile_active is not None,
            lengths=lengths, finished=finished, edited=edited,
            ran_lengths=ran_lengths,
            nstats=out[8] if numer else None,
            share=out[-1] if self.share_stats else None, held=held,
        )

    # hot-path
    def _harvest_chunk(self, flight: _Flight) -> None:
        """The one harvest of a decode chunk, against the record of
        what it was given: its tokens go through `_advance`, the rows
        of a slot that no longer holds the placement it rode for are
        dropped, and the chunk's steps are counted ONCE, here, from
        the lengths it ran with and the lengths it confirmed."""
        self.metrics.inc("harvest_total")
        # The first read waits for the program; the rest copy what is
        # done (the per-step `fin`, `tok` and `recent` stay on the
        # device: nothing on the host reads them). With a chunk
        # enqueued behind this one the device has work when the wait
        # returns: no `host` event opens.
        # oryxlint: off=host-sync
        with self._phase(
            "harvest", "blocked" if self._inflight is None else "wait"
        ):
            toks = np.asarray(flight.toks)
        with self._phase("copy_out"):
            lengths = np.asarray(flight.lengths).copy()
            finished = np.asarray(flight.finished)
            share = None if flight.share is None else np.asarray(flight.share)
        # oryxlint: on=host-sync
        self._count_lane_steps(
            np.where(flight.edited, flight.ran_lengths, self._seen_lengths),
            lengths,
        )
        self._seen_lengths = lengths
        self._drain_prefill_held(flight.held)
        t0_ns, dt = self._flight_window(flight)
        with self._phase("emit"):
            dev_us = (
                self._profile_dispatch_end(flight.sampled, "decode", t0_ns)
                if flight.captured else None
            )
            self._record_numerics(flight.nstats)
            if share is not None:
                self._count_share(share)
            live = self._seated_riders(flight, "decode_rows_dropped_total")
            # What `_finish` may donate: never the advanced length.
            self.confirmed[live] = lengths[live]
            self.finished[live] |= finished[live]
            self._finish_dispatch(
                "decode", len(flight.riders), live, toks, t0_ns, dt,
                device_us=dev_us, temps=flight.temp,
            )
            self._occupancy_gauge()

    def _count_lane_steps(self, ran, lengths) -> None:
        """The decode reads of one chunk, for the families a state or a
        window makes the host reckon: a lane that was live for n steps
        from length a (`ran`) to `lengths`."""
        if not (self.recurrent or self.windowed or self.indexed):
            return
        a = ran.astype(np.int64)
        n = lengths.astype(np.int64) - a

        def capped(limit: int) -> int:
            """Rows the lanes' steps read where a row at length
            a + i + 1 reads min(that, limit)."""
            i = np.arange(self.chunk, dtype=np.int64)[None, :]
            read = np.minimum(a[:, None] + i + 1, limit)
            return int((read * (i < n[:, None])).sum())

        if self.indexed:
            # The latent rows the indexer kept (it scored a + i + 1
            # index keys: kv_tokens of the step's own statistics).
            self.metrics.inc("decode_selected_tokens_total",
                             capped(self.cfg.llm.index_topk))
        if self.recurrent:
            # It advanced n positions and read a + 1 .. a + n cached
            # tokens.
            self.metrics.inc(
                f"{self._state}_decode_lane_steps_total", int(n.sum()))
            if not self.share_stats:  # (else the step's own statistics)
                self.metrics.inc(
                    "decode_kv_tokens_total",
                    int((n * a + n * (n + 1) // 2).sum()))
            if self.conv_state:
                # It fed positions a .. a + n - 1: a snapshot where one
                # was the last of its page.
                ps = self.page_size
                self.metrics.inc(
                    "conv_edge_writes_total",
                    int(((a + n) // ps - a // ps).sum()))
        if self.windowed:
            # It fed positions a .. a + n - 1, and a window layer's row
            # at p read min(p + 1, W) cached tokens (a global layer's
            # p + 1: kv_tokens of the step's own statistics).
            self.metrics.inc("decode_window_kv_tokens_total",
                             capped(self.cfg.llm.sliding_window))

    def _seated_riders(self, flight: _Flight, dropped: str) -> list[int]:
        """The riders of a dispatch whose slot still holds the
        placement they rode for; the others' rows are dropped, and
        counted under `dropped`."""
        live = [
            s for s, seq in flight.riders.items()
            if self.slots[s] is not None
            and self.slots[s].admit_seq == seq
        ]
        self.metrics.inc(dropped, len(flight.riders) - len(live))
        return live

    def _flight_window(self, flight: _Flight) -> tuple[int, float]:
        """(start ns, seconds) of a dispatch that was just read,
        harvest to harvest, the pace a client feels: one enqueued
        ahead began when the one before it was read, the first after a
        drain at its enqueue."""
        t0_ns = max(flight.t0_ns, self._read_ns)
        self._read_ns = trace_lib.now_ns()
        return t0_ns, (self._read_ns - t0_ns) / 1e9

    # hot-path
    def _enqueue_block(self, ahead: bool) -> _Flight | None:
        """Enqueue one block for every slot that rides it and move the
        host's state to where the block will leave it: lengths past
        the block, the next block all masked, and a slot whose request
        reaches max_tokens inside this block off the next one (the
        host counts; EOS, a stop and a cancel are learned from the
        harvest, one block late). None when no slot rides."""
        riders = {
            s: r.admit_seq for s, r in enumerate(self.slots)
            if r is not None and r.activated and not self.finished[s]
        }
        if not riders:
            return None
        gen = self.cfg.generation
        # Copies: the host changes its arrays while the block is in
        # flight, and on the CPU `jnp.asarray` shares the numpy buffer.
        known, temp = self.blk_known.copy(), self.temp.copy()
        # The riders whose block before this one is still theirs to
        # commit. Its tokens are the last dispatch's output as it lies
        # on the device: nothing waits for them.
        commit = np.zeros((self.num_slots,), bool)
        for s, seq in riders.items():
            commit[s] = self.blk_pending[s] == seq
        if self._pending_toks is None:
            self._pending_toks = jnp.zeros(self.blk.shape, jnp.int32)

        with self._phase("denoise", "dispatch"):
            sampled = self._profile_dispatch_begin()
            t0_ns = trace_lib.now_ns()
            with self.pipe._mesh_scope():
                out = generate_lib.paged_block_step(
                    self.pipe.params["llm"], self.cfg.llm, self.kv_pages,
                    _handed(self.bt),
                    _handed(self.blk),
                    jnp.asarray(known),
                    _handed(self.lengths),
                    _handed(self.finished),
                    self.keys,
                    jnp.asarray(temp),
                    _handed(self.top_p),
                    _handed(self.top_k),
                    self._pending_toks,
                    jnp.asarray(commit),
                    steps=gen.denoising_steps or self.block,
                    remasking=gen.remasking,
                    threshold=gen.confidence_threshold,
                    eos=gen.eos_token_id,
                    attn_impl=self.cfg.attn_impl,
                    compute_dtype=oryx.compute_dtype(self.cfg),
                )
        self.kv_pages, toks, _, _, _, self.keys, counts = out
        self._pending_toks = toks
        self.lengths[~self.finished] += self.block  # the device's `live`
        self.blk[:] = 0
        self.blk_known[:] = 0
        for s, seq in riders.items():
            req = self.slots[s]
            self.blk_pending[s] = seq
            if int(self.lengths[s]) - req.length >= req.max_new:
                self.finished[s] = True  # its last block is this one,
                self._drop_pending(s)  # which nothing will read
        if ahead:
            self.metrics.inc("block_dispatches_ahead_total")
        return _Flight(
            toks=toks, counts=counts, riders=riders,
            fused=int(commit.sum()), known=known,
            temp=temp, t0_ns=t0_ns, sampled=sampled,
            captured=sampled or self._profile_active is not None,
        )

    def _drain_flight(self) -> None:
        """Read the dispatch in flight now, and the first tokens not
        yet read. The rare paths call this before they act (an
        eviction, a deadline's error, going idle, a capture's window
        edge, close()), so that they see the engine as a step without
        a dispatch in flight leaves it."""
        flight, self._inflight = self._inflight, None
        # First tokens first: a chunk's rows for a lane that joined it
        # begin with the first token again (`_read_first_tokens`).
        self._read_first_tokens()
        if flight is not None:
            (self._harvest_block if self.block
             else self._harvest_chunk)(flight)

    # hot-path
    def _harvest_block(self, flight: _Flight) -> None:
        """The one harvest of a block, against the record of what it
        was given. Its new tokens go through `_advance` in position
        order, as one emission (one SSE chunk a block); EOS and
        max_tokens cut inside it. The rows of a slot that no longer
        holds the placement it rode for are dropped."""
        self.metrics.inc("harvest_total")
        # Three blocking copies: the tokens, which wait for the block,
        # then the five statistics as one array and the per-slot
        # forwards (each about a millisecond once the first has
        # waited, PERF.md section 6, PR 24). Lengths advance on the
        # host. With a block enqueued behind this one the device has
        # work when the wait returns: no `host` event opens.
        # oryxlint: off=host-sync
        with self._phase(
            "harvest", "blocked" if self._inflight is None else "wait"
        ):
            toks = np.asarray(flight.toks)
        with self._phase("copy_out"):
            stats = dict(zip(
                generate_lib.BLOCK_STATS,
                (int(x) for x in np.asarray(flight.counts["stats"])),
            ))
            slot_forwards = np.asarray(flight.counts["slot_forwards"])
        # oryxlint: on=host-sync
        t0_ns, dt = self._flight_window(flight)
        with self._phase("emit"):
            dev_us = (
                self._profile_dispatch_end(flight.sampled, "block", t0_ns)
                if flight.captured else None
            )
            live = self._seated_riders(flight, "block_rows_dropped_total")
            for s in live:
                req = self.slots[s]
                self._observe_ttft(req)
                if req.adm_span >= 0:
                    req.trace.end(req.adm_span)
                    req.adm_span = -1
            self._count_block_dispatch(flight, stats)
            self._finish_dispatch(
                "block", len(flight.riders) * self.block, live,
                {s: [int(t) for t in toks[s, flight.known[s]:]]
                 for s in live},
                t0_ns, dt, device_us=dev_us,
                slot_forwards=slot_forwards, forwards=stats["forwards"],
                temps=flight.temp,
            )
            self._occupancy_gauge()

    def _count_block_dispatch(self, flight: _Flight,
                              stats: dict) -> None:
        """The diffusion_* and moe_* families for one block dispatch
        (`stats`: generate.BLOCK_STATS by name). Every forward has a
        head and denoises; the first also commits `flight.fused`
        slots' blocks before it."""
        m = self.metrics
        m.inc("diffusion_blocks_total", len(flight.riders))
        m.inc("diffusion_forwards_total", stats["forwards"],
              labels={"kind": "denoise"})
        m.inc("diffusion_commits_total", flight.fused,
              labels={"how": "fused"})
        m.inc("diffusion_tokens_unmasked_total", stats["unmasked"])
        m.inc("moe_rows_routed_total", stats["moe_rows_routed"])
        m.inc("moe_expert_rows_max_total", stats["moe_rows_max"])
        m.inc("moe_expert_rows_mean_total",
              stats["moe_rows_routed"] / max(1, self.cfg.llm.num_experts))
        m.inc("moe_experts_hit_total", stats["moe_experts_hit"])

    def _count_share(self, stats) -> None:
        """The moe_* and decode_kv_tokens families for one decode
        dispatch (`stats`: generate.SHARE_STATS, summed over its
        steps)."""
        st = dict(zip(generate_lib.SHARE_STATS, (int(x) for x in stats)))
        count = self.cfg.llm.held[1]
        m = self.metrics
        m.inc("moe_pairs_total", st["pairs"])
        m.inc("moe_zero_pairs_total", st["zero_pairs"])
        m.inc("moe_held_experts_hit_total", st["held_hit"])
        m.inc("moe_held_expert_slots_total", st["layer_forwards"] * count)
        m.inc("moe_expert_rows_max_total", st["held_rows_max"])
        m.inc("moe_expert_rows_mean_total", st["held_rows"] / count)
        m.inc("decode_kv_tokens_total", st["kv_tokens"])
        if self.windowed or self.recurrent:
            m.inc("moe_experts_hit_total", st["held_hit"])
        if self.cfg.llm.n_shared_experts:
            m.inc(
                "moe_shared_rows_total",
                st["pairs"] // self.cfg.llm.num_experts_per_tok,
            )

    def _drain_prefill_held(self, pending: list | None = None) -> None:
        """The moe_prefill_* families for the prefill chunks dispatched
        since the last drain (each `generate.SHARE_STATS` of a chunk's
        real rows; one transfer a chunk, of work that is done): the
        (token, expert) pairs, those that landed on an expert held
        here, and the held experts that took a row of the slots a
        layer-forward has."""
        if pending is None:
            pending, self._prefill_held = self._prefill_held, []
        m = self.metrics
        for stats in pending:
            with self._phase("copy_out"):
                st = dict(zip(generate_lib.SHARE_STATS,
                              (int(x) for x in np.asarray(stats))))
            m.inc("moe_prefill_pairs_total", st["pairs"])
            m.inc("moe_prefill_held_rows_total", st["held_rows"])
            m.inc("moe_prefill_held_experts_hit_total", st["held_hit"])
            m.inc("moe_prefill_held_expert_slots_total",
                  st["layer_forwards"] * self.cfg.llm.held[1])
            if self.windowed or self.recurrent:
                # A whole-expert config's chunks under the block step's
                # names too, beside its decode dispatches'.
                m.inc("moe_expert_rows_max_total", st["held_rows_max"])
                m.inc("moe_expert_rows_mean_total",
                      st["held_rows"] / self.cfg.llm.held[1])
                m.inc("moe_experts_hit_total", st["held_hit"])

    def _count_dispatch(self, kind: str, rows: int, *temps) -> None:
        """ONE device dispatch happened: its kind, its rows, and whether
        a row handed to it asked for sampling. `temps` are the
        temperatures the dispatch was given (the slots' array, a
        prefilled prompt's own), read before any slot retires. The
        sampler's conditional (generate.sample_token_rows) decides on
        the same values, so the second counter counts the dispatches
        that paid for a sort without a read from the device."""
        self.metrics.inc("dispatches_total", labels={"kind": kind})
        if any(np.any(np.asarray(t) > 0) for t in temps):
            self.metrics.inc(
                "sampler_sort_dispatches_total", labels={"kind": kind}
            )
        self.metrics.observe(
            "dispatch_rows", rows, buckets=DISPATCH_ROWS_BUCKETS
        )

    def _finish_dispatch(
        self, kind: str, rows: int, live: list[int], toks, t0_ns, dt,
        n_new=None, device_us=None, slot_forwards=None, forwards=None,
        pf_temp: float = 0.0, temps=None,
    ) -> None:
        """Post-dispatch accounting shared by the split decode chunk,
        the fused ragged step and the speculative step — ONE definition
        so the metric A/B across engine modes can never drift: beat
        bookkeeping, dispatch metrics, the per-slot harvest/billing
        loop, and the decode-step utilization counters. The decode-side
        numbers (TPOT, the decode_steps family) are skipped when NO
        slot decoded during the dispatch: a prefill-only fused step
        produces zero output tokens, and billing its dead decode lanes
        would skew TPOT and the wasted-step fraction against the ragged
        engine for a structural reason the utilization metric doesn't
        track (the split engine simply runs no decode dispatch in that
        state).

        n_new (speculative harvest): per-slot count of valid tokens in
        `toks` this step (fed token + accepted drafts). Billing then
        switches from steps==tokens to the honest split: device work
        per slot is its 1+k verify lanes (rejected drafts are paid
        compute, visible as wasted steps), tokens consumed are the
        n_new prefix, and the accepted_tokens_per_step histogram
        observes each live slot's advance — its sum/count mean is the
        speculation headline the bench gates on.

        pf_temp: the temperature of the prompt whose window rode in
        this dispatch (ragged and speculative steps), for
        `_count_dispatch`; temps: the slots' temperatures as the
        dispatch was handed them, where the slots have moved on since
        (block mode reads a block one enqueue late).

        slot_forwards / forwards (block mode; `toks` is then already
        {slot: the block's new tokens}): the dispatch ran `forwards`
        forwards of every slot's lanes, and slot s had work to do in
        slot_forwards[s] of them. The decode_steps family keeps its
        meaning, slot-forwards dispatched and those of live slots with
        work, and TPOT is the dispatch over the tokens a slot got."""
        self.chunks_run += 1
        self.metrics.inc("chunks")
        self._count_dispatch(
            kind, rows, self.temp if temps is None else temps, pf_temp
        )
        if self.watchdog is not None:
            self.watchdog.beat()
        lane_steps = (
            1 + self.speculate if n_new is not None else self.chunk
        )
        block = forwards is not None
        if block:
            lane_steps = forwards
        # The dispatch gave a slot a varying number of tokens.
        multi = block or n_new is not None
        useful = 0
        emitted = 0
        per_slot = toks if block else (
            generate_lib.unpack_ragged_rows(toks, live)
        )
        for s, tokens in per_slot.items():
            req = self.slots[s]
            if req is None:
                continue
            if n_new is not None:
                tokens = tokens[: int(n_new[s])]
                emitted += len(tokens)
                self.metrics.observe(
                    "accepted_tokens_per_step", len(tokens),
                    buckets=SPEC_ACCEPT_BUCKETS,
                )
            # The same device window lands on every live request: decode
            # chunks are shared dispatches, and per-request attribution
            # is exactly what makes occupancy problems visible in a
            # single request's /debug/trace.
            req.trace.add_complete(
                "decode_chunk", t0_ns, int(dt * 1e9),
                chunk=self.chunks_run, slot=s,
            )
            # Ledger: the device ran `chunk` scan steps (or 1+k verify
            # lanes) for this row whether or not the host kept them
            # (replay skips and rejected drafts are still cost); the
            # per-chunk accrual keeps page-seconds refcount samples
            # fresh while neighbors splice and release shared pages.
            req.cost_decode_steps += lane_steps
            self._accrue_page_seconds(s)
            got = self._advance(s, tokens)
            if block:
                emitted += got
                got = int(slot_forwards[s])
            useful += got
        if live and n_new is not None and self.anomaly is not None:
            # Speculation drift guard (default-armed whenever
            # --speculate is set): the mean tokens a live slot advanced
            # this dispatch, against its own rolling baseline — a
            # degraded drafter pages once per collapse episode.
            self.anomaly.observe_spec_accept(
                emitted / len(live), step=self.chunks_run,
            )
        if live:
            # Per-token latency: tokens per slot this dispatch is
            # `chunk` for the scan paths, the mean accepted advance for
            # the speculative path (the whole point: dt buys >1 token).
            per_tok = emitted / len(live) if multi else self.chunk
            self.metrics.observe(
                "time_per_output_token_seconds", dt / max(1.0, per_tok)
            )
            total = self.num_slots * lane_steps
            self.metrics.inc("decode_steps_total", total)
            self.metrics.inc("decode_steps_useful", useful)
            self.metrics.inc("decode_steps_wasted", total - useful)
        self._timeline_record(
            dur_s=dt, kind=kind, rows=rows,
            accepted=emitted if multi else useful,
            device_us=device_us,
        )

    def _numerics_due(self) -> bool:
        """Host-side cadence for the in-dispatch logit probe: every
        `numerics_every` engine steps the dispatch runs the probe-armed
        twin of its compiled program (a STATIC flag — two stable
        programs per shape class, tokens bit-identical either way)."""
        return (
            self.numerics_every > 0
            and self.chunks_run % self.numerics_every == 0
        )

    def _record_numerics(self, nstats) -> None:
        """Publish one probe sample (engine thread, post-harvest):
        oryx_numerics_* gauges + the entropy_collapse /
        absmax_explosion sentinels. None / zero-row accumulators (a
        probe-armed dispatch where nothing decoded) are silently
        skipped."""
        if nstats is None:
            return
        stats = numerics_lib.finalize_logit_stats(nstats)
        if stats is None:
            return
        for key, gauge in self._numerics_gauges.items():
            gauge.set(stats[key])
        self._numerics_samples.inc()
        if self.anomaly is not None:
            self.anomaly.observe_numerics(
                entropy=stats["entropy"], absmax=stats["absmax"],
                source_step=self.chunks_run,
            )

    def _timeline_record(self, *, dur_s: float, kind: str, rows: int,
                         accepted: int,
                         device_us: int | None = None) -> None:
        """One step record into the engine flight data recorder
        (utils/timeline.py). Engine thread only; the queue-depth and
        degraded-mode reads go through the metrics registry's own
        gauges, so the hot path never takes the scheduler lock for a
        telemetry sample."""
        live = sum(
            1 for r in self.slots if r is not None and r.activated
        )
        self.timeline.record(
            dur_s=dur_s, kind=kind, rows=rows,
            live_slots=live,
            accepted_tokens=accepted,
            queue_depth=int(self.metrics.get("queue_depth")),
            free_pages=self.allocator.num_free,
            degraded_mode=int(self.metrics.get("degraded_mode")),
            device_us=device_us,
        )
        # The journal's step clock: EVERY recorded dispatch advances it
        # (prefill, decode, ragged, spec), so steps_run is the count of
        # dispatches completed — the gate replay feeds admissions on.
        self.steps_run += 1
        if self.journal is not None:
            # Deliberately no dur_s/device_us/queue_depth: the journal
            # records only what replays deterministically.
            self.journal.append(journal_lib.build_journal_event(
                kind="step", step=self.steps_run, dispatch=kind,
                rows=rows, live_slots=live, accepted_tokens=accepted,
                free_pages=self.allocator.num_free,
            ))

    # hot-path
    def _read_chunk(self, tok, lengths, finished, recent, toks, fin):
        """Blocking host copies of the ragged step's outputs, which
        are read before the next dispatch (the split engine's harvest
        is `_harvest_chunk`).
        Host copies BLOCK on the device result — callers measure dt
        AFTER this, or async dispatch makes the window (and the
        per-token histogram) cover only dispatch time, and the
        span<->xplane join would land the decode ops outside every
        window. This is the engine's deliberate sync point per chunk
        (the harvest the chunk exists to amortize). There is a second
        one, per admission: the read of the first token
        (`_read_first_tokens`, phase `first_token`). Anything else
        host-syncing on the step paths is a regression the host-sync
        rule catches."""
        self.metrics.inc("harvest_total")
        # The first read waits for the program; the rest copy with the
        # device drained, which is the host's time, not a wait.
        # oryxlint: off=host-sync
        with self._phase("harvest", "blocked"):
            tok = np.asarray(tok)
        with self._phase("copy_out"):
            self.tok = tok.copy()
            self.lengths = np.asarray(lengths).copy()
            self.finished = np.asarray(finished).copy()
            self.recent = np.asarray(recent).copy()
            out = np.asarray(toks), np.asarray(fin)
        # oryxlint: on=host-sync
        return out

    # hot-path
    def _ragged_step(self) -> None:
        """The fused engine step (ragged mode): ONE device dispatch
        (`generate.paged_ragged_step`) advances the in-flight admission
        by up to chunk*pf_width prefill tokens AND decodes `chunk`
        tokens for every resident stream — replacing the
        `_prefill_step` + `_step_chunk` dispatch pair. Host state
        machinery (admission, eviction, harvest, activation, the cost
        ledger) is unchanged; only the device-call structure fuses.
        A slot whose prefill completes activates AFTER the harvest and
        joins the next dispatch (token streams are identical either
        way — per-row math never depends on dispatch grouping).

        Speculative mode (`speculate=k`, docs/DESIGN.md "Speculative
        decoding"): the dispatch becomes `generate.paged_spec_step` — a
        SINGLE packed verify forward where every live slot rides 1+k
        lanes (its fed token plus k host-proposed drafts) and the one
        admitting slot rides `prefill_chunk` prefill lanes. Still
        exactly one dispatch per engine step (kind="spec"), but a slot
        advances 1..k+1 tokens per step instead of 1. Stop STRINGS are
        detected host-side only (`_advance` runs at every step-harvest
        in this mode, so detection lands at the same token position the
        device-side window would have frozen at); device-side EOS
        truncation inside an accepted span matches the sequential
        freeze semantics (see spec_verify_rows)."""
        # Mid-admission cancels first (same invariant as _prefill_step:
        # a hung-up client's prefill must not ride the dispatch and its
        # pages — including spliced shares — return now).
        for s, req in enumerate(self.slots):
            if req is None or req.activated:
                continue
            if req.handle.cancelled:
                self._cancel_slot(s, req, "mid-prefill")
        if any(r is not None and r.activated for r in self.slots):
            with self._phase("housekeeping"):
                self._ensure_capacity()  # may evict: live is recomputed
        live = [
            s for s, r in enumerate(self.slots)
            if r is not None and r.activated
        ]
        pf_s, pf_req = None, None
        for s, req in enumerate(self.slots):
            if req is not None and not req.activated:
                # `_admit` holds further admission while one chunked
                # prefill is in flight, so at most one slot admits.
                pf_s, pf_req = s, req
                break
        if pf_req is None and not live:
            return
        # Chaos sites: the fused dispatch is both the admission's
        # prefill work and the residents' decode beat, so both named
        # fault sites keep their meaning in ragged mode.
        if pf_req is not None:
            faults.fault_point("prefill_dispatch")
        faults.fault_point("decode_dispatch")
        hot_dispatch("scheduler._ragged_step")
        W = self.pf_width
        # Per-dispatch prefill budget in TOKENS: the spec step is a
        # single forward carrying prefill_chunk lanes; the ragged scan
        # carries W lanes per each of its `chunk` iterations.
        win_tokens = (
            self.prefill_chunk if self.speculate else self.chunk * W
        )
        dtype = oryx.compute_dtype(self.cfg)
        pf_span = -1
        pf_off = pf_len = 0
        pf_temp = pf_req.temp if pf_req is not None else 0.0
        if pf_req is not None:
            # Packing the prompt's window is the prefill's host part;
            # its enqueue is the fused dispatch below.
            with self._phase("prefill"):
                pf_off, pf_len = pf_req.prefill_pos, pf_req.length
                window = generate_lib.pack_prefill_window(
                    pf_req.embeds_np, pf_off, win_tokens
                )
                pf_span = pf_req.trace.begin(
                    "prefill", slot=pf_s, start=pf_off,
                    tokens=min(win_tokens, pf_len - pf_off),
                    cached=pf_req.spliced > 0, replay=pf_req.replay > 0,
                    ragged=True,
                )
                pfw = self.prefill_chunk if self.speculate else W
                slot_c, len_c, active_c, key_c, temp_c, topp_c, topk_c = (
                    pf_req.pf_consts
                )
                pf_args = (
                    jnp.asarray(window),
                    slot_c,
                    jnp.asarray(pf_off, jnp.int32),
                    len_c,
                    active_c,
                    key_c,
                    temp_c,
                    topp_c,
                    topk_c,
                )
        else:
            # Pure-decode shape class: zero prefill lanes (pf_width=0
            # is STATIC, so this is the second — and last — compiled
            # program; host branching on engine state here is exactly
            # what keeps traced state out of Python control flow). The
            # constant blank operands were built once at construction.
            pfw = 0
            pf_args = self._ragged_blanks
        sampled = self._profile_dispatch_begin()
        t0 = time.monotonic()
        t0_ns = trace_lib.now_ns()
        if self.speculate:
            # Host-side self-drafting BEFORE the dispatch (the drafter
            # needs the token history the device never holds); the
            # whole fleet's proposals then verify in the one forward.
            with self._phase("decode", "dispatch"):
                drafts, dlen = self._propose_drafts(live)
                with self.pipe._mesh_scope():
                    (self.kv_pages, tok, lengths, finished, self.keys,
                     toks, n_new, acc, pf_tok0, pf_key) = (
                        generate_lib.paged_spec_step(
                            self.pipe.params["llm"], self.cfg.llm,
                            self.kv_pages,
                            jnp.asarray(self.bt),
                            jnp.asarray(self.tok),
                            jnp.asarray(self.lengths),
                            jnp.asarray(self.finished),
                            self.keys,
                            jnp.asarray(self.temp),
                            jnp.asarray(self.top_p),
                            jnp.asarray(self.top_k),
                            jnp.asarray(drafts),
                            jnp.asarray(dlen),
                            *pf_args,
                            k=self.speculate, pf_width=pfw,
                            eos=self.cfg.generation.eos_token_id,
                            attn_impl=self.cfg.attn_impl,
                            compute_dtype=dtype,
                        )
                    )
            toks, n_new, acc = self._harvest_spec(
                tok, lengths, finished, toks, n_new, acc
            )
            dt = time.monotonic() - t0
            with self._phase("emit"):
                dev_us = self._profile_dispatch_end(sampled, "spec", t0_ns)
                if live:
                    self.metrics.inc(
                        "draft_proposed_total", int(dlen[live].sum())
                    )
                    self.metrics.inc(
                        "draft_accepted_total", int(acc[live].sum())
                    )
                rows = len(live) * (1 + self.speculate) + (
                    min(pfw, pf_len - pf_off) if pf_req is not None else 0
                )
                self._finish_dispatch(
                    "spec", rows, live, toks, t0_ns, dt, n_new=n_new,
                    device_us=dev_us, pf_temp=pf_temp,
                )
        else:
            with self._phase("decode", "dispatch"):
                numer = self._numerics_due() and bool(live)
                with self.pipe._mesh_scope():
                    out = generate_lib.paged_ragged_step(
                        self.pipe.params["llm"], self.cfg.llm,
                        self.kv_pages,
                        jnp.asarray(self.bt),
                        jnp.asarray(self.tok),
                        jnp.asarray(self.lengths),
                        jnp.asarray(self.finished),
                        jnp.asarray(self.recent),
                        self.keys,
                        jnp.asarray(self.temp),
                        jnp.asarray(self.top_p),
                        jnp.asarray(self.top_k),
                        self.stop_sequences,
                        *pf_args,
                        chunk=self.chunk, pf_width=pfw,
                        eos=self.cfg.generation.eos_token_id,
                        attn_impl=self.cfg.attn_impl,
                        compute_dtype=dtype,
                        numerics=numer,
                    )
            nstats = out[10] if numer else None
            (self.kv_pages, tok, lengths, finished, recent, self.keys,
             toks, fin, pf_tok0, pf_key) = out[:10]
            toks, fin = self._read_chunk(
                tok, lengths, finished, recent, toks, fin
            )
            dt = time.monotonic() - t0
            with self._phase("emit"):
                dev_us = self._profile_dispatch_end(
                    sampled, "ragged", t0_ns
                )
                self._record_numerics(nstats)
                # Decode billing covers only slots live DURING the
                # dispatch — a slot activated below joins the next
                # dispatch, and its toks row this time was frozen
                # filler.
                rows = len(live) + (
                    min(W, pf_len - pf_off) if pf_req is not None else 0
                )
                self._finish_dispatch(
                    "ragged", rows, live, toks, t0_ns, dt,
                    device_us=dev_us, pf_temp=pf_temp,
                )
        # Prefill bookkeeping + activation (after harvest by design).
        if pf_req is not None:
            pf_req.trace.end(pf_span)
            advanced = min(win_tokens, pf_len - pf_off)
            pf_req.prefill_pos = pf_off + advanced
            pf_req.cost_prefill_tokens += advanced
            self.metrics.inc("prefill_tokens_total", advanced)
            self.metrics.observe(
                "prefill_chunk_tokens", advanced,
                buckets=PREFILL_CHUNK_BUCKETS,
            )
            if pf_req.prefill_pos >= pf_len:
                self._activate(pf_s, pf_req, pf_tok0[np.newaxis], pf_key)
        self._occupancy_gauge()

    def _propose_drafts(self, live: list[int]):
        """Host-side draft proposal for every live slot: the drafter
        sees the request's DEVICE-CONFIRMED stream — prompt ids +
        emitted[:confirmed] + the pending fed token — never the full
        host `emitted`, which runs AHEAD of the device during eviction
        replay; proposing from it would change the accept pattern
        between the original run and its replay and diverge the
        replayed RNG stream from what the client already saw.
        Multimodal prompts (no clean token-id stream) draft from the
        reply history alone. Only the drafter's declared `window` tail
        is materialized (None = everything): without the bound, the
        per-step host cost here grows O(prompt + reply) per slot —
        exactly the sequential-latency bill speculation exists to cut.
        Returns (drafts [S, k] int32, draft_len [S] int32); unproposed
        lanes ride the dispatch masked."""
        k = self.speculate
        win = getattr(self.drafter, "window", None)
        drafts = np.zeros((self.num_slots, k), np.int32)
        dlen = np.zeros((self.num_slots,), np.int32)
        for s in live:
            req = self.slots[s]
            confirmed = max(0, int(self.lengths[s]) - req.length)
            prompt = (
                req.cache_tokens if req.cache_tokens is not None
                else np.zeros((0,), np.int64)
            )
            reply = req.emitted[:confirmed]
            if win is not None:
                # Suffix of (prompt + confirmed reply + fed token),
                # assembled from tail slices so nothing longer than
                # the window is ever copied.
                keep = max(0, win - 1 - len(reply))
                prompt = (
                    prompt[max(0, len(prompt) - keep):]
                    if keep else prompt[:0]
                )
                reply = reply[max(0, len(reply) - (win - 1)):]
            ctx = np.concatenate([
                np.asarray(prompt, np.int64),
                np.asarray(reply, np.int64),
                np.asarray([int(self.tok[s])], np.int64),
            ])
            prop = self.drafter.propose(ctx, k)[:k]
            drafts[s, : len(prop)] = prop
            dlen[s] = len(prop)
        return drafts, dlen

    # hot-path
    def _harvest_spec(self, tok, lengths, finished, toks, n_new, acc):
        """Blocking host copies of a speculative dispatch's outputs —
        the spec twin of `_read_chunk` (no `recent` window: stop
        strings are host-detected in this mode, and fin is subsumed by
        the finished vector + the EOS the accepted span carries). Same
        one-deliberate-sync-per-step contract."""
        self.metrics.inc("harvest_total")
        # oryxlint: off=host-sync
        with self._phase("harvest", "blocked"):
            tok = np.asarray(tok)
        with self._phase("copy_out"):
            self.tok = tok.copy()
            self.lengths = np.asarray(lengths).copy()
            self.finished = np.asarray(finished).copy()
            out = np.asarray(toks), np.asarray(n_new), np.asarray(acc)
        # oryxlint: on=host-sync
        return out

    def _occupancy_gauge(self) -> None:
        live = sum(
            1 for s, r in enumerate(self.slots)
            if r is not None and not self.finished[s]
        )
        self.metrics.set_gauge("slot_occupancy", live / self.num_slots)
        if self.wplane is not None:
            for plane, alloc in (("global", self.allocator),
                                 ("window", self.wplane.allocator)):
                self._pages_live[plane](alloc.num_pages - alloc.num_free)
        u = self.metrics.get("decode_steps_useful")
        t = self.metrics.get("decode_steps_total")
        if t:
            self.metrics.set_gauge("decode_step_utilization", u / t)

    # ---- harvest / text emission ----------------------------------------

    # hot-path
    def _advance(self, s: int, tokens: list[int]) -> int:
        """Feed slot s's newly decoded tokens through the host-side text
        machine; returns the number of USEFUL steps consumed (replayed
        steps count as wasted — they are eviction overhead). Mirrors
        chat_stream's emission rules (stop trim, stable prefix, EOS
        fill, length cap) at a cost bounded by the CHUNK: token-level
        checks (EOS, max_new) run per token, and once per chunk
        `ReplyText.advance` decodes the chunk's tokens behind a few
        tokens of context, scans for a stop where one could have
        completed and holds back by the text's end. Nothing here reads
        `req.emitted` whole or the text sent so far, however many
        tokens the dispatch gave the lane (8, a block, 1..k+1)."""
        req = self.slots[s]
        eos = self.cfg.generation.eos_token_id
        useful = 0
        if req.handle.cancelled:
            self._cancel_slot(s, req, "mid-decode")
            return useful
        chunk_start = len(req.emitted)
        finish = None  # (reason, completion_count)
        for t in tokens:
            if req.replay > 0:
                req.replay -= 1
                continue
            req.processed += 1
            useful += 1
            if t == eos:
                finish = ("stop", len(req.emitted) + 1)
                break
            req.emitted.append(t)
            if len(req.emitted) >= req.max_new:
                finish = ("length", len(req.emitted))
                break
        if len(req.emitted) == chunk_start and finish is None:
            return useful  # pure replay skip: nothing new to decode
        t_emit = trace_lib.now_ns()
        # On a finish the held-back tail (whitespace, a stop-string
        # prefix) is flushed exactly as chat_stream does.
        delta, n = req.text.advance(
            self._emit_decode, req.emitted, req.stops, chunk_start,
            final=finish is not None,
        )
        if n is not None and (finish is None or n <= finish[1]):
            # The stop completed in THIS chunk (earlier chunks were
            # checked clean); it precedes any EOS/length finish seen
            # later in the same chunk.
            finish = ("stop", n)
        if finish is not None:
            # Wasted-step honesty: a stop STRING is detected host-side,
            # so the token loop above consumed (and billed as useful)
            # every token up to the chunk end or an EOS — but tokens
            # past the one that completed the finish did nothing for
            # the client. Clamp useful to the finish point in
            # CONSUMED-token space (finish[1] counts completion tokens;
            # chunk_start is where this chunk's consumption began —
            # this also covers an EOS consumed after a stop completed,
            # which was billed but never appended to `emitted`).
            # Without this the wasted-step fraction under-counts
            # whenever a slot finishes mid-chunk on a stop string.
            useful = min(useful, finish[1] - chunk_start)
        # Ledger: tokens of client-visible completion progress this
        # step (replay skips excluded, post-stop tokens clamped away) —
        # the "decode_tokens" half of the steps-vs-tokens split.
        req.cost_decode_tokens += useful
        if delta and req.handle.streaming:
            # Only streaming consumers drain the event queue; for plain
            # requests the reply accumulates in `text` and queued
            # fragments would just sit there.
            req.handle.events.put(("delta", delta))
        req.trace.add_complete("emission", t_emit, chars=len(req.text_done))
        if finish is not None:
            self._finish(s, finish[0], completion=finish[1])
        return useful

    # obligations: _finalize_cost, _clear_slot, _emit_request_event, completed
    def _finish(self, s: int, reason: str, completion: int) -> None:
        req = self.slots[s]
        cost = self._finalize_cost(s, req)
        # Donate the full-page prefix of prompt + reply before the
        # slot's references go: the cache's own share keeps the pages
        # alive, so the NEXT turn of this conversation (whose prompt
        # embeds this reply) splices instead of recomputing. Capped at
        # the DEVICE-confirmed KV length: a token the host emitted but
        # the device never fed back (tok0 of a max_tokens=1 request
        # finishing at activation) has no KV at its slot, and donating
        # it would poison the cache with prefill pad garbage.
        # In block mode the last block a slot was enqueued for is not
        # in the pages (it is pending, or it opened behind the reply's
        # last block and holds nothing final): `lengths` less a block.
        # In the split engine `lengths` has run ahead with the chunk in
        # flight, whose K/V past the harvest's length is not this
        # reply's: `confirmed` is the harvest's.
        self._donate_prefix(
            s, req,
            min(req.length + len(req.emitted),
                int(self.confirmed[s]) if self._ahead
                else int(self.lengths[s]) - self.block),
        )
        self._clear_slot(s)
        req.handle.reply = req.text_done
        req.handle.finish_reason = reason
        req.handle.usage = (req.length, completion)
        req.handle.debug["finish_chunk"] = self.chunks_run
        req.handle.events.put(("end", reason, req.handle.usage))
        req.handle.done.set()
        req.trace.finish(
            finish_reason=reason, prompt_tokens=req.length,
            completion_tokens=completion, cost=cost,
        )
        self._emit_request_event(req, status="ok")
        # Output-audit sampling: every Nth finished request queues a
        # shadow-parity replay job (host copies only; the replay runs
        # later, at an idle point of this same thread).
        if self.auditor.sample_every:
            self._ensure_embeds(req)
        self.auditor.observe_finished(req)
        _LOG.info(
            "request %s finished (%s, %d tokens)",
            req.trace.id, reason, completion,
        )
        self.metrics.inc("completed")

    # obligations: _finalize_cost, _clear_slot, _emit_request_event
    def _finish_error(
        self, s: int, msg: str, *, kind: str = "server_error"
    ) -> None:
        req = self.slots[s]
        cost = self._finalize_cost(s, req)
        self._clear_slot(s)
        req.handle.error = msg
        req.handle.error_kind = kind
        req.handle.events.put(("error", msg))
        req.handle.done.set()
        req.trace.finish(error=msg, cost=cost)
        self._emit_request_event(req, status="error", error_kind=kind)
        _LOG.info("request %s errored: %s", req.trace.id, msg)
