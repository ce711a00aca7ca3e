"""Continuous output auditing: shadow-parity replay off the hot path.

Everything before this module observes where time and memory go; the
auditor observes *what the model computes*, in production, without
perturbing it. A seeded sampler picks every Nth FINISHED request
(``--audit-sample-every N``, 0 = off) and replays it COLD through the
split XLA reference path — `generate.paged_prefill` + a single-row
decode step, over the auditor's own private page pool, with no
prefix-cache splice — then compares:

  * **greedy byte parity**: the replayed token stream against the
    tokens the client actually received, with the first-divergence
    position on mismatch. This is exactly the determinism the engine
    already leans on for eviction replay and supervised restart — the
    auditor turns that invariant from a test-time assertion into a
    continuously measured production signal.
  * **logit drift**: at K sampled reply positions, the full logit row
    from the reference replay against the row from a second replay run
    under the PRODUCTION configuration (the engine's attn_impl — e.g.
    the Pallas ragged kernel — and its pool format: with
    ``--kv-dtype int8`` the twin replays through a private QUANTIZED
    pool and the fp reference is teacher-forced on the live stream so
    every compared row shares the twin's context): per-position
    max-abs-diff and KL. On the fp path the two programs are
    bit-identical and the diff is exactly 0; on the quantized path the
    drift gates against the ``--audit-tol-maxdiff``/``--audit-tol-kl``
    boundary (defaults derived from utils/quant.roundtrip_error_stats
    — ``drift`` within it, ``fail`` above it), which is ROADMAP item
    3's standing quantized-vs-fp tolerance gate.

Verdicts land in ``oryx_audit_total{verdict=pass|drift|fail}`` plus the
``oryx_audit_logit_max_abs_diff`` / ``oryx_audit_kl`` histograms, a
bounded forensic ring served at ``GET /debug/audit?n=`` (divergence
position, top-k logit table at the worst position, both token streams'
tails), one ``kind="audit"`` wide event per audit through the PR 12
request-log sink (schema utils.metrics.AUDIT_EVENT_KEYS), and the
``audit_drift`` anomaly detector (one event per drift episode).

Never perturbs serving — the contract, mechanically:

  * replays run ON the engine thread, but only at idle points of its
    loop (no queue, no residents — the same quiesce discipline the
    /debug/profile adopt-a-holder pattern uses), so a replay dispatch
    can never interleave with, delay, or recompile a live step;
  * the replay uses a PRIVATE page pool and block table — it never
    allocates from the serving allocator, touches the prefix cache, or
    donates the engine's KV arrays;
  * it increments only ``oryx_audit_*`` families — live-traffic byte
    parity and `oryx_serving_dispatches_total` under
    ``--audit-sample-every 1`` are CI-gated bit-identical to an
    unarmed run (scripts/check_serving_endpoints.py --audit-smoke).

Scope: greedy requests only (temperature == 0). Sampled streams are
replay-deterministic through the engine's own machinery, but the
speculative path is distribution-exact rather than stream-identical at
temperature > 0, so non-greedy picks count in
``oryx_audit_skipped_total{reason="sampled"}`` instead of producing a
verdict that could false-alarm.

Thread contract: the sampler (`observe_finished`) and the replay
(`run_one`) run on the engine thread only; HTTP handler threads read
snapshots through `to_dict` under the leaf ``audit._lock`` (declared
in oryx_tpu/concurrency.py), held only for ring/counter edits — never
across a replay.
"""

from __future__ import annotations

import logging
import time
from collections import deque
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from oryx_tpu.analysis.sanitizers import named_lock
from oryx_tpu.models import generate as generate_lib
from oryx_tpu.models import oryx, qwen2
from oryx_tpu.ops import paged_kv
from oryx_tpu.ops.packing import round_up_bucket
from oryx_tpu.utils import request_log as request_log_lib
from oryx_tpu.utils.metrics import (
    AUDIT_DIFF_BUCKETS,
    AUDIT_KL_BUCKETS,
    ServingMetrics,
)

_LOG = logging.getLogger("oryx.serve.audit")

# Tokens of each stream retained in a forensic record's tails: enough
# to see the divergence neighborhood, bounded so a record stays one
# readable screen (the forensics TOP_K discipline).
TAIL_TOKENS = 16
# Top-k logit rows kept in the worst-position table.
TOP_LOGITS = 5


@partial(
    jax.jit,
    static_argnames=("cfg", "attn_impl", "compute_dtype"),
    donate_argnames=("kv_pages",),
)
def audit_decode_step(
    params,
    cfg,
    kv_pages: dict,  # donated (the auditor's PRIVATE pool)
    block_tables: jnp.ndarray,  # [1, max_pages] int32
    tok: jnp.ndarray,  # [1] token to feed
    cur_len: jnp.ndarray,  # [1] kv tokens held
    keys: jax.Array,  # [1] per-row PRNG key
    temperature: jnp.ndarray,  # [1]
    top_p: jnp.ndarray,  # [1]
    top_k: jnp.ndarray,  # [1]
    *,
    attn_impl: str = "xla",
    compute_dtype=None,
):
    """One single-row decode step that ALSO returns the logit row —
    the audit replay's inner loop. Step semantics (cache write, mask,
    RNG split order, sampler) mirror `paged_decode_chunk`'s scan body
    exactly, so the replayed stream is bit-identical to the engine's;
    the only addition is the [1, V] float32 logits output the drift
    comparison reads. One dispatch per replayed token — fine off the
    hot path, where this exclusively runs.

    Returns (kv_pages, next_tok [1], logits [1, V] f32, keys')."""
    page_size = paged_kv.pool_plane(kv_pages).shape[2]
    K = block_tables.shape[1] * page_size
    slot_ar = jnp.arange(K, dtype=jnp.int32)[None, :]
    pair = jax.vmap(lambda k: jax.random.split(k, 2))(keys)
    kv_mask = (slot_ar <= cur_len[:, None]).astype(jnp.int32)
    logits, kv_pages = qwen2.forward(
        params, cfg,
        input_ids=tok[:, None], positions=cur_len[:, None],
        kv_cache=kv_pages, write_slots=cur_len, kv_mask=kv_mask,
        block_tables=block_tables,
        write_mask=jnp.ones((1,), bool),
        kv_lengths=cur_len + 1,
        attn_impl=attn_impl, compute_dtype=compute_dtype,
    )
    lg = logits[:, 0]
    nxt = generate_lib.sample_token_rows(
        lg, pair[:, 1],
        temperature=temperature, top_p=top_p, top_k=top_k,
    )
    return kv_pages, nxt, lg.astype(jnp.float32), pair[:, 0]


def sample_positions(reply_tokens: int, k: int) -> list[int]:
    """K reply positions (1-based: position i is the logit row that
    produced reply token i, the first decode dispatch's output) spread
    evenly over a reply of `reply_tokens` tokens. Position 0 (tok0,
    sampled inside the prefill dispatch) has no separately harvestable
    logit row, so the usable range is [1, reply_tokens - 1]; a 1-token
    reply audits parity only. Deterministic — the same request samples
    the same positions on every replica and every re-run."""
    hi = reply_tokens - 1
    if hi < 1 or k < 1:
        return []
    return sorted({
        1 + round(i * (hi - 1) / max(1, k - 1)) for i in range(k)
    })


def drift_fail_tolerances(kv_dtype: str) -> tuple[float, float]:
    """Default (max_abs_diff, kl) boundary between the `drift` and
    `fail` verdicts — the --audit-tol-maxdiff / --audit-tol-kl
    defaults, derived from utils.quant.roundtrip_error_stats on the
    pool's storage format so the gate's looseness is BACKED BY the
    quantizer's measured error envelope, not a magic number.

    fp pools reproduce the reference bit-for-bit, so any nonzero
    drift is already suspicious: the boundary sits one decade above
    the pass/drift tolerance class. Quantized pools legitimately
    drift: a seeded unit-normal [N, Hk, D] probe pushed through the
    pool's OWN quantizer (quantize_kv_rows — one scale per token row
    over the joint head x dim axes, exactly the write path's
    granularity) gives the format's relative rms error, and the
    boundary is that error scaled into logit units with a safety
    factor of 64 (logits accumulate many quantized inner products;
    empirically the tiny-model drift sits 1-2 decades below this
    line, and a kernel/layout bug sits well above it)."""
    if kv_dtype in (None, "bf16", "fp"):
        return 1e-2, 1e-3
    from oryx_tpu.utils import quant as quant_lib

    probe = jax.random.normal(jax.random.key(0), (256, 4, 32))
    codes, scale = quant_lib.quantize_kv_rows(probe, kv_dtype)
    err = quant_lib.dequantize_kv_rows(codes, scale) - probe
    rel = float(
        jnp.sqrt(jnp.mean(err * err)) / jnp.max(jnp.abs(probe))
    )
    rel = max(rel, 1e-6)
    return 64.0 * rel, 8.0 * rel


def logit_divergence(ref: np.ndarray, cmp: np.ndarray
                     ) -> tuple[float, float]:
    """(max_abs_diff, KL(ref || cmp)) of two logit rows, fp64 softmax
    so the KL of near-identical rows is a clean 0-ish, not fp32 noise."""
    a = np.asarray(ref, np.float64)
    b = np.asarray(cmp, np.float64)
    max_abs = float(np.max(np.abs(a - b))) if a.size else 0.0
    pa = np.exp(a - a.max())
    pa /= pa.sum()
    pb = np.exp(b - b.max())
    pb /= pb.sum()
    tiny = np.finfo(np.float64).tiny
    kl = float(np.sum(pa * (np.log(pa + tiny) - np.log(pb + tiny))))
    return max_abs, max(0.0, kl)


def top_logit_table(row: np.ndarray, k: int = TOP_LOGITS) -> list:
    """[[token_id, logit], ...] of the row's top-k — the forensic
    record's 'what did the model actually prefer' table."""
    row = np.asarray(row, np.float64)
    idx = np.argsort(row)[::-1][:k]
    return [[int(i), round(float(row[i]), 6)] for i in idx]


class OutputAuditor:
    """Seeded shadow-parity auditor around one pipeline (see module
    docstring). Constructed unconditionally by the scheduler — with
    sample_every=0 it only pre-registers its metric families (ladders
    render at zero) and every observe/run call is a no-op."""

    def __init__(
        self,
        pipe,
        *,
        page_size: int,
        max_ctx: int,
        sample_every: int = 0,
        positions: int = 8,
        abs_tol: float = 1e-3,
        kl_tol: float = 1e-4,
        keep: int = 64,
        max_pending: int = 8,
        metrics: ServingMetrics | None = None,
        request_log: request_log_lib.RequestLog | None = None,
        anomaly=None,
        engine_label: str = "continuous",
        replica_id: str | None = None,
        kv_dtype: str = "bf16",
        fail_abs_tol: float | None = None,
        fail_kl_tol: float | None = None,
    ):
        if not isinstance(sample_every, int) or sample_every < 0:
            raise ValueError(
                "audit_sample_every must be a non-negative integer "
                f"(audit every Nth finished request; 0 = off), got "
                f"{sample_every!r}"
            )
        self.pipe = pipe
        self.cfg = pipe.cfg
        self.page_size = page_size
        self.max_ctx = max_ctx
        self.max_pages = max_ctx // page_size
        self.sample_every = sample_every
        self.positions = max(1, int(positions))
        self.abs_tol = float(abs_tol)
        self.kl_tol = float(kl_tol)
        # The drift-vs-fail boundary (--audit-tol-maxdiff /
        # --audit-tol-kl): drift above THESE lines is a `fail`
        # verdict, not just `drift`. Defaults derive from the pool
        # format's measured round-trip error (drift_fail_tolerances).
        d_abs, d_kl = drift_fail_tolerances(kv_dtype)
        self.fail_abs_tol = (
            float(fail_abs_tol) if fail_abs_tol is not None else d_abs
        )
        self.fail_kl_tol = (
            float(fail_kl_tol) if fail_kl_tol is not None else d_kl
        )
        self.metrics = metrics or ServingMetrics()
        self.request_log = request_log
        self.anomaly = anomaly
        self.engine_label = engine_label
        self.replica_id = replica_id
        # The production-config twin: a second replay under the
        # engine's own configuration when it differs from the split
        # fp XLA reference — its attention impl (e.g. the Pallas
        # ragged kernel), its pool dtype (the int8 paged pool), or
        # both. On the plain fp XLA path the reference IS the
        # production program and the drift is exactly 0 without a
        # second replay. With a QUANTIZED pool the twin's replay is
        # what must reproduce the client's bytes (the engine served
        # from the quantized pool); the fp reference's token stream
        # may legitimately diverge, and the ref-vs-twin logit drift
        # against the fail tolerances is the standing numerics gate
        # (ROADMAP item 3).
        self.kv_dtype = kv_dtype
        self.compare_quant = kv_dtype == "int8"
        self.compare_impl = (
            self.cfg.attn_impl
            if (self.cfg.attn_impl != "xla" or self.compare_quant)
            else None
        )
        # Pre-registered raw-named families: the whole audit surface
        # renders (at zero) from the first scrape, armed or not.
        reg = self.metrics.registry
        fam = reg.counter("oryx_audit_total", ("verdict",), raw_name=True)
        for verdict in ("pass", "drift", "fail"):
            fam.labels(verdict=verdict)
        reg.counter("oryx_audit_sampled_total", raw_name=True)
        reg.counter(
            "oryx_audit_skipped_total", ("reason",), raw_name=True
        ).labels(reason="sampled")
        reg.counter("oryx_audit_dropped_total", raw_name=True)
        reg.counter("oryx_audit_replayed_tokens_total", raw_name=True)
        reg.gauge("oryx_audit_pending", raw_name=True)
        reg.histogram(
            "oryx_audit_logit_max_abs_diff", AUDIT_DIFF_BUCKETS,
            raw_name=True,
        )
        reg.histogram("oryx_audit_kl", AUDIT_KL_BUCKETS, raw_name=True)
        # Engine-thread-owned capture state.
        self._finished_seen = 0  # thread-owned: engine
        self._pending: deque[dict[str, Any]] = deque()  # thread-owned: engine
        self.max_pending = max(1, int(max_pending))
        self._kv = None  # thread-owned: engine (lazy private pool)
        self._kv_prod = None  # thread-owned: engine (quantized twin pool)
        self._bt = None  # thread-owned: engine
        # Ring + monotone verdict counts, shared with debug threads.
        self._lock = named_lock("audit._lock")
        self._ring: deque[dict[str, Any]] = deque(  # guarded-by: _lock
            maxlen=max(1, int(keep))
        )
        self._total = 0  # guarded-by: _lock
        self._verdicts = {  # guarded-by: _lock
            "pass": 0, "drift": 0, "fail": 0,
        }

    # ---- sampling (engine thread, at a request's finish) -----------------

    def observe_finished(self, req) -> None:
        """Every-Nth sampler over successfully FINISHED requests (the
        scheduler's `_finish` calls this before the slot clears, while
        `req.embeds` is still alive). Captures a self-contained replay
        job — host copies only, nothing that pins engine state."""
        if not self.sample_every:
            return
        self._finished_seen += 1
        if self._finished_seen % self.sample_every:
            return
        self.metrics.registry.counter(
            "oryx_audit_sampled_total", raw_name=True
        ).inc()
        if float(getattr(req, "temp", 0.0) or 0.0) != 0.0:
            self.metrics.registry.counter(
                "oryx_audit_skipped_total", ("reason",), raw_name=True
            ).labels(reason="sampled").inc()
            return
        if len(self._pending) >= self.max_pending:
            # Bounded backlog: under sustained saturation the engine
            # never idles, so jobs would otherwise accumulate without
            # limit. Dropping the OLDEST keeps the audits that will
            # run closest to the traffic that produced them.
            self._pending.popleft()
            self.metrics.registry.counter(
                "oryx_audit_dropped_total", raw_name=True
            ).inc()
        embeds = (
            req.embeds_np if req.embeds_np is not None
            else np.asarray(req.embeds)
        )
        usage = req.handle.usage or (req.length, len(req.emitted))
        self._pending.append({
            "request_id": req.trace.id,
            "embeds": embeds,  # [1, T, H] host copy
            "length": int(req.length),
            "max_new": int(req.max_new),
            "seed": int(req.sampling.get("seed") or 0),
            "emitted": list(req.emitted),
            "completion": int(usage[1]),
            "finish_reason": req.handle.finish_reason,
            "evictions": int(req.evictions),
        })
        self._update_pending_gauge()

    def _update_pending_gauge(self) -> None:
        self.metrics.registry.gauge(
            "oryx_audit_pending", raw_name=True
        ).set(len(self._pending))

    def pending(self) -> int:
        """Jobs waiting for an idle point (engine thread's idle check;
        also read — benignly racily — by /debug/audit)."""
        return len(self._pending)

    # ---- replay (engine thread, idle points only) ------------------------

    def _ensure_pool(self):
        """Lazily build the PRIVATE replay pool: one request's worth of
        pages + an identity block table. Never touches the serving
        allocator — audit capacity is budgeted HBM, not contended HBM."""
        if self._kv is None:
            self._kv = qwen2.init_paged_kv_cache(
                self.cfg.llm, self.max_pages, self.page_size,
                dtype=oryx.compute_dtype(self.cfg),
            )
            self._bt = jnp.asarray(
                np.arange(self.max_pages, dtype=np.int32)[None]
            )
        if self.compare_quant and self._kv_prod is None:
            # The production twin's pool: same geometry, the engine's
            # quantized wire format — what makes the twin's replay an
            # honest reproduction of what the client was served from.
            self._kv_prod = qwen2.init_paged_kv_cache(
                self.cfg.llm, self.max_pages, self.page_size,
                dtype=oryx.compute_dtype(self.cfg),
                kv_dtype=self.kv_dtype,
            )

    def _replay(self, job: dict[str, Any], attn_impl: str,
                want_positions: list[int], pool: str = "_kv",
                force: list[int] | None = None):
        """One cold replay of `job` through the split path under
        `attn_impl`: paged_prefill seeded with the request's own key0,
        then one audit_decode_step per reply token, mirroring the
        host consume loop of `scheduler._advance` (EOS -> "stop",
        max_new -> "length"). `pool` names the private pool attr the
        replay dispatches donate ("_kv" = the fp reference pool,
        "_kv_prod" = the quantized production twin). Returns (emitted
        tokens, finish reason or None at the divergence-guard cap,
        {position: logits [V]}, replayed token count, first index
        where the model's own greedy choice departed from `force`).

        force: TEACHER-FORCED mode (the quantized-pool reference
        replay): feed this token stream — the client's live reply —
        instead of the replay's own samples, so every recorded logit
        row is computed in the SAME context the production twin
        decodes in. Without it, the fp reference's greedy stream can
        legitimately depart from a drifting quantized stream, and
        rows past the departure would compare logits of DIFFERENT
        prefixes — an apples-to-oranges diff that explodes for a
        structural reason, not a numeric one."""
        self._ensure_pool()
        gen = self.cfg.generation
        eos = gen.eos_token_id
        dtype = oryx.compute_dtype(self.cfg)
        L = job["length"]
        emb = job["embeds"]
        width = round_up_bucket(emb.shape[1])
        if width > emb.shape[1]:
            emb = np.concatenate([
                emb,
                np.zeros(
                    (1, width - emb.shape[1], emb.shape[2]), emb.dtype
                ),
            ], axis=1)
        key0 = jax.random.key(job["seed"])
        B1 = np.newaxis
        with self.pipe._mesh_scope():
            kv, tok0, key = generate_lib.paged_prefill(
                self.pipe.params["llm"], self.cfg.llm,
                jnp.asarray(emb),
                jnp.asarray([L], np.int32),
                self._bt,
                getattr(self, pool),
                jnp.asarray([0], np.int32),
                key0[B1],
                jnp.zeros((1,), np.float32),  # greedy-only audits
                jnp.ones((1,), np.float32),
                jnp.zeros((1,), np.int32),
                attn_impl=attn_impl,
                compute_dtype=dtype,
            )
        setattr(self, pool, kv)
        want = set(want_positions)
        # Divergence guard: one token past the live reply is enough to
        # expose any mismatch; without the cap a diverged replay could
        # run to max_new.
        target = len(job["emitted"]) + 1
        t = int(np.asarray(tok0)[0])
        choice_div = -1
        if force is not None and force:
            if t != force[0]:
                choice_div = 0
            t = force[0]
        cur_len = L
        emitted: list[int] = []
        reason: str | None = None
        rows: dict[int, np.ndarray] = {}
        pos = 0
        steps = 0
        while True:
            if t == eos:
                reason = "stop"
                break
            emitted.append(t)
            if len(emitted) >= job["max_new"]:
                reason = "length"
                break
            if len(emitted) >= target:
                break
            with self.pipe._mesh_scope():
                kv, nxt, lg, key = audit_decode_step(
                    self.pipe.params["llm"], self.cfg.llm,
                    getattr(self, pool), self._bt,
                    jnp.asarray([t], np.int32),
                    jnp.asarray([cur_len], np.int32),
                    key,
                    jnp.zeros((1,), np.float32),
                    jnp.ones((1,), np.float32),
                    jnp.zeros((1,), np.int32),
                    attn_impl=attn_impl,
                    compute_dtype=dtype,
                )
            setattr(self, pool, kv)
            steps += 1
            cur_len += 1
            pos += 1
            if pos in want:
                rows[pos] = np.asarray(lg[0])
            t = int(np.asarray(nxt)[0])
            if force is not None:
                idx = len(emitted)
                if idx < len(force):
                    if t != force[idx] and choice_div < 0:
                        choice_div = idx
                    t = force[idx]
        return emitted, reason, rows, steps, choice_div

    def run_one(self) -> bool:
        """Run ONE queued audit to completion (engine thread, idle
        point). Returns whether a job ran. A replay that itself raises
        is contained into a `fail` verdict — a broken audit path must
        page, never kill the engine loop it rides."""
        if not self._pending:
            return False
        job = self._pending.popleft()
        self._update_pending_gauge()
        t0 = time.monotonic()
        try:
            record = self._audit_one(job)
        # fault-boundary: a failed replay is itself an audit FAILURE
        # verdict, never an engine-loop exception
        except Exception as e:
            # The replay donates the private pools into its dispatches:
            # a raise mid-dispatch may have invalidated them. Drop both
            # so the NEXT audit rebuilds from fresh buffers instead of
            # converting one transient into a permanent fail loop.
            self._kv = None
            self._kv_prod = None
            self._bt = None
            record = {
                "request_id": job["request_id"],
                "verdict": "fail",
                "error": f"{type(e).__name__}: {e}",
                "first_divergence": -1,
                "replayed_tokens": 0,
                "positions": [],
                "logit_max_abs_diff": None,
                "kl": None,
                "evictions": job["evictions"],
                "live_finish_reason": job["finish_reason"],
                "replay_finish_reason": None,
                "live_tail": job["emitted"][-TAIL_TOKENS:],
                "replay_tail": [],
            }
        record["audit_s"] = round(time.monotonic() - t0, 6)
        self._publish(record)
        return True

    def _audit_one(self, job: dict[str, Any]) -> dict[str, Any]:
        live = job["emitted"]
        want = sample_positions(len(live), self.positions)
        # Quantized pool: the fp reference replays TEACHER-FORCED on
        # the live stream, so its logit rows share the twin's context
        # at every compared position (see _replay's force doc); its
        # own greedy choices vs the live stream land in choice_div as
        # information, not a verdict.
        ref_emitted, ref_reason, ref_rows, ref_steps, ref_choice_div = (
            self._replay(
                job, "xla", want,
                force=live if self.compare_quant else None,
            )
        )
        replayed = ref_steps + 1  # tok0 rides the prefill dispatch
        cmp_emitted, cmp_reason = ref_emitted, ref_reason
        cmp_rows = ref_rows
        if self.compare_impl is not None:
            cmp_emitted, cmp_reason, cmp_rows, cmp_steps, _ = (
                self._replay(
                    job, self.compare_impl, want,
                    pool="_kv_prod" if self.compare_quant else "_kv",
                )
            )
            replayed += cmp_steps + 1
        # Byte parity: the replayed stream must reproduce the client's
        # byte-for-byte. A replay that stopped early (EOS before the
        # live stream's length) diverged at its stop point — and a
        # live stream that stopped on EOS (completion counts one past
        # the appended tokens, `scheduler._finish` semantics) pins the
        # replay's STOP DECISION too: the replay must terminate on EOS
        # at exactly the live length, or the one-past token diverged.
        eos_finish = job["completion"] > len(live)

        def diverges(emitted: list[int], reason: str | None) -> int:
            for i, t in enumerate(live):
                if i >= len(emitted) or emitted[i] != t:
                    return i
            if eos_finish and (
                reason != "stop" or len(emitted) != len(live)
            ):
                return len(live)
            return -1

        # Byte parity: on a QUANTIZED pool the client's bytes came off
        # the quantized program, so the production TWIN is what must
        # reproduce them exactly (a twin mismatch is nondeterminism —
        # a hard fail); the fp reference's stream may legitimately
        # pick a different argmax under drift, which is recorded
        # informationally, not failed. On the fp path the reference
        # and the twin are bit-identical programs and either mismatch
        # fails, exactly as before.
        if self.compare_quant:
            # The forced reference's own stream is the live stream by
            # construction; parity is judged against the production
            # twin, and the fp argmax departures are informational.
            ref_div = ref_choice_div
            first_div = diverges(cmp_emitted, cmp_reason)
        else:
            ref_div = diverges(ref_emitted, ref_reason)
            first_div = ref_div
            if first_div < 0 and self.compare_impl is not None:
                first_div = diverges(cmp_emitted, cmp_reason)
        # Logit drift across the sampled positions (reference vs the
        # production-config twin; identical programs -> exact zeros;
        # a quantized twin drifts within the fail tolerances — the
        # roundtrip_error_stats-derived boundary — or FAILS above
        # them).
        max_abs = 0.0
        max_kl = 0.0
        worst = None
        finite = True
        for p in want:
            a, b = ref_rows.get(p), cmp_rows.get(p)
            if a is None or b is None:
                continue
            if not (np.isfinite(a).all() and np.isfinite(b).all()):
                finite = False
            d_abs, d_kl = logit_divergence(a, b)
            if worst is None or d_abs > max_abs:
                worst = p
            max_abs = max(max_abs, d_abs)
            max_kl = max(max_kl, d_kl)
        if (
            first_div >= 0 or not finite
            or max_abs > self.fail_abs_tol or max_kl > self.fail_kl_tol
        ):
            verdict = "fail"
        elif max_abs > self.abs_tol or max_kl > self.kl_tol:
            verdict = "drift"
        else:
            verdict = "pass"
        record: dict[str, Any] = {
            "request_id": job["request_id"],
            "verdict": verdict,
            "first_divergence": first_div,
            "replayed_tokens": replayed,
            "positions": want,
            "logit_max_abs_diff": round(max_abs, 9),
            "kl": round(max_kl, 9),
            "evictions": job["evictions"],
            "live_finish_reason": job["finish_reason"],
            "replay_finish_reason": ref_reason,
            "live_tail": live[-TAIL_TOKENS:],
            "replay_tail": ref_emitted[-TAIL_TOKENS:],
        }
        if self.compare_quant and ref_div >= 0:
            # Informational: where the fp reference's greedy stream
            # departed from the quantized serving stream (expected
            # under drift; the tolerance gate above is the judge).
            record["ref_first_divergence"] = ref_div
        if worst is not None:
            record["top_logits"] = {
                "position": worst,
                "reference": top_logit_table(ref_rows[worst]),
                "production": top_logit_table(cmp_rows[worst]),
            }
        return record

    def _publish(self, record: dict[str, Any]) -> None:
        """Ring + counters + histograms + wide event + anomaly feed —
        the one place a verdict becomes observable, so the /debug ring
        and oryx_audit_total can never drift apart."""
        verdict = record["verdict"]
        record.setdefault("ts_unix_s", time.time())
        with self._lock:
            idx = self._total
            record["index"] = idx
            self._ring.append(record)
            self._total += 1
            self._verdicts[verdict] = self._verdicts.get(verdict, 0) + 1
        reg = self.metrics.registry
        reg.counter(
            "oryx_audit_total", ("verdict",), raw_name=True
        ).labels(verdict=verdict).inc()
        reg.counter(
            "oryx_audit_replayed_tokens_total", raw_name=True
        ).inc(record.get("replayed_tokens") or 0)
        if record.get("logit_max_abs_diff") is not None:
            reg.histogram(
                "oryx_audit_logit_max_abs_diff", AUDIT_DIFF_BUCKETS,
                raw_name=True,
            ).observe(record["logit_max_abs_diff"])
        if record.get("kl") is not None:
            reg.histogram(
                "oryx_audit_kl", AUDIT_KL_BUCKETS, raw_name=True,
            ).observe(record["kl"])
        if self.request_log is not None:
            self.request_log.append(request_log_lib.build_audit_event(
                request_id=record["request_id"],
                engine=self.engine_label,
                replica=self.replica_id,
                verdict=verdict,
                first_divergence=record["first_divergence"],
                replayed_tokens=record["replayed_tokens"],
                positions_checked=len(record.get("positions") or []),
                logit_max_abs_diff=record.get("logit_max_abs_diff"),
                kl=record.get("kl"),
                evictions=record.get("evictions", 0),
                audit_index=idx,
            ))
        if self.anomaly is not None:
            self.anomaly.observe_audit(
                verdict, request_id=record["request_id"],
            )
        if verdict != "pass":
            _LOG.warning(
                "output audit %s for request %s (first_divergence=%s "
                "max_abs=%s kl=%s)", verdict, record["request_id"],
                record["first_divergence"],
                record.get("logit_max_abs_diff"), record.get("kl"),
            )
        else:
            _LOG.info(
                "output audit pass for request %s (%d tokens replayed)",
                record["request_id"], record.get("replayed_tokens") or 0,
            )

    # ---- readers ---------------------------------------------------------

    def to_dict(self, n: int | None = None) -> dict[str, Any]:
        """The GET /debug/audit body (minus the engine label the server
        adds): monotone totals that reconcile EXACTLY with
        oryx_audit_total, the pending/dropped view, and the newest-first
        record ring."""
        with self._lock:
            records = list(self._ring)
            total = self._total
            verdicts = dict(self._verdicts)
        if n is not None:
            records = records[-max(0, int(n)):]
        reg = self.metrics.registry
        return {
            "sample_every": self.sample_every,
            "total": total,
            "verdicts": verdicts,
            "pending": len(self._pending),
            "sampled": reg.get("oryx_audit_sampled_total", raw_name=True),
            "dropped": reg.get("oryx_audit_dropped_total", raw_name=True),
            "records": [dict(r) for r in reversed(records)],
        }
