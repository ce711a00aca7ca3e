"""Per-step engine timeline: a bounded lock-free ring of step records.

The metrics layer answers "how is the system doing on average" and the
tracer answers "why was THIS request slow"; neither answers "what was
the ENGINE doing, step by step, when the loadgen knee moved". This
module is that third view — a flight data recorder for the engine
loop: one fixed-shape record per device dispatch (step wall time,
dispatch kind, packed rows, live slots, accepted tokens, queue depth,
free pages, degraded mode), kept in a bounded ring and served at
``GET /debug/timeline?n=`` (serve/api_server.py) plus snapshotted into
loadgen per-stage reports (scripts/loadgen.py). Knee diagnosis becomes
"read the timeline at the knee stage" instead of inferring engine
state from counter deltas.

Dependency-free stdlib, like utils/trace.py.

Concurrency model: the ring is single-writer (the engine thread owns
``record``; the scheduler calls it from its dispatch-accounting path)
and lock-free by design — readers (debug endpoints, loadgen) take
best-effort snapshots without ever making the engine hot path wait on
a reader. Records are immutable dicts swapped into the ring wholesale
(one reference assignment), so a reader can observe a slightly stale
ring but never a torn record. The per-kind counters are cumulative
since construction, so dispatch-kind reconciliation against
``oryx_serving_dispatches_total`` deltas works over ANY window — it
never depends on the ring being deep enough to hold the window.
"""

from __future__ import annotations

import time
from typing import Any

# The fixed record shape: every record carries exactly these keys (the
# /debug/timeline consumers and the loadgen snapshot depend on it).
STEP_RECORD_KEYS = (
    "step",             # monotone step ordinal (1-based, never wraps)
    "ts_unix_s",        # wall-clock time the dispatch COMPLETED
    "dur_s",            # step wall time (dispatch + harvest sync); a
                        # split-engine `prefill` record has no harvest,
                        # so its dur_s is the ENQUEUE (the wait is the
                        # engine's first_token phase)
    "kind",             # ragged | spec | prefill | decode
    "rows",             # valid query rows the dispatch carried
    "live_slots",       # slots decoding during the dispatch
    "accepted_tokens",  # client-progress tokens this step (all slots)
    "queue_depth",      # admission queue depth at the step
    "free_pages",       # allocator free pages at the step
    "degraded_mode",    # degraded-ladder level at the step
    "device_us",        # device busy time inside the step window, from
                        # the sampled profiler capture bracketing this
                        # dispatch (utils/profiling.DeviceTimeSampler);
                        # null on unsampled steps
)


class StepTimeline:
    """Bounded ring of per-engine-step records (see module docstring).

    ``record`` is engine-thread-only and never blocks on readers;
    ``snapshot``/``counts_by_kind`` are safe from any thread.
    """

    def __init__(self, capacity: int = 1024):
        # Same clamp rationale as the trace flight recorder: capacity 0
        # has no useful disable semantics.
        self.capacity = max(1, int(capacity))
        self._buf: list[dict[str, Any] | None] = [None] * self.capacity
        # Monotone write counter: doubles as the step ordinal and the
        # total-steps count. Written only by the engine thread; a bare
        # int read is atomic for readers.
        self._n = 0
        # Cumulative dispatch count per kind since construction —
        # written by the engine thread only, read racily by the
        # reconciliation consumers (plain dict of ints: a reader sees
        # the value before or after one increment, never garbage).
        self._by_kind: dict[str, int] = {}

    # ---- writer (engine thread) ------------------------------------------

    def record(
        self,
        *,
        dur_s: float,
        kind: str,
        rows: int,
        live_slots: int,
        accepted_tokens: int,
        queue_depth: int,
        free_pages: int,
        degraded_mode: int,
        device_us: int | None = None,
        ts_unix_s: float | None = None,
    ) -> None:
        """Append one step record. The dict is built fresh and swapped
        into the ring in one reference assignment — readers never see a
        half-written record."""
        n = self._n + 1
        rec = {
            "step": n,
            "ts_unix_s": time.time() if ts_unix_s is None else ts_unix_s,
            "dur_s": round(float(dur_s), 6),
            "kind": kind,
            "rows": int(rows),
            "live_slots": int(live_slots),
            "accepted_tokens": int(accepted_tokens),
            "queue_depth": int(queue_depth),
            "free_pages": int(free_pages),
            "degraded_mode": int(degraded_mode),
            "device_us": None if device_us is None else int(device_us),
        }
        self._buf[(n - 1) % self.capacity] = rec
        self._by_kind[kind] = self._by_kind.get(kind, 0) + 1
        self._n = n  # publish last: a reader indexing off _n sees rec

    # ---- readers (any thread) --------------------------------------------

    @property
    def total_steps(self) -> int:
        return self._n

    def counts_by_kind(self) -> dict[str, int]:
        """Cumulative dispatch count per kind since construction.
        Deltas of this dict reconcile exactly against deltas of
        ``oryx_serving_dispatches_total{kind=}`` over the same window —
        the acceptance check scripts/check_serving_endpoints.py runs."""
        return dict(self._by_kind)

    def snapshot(self, n: int | None = None) -> list[dict[str, Any]]:
        """Newest-first copies of the last ``n`` records (all retained
        records when None). Best-effort under a concurrent writer: a
        record may be superseded between the counter read and the slot
        read, in which case the newer record is returned in its place —
        still a real, whole record."""
        end = self._n
        avail = min(end, self.capacity)
        want = avail if n is None else max(0, min(int(n), avail))
        out: list[dict[str, Any]] = []
        for i in range(want):
            rec = self._buf[(end - 1 - i) % self.capacity]
            if rec is not None:
                out.append(dict(rec))
        return out

    def to_dict(self, n: int | None = None) -> dict[str, Any]:
        """The /debug/timeline response body (minus the engine label
        the server adds)."""
        return {
            "capacity": self.capacity,
            "total_steps": self.total_steps,
            "counts_by_kind": self.counts_by_kind(),
            "records": self.snapshot(n),
        }
