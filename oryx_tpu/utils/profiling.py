"""Tracing / profiling: jax.profiler glue + a loop's phases.

Reference parity: the reference has no first-class tracing — ad-hoc torch
profiler + DeepSpeed wall-clock timers / flops_profiler toggles
(SURVEY.md §5 "Tracing / profiling"). Here profiling is first-class:
Perfetto/TensorBoard traces via jax.profiler, the engine's and the
trainer's loop phases as named host events and exclusive seconds
(PhaseClock), and device time attributed to dispatch kinds.
"""

from __future__ import annotations

import contextlib
import dataclasses
import time
from typing import Iterator

import jax


def _start_trace(logdir: str, *, host_tracer_level: int = 2) -> None:
    """jax.profiler.start_trace at a host tracer level; one helper so
    every capture path (the trace() context manager, the continuous
    DeviceTimeSampler) shares it."""
    opts = jax.profiler.ProfileOptions()
    opts.host_tracer_level = host_tracer_level
    jax.profiler.start_trace(logdir, profiler_options=opts)


@contextlib.contextmanager
def trace(logdir: str, *, host_tracer_level: int = 2) -> Iterator[None]:
    """Capture a trace viewable in TensorBoard/Perfetto."""
    _start_trace(logdir, host_tracer_level=host_tracer_level)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


class PhaseClock:
    """Names where ONE thread's loop spends its wall time. Every phase
    is written twice: as a `jax.profiler.TraceAnnotation`
    `<prefix>.<name>` — a host event in the profiler's own trace, on
    the device trace's clock, whenever a capture is running (an atomic
    load when none is) — and as EXCLUSIVE seconds handed to
    `record(name, seconds)`: entering a phase bills the time since the
    last boundary to the phase that was running (`base` when none is
    open), so a nested phase's seconds are not counted again in its
    parent and the recorded seconds of a window add up to its wall
    time. `record` is called once a boundary, so the seconds of one
    call are one uninterrupted stretch of one phase.

    `kind` places the one enclosing annotation, `<prefix>.host`: it
    opens when a top-level "blocked" phase returns (the device has
    drained, the host holds the pace) and closes after the next
    "dispatch" phase (the device has work again) or before the next
    blocked one. A device idle gap made of several short phases is
    then covered by one named host event instead of none. The seconds
    under it are the ones the device waited for this thread: each
    stretch billed while it is open also goes to
    `starved(name, seconds)`, where one is given.

    Bound to the thread that enters its phases (a TraceAnnotation
    cannot cross threads); a loop that restarts on a new thread makes
    a new clock."""

    def __init__(self, prefix: str, record, *, base: str, starved=None):
        self._prefix = prefix + "."
        self._record = record
        self._starved = starved
        self._open = [base]  # innermost last; base never closes
        self._t = time.perf_counter()
        self._host = None
        self._held = None

    def _switch(self) -> None:
        now = time.perf_counter()
        name, seconds = self._open[-1], now - self._t
        self._record(name, seconds)
        if self._host is not None and self._starved is not None:
            self._starved(name, seconds)
        self._t = now

    def _end_host(self) -> None:
        if self._host is not None:
            self._host.__exit__(None, None, None)
            self._host = None

    @contextlib.contextmanager
    def phase(self, name: str, kind: str = "host") -> Iterator[None]:
        """kind: "host" (the host works), "dispatch" (host work that
        ends with a device program enqueued), "blocked" (the host
        waits: for the device, for data, for a request) or "wait" (the
        host waits for a program with another enqueued behind it: the
        device has work when the wait returns, so no `host` event
        opens)."""
        self._switch()  # before the host event ends: its seconds starved
        if kind == "blocked":
            self._end_host()
        self._open.append(name)
        try:
            with jax.profiler.TraceAnnotation(self._prefix + name):
                yield
        finally:
            self._switch()
            self._open.pop()
            if kind == "dispatch":
                self._end_host()
            elif kind == "blocked" and len(self._open) == 1:
                self._host = jax.profiler.TraceAnnotation(
                    self._prefix + "host"
                )
                self._host.__enter__()

    def hold(self, name: str) -> None:
        """Enter the blocked phase `name` and stay in it however often
        the loop comes round: the first call opens it (ONE annotation
        for the whole stretch), a later one bills the seconds so far,
        so a reader of the counter is never further behind than the
        loop's own period. `release()` ends it."""
        if self._held is None:
            self._held = self.phase(name, "blocked")
            self._held.__enter__()
        else:
            self._switch()

    def release(self) -> None:
        """End the held phase, if one is open."""
        if self._held is not None:
            held, self._held = self._held, None
            held.__exit__(None, None, None)

    def close(self) -> None:
        """The loop ends: a held phase is billed, no annotation of the
        clock's own stays open."""
        self.release()
        self._end_host()


# The device's work by the program's own names: the top-level layers
# of every step program, each entered with `jax.named_scope` where the
# layer's work is (docs/OBSERVABILITY.md "Memory & device time" has
# what each holds), and the scopes inside a layer. A capture's
# `XLA Ops` events carry them in their op_name; `scope_table` reads
# them back. Metadata on the HLO: nothing at run time, and there
# whether or not anyone traces.
DEVICE_SCOPES = (
    "embed", "attn", "ffn", "moe", "mixer", "head", "sample", "vision",
    "loss", "optimizer_update", "nonfinite_guard",
    # a layer's (or a period's) weights cut out of the stacked arrays by
    # the model's own code (`_window_layers`, `_hybrid_layers.at`): what
    # a scan does with its xs is jax's and carries no scope.
    "stack",
)
DEVICE_SUBSCOPES = (
    "attn_global", "attn_window", "mla", "dsa_index", "dsa_select",
    "dsa_attend", "dense_ffn", "moe_routed", "moe_shared", "mamba",
    "short_conv", "ssm_scan", "ssm_step", "conv_handover",
    "vocab_parallel", "mamba2", "ssd_chunk", "ssd_step", "moe_latent",
)


def scope_table(planes) -> dict:
    """{program: {scope path: [self seconds, count]}} of one parsed
    capture (xplane.scope_seconds over this program's vocabulary); {}
    where it holds no device plane."""
    from oryx_tpu.utils import xplane

    return xplane.scope_seconds(planes, DEVICE_SCOPES, DEVICE_SUBSCOPES)


@dataclasses.dataclass
class OpProfile:
    """Result of op_profile: ranked (name, scope path, total_ms) plus
    provenance. `source` distinguishes real device op time
    ("tpu_xla_ops") from the host-event fallback ("host_fallback"),
    which measures python/dispatch and must never be mistaken for
    device time when optimizing. The name is the compiler's and changes
    with every compile; the scope path (xplane.scope_of: "attn/mla",
    "unscoped", "" for a host event) is the program's and does not.
    `scopes` is the capture's `scope_table`."""

    top: list[tuple[str, str, float]]
    source: str
    xplane_path: str
    plane_names: list[str]
    scopes: dict = dataclasses.field(default_factory=dict)


def op_profile(
    fn, *args, trace_dir: str, steps: int = 3, top_n: int = 25
) -> OpProfile:
    """Run `fn(*args)` `steps` times under a trace and return an
    OpProfile: top ops by total device time, each with its scope path,
    and the device's time by scope path (`.scopes`) — self-contained:
    the written xplane.pb is decoded by utils/xplane.py, no TensorBoard
    tooling needed. On TPU this reads the 'XLA Ops' device lines; on CPU
    it falls back to host events (module aggregates excluded), flagged
    via `.source`, and `.scopes` is empty.

    fn should already be compiled (call it once beforehand) — compile
    time inside the trace would swamp the profile."""
    from oryx_tpu.utils import xplane

    with trace(trace_dir):
        out = None
        for _ in range(steps):
            out = fn(*args)
        jax.block_until_ready(out)
    files = xplane.find_xplane_files(trace_dir)
    if not files:
        raise RuntimeError(f"no xplane.pb written under {trace_dir}")
    planes = xplane.parse_xspace(files[-1])
    names = [p.name for p in planes]
    device = xplane.top_ops(
        planes, n=top_n, plane_filter="TPU", line_filter="Ops"
    )
    if device:
        op_names = {
            ev.name: ev.op_name
            for p in planes if "TPU" in p.name
            for ln in p.lines if "Ops" in ln.name for ev in ln.events
        }
        return OpProfile(
            [(name, xplane.scope_of(
                op_names[name], DEVICE_SCOPES, DEVICE_SUBSCOPES), ms)
             for name, ms in device],
            "tpu_xla_ops", files[-1], names, scope_table(planes),
        )
    host = [
        xplane.Plane(p.name, [l for l in p.lines if "Modules" not in l.name])
        for p in planes
    ]
    return OpProfile(
        [(name, "", ms) for name, ms in xplane.top_ops(host, n=top_n)],
        "host_fallback", files[-1], names,
    )


# ---------------------------------------------------------------------------
# Continuous device-time attribution (docs/OBSERVABILITY.md "Memory &
# device time")
# ---------------------------------------------------------------------------

# The dispatch kinds the serving engine emits (utils/timeline.py) plus
# the "other" bucket for capture time outside every window.
DISPATCH_KINDS = (
    "ragged", "spec", "prefill", "decode", "block", "other",
)


def attribute_capture(
    planes, windows: list[tuple[str, int, int]],
    session_end_ns: int = 0,
) -> dict:
    """Pure attribution of one parsed capture onto labeled host
    windows: per-label busy microseconds (interval union on the
    busiest execution line, clipped per window — in-window time can
    never exceed the window), plus "other" (capture busy time outside
    every window) and the provenance source. TPU device planes ('XLA
    Ops' lines) are preferred; without them the host-event fallback
    measures python/dispatch time ('Modules' aggregate lines excluded)
    — same convention as op_profile, and the source says which you
    got. Unit-tested against synthetic planes (tests/test_device_time
    .py); DeviceTimeSampler feeds it live captures."""
    from oryx_tpu.utils import xplane

    # Precompute the busiest line's merged spans ONCE; each window is
    # then a cheap clip — an on-demand capture may carry hundreds of
    # windows and this runs on the engine thread.
    spans = xplane.busiest_line_spans(
        planes, plane_filter="TPU", line_filter="Ops",
        session_end_ns=session_end_ns,
    )
    source = "tpu_xla_ops"
    if not spans:
        spans = xplane.busiest_line_spans(
            planes, line_exclude="Modules",
            session_end_ns=session_end_ns, event_exclude="oryx.",
        )
        source = "host_fallback"
    out: dict = {"by_kind_us": {}, "other_us": 0, "source": source}
    windowed = 0
    for label, t0, t1 in windows:
        busy = xplane.clipped_us(spans, t0, t1)
        out["by_kind_us"][label] = out["by_kind_us"].get(label, 0) + busy
        windowed += busy
    total_busy = sum(e - s for s, e in spans) // 1000
    out["other_us"] = max(0, total_busy - windowed)
    return out


class DeviceTimeSampler:
    """Always-on sampled device-time attributor for the serving engine.

    Every N engine steps (``every``; 0 = off) the scheduler brackets
    ONE dispatch in a ``jax.profiler`` capture to a private temp dir,
    and the capture's busy time inside the dispatch window lands on
    ``oryx_device_time_seconds_total{kind=}`` (the window's dispatch
    kind; capture busy time outside the window goes to kind="other")
    with the sampled wall window on
    ``oryx_profile_sampled_wall_seconds_total{kind=}`` — so
    device/wall per kind is a ratio of two counters scraped together.
    The same begin/finish machinery serves the on-demand
    ``GET /debug/profile?steps=K`` capture (a multi-window capture
    returning the Perfetto-loadable Chrome trace).

    Failure contract (the satellite bar): a capture that cannot start,
    stop, parse or attribute increments
    ``oryx_profile_capture_errors_total{stage=}`` and the engine step
    proceeds untouched — sampling may lose a sample, never a token.
    Profiling never alters the computation: the dispatch itself is
    byte-identical sampled or not (gated by tests/test_device_time.py).

    Engine-thread-owned; one sampler per engine, but jax's profiler is
    process-global — a concurrent capture elsewhere in the process
    surfaces as a counted stage="start" error, not a crash."""

    def __init__(self, registry=None, *, every: int = 0):
        self.every = max(0, int(every))
        self._step = 0  # thread-owned: engine
        self._dir: str | None = None  # thread-owned: engine
        self._dev = self._wall = self._errs = self._caps = None
        if registry is not None:
            self._dev = registry.counter(
                "oryx_device_time_seconds_total", ("kind",),
                raw_name=True,
            )
            self._wall = registry.counter(
                "oryx_profile_sampled_wall_seconds_total", ("kind",),
                raw_name=True,
            )
            self._errs = registry.counter(
                "oryx_profile_capture_errors_total", ("stage",),
                raw_name=True,
            )
            self._caps = registry.counter(
                "oryx_profile_captures_total", raw_name=True
            )

    def _err(self, stage: str) -> None:
        if self._errs is not None:
            self._errs.labels(stage=stage).inc()

    def tick(self) -> bool:
        """Advance the engine-step counter; True when THIS step is due
        a sample (every Nth step; never with every=0)."""
        due = self.due_next()
        self._step += 1
        return due

    def due_next(self) -> bool:
        """Whether the next tick() will be due, without advancing: a
        loop that keeps a dispatch in flight reads it out first."""
        return self.every > 0 and (self._step + 1) % self.every == 0

    def begin(self) -> bool:
        """Start one capture into a fresh temp dir. False (with the
        labeled error counted) when the profiler cannot start —
        callers then run the step unprofiled."""
        import shutil
        import tempfile

        d = tempfile.mkdtemp(prefix="oryx-devtime-")
        try:
            _start_trace(d)
        except Exception:
            self._err("start")
            shutil.rmtree(d, ignore_errors=True)
            return False
        self._dir = d
        return True

    def abort(self) -> None:
        """Discard an in-flight capture (the dispatch-failure
        containment path): stop the process-global profiler if this
        sampler started it and reclaim the temp dir, reporting
        nothing. Without this, a dispatch exception between begin()
        and end() would leave the profiler running forever and every
        later capture failing at start."""
        import shutil

        d, self._dir = self._dir, None
        if d is None:
            return
        try:
            jax.profiler.stop_trace()
        except Exception:
            self._err("stop")
        shutil.rmtree(d, ignore_errors=True)

    def _stop_and_parse(self):
        """Stop the in-flight capture and parse its xplane file;
        returns (planes, session_end_ns) or None with the stage
        counted. Always reclaims the temp dir."""
        import shutil

        from oryx_tpu.utils import trace as trace_lib
        from oryx_tpu.utils import xplane

        d, self._dir = self._dir, None
        try:
            jax.profiler.stop_trace()
            end_ns = trace_lib.now_ns()
        except Exception:
            self._err("stop")
            shutil.rmtree(d, ignore_errors=True)
            return None
        try:
            files = xplane.find_xplane_files(d)
            if not files:
                raise RuntimeError(f"no xplane.pb written under {d}")
            planes = xplane.parse_xspace(files[-1])
        except Exception:
            self._err("parse")
            return None
        finally:
            shutil.rmtree(d, ignore_errors=True)
        return planes, end_ns

    def _credit(self, att: dict, windows) -> None:
        if self._dev is None:
            return
        for kind, us in att["by_kind_us"].items():
            if us:
                self._dev.labels(kind=kind).inc(us / 1e6)
        if att["other_us"]:
            self._dev.labels(kind="other").inc(att["other_us"] / 1e6)
        for kind, t0, t1 in windows:
            self._wall.labels(kind=kind).inc(max(0, t1 - t0) / 1e9)
        if self._caps is not None:
            self._caps.inc()

    def end(self, kind: str, t0_ns: int, t1_ns: int) -> int | None:
        """Close a per-step sample around one dispatch window: counters
        fed, temp dir reclaimed; returns the window's device
        microseconds (the timeline record's device_us field) or None
        on a counted failure."""
        parsed = self._stop_and_parse()
        if parsed is None:
            return None
        planes, end_ns = parsed
        try:
            att = attribute_capture(
                planes, [(kind, t0_ns, t1_ns)], session_end_ns=end_ns
            )
        except Exception:
            self._err("attribute")
            return None
        self._credit(att, [(kind, t0_ns, t1_ns)])
        return att["by_kind_us"].get(kind, 0)

    def finish_capture(self, windows: list[tuple[str, int, int]]) -> dict:
        """Close an on-demand multi-step capture: the /debug/profile
        response — Perfetto-loadable Chrome trace + per-kind
        attribution over the captured dispatch windows + the device's
        time by scope path (`scope_table`). Errors come
        back as {"error": ...} (and the stage counter), never raised
        into the engine loop."""
        from oryx_tpu.utils import xplane

        parsed = self._stop_and_parse()
        if parsed is None:
            return {"error": "profile capture failed (see "
                    "oryx_profile_capture_errors_total)"}
        planes, end_ns = parsed
        try:
            att = attribute_capture(planes, windows,
                                    session_end_ns=end_ns)
            body = xplane.chrome_trace(planes)
            scopes = scope_table(planes)
        except Exception as e:
            self._err("attribute")
            return {"error": f"profile attribution failed: "
                    f"{type(e).__name__}: {e}"}
        self._credit(att, windows)
        body["steps"] = len(windows)
        body["device_time_us"] = att["by_kind_us"]
        body["other_us"] = att["other_us"]
        body["source"] = att["source"]
        # {program: {scope path: [self seconds, count]}}; {} off a TPU.
        body["scope_seconds"] = scopes
        return body
