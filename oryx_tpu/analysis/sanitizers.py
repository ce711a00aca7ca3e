"""Runtime sanitizers: the dynamic half of oryxlint.

Static checks catch the patterns; these catch the behaviors — in unit
tests and canary runs, on CPU, before a TPU fleet burns hours on them:

  * `recompile_watchdog()` — counts jax compilation-cache misses per
    traced function for the duration of a `with` block (via jax's own
    compilation logging, no private APIs), exports them as
    `oryx_recompiles_total{fn=...}` through the existing metrics
    registry, and raises `RecompileStormError` when any one function
    compiles more than `budget` times. A decode loop that recompiles
    per step because someone passed a fresh tuple as a static arg
    fails the test in seconds instead of showing up as a 10x TTFT
    regression.
  * `donation_guard()` — tracks the live jax arrays of one or more
    pytrees across a donating call: `assert_consumed()` proves the
    donation actually happened (an aliasing contract silently
    degrading to copies is an HBM regression), and `check(tree)`
    raises `UseAfterDonateError` naming the first deleted leaf — the
    runtime twin of the `use-after-donate` static rule.
  * `LockOrderSanitizer` — the runtime twin of the `lock-order` static
    rule. Production code creates its locks through `named_lock(name,
    kind=...)`: disarmed (the default) that returns a plain
    `threading.Lock/RLock/Condition` at the cost of one global read;
    armed (`ORYX_LOCK_SANITIZER=1`, or `lock_sanitizer()` in tests) it
    returns an instrumented wrapper that keeps a per-thread held-lock
    stack, raises `LockOrderViolation` at the acquire that inverts the
    declared order (oryx_tpu/concurrency.py), forms a cycle, or
    re-enters a non-reentrant lock, counts re-entrant acquires per
    name, and exports `oryx_lock_wait_seconds{lock=}` /
    `oryx_lock_hold_seconds{lock=}` histograms through a bound
    Registry. `hot_dispatch(name)` flags a device dispatch entered
    while holding ANY instrumented lock.
  * `RaceDetector` — a lightweight LockSet/Eraser-style happens-before
    race detector over the `# guarded-by:` / `# thread-owned:`
    annotated fields (the SAME source annotations the static rules
    read, via analysis.core.field_annotations). Armed, it installs
    data descriptors on the annotated classes: per-field last-accessor
    tracking with ownership HANDOFF (A A B B is a legal transfer;
    A B A — a prior live accessor interleaving back — makes the field
    shared), after which a guarded field must be accessed under its
    declared lock and a thread-owned field must not be touched at all
    by a second live thread. Thread death is a happens-before edge:
    a dead owner's state hands off freely (what makes supervisor
    restart and drain-of-a-dead-engine legal).

jax imports are deferred into the functions so `oryx_tpu.analysis`
stays importable (and the static linter runnable) without the
accelerator stack.
"""

from __future__ import annotations

import contextlib
import logging
import os
import threading
import time
from typing import Any, Iterator


class RecompileStormError(RuntimeError):
    """A traced function exceeded its compile budget inside a
    `recompile_watchdog` block."""


class UseAfterDonateError(RuntimeError):
    """A donated (deleted) buffer was about to be read."""


class RecompileStats:
    """Per-traced-function compile counts observed by the watchdog."""

    def __init__(self, budget: int):
        self.budget = budget
        self.counts: dict[str, int] = {}
        self._lock = threading.Lock()

    def record(self, fn_name: str) -> int:
        with self._lock:
            self.counts[fn_name] = self.counts.get(fn_name, 0) + 1
            return self.counts[fn_name]

    @property
    def total(self) -> int:
        with self._lock:
            return sum(self.counts.values())

    def over_budget(self) -> dict[str, int]:
        with self._lock:
            return {
                k: v for k, v in self.counts.items() if v > self.budget
            }


class _CompileLogHandler(logging.Handler):
    """Captures jax's "Compiling <fn> ..." records (emitted on every
    tracing-cache miss when `jax_log_compiles` is on)."""

    def __init__(self, callback):
        super().__init__(level=logging.DEBUG)
        self._callback = callback

    def emit(self, record: logging.LogRecord) -> None:
        try:
            msg = record.msg if isinstance(record.msg, str) else ""
            if not msg.startswith("Compiling"):
                return
            fn = "<unknown>"
            if record.args:
                fn = str(
                    record.args[0]
                    if isinstance(record.args, tuple)
                    else record.args
                )
            # jax names the computation "jit(<fn>)"; count under <fn>.
            if fn.startswith("jit(") and fn.endswith(")"):
                fn = fn[4:-1]
            self._callback(fn)
        # fault-boundary: a broken sanitizer must never break the run
        except Exception:
            pass


@contextlib.contextmanager
def recompile_watchdog(
    budget: int = 1,
    *,
    registry=None,
    action: str = "raise",
    logger_name: str = "jax",
) -> Iterator[RecompileStats]:
    """Count per-function jax compiles inside the block; over-budget
    raises (action="raise") at exit or just records (action="record").

    budget: max compiles allowed PER traced function name — distinct
    shapes of one function share a name, which is exactly the point:
    a shape-unstable loop is a recompile storm no matter how "valid"
    each individual compile is. The first compile of a function is
    expected (that's a cold start, not a recompile); every compile
    beyond the first increments `oryx_recompiles_total{fn=...}` on
    `registry` (a `utils.metrics.Registry`; pass
    `serving_metrics.registry` from serving code).
    """
    if action not in ("raise", "record"):
        raise ValueError(f"action must be 'raise' or 'record', got {action!r}")
    import jax

    stats = RecompileStats(budget)
    counter = None
    if registry is not None:
        counter = registry.counter(
            "oryx_recompiles_total", ("fn",), raw_name=True
        )

    def on_compile(fn_name: str) -> None:
        n = stats.record(fn_name)
        if n > 1 and counter is not None:
            counter.labels(fn=fn_name).inc()

    handler = _CompileLogHandler(on_compile)
    jax_logger = logging.getLogger(logger_name)
    prev_log_compiles = jax.config.jax_log_compiles
    jax.config.update("jax_log_compiles", True)
    jax_logger.addHandler(handler)
    try:
        yield stats
    finally:
        jax_logger.removeHandler(handler)
        jax.config.update("jax_log_compiles", prev_log_compiles)
    over = stats.over_budget()
    if over and action == "raise":
        worst = max(over, key=over.get)
        raise RecompileStormError(
            f"recompile storm: {worst!r} compiled {over[worst]} times "
            f"(budget {stats.budget}) inside a recompile_watchdog block; "
            f"all over budget: {over}. A fresh unhashable static operand "
            "or an unbucketed shape is the usual cause."
        )


class DonationGuard:
    """Tracks the jax-array leaves of pytrees across donating calls."""

    def __init__(self, *trees: Any, label: str = ""):
        import jax

        self.label = label
        self._leaves = [
            leaf
            for tree in trees
            for leaf in jax.tree_util.tree_leaves(tree)
            if isinstance(leaf, jax.Array)
        ]

    def _deleted(self) -> list[int]:
        return [
            i for i, a in enumerate(self._leaves) if a.is_deleted()
        ]

    @property
    def consumed(self) -> bool:
        """True when every tracked buffer was donated (deleted)."""
        return bool(self._leaves) and len(self._deleted()) == len(
            self._leaves
        )

    def assert_consumed(self) -> None:
        """The donation contract held: every tracked buffer is gone.
        Failing means the aliasing silently degraded to a copy — an
        HBM-footprint regression on real hardware. Tracking zero
        jax-array leaves also fails: a guard over an all-host tree
        verifies nothing, which is its own refactor hazard."""
        if not self._leaves:
            raise AssertionError(
                f"donation_guard{f' [{self.label}]' if self.label else ''}: "
                "no jax-array leaves were tracked — the guarded tree has "
                "no device buffers, so consumption cannot be verified"
            )
        dead = self._deleted()
        if len(dead) != len(self._leaves):
            live = len(self._leaves) - len(dead)
            raise AssertionError(
                f"donation_guard{f' [{self.label}]' if self.label else ''}: "
                f"{live}/{len(self._leaves)} tracked buffers were NOT "
                "consumed by the donating call (donation degraded to a "
                "copy, or the call never donated)"
            )

    def check(self, tree: Any = None) -> None:
        """Raise `UseAfterDonateError` if any leaf of `tree` (default:
        the tracked trees) has been deleted — call this before a read
        that must not touch donated storage."""
        import jax

        leaves = (
            self._leaves
            if tree is None
            else [
                leaf
                for leaf in jax.tree_util.tree_leaves(tree)
                if isinstance(leaf, jax.Array)
            ]
        )
        for i, a in enumerate(leaves):
            if a.is_deleted():
                raise UseAfterDonateError(
                    f"donation_guard"
                    f"{f' [{self.label}]' if self.label else ''}: "
                    f"leaf {i} ({a.aval}) was donated and deleted; "
                    "reading it is use-after-donate"
                )


@contextlib.contextmanager
def donation_guard(
    *trees: Any, expect_consumed: bool = False, label: str = ""
) -> Iterator[DonationGuard]:
    """Context-manager sugar over `DonationGuard`. With
    `expect_consumed=True` the exit asserts every tracked buffer was
    donated (use in tests around a single donating call)."""
    guard = DonationGuard(*trees, label=label)
    yield guard
    if expect_consumed:
        guard.assert_consumed()


# ---------------------------------------------------------------------------
# Lock-order sanitizer + race detector (the runtime half of the
# concurrency-correctness suite; static twins live in lockorder.py)
# ---------------------------------------------------------------------------


class LockOrderViolation(RuntimeError):
    """An instrumented lock acquire inverted the declared order,
    formed a cycle, re-entered a non-reentrant lock, or a hot-path
    dispatch ran while a lock was held."""


class RaceViolation(RuntimeError):
    """An annotated field was touched off its declared lock (shared
    state) or by an interloping live thread (thread-owned state)."""


class LockStats:
    """What the sanitizer observed: violations (recorded even when
    action='record'), per-name acquire / re-entrant-acquire counts,
    and buffered wait/hold samples awaiting a registry flush."""

    def __init__(self) -> None:
        self.violations: list[str] = []
        self.acquires: dict[str, int] = {}
        self.reentrant: dict[str, int] = {}


class _Held:
    __slots__ = ("lock", "t0")

    def __init__(self, lock: "_InstrumentedLock", t0: float):
        self.lock = lock
        self.t0 = t0


class LockOrderSanitizer:
    """Per-thread held-lock stacks + declared-order / cycle checking
    for every lock created through `named_lock` while armed."""

    _SAMPLE_CAP = 100_000  # buffered (kind, name, seconds) samples

    def __init__(self, order: tuple[str, ...] | None = None,
                 action: str = "raise"):
        if action not in ("raise", "record"):
            raise ValueError(
                f"action must be 'raise' or 'record', got {action!r}"
            )
        if order is None:
            from oryx_tpu.concurrency import LOCK_ORDER

            order = LOCK_ORDER
        self.order = tuple(order)
        self.rank = {name: i for i, name in enumerate(self.order)}
        self.action = action
        self.stats = LockStats()
        # Internal state lock: a PLAIN lock, deliberately outside the
        # instrumented world (it is a leaf and must never recurse into
        # the sanitizer).
        self._mu = threading.Lock()
        self._tls = threading.local()
        self._edges: dict[str, set[str]] = {}
        self._samples: list[tuple[str, str, float]] = []
        self._dropped_samples = 0
        # Newest bind_registry() call owns the sample stream; stale
        # bindings' collectors no-op against this token.
        self._bind_gen: object | None = None

    # ---- lock factory ----------------------------------------------------

    def make(self, name: str, kind: str = "lock") -> "_InstrumentedLock":
        return _InstrumentedLock(self, name, kind)

    # ---- held-stack bookkeeping ------------------------------------------

    def _held(self) -> list[_Held]:
        held = getattr(self._tls, "held", None)
        if held is None:
            held = self._tls.held = []
        return held

    def held_names(self) -> list[str]:
        return [e.lock.name for e in self._held()]

    def _violation(self, msg: str) -> None:
        with self._mu:
            self.stats.violations.append(msg)
        if self.action == "raise":
            raise LockOrderViolation(msg)

    def before_acquire(self, lock: "_InstrumentedLock") -> bool:
        """Order/cycle check; returns True when this is a re-entrant
        acquire of the same (reentrant) instance."""
        held = self._held()
        if any(e.lock is lock for e in held):
            if lock.kind == "lock":
                self._violation(
                    f"re-entrant acquire of non-reentrant lock "
                    f"'{lock.name}': guaranteed self-deadlock"
                )
            with self._mu:
                self.stats.reentrant[lock.name] = (
                    self.stats.reentrant.get(lock.name, 0) + 1
                )
            return True
        flagged: set[str] = set()  # held-lock names already reported
        for e in held:
            h = e.lock
            if h.name == lock.name:
                flagged.add(h.name)
                self._violation(
                    f"acquiring '{lock.name}' while already holding a "
                    f"DIFFERENT lock of the same name: same-rank locks "
                    "must never nest (no order between instances)"
                )
                continue
            ra = self.rank.get(h.name)
            rb = self.rank.get(lock.name)
            if ra is not None and rb is not None and rb < ra:
                flagged.add(h.name)
                self._violation(
                    f"acquiring '{lock.name}' while holding '{h.name}' "
                    f"inverts the declared lock order "
                    f"('{lock.name}' < '{h.name}' in "
                    "oryx_tpu/concurrency.py)"
                )
        with self._mu:
            # Pairs already reported above (same-name, declared-order
            # inversion) are excluded from BOTH the cycle check and
            # the edge insert: in record mode a recorded inverted edge
            # would otherwise turn every later LEGAL nesting of the
            # same pair into a spurious "cycle" at the correct site.
            for e in held:
                if e.lock.name in flagged:
                    continue
                if self._reaches(lock.name, e.lock.name):
                    cycle = f"'{e.lock.name}' -> '{lock.name}'"
                    self.stats.violations.append(
                        f"lock-order cycle closed by acquiring "
                        f"'{lock.name}' while holding '{e.lock.name}' "
                        f"(the reverse path {cycle} was already "
                        "observed)"
                    )
                    if self.action == "raise":
                        raise LockOrderViolation(
                            self.stats.violations[-1]
                        )
            for e in held:
                if e.lock.name not in flagged \
                        and e.lock.name != lock.name:
                    self._edges.setdefault(
                        e.lock.name, set()
                    ).add(lock.name)
        return False

    def _reaches(self, a: str, b: str) -> bool:
        # Caller holds self._mu.
        seen: set[str] = set()
        stack = [a]
        while stack:
            n = stack.pop()
            if n == b:
                return True
            if n in seen:
                continue
            seen.add(n)
            stack.extend(self._edges.get(n, ()))
        return False

    def note_acquired(self, lock: "_InstrumentedLock",
                      waited_s: float) -> None:
        self._held().append(_Held(lock, time.perf_counter()))
        with self._mu:
            self.stats.acquires[lock.name] = (
                self.stats.acquires.get(lock.name, 0) + 1
            )
            self._sample("wait", lock.name, waited_s)

    def note_release(self, lock: "_InstrumentedLock") -> None:
        held = self._held()
        for i in range(len(held) - 1, -1, -1):
            if held[i].lock is lock:
                e = held.pop(i)
                with self._mu:
                    self._sample(
                        "hold", lock.name,
                        time.perf_counter() - e.t0,
                    )
                return
        # Releasing a lock this thread never acquired through the
        # sanitizer (armed mid-flight): let the inner lock complain.

    def _sample(self, kind: str, name: str, seconds: float) -> None:
        # Caller holds self._mu. Buffered, flushed by the registry
        # collector at scrape time: observing directly from here would
        # take registry._lock inside lock bookkeeping — exactly the
        # kind of hidden nesting this sanitizer exists to forbid.
        if len(self._samples) >= self._SAMPLE_CAP:
            self._dropped_samples += 1
            return
        self._samples.append((kind, name, seconds))

    # ---- metrics ---------------------------------------------------------

    def bind_registry(self, registry) -> None:
        """Pre-register the oryx_lock_* histograms on `registry` and
        flush buffered samples into them at every scrape. Re-binding
        (chaos boots one server per scenario) moves the stream: the
        NEWEST binding owns all subsequently buffered samples, and a
        superseded registry's scrape no-ops instead of draining the
        shared buffer into the wrong server's series. Samples dropped
        at the buffer cap are surfaced as
        `oryx_lock_samples_dropped_total`, never silently."""
        from oryx_tpu.utils.metrics import LOCK_SECONDS_BUCKETS

        wait_hist = registry.histogram(
            "oryx_lock_wait_seconds", LOCK_SECONDS_BUCKETS, ("lock",),
            raw_name=True,
        )
        hold_hist = registry.histogram(
            "oryx_lock_hold_seconds", LOCK_SECONDS_BUCKETS, ("lock",),
            raw_name=True,
        )
        dropped = registry.counter(
            "oryx_lock_samples_dropped_total", raw_name=True
        )
        self._bind_gen = gen = object()

        def flush() -> None:
            if self._bind_gen is not gen:
                return  # superseded by a newer binding
            with self._mu:
                samples, self._samples = self._samples, []
                d, self._dropped_samples = self._dropped_samples, 0
            for kind, name, seconds in samples:
                hist = wait_hist if kind == "wait" else hold_hist
                hist.labels(lock=name).observe(seconds)
            if d:
                dropped.inc(d)

        self._flush = flush
        registry.register_collector(flush)

    def flush_metrics(self) -> None:
        """Flush into the current binding (no-op when never bound)."""
        flush = getattr(self, "_flush", None)
        if flush is not None:
            flush()


class _InstrumentedLock:
    """Wrapper over threading.Lock/RLock/Condition that reports to a
    LockOrderSanitizer. Same surface as the wrapped primitive (plus
    Condition's wait/notify family, which keeps the held stack honest
    across the wait's internal release/re-acquire)."""

    __slots__ = ("_san", "name", "kind", "_inner")

    def __init__(self, san: LockOrderSanitizer, name: str, kind: str):
        if kind not in ("lock", "rlock", "condition"):
            raise ValueError(f"unknown lock kind {kind!r}")
        self._san = san
        self.name = name
        self.kind = kind
        self._inner = (
            threading.Condition() if kind == "condition"
            else threading.RLock() if kind == "rlock"
            else threading.Lock()
        )

    def acquire(self, blocking: bool = True, timeout: float = -1) -> bool:
        reentrant = self._san.before_acquire(self)
        t0 = time.perf_counter()
        ok = self._inner.acquire(blocking, timeout)
        if ok and not reentrant:
            self._san.note_acquired(self, time.perf_counter() - t0)
        elif ok and reentrant:
            self._san._held().append(_Held(self, time.perf_counter()))
        return ok

    def release(self) -> None:
        self._san.note_release(self)
        self._inner.release()

    def __enter__(self):
        self.acquire()
        return self

    def __exit__(self, *exc) -> None:
        self.release()

    def locked(self) -> bool:
        inner = self._inner
        if hasattr(inner, "locked"):
            return inner.locked()
        return bool(inner._is_owned())

    def held_by_current(self) -> bool:
        return any(e.lock is self for e in self._san._held())

    # ---- Condition surface ----------------------------------------------

    def _wait_around(self, fn, *args):
        # Condition.wait releases the underlying lock and re-acquires
        # it before returning — but the ENTRY STAYS on the held stack:
        # while blocked this thread executes nothing, so its stack is
        # unobservable to itself, and wait_for's PREDICATE runs with
        # the lock genuinely held (popping here made a guarded-field
        # read inside the predicate a false RaceViolation). Only the
        # hold-time metric honors the release: the segment up to the
        # wait is sampled now and the clock restarts at wake-up.
        san = self._san
        entry = next(
            (e for e in reversed(san._held()) if e.lock is self), None
        )
        if entry is not None:
            with san._mu:
                san._sample(
                    "hold", self.name,
                    time.perf_counter() - entry.t0,
                )
        try:
            return fn(*args)
        finally:
            if entry is not None:
                entry.t0 = time.perf_counter()

    def wait(self, timeout: float | None = None):
        return self._wait_around(self._inner.wait, timeout)

    def wait_for(self, predicate, timeout: float | None = None):
        return self._wait_around(self._inner.wait_for, predicate, timeout)

    def notify(self, n: int = 1) -> None:
        self._inner.notify(n)

    def notify_all(self) -> None:
        self._inner.notify_all()


# ---------------------------------------------------------------------------
# Race detector over annotated fields
# ---------------------------------------------------------------------------

_MISSING = object()


class _FieldState:
    __slots__ = ("owner", "prior", "shared")

    def __init__(self, owner: threading.Thread):
        self.owner = owner
        self.prior: set[threading.Thread] = set()
        self.shared = False


class _RaceField:
    """Data descriptor installed over an annotated field. Shadows the
    class attribute, stores the live value in the instance __dict__
    (or delegates to the original slot descriptor) and runs the
    handoff/lockset state machine on every access."""

    __slots__ = ("det", "field", "kind", "arg", "orig", "skey")

    def __init__(self, det: "RaceDetector", field: str, kind: str,
                 arg: str, orig: Any):
        self.det = det
        self.field = field
        self.kind = kind  # "guarded-by" | "thread-owned"
        self.arg = arg    # lock attr name | owner tag
        self.orig = orig  # original slot/other descriptor, or _MISSING
        self.skey = f"__race_{field}"

    # -- state machine -----------------------------------------------------

    def _check(self, obj: Any, write: bool) -> None:
        det = self.det
        # Exemption is MODULE-global (thread-local), not per-detector:
        # descriptors can outlive the detector epoch that installed
        # them (build_server's maybe_arm_from_env arms process-wide
        # and a later re-arming skips already-instrumented fields), so
        # a per-detector flag would ignore race_exempt() taken under
        # the CURRENT detector — the pool-invariant check then raises
        # from a stale descriptor despite being declared exempt.
        if getattr(_EXEMPT, "depth", 0):
            return
        t = threading.current_thread()
        with det._mu:
            state = obj.__dict__.get(self.skey)
            if state is None:
                obj.__dict__[self.skey] = _FieldState(t)
                return
            if state.owner is t:
                if state.shared and self.kind == "guarded-by":
                    self._require_lock(obj, t)
                return
            if not state.owner.is_alive():
                # Happens-before via thread death: a fresh exclusive
                # epoch (supervisor touching a dead engine's state,
                # drain failing out a dead engine's queue).
                state.owner = t
                state.prior.clear()
                state.shared = False
                return
            state.prior = {p for p in state.prior if p.is_alive()}
            if state.shared or t in state.prior:
                # A PRIOR live accessor interleaved back: the field is
                # genuinely shared from here on.
                state.shared = True
                state.prior.add(state.owner)
                state.owner = t
                if self.kind == "thread-owned":
                    self.det._violation(
                        f"thread-owned field "
                        f"'{type(obj).__name__}.{self.field}' (owner: "
                        f"{self.arg}) touched by interleaving live "
                        f"threads ({t.name} while prior accessors are "
                        "alive) — ownership never transferred"
                    )
                else:
                    self._require_lock(obj, t)
            else:
                # Clean handoff: previous owner never came back.
                state.prior.add(state.owner)
                state.owner = t

    def _require_lock(self, obj: Any, t: threading.Thread) -> None:
        lock = getattr(obj, self.arg, None)
        if not _held_by_current(lock):
            self.det._violation(
                f"guarded field '{type(obj).__name__}.{self.field}' "
                f"accessed by {t.name} without holding its declared "
                f"lock 'self.{self.arg}' while the field is shared "
                "between live threads"
            )

    # -- descriptor protocol -----------------------------------------------

    def __get__(self, obj, objtype=None):
        if obj is None:
            return self
        self._check(obj, write=False)
        if self.orig is not _MISSING and hasattr(self.orig, "__get__"):
            return self.orig.__get__(obj, objtype)
        try:
            return obj.__dict__[self.field]
        except KeyError:
            raise AttributeError(self.field) from None

    def __set__(self, obj, value) -> None:
        self._check(obj, write=True)
        if self.orig is not _MISSING and hasattr(self.orig, "__set__"):
            self.orig.__set__(obj, value)
        else:
            obj.__dict__[self.field] = value

    def __delete__(self, obj) -> None:
        self._check(obj, write=True)
        if self.orig is not _MISSING and hasattr(self.orig, "__delete__"):
            self.orig.__delete__(obj)
        else:
            del obj.__dict__[self.field]


def _held_by_current(lock: Any) -> bool:
    if lock is None:
        return False
    if isinstance(lock, _InstrumentedLock):
        return lock.held_by_current()
    if hasattr(lock, "_is_owned"):  # Condition / RLock
        try:
            return bool(lock._is_owned())
        # fault-boundary: an exotic lock type must degrade to
        # approximate checking, not break the run under test
        except Exception:
            return True
    if hasattr(lock, "locked"):
        # Plain Lock predates per-thread ownership: `locked()` is the
        # best approximation (someone holds it). Armed runs create
        # instrumented locks, so this path only covers stragglers
        # constructed before arming.
        return lock.locked()
    return True


class RaceDetector:
    """Installs _RaceField descriptors over every `# guarded-by:` /
    `# thread-owned:` annotated field of the classes in the target
    modules — the annotations are parsed from SOURCE with the same
    analysis.core machinery the static rules use."""

    def __init__(self, action: str = "raise",
                 stats_sink: LockStats | None = None):
        if action not in ("raise", "record"):
            raise ValueError(
                f"action must be 'raise' or 'record', got {action!r}"
            )
        self.action = action
        self.violations: list[str] = []
        # Mirror race findings into the paired sanitizer's stats so
        # one `lock_stats().violations` assertion covers both halves.
        self._sink = stats_sink
        self._mu = threading.Lock()
        self._installed: list[tuple[type, str, Any]] = []

    def _violation(self, msg: str) -> None:
        # Caller holds self._mu. (list.append is atomic under the GIL,
        # so the cross-object sink append needs no extra lock.)
        self.violations.append(msg)
        if self._sink is not None:
            self._sink.violations.append(msg)
        if self.action == "raise":
            raise RaceViolation(msg)

    def install_module(self, module) -> int:
        """Instrument every annotated field of `module`'s classes;
        returns the number of fields instrumented."""
        import ast as ast_mod
        import inspect

        from oryx_tpu.analysis.core import (
            ParsedModule,
            field_annotations,
        )

        try:
            source = inspect.getsource(module)
        except (OSError, TypeError):
            return 0
        mod = ParsedModule(getattr(module, "__file__", "<mem>"), source)
        count = 0
        for node in ast_mod.walk(mod.tree):
            if not isinstance(node, ast_mod.ClassDef):
                continue
            cls = getattr(module, node.name, None)
            if not isinstance(cls, type):
                continue
            for field, (kind, arg) in field_annotations(mod, node).items():
                orig = cls.__dict__.get(field, _MISSING)
                if isinstance(orig, _RaceField):
                    continue  # already instrumented
                setattr(
                    cls, field,
                    _RaceField(self, field, kind, arg, orig),
                )
                self._installed.append((cls, field, orig))
                count += 1
        return count

    def uninstall(self) -> None:
        for cls, field, orig in reversed(self._installed):
            if orig is _MISSING:
                try:
                    delattr(cls, field)
                except AttributeError:
                    pass
            else:
                setattr(cls, field, orig)
        self._installed.clear()


# ---------------------------------------------------------------------------
# Arming (module-global, same contract as utils.faults: one global
# read on the hot path when disarmed)
# ---------------------------------------------------------------------------

_SAN: LockOrderSanitizer | None = None
_RACE: RaceDetector | None = None
_ENV_VAR = "ORYX_LOCK_SANITIZER"

# Module paths whose annotated classes the race detector instruments
# when armed from the environment (the concurrency surface of serving).
_RACE_MODULES = (
    "oryx_tpu.serve.scheduler",
    "oryx_tpu.serve.prefix_cache",
    "oryx_tpu.serve.api_server",
    "oryx_tpu.utils.trace",
    "oryx_tpu.utils.metrics",
)


def named_lock(name: str, kind: str = "lock"):
    """Create the lock for a `with self.<lock>:` site. Disarmed: a
    plain threading primitive (one global read of overhead). Armed:
    an instrumented wrapper reporting to the active sanitizer. The
    name is BOTH the runtime identity (held stacks, metrics labels,
    violation messages) and the static one (oryxlint's lock-order
    rule reads it from this call's literal)."""
    san = _SAN
    if san is None:
        if kind == "condition":
            return threading.Condition()
        if kind == "rlock":
            return threading.RLock()
        return threading.Lock()
    return san.make(name, kind)


def hot_dispatch(name: str) -> None:
    """Marker call at the top of a `# hot-path` device dispatch: armed,
    it flags the dispatch running while the current thread holds any
    instrumented lock (which would serialize every other thread on
    device latency). Disarmed: one global read."""
    san = _SAN
    if san is None:
        return
    held = san.held_names()
    if held:
        san._violation(
            f"hot-path dispatch '{name}' entered while holding "
            f"{held}: a device dispatch must never run under a lock"
        )


# Thread-local race-exemption depth, shared by EVERY detector epoch's
# descriptors (see _RaceField._check: descriptors can outlive the
# detector that installed them, so the flag cannot live on a detector).
_EXEMPT = threading.local()


@contextlib.contextmanager
def race_exempt(reason: str = "") -> Iterator[None]:
    """Mark the current thread's annotated-field accesses as
    externally synchronized for the duration (e.g. the pool-invariant
    check, which callers only run quiesced). The mark applies to ANY
    installed race descriptor — including one from an earlier arming
    epoch still instrumenting a class (process-wide arming via
    $ORYX_LOCK_SANITIZER has no disarm point). No-op disarmed."""
    _EXEMPT.depth = getattr(_EXEMPT, "depth", 0) + 1
    try:
        yield
    finally:
        _EXEMPT.depth -= 1


def arm_lock_sanitizer(
    *,
    order: tuple[str, ...] | None = None,
    action: str = "raise",
    race_modules: Iterator | tuple | list | None = None,
    registry=None,
) -> LockOrderSanitizer:
    """Arm the global sanitizer (locks created through `named_lock`
    from now on are instrumented) and install the race detector over
    `race_modules` (imported module objects; default: the serving
    concurrency surface). Idempotent-ish: re-arming replaces the
    global but leaves existing instrumented locks reporting to their
    original sanitizer."""
    global _SAN, _RACE
    san = LockOrderSanitizer(order=order, action=action)
    det = RaceDetector(action=action, stats_sink=san.stats)
    if race_modules is None:
        import importlib

        race_modules = []
        for name in _RACE_MODULES:
            try:
                race_modules.append(importlib.import_module(name))
            # fault-boundary: a surface module that cannot import in
            # this environment simply is not instrumented
            except Exception:
                pass
    for module in race_modules:
        det.install_module(module)
    if registry is not None:
        san.bind_registry(registry)
    _SAN = san
    _RACE = det
    return san


def disarm_lock_sanitizer() -> None:
    global _SAN, _RACE
    if _RACE is not None:
        _RACE.uninstall()
    _SAN = None
    _RACE = None


@contextlib.contextmanager
def lock_sanitizer(
    *,
    order: tuple[str, ...] | None = None,
    action: str = "raise",
    race_modules=None,
    registry=None,
) -> Iterator[LockOrderSanitizer]:
    """Context-manager arming for tests — the recompile_watchdog
    contract: arm on entry, disarm (descriptors uninstalled, classes
    restored) on exit."""
    san = arm_lock_sanitizer(
        order=order, action=action, race_modules=race_modules,
        registry=registry,
    )
    try:
        yield san
    finally:
        disarm_lock_sanitizer()


def lock_sanitizer_armed() -> bool:
    return _SAN is not None


def lock_stats() -> LockStats | None:
    """The active sanitizer's stats (None disarmed). When armed via
    arm_lock_sanitizer/lock_sanitizer/maybe_arm_from_env, the paired
    race detector mirrors its findings into these violations too, so
    one `lock_stats().violations == []` assertion covers both halves
    (a standalone RaceDetector only mirrors if given a stats_sink)."""
    return _SAN.stats if _SAN is not None else None


def race_violations() -> list[str]:
    return list(_RACE.violations) if _RACE is not None else []


def bind_lock_metrics(registry) -> bool:
    """Attach the armed sanitizer's wait/hold histograms to `registry`
    (no-op disarmed). The API server calls this with its serving
    registry so armed runs surface oryx_lock_* on /metrics."""
    if _SAN is None:
        return False
    _SAN.bind_registry(registry)
    return True


def maybe_arm_from_env(registry=None) -> bool:
    """Arm from $ORYX_LOCK_SANITIZER unless empty/0/off/false (the
    ORYX_RECOMPILE_WATCHDOG convention). Called by tests/conftest.py,
    scripts/chaos_suite.py and the API server build — never at import
    (a library import must not mutate classes as a side effect)."""
    spec = os.environ.get(_ENV_VAR, "").strip().lower()
    if spec in ("", "0", "off", "false"):
        return False
    if _SAN is None:
        arm_lock_sanitizer(registry=registry)
    elif registry is not None:
        _SAN.bind_registry(registry)
    return True


def backend_donates() -> bool:
    """Whether this backend actually consumes donated buffers (CPU on
    some jax versions silently ignores donation) — tests gate
    `assert_consumed` on this."""
    import jax
    import jax.numpy as jnp

    probe = jax.jit(lambda x: x + 1, donate_argnums=0)
    x = jnp.zeros((8,))
    probe(x).block_until_ready()
    # The read IS the probe: asking whether donation consumed it.
    return x.is_deleted()  # oryxlint: disable=use-after-donate
