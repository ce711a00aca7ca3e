"""The Engine interface: what the serving tier runs behind.

`ContinuousScheduler` grew every behavior production serving needs —
bounded admission, deadlines, drain, supervised restart, the cost
ledger — but until this module it was also the only SHAPE an engine
could have, hard-wired into `api_server.build_server` and the
supervisor. The multi-replica tier (serve/router.py, ROADMAP item 2)
and the later disaggregated prefill/decode split (item 3's engine
family) need "an engine" to be a contract, not a class:

  * `Engine` — the structural protocol. submit/cancel for the request
    path; queue_len/alive/readiness for the health surface routers
    eject on; begin_drain/drain/stop for the shutdown ladder;
    restart/set_supervised for the EngineSupervisor. Anything
    satisfying it is drop-in behind the API server, the supervisor,
    and every check/chaos/load script.
  * `register_engine` / `create_engine` — the factory registry keyed
    by the `--engine` flag. Registration binds the server's metrics
    registry, tracer and anomaly monitor into the engine at
    construction (the "metrics registry binding" half of the
    contract): every engine exposes its families through the SAME
    `ServingMetrics` the server scrapes at /metrics, so a new engine
    shape never grows a second exposition path.

Registered shapes:

  * `continuous` — `ContinuousScheduler` over one pipeline. If the
    pipeline carries a mesh (built with `--shard tp=N`), the paged KV
    pool is placed with heads sharded over the tp axis and decode runs
    tensor-parallel under GSPMD (`ContinuousScheduler._place_kv`) —
    single-chip and sharded serving are the same engine, differing
    only in placement.
  * `sharded` — the same scheduler, but construction FAILS unless the
    pipeline actually has a multi-device mesh whose tp axis splits the
    KV heads. Use it in deployments where "this replica is
    tensor-parallel" must be an invariant, not an accident of flags.
"""

from __future__ import annotations

from typing import Any, Callable, Protocol, runtime_checkable


@runtime_checkable
class Engine(Protocol):
    """Structural contract every serving engine satisfies (the
    continuous scheduler is the reference implementation; tests assert
    conformance so a refactor can't silently shed a method).

    Request path: `submit` returns a handle whose `events` queue /
    `done` event the HTTP layer consumes; it raises AdmissionRejected
    (scheduler.py) instead of queueing when shedding. `cancel` releases
    a request wherever it lives. Health: `alive` is the loop-thread
    liveness bit, `readiness` the full (ready, reason) /readyz signal,
    `queue_len` the admission-queue depth. Shutdown: `begin_drain`
    stops admission now (readiness flips immediately), `drain` waits
    for residents, `stop` kills the loop. Supervision: `restart`
    revives a dead loop with deterministic replay; `set_supervised`
    tells submit whether anyone is committed to reviving a dead
    engine. `metrics` is the bound ServingMetrics — the registry the
    server renders at /metrics."""

    metrics: Any

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
    ) -> Any: ...

    def cancel(self, handle: Any) -> None: ...

    def queue_len(self) -> int: ...

    def alive(self) -> bool: ...

    def readiness(self) -> tuple[bool, str]: ...

    def begin_drain(self) -> None: ...

    def drain(self, timeout: float | None = 60.0) -> bool: ...

    def stop(self) -> None: ...

    def restart(self) -> None: ...

    def set_supervised(self, value: bool) -> None: ...

    def fail_inflight(self, msg: str, *, kind: str = "unavailable"
                      ) -> None: ...

    @property
    def draining(self) -> bool: ...

    @property
    def stopping(self) -> bool: ...


# name -> factory(pipe, **kwargs) -> Engine. Factories receive the
# server-owned observability objects (metrics / tracer / anomaly) plus
# the engine-geometry kwargs of build_server; unknown names fail fast
# at server construction with the registered choices.
ENGINES: dict[str, Callable[..., Engine]] = {}


def register_engine(name: str):
    """Decorator: register a factory under an `--engine` name."""

    def deco(fn: Callable[..., Engine]):
        if name in ENGINES:
            raise ValueError(f"engine {name!r} already registered")
        ENGINES[name] = fn
        return fn

    return deco


def engine_names() -> list[str]:
    return sorted(ENGINES)


def create_engine(name: str, pipe, **kwargs) -> Engine:
    """Build the named engine around `pipe`, binding the server's
    metrics registry / tracer / anomaly monitor passed in kwargs."""
    factory = ENGINES.get(name)
    if factory is None:
        raise ValueError(
            f"unknown engine {name!r} (registered: {', '.join(engine_names())})"
        )
    return factory(pipe, **kwargs)


@register_engine("continuous")
def _continuous(pipe, **kwargs) -> Engine:
    from oryx_tpu.serve.scheduler import ContinuousScheduler

    return ContinuousScheduler(pipe, **kwargs)


@register_engine("sharded")
def _sharded(pipe, **kwargs) -> Engine:
    """Tensor-parallel continuous engine: the same scheduler, with the
    mesh made a REQUIREMENT. The KV pool is heads-sharded over tp
    (scheduler._place_kv) and decode runs under GSPMD; construction
    fails when the pipe has no mesh, the mesh has no tp width, or the
    KV heads don't divide — a deployment asking for sharded serving
    must never silently fall back to one chip."""
    from oryx_tpu.parallel.sharding import paged_kv_spec
    from oryx_tpu.serve.scheduler import ContinuousScheduler

    mesh = getattr(pipe, "mesh", None)
    if mesh is None:
        raise ValueError(
            "--engine sharded needs a multi-device pipeline: pass "
            "--shard tp=N (mesh absent)"
        )
    if paged_kv_spec(mesh) is None:
        raise ValueError(
            f"--engine sharded needs a tp axis > 1 on the mesh, got "
            f"axes {dict(mesh.shape)!r} (use --shard tp=N)"
        )
    heads = pipe.cfg.llm.num_kv_heads
    if heads % mesh.shape["tp"]:
        raise ValueError(
            f"--engine sharded: {heads} KV heads do not divide over "
            f"tp={mesh.shape['tp']}"
        )
    return ContinuousScheduler(pipe, **kwargs)
