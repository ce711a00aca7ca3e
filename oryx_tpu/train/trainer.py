"""SFT trainer: mesh setup, sharded state, step loop, checkpoint/resume.

Reference parity: `train()` in `oryx/train/train.py` + the HF
Trainer/DeepSpeed loop (SURVEY.md §3.1), re-composed TPU-first:
mesh + GSPMD shardings replace the DeepSpeed engine; the jitted
`train.step.train_step` replaces forward/backward/fused-Adam; orbax
replaces ZeRO partitioned checkpoints. Entry scripts call `Trainer.fit()`.
"""

from __future__ import annotations

import time
from typing import Any, Iterator

import jax
import jax.numpy as jnp
import numpy as np

from oryx_tpu.config import OryxConfig
from oryx_tpu.models import oryx
from oryx_tpu.parallel import mesh as mesh_lib
from oryx_tpu.parallel import sharding
from oryx_tpu.train import loss as loss_lib
from oryx_tpu.train import step as step_lib
from oryx_tpu.train import telemetry as telemetry_lib
from oryx_tpu.train.optimizer import (
    make_optimizer,
    make_schedule,
    trainable_mask,
)
from oryx_tpu.utils import faults
from oryx_tpu.utils import trace as trace_lib
from oryx_tpu.utils.anomaly import AnomalyThresholds
from oryx_tpu.utils.checkpoint import CheckpointManager
from oryx_tpu.utils.metrics import MetricLogger, rank0_print
from oryx_tpu.utils.profiling import PhaseClock


def validate_train_batch(cfg: OryxConfig, batch: dict) -> None:
    """Fail fast on config x data combinations that would otherwise die
    deep inside jit tracing (or train silently wrong). Today: packed
    text under ring attention — ring has no segment support
    (docs/MIGRATING.md), so samples packed into one row would attend
    across sample boundaries."""
    if "text_segment_ids" in batch and cfg.attn_impl.startswith("ring"):
        raise ValueError(
            f"packed-text batches (text_segment_ids) cannot train under "
            f"attn_impl={cfg.attn_impl!r}: ring attention has no "
            "segment support, so packed samples would attend across "
            "sample boundaries. Use attn_impl='xla'|'pallas' (sp=1) "
            "or disable text packing (see docs/MIGRATING.md)."
        )


def _poison_one_float_leaf(batch: dict) -> dict:
    """Chaos helper (`data_loader_next:corrupt=1`): NaN one element of
    the first floating-point field, simulating a corrupt record — the
    skip_nonfinite guard should skip the step, not crash the run."""
    batch = dict(batch)
    for k, v in batch.items():
        arr = np.asarray(v)
        if np.issubdtype(arr.dtype, np.floating) and arr.size:
            bad = arr.copy()
            bad.flat[0] = np.nan
            batch[k] = bad
            rank0_print(f"fault injection: poisoned batch field {k!r}")
            break
    return batch


class Trainer:
    def __init__(
        self,
        cfg: OryxConfig,
        *,
        params: dict[str, Any] | None = None,
        sharding_mode: str = "fsdp",
        metrics_path: str | None = None,
        tensorboard_dir: str | None = None,
        tracer: trace_lib.Tracer | None = None,
        flight_recorder_size: int = 64,
        stall_timeout: float | None = None,
        metrics_port: int | None = None,
        events_path: str | None = None,
        on_anomaly: str = "warn",
        anomaly_thresholds: AnomalyThresholds | None = None,
        telemetry: telemetry_lib.TrainTelemetry | None = None,
        max_data_faults: int = 8,
        numerics_every: int = 0,
    ) -> None:
        self.cfg = cfg
        # Numerics sentinels (utils/numerics.py): every N steps the
        # jitted step runs its probe-armed static twin — per-layer
        # grad absmax, activation/param absmax — feeding the
        # oryx_numerics_* gauges and the absmax_explosion detector.
        # 0 = off (the default: the probe tree-maps the grad tree and
        # the params, which is cheap but not free on giant models).
        if not isinstance(numerics_every, int) or numerics_every < 0:
            raise ValueError(
                "numerics_every must be a non-negative integer (steps "
                f"between probe samples; 0 = off), got {numerics_every!r}"
            )
        self.numerics_every = numerics_every
        # Data-loader containment: a transient loader failure skips
        # that fetch and pulls the next batch (bounded by
        # max_data_faults consecutive failures — a dead loader still
        # fails loudly). `data_faults` counts the recoveries.
        self.max_data_faults = max_data_faults
        self.data_faults = 0
        self.mesh = mesh_lib.build_mesh(cfg.mesh)
        self.sharding_mode = sharding_mode
        self.logger = MetricLogger(
            metrics_path, log_every=cfg.train.log_every,
            tensorboard_dir=tensorboard_dir,
        )
        self.ckpt = CheckpointManager(cfg.train.checkpoint_dir)
        # Fleet-level telemetry (train/telemetry.py): a /metrics +
        # /healthz + /readyz HTTP exporter plus the anomaly monitor.
        # Off by default (no thread, no sink) — any of metrics_port /
        # events_path / an injected TrainTelemetry turns it on, and so
        # does on_anomaly="halt": the halt policy lives in the monitor,
        # so asking for it MUST construct one (registry-only, no HTTP,
        # when no port was given) rather than silently not protecting
        # the run. Only process 0 exports: one scrape target per job,
        # and the per-step metrics are already global reductions.
        self.telemetry = telemetry
        if (
            self.telemetry is None
            and (
                metrics_port is not None
                or events_path
                or on_anomaly == "halt"
            )
            and jax.process_index() == 0
        ):
            self.telemetry = telemetry_lib.TrainTelemetry(
                port=metrics_port, events_path=events_path,
                thresholds=anomaly_thresholds, on_anomaly=on_anomaly,
            )
        if self.telemetry is not None and faults.armed():
            # Chaos runs export oryx_faults_injected_total{site=}
            # through the trainer registry, mirroring the serving side.
            faults.bind_registry(self.telemetry.registry)
        self._lr_fn = make_schedule(cfg.train, cfg.train.learning_rate)
        # Per-step flight recorder (same Trace/Span model as serving):
        # each step records data / h2d / step_dispatch / device_sync /
        # checkpoint_save spans. The same blocks are the step loop's
        # phases (fit's PhaseClock: oryx.train.data / .h2d / .dispatch /
        # .sync / .checkpoint, and .log for the rest of an iteration),
        # whose seconds land in the MetricLogger record and the
        # telemetry counters. stall_timeout arms a watchdog that dumps
        # thread stacks + the recorder tail when no step completes in
        # time (a hung collective, a wedged data loader, ...).
        self.tracer = tracer or trace_lib.Tracer(flight_recorder_size)
        self.watchdog: trace_lib.StallWatchdog | None = None
        if stall_timeout is not None:
            self.watchdog = trace_lib.StallWatchdog(
                self.tracer, stall_timeout, name="trainer"
            ).start()

        with sharding.mesh_scope(self.mesh):
            if params is None:
                params = oryx.init_params(cfg, jax.random.key(cfg.train.seed))
            if cfg.train.tune == "lora" and not cfg.train.lora.enable:
                raise ValueError(
                    "tune='lora' requires train.lora.enable=True (otherwise "
                    "no adapters exist and only the projector would train)"
                )
            if cfg.train.lora.enable:
                if not cfg.train.lora.targets:
                    raise ValueError("lora.enable with empty lora.targets")
                layers = params["llm"]["layers"]
                have = [
                    t for t in cfg.train.lora.targets
                    if "lora_a" in layers.get(t, {})
                ]
                if not have:
                    # Attach adapters to the (fresh or pretrained) base
                    # model; tune="lora" freezes all but A/B + projector.
                    params = oryx.enable_lora(
                        params, cfg, jax.random.key(cfg.train.seed + 1)
                    )
                elif set(have) != set(cfg.train.lora.targets):
                    raise ValueError(
                        f"params carry adapters on {sorted(have)} but "
                        f"config targets {sorted(cfg.train.lora.targets)} "
                        f"— refusing to train a silently narrower adapter"
                    )
            self.tx = make_optimizer(cfg.train, params)
            # How much the optimizer updates and how much the step
            # differentiates (static per compiled step; equal since the
            # step splits the tree by the optimizer's mask): they ride
            # the first metric record and the oryx_train_* gauges.
            mask = trainable_mask(params, cfg.train.tune)
            self.param_counts = {
                "trainable_params": sum(
                    p.size for p, m in zip(
                        jax.tree.leaves(params), jax.tree.leaves(mask)
                    ) if m
                ),
                "differentiated_params": sum(
                    p.size for p in jax.tree.leaves(
                        step_lib.trainable(params, cfg.train.tune)
                    )
                ),
            }
            pspecs = sharding.param_shardings(self.mesh, params, sharding_mode)
            params = sharding.shard_params(params, pspecs)
            opt_state = self.tx.init(params)
            opt_mode = "fsdp" if sharding_mode in ("fsdp", "zero2") else "ddp"
            ospecs = sharding.opt_state_specs(opt_state, params, opt_mode)
            opt_state = jax.tree.map(
                lambda x, s: jax.device_put(
                    x, jax.sharding.NamedSharding(self.mesh, s)
                ),
                opt_state, ospecs,
            )
            self.state = step_lib.TrainState(
                step=jnp.zeros((), jnp.int32), params=params,
                opt_state=opt_state,
            )
            # Jit the step with state out_shardings pinned, so updated
            # params keep THIS mode's placement (zero2 keeps params
            # replicated instead of inheriting the optimizer's fsdp spec).
            oshard = jax.tree.map(
                lambda s: jax.sharding.NamedSharding(self.mesh, s),
                ospecs,
                is_leaf=lambda x: isinstance(x, jax.sharding.PartitionSpec),
            )
            state_shardings = step_lib.TrainState(
                step=jax.sharding.NamedSharding(
                    self.mesh, jax.sharding.PartitionSpec()
                ),
                params=pspecs,
                opt_state=oshard,
            )
            self._step = jax.jit(
                step_lib.train_step_fn,
                static_argnames=("cfg", "tx", "sharding_mode", "numerics"),
                donate_argnames=("state",),
                out_shardings=(state_shardings, None),
            )
            axes, n = loss_lib.vocab_parallel_axes(
                sharding_mode, cfg.llm.vocab_size
            )
            rank0_print(
                f"trainer: mesh {dict(self.mesh.shape)}, params "
                f"{sharding_mode}; loss: vocabulary matrix "
                + (f"split by vocabulary over {'x'.join(axes)} (n={n})"
                   if axes else "whole at every chunk (n=1)")
            )

    def close(self) -> None:
        """Release background resources: the stall-watchdog thread (a
        forever-polling daemon otherwise — N constructed Trainers would
        leak N of them) and the metric writer. fit() can still be
        called again before close()."""
        if self.watchdog is not None:
            self.watchdog.stop()
            self.watchdog = None
        if self.telemetry is not None:
            self.telemetry.close()
            self.telemetry = None
        self.logger.close()

    def resume_if_available(self) -> int:
        """Restore latest checkpoint if present; returns start step."""
        if self.ckpt.latest_step() is None:
            return 0
        t0 = time.perf_counter()
        self.state = self.ckpt.restore(self.state)
        start = int(self.state.step)
        if self.telemetry is not None:
            # Restore time is goodput-relevant (MegaScale: restart
            # overhead is a first-class loss term) — attribute it.
            self.telemetry.record_restore(time.perf_counter() - t0)
        rank0_print(f"resumed from step {start}")
        return start

    def _device_batch(self, batch: dict[str, np.ndarray]) -> dict[str, Any]:
        """Host batch → device with [accum, ...] leading axis.

        With grad_accum_steps > 1 the host batch must ALREADY be stacked
        per-microbatch (data.collate_microbatches) — each microbatch owns
        its own packed visual buffer; slicing a globally-packed buffer
        would corrupt visual_idx/region_ids.

        Placement is per-field (sharding.batch_field_spec): packed
        visual buffers shard their packing axis over the FULL
        (dp, fsdp, sp) width — matching the vision tower's pinned specs
        and the AOT memory proofs — while token-stream rows shard over
        the data width.
        """
        accum = self.cfg.train.grad_accum_steps

        def put(name, x):
            x = np.asarray(x)
            if accum > 1:
                if x.shape[0] != accum:
                    raise ValueError(
                        f"{name}: expected stacked [accum={accum}, ...] "
                        f"microbatches (use data.collate_microbatches), "
                        f"got shape {x.shape}"
                    )
            else:
                x = x[None]
            spec = sharding.batch_field_spec(name)
            width = 1
            for ax in spec[1]:
                width *= self.mesh.shape[ax]
            if x.shape[1] % max(width, 1) != 0:
                spec = jax.sharding.PartitionSpec()
            return jax.device_put(
                jnp.asarray(x), jax.sharding.NamedSharding(self.mesh, spec)
            )

        return {k: put(k, v) for k, v in batch.items()}

    def _next_batch(self, batches: Iterator, tr,
                    clock: PhaseClock) -> dict:
        """Fetch the next host batch with skip-and-requeue containment:
        a transient loader exception (injectable at the
        `data_loader_next` chaos site) logs, counts, and fetches the
        NEXT batch instead of killing the run; `max_data_faults`
        consecutive failures still abort loudly. StopIteration (data
        genuinely exhausted) passes through untouched."""
        consecutive = 0
        while True:
            try:
                with clock.phase("data"), tr.span("data"):
                    # corrupt=1 at this site poisons one float leaf
                    # with NaN instead of raising — driving the
                    # existing skip_nonfinite guard end-to-end.
                    corrupt = faults.fault_point("data_loader_next")
                    batch = next(batches)
                    if corrupt:
                        batch = _poison_one_float_leaf(batch)
                    return batch
            except StopIteration:
                raise
            # fault-boundary: transient data fault -> skip this fetch
            except Exception as e:
                consecutive += 1
                self.data_faults += 1
                rank0_print(
                    f"data loader fault ({consecutive}/"
                    f"{self.max_data_faults} consecutive): "
                    f"{type(e).__name__}: {e}; skipping to next batch"
                )
                if consecutive >= self.max_data_faults:
                    raise RuntimeError(
                        f"{consecutive} consecutive data-loader "
                        "failures — aborting (see Trainer "
                        "max_data_faults)"
                    ) from e

    # hot-path
    def fit(
        self,
        batches: Iterator[dict[str, np.ndarray]],
        *,
        num_steps: int | None = None,
        resume: bool = True,
        prefetch: int = 2,
    ) -> step_lib.TrainState:
        cfg = self.cfg
        num_steps = num_steps or cfg.train.num_train_steps
        start = self.resume_if_available() if resume else 0
        prefetcher = None
        if prefetch > 0 and start < num_steps:
            from oryx_tpu.train.data import PrefetchIterator

            batches = prefetcher = PrefetchIterator(batches, depth=prefetch)
        consecutive_skipped = 0
        first_record = self.param_counts
        # Where the loop's wall time goes, in exclusive seconds: what is
        # in no named phase is `log` (the metric record, telemetry,
        # numerics, the checkpoint decision), so the phases between two
        # syncs add up to the wall time between them. `data` is not
        # "blocked": oryx.train.host then runs unbroken from the sync's
        # return to the next dispatch.
        phase_s = dict.fromkeys(
            ("data", "h2d", "dispatch", "sync", "log", "checkpoint"), 0.0
        )
        seen = dict(phase_s)

        def bill(name: str, seconds: float) -> None:
            phase_s[name] += seconds

        clock = PhaseClock("oryx.train", bill, base="log")
        if self.watchdog is not None and start < num_steps:
            self.watchdog.set_active(True)
        if self.telemetry is not None:
            self.telemetry.mark_ready(True, "ok")
        try:
            with sharding.mesh_scope(self.mesh):
                for step_i in range(start, num_steps):
                    # Chaos site: a mid-run process death (raises out
                    # of fit; nothing contains it — the test of this
                    # site is that a FRESH Trainer auto-resumes from
                    # the last good checkpoint bit-identically).
                    faults.fault_point("trainer_crash")
                    t_step0 = time.perf_counter()
                    tr = self.tracer.start_trace(
                        "train_step", label=f"step {step_i + 1}"
                    )
                    try:
                        host_batch = self._next_batch(batches, tr, clock)
                    except StopIteration:
                        tr.finish(exhausted=True)
                        rank0_print("data exhausted; stopping")
                        break
                    validate_train_batch(cfg, host_batch)
                    with clock.phase("h2d"), tr.span("h2d"):
                        batch = self._device_batch(host_batch)
                    # Must use self._step (out_shardings pinned): the plain
                    # step_lib.train_step jit lets GSPMD reshard zero2's
                    # replicated params to the fsdp opt-state spec after
                    # step 1 (see train_step_fn docstring).
                    numer = (
                        self.numerics_every > 0
                        and step_i % self.numerics_every == 0
                    )
                    with clock.phase("dispatch", "dispatch"), \
                            tr.span("step_dispatch"):
                        self.state, metrics = self._step(
                            self.state, batch, cfg=cfg, tx=self.tx,
                            sharding_mode=self.sharding_mode,
                            numerics=numer,
                        )
                    # Async dispatch returns immediately; the sync span
                    # is where the device actually runs the step (plus
                    # the compile on step 1). The step loop's ONE
                    # deliberate sync: everything downstream (logging,
                    # anomaly detection) needs host scalars.
                    with clock.phase("sync", "blocked"), \
                            tr.span("device_sync"):
                        host_metrics = jax.device_get(metrics)  # oryxlint: disable=host-sync
                    if self.watchdog is not None:
                        self.watchdog.beat()
                    # The per-layer probe vector is telemetry-only: the
                    # MetricLogger record holds scalars (the absmax
                    # scalars ride it; the [L] vector would not
                    # serialize as one number).
                    layer_absmax = host_metrics.pop(
                        "grad_layer_absmax", None
                    )
                    if numer and self.telemetry is not None:
                        self.telemetry.record_numerics(
                            step_i + 1, host_metrics,
                            layer_absmax=layer_absmax,
                        )
                    # Phase seconds ride the metric record too, so the
                    # JSONL/TensorBoard stream shows where a slow step
                    # went without pulling the flight recorder. log_s
                    # runs from the previous step's sync to this one's
                    # (this iteration's own log phase has just begun).
                    step_s = {
                        f"{k}_s": phase_s[k] - seen[k]
                        for k in ("data", "h2d", "dispatch", "sync", "log")
                    }
                    seen = dict(phase_s)
                    host_metrics.update(step_s)
                    if first_record and (
                        (step_i + 1) % self.logger.log_every == 0
                    ):
                        host_metrics.update(first_record)
                        first_record = None
                    self.logger.log_step(step_i + 1, host_metrics)
                    if int(host_metrics.get("skipped", 0)):
                        consecutive_skipped += 1
                        if (
                            consecutive_skipped
                            >= cfg.train.max_consecutive_skipped
                        ):
                            # Persistently non-finite: a silent no-op pod
                            # is worse than a dead one (params frozen,
                            # checkpoints advancing, compute burning).
                            raise RuntimeError(
                                f"{consecutive_skipped} consecutive "
                                "non-finite steps skipped — aborting "
                                "(see train.max_consecutive_skipped)"
                            )
                    else:
                        consecutive_skipped = 0
                    if (step_i + 1) % cfg.train.checkpoint_every == 0:
                        with clock.phase("checkpoint"), \
                                tr.span("checkpoint_save"):
                            self.ckpt.save(step_i + 1, self.state)
                    tr.finish(
                        step=step_i + 1,
                        skipped=int(host_metrics.get("skipped", 0)),
                    )
                    if self.telemetry is not None:
                        # May raise AnomalyHalt under --on-anomaly=halt
                        # (the finally below still releases resources).
                        self.telemetry.record_step(
                            step_i + 1, host_metrics,
                            step_seconds=time.perf_counter() - t_step0,
                            **step_s,
                            checkpoint_s=(
                                phase_s["checkpoint"] - seen["checkpoint"]
                            ),
                            flops=telemetry_lib.batch_flops(
                                cfg, host_batch
                            ),
                            lr=float(self._lr_fn(step_i + 1)),
                        )
        finally:
            if self.watchdog is not None:
                self.watchdog.set_active(False)
            if prefetcher is not None:
                prefetcher.close()
            # /readyz must stop saying ready once the step loop is
            # gone — completed, crashed, or halted (record_step already
            # set the more specific "halted: <kind>" reason; keep it).
            if self.telemetry is not None and self.telemetry._ready:
                self.telemetry.mark_ready(False, "step loop exited")
        # Post-loop, pre-checkpoint: one sync after the last step.
        final_step = int(jax.device_get(self.state.step))  # oryxlint: disable=host-sync
        if final_step > 0 and self.ckpt.latest_step() != final_step:
            self.ckpt.save(final_step, self.state, force=True)
        self.ckpt.wait()
        return self.state
