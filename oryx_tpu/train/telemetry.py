"""Trainer telemetry exporter: the fleet-level view of a training run.

PR 2 gave the trainer per-step spans and a flight recorder ("why was
THIS step slow"); this module gives it the Prometheus side ("are we
healthy, are we fast, are we regressing") — the same exposition path as
serving (utils/metrics.Registry), served from a background stdlib HTTP
endpoint (`--metrics-port`):

  GET /metrics — oryx_train_* series: per-step loss / grad-norm / lr,
                 tokens/sec(/chip), MFU (the shared 6N model in
                 utils/flops.py — same arithmetic as bench.py), phase
                 seconds (data / h2d / dispatch / sync / log /
                 checkpoint: the trainer's PhaseClock), goodput
                 accounting, HBM telemetry, process collectors, plus
                 the cross-source oryx_anomaly_total{kind=} counter.
  GET /healthz — process liveness.
  GET /readyz  — 200 once the step loop is running (flips 503 with a
                 reason before the first step and after a halt).

Goodput here is the MegaScale-style ratio: seconds spent in steps that
actually advanced the model (skipped non-finite steps excluded,
checkpoint time excluded) over wall seconds since the trainer came up —
checkpoint/restore time is attributed to its own counters so a low
ratio says WHERE the time went, not just that it went.

An `AnomalyMonitor` (utils/anomaly.py) rides the same stream:
NaN/Inf loss, loss spikes, grad-norm explosions and throughput
collapses each fire one structured event into `events.jsonl`, increment
`oryx_anomaly_total{kind=...}`, and — under `--on-anomaly=halt` — raise
`AnomalyHalt` out of `Trainer.fit()`.
"""

from __future__ import annotations

import time
from typing import Any

import numpy as np

from oryx_tpu.utils import flops as flops_lib
from oryx_tpu.utils.anomaly import (
    AnomalyHalt,
    AnomalyMonitor,
    AnomalyThresholds,
)
from oryx_tpu.utils.metrics import (
    Registry,
    TelemetryServer,
    register_device_memory_collector,
    register_process_collector,
)

# Step wall-clock ladder (seconds): tiny CPU smoke steps to multi-minute
# 34B steps.
STEP_TIME_BUCKETS = (0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 30.0,
                     60.0, 120.0, 300.0)

# The trainer's whole scrape surface, one place: keep this list in sync
# with docs/OBSERVABILITY.md.
TRAIN_GAUGES = (
    "loss", "grad_norm", "lr", "tokens_per_sec", "tokens_per_sec_per_chip",
    "mfu", "model_flops_per_sec", "goodput_ratio", "last_step",
    "trainable_params", "differentiated_params",
)


class TrainTelemetry:
    """Registry + exporter + anomaly monitor for one Trainer.

    Construct with `port` (0 = ephemeral, see `.port`) to serve HTTP, or
    `port=None` for a registry-only instance (tests, offline use). All
    recording is host-side floats — nothing here touches the device
    except the scrape-time HBM collector."""

    def __init__(
        self,
        *,
        port: int | None = 0,
        host: str = "127.0.0.1",
        registry: Registry | None = None,
        events_path: str | None = None,
        thresholds: AnomalyThresholds | None = None,
        on_anomaly: str = "warn",
    ):
        if on_anomaly not in ("warn", "halt"):
            raise ValueError(
                f"on_anomaly must be 'warn' or 'halt', got {on_anomaly!r}"
            )
        self.on_anomaly = on_anomaly
        self.registry = registry or Registry(prefix="oryx_train")
        register_process_collector(self.registry)
        register_device_memory_collector(self.registry)
        self.anomaly = AnomalyMonitor(
            source="train", thresholds=thresholds,
            events_path=events_path, registry=self.registry,
        )
        r = self.registry
        # Names come from the TRAIN_GAUGES literal table above — the
        # greppable declaration the metric-name rule wants lives there.
        self._gauges = {name: r.gauge(name) for name in TRAIN_GAUGES}  # oryxlint: disable=metric-name
        self._steps = r.counter("steps_total")
        self._skipped = r.counter("skipped_steps_total")
        self._tokens = r.counter("tokens_total")
        self._checkpoints = r.counter("checkpoints_total")
        self._step_time = r.histogram(
            "step_time_seconds", STEP_TIME_BUCKETS
        )
        # Wall-time attribution counters: productive + checkpoint +
        # restore + data-wait never exceed wall; the remainder is
        # startup/compile/stall — exactly the split a goodput
        # regression needs to be debuggable from one scrape.
        self._phase = {
            k: r.counter(f"{k}_seconds_total")  # oryxlint: disable=metric-name
            for k in ("productive", "checkpoint", "restore",
                      "data_wait", "h2d", "dispatch", "device_sync",
                      "log")
        }
        self._t0 = time.perf_counter()
        self._ready = False
        self._ready_reason = "training loop not started"
        self.server: TelemetryServer | None = None
        if port is not None:
            self.server = TelemetryServer(
                self.registry, host=host, port=port,
                ready_check=lambda: (self._ready, self._ready_reason),
            ).start()

    @property
    def port(self) -> int | None:
        return self.server.port if self.server else None

    def mark_ready(self, ready: bool = True,
                   reason: str = "ok") -> None:
        self._ready, self._ready_reason = ready, reason

    def record_restore(self, seconds: float) -> None:
        self._phase["restore"].inc(max(0.0, seconds))

    def record_numerics(
        self,
        step: int,
        metrics: dict[str, Any],
        *,
        layer_absmax=None,
    ) -> list:
        """Publish one sampled numerics probe (utils/numerics.py via
        train_step_fn's static `numerics` flag): the absmax scalars as
        raw-named oryx_numerics_* gauges (the SAME family names the
        serving registry publishes — one dashboard row covers both),
        the per-layer grad absmax as a layer-labeled gauge, and the
        absmax_explosion sentinel. Returns the anomalies fired, after
        honoring the halt policy like record_step."""
        r = self.registry
        grad_absmax = metrics.get("grad_absmax")
        for name, fam in (
            ("grad_absmax", r.gauge(
                "oryx_numerics_grad_absmax", raw_name=True
            )),
            ("act_absmax", r.gauge(
                "oryx_numerics_act_absmax", raw_name=True
            )),
            ("param_absmax", r.gauge(
                "oryx_numerics_param_absmax", raw_name=True
            )),
        ):
            v = metrics.get(name)
            if v is not None:
                v = float(v)
                fam.set(v if np.isfinite(v) else float("nan"))
        r.counter("oryx_numerics_samples_total", raw_name=True).inc()
        if layer_absmax is not None:
            fam = r.gauge(
                "oryx_numerics_grad_layer_absmax", ("layer",),
                raw_name=True,
            )
            for i, v in enumerate(np.asarray(layer_absmax).tolist()):
                fam.labels(layer=str(i)).set(float(v))
        events = self.anomaly.observe_numerics(
            absmax=(
                float(grad_absmax) if grad_absmax is not None else None
            ),
            step=step,
        )
        if events and self.on_anomaly == "halt":
            self.mark_ready(False, f"halted: {events[0].kind}")
            raise AnomalyHalt(events)
        return events

    def record_step(
        self,
        step: int,
        metrics: dict[str, Any],
        *,
        step_seconds: float,
        data_s: float = 0.0,
        h2d_s: float = 0.0,
        dispatch_s: float = 0.0,
        sync_s: float = 0.0,
        log_s: float = 0.0,
        checkpoint_s: float = 0.0,
        flops: float | None = None,
        lr: float | None = None,
    ) -> list:
        """Publish one completed step; returns anomalies fired (after
        raising AnomalyHalt when the policy says so)."""
        import jax

        g = self._gauges
        loss = float(metrics.get("loss", float("nan")))
        tokens = int(metrics.get("num_tokens", 0))
        skipped = bool(int(metrics.get("skipped", 0)))
        n_chips = max(1, jax.device_count())
        dt = max(step_seconds, 1e-9)
        tps = tokens / dt

        g["loss"].set(loss if np.isfinite(loss) else float("nan"))
        # grad_norm: every step; the two counts: the first record only.
        for name in ("grad_norm", "trainable_params",
                     "differentiated_params"):
            if name in metrics:
                g[name].set(float(metrics[name]))
        if lr is not None:
            g["lr"].set(float(lr))
        g["tokens_per_sec"].set(tps)
        g["tokens_per_sec_per_chip"].set(tps / n_chips)
        g["last_step"].set(step)
        self._steps.inc()
        self._tokens.inc(tokens)
        if skipped:
            self._skipped.inc()
        self._step_time.observe(step_seconds)
        self._phase["data_wait"].inc(max(0.0, data_s))
        self._phase["h2d"].inc(max(0.0, h2d_s))
        self._phase["dispatch"].inc(max(0.0, dispatch_s))
        self._phase["device_sync"].inc(max(0.0, sync_s))
        self._phase["log"].inc(max(0.0, log_s))
        if checkpoint_s > 0:
            self._phase["checkpoint"].inc(checkpoint_s)
            self._checkpoints.inc()
        # Productive = the step's own wall time, checkpoint excluded —
        # and only when the step actually advanced the params.
        if not skipped:
            self._phase["productive"].inc(
                max(0.0, step_seconds - checkpoint_s)
            )
        wall = max(time.perf_counter() - self._t0, 1e-9)
        g["goodput_ratio"].set(
            min(1.0, self._phase["productive"].value / wall)
        )
        if flops is not None:
            rate = flops / dt
            g["model_flops_per_sec"].set(rate)
            peak = flops_lib.chip_peak_flops(
                getattr(jax.devices()[0], "device_kind", "")
            )
            # Unknown peak (CPU, exotic backends): MFU pinned to 0
            # rather than absent — scrape gates can assert the series
            # exists, dashboards read 0 as "not a TPU", and we never
            # fake a utilization number we can't defend.
            g["mfu"].set(rate / (n_chips * peak) if peak else 0.0)
        events = self.anomaly.observe_train_step(
            step, loss,
            grad_norm=metrics.get("grad_norm"),
            tokens_per_sec=tps if tokens else None,
        )
        if events and self.on_anomaly == "halt":
            self.mark_ready(False, f"halted: {events[0].kind}")
            raise AnomalyHalt(events)
        return events

    def close(self) -> None:
        if self.server is not None:
            self.server.close()
            self.server = None
        self.anomaly.close()


def batch_flops(cfg, host_batch: dict[str, Any]) -> float:
    """Model FLOPs for one step over a host batch (padded shapes — the
    device computes padding too, and MFU measures device work).

    A 3-D token_ids is [accum, B, T] (data.collate_microbatches): each
    microbatch runs its OWN vision tower over its own packed buffer, so
    the per-microbatch flops multiply by accum — flattening accum into
    the patch count would square-law-inflate the vision attention term."""
    tok = np.asarray(host_batch["token_ids"]).shape
    if len(tok) >= 3:
        accum, batch, seq = int(tok[0]), int(np.prod(tok[1:-1])), int(tok[-1])
    else:
        accum, batch, seq = 1, int(np.prod(tok[:-1]) or 1), int(tok[-1])
    seg = host_batch.get("segment_ids")
    patch_tokens = int(np.asarray(seg).shape[-1]) if seg is not None else 0
    return accum * flops_lib.train_step_flops(
        cfg, flops_lib.count_llm_params(cfg.llm),
        batch=batch, seq_len=seq, patch_tokens=patch_tokens,
    )
