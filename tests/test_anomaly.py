"""Anomaly detectors (utils/anomaly.py): synthetic NaN / spike /
collapse streams fire exactly-one structured events (JSONL sink +
oryx_anomaly_total{kind=} counter), a steady stream fires nothing, and
the SLO detectors re-arm with hysteresis."""

import json
import math

import numpy as np
import pytest

from oryx_tpu.utils.anomaly import (
    AnomalyHalt,
    AnomalyMonitor,
    AnomalyThresholds,
)
from oryx_tpu.utils.metrics import Registry


def _events(path) -> list[dict]:
    with open(path) as f:
        return [json.loads(line) for line in f if line.strip()]


def test_nan_loss_stream_exactly_one_event(tmp_path):
    """Acceptance: a synthetic NaN-loss stream -> exactly one nan_loss
    event in events.jsonl plus oryx_anomaly_total{kind="nan_loss"} == 1."""
    path = tmp_path / "events.jsonl"
    reg = Registry(prefix="oryx_train")
    mon = AnomalyMonitor(source="train", events_path=str(path), registry=reg)
    for step in range(1, 21):
        loss = 2.0 if step < 5 else float("nan")
        mon.observe_train_step(step, loss)
    evs = _events(path)
    assert len(evs) == 1
    ev = evs[0]
    assert ev["kind"] == "nan_loss"
    assert ev["source"] == "train"
    assert ev["value"] is None  # NaN serializes as RFC-strict null
    assert ev["context"]["step"] == 5
    assert "time_unix_s" in ev and "message" in ev
    assert 'oryx_anomaly_total{kind="nan_loss"} 1' in reg.render()
    mon.close()


def test_nan_loss_rearms_after_recovery(tmp_path):
    path = tmp_path / "events.jsonl"
    mon = AnomalyMonitor(events_path=str(path))
    stream = [1.0, float("nan"), float("nan"), 1.0, float("inf")]
    for i, loss in enumerate(stream):
        mon.observe_train_step(i, loss)
    kinds = [e["kind"] for e in _events(path)]
    assert kinds == ["nan_loss", "nan_loss"]  # one per episode, not per step


def test_steady_stream_no_false_positives(tmp_path):
    """A noisy-but-healthy run must stay silent: loss wandering within
    2x, grad norms within 3x, throughput within 30%."""
    path = tmp_path / "events.jsonl"
    mon = AnomalyMonitor(events_path=str(path))
    rng = np.random.default_rng(0)
    for step in range(200):
        fired = mon.observe_train_step(
            step,
            loss=2.0 + 0.3 * rng.standard_normal(),
            grad_norm=1.0 + 0.2 * abs(rng.standard_normal()),
            tokens_per_sec=1000.0 * (1 + 0.15 * rng.standard_normal()),
        )
        assert fired == []
    assert not path.exists() or _events(path) == []
    assert mon.total == 0


def test_loss_spike_one_shot():
    mon = AnomalyMonitor(thresholds=AnomalyThresholds(min_window=4))
    for step in range(10):
        assert mon.observe_train_step(step, 1.0) == []
    fired = mon.observe_train_step(10, 50.0)
    assert [e.kind for e in fired] == ["loss_spike"]
    assert fired[0].value == 50.0
    assert fired[0].threshold == pytest.approx(3.0)  # 3x median 1.0
    # Still elevated: no re-fire until it drops back under the line.
    assert mon.observe_train_step(11, 49.0) == []


def test_cold_start_spike_silent():
    """min_window unmet: a wild early loss must not alert (step-1
    losses are routinely 10x the converged value)."""
    mon = AnomalyMonitor(thresholds=AnomalyThresholds(min_window=8))
    assert mon.observe_train_step(0, 1.0) == []
    assert mon.observe_train_step(1, 100.0) == []


def test_grad_norm_explosion():
    mon = AnomalyMonitor(thresholds=AnomalyThresholds(min_window=4))
    for step in range(8):
        mon.observe_train_step(step, 1.0, grad_norm=0.5)
    fired = mon.observe_train_step(8, 1.0, grad_norm=500.0)
    assert [e.kind for e in fired] == ["grad_norm_explosion"]


def test_throughput_collapse_does_not_rebaseline():
    """Collapsed samples must NOT enter the rolling window — otherwise
    the median drifts down onto the collapsed level and a permanently
    degraded run stops looking anomalous."""
    mon = AnomalyMonitor(thresholds=AnomalyThresholds(min_window=4))
    for step in range(10):
        mon.observe_train_step(step, 1.0, tokens_per_sec=1000.0)
    fired = mon.observe_train_step(10, 1.0, tokens_per_sec=10.0)
    assert [e.kind for e in fired] == ["throughput_collapse"]
    for step in range(11, 40):
        assert mon.observe_train_step(step, 1.0, tokens_per_sec=10.0) == []
    # Window median still reflects the healthy regime.
    assert mon._tput.median() == pytest.approx(1000.0)
    # Recovery re-arms; a second collapse fires a second event.
    mon.observe_train_step(40, 1.0, tokens_per_sec=900.0)
    fired = mon.observe_train_step(41, 1.0, tokens_per_sec=5.0)
    assert [e.kind for e in fired] == ["throughput_collapse"]
    assert mon.counts["throughput_collapse"] == 2


def test_ttft_slo_disabled_by_default_and_rearms():
    mon = AnomalyMonitor(source="serve")
    assert mon.observe_ttft(999.0) == []  # no SLO configured -> silent
    mon = AnomalyMonitor(
        source="serve",
        thresholds=AnomalyThresholds(ttft_slo_s=1.0),
    )
    assert [e.kind for e in mon.observe_ttft(2.0, request_id="r1")] == [
        "ttft_slo"
    ]
    assert mon.observe_ttft(3.0) == []  # still breached: one per episode
    assert mon.observe_ttft(0.5) == []  # compliant -> re-arm
    assert [e.kind for e in mon.observe_ttft(2.0)] == ["ttft_slo"]


def test_queue_depth_slo_one_rearms_on_drain():
    """slo=1 regression: the drain-side observation (depth 0) must
    re-arm the detector — with submit-only feeding it never could."""
    mon = AnomalyMonitor(
        source="serve",
        thresholds=AnomalyThresholds(queue_depth_slo=1),
    )
    assert [e.kind for e in mon.observe_queue_depth(2)] == [
        "queue_depth_slo"
    ]
    assert mon.observe_queue_depth(0) == []  # scheduler drained
    assert [e.kind for e in mon.observe_queue_depth(2)] == [
        "queue_depth_slo"
    ]


def test_queue_depth_hysteresis():
    mon = AnomalyMonitor(
        source="serve",
        thresholds=AnomalyThresholds(queue_depth_slo=10),
    )
    assert [e.kind for e in mon.observe_queue_depth(11)] == [
        "queue_depth_slo"
    ]
    assert mon.observe_queue_depth(12) == []
    # Dropping just under the SLO does not re-arm (oscillation guard)...
    assert mon.observe_queue_depth(9) == []
    assert mon.observe_queue_depth(11) == []
    # ...draining to half does.
    assert mon.observe_queue_depth(5) == []
    assert [e.kind for e in mon.observe_queue_depth(11)] == [
        "queue_depth_slo"
    ]


def test_event_jsonl_is_rfc_strict(tmp_path):
    """Every sink line must json.loads cleanly (jq/JSON.parse consumers)
    even when the payload is the non-finite value itself."""
    path = tmp_path / "events.jsonl"
    mon = AnomalyMonitor(events_path=str(path))
    mon.observe_train_step(1, float("inf"))
    raw = path.read_text()
    assert "Infinity" not in raw and "NaN" not in raw
    assert _events(path)[0]["value"] is None


def test_halt_policy_via_train_telemetry(tmp_path):
    """--on-anomaly=halt: the first anomaly raises AnomalyHalt out of
    record_step (and the exporter flips /readyz not-ready)."""
    from oryx_tpu.train.telemetry import TrainTelemetry

    tel = TrainTelemetry(
        port=None, events_path=str(tmp_path / "ev.jsonl"),
        on_anomaly="halt",
    )
    tel.mark_ready()
    tel.record_step(1, {"loss": 2.0, "num_tokens": 10}, step_seconds=0.1)
    with pytest.raises(AnomalyHalt) as ei:
        tel.record_step(
            2, {"loss": float("nan"), "num_tokens": 10}, step_seconds=0.1
        )
    assert ei.value.events[0].kind == "nan_loss"
    assert tel._ready is False and "halted" in tel._ready_reason
    assert len(_events(tmp_path / "ev.jsonl")) == 1
    tel.close()

    with pytest.raises(ValueError, match="on_anomaly"):
        TrainTelemetry(port=None, on_anomaly="explode")


def test_warn_policy_keeps_training(tmp_path):
    from oryx_tpu.train.telemetry import TrainTelemetry

    tel = TrainTelemetry(port=None, on_anomaly="warn")
    evs = tel.record_step(
        1, {"loss": float("nan"), "num_tokens": 10}, step_seconds=0.1
    )
    assert [e.kind for e in evs] == ["nan_loss"]
    assert math.isnan(tel.registry.get("loss"))
    assert 'oryx_anomaly_total{kind="nan_loss"} 1' in tel.registry.render()
    tel.close()


def test_events_jsonl_size_capped_rotation(tmp_path):
    """The sink must not grow without bound: past events_max_bytes the
    file rolls to events.jsonl.1 and a fresh file starts. Both files
    stay valid JSONL, the live file stays under ~cap + one event, and
    the newest event is in the live file."""
    path = tmp_path / "events.jsonl"
    mon = AnomalyMonitor(
        source="serve",
        thresholds=AnomalyThresholds(ttft_slo_s=1.0),
        events_path=str(path),
        events_max_bytes=400,
    )
    for i in range(20):
        fired = mon.observe_ttft(2.0, request_id=f"req-{i:02d}")
        assert len(fired) == 1  # re-armed below, so every breach fires
        mon.observe_ttft(0.1)  # clear -> re-arm
    mon.close()
    assert mon.counts["ttft_slo"] == 20
    rolled = tmp_path / "events.jsonl.1"
    assert rolled.exists(), "rotation never rolled to events.jsonl.1"
    live, old = _events(path), _events(rolled)
    for ev in live + old:  # every surviving line is a whole event
        assert ev["kind"] == "ttft_slo"
    # The live file was rotated down: bounded by the cap plus at most
    # the one event whose write crossed it.
    assert path.stat().st_size < 400 + 300
    assert any(
        ev["context"]["request_id"] == "req-19" for ev in live + old
    ), "the newest event was lost in rotation"
    # Rotation preserves ordering: old file's events all precede the
    # live file's.
    if live and old:
        assert old[-1]["time_unix_s"] <= live[0]["time_unix_s"]


def test_events_jsonl_rotation_disabled_with_zero_cap(tmp_path):
    path = tmp_path / "events.jsonl"
    mon = AnomalyMonitor(
        source="serve",
        thresholds=AnomalyThresholds(ttft_slo_s=1.0),
        events_path=str(path),
        events_max_bytes=0,
    )
    for _ in range(10):
        mon.observe_ttft(2.0)
        mon.observe_ttft(0.1)
    mon.close()
    assert not (tmp_path / "events.jsonl.1").exists()
    assert len(_events(path)) == 10


# ---------------------------------------------------------------------------
# Numerics & output-quality sentinels (ISSUE 14)
# ---------------------------------------------------------------------------


def test_entropy_collapse_one_shot_no_rebaseline():
    """A collapsing logits entropy fires once per episode, collapsed
    values never enter the rolling window (no silent re-baselining),
    and a recovery re-arms."""
    mon = AnomalyMonitor(
        source="serve",
        thresholds=AnomalyThresholds(min_window=4, entropy_floor_frac=0.5),
    )
    for _ in range(6):
        assert mon.observe_numerics(entropy=4.0) == []
    fired = mon.observe_numerics(entropy=0.5)
    assert [e.kind for e in fired] == ["entropy_collapse"]
    # Still collapsed: silent (episode), and the window median is
    # untouched by the collapsed samples.
    for _ in range(10):
        assert mon.observe_numerics(entropy=0.4) == []
    assert mon.counts["entropy_collapse"] == 1
    # Recovery re-arms; a second collapse is a second episode.
    for _ in range(3):
        assert mon.observe_numerics(entropy=4.0) == []
    assert [e.kind for e in mon.observe_numerics(entropy=0.3)] == [
        "entropy_collapse"
    ]
    assert mon.counts["entropy_collapse"] == 2
    mon.close()


def test_absmax_explosion_spikes_enter_window():
    """absmax mirrors grad_norm_explosion: one event per episode, and
    spikes DO enter the window (a genuinely higher plateau becomes the
    baseline instead of firing forever)."""
    mon = AnomalyMonitor(
        source="serve",
        thresholds=AnomalyThresholds(min_window=4, absmax_factor=4.0),
    )
    for _ in range(6):
        assert mon.observe_numerics(absmax=10.0) == []
    fired = mon.observe_numerics(absmax=100.0)
    assert [e.kind for e in fired] == ["absmax_explosion"]
    assert mon.observe_numerics(absmax=100.0) == []  # same episode
    # Keep feeding the new plateau: it enters the window, the median
    # climbs, and the detector stops considering it anomalous.
    for _ in range(12):
        mon.observe_numerics(absmax=100.0)
    assert mon.observe_numerics(absmax=100.0) == []
    assert mon.counts["absmax_explosion"] == 1
    mon.close()


def test_audit_drift_episode_semantics():
    mon = AnomalyMonitor(source="serve")
    assert [e.kind for e in mon.observe_audit("drift")] == ["audit_drift"]
    assert mon.observe_audit("fail") == []  # same episode
    assert mon.observe_audit("pass") == []  # re-arms
    assert [e.kind for e in mon.observe_audit("fail")] == ["audit_drift"]
    assert mon.counts["audit_drift"] == 2
    ev = mon.recent[-1]
    assert ev.context["verdict"] == "fail"
    mon.close()


def test_spec_accept_collapse_rolling_baseline():
    """Accept-rate off its own rolling baseline: one event per
    collapse episode, collapsed rates stay out of the window, recovery
    re-arms — and a drafter that was never good (baseline ~1.0) can
    never fire (1.0 is the floor of the signal)."""
    mon = AnomalyMonitor(
        source="serve",
        thresholds=AnomalyThresholds(
            min_window=4, spec_accept_floor_frac=0.5,
        ),
    )
    for _ in range(8):
        assert mon.observe_spec_accept(4.0) == []
    fired = mon.observe_spec_accept(1.0)
    assert [e.kind for e in fired] == ["spec_accept_collapse"]
    for _ in range(5):
        assert mon.observe_spec_accept(1.0) == []
    assert mon.counts["spec_accept_collapse"] == 1
    for _ in range(3):
        assert mon.observe_spec_accept(4.0) == []
    assert [e.kind for e in mon.observe_spec_accept(1.5)] == [
        "spec_accept_collapse"
    ]
    mon.close()
    # Never-good drafter: baseline 1.0, rate can't go below 0.5x it.
    mon2 = AnomalyMonitor(source="serve")
    for _ in range(40):
        assert mon2.observe_spec_accept(1.0) == []
    assert mon2.counts.get("spec_accept_collapse", 0) == 0
    mon2.close()
