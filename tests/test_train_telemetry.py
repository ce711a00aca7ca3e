"""Trainer telemetry exporter (train/telemetry.py): a 3-step CPU run
must expose the oryx_train_* series over live HTTP — scraped DURING the
run, monotone between scrapes — with /healthz and /readyz behaving like
a load balancer expects. Plus unit coverage of the goodput/MFU
accounting that doesn't need a real trainer."""

import dataclasses
import json
import urllib.error
import urllib.request

import numpy as np
import pytest

import jax

from oryx_tpu import config as cfg_lib
from oryx_tpu.train.telemetry import TrainTelemetry, batch_flops
from oryx_tpu.train.trainer import Trainer
from oryx_tpu.utils import flops as flops_lib

from tests.test_metrics_registry import parse_exposition
from tests.test_trainer_modes import _batch

REQUIRED_SERIES = (
    "oryx_train_loss",
    "oryx_train_tokens_per_sec",
    "oryx_train_mfu",
    "oryx_train_goodput_ratio",
    "oryx_train_hbm_live_bytes",
)


def _scrape(port: int) -> dict[str, float]:
    with urllib.request.urlopen(
        f"http://127.0.0.1:{port}/metrics", timeout=30
    ) as r:
        return parse_exposition(r.read().decode())


def _get_json(port: int, path: str):
    """(status, body) without raising on 503."""
    try:
        with urllib.request.urlopen(
            f"http://127.0.0.1:{port}{path}", timeout=30
        ) as r:
            return r.status, json.load(r)
    except urllib.error.HTTPError as e:
        return e.code, json.load(e)


def test_three_step_cpu_run_scrapes_live(tmp_path):
    """Acceptance: a 3-step CPU smoke train exposes
    oryx_train_{loss,tokens_per_sec,mfu,goodput_ratio,hbm_live_bytes}
    over HTTP, scraped while the step loop is running, and the step
    counter is monotone across scrapes."""
    if jax.device_count() < 8:
        pytest.skip("needs the 8-device CPU mesh (conftest)")
    base = cfg_lib.oryx_tiny()
    cfg = dataclasses.replace(
        base,
        mesh=cfg_lib.MeshConfig(dp=2, fsdp=4, tp=1, sp=1),
        train=dataclasses.replace(
            base.train, num_train_steps=3, log_every=1,
            checkpoint_every=100,
            checkpoint_dir=str(tmp_path / "ckpt"),
        ),
    )
    t = Trainer(
        cfg, metrics_port=0,
        events_path=str(tmp_path / "events.jsonl"),
    )
    assert t.telemetry is not None
    port = t.telemetry.port
    code, body = _get_json(port, "/readyz")
    assert code == 503 and body["ready"] is False  # loop not started yet
    assert _get_json(port, "/healthz") == (200, {"status": "ok"})

    host = _batch(cfg)
    scrapes: list[dict[str, float]] = []

    def feeding():
        # The iterator runs on the fit thread between steps — each
        # yield scrapes the exporter mid-run (steps 2 and 3 observe the
        # previous step's published state).
        for i in range(3):
            if i:
                scrapes.append(_scrape(port))
                code, body = _get_json(port, "/readyz")
                assert code == 200 and body["ready"] is True
            yield host
    try:
        t.fit(feeding(), num_steps=3, resume=False, prefetch=0)
        # The step loop is gone: /readyz must stop saying ready.
        code, body = _get_json(port, "/readyz")
        assert code == 503 and "exited" in body["reason"]
        scrapes.append(_scrape(port))
        final = scrapes[-1]
        for name in REQUIRED_SERIES:
            assert name in final, f"missing {name}"
        assert np.isfinite(final["oryx_train_loss"])
        assert final["oryx_train_tokens_per_sec"] > 0
        assert final["oryx_train_mfu"] == 0.0  # CPU: peak unknown, pinned 0
        assert final["oryx_train_model_flops_per_sec"] > 0
        assert 0 < final["oryx_train_goodput_ratio"] <= 1.0
        assert final["oryx_train_hbm_live_bytes"] > 0  # params are live
        assert final["oryx_train_steps_total"] == 3
        assert final["oryx_train_last_step"] == 3
        assert final["oryx_train_tokens_total"] > 0
        assert final["oryx_train_skipped_steps_total"] == 0
        assert final["oryx_train_step_time_seconds_count"] == 3
        assert final["oryx_train_productive_seconds_total"] > 0
        assert final["oryx_train_lr"] >= 0
        assert final["oryx_train_grad_norm"] > 0
        # Monotone across the in-run scrapes.
        steps_seen = [s["oryx_train_steps_total"] for s in scrapes]
        assert steps_seen == sorted(steps_seen)
        assert steps_seen[0] >= 1 and steps_seen[-1] == 3
        tokens_seen = [s["oryx_train_tokens_total"] for s in scrapes]
        assert tokens_seen == sorted(tokens_seen)
        # Every sample name carries a defensible prefix.
        for name in final:
            base_name = name.split("{")[0]
            assert base_name.startswith(("oryx_train_", "oryx_anomaly_")), \
                name
    finally:
        t.close()


def test_goodput_attribution_unit():
    tel = TrainTelemetry(port=None)
    tel.record_restore(2.0)
    tel.record_step(
        1, {"loss": 1.0, "num_tokens": 100}, step_seconds=1.0,
        data_s=0.2, h2d_s=0.04, dispatch_s=0.1, sync_s=0.6, log_s=0.01,
        checkpoint_s=0.25,
    )
    r = tel.registry
    assert r.get("productive_seconds_total") == pytest.approx(0.75)
    assert r.get("checkpoint_seconds_total") == pytest.approx(0.25)
    assert r.get("restore_seconds_total") == pytest.approx(2.0)
    assert r.get("data_wait_seconds_total") == pytest.approx(0.2)
    assert r.get("h2d_seconds_total") == pytest.approx(0.04)
    assert r.get("log_seconds_total") == pytest.approx(0.01)
    assert r.get("checkpoints_total") == 1
    ratio = r.get("goodput_ratio")
    assert 0 < ratio <= 1.0
    # A skipped step is wall time but NOT goodput.
    tel.record_step(
        2, {"loss": float("nan"), "num_tokens": 100, "skipped": 1},
        step_seconds=1.0,
    )
    assert r.get("productive_seconds_total") == pytest.approx(0.75)
    assert r.get("skipped_steps_total") == 1
    tel.close()


def test_mfu_math_with_known_peak(monkeypatch):
    """With a known chip peak the MFU gauge must equal
    flops / (dt * n_chips * peak) — pinned against the shared 6N model."""
    tel = TrainTelemetry(port=None)
    monkeypatch.setattr(
        flops_lib, "chip_peak_flops", lambda kind: 100e12
    )
    tel.record_step(
        1, {"loss": 1.0, "num_tokens": 100}, step_seconds=2.0,
        flops=40e12,
    )
    n_chips = jax.device_count()
    want = (40e12 / 2.0) / (n_chips * 100e12)
    assert tel.registry.get("mfu") == pytest.approx(want)
    assert tel.registry.get("model_flops_per_sec") == pytest.approx(20e12)
    tel.close()


def test_batch_flops_matches_bench_model():
    """train/telemetry.batch_flops and bench.model_flops_per_step must
    agree exactly — one 6N model, two callers."""
    import bench

    cfg = cfg_lib.oryx_tiny()
    host = _batch(cfg)
    n_llm = flops_lib.count_llm_params(cfg.llm)
    assert batch_flops(cfg, host) == pytest.approx(
        bench.model_flops_per_step(cfg, n_llm, host)
    )
    # The accum axis multiplies tokens AND patches.
    stacked = {k: np.asarray(v)[None] for k, v in host.items()}
    assert batch_flops(cfg, stacked) == pytest.approx(
        batch_flops(cfg, host)
    )
    two = {k: np.stack([v, v]) for k, v in host.items()}
    assert batch_flops(cfg, two) == pytest.approx(2 * batch_flops(cfg, host))


def test_trainer_without_telemetry_has_none(tmp_path):
    if jax.device_count() < 8:
        pytest.skip("needs the 8-device CPU mesh (conftest)")
    cfg = dataclasses.replace(
        cfg_lib.oryx_tiny(),
        mesh=cfg_lib.MeshConfig(dp=2, fsdp=4, tp=1, sp=1),
        train=dataclasses.replace(
            cfg_lib.oryx_tiny().train,
            checkpoint_dir=str(tmp_path / "ckpt"),
        ),
    )
    t = Trainer(cfg)
    assert t.telemetry is None
    t.close()
    # But asking for the halt policy must construct the monitor even
    # with no exporter port — a silently unprotected run is the failure
    # mode the flag exists to prevent.
    t = Trainer(cfg, on_anomaly="halt")
    assert t.telemetry is not None
    assert t.telemetry.server is None  # registry-only, no HTTP thread
    assert t.telemetry.on_anomaly == "halt"
    t.close()
