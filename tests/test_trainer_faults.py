"""Trainer failure containment under injected faults: checkpoint-save
retry with a pinned backoff schedule, data-loader skip-and-requeue,
corrupt-batch -> skip_nonfinite, and the headline scenario — a mid-run
crash auto-resumes from the last good checkpoint with a bit-identical
loss trajectory."""

import dataclasses
import json

import numpy as np
import pytest

import jax

from oryx_tpu import config as cfg_lib
from oryx_tpu.constants import IGNORE_INDEX, IMAGE_TOKEN_INDEX
from oryx_tpu.models import splice
from oryx_tpu.ops import packing
from oryx_tpu.train.trainer import Trainer
from oryx_tpu.utils import faults
from oryx_tpu.utils.checkpoint import (
    CheckpointManager,
    save_projector_only,
)
from oryx_tpu.utils.retry import BackoffPolicy


@pytest.fixture(autouse=True)
def _clean_faults():
    faults.reset()
    yield
    faults.reset()


def _cfg(tmp_path, name, *, steps=4, ckpt_every=1):
    cfg = cfg_lib.oryx_tiny()
    return dataclasses.replace(
        cfg,
        mesh=cfg_lib.MeshConfig(dp=2, fsdp=4, tp=1, sp=1),
        train=dataclasses.replace(
            cfg.train,
            num_train_steps=steps, log_every=1,
            checkpoint_every=ckpt_every,
            checkpoint_dir=str(tmp_path / name),
        ),
    )


def _batch(cfg, seed):
    """One deterministic multimodal batch; distinct `seed`s make the
    loss trajectory step-dependent (a resume mismatch cannot hide)."""
    rng = np.random.default_rng(seed)
    p = cfg.vision.patch_size
    imgs = [
        rng.standard_normal((2 * p, 2 * p, 3)).astype(np.float32)
        for _ in range(8)
    ]
    packed = packing.pack_images(
        imgs, patch_size=p, base_grid=cfg.vision.base_grid,
        side_factors=1, buckets=(64, 256),
    )
    slots = splice.query_slots(packed)
    ids, labels = [], []
    for _ in range(8):
        row = np.concatenate(
            [[5, IMAGE_TOKEN_INDEX], rng.integers(3, 500, 6)]
        )
        lab = np.full(row.shape, IGNORE_INDEX, np.int64)
        lab[-6:] = row[-6:]
        ids.append(row)
        labels.append(lab)
    mm = splice.build_mm_batch(ids, slots, labels=labels, buckets=(16, 64))
    return {
        "patches": packed.patches, "segment_ids": packed.segment_ids,
        "pos_coords": packed.pos_coords, "region_ids": packed.region_ids,
        "q_region_ids": packed.q_region_ids, "token_ids": mm.token_ids,
        "visual_idx": mm.visual_idx, "is_visual": mm.is_visual,
        "attn_mask": mm.attn_mask, "positions": mm.positions,
        "labels": mm.labels,
    }


def _batches(cfg, n):
    return [_batch(cfg, seed=100 + i) for i in range(n)]


def _losses(metrics_path) -> dict[int, float]:
    out = {}
    for line in metrics_path.read_text().splitlines():
        rec = json.loads(line)
        out[rec["step"]] = rec["loss"]
    return out


# ---------------------------------------------------------------------------
# Checkpoint-save retry (no trainer needed: manager-level)
# ---------------------------------------------------------------------------


def test_checkpoint_save_retries_injected_failures(tmp_path):
    slept = []
    mgr = CheckpointManager(
        str(tmp_path / "ck"),
        save_retry=BackoffPolicy(retries=3, base_s=0.5, factor=2.0,
                                 jitter=0.0),
        sleep=slept.append,
    )
    faults.configure("checkpoint_save:times=2")
    state = {"x": np.arange(8, dtype=np.float32)}
    assert mgr.save(1, state) is True
    mgr.wait()
    assert mgr.save_retries == 2
    assert slept == [0.5, 1.0]  # pinned schedule, no wall clock
    assert mgr.latest_step() == 1
    restored = mgr.restore(None)
    np.testing.assert_array_equal(np.asarray(restored["x"]), state["x"])
    mgr.close()


def test_checkpoint_save_budget_exhaustion_raises(tmp_path):
    slept = []
    mgr = CheckpointManager(
        str(tmp_path / "ck2"),
        save_retry=BackoffPolicy(retries=2, base_s=0.1, jitter=0.0),
        sleep=slept.append,
    )
    faults.configure("checkpoint_save:times=10")  # > budget: permanent
    with pytest.raises(faults.FaultInjected):
        mgr.save(1, {"x": np.zeros(2)})
    assert slept == [0.1, 0.2]  # the full bounded budget was spent
    assert mgr.latest_step() is None
    mgr.close()


def test_projector_save_is_atomic(tmp_path):
    cfg = cfg_lib.oryx_tiny()
    from oryx_tpu.models import oryx

    params = oryx.init_params(cfg, jax.random.key(0))
    path = tmp_path / "proj.npz"
    save_projector_only(str(path), params)
    assert path.exists()
    leftovers = [p for p in tmp_path.iterdir() if ".tmp" in p.name]
    assert not leftovers, leftovers
    data = np.load(path)
    assert len(data.files) > 0


# ---------------------------------------------------------------------------
# Data-loader containment
# ---------------------------------------------------------------------------


def test_data_fault_skips_and_preserves_trajectory(tmp_path):
    """A transient loader failure retries the SAME fetch (nothing was
    consumed), so the run completes with the exact fault-free loss
    trajectory — containment that provably changes nothing."""
    if jax.device_count() < 8:
        pytest.skip("needs the 8-device CPU mesh (conftest)")
    cfg = _cfg(tmp_path, "clean", steps=2, ckpt_every=100)
    mpath = tmp_path / "clean.jsonl"
    t = Trainer(cfg, sharding_mode="fsdp", metrics_path=str(mpath))
    t.fit(iter(_batches(cfg, 2)), num_steps=2, resume=False, prefetch=0)
    t.close()
    clean = _losses(mpath)

    cfg2 = _cfg(tmp_path, "faulted", steps=2, ckpt_every=100)
    mpath2 = tmp_path / "faulted.jsonl"
    faults.configure("data_loader_next:after=1")  # 2nd fetch fails once
    t2 = Trainer(cfg2, sharding_mode="fsdp", metrics_path=str(mpath2))
    t2.fit(iter(_batches(cfg2, 2)), num_steps=2, resume=False, prefetch=0)
    t2.close()
    assert t2.data_faults == 1
    assert faults.injected_count("data_loader_next") == 1
    assert _losses(mpath2) == clean  # bit-identical despite the fault


def test_data_fault_budget_exhaustion_aborts(tmp_path):
    if jax.device_count() < 8:
        pytest.skip("needs the 8-device CPU mesh (conftest)")
    cfg = _cfg(tmp_path, "deadloader", steps=2, ckpt_every=100)
    faults.configure("data_loader_next:every=1")  # permanently broken
    t = Trainer(cfg, sharding_mode="fsdp", max_data_faults=3)
    with pytest.raises(RuntimeError, match="consecutive data-loader"):
        t.fit(iter(_batches(cfg, 2)), num_steps=2, resume=False,
              prefetch=0)
    t.close()
    assert t.data_faults == 3


def test_corrupt_batch_hits_skip_guard(tmp_path):
    """corrupt=1 at the loader site NaNs one float leaf; the
    skip_nonfinite guard skips the step instead of training on it."""
    if jax.device_count() < 8:
        pytest.skip("needs the 8-device CPU mesh (conftest)")
    cfg = _cfg(tmp_path, "poisoned", steps=1, ckpt_every=100)
    cfg = dataclasses.replace(
        cfg, train=dataclasses.replace(
            cfg.train, skip_nonfinite_steps=True
        ),
    )
    mpath = tmp_path / "poisoned.jsonl"
    faults.configure("data_loader_next:corrupt=1,times=1")
    t = Trainer(cfg, sharding_mode="fsdp", metrics_path=str(mpath))
    t.fit(iter(_batches(cfg, 1)), num_steps=1, resume=False, prefetch=0)
    t.close()
    rec = json.loads(mpath.read_text().splitlines()[-1])
    assert rec["skipped"] == 1


# ---------------------------------------------------------------------------
# The headline: injected mid-run crash -> auto-resume, bit-identical
# ---------------------------------------------------------------------------


def test_injected_crash_auto_resumes_bit_identical(tmp_path):
    if jax.device_count() < 8:
        pytest.skip("needs the 8-device CPU mesh (conftest)")
    steps = 4
    # Reference: uninterrupted 4-step run.
    cfg_a = _cfg(tmp_path, "uninterrupted", steps=steps)
    mpath_a = tmp_path / "a.jsonl"
    ta = Trainer(cfg_a, sharding_mode="fsdp", metrics_path=str(mpath_a))
    ta.fit(iter(_batches(cfg_a, steps)), num_steps=steps, resume=False,
           prefetch=0)
    ta.close()
    ref = _losses(mpath_a)
    assert sorted(ref) == [1, 2, 3, 4]
    assert len({ref[s] for s in ref}) > 1, (
        "trajectory must be step-dependent for the comparison to mean "
        "anything"
    )

    # Crash run: the process dies at the top of step 3 (checkpoints at
    # 1 and 2 already on disk — checkpoint_every=1).
    cfg_b = _cfg(tmp_path, "crashed", steps=steps)
    mpath_b = tmp_path / "b.jsonl"
    faults.configure("trainer_crash:after=2")
    tb = Trainer(cfg_b, sharding_mode="fsdp", metrics_path=str(mpath_b))
    with pytest.raises(faults.FaultInjected):
        tb.fit(iter(_batches(cfg_b, steps)), num_steps=steps,
               resume=False, prefetch=0)
    # Flush the async save pipeline so "last good checkpoint" is
    # deterministic (orbax's temp+rename means a genuinely torn save
    # would be invisible to latest_step, which is the same guarantee).
    tb.ckpt.wait()
    tb.close()
    assert faults.injected_count("trainer_crash") == 1
    faults.reset()

    # The restart path: a FRESH Trainer on the same checkpoint_dir
    # auto-resumes from the last good step and replays the remaining
    # data (the loader is re-seekable; steps 1-2's batches skipped).
    mpath_c = tmp_path / "c.jsonl"
    tc = Trainer(cfg_b, sharding_mode="fsdp", metrics_path=str(mpath_c))
    start = tc.resume_if_available()
    assert start == 2, "must resume from the last completed checkpoint"
    tc.fit(iter(_batches(cfg_b, steps)[start:]), num_steps=steps,
           resume=True, prefetch=0)
    tc.close()

    got = {**_losses(mpath_b), **_losses(mpath_c)}
    assert sorted(got) == [1, 2, 3, 4]
    for s in (1, 2, 3, 4):
        assert got[s] == ref[s], (
            f"step {s}: loss {got[s]!r} != uninterrupted {ref[s]!r}"
        )
