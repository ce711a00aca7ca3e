"""Trainer sharding-mode coverage on the 8-device CPU mesh: fsdp (ZeRO-3),
zero2 (params replicated, optimizer state sharded), ddp (all replicated)
— SURVEY.md §2b parallelism inventory."""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from oryx_tpu import config as cfg_lib
from oryx_tpu.constants import IGNORE_INDEX, IMAGE_TOKEN_INDEX
from oryx_tpu.models import splice
from oryx_tpu.ops import packing
from oryx_tpu.train.trainer import Trainer


def _cfg(tmp_path, mode_dir):
    cfg = cfg_lib.oryx_tiny()
    return dataclasses.replace(
        cfg,
        mesh=cfg_lib.MeshConfig(dp=2, fsdp=4, tp=1, sp=1),
        train=dataclasses.replace(
            cfg.train,
            num_train_steps=1, log_every=1, checkpoint_every=100,
            checkpoint_dir=str(tmp_path / mode_dir),
        ),
    )


def _batch(cfg, n=8):
    rng = np.random.default_rng(0)
    p = cfg.vision.patch_size
    imgs = [
        rng.standard_normal((2 * p, 2 * p, 3)).astype(np.float32)
        for _ in range(n)
    ]
    packed = packing.pack_images(
        imgs, patch_size=p, base_grid=cfg.vision.base_grid,
        side_factors=1, buckets=(64, 256),
    )
    slots = splice.query_slots(packed)
    ids, labels = [], []
    for _ in range(n):
        row = np.concatenate([[5, IMAGE_TOKEN_INDEX], rng.integers(3, 500, 6)])
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


def test_trainer_checkpoint_resume(tmp_path):
    """Failure posture (SURVEY.md §5): a fresh Trainer on the same
    checkpoint_dir resumes from the saved step and continues — the
    crashed-pod restart path."""
    if jax.device_count() < 8:
        pytest.skip("needs the 8-device CPU mesh (conftest)")
    cfg = _cfg(tmp_path, "resume")
    cfg = dataclasses.replace(
        cfg, train=dataclasses.replace(cfg.train, checkpoint_every=1)
    )
    b = _batch(cfg)
    t1 = Trainer(cfg, sharding_mode="fsdp")
    s1 = t1.fit(iter([b]), num_steps=1, resume=False, prefetch=0)
    assert int(jax.device_get(s1.step)) == 1

    t2 = Trainer(cfg, sharding_mode="fsdp")
    start = t2.resume_if_available()
    assert start == 1
    # Resumed params equal the step-1 params, not a fresh init.
    for a, c in zip(
        jax.tree_util.tree_leaves(s1.params),
        jax.tree_util.tree_leaves(t2.state.params),
    ):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(c))
    s2 = t2.fit(iter([b]), num_steps=2, resume=True, prefetch=0)
    assert int(jax.device_get(s2.step)) == 2


@pytest.mark.parametrize("mode", ["fsdp", "zero2", "ddp"])
def test_trainer_mode_one_step(tmp_path, mode):
    if jax.device_count() < 8:
        pytest.skip("needs the 8-device CPU mesh (conftest)")
    cfg = _cfg(tmp_path, mode)
    trainer = Trainer(cfg, sharding_mode=mode)
    batch = _batch(cfg)
    state = trainer.fit(iter([batch]), num_steps=1, resume=False,
                        prefetch=0)
    assert int(jax.device_get(state.step)) == 1
    # Param placement matches the mode: fsdp shards embed over the mesh;
    # zero2/ddp replicate params.
    embed = state.params["llm"]["embed"]["weight"]
    if mode == "fsdp":
        assert not embed.sharding.is_fully_replicated
    else:
        assert embed.sharding.is_fully_replicated
    # Optimizer moments shard over fsdp in both fsdp AND zero2 (ZeRO-2 =
    # replicated params + partitioned optimizer state); ddp replicates.
    embed_shape = embed.shape
    mu_like = [
        leaf for leaf in jax.tree_util.tree_leaves(state.opt_state)
        if getattr(leaf, "shape", None) == embed_shape
    ]
    assert mu_like, "no optimizer moment matching embed shape"
    if mode in ("fsdp", "zero2"):
        assert any(not m.sharding.is_fully_replicated for m in mu_like)
    else:
        assert all(m.sharding.is_fully_replicated for m in mu_like)


def test_trainer_step_traces_and_phase_metrics(tmp_path):
    """Observability: each step lands in the trainer's flight recorder
    with data/h2d/step_dispatch/device_sync phase spans, and the phase
    seconds ride the MetricLogger JSONL record."""
    import json

    if jax.device_count() < 8:
        pytest.skip("needs the 8-device CPU mesh (conftest)")
    cfg = _cfg(tmp_path, "traced")
    b = _batch(cfg)
    mpath = tmp_path / "metrics.jsonl"
    t = Trainer(cfg, sharding_mode="fsdp", metrics_path=str(mpath))
    t.fit(iter([b]), num_steps=1, resume=False, prefetch=0)

    traces = t.tracer.traces()
    assert len(traces) == 1
    tr = traces[0]
    assert tr.kind == "train_step" and tr.done
    assert tr.meta["step"] == 1
    names = [s.name for s in tr.spans]
    for want in ("data", "h2d", "step_dispatch", "device_sync"):
        assert want in names, names
    assert all(s.dur_ns is not None for s in tr.spans)

    rec = json.loads(mpath.read_text().splitlines()[-1])
    for key in ("data_s", "h2d_s", "dispatch_s", "sync_s", "log_s"):
        assert key in rec and rec[key] >= 0
    # the record's phases are the loop's time from the previous sync
    # (here: fit's start) to this step's: they cannot exceed the step
    assert 0 < rec["h2d_s"] + rec["dispatch_s"] + rec["sync_s"]
    # Chrome export of a step trace is loadable JSON with X events.
    body = t.tracer.chrome_trace([tr])
    assert any(e.get("ph") == "X" for e in body["traceEvents"])
    json.dumps(body)


def test_trainer_rejects_packed_text_under_ring():
    """The ring x packed-text trap fails
    fast at the trainer boundary with an actionable message instead of
    dying deep in jit (or training silently wrong)."""
    import numpy as np

    from oryx_tpu.train.trainer import validate_train_batch

    packed = {"text_segment_ids": np.ones((1, 2, 8), np.int32)}
    for impl in ("ring", "ring_flash"):
        cfg = dataclasses.replace(cfg_lib.oryx_tiny(), attn_impl=impl)
        with pytest.raises(ValueError, match="no.*segment support"):
            validate_train_batch(cfg, packed)
    # Packed text under xla/pallas is fine; ring without packing is fine.
    validate_train_batch(cfg_lib.oryx_tiny(), packed)
    validate_train_batch(
        dataclasses.replace(cfg_lib.oryx_tiny(), attn_impl="ring_flash"),
        {"token_ids": np.zeros((1, 2, 8), np.int32)},
    )


@pytest.mark.parametrize("mode", ["one_device", "zero2", "ddp"])
def test_lora_step_donates_state_and_aliases_frozen_leaves(tmp_path, mode):
    """The compiled LoRA step consumes `state` (donated) and hands every
    frozen leaf back in the device buffers it came in: the step rebuilds
    the params tree from a trainable part and a frozen part, and a copy
    of the base a step is what such a merge could cost (the static
    use-after-donate rule cannot see that). `one_device` is the module's
    own jit on one device (the single-chip LoRA recipe's layout), the
    others the Trainer's pinned-sharding jit over the mesh. Not under
    `fsdp` here: across the 8-device CPU mesh XLA hands three sharded
    leaves another donated buffer of their type, whatever the step
    differentiates."""
    if jax.device_count() < 8:
        pytest.skip("needs the 8-device CPU mesh (conftest)")
    from oryx_tpu.train import step as step_lib
    from oryx_tpu.train.optimizer import trainable_mask

    cfg = _cfg(tmp_path, f"alias_{mode}")
    cfg = dataclasses.replace(cfg, train=dataclasses.replace(
        cfg.train, tune="lora",
        lora=cfg_lib.LoraConfig(enable=True, r=4, alpha=8.0),
    ))
    trainer = Trainer(cfg, sharding_mode="ddp" if mode == "one_device"
                      else mode)
    old = trainer.state
    if mode == "one_device":
        old = jax.device_put(old, jax.devices()[0])

    def pointers(x):
        return [s.data.unsafe_buffer_pointer() for s in x.addressable_shards]

    mask = jax.tree.leaves(trainable_mask(old.params, "lora"))
    assert not all(mask) and any(mask)
    before = [pointers(x) for x in jax.tree.leaves(old.params)]
    if mode == "one_device":
        batch = {k: jnp.asarray(v)[None] for k, v in _batch(cfg).items()}
        state, _ = step_lib.train_step(old, batch, cfg, trainer.tx)
    else:
        state = trainer.fit(iter([_batch(cfg)]), num_steps=1, resume=False,
                            prefetch=0)
    trainer.close()
    assert all(x.is_deleted() for x in jax.tree.leaves(old))
    for (path, x), was, m in zip(
        jax.tree_util.tree_flatten_with_path(state.params)[0], before, mask
    ):
        if not m:
            assert pointers(x) == was, jax.tree_util.keystr(path)
