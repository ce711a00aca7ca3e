"""Oryx-34B (Yi geometry) AOT sharding validation (SURVEY.md §7 stage
6): lower + compile the full FSDP train step on the 8-device CPU mesh
WITHOUT materializing 34B params (ShapeDtypeStructs only), then check
the compiler's memory analysis against the ZeRO-3 math: per-device
argument bytes ≈ total state / 8 → every large leaf is actually sharded
(an accidentally-replicated embedding would add ~2 GB/device and fail
the tolerance), and the donated state aliases in place.

The 16 GB-per-chip POD fit is no longer extrapolated from CPU temps
(XLA:CPU widens bf16 buffers and its fusion differs) — it is proven
directly against the real XLA:TPU compiler on a v5e:8x8 topology by
test_pod_configs_v5e64_tpu_aot_memory below (round 5)."""

import dataclasses
import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from oryx_tpu import config as cfg_lib
from oryx_tpu.models import oryx
from oryx_tpu.parallel import mesh as mesh_lib
from oryx_tpu.parallel import sharding
from oryx_tpu.train import step as step_lib
from oryx_tpu.train.optimizer import make_optimizer

GB = 1024**3


def _aot_fsdp_memory_check(cfg, shape, min_state_gb):
    if jax.device_count() < 8:
        pytest.skip("needs the 8-device CPU mesh (conftest)")
    cfg = dataclasses.replace(
        cfg,
        mesh=cfg_lib.MeshConfig(dp=1, fsdp=8, tp=1, sp=1),
        train=dataclasses.replace(cfg.train, grad_accum_steps=1),
        attn_impl="xla",
    )
    mesh = mesh_lib.build_mesh(cfg.mesh)

    params_shape = jax.eval_shape(
        lambda: oryx.init_params(cfg, jax.random.key(0))
    )
    tx = make_optimizer(cfg.train, params_shape)
    opt_shape = jax.eval_shape(tx.init, params_shape)

    pshard = sharding.param_shardings(mesh, params_shape, "fsdp")
    ospecs = sharding.opt_state_specs(opt_shape, params_shape, "fsdp")
    oshard = jax.tree.map(
        lambda s: jax.sharding.NamedSharding(mesh, s), ospecs,
        is_leaf=lambda x: isinstance(x, jax.sharding.PartitionSpec),
    )

    def sds(shape_struct, shard):
        return jax.ShapeDtypeStruct(
            shape_struct.shape, shape_struct.dtype, sharding=shard
        )

    state_in = step_lib.TrainState(
        step=sds(
            jax.ShapeDtypeStruct((), jnp.int32),
            jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec()),
        ),
        params=jax.tree.map(sds, params_shape, pshard),
        opt_state=jax.tree.map(sds, opt_shape, oshard),
    )

    B, T, P, Q = shape["B"], shape["T"], shape["P"], shape["Q"]
    bspec = sharding.batch_spec()
    PS = jax.sharding.PartitionSpec

    def bsds(shape, dtype):
        return jax.ShapeDtypeStruct(
            shape, dtype,
            sharding=jax.sharding.NamedSharding(mesh, PS(None, *bspec)),
        )

    batch = {
        "patches": bsds((1, P, cfg.vision.patch_size**2 * 3), jnp.float32),
        "segment_ids": bsds((1, P), jnp.int32),
        "pos_coords": bsds((1, P, 2), jnp.float32),
        "region_ids": bsds((1, P), jnp.int32),
        "q_region_ids": bsds((1, Q), jnp.int32),
        "token_ids": bsds((1, B, T), jnp.int32),
        "visual_idx": bsds((1, B, T), jnp.int32),
        "is_visual": bsds((1, B, T), jnp.bool_),
        "attn_mask": bsds((1, B, T), jnp.int32),
        "positions": bsds((1, B, T), jnp.int32),
        "labels": bsds((1, B, T), jnp.int32),
    }

    jit_step = jax.jit(
        step_lib.train_step_fn, static_argnames=("cfg", "tx"),
        donate_argnames=("state",),
    )
    with jax.sharding.set_mesh(mesh):
        compiled = jit_step.lower(state_in, batch, cfg=cfg, tx=tx).compile()
    ma = compiled.memory_analysis()

    # Analytic state: params + AdamW mu/nu, all fp32 here.
    param_bytes = sum(
        int(np.prod(l.shape)) * l.dtype.itemsize
        for l in jax.tree.leaves(params_shape)
    )
    opt_bytes = sum(
        int(np.prod(getattr(l, "shape", ()))) * l.dtype.itemsize
        for l in jax.tree.leaves(opt_shape)
        if hasattr(l, "dtype")
    )
    total_state = param_bytes + opt_bytes
    # Sanity: this really is the advertised multi-hundred-GB state tree.
    assert total_state > min_state_gb * GB

    per_dev_args = ma.argument_size_in_bytes
    # Batch args are negligible; a replicated 64000x7168 embedding (1.7 GB
    # + its two moments) would blow this 5% tolerance.
    assert abs(per_dev_args - total_state / 8) < 0.05 * total_state / 8, (
        f"per-device args {per_dev_args / GB:.2f} GB vs expected "
        f"{total_state / 8 / GB:.2f} GB — a large leaf is not sharded"
    )

    # Donated state aliases in-place (no second copy of the state).
    assert ma.alias_size_in_bytes > 0.95 * per_dev_args
    # (The former CPU-temp pod extrapolation lived here; the v5e-64 fit
    # is now proven directly on the real TPU compiler —
    # test_34b_longvideo_v5e64_tpu_aot_memory — and CPU temp totals are
    # not comparable across backends, so they are no longer asserted.)


@pytest.mark.slow
@pytest.mark.parametrize(
    "shape",
    [
        # Text-dominant SFT microbatch (1 row/device, seq 512).
        dict(B=8, T=512, P=256, Q=64),
        # BASELINE config 5: long-video SFT — 256 frames/row at 64
        # patches/frame under 16x compression = 16384 patches + 1024
        # visual tokens PER ROW; the packed buffers are batch-global
        # (ops/packing.PackedVisual), so 8 rows need P=131072, Q=8192.
        dict(B=8, T=2048, P=131072, Q=8192),
    ],
    ids=["text", "video256"],
)
def test_34b_fsdp_aot_memory(shape):
    _aot_fsdp_memory_check(cfg_lib.oryx_34b(), shape, min_state_gb=380)


@pytest.mark.slow
def test_oryx_1_5_32b_fsdp_aot_memory():
    """Oryx-1.5-32B (Qwen2.5-32B backbone): same ZeRO-3 math as the 34B
    path; text shape only (the video256 compile is covered by 34B)."""
    _aot_fsdp_memory_check(
        cfg_lib.oryx_1_5_32b(), dict(B=8, T=512, P=256, Q=64),
        min_state_gb=360,
    )


@pytest.mark.slow
@pytest.mark.parametrize(
    "config,frames",
    [
        ("oryx_34b_longvideo.json", 256),  # BASELINE config 5
        ("oryx_34b_sft.json", 0),
        ("oryx_1_5_32b_sft.json", 0),
    ],
    ids=["34b_longvideo256", "34b_sft", "32b_sft"],
)
def test_pod_configs_v5e64_tpu_aot_memory(config, frames):
    """Every SHIPPED pod-scale config on the REAL compiler: the full
    sharded train step compiled for a v5e:8x8 (64-chip) target via the
    topology API — no extrapolation, the actual buffer assignment.

    Pins the recipe that makes pod-scale 32B/34B fit 16 GB/chip:
    ZeRO-3 over the COMBINED fsdp x sp width
    + vision patch shards riding sp + grad_accum 8 (512 tokens/chip/
    microbatch) + bf16 moments + block remat (34B long-video measured
    14.71 GB, 32B 13.67; the pre-round-5 pure-FSDP accum-2 configs OOM
    at 21.5-24.9 GB).
    """
    import importlib.util
    import subprocess
    import sys

    if importlib.util.find_spec("libtpu") is None:
        pytest.skip("libtpu not installed (TPU topology AOT unavailable)")
    script = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "scripts", "estimate_7b_mesh_memory.py",
    )
    env = dict(os.environ)
    env.update(
        AOT_CONFIG=f"scripts/configs/{config}",
        AOT_FRAMES=str(frames),
    )
    proc = subprocess.run(
        [sys.executable, script, "block:bfloat16:8"],
        capture_output=True, text=True, timeout=3000, env=env,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    recs = [
        json.loads(l) for l in proc.stdout.splitlines() if l.startswith("{")
    ]
    rec = next(r for r in recs if r.get("policy") == "block")
    assert rec["target"] == "tpu_v5e_8x8_topology"
    assert rec["mesh"] == "dp1_fsdp16_tp1_sp4"
    assert rec["attn_impl"] == "ring_flash"
    # ZeRO-3 over all 64 chips: ~310-325 GB bf16-moment state / 64.
    assert rec["sharded_ok"], rec
    assert 4.3 < rec["args_gb"] < 6.2, rec
    assert rec["fits_16gb"] and rec["total_gb"] < 16.0, rec
