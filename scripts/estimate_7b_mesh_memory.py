"""AOT per-chip memory proof: an Oryx config's SFT step on its full mesh.

Answers SURVEY.md §7 hard part 5 ("does the 7B train state actually fit
a v5e-16?" — and the 34B/longvideo pod questions) without chips: lowers
+ compiles the FULL sharded train step for a shipped config JSON on its
own `mesh` block from ShapeDtypeStructs — no params are ever
materialized — and reads the compiler's per-device memory analysis for
each (remat policy, moment dtype, grad accum) point.

Defaults prove `scripts/configs/oryx_7b_sft.json` on a v5e-16; env
knobs generalize it:
  AOT_CONFIG      config JSON path (default scripts/configs/oryx_7b_sft.json);
                  the device count and mesh shape come from its `mesh`
  AOT_ROWS_STEP   rows per optimizer step (default 128)
  AOT_SEQ         token bucket per row (default 2048)
  AOT_FRAMES      0 (default) = one 448px image per row (256 patches,
                  64 visual tokens at 4x); N = N-frame video per row
                  (64 patches and 4 visual tokens per frame at 16x —
                  BASELINE config 5's long-video shape)
  AOT_MESH        "dp,fsdp,tp,sp" mesh override (same device count).
                  sp>1 switches attention to ring_flash (sequence
                  parallelism) — the long-video lever: a smaller data
                  width admits deeper grad accumulation, cutting
                  tokens/chip/microbatch below pure-FSDP's floor of
                  one full row per chip

Compiler target, in order of preference:
  * **TPU topology AOT** (default): `jax.experimental.topologies` with
    the local libtpu compiles for a REAL v5e target (4x4 for 16-chip
    meshes, 8x8 for 64, ...) with no chips attached — argument/temp
    bytes are the actual XLA:TPU buffer assignment, bf16 at true width.
    CAVEAT (PR 21): this runs under JAX_PLATFORMS=cpu, where the Pallas
    kernels pick interpret mode, so what compiles for the shipped
    attn_impl is the interpreter's emulation, not the Mosaic kernel —
    which the chip's compiler refuses under a mesh until it is wrapped
    in a shard_map (ROADMAP S8).
  * CPU forced-N-device fallback (`AOT7B_PLATFORM=cpu`): portable, but
    the xla attention path substitutes (no Pallas on CPU) and XLA:CPU's
    float normalization widens every bf16 buffer to fp32 (measured:
    15.8 GB CPU-temp vs 9.3 GB TPU-temp for the same attn/accum-8
    program). Use only for policy DELTAS.

    python scripts/estimate_7b_mesh_memory.py [policy[:moment_dtype[:accum]] ...]

One JSON line per case:
  {"policy": ..., "moment_dtype": ..., "grad_accum_steps": ...,
   "args_gb": ..., "temp_gb": ..., "total_gb": ..., "state_gb_total": ...,
   "sharded_ok": true, "fits_16gb": ...}
and a final {"winner": ..., "table": [...]} summary line.
"""

from __future__ import annotations

import json
import os
import re
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

GB = 1024**3
_CHILD_ENV = "ORYX_TPU_AOT7B_CHILD"
V5E_HBM_GB = 16.0

CONFIG = os.environ.get("AOT_CONFIG", "scripts/configs/oryx_7b_sft.json")
# Grad accumulation splits the step's rows into microbatches (the scan
# in train/step.py) — THE activation-memory lever at fixed global batch.
ROWS_STEP = int(os.environ.get("AOT_ROWS_STEP", "128"))
SEQ = int(os.environ.get("AOT_SEQ", "2048"))
FRAMES = int(os.environ.get("AOT_FRAMES", "0"))
if FRAMES:
    # Long-video rows: FRAMES frames x 64 patches, 16x compression.
    PATCHES_PER_ROW, Q_PER_ROW = FRAMES * 64, FRAMES * 4
else:
    # One 448px image per row: 256 patches, 64 visual tokens at 4x.
    PATCHES_PER_ROW, Q_PER_ROW = 256, 64

_TOPO_BY_N = {16: "v5e:4x4", 32: "v5e:4x8", 64: "v5e:8x8",
              128: "v5e:8x16", 256: "v5e:16x16"}


def _devices(n_dev: int):
    """n compile-target devices: TPU topology (preferred) or forced CPU."""
    import numpy as np

    import jax

    if os.environ.get("AOT7B_PLATFORM") == "cpu":
        devs = jax.devices("cpu")
        if len(devs) < n_dev:
            raise RuntimeError(
                f"need {n_dev} CPU devices "
                f"(XLA_FLAGS=--xla_force_host_platform_device_count={n_dev})"
            )
        return np.array(devs[:n_dev]), f"cpu_forced{n_dev}"
    from jax.experimental import topologies

    if n_dev not in _TOPO_BY_N:
        raise ValueError(
            f"no v5e topology mapped for {n_dev} devices; supported: "
            f"{sorted(_TOPO_BY_N)} (or AOT7B_PLATFORM=cpu with a forced "
            f"device count)"
        )
    name = _TOPO_BY_N[n_dev]
    topo = topologies.get_topology_desc(platform="tpu", topology_name=name)
    return np.array(topo.devices), f"tpu_{name.replace(':', '_')}_topology"


def one(policy: str, moment_dtype: str = "float32", accum: int = 1) -> dict:
    import dataclasses

    import numpy as np

    import jax
    import jax.numpy as jnp

    from oryx_tpu import config as cfg_lib
    from oryx_tpu.models import oryx
    from oryx_tpu.parallel import sharding
    from oryx_tpu.train import step as step_lib
    from oryx_tpu.train.optimizer import make_optimizer

    with open(os.path.join(REPO, CONFIG)) as f:
        cfg = cfg_lib.OryxConfig.from_dict(json.load(f))
    if os.environ.get("AOT_MESH"):
        dp, fsdp, tp, sp = map(int, os.environ["AOT_MESH"].split(","))
        cfg = dataclasses.replace(
            cfg,
            mesh=dataclasses.replace(cfg.mesh, dp=dp, fsdp=fsdp,
                                     tp=tp, sp=sp),
        )
    n_dev = cfg.mesh.num_devices
    # As-shipped attn impl on the TPU target (in interpret-mode
    # emulation — see the module docstring's caveat); the CPU fallback
    # substitutes the xla path. Sequence
    # parallelism trains under ring attention (the dryrun's rule).
    if os.environ.get("AOT7B_PLATFORM") == "cpu":
        overrides_impl = {"attn_impl": "xla" if cfg.mesh.sp == 1
                          else "ring"}
    elif cfg.mesh.sp > 1 and not cfg.attn_impl.startswith("ring"):
        overrides_impl = {"attn_impl": "ring_flash"}
    else:
        overrides_impl = {}
    cfg = dataclasses.replace(
        cfg,
        **overrides_impl,
        train=dataclasses.replace(
            cfg.train,
            remat=policy != "none",
            remat_policy=policy if policy != "none" else "block",
            moment_dtype=moment_dtype,
            grad_accum_steps=accum,
        ),
    )
    devs, target = _devices(n_dev)
    mesh = jax.sharding.Mesh(
        devs.reshape(cfg.mesh.dp, cfg.mesh.fsdp, cfg.mesh.tp, cfg.mesh.sp),
        ("dp", "fsdp", "tp", "sp"),
    )

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

    assert ROWS_STEP % accum == 0
    rows = ROWS_STEP // accum  # rows per microbatch (scan over accum)
    P = rows * PATCHES_PER_ROW
    Q = rows * Q_PER_ROW
    PS = jax.sharding.PartitionSpec
    data_width = cfg.mesh.dp * cfg.mesh.fsdp

    mesh_sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    batch_bytes_per_chip = [0]

    def bsds(name, shape, dtype):
        # THE trainer placement rule (sharding.batch_field_spec, applied
        # by field name — a divisibility heuristic would let the row
        # axis leak onto sp at low accum): packed visual buffers shard
        # over the full (dp, fsdp, sp) width, token rows over the data
        # width; non-divisible axes replicate. Width derives from the
        # spec itself (the trainer's drift-proof form).
        spec = sharding.batch_field_spec(name)
        width = 1
        for ax in spec[1]:
            width *= mesh_sizes[ax]
        if shape[1] % width != 0:
            spec, width = PS(), 1
        batch_bytes_per_chip[0] += (
            int(np.prod(shape)) * jnp.dtype(dtype).itemsize // width
        )
        return jax.ShapeDtypeStruct(
            shape, dtype, sharding=jax.sharding.NamedSharding(mesh, spec)
        )

    patch_dim = cfg.vision.patch_size**2 * 3
    shapes = {
        "patches": ((accum, P, patch_dim), jnp.float32),
        "segment_ids": ((accum, P), jnp.int32),
        "pos_coords": ((accum, P, 2), jnp.float32),
        "region_ids": ((accum, P), jnp.int32),
        "q_region_ids": ((accum, Q), jnp.int32),
        "token_ids": ((accum, rows, SEQ), jnp.int32),
        "visual_idx": ((accum, rows, SEQ), jnp.int32),
        "is_visual": ((accum, rows, SEQ), jnp.bool_),
        "attn_mask": ((accum, rows, SEQ), jnp.int32),
        "positions": ((accum, rows, SEQ), jnp.int32),
        "labels": ((accum, rows, SEQ), jnp.int32),
    }
    batch = {k: bsds(k, s, d) for k, (s, d) in shapes.items()}

    jit_step = jax.jit(
        step_lib.train_step_fn,
        static_argnames=("cfg", "tx", "sharding_mode"),
        donate_argnames=("state",),
    )
    base = {
        "target": target,
        "policy": policy,
        "moment_dtype": moment_dtype,
        "grad_accum_steps": accum,
        "mesh": f"dp{cfg.mesh.dp}_fsdp{cfg.mesh.fsdp}"
                f"_tp{cfg.mesh.tp}_sp{cfg.mesh.sp}",
        "attn_impl": cfg.attn_impl,
        "tokens_per_chip_micro": rows * SEQ // n_dev,
    }
    try:
        with jax.sharding.set_mesh(mesh):
            compiled = jit_step.lower(
                state_in, batch, cfg=cfg, tx=tx, sharding_mode="fsdp"
            ).compile()
    except Exception as e:  # XLA:TPU enforces HBM at compile time:
        # RESOURCE_EXHAUSTED "Used X of Y hbm" IS the does-not-fit
        # verdict, with the exact required footprint in the message.
        msg = str(e)
        if "RESOURCE_EXHAUSTED" not in msg:
            raise
        m = re.search(r"Used ([\d.]+)G of ([\d.]+)G hbm", msg)
        return {
            **base,
            "oom": True,
            "total_gb": float(m.group(1)) if m else None,
            "hbm_gb": float(m.group(2)) if m else None,
            "sharded_ok": False,
            "fits_16gb": False,
        }
    ma = compiled.memory_analysis()

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
    per_dev_args = ma.argument_size_in_bytes
    # ZeRO-3 proof: per-device args minus the batch's own per-chip
    # share ~ state/n — a replicated embedding (2.2 GB at Qwen2-7B
    # vocab, + its moments) would blow the 5% tolerance. At long-video
    # shapes the input buffers are GBs, so they must be accounted, not
    # assumed negligible.
    state_args = per_dev_args - batch_bytes_per_chip[0]
    sharded_ok = (
        abs(state_args - total_state / n_dev) < 0.05 * total_state / n_dev
    )
    total = (
        ma.argument_size_in_bytes + ma.temp_size_in_bytes
        + ma.output_size_in_bytes - ma.alias_size_in_bytes
    )
    return {
        **base,
        "params_b": round(param_bytes / 4 / 1e9, 2),
        "state_gb_total": round(total_state / GB, 1),
        "args_gb": round(per_dev_args / GB, 2),
        "temp_gb": round(ma.temp_size_in_bytes / GB, 2),
        "alias_gb": round(ma.alias_size_in_bytes / GB, 2),
        "total_gb": round(total / GB, 2),
        "sharded_ok": bool(sharded_ok),
        "fits_16gb": bool(total < V5E_HBM_GB * GB),
    }


def main() -> None:
    if os.environ.get(_CHILD_ENV) != "1":
        # Re-exec in a clean child: the caller's process may hold the
        # chip or an 8-device test platform. The child's jax client is
        # CPU; the TPU *compiler* target comes from the topology API,
        # not the client platform.
        env = dict(os.environ)
        env[_CHILD_ENV] = "1"
        env["JAX_PLATFORMS"] = "cpu"
        # Forced device count only matters for the CPU fallback; size it
        # from the config so any mesh width works.
        cfg_path = os.path.join(REPO, CONFIG)
        with open(cfg_path) as f:
            m = json.load(f).get("mesh", {})
        n_dev = 1
        for ax in ("dp", "fsdp", "tp", "sp"):
            n_dev *= int(m.get(ax, 1))
        prior = [
            f
            for f in env.get("XLA_FLAGS", "").split()
            if not f.startswith("--xla_force_host_platform_device_count")
        ]
        env["XLA_FLAGS"] = " ".join(
            prior + [f"--xla_force_host_platform_device_count={n_dev}"]
        )
        proc = subprocess.run(
            [sys.executable, os.path.abspath(__file__), *sys.argv[1:]],
            env=env, cwd=REPO,
        )
        sys.exit(proc.returncode)

    from oryx_tpu.utils.compile_cache import configure_compile_cache

    configure_compile_cache()
    # Case syntax: policy[:moment_dtype[:accum]] (e.g. attn_o:bfloat16:4).
    # Default ladder: the accum=1 whole-step compile documents WHY grad
    # accumulation is required (temps blow 16 GB), then the remat ladder
    # at the config's production accum (fp32 moments after bf16 at equal
    # policy, so the winner rule below prefers fp32 when both fit).
    cases = [("attn", "float32", 1),
             ("block", "float32", 8), ("attn", "float32", 8),
             ("attn_qkv", "float32", 8), ("attn_o", "bfloat16", 8),
             ("attn_o", "float32", 8)]
    if len(sys.argv) > 1:
        def parse(p):
            bits = p.split(":")
            return (bits[0], bits[1] if len(bits) > 1 else "float32",
                    int(bits[2]) if len(bits) > 2 else 1)
        cases = [parse(p) for p in sys.argv[1:]]
    table = []
    for policy, mdt, accum in cases:
        rec = one(policy, mdt, accum)
        table.append(rec)
        print(json.dumps(rec), flush=True)
    fitting = [r for r in table if r["fits_16gb"] and r["sharded_ok"]]
    # Winner: the fitting policy that saves the most recompute — the
    # ladder is ordered cheapest-recompute-last (and fp32 moments after
    # bf16 at equal policy), so take the LAST fit.
    winner = fitting[-1] if fitting else None
    print(json.dumps({
        "winner": winner and (
            f"{winner['policy']}:{winner['moment_dtype']}"
            f":{winner['grad_accum_steps']}"
        ),
        "n_fitting": len(fitting),
        "table": [
            {k: r[k] for k in ("policy", "moment_dtype", "grad_accum_steps",
                               "total_gb", "fits_16gb", "sharded_ok")}
            for r in table
        ],
    }), flush=True)


if __name__ == "__main__":
    main()
