"""AOT memory estimate of the bench-geometry train step per remat
policy — no TPU chip needed.

Lowers + compiles the full SFT step for the REAL bench geometry
(bench._bench_cfg's TPU branch) from ShapeDtypeStructs (no 0.7B params
materialized) and reads the compiler's memory analysis.

Compile target (REMAT_EST_PLATFORM env, default "tpu"): with the local
libtpu, a v5e TOPOLOGY compile gives the actual XLA:TPU buffer
assignment — bf16 at true width, HBM capacity enforced at compile time
(RESOURCE_EXHAUSTED is captured and reported as {"oom": true} with the
required footprint) — for the bench program, with its Pallas
flash-attention kernels in interpret-mode EMULATION (this runs under
JAX_PLATFORMS=cpu, where the kernels pick interpret mode; the Mosaic
kernels themselves compile in tests/test_pallas_topology_compile.py).
"cpu" falls
back to the one-CPU-device compile: no Pallas lowering there, so the
xla attention path substitutes (its larger backward transients make
those numbers conservative), and XLA:CPU's float normalization widens
bf16 buffers to fp32 — CPU temp bytes support policy DELTAS only, not
absolute fits.

    python scripts/estimate_remat_memory.py [policy[:moment_dtype] ...]
"""

from __future__ import annotations

import json
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

GB = 1024**3


def _target_device():
    """One compile-target device: v5e topology (default) or local CPU."""
    import jax

    if os.environ.get("REMAT_EST_PLATFORM", "tpu") == "cpu":
        return jax.devices("cpu")[0], "cpu"
    from jax.experimental import topologies

    # Smallest valid v5e layout is 2x2 (host bounds); the single-device
    # program below targets one chip of it.
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    return topo.devices[0], "tpu_v5e_topology"


def one(policy: str, moment_dtype: str = "float32") -> dict:
    import dataclasses
    import re

    import jax
    import jax.numpy as jnp

    from bench import _bench_cfg, _make_batch
    from oryx_tpu.models import oryx
    from oryx_tpu.train import step as step_lib
    from oryx_tpu.train.optimizer import make_optimizer

    geo, cfg, batch_size, seq_bucket, img_side = _bench_cfg(
        "tpu", 16 * GB
    )
    # The TPU topology target compiles the bench cfg AS-IS — whatever
    # attention impl the real bench runs (its kernels as interpret-mode
    # emulation, see the module docstring). Only the CPU fallback
    # substitutes the xla path (no Pallas lowering on CPU; its larger
    # backward transients make those numbers conservative).
    overrides_impl = (
        {"attn_impl": "xla"}
        if os.environ.get("REMAT_EST_PLATFORM", "tpu") == "cpu"
        else {}
    )
    cfg = dataclasses.replace(
        cfg,
        **overrides_impl,
        train=dataclasses.replace(
            cfg.train, remat=policy != "none", moment_dtype=moment_dtype,
            remat_policy=policy if policy != "none" else "block",
        ),
    )
    host = _make_batch(cfg, batch_size, seq_bucket, img_side)

    params_shape = jax.eval_shape(
        lambda: oryx.init_params(cfg, jax.random.key(0))
    )
    tx = make_optimizer(cfg.train, params_shape)
    opt_shape = jax.eval_shape(tx.init, params_shape)
    state_in = step_lib.TrainState(
        step=jax.ShapeDtypeStruct((), jnp.int32),
        params=params_shape,
        opt_state=opt_shape,
    )
    dev, target = _target_device()
    shard = jax.sharding.SingleDeviceSharding(dev)
    state_in = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=shard),
        state_in,
    )
    batch = {
        # canonicalize_dtype (x64-off int64->int32 etc.) without
        # materializing device arrays.
        k: jax.ShapeDtypeStruct(
            (1, *v.shape), jax.dtypes.canonicalize_dtype(v.dtype),
            sharding=shard,
        )
        for k, v in host.items()
    }
    jit_step = jax.jit(
        step_lib.train_step_fn, static_argnames=("cfg", "tx"),
        donate_argnames=("state",),
    )
    overrides = {
        k: os.environ[k]
        for k in ("BENCH_BATCH", "BENCH_SEQ", "BENCH_LOSS_CHUNK")
        if os.environ.get(k)
    }
    base = {
        "target": target,
        "geometry": geo,
        "policy": policy,
        "moment_dtype": moment_dtype,
        # Inherited bench env overrides, recorded so a sweep-polluted
        # shell can't pass these numbers off as the default geometry.
        **({"env_overrides": overrides} if overrides else {}),
    }
    try:
        compiled = jit_step.lower(state_in, batch, cfg=cfg, tx=tx).compile()
    except Exception as e:  # XLA:TPU enforces HBM at compile time.
        msg = str(e)
        if "RESOURCE_EXHAUSTED" not in msg:
            raise
        m = re.search(r"Used ([\d.]+)G of ([\d.]+)G hbm", msg)
        return {
            **base,
            "oom": True,
            "total_gb": float(m.group(1)) if m else None,
            "hbm_gb": float(m.group(2)) if m else None,
        }
    ma = compiled.memory_analysis()
    return {
        **base,
        "args_gb": round(ma.argument_size_in_bytes / GB, 2),
        "temp_gb": round(ma.temp_size_in_bytes / GB, 2),
        "total_gb": round(
            (ma.argument_size_in_bytes + ma.temp_size_in_bytes
             + ma.output_size_in_bytes - ma.alias_size_in_bytes) / GB, 2
        ),
    }


_CHILD_ENV = "ORYX_TPU_REMAT_EST_CHILD"


def main() -> None:
    if os.environ.get(_CHILD_ENV) != "1":
        # Re-exec in a clean CPU-client child: the caller's process may
        # otherwise initialize the chip's backend just to build
        # ShapeDtypeStructs — and a chip belongs to one process. The
        # TPU *compiler* target comes from the topology API, not the
        # client platform.
        import subprocess

        env = dict(os.environ)
        env[_CHILD_ENV] = "1"
        env["JAX_PLATFORMS"] = "cpu"
        sys.exit(subprocess.run(
            [sys.executable, os.path.abspath(__file__), *sys.argv[1:]],
            env=env,
        ).returncode)

    from oryx_tpu.utils.compile_cache import configure_compile_cache

    configure_compile_cache()
    cases = [("block", "float32"), ("attn", "float32"),
             ("attn_qkv", "float32"), ("attn_o", "float32"),
             ("attn_o", "bfloat16")]
    if len(sys.argv) > 1:
        # "policy" or "policy:moment_dtype" (e.g. attn_o:bfloat16).
        cases = [
            (p.split(":")[0], p.split(":")[1] if ":" in p else "float32")
            for p in sys.argv[1:]
        ]
    for policy, mdt in cases:
        print(json.dumps(one(policy, mdt)), flush=True)


if __name__ == "__main__":
    main()
