"""AOT proof: real-7B int8 serving fits ONE 16 GB v5e chip.

MIGRATING.md promises "7B-class models fit ONE 16 GB v5e" under
weight-only int8 (`--quantize int8`, utils/quant.py). This compiles the
claim against the actual XLA:TPU compiler (chipless v5e:2x2 topology,
one device) at the TRUE Oryx-7B geometry — no weights materialized:

  * the 64-frame video-QA visual encode (ViT + compressor over the
    packed 4096-patch buffer, the BASELINE config-3 prefill load), and
  * `models/generate.generate` (jitted prefill + decode while-loop)
    over a 1024-token prompt with a 2048-slot KV cache,

both with the int8 param tree (eval_shape of utils/quant.quantize_params
over the fp32 init: int8 kernels + embedding, f32 scales, bf16 cast for
the rest). Per-program totals (args + temps + outputs - aliases) must
sit under the 16 GB HBM; the TPU compiler would refuse at compile time
otherwise (RESOURCE_EXHAUSTED).

    python scripts/estimate_serving_memory.py

One JSON line per program and a summary line. Pinned by
tests/test_aot_serving_7b.py.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
from functools import partial

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

GB = 1024**3
_CHILD_ENV = "ORYX_TPU_AOTSRV_CHILD"

# BASELINE config 3 serving shapes: 64-frame video at the per-frame
# patch cap (4096/64 = 64 patches -> 4 visual tokens at 16x), 1024-token
# prompt bucket, 128 new tokens in a 2048-slot cache.
FRAMES = 64
PATCHES = FRAMES * 64
Q_TOKENS = FRAMES * 4
PROMPT_T = 1024
MAX_NEW = 128
CACHE_LEN = 2048


def main() -> None:
    if os.environ.get(_CHILD_ENV) != "1":
        env = dict(os.environ)
        env[_CHILD_ENV] = "1"
        env["JAX_PLATFORMS"] = "cpu"
        sys.exit(subprocess.run(
            [sys.executable, os.path.abspath(__file__), *sys.argv[1:]],
            env=env, cwd=REPO,
        ).returncode)

    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies

    from oryx_tpu import config as cfg_lib
    from oryx_tpu.models import generate as gen_lib
    from oryx_tpu.models import oryx
    from oryx_tpu.utils.compile_cache import configure_compile_cache
    from oryx_tpu.utils.quant import quantize_params

    configure_compile_cache()
    with open(os.path.join(REPO, "scripts/configs/oryx_7b_sft.json")) as f:
        cfg = cfg_lib.OryxConfig.from_dict(json.load(f))

    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    dev = topo.devices[0]
    shard = jax.sharding.SingleDeviceSharding(dev)

    from oryx_tpu.utils.quant import quantized_bytes

    params_shape = jax.eval_shape(
        lambda: oryx.init_params(cfg, jax.random.key(0))
    )
    q_shape = jax.eval_shape(
        partial(quantize_params, cast=lambda x: x.astype(jnp.bfloat16)),
        params_shape,
    )
    weight_bytes = quantized_bytes(q_shape)
    llm_bytes = quantized_bytes(q_shape["llm"])
    vis_bytes = weight_bytes - llm_bytes

    def sds(s):
        return jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=shard)

    q_in = jax.tree.map(sds, q_shape)

    def bsds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=shard)

    def analyze(name, compiled):
        ma = compiled.memory_analysis()
        total = (
            ma.argument_size_in_bytes + ma.temp_size_in_bytes
            + ma.output_size_in_bytes - ma.alias_size_in_bytes
        )
        rec = {
            "program": name,
            "weight_gb": round(weight_bytes / GB, 2),
            "args_gb": round(ma.argument_size_in_bytes / GB, 2),
            "temp_gb": round(ma.temp_size_in_bytes / GB, 2),
            "total_gb": round(total / GB, 2),
            "fits_16gb": bool(total < 16 * GB),
        }
        print(json.dumps(rec), flush=True)
        return rec

    # Program 1: visual encode at the 64-frame packed shapes.
    patch_dim = cfg.vision.patch_size**2 * 3

    def visual(p, patches, seg, pos, reg, qreg):
        return oryx.encode_visual(
            p, cfg, patches, seg, pos, reg, qreg,
            compute_dtype=jnp.bfloat16,
        )

    vis = jax.jit(visual).lower(
        q_in,
        bsds((PATCHES, patch_dim), jnp.float32),
        bsds((PATCHES,), jnp.int32),
        bsds((PATCHES, 2), jnp.float32),
        bsds((PATCHES,), jnp.int32),
        bsds((Q_TOKENS,), jnp.int32),
    ).compile()
    r1 = analyze("visual_encode_64f", vis)

    # Program 2: prefill + decode (the serving generate jit, as the
    # pipeline invokes it: Pallas attention, bf16 compute).
    gen = gen_lib.generate.lower(
        q_in["llm"], cfg.llm, cfg.generation,
        inputs_embeds=bsds((1, PROMPT_T, cfg.llm.hidden_size),
                           jnp.bfloat16),
        lengths=bsds((1,), jnp.int32),
        max_new_tokens=MAX_NEW,
        cache_len=CACHE_LEN,
        key=None,
        attn_impl="pallas",
        compute_dtype=jnp.bfloat16,
    ).compile()
    r2 = analyze("generate_prefill_decode", gen)

    # The SERVING PEAK: the pipeline runs the two programs back to back
    # with the whole int8 tree resident in HBM throughout (per-program
    # args only count the subtree each program reads — XLA DCEs the
    # rest, so neither program's total alone bounds the peak). Peak =
    # resident weights + the larger program's non-weight working set.
    extra_vis = r1["total_gb"] - round(vis_bytes / GB, 2)
    extra_gen = r2["total_gb"] - round(llm_bytes / GB, 2)
    peak = round(weight_bytes / GB + max(extra_vis, extra_gen), 2)
    print(json.dumps({
        "summary": "7b_int8_serving_one_v5e",
        "serving_peak_gb": peak,
        "all_fit": bool(
            r1["fits_16gb"] and r2["fits_16gb"] and peak < 16.0
        ),
    }), flush=True)


if __name__ == "__main__":
    main()
