"""Component-wise latency breakdown of the video-QA serving path.

Where do the 64/256-frame milliseconds go? bench.py's latency cases time
the fused program end-to-end; this script times the pipeline's stages as
separate jitted programs on the same request (same packing, same shapes):

  encode   — ViT + Dynamic Compressor + splice into the text stream
             (oryx.mm_embeds: the whole visual front-end)
  prefill  — decoder forward over the spliced embeds (qwen2.forward,
             no cache), the prompt-processing cost
  decode   — per-token decode cost, measured as the slope between two
             _jit_mm_generate windows (16 vs 48 new tokens) so the
             shared prefill+encode cost cancels

Prints one JSON line per component plus a summary line, each timed to
block_until_ready. CPU runs exercise the same code with meaningless
numbers; not measured on the current chip.
"""

from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

REPS = int(os.environ.get("COMPONENT_REPS", "10"))


def _p50_spread(ts):
    ts = np.asarray(ts)
    p50 = float(np.percentile(ts, 50))
    return round(p50, 4), round(float((ts.max() - ts.min()) / max(p50, 1e-9)), 3)


def time_fn(fn, sync, reps=REPS):
    sync(fn())  # warmup/compile
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        sync(fn())
        ts.append(time.perf_counter() - t0)
    return _p50_spread(ts)


def main() -> None:
    import jax
    import jax.numpy as jnp

    from bench import _CharTokenizer, _bench_cfg, chip_info, make_video_request
    from oryx_tpu.models import oryx, qwen2
    from oryx_tpu.ops import packing
    from oryx_tpu.serve.pipeline import OryxInference

    backend = jax.default_backend()
    _, hbm, _ = chip_info(jax)
    _, cfg, *_ = _bench_cfg(backend, hbm)
    num_frames = int(os.environ.get("COMPONENT_FRAMES", "64"))
    new_tokens = (16, 48)

    params = oryx.init_params(cfg, jax.random.key(0))
    pipe = OryxInference(_CharTokenizer(), params, cfg)
    _, _, batch, arrays = make_video_request(pipe, cfg, num_frames)
    T = int(batch.token_ids.shape[1])
    out = {
        "metric": "component_latency_p50_s", "unit": "s",
        "frames": num_frames, "prompt_tokens": T,
        "patch_bucket": int(arrays["patches"].shape[0]),
        "backend": backend,
    }

    # encode: whole visual front-end (jit cached in oryx.mm_embeds).
    enc = lambda: oryx.mm_embeds(params, cfg, arrays)
    p50, spread = time_fn(enc, lambda e: jax.device_get(e[:1, :1]))
    out["encode_p50_s"], out["encode_spread"] = p50, spread

    embeds = enc()
    positions = jnp.asarray(batch.positions)
    kv_mask = jnp.asarray(batch.attn_mask)

    # prefill: decoder forward over the spliced embeds, no cache.
    @jax.jit
    def _prefill(params_llm, embeds):
        h, _ = qwen2.forward(
            params_llm, cfg.llm, inputs_embeds=embeds, positions=positions,
            kv_mask=kv_mask, attn_impl=cfg.attn_impl,
            compute_dtype=oryx.compute_dtype(cfg), return_hidden=True,
        )
        return h
    p50, spread = time_fn(
        lambda: _prefill(params["llm"], embeds),
        lambda h: jax.device_get(h[:1, :1, :1]),
    )
    out["prefill_p50_s"], out["prefill_spread"] = p50, spread

    # decode: slope between two generate windows (shared cost cancels).
    # No stop sequences, and the slope is only reported when BOTH windows
    # ran full length — the early-exit decode loop (models/generate.
    # _decode_while) otherwise stops at EOS and the slope measures noise.
    totals, full = {}, True
    for n in new_tokens:
        cache_len = packing.round_up_bucket(T + n)
        run = lambda: oryx._jit_mm_generate(
            params, cfg, arrays, n, cache_len, jax.random.key(0), None
        )
        p50, spread = time_fn(
            run, lambda r: jax.device_get(r[1]), reps=max(3, REPS // 2)
        )
        generated = int(jax.device_get(run()[1])[0])
        full &= generated == n
        totals[n] = p50
        out[f"generate{n}_p50_s"], out[f"generate{n}_spread"] = p50, spread
        out[f"generate{n}_tokens"] = generated
    n1, n2 = new_tokens
    out["decode_per_token_s"] = (
        round((totals[n2] - totals[n1]) / (n2 - n1), 5) if full else None
    )
    if not full:
        out["note"] = "early EOS: decode windows not full, slope unreliable"

    print(json.dumps(out))


if __name__ == "__main__":
    main()
