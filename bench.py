"""Benchmark: Oryx SFT training throughput + 64-frame video-QA latency.

Prints ONE JSON line with the north-star metric (BASELINE.md rows 1-2):

    {"metric": "sft_tokens_per_sec_per_chip", "value": N, "unit": "tok/s",
     "vs_baseline": R, "chip": ..., "hbm_gb": ..., "mfu": ...,
     "geometry": ..., "params_b": ..., "latency_video64_p50_s": ...,
     "latency_video64": {"device_p50_s": ..., "device_spread": ...,
     "e2e_p50_s": ..., ...}, "latency_video256": {...},
     "baseline_source": ...}

One process, one chip: it fails (non-zero exit, no metric line) when
`jax.devices()[0].platform` is not "tpu" — a CPU run has no time to
report. Nothing here has run on the current chip yet; the benchmark PR
(ROADMAP S1) replaces this file.

Throughput: the full multimodal SFT step (OryxViT → Dynamic Compressor →
splice → decoder fwd, masked CE, bwd, AdamW; Pallas flash attention on
TPU) on the LARGEST 7B-shaped geometry whose fp32 AdamW training state
fits the detected chip's HBM. Oryx-7B itself needs ~16 bytes/param of
state (~122 GB) — more than any single chip; the geometry ladder below
keeps the 7B shape (head_dim 128, GQA, vocab 152064, attention bias) and
scales width/depth, so tokens/sec/chip and MFU are honest for the chip
being measured. `geometry`/`params_b` in the output say exactly what ran.

MFU uses the standard 6*N*tokens + attention-matmul model FLOPs (remat
recompute NOT counted as useful work) over the chip's peak bf16 FLOPs.

Latency: BASELINE config 3 — 64-frame video QA (16x compression) through
serve/pipeline.OryxInference, greedy, 32 new tokens; p50 over repeats.

`vs_baseline`: BASELINE.json publishes no reference number ("published":
{}), so the bar is DERIVED from first principles (see the "defended
baseline" block below and BASELINE.md "Derivation"): 8xA100 bf16 peak x
the documented HF-Trainer+DeepSpeed multimodal-SFT MFU band / 6N
flops-per-token, divided over the 16 v5e chips of the north-star slice.
When the measured geometry is a sub-7B proxy, the comparable number is
the MFU projection to 7B on this chip (raw proxy tok/s is inflated by
the smaller model); `baseline_source` labels which regime produced the
ratio.
"""

from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

# ---- defended baseline (derivation recorded in BASELINE.md) ---------------
# The reference trains Oryx-7B SFT on 8xA100-80G (HF Trainer + DeepSpeed
# ZeRO, bf16, flash-attn-2; SURVEY.md §6). No published throughput is
# readable (/root/reference is empty, BASELINE.json.published == {}), so
# the bar is derived and carried as a band:
#   tokens/s(total) = n_gpus * peak_bf16 * MFU / flops_per_token
#   flops_per_token ~= 6N (dense decoder fwd+bwd; attention FLOPs and the
#   vision tower push the reference's true flops/token HIGHER, which makes
#   this bar conservative — i.e. harder for us to beat)
# with A100 bf16 peak 312 TFLOP/s, N = 7.6e9 (Qwen2-7B incl. embeddings),
# and MFU band 0.25-0.40 (mid 0.32): the range public HF-Trainer+ZeRO
# multimodal-SFT runs land in on A100 with flash-attn-2 — dense LLM
# pretrain reaches ~0.40-0.50, multimodal SFT loses ground to dynamic
# shapes, per-sample vision towers, and ZeRO comm. The north star is
# matching the 8-GPU TOTAL on a v5e-16 slice, so the per-chip bar
# divides by 16.
A100_BF16_PEAK = 312e12
REF_N_GPUS = 8
REF_PARAMS = 7.6e9
REF_FLOPS_PER_TOK = 6 * REF_PARAMS
REF_MFU_BAND = (0.25, 0.40)
REF_MFU_MID = 0.32
_REF_TOK_S = REF_N_GPUS * A100_BF16_PEAK / REF_FLOPS_PER_TOK  # at MFU 1.0
V5E16_CHIPS = 16
BASELINE_TOK_S_CHIP = _REF_TOK_S * REF_MFU_MID / V5E16_CHIPS  # ~1095
BASELINE_BAND_TOK_S_CHIP = tuple(
    round(_REF_TOK_S * m / V5E16_CHIPS, 1) for m in REF_MFU_BAND
)


def score_vs_baseline(n_llm: float, tok_s_chip: float, mfu, peak):
    """(vs_baseline, baseline_source, projected_7b) — most→least direct:
    a real-7B measurement scores directly per chip; a sub-7B proxy with
    measured MFU scores as the 7B-at-that-MFU projection on this chip's
    peak (the proxy's raw tok/s is inflated by the smaller model's fewer
    flops/token); without a known chip peak (CPU) the raw ratio is
    labeled geometry-incomparable rather than claimed."""
    if n_llm >= 6e9:
        return tok_s_chip / BASELINE_TOK_S_CHIP, \
            "derived_8xA100_mfu_band/direct", None
    if mfu is not None and peak:
        projected = mfu * peak / REF_FLOPS_PER_TOK
        return projected / BASELINE_TOK_S_CHIP, \
            "derived_8xA100_mfu_band/projected_7b_at_measured_mfu", projected
    return tok_s_chip / BASELINE_TOK_S_CHIP, \
        "derived_8xA100_mfu_band/geometry_incomparable", None

WARMUP_STEPS = 2
TIMED_STEPS = 5
LATENCY_REPEATS = 5
LATENCY_NEW_TOKENS = 32

# 7B-shaped ladder: (name, llm kwargs). All keep vocab 152064, head_dim
# 128, GQA, attention bias — only width/depth shrink. Ordered largest
# first; the largest whose training state fits HBM is benched.
GEOMETRY_LADDER = (
    ("oryx_7b", dict(
        hidden_size=3584, intermediate_size=18944, num_layers=28,
        num_heads=28, num_kv_heads=4)),
    ("oryx_7b_depth14", dict(
        hidden_size=3584, intermediate_size=18944, num_layers=14,
        num_heads=28, num_kv_heads=4)),
    ("oryx_3b", dict(
        hidden_size=2560, intermediate_size=13696, num_layers=20,
        num_heads=20, num_kv_heads=4)),
    ("oryx_1_5b", dict(
        hidden_size=1536, intermediate_size=8960, num_layers=28,
        num_heads=12, num_kv_heads=2)),
    ("oryx_0_9b", dict(
        hidden_size=1280, intermediate_size=6912, num_layers=24,
        num_heads=10, num_kv_heads=2)),
    ("oryx_0_6b", dict(
        hidden_size=1024, intermediate_size=5504, num_layers=20,
        num_heads=8, num_kv_heads=2)),
)

STATE_BYTES_PER_PARAM = 16  # fp32 params + AdamW mu/nu + fp32 grads
HBM_FRACTION = 0.82  # leave room for activations/logits/workspace


def _llm_cfg(kw):
    from oryx_tpu import config as cfg_lib

    return cfg_lib.LLMConfig(
        vocab_size=152064, head_dim=128, rope_theta=1_000_000.0,
        attention_bias=True, **kw,
    )


def count_llm_params(c) -> int:
    # Shared with the trainer telemetry exporter (utils/flops.py) so
    # bench MFU and /metrics MFU can never disagree on the model.
    from oryx_tpu.utils import flops as flops_lib

    return flops_lib.count_llm_params(c)


def chip_info(jax):
    """(device_kind, HBM bytes the device reports, peak bf16 FLOP/s or
    None for a kind utils/flops.py has no row for)."""
    from oryx_tpu.utils import flops as flops_lib

    dev = jax.devices()[0]
    hbm = int((dev.memory_stats() or {}).get("bytes_limit", 0))
    return dev.device_kind, hbm, flops_lib.chip_peak_flops(dev.device_kind)


def pick_geometry(hbm_bytes: int):
    budget = hbm_bytes * HBM_FRACTION
    for name, kw in GEOMETRY_LADDER:
        c = _llm_cfg(kw)
        if count_llm_params(c) * STATE_BYTES_PER_PARAM < budget:
            return name, c
    name, kw = GEOMETRY_LADDER[-1]
    return name, _llm_cfg(kw)


def _bench_cfg(backend: str, hbm_bytes: int):
    from oryx_tpu import config as cfg_lib

    if backend == "tpu" and not os.environ.get("BENCH_SMALL"):
        geo_name, llm = pick_geometry(hbm_bytes)
        vision = cfg_lib.VisionConfig(
            hidden_size=768,
            intermediate_size=2048,
            num_layers=6,
            num_heads=12,
            head_dim=64,
            patch_size=14,
            base_grid=27,
        )
        batch_size, seq_bucket, img_patches_side = 8, (2048,), 16
        comp_heads = 12
    else:
        geo_name, llm = "tiny", cfg_lib.tiny_llm()
        vision = cfg_lib.tiny_vision()
        batch_size, seq_bucket, img_patches_side = 2, (128,), 4
        comp_heads = 4
    # Sweepable geometry knobs: more
    # tokens/step amortizes per-step overhead where the memory freed by
    # bf16 moments / thin remat policies allows. Honored on every
    # backend — a CPU sweep must measure the requested geometry, not
    # silently bank distinct records for the same default tiny shape.
    if os.environ.get("BENCH_BATCH"):
        batch_size = int(os.environ["BENCH_BATCH"])
    if os.environ.get("BENCH_SEQ"):
        seq_bucket = (int(os.environ["BENCH_SEQ"]),)
    cfg = cfg_lib.OryxConfig(
        llm=llm,
        vision=vision,
        compressor=cfg_lib.CompressorConfig(num_heads=comp_heads),
        dtype="bfloat16",
        # Pallas flash attention on the real chip; portable XLA path on CPU.
        attn_impl="pallas" if backend == "tpu" else "xla",
    )
    # Remat policy (utils/remat.py), BENCH_REMAT_POLICY = none|block|
    # dots|attn|attn_qkv|attn_o. TPU default "attn": saving the flash
    # outputs + lse skips the kernel recompute in the backward (its
    # effect on step time is not measured on the current chip).
    pol = os.environ.get(
        "BENCH_REMAT_POLICY", "attn" if cfg.attn_impl == "pallas" else ""
    )
    chunk = os.environ.get("BENCH_LOSS_CHUNK")
    train_updates = {}
    if pol:
        train_updates.update(
            remat=pol != "none",
            remat_policy=pol if pol != "none" else "block",
        )
    if chunk:
        train_updates.update(loss_chunk=int(chunk))
    if os.environ.get("BENCH_MOMENT_DTYPE"):  # float32|bfloat16
        train_updates.update(moment_dtype=os.environ["BENCH_MOMENT_DTYPE"])
    if train_updates:
        import dataclasses

        cfg = dataclasses.replace(
            cfg, train=dataclasses.replace(cfg.train, **train_updates)
        )
    return geo_name, cfg, batch_size, seq_bucket, img_patches_side


def _make_batch(cfg, batch_size, seq_bucket, img_side):
    from oryx_tpu.constants import IGNORE_INDEX, IMAGE_TOKEN_INDEX
    from oryx_tpu.models import splice
    from oryx_tpu.ops import packing

    rng = np.random.default_rng(0)
    p = cfg.vision.patch_size
    images = [
        rng.standard_normal((img_side * p, img_side * p, 3)).astype(np.float32)
        for _ in range(batch_size)
    ]
    packed = packing.pack_images(
        images,
        patch_size=p,
        base_grid=cfg.vision.base_grid,
        side_factors=2,
    )
    slots = splice.query_slots(packed)
    vis_tokens = slots[0][1]
    # Fill the sequence bucket: prompt + image + supervised text.
    text_len = seq_bucket[-1] - vis_tokens - 1
    ids, labels = [], []
    for _ in range(batch_size):
        text = rng.integers(3, cfg.llm.vocab_size, size=text_len)
        row = np.concatenate([text[:8], [IMAGE_TOKEN_INDEX], text[8:]])
        lab = np.full(row.shape, IGNORE_INDEX, np.int64)
        lab[9 + 8:] = row[9 + 8:]
        ids.append(row)
        labels.append(lab)
    batch = splice.build_mm_batch(ids, slots, labels=labels, buckets=seq_bucket)
    return {
        "patches": packed.patches,
        "segment_ids": packed.segment_ids,
        "pos_coords": packed.pos_coords,
        "region_ids": packed.region_ids,
        "q_region_ids": packed.q_region_ids,
        "token_ids": batch.token_ids,
        "visual_idx": batch.visual_idx,
        "is_visual": batch.is_visual.astype(np.bool_),
        "attn_mask": batch.attn_mask,
        "positions": batch.positions,
        "labels": batch.labels,
    }


def model_flops_per_step(cfg, n_llm_params, host) -> float:
    """Analytic model FLOPs for one SFT step (the shared 6N + attention
    model in utils/flops.py — remat recompute excluded)."""
    from oryx_tpu.utils import flops as flops_lib

    B, T = host["token_ids"].shape
    return flops_lib.train_step_flops(
        cfg, n_llm_params, batch=B, seq_len=T,
        patch_tokens=int(host["segment_ids"].shape[-1]),
    )


class _CharTokenizer:
    """Deterministic host-side tokenizer for the latency bench (no
    pretrained vocab available offline)."""

    def encode(self, text, add_special_tokens=False):
        return [min(ord(c), 50000) for c in text]

    def decode(self, ids, skip_special_tokens=True):
        return "".join(chr(i) for i in ids if 0 < i < 50000)


def make_video_request(pipe, cfg, num_frames: int):
    """One deterministic video-QA request, prepped + packed the way the
    serving pipeline does it. Shared by the end-to-end latency bench and
    scripts/bench_components.py so the component breakdown measures the
    SAME request shape the e2e number comes from.

    Returns (frames, question, mm_batch, staged_arrays)."""
    from oryx_tpu.models import oryx, splice
    from oryx_tpu.ops import packing

    rng = np.random.default_rng(0)
    frames = [
        rng.integers(0, 255, size=(224, 224, 3), dtype=np.uint8)
        for _ in range(num_frames)
    ]
    question = "what happens?"
    ids, images, factors, caps = pipe._prepare_request({
        "question": question, "images": frames, "is_video": True,
    })
    packed = packing.pack_raw_images(
        images, patch_size=cfg.vision.patch_size,
        base_grid=cfg.vision.base_grid, side_factors=factors,
        max_patches=caps,
    )
    batch = splice.build_mm_batch([ids], splice.query_slots(packed))
    return frames, question, batch, oryx.stage_mm_arrays(packed, batch)


def bench_video_latency(params, cfg, num_frames: int = 64) -> dict:
    """Video-QA latency through the serving pipeline, split into two
    components:

      device_p50_s  — the compiled ViT+compressor+splice+prefill+decode
                      program, inputs pre-placed on device, timed to
                      block_until_ready: none of the host preprocessing
                      or frame upload. Run-to-run spread is reported.
      e2e_p50_s     — full pipe.chat_video wall clock (preprocess + pack
                      + upload + decode + detokenize), what a user sees.

    num_frames=64 is BASELINE config 3; 256 is the north-star long-video
    case (16x compression, shared patch budget across frames)."""
    import jax

    from oryx_tpu.models import oryx
    from oryx_tpu.ops import packing
    from oryx_tpu.serve.pipeline import OryxInference

    pipe = OryxInference(_CharTokenizer(), params, cfg)
    frames, question, batch, arrays = make_video_request(pipe, cfg, num_frames)

    # --- device-only component ------------------------------------------
    cache_len = packing.round_up_bucket(
        batch.token_ids.shape[1] + LATENCY_NEW_TOKENS
    )
    key = jax.random.key(0)
    run = lambda: oryx._jit_mm_generate(
        params, cfg, arrays, LATENCY_NEW_TOKENS, cache_len, key,
        pipe.stop_sequences,
    )
    jax.block_until_ready(run())  # warmup compile
    dev = []
    for _ in range(LATENCY_REPEATS):
        t0 = time.perf_counter()
        jax.block_until_ready(run())
        dev.append(time.perf_counter() - t0)

    # --- end-to-end component -------------------------------------------
    pipe.chat_video(frames, question, max_new_tokens=LATENCY_NEW_TOKENS)
    e2e = []
    for _ in range(max(3, LATENCY_REPEATS // 2)):
        t0 = time.perf_counter()
        pipe.chat_video(frames, question, max_new_tokens=LATENCY_NEW_TOKENS)
        e2e.append(time.perf_counter() - t0)

    dev, e2e = np.asarray(dev), np.asarray(e2e)
    return {
        "device_p50_s": round(float(np.percentile(dev, 50)), 4),
        "device_spread": round(
            float((dev.max() - dev.min()) / max(np.percentile(dev, 50), 1e-9)),
            3,
        ),
        "e2e_p50_s": round(float(np.percentile(e2e, 50)), 4),
        "patch_bucket": int(arrays["patches"].shape[0]),
        "seq_bucket": int(batch.token_ids.shape[1]),
    }


def _phase(msg: str) -> None:
    """Timestamped phase marker on stderr: says where a run that was
    killed at its time limit had got to."""
    print(f"# [{time.strftime('%H:%M:%S')}] {msg}", file=sys.stderr, flush=True)


def main() -> None:
    import jax
    import jax.numpy as jnp

    from oryx_tpu.models import oryx
    from oryx_tpu.train import step as step_lib
    from oryx_tpu.train.optimizer import make_optimizer
    from oryx_tpu.utils.compile_cache import configure_compile_cache

    configure_compile_cache()
    _phase("backend init")
    backend = jax.devices()[0].platform
    if backend != "tpu":
        raise SystemExit(
            f"bench.py measures the chip and found platform {backend!r}: "
            "run it where jax.devices() is a TPU"
        )
    n_chips = jax.device_count()
    chip, hbm, peak = chip_info(jax)
    geo_name, cfg, batch_size, seq_bucket, img_side = _bench_cfg(backend, hbm)
    n_llm = count_llm_params(cfg.llm)
    host = _make_batch(cfg, batch_size, seq_bucket, img_side)
    batch = {k: jnp.asarray(v)[None] for k, v in host.items()}  # accum=1

    _phase(f"init params ({geo_name})")
    params = oryx.init_params(cfg, jax.random.key(0))
    tx = make_optimizer(cfg.train, params)
    state = step_lib.TrainState(
        step=jnp.zeros((), jnp.int32), params=params, opt_state=tx.init(params)
    )

    tokens_per_step = int(np.sum(host["attn_mask"]))
    _phase("train_step compile + warmup")
    for _ in range(WARMUP_STEPS):
        state, metrics = step_lib.train_step(state, batch, cfg, tx)
    jax.block_until_ready(state)

    _phase("train_step timed loop")
    t0 = time.perf_counter()
    for _ in range(TIMED_STEPS):
        state, metrics = step_lib.train_step(state, batch, cfg, tx)
    jax.block_until_ready(state)
    dt = time.perf_counter() - t0
    loss = float(metrics["loss"])
    if not np.isfinite(loss):
        raise RuntimeError(f"non-finite loss {loss} in bench step")

    step_time = dt / TIMED_STEPS
    tok_s_chip = tokens_per_step / step_time / n_chips
    mfu = None
    if peak:
        flops = model_flops_per_step(cfg, n_llm, host)
        mfu = round(flops / step_time / (n_chips * peak), 4)

    del state, metrics, batch  # free HBM for the inference latency bench
    lat64 = lat256 = None
    if not os.environ.get("BENCH_NO_LATENCY"):
        try:
            # Fresh params: the originals were donated into train_step.
            _phase("latency: 64-frame video-QA")
            params = oryx.init_params(cfg, jax.random.key(0))
            lat64 = bench_video_latency(params, cfg, 64)
        # fault-boundary: keep the primary metric even if this fails
        except Exception as e:
            print(f"# latency bench failed: {e!r}")
        # 256-frame north-star case (BASELINE config 3): real chips only
        # by default (256 frames through the tiny CPU config is all
        # compile time); BENCH_VIDEO256=1 forces, =0 skips.
        want256 = os.environ.get(
            "BENCH_VIDEO256", "1" if backend == "tpu" else "0"
        ) == "1"
        if want256 and lat64 is not None:
            try:
                _phase("latency: 256-frame video-QA (north star)")
                lat256 = bench_video_latency(params, cfg, 256)
            except Exception as e:  # OOM here is itself a finding
                print(f"# 256-frame latency bench failed: {e!r}")
                lat256 = {"error": f"{type(e).__name__}: {e}"[:300]}

    # int8 weight-only serving latency (utils/quant.py): decode is
    # HBM-bandwidth-bound, so halving weight bytes should show directly
    # in device_p50 — measured on the same 64-frame case.
    lat64_q8 = None
    want_q8 = os.environ.get(
        "BENCH_INT8", "1" if backend == "tpu" else "0"
    ) == "1"
    if want_q8 and lat64 is not None:
        try:
            from oryx_tpu.utils.quant import quantize_params

            _phase("latency: 64-frame video-QA, int8 weights")
            params = quantize_params(params)
            lat64_q8 = bench_video_latency(params, cfg, 64)
        except Exception as e:  # attempted-and-failed must be auditable
            print(f"# int8 latency bench failed: {e!r}")
            lat64_q8 = {"error": f"{type(e).__name__}: {e}"[:300]}

    vs_baseline, baseline_source, projected_7b = score_vs_baseline(
        n_llm, tok_s_chip, mfu, peak
    )
    print(json.dumps({
        "metric": "sft_tokens_per_sec_per_chip",
        "value": round(tok_s_chip, 2),
        "unit": "tok/s",
        "backend": backend,
        "vs_baseline": round(vs_baseline, 4),
        "baseline_source": baseline_source,
        "baseline_tok_s_chip": round(BASELINE_TOK_S_CHIP, 1),
        "baseline_band_tok_s_chip": list(BASELINE_BAND_TOK_S_CHIP),
        "projected_7b_tok_s_chip": projected_7b and round(projected_7b, 1),
        "chip": chip,
        "hbm_gb": round(hbm / 1024**3, 1) if hbm else None,
        "geometry": geo_name,
        "params_b": round(n_llm / 1e9, 2),
        "step_time_s": round(step_time, 3),
        "mfu": mfu,
        "latency_video64_p50_s": lat64 and lat64["e2e_p50_s"],
        "latency_video64": lat64,
        "latency_video256": lat256,
        "latency_video64_int8": lat64_q8,
    }))


if __name__ == "__main__":
    main()
