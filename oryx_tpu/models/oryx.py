"""Oryx multimodal model: OryxViT + Dynamic Compressor + Qwen2/Yi decoder.

Reference parity: `OryxQwenForCausalLM` + `OryxMetaForCausalLM`
(`oryx/model/language_model/oryx_qwen.py`, `oryx/model/oryx_arch.py`;
SURVEY.md §1 L1c/L1d). The reference threads `images=` kwargs through HF
`forward`/`generate`; here the visual encode, splice, decoder forward and
decode loop are separate pure functions composed under one jit, all
operating on the static-shape packed buffers from ops/packing.py +
models/splice.py.

Param tree: {"llm": qwen2 params, "vit": oryx_vit params,
             "compressor": compressor params}.
"""

from __future__ import annotations

from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from oryx_tpu.config import OryxConfig
from oryx_tpu.models import compressor as compressor_lib
from oryx_tpu.models import generate as generate_lib
from oryx_tpu.models import oryx_vit, qwen2, splice
from oryx_tpu.ops.packing import PackedVisual, round_up_bucket

Params = dict[str, Any]


def init_params(cfg: OryxConfig, key: jax.Array, dtype=jnp.float32) -> Params:
    k1, k2, k3 = jax.random.split(key, 3)
    if cfg.vision is None:  # a text-only model: the decoder alone
        return {"llm": qwen2.init_params(cfg.llm, k1, dtype)}
    return {
        "llm": qwen2.init_params(cfg.llm, k1, dtype),
        "vit": oryx_vit.init_params(cfg.vision, k2, dtype),
        "compressor": compressor_lib.init_params(
            cfg.compressor, cfg.vision, cfg.llm, k3, dtype
        ),
    }


def enable_lora(params: Params, cfg: OryxConfig, key: jax.Array) -> Params:
    """Attach LoRA adapters to the decoder (reference `lora_enable`)."""
    return {
        **params,
        "llm": qwen2.add_lora_params(
            params["llm"], cfg.llm, cfg.train.lora, key
        ),
    }


def merge_lora(params: Params) -> Params:
    """Fold trained adapters into the decoder kernels for serving."""
    return {**params, "llm": qwen2.merge_lora_params(params["llm"])}


def encode_visual(
    params: Params,
    cfg: OryxConfig,
    patches: jnp.ndarray,
    segment_ids: jnp.ndarray,
    pos_coords: jnp.ndarray,
    region_ids: jnp.ndarray,
    q_region_ids: jnp.ndarray,
    *,
    remat: bool | str = False,
    compute_dtype=None,
) -> jnp.ndarray:
    """Packed patches → packed LLM-space visual embeddings [Q, H_llm].

    The reference's `encode_images` (SURVEY.md §3.4): one ViT pass over all
    images/frames of the batch, then the Dynamic Compressor.
    """
    # The vision tower keeps Pallas ONLY for single-program ("pallas")
    # configs. Under the sequence-parallel decoder modes the packed
    # patch axis is sharded across the mesh, and a pallas_call is not
    # GSPMD-partitionable — XLA would all-gather the full packed q/k/v
    # and run the kernel replicated per chip (+3.1 GB/chip at the
    # 256-frame 34B/v5e-64 point, AOT-measured, round 5) — so the
    # partitionable XLA segment-attention path is the right kernel
    # there, not a fallback.
    with jax.named_scope("vision"):
        feats = oryx_vit.forward(
            params["vit"], cfg.vision, patches, segment_ids, pos_coords,
            remat=remat, attn_impl=cfg.attn_impl,
            compute_dtype=compute_dtype,
        )
        return compressor_lib.forward(
            params["compressor"], cfg.compressor, cfg.vision,
            feats, region_ids, q_region_ids,
            attn_impl="pallas" if cfg.attn_impl == "pallas" else "xla",
        )


def forward(
    params: Params,
    cfg: OryxConfig,
    *,
    # Packed visual arrays (ops/packing.PackedVisual fields, device arrays):
    patches: jnp.ndarray,
    segment_ids: jnp.ndarray,
    pos_coords: jnp.ndarray,
    region_ids: jnp.ndarray,
    q_region_ids: jnp.ndarray,
    # Spliced text stream (models/splice.MMBatch fields, device arrays):
    token_ids: jnp.ndarray,
    visual_idx: jnp.ndarray,
    is_visual: jnp.ndarray,
    attn_mask: jnp.ndarray,
    positions: jnp.ndarray,
    remat: bool | str = False,
    mesh=None,
    compute_dtype=None,
    logits_dtype=jnp.float32,
    return_hidden: bool = False,
    text_segment_ids: jnp.ndarray | None = None,
) -> jnp.ndarray:
    """Training/prefill forward: visual encode → splice → decoder logits
    (or final hidden states when return_hidden, for the chunked-CE loss).

    mesh: only needed for attn_impl='ring' without an ambient mesh
    (jax.sharding.set_mesh) in scope.
    text_segment_ids: decoder-row sample ids for sequence-packed text
    training (train/data.collate_packed_text) — distinct from the
    VISUAL buffer's `segment_ids`."""
    vis = encode_visual(
        params, cfg, patches, segment_ids, pos_coords, region_ids,
        q_region_ids, remat=remat, compute_dtype=compute_dtype,
    )
    embeds = splice.embed_spliced(
        params["llm"]["embed"]["weight"], vis, token_ids, visual_idx, is_visual
    )
    out, _ = qwen2.forward(
        params["llm"], cfg.llm,
        inputs_embeds=embeds, positions=positions, kv_mask=attn_mask,
        remat=remat, attn_impl=cfg.attn_impl, mesh=mesh,
        compute_dtype=compute_dtype, logits_dtype=logits_dtype,
        return_hidden=return_hidden,
        segment_ids=text_segment_ids,
    )
    return out


@partial(jax.jit, static_argnames=("cfg",))
def mm_embeds(params, cfg: OryxConfig, arrays):
    """Visual encode + splice only → [B, T, H] decoder inputs (the
    prefill half of `mm_generate`; used by the streaming decode path)."""
    vis = encode_visual(
        params, cfg,
        arrays["patches"], arrays["segment_ids"], arrays["pos_coords"],
        arrays["region_ids"], arrays["q_region_ids"],
        compute_dtype=_dtype(cfg),
    )
    return splice.embed_spliced(
        params["llm"]["embed"]["weight"], vis,
        arrays["token_ids"], arrays["visual_idx"], arrays["is_visual"],
    )


@partial(jax.jit, static_argnames=("cfg", "max_new_tokens", "cache_len"))
def _jit_mm_generate(
    params, cfg: OryxConfig, arrays, max_new_tokens: int, cache_len: int,
    key, stop_sequences=None,
):
    vis = encode_visual(
        params, cfg,
        arrays["patches"], arrays["segment_ids"], arrays["pos_coords"],
        arrays["region_ids"], arrays["q_region_ids"],
        compute_dtype=_dtype(cfg),
    )
    embeds = splice.embed_spliced(
        params["llm"]["embed"]["weight"], vis,
        arrays["token_ids"], arrays["visual_idx"], arrays["is_visual"],
    )
    return generate_lib.generate(
        params["llm"], cfg.llm, cfg.generation,
        inputs_embeds=embeds, lengths=arrays["lengths"],
        max_new_tokens=max_new_tokens, cache_len=cache_len, key=key,
        attn_impl=cfg.attn_impl, compute_dtype=_dtype(cfg),
        stop_sequences=stop_sequences,
    )


def compute_dtype(cfg: OryxConfig):
    """cfg.dtype string → jnp dtype for matmuls/activations."""
    return {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[cfg.dtype]


_dtype = compute_dtype


def mm_generate(
    params: Params,
    cfg: OryxConfig,
    packed: PackedVisual,
    batch: splice.MMBatch,
    *,
    max_new_tokens: int | None = None,
    key: jax.Array | None = None,
    stop_sequences: jnp.ndarray | None = None,
) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """End-to-end multimodal generation from host-side packed inputs.

    Returns (tokens [B, max_new_tokens], num_generated [B], finished [B]
    bool — False means cut off by max_new_tokens) as numpy.
    The reference equivalent is `model.generate(input_ids, images=...)`
    (SURVEY.md §3.2). stop_sequences: see generate.make_stop_sequences.
    """
    if max_new_tokens is None:
        max_new_tokens = cfg.generation.max_new_tokens
    if key is None:
        key = jax.random.key(0)
    T = batch.token_ids.shape[1]
    cache_len = round_up_bucket(T + max_new_tokens)
    arrays = stage_mm_arrays(packed, batch)
    toks, num, fin = _jit_mm_generate(
        params, cfg, arrays, max_new_tokens, cache_len, key, stop_sequences
    )
    return np.asarray(toks), np.asarray(num), np.asarray(fin)


def stage_mm_arrays(packed: PackedVisual, batch: splice.MMBatch) -> dict:
    """Host packed/batch structs → the device-array dict `_jit_mm_generate`
    consumes. Single owner of the staging layout — the latency bench times
    the jitted program over these same arrays, so it can never drift from
    what serving runs."""
    return {
        "patches": jnp.asarray(packed.patches),
        "segment_ids": jnp.asarray(packed.segment_ids),
        "pos_coords": jnp.asarray(packed.pos_coords),
        "region_ids": jnp.asarray(packed.region_ids),
        "q_region_ids": jnp.asarray(packed.q_region_ids),
        "token_ids": jnp.asarray(batch.token_ids),
        "visual_idx": jnp.asarray(batch.visual_idx),
        "is_visual": jnp.asarray(batch.is_visual),
        "lengths": jnp.asarray(batch.lengths),
    }
