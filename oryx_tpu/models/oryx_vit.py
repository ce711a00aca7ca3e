"""OryxViT — SigLIP-derived vision transformer at arbitrary resolution.

Reference parity: `oryx/model/multimodal_encoder/oryx_vit.py` (SURVEY.md §1
L1a, §2 "OryxViT"; reference mount empty — behavior reconstructed). The
reference packs variable-size images into one `flash_attn_varlen_func` call
with cu_seqlens; here the packing is segment-ids over a bucketed static
buffer (ops/packing.py) and attention masks on segment equality — the
Pallas splash-attention kernel consumes the same layout (SURVEY.md §2a).

Structure per block (SigLIP family): pre-LN → MHA (biased projections) →
residual; pre-LN → MLP (gelu tanh) → residual; final post-LN. Learned
position embeddings live at base_grid² and are bilinearly resampled to each
image's (h, w) patch grid via per-patch continuous coordinates — one gather,
no per-image dynamic shapes.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from oryx_tpu.config import VisionConfig
from oryx_tpu.ops.attention import attention
from jax.ad_checkpoint import checkpoint_name

from oryx_tpu.ops.norms import layer_norm
from oryx_tpu.parallel.sharding import constrain
from oryx_tpu.utils.remat import wrap_remat

Params = dict[str, Any]


def init_params(
    cfg: VisionConfig, key: jax.Array, dtype: jnp.dtype = jnp.float32
) -> Params:
    L, H, I = cfg.num_layers, cfg.hidden_size, cfg.intermediate_size
    D = cfg.num_heads * cfg.head_dim
    patch_dim = cfg.patch_size * cfg.patch_size * cfg.num_channels
    keys = iter(jax.random.split(key, 12))

    def dense(k, shape):
        return (jax.random.normal(k, shape, jnp.float32) * 0.02).astype(dtype)

    def ln(shape=(L, H)):
        return {"weight": jnp.ones(shape, dtype), "bias": jnp.zeros(shape, dtype)}

    def proj(shape_in, shape_out):
        return {
            "kernel": dense(next(keys), (L, shape_in, shape_out)),
            "bias": jnp.zeros((L, shape_out), dtype),
        }

    return {
        "patch_embed": {
            "kernel": dense(next(keys), (patch_dim, H)),
            "bias": jnp.zeros((H,), dtype),
        },
        "pos_embed": {
            "weight": dense(next(keys), (cfg.base_grid * cfg.base_grid, H))
        },
        "layers": {
            "norm1": ln(),
            "norm2": ln(),
            "q_proj": proj(H, D),
            "k_proj": proj(H, D),
            "v_proj": proj(H, D),
            "o_proj": proj(D, H),
            "fc1": proj(H, I),
            "fc2": proj(I, H),
        },
        "post_norm": {"weight": jnp.ones((H,), dtype), "bias": jnp.zeros((H,), dtype)},
    }


def interp_pos_embed(
    table: jnp.ndarray, coords: jnp.ndarray, base_grid: int
) -> jnp.ndarray:
    """Bilinearly sample the posemb table at continuous coordinates.

    table: [G*G, H]; coords: [P, 2] source-space (sy, sx) from
    ops/packing.posemb_source_coords (align_corners=False semantics, edge
    clamped). Returns [P, H] float32.
    """
    G = base_grid
    grid = table.reshape(G, G, -1).astype(jnp.float32)
    sy, sx = coords[:, 0], coords[:, 1]
    y0f, x0f = jnp.floor(sy), jnp.floor(sx)
    ly, lx = sy - y0f, sx - x0f
    y0 = jnp.clip(y0f.astype(jnp.int32), 0, G - 1)
    y1 = jnp.clip(y0f.astype(jnp.int32) + 1, 0, G - 1)
    x0 = jnp.clip(x0f.astype(jnp.int32), 0, G - 1)
    x1 = jnp.clip(x0f.astype(jnp.int32) + 1, 0, G - 1)
    ly, lx = ly[:, None], lx[:, None]
    return (
        grid[y0, x0] * (1 - ly) * (1 - lx)
        + grid[y0, x1] * (1 - ly) * lx
        + grid[y1, x0] * ly * (1 - lx)
        + grid[y1, x1] * ly * lx
    )


def _linear(x, p):
    return x @ p["kernel"].astype(x.dtype) + p["bias"].astype(x.dtype)


def forward(
    params: Params,
    cfg: VisionConfig,
    patches: jnp.ndarray,
    segment_ids: jnp.ndarray,
    pos_coords: jnp.ndarray,
    *,
    remat: bool | str = False,
    attn_impl: str = "xla",
    compute_dtype: jnp.dtype | None = None,
) -> jnp.ndarray:
    """Encode a packed patch buffer.

    patches: [P, patch_dim]; segment_ids: [P] (0 = pad); pos_coords: [P, 2].
    Returns features [P, hidden] in compute dtype (pad rows are garbage;
    consumers mask on segment_ids).
    """
    H = cfg.hidden_size
    emb = patches.astype(jnp.float32) @ params["patch_embed"]["kernel"].astype(
        jnp.float32
    ) + params["patch_embed"]["bias"].astype(jnp.float32)
    emb = emb + interp_pos_embed(
        params["pos_embed"]["weight"], pos_coords, cfg.base_grid
    )
    if compute_dtype is not None:
        emb = emb.astype(compute_dtype)
    else:
        emb = emb.astype(patches.dtype)

    # Batch dim of 1: the packed buffer IS the batch; the packing axis
    # shards over the data width (Trainer._device_batch) — pin it so GSPMD
    # doesn't guess intermediates. "sp" rides along: to the vision tower
    # the patch axis is pure data, so sequence-parallel devices take
    # patch shards too — at the 256-frame long-video scale the 27-layer
    # residual stacks over 16k patches/chip are the memory; an sp-less
    # mesh drops the axis (constrain).
    pk_spec = (None, ("dp", "fsdp", "sp"), None)
    h = constrain(emb[None], *pk_spec)  # [1, P, H]
    seg = segment_ids[None]  # [1, P]

    if attn_impl == "pallas":
        from oryx_tpu.ops.pallas import segment_attention as _sa

        def attn_fn(q, k, v):
            return _sa.segment_attention(q, k, v, seg, seg)
    elif attn_impl in ("xla", "ring", "ring_flash"):
        # "ring"/"ring_flash" (decoder sequence parallelism) have no
        # meaning for the packed ViT buffer; its parallel story is
        # sharding the packing axis, which the XLA path handles under
        # GSPMD.
        def attn_fn(q, k, v):
            return attention(q, k, v, q_segment_ids=seg, kv_segment_ids=seg)
    else:
        raise ValueError(f"unknown attn_impl {attn_impl!r}")

    def body(carry, lp):
        h = carry
        x = layer_norm(
            h, lp["norm1"]["weight"], lp["norm1"]["bias"], cfg.layer_norm_eps
        )
        B, P, _ = x.shape
        q = _linear(x, lp["q_proj"]).reshape(B, P, cfg.num_heads, cfg.head_dim)
        k = _linear(x, lp["k_proj"]).reshape(B, P, cfg.num_heads, cfg.head_dim)
        v = _linear(x, lp["v_proj"]).reshape(B, P, cfg.num_heads, cfg.head_dim)
        # Same remat tags as the decoder block (models/qwen2._block) so the
        # "attn_qkv"/"attn_o" policies skip the encoder's projection and
        # attention recompute too; the attention output itself is tagged
        # "flash_out" inside attn_fn's implementation.
        q = checkpoint_name(q, "attn_q")
        k = checkpoint_name(k, "attn_k")
        v = checkpoint_name(v, "attn_v")
        o = attn_fn(q, k, v).reshape(B, P, -1)
        h = h + checkpoint_name(_linear(o, lp["o_proj"]), "attn_o")
        x = layer_norm(
            h, lp["norm2"]["weight"], lp["norm2"]["bias"], cfg.layer_norm_eps
        )
        x = jax.nn.gelu(_linear(x, lp["fc1"]), approximate=True)
        h = h + _linear(x, lp["fc2"])
        return constrain(h, *pk_spec), None

    body = wrap_remat(body, remat)
    h, _ = jax.lax.scan(body, h, params["layers"])

    h = layer_norm(
        h, params["post_norm"]["weight"], params["post_norm"]["bias"],
        cfg.layer_norm_eps,
    )
    return h[0]
