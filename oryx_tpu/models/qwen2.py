"""Qwen2/Yi-class causal decoder, TPU-first functional implementation.

Reference parity: the HF `Qwen2ForCausalLM` backbone that Oryx wraps
(SURVEY.md §1 L1d, §2 "LLM wrapper"). Geometry covers both Oryx-7B
(Qwen2-7B, attention bias) and Oryx-34B (Yi-34B, no bias) via `LLMConfig`.

Design (deliberately not a torch translation):
  * Params are plain nested-dict pytrees; per-layer weights are STACKED along
    a leading layer axis and the block is applied with `lax.scan`. One block
    compiles once regardless of depth, remat applies per scan step, and FSDP
    all-gathers one layer at a time — the idiomatic XLA/TPU layout.
  * All matmuls take bf16 inputs with fp32 softmax/norm accumulation
    (ops/norms.py, ops/attention.py) so TPU runs track the CUDA reference.
  * KV cache is a pytree of [L, B, S, Hk, D] arrays written with per-row
    dynamic slices — static shapes throughout, decode step fully jittable.

Weight layout: linear kernels are [in, out] (x @ W); the HF importer
transposes torch's [out, in].
"""

from __future__ import annotations

from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from oryx_tpu.config import (
    LLMConfig, unsupported_for_recurrent, unsupported_for_window,
)
from oryx_tpu.ops.attention import attention
from oryx_tpu.ops.norms import rms_norm
from oryx_tpu.ops.rope import apply_rope, rope_cos_sin, yarn_frequencies
from oryx_tpu.parallel.sharding import constrain
from oryx_tpu.utils.remat import wrap_remat

Params = dict[str, Any]


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------


def init_params(
    cfg: LLMConfig, key: jax.Array, dtype: jnp.dtype = jnp.float32
) -> Params:
    """Random-normal init (scale 0.02, zero biases) in the stacked layout."""
    if cfg.latent:
        return _init_latent_params(cfg, key, dtype)
    if cfg.recurrent:
        return _init_recurrent_params(cfg, key, dtype)
    L, H = cfg.num_layers, cfg.hidden_size
    Dq = cfg.num_heads * cfg.head_dim
    Dkv = cfg.num_kv_heads * cfg.head_dim
    I = cfg.intermediate_size
    keys = iter(jax.random.split(key, 16))

    def dense(k, shape, dt=dtype):
        return (jax.random.normal(k, shape, jnp.float32) * 0.02).astype(dt)

    def stack(shape):
        return dense(next(keys), (L, *shape))

    params: Params = {
        "embed": {"weight": dense(next(keys), (cfg.vocab_size, H))},
        "layers": {
            "input_norm": {"weight": jnp.ones((L, H), dtype)},
            "post_attn_norm": {"weight": jnp.ones((L, H), dtype)},
            "q_proj": {"kernel": stack((H, Dq))},
            "k_proj": {"kernel": stack((H, Dkv))},
            "v_proj": {"kernel": stack((H, Dkv))},
            "o_proj": {"kernel": stack((Dq, H))},
        },
        "final_norm": {"weight": jnp.ones((H,), dtype)},
    }
    if cfg.num_experts:
        # Expert layer (`_moe`): the router stays float32 whatever the
        # serving dtype (routing is a discontinuous function of it);
        # expert kernels are stacked [L, E, in, out].
        E, Ie = cfg.num_experts, cfg.moe_intermediate_size
        params["layers"]["router"] = {
            "kernel": dense(next(keys), (L, H, E), jnp.float32)
        }
        params["layers"]["experts"] = {
            "gate": stack((E, H, Ie)),
            "up": stack((E, H, Ie)),
            "down": stack((E, Ie, H)),
        }
    else:
        params["layers"]["gate_proj"] = {"kernel": stack((H, I))}
        params["layers"]["up_proj"] = {"kernel": stack((H, I))}
        params["layers"]["down_proj"] = {"kernel": stack((I, H))}
    if cfg.qk_norm:
        params["layers"]["q_norm"] = {
            "weight": jnp.ones((L, cfg.head_dim), dtype)
        }
        params["layers"]["k_norm"] = {
            "weight": jnp.ones((L, cfg.head_dim), dtype)
        }
    if cfg.attention_bias:
        params["layers"]["q_proj"]["bias"] = jnp.zeros((L, Dq), dtype)
        params["layers"]["k_proj"]["bias"] = jnp.zeros((L, Dkv), dtype)
        params["layers"]["v_proj"]["bias"] = jnp.zeros((L, Dkv), dtype)
    if not cfg.tie_word_embeddings:
        params["lm_head"] = {"kernel": dense(next(keys), (H, cfg.vocab_size))}
    return params


def _init_recurrent_params(cfg: LLMConfig, key: jax.Array, dtype) -> Params:
    """A hybrid's weights (a config with state layers): homogeneous
    stacks, in layer order within each. `layers["attn"]` [La, ...] is
    `_block`'s own set (norms, q/k/v/o, q/k norm) for the layers that
    attend; `layers[cfg.state_kind]` ("mamba" or "conv") [Ls, ...]
    holds the two norms and under `"mixer"` the mixer
    (`mamba.init_mixer_params` / `short_conv.init_mixer_params`) of
    the others. The FFN is by position (`cfg.ffn_kinds`). Without
    experts every layer's dense SwiGLU lies in its own stack, beside
    its norms. With experts: `layers["dense"]` [dense_layers, ...] the
    leading dense SwiGLUs and `layers["router"]` / `layers["experts"]`
    [moe layers, ...] the expert layers', each indexed by the layer's
    number within its FFN kind. The head is the embedding, transposed
    (tied), unless the config unties it."""
    from oryx_tpu.models import mamba, mamba2, short_conv

    H, I = cfg.hidden_size, cfg.intermediate_size
    Dq = cfg.num_heads * cfg.head_dim
    Dkv = cfg.num_kv_heads * cfg.head_dim
    keys = iter(jax.random.split(key, 16))
    own = not cfg.num_experts
    # One sublayer a layer (`cfg.hybrid_override_pattern`): one norm a
    # layer, a mixer's in its own stack, an expert layer's under
    # `layers["ffn_norm"]`.
    single = bool(cfg.hybrid_override_pattern)

    def dense(shape, dt=dtype, scale=0.02):
        return (
            jax.random.normal(next(keys), shape, jnp.float32) * scale
        ).astype(dt)

    def swiglu(L):
        return {
            "gate_proj": {"kernel": dense((L, H, I))},
            "up_proj": {"kernel": dense((L, H, I))},
            "down_proj": {"kernel": dense((L, I, H))},
        }

    def common(L):
        if single:
            return {"input_norm": {"weight": jnp.ones((L, H), dtype)}}
        return dict({
            "input_norm": {"weight": jnp.ones((L, H), dtype)},
            "post_attn_norm": {"weight": jnp.ones((L, H), dtype)},
        }, **(swiglu(L) if own else {}))

    La, Ls = cfg.num_attn_layers, cfg.num_state_layers
    kind = cfg.state_kind
    mixers = {"mamba": mamba, "mamba2": mamba2, "conv": short_conv}[kind]
    params: Params = {
        "embed": {"weight": dense((cfg.vocab_size, H))},
        "layers": {
            "attn": dict(
                common(La),
                q_proj={"kernel": dense((La, H, Dq))},
                k_proj={"kernel": dense((La, H, Dkv))},
                v_proj={"kernel": dense((La, H, Dkv))},
                o_proj={"kernel": dense((La, Dq, H))},
            ),
            kind: dict(
                common(Ls),
                mixer=mixers.init_mixer_params(cfg, next(keys), Ls, dtype),
            ),
        },
        "final_norm": {"weight": jnp.ones((H,), dtype)},
    }
    if not cfg.tie_word_embeddings:
        params["lm_head"] = {"kernel": dense((H, cfg.vocab_size))}
    layers = params["layers"]
    if not own or cfg.qk_norm:
        # Keys of their own, off one the leaves above leave unused.
        keys = iter(jax.random.split(next(keys), 10))
    if cfg.qk_norm:
        # Log-normal around 1 (sigma 0.5), not ones: at these kernels q
        # and k come out of their projections near unit rms, so a norm
        # with a weight of 1 is nearly the identity and a program that
        # left it out could not be told from one that has it.
        for n in ("q_norm", "k_norm"):
            layers["attn"][n] = {"weight": jnp.exp(0.5 * jax.random.normal(
                next(keys), (La, cfg.head_dim), jnp.float32)).astype(dtype)}
    if not own:
        E, Ie = cfg.num_experts, cfg.moe_intermediate_size
        Ld, Lm = cfg.dense_layers, cfg.moe_layers
        if Ld:
            layers["dense"] = swiglu(Ld)
        layers["router"] = {"kernel": dense((Lm, H, E), jnp.float32)}
        if cfg.router_bias:
            # Small against a sigmoid's ~1/2 and not 0: the selection is
            # not the scores' order at some tokens, so selecting by
            # p + b while weighing by p can be told from weighing by
            # p + b, and the experts' load stays as even as the scores
            # leave it (at 0.2, the scores' own spread, a few experts
            # with the largest bias took most rows: rows of the fullest
            # expert over the mean 10.0 where 0.05 reads 3.6).
            layers["router"]["bias"] = dense(
                (Lm, E), jnp.float32,
                scale=0.05 if cfg.router_scoring == "sigmoid" else 0.2 / E)
        # The experts this chip holds (`cfg.experts_held`), in a latent
        # of their own where the config has one.
        count, Hl = cfg.held[1], cfg.moe_latent_size or H
        gated = cfg.moe_activation != "relu2"
        layers["experts"] = dict(
            {"gate": dense((Lm, count, Hl, Ie))} if gated else {},
            up=dense((Lm, count, Hl, Ie)),
            down=dense((Lm, count, Ie, Hl)),
        )
        if cfg.moe_latent_size:
            layers["latent"] = {
                "down": {"kernel": dense((Lm, H, Hl))},
                "up": {"kernel": dense((Lm, Hl, H))},
            }
        if cfg.n_shared_experts:
            Is = cfg.shared_expert_width
            layers["shared"] = dict(
                {"gate_proj": {"kernel": dense((Lm, H, Is))}} if gated else {},
                up_proj={"kernel": dense((Lm, H, Is))},
                down_proj={"kernel": dense((Lm, Is, H))},
            )
        if single:
            layers["ffn_norm"] = {"weight": jnp.ones((Lm, H), dtype)}
    return params


def _init_latent_params(cfg: LLMConfig, key: jax.Array, dtype) -> Params:
    """A latent-attention model's weights. The double layer's
    (`_double_block`): `layers["sub0"]` / `["sub1"]` hold the two
    (attention, dense FFN) sublayers; the single block's
    (`_latent_block`): the one attention's leaves lie in `layers` itself
    and `layers["shared"]` is the shared expert. Every leaf is
    [L, ...], so the layer scan slices a
    kernel out as it lies; projections that the published checkpoint
    keeps fused are stored as the parts they are used in (a fused
    kernel sliced by column inside a step is copied whole, every
    dispatch): Wq_b by columns into `q_b_nope` / `q_b_rope`, Wkv_a into
    `kv_a_proj` (the latent) / `k_rope_proj` (the shared key), Wkv_b by
    head into `w_uk` [Hq, dn, R] (transposed, as the absorbed decode
    multiplies it) and `w_uv` [Hq, R, dv]. The router [L, H, E + Z]
    and its selection bias [L, E + Z] stay float32; the expert stacks
    hold the HELD experts only, [L, count, in, out] (expert first + j
    at j). The bias is small against a probability of 1 / (E + Z) and
    not 0, so that selecting by p + b and weighing by p can be told
    apart."""
    L, H, I = cfg.num_layers, cfg.hidden_size, cfg.intermediate_size
    Hq, Rq, R = cfg.num_heads, cfg.q_lora_rank, cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    E, Z, Ie = cfg.num_experts, cfg.zero_experts, cfg.moe_intermediate_size
    count = cfg.held[1]
    Ld = cfg.dense_layers
    L = L - Ld  # the expert layers; the leading dense ones lie beside them
    keys = iter(jax.random.split(key, 40))

    def dense(shape, dt=dtype, scale=0.02):
        return (
            jax.random.normal(next(keys), shape, jnp.float32) * scale
        ).astype(dt)

    def sublayer(ffn=True, L=L):
        sub = {
            "input_norm": {"weight": jnp.ones((L, H), dtype)},
            "post_attn_norm": {"weight": jnp.ones((L, H), dtype)},
            "q_a_proj": {"kernel": dense((L, H, Rq))},
            "q_a_norm": {"weight": jnp.ones((L, Rq), dtype)},
            "q_b_nope": {"kernel": dense((L, Rq, Hq * dn))},
            "q_b_rope": {"kernel": dense((L, Rq, Hq * dr))},
            "kv_a_proj": {"kernel": dense((L, H, R))},
            "k_rope_proj": {"kernel": dense((L, H, dr))},
            "kv_a_norm": {"weight": jnp.ones((L, R), dtype)},
            "w_uk": dense((L, Hq, dn, R)),
            "w_uv": dense((L, Hq, R, dv)),
            "o_proj": {"kernel": dense((L, Hq * dv, H))},
        }
        if ffn:
            sub.update({
                "gate_proj": {"kernel": dense((L, H, I))},
                "up_proj": {"kernel": dense((L, H, I))},
                "down_proj": {"kernel": dense((L, I, H))},
            })
        return sub

    def indexer(L):
        # The indexer's leaves (`_index_inputs`): queries off the query
        # latent, ONE key a token off the layer's normed input through a
        # LayerNorm, the heads' weights off the same input.
        Hi, Di = cfg.index_heads, cfg.index_head_dim
        return {
            "q_b": {"kernel": dense((L, Rq, Hi * Di))},
            "k_proj": {"kernel": dense((L, H, Di))},
            "k_norm": {"weight": jnp.ones((L, Di), dtype),
                       "bias": dense((L, Di))},
            "head_weights": {"kernel": dense((L, H, Hi))},
        }

    if cfg.shortcut_double_layer:
        layers = {"sub0": sublayer(), "sub1": sublayer()}
    else:
        # The single latent block (`_latent_block`): one attention, no
        # dense FFN; the shared expert is ONE SwiGLU of n_shared_experts
        # * moe_intermediate_size beside the routed experts.
        layers = sublayer(ffn=False)
        if cfg.n_shared_experts:
            Is = cfg.n_shared_experts * Ie
            layers["shared"] = {
                "gate_proj": {"kernel": dense((L, H, Is))},
                "up_proj": {"kernel": dense((L, H, Is))},
                "down_proj": {"kernel": dense((L, Is, H))},
            }
    layers.update({
        "router": {"kernel": dense((L, H, E + Z), jnp.float32)},
        "experts": {
            "gate": dense((L, count, H, Ie)),
            "up": dense((L, count, H, Ie)),
            "down": dense((L, count, Ie, H)),
        },
    })
    if cfg.router_bias:
        # (a sigmoid's probabilities are near 1/2 each, not 1 / (E + Z).)
        layers["router"]["bias"] = dense(
            (L, E + Z), jnp.float32,
            scale=0.05 if cfg.router_scoring == "sigmoid" else 0.2 / (E + Z)
        )
    params = {
        "embed": {"weight": dense((cfg.vocab_size, H))},
        "layers": layers,
        "final_norm": {"weight": jnp.ones((H,), dtype)},
        "lm_head": {"kernel": dense((H, cfg.vocab_size))},
    }
    if cfg.indexed or Ld:
        # Keys of their own, off one the leaves above leave unused:
        # every leaf above is what it was without these.
        keys = iter(jax.random.split(next(keys), 40))
        if cfg.indexed:
            layers["indexer"] = indexer(L)
        if Ld:
            # [Ld, ...]: `_latent_block` with a dense FFN and no experts.
            params["dense_layers"] = sublayer(L=Ld)
            if cfg.indexed:
                params["dense_layers"]["indexer"] = indexer(Ld)
    return params


# ---------------------------------------------------------------------------
# KV cache
# ---------------------------------------------------------------------------


def init_kv_cache(
    cfg: LLMConfig, batch: int, max_len: int, dtype: jnp.dtype = jnp.bfloat16
) -> Params:
    shape = (cfg.num_layers, batch, max_len, cfg.num_kv_heads, cfg.head_dim)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def init_paged_kv_cache(
    cfg: LLMConfig, num_pages: int, page_size: int,
    dtype: jnp.dtype = jnp.bfloat16,
    kv_dtype: str | None = None,
    num_slots: int | None = None,
) -> Params:
    """Page-pool KV cache (ops/paged_kv.py): one pool of fixed-size
    pages shared by every sequence; rows address it through per-row
    block tables passed to `forward`. HBM cost is the POOL size, not
    batch × max_len.

    kv_dtype: None/"bf16" stores pages densely in `dtype` (the
    compute dtype — today's path, byte-for-byte). "int8" (or
    "fp8_e4m3") stores QUANTIZED pages — ops/paged_kv.QuantPages
    planes: codes + per-page scale blocks, quantize-on-write /
    dequantize-in-the-page-walk — roughly doubling resident KV tokens
    per HBM byte; `dtype` then names the dequant target the kernels
    multiply out into.

    Stored layout: every leaf is [L, P, page, ...] (codes and, on a
    quantized pool, the [L, P, page] scale planes), and that is what
    `copy_pages`, `fetch_page`, `upload_page`, the prefix cache, the
    spill tier and `sharding.paged_kv_spec` address. `forward` views it
    as L*P pages while it runs (see its `block_tables` contract) and
    hands it back in this layout.

    A config with state layers (`cfg.recurrent`) has TWO kinds of
    state in the one pytree: paged `k` / `v` over its attention layers
    alone ([La, P, page, Hk, D]; [La, P, page, Hk / r, r * D] where
    r = `cfg.kv_pack` heads of under 128 lanes share a row of lanes) and
    per-SLOT planes that no block
    table addresses, `conv` [Ls, S, (K-1) * d] in `dtype` (the last
    K - 1 conv inputs, flat so that the plane has no 3-row tile to pad)
    and, for Mamba layers, `ssm` [Ls, S, N, d] float32 (channels in the
    lanes), S = `num_slots`. `ops/paged_kv.paged_planes` tells them
    apart. Gated short convolutions (`cfg.state_kind == "conv"`) have
    no `ssm` plane and one more PAGED plane, `conv_edge` [Ls, P,
    (K-1) * d]: the `conv` rows as they stood after each page's last
    token (`ops/paged_kv.CONV_EDGE`).

    A config with window layers (`cfg.windowed`) has TWO paged planes,
    one a layer kind, each with its own page count, allocator and block
    table: `num_pages` is then the pair (global pages, window pages),
    `k` / `v` hold the global layers' [Lg, Pg, page, Hk, D] and
    `ops/paged_kv.WINDOW_PLANES` (`wk` / `wv`) the window layers'
    [Lw, Pw, page, Hk, D]. A page index means something in ONE of the
    two."""
    if cfg.windowed:
        from oryx_tpu.ops import paged_kv

        if kv_dtype not in (None, "bf16", "fp"):
            raise ValueError(unsupported_for_window(f"kv_dtype={kv_dtype!r}"))
        try:
            Pg, Pw = num_pages
        except TypeError:
            raise ValueError(
                "a config with window layers keeps a plane a layer kind: "
                "init_paged_kv_cache needs num_pages = (global pages, "
                "window pages)") from None
        tail = (page_size, cfg.num_kv_heads, cfg.head_dim)
        shapes = dict(zip(
            ("k", "v") + paged_kv.WINDOW_PLANES,
            2 * [(cfg.num_global_layers, Pg) + tail]
            + 2 * [(cfg.num_window_layers, Pw) + tail]))
        return {n: jnp.zeros(sh, dtype) for n, sh in shapes.items()}
    if cfg.recurrent:
        from oryx_tpu.ops import paged_kv

        if kv_dtype not in (None, "bf16", "fp"):
            raise ValueError(
                unsupported_for_recurrent(f"kv_dtype={kv_dtype!r}"))
        if num_slots is None:
            raise ValueError(
                "a config with state-space layers keeps a state a slot: "
                "init_paged_kv_cache needs num_slots")
        La, Ls = cfg.num_attn_layers, cfg.num_state_layers
        CONV, SSM = paged_kv.SLOT_PLANES
        # cfg.kv_pack heads to a row of lanes (`_block_attention`).
        r = cfg.kv_pack
        shape = (La, num_pages, page_size, cfg.num_kv_heads // r,
                 r * cfg.head_dim)
        pool = {
            "k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype),
            CONV: jnp.zeros((Ls, num_slots, cfg.conv_state_width), dtype),
        }
        if cfg.state_kind in ("mamba", "mamba2"):
            # (Mamba-2: head h's [N, P] state the P lanes from h P.)
            pool[SSM] = jnp.zeros(
                (Ls, num_slots, cfg.mamba_d_state, cfg.mamba_d_inner),
                jnp.float32)
        else:
            # A gated short convolution's rows are its whole state: a
            # snapshot of them a PAGE, behind the pages' own table.
            pool[paged_kv.CONV_EDGE] = jnp.zeros(
                (Ls, num_pages, cfg.conv_state_width), dtype)
        return pool
    if cfg.latent:
        # One plane of cfg.cache_layers layers (two a model layer in the
        # double layer, else one), no head axis: a token's row is
        # (latent | roped shared key | zeros to whole lane tiles). See
        # ops/paged_kv.LATENT.
        from oryx_tpu.ops import paged_kv

        if kv_dtype not in (None, "bf16", "fp"):
            raise ValueError(unsupported_for_latent(f"kv_dtype={kv_dtype!r}"))
        pool = {paged_kv.LATENT: jnp.zeros(
            (cfg.cache_layers, num_pages, page_size, cfg.latent_page_dim),
            dtype,
        )}
        if cfg.indexed:
            # A token's index key lies where its latent does.
            pool[paged_kv.INDEX_K] = jnp.zeros(
                (cfg.cache_layers, num_pages, page_size, cfg.index_head_dim),
                dtype,
            )
        return pool
    shape = (
        cfg.num_layers, num_pages, page_size, cfg.num_kv_heads, cfg.head_dim
    )
    if kv_dtype in (None, "bf16", "fp"):
        return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}
    from oryx_tpu.ops import paged_kv

    mk = lambda: paged_kv.init_quant_pages(  # noqa: E731
        cfg.num_layers, num_pages, page_size, cfg.num_kv_heads,
        cfg.head_dim, fmt=kv_dtype, dequant_dtype=dtype,
    )
    return {"k": mk(), "v": mk()}


def unsupported_for_latent(mode: str) -> str:
    """The one refusal of a mode that is not built for a latent-attention
    (MLA) config: its pool is one plane of latents with no head axis
    (two cache layers a model layer in the double layer, else one),
    which only the split engine's `paged_prefill` / `paged_decode_chunk`
    read."""
    return (
        f"latent attention (kv_lora_rank > 0): {mode} is not built for a "
        "latent pool (one [layers, P, page, latent] plane, no K/V heads); it "
        "serves through the continuous split engine with a bf16 pool only"
    )


def _cache_write(cache_layer: jnp.ndarray, new: jnp.ndarray, slots: jnp.ndarray):
    """Write new [B, T, Hk, D] into cache [B, S, Hk, D] at per-row start slots.

    slots: [B] int32 — index of the first written position per row. Assumes
    the T new entries occupy contiguous slots (true for prefill-from-0 and
    single-token decode).
    """

    def row(c, x, s):
        return jax.lax.dynamic_update_slice(c, x.astype(c.dtype), (s, 0, 0))

    return jax.vmap(row)(cache_layer, new, slots)


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------


def _linear(x, p):
    y = x @ p["kernel"].astype(x.dtype)
    if "lora_a" in p:
        # Low-rank residual (W + scale·A·B)x; scale rides as a [1, 1]
        # per-layer leaf so the stacked-layer scan slices it with the rest.
        delta = (x @ p["lora_a"].astype(x.dtype)) @ p["lora_b"].astype(x.dtype)
        y = y + delta * p["lora_scale"].astype(x.dtype)
    if "bias" in p:
        y = y + p["bias"].astype(x.dtype)
    return y


def add_lora_params(
    params: Params, cfg: LLMConfig, lora, key: jax.Array,
    dtype: jnp.dtype = jnp.float32,
) -> Params:
    """Attach LoRA adapters to the stacked decoder projections.

    Reference parity: train.py's `lora_enable` (PEFT LoraConfig on the
    decoder projections). A ~ N(0, 0.02), B = 0 — the adapted model is
    exactly the base model at step 0. `lora` is config.LoraConfig.
    """
    import copy

    L = cfg.num_layers
    layers = dict(params["layers"])
    keys = iter(jax.random.split(key, len(lora.targets)))
    for name in lora.targets:
        if name not in layers:
            raise ValueError(f"unknown LoRA target {name!r}")
        p = dict(layers[name])
        d_in, d_out = p["kernel"].shape[1], p["kernel"].shape[2]
        p["lora_a"] = (
            jax.random.normal(next(keys), (L, d_in, lora.r), jnp.float32)
            * 0.02
        ).astype(dtype)
        p["lora_b"] = jnp.zeros((L, lora.r, d_out), dtype)
        p["lora_scale"] = jnp.full((L, 1, 1), lora.scaling, dtype)
        layers[name] = p
    out = copy.copy(params)
    out["layers"] = layers
    return out


def merge_lora_params(params: Params) -> Params:
    """Fold trained adapters into the base kernels (for serving/export):
    kernel += scale·A·B per layer; adapter leaves are dropped."""
    import copy

    layers = {}
    for name, p in params["layers"].items():
        if isinstance(p, dict) and "lora_a" in p:
            p = dict(p)
            delta = jnp.einsum(
                "lir,lro->lio", p["lora_a"].astype(jnp.float32),
                p["lora_b"].astype(jnp.float32),
            ) * p["lora_scale"].astype(jnp.float32)
            p["kernel"] = (
                p["kernel"].astype(jnp.float32) + delta
            ).astype(params["layers"][name]["kernel"].dtype)
            for k_ in ("lora_a", "lora_b", "lora_scale"):
                del p[k_]
        layers[name] = p
    out = copy.copy(params)
    out["layers"] = layers
    return out


def moe_route(cfg: LLMConfig, x: jnp.ndarray, router_kernel: jnp.ndarray,
              router_bias: jnp.ndarray | None = None):
    """Router of the expert layer, in float32 at full matmul precision:
    x [N, H] -> (weights [N, K] float32, expert ids [N, K] int32), the K
    largest softmax probabilities (ties: the lower expert id first),
    renormalized to sum 1 when cfg.norm_topk_prob. `router_bias` [E]
    is added for the SELECTION only (the weights stay the
    probabilities); cfg.routed_scaling_factor multiplies the weights."""
    return moe_select(cfg, router_logits(x, router_kernel), router_bias)


def router_logits(x: jnp.ndarray, router_kernel: jnp.ndarray):
    """x [N, H] -> the router's logits [N, E], float32 at full matmul
    precision."""
    return jnp.matmul(
        x.astype(jnp.float32), router_kernel.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST,
    )


def moe_select(cfg: LLMConfig, r: jnp.ndarray,
               router_bias: jnp.ndarray | None = None):
    """`moe_route` from the logits r [N, E] on: wherever they were
    taken (cfg.router_input)."""
    if cfg.router_scoring == "sigmoid":
        p = jax.nn.sigmoid(r)
    else:
        p = jax.nn.softmax(r, axis=-1)
    if router_bias is None:
        w, idx = jax.lax.top_k(p, cfg.num_experts_per_tok)
    else:
        _, idx = jax.lax.top_k(
            p + router_bias.astype(jnp.float32), cfg.num_experts_per_tok
        )
        w = jnp.take_along_axis(p, idx, axis=-1)
    if cfg.norm_topk_prob:
        total = jnp.sum(w, axis=-1, keepdims=True)
        w = w / (total + cfg.norm_topk_eps if cfg.norm_topk_eps else total)
    if cfg.routed_scaling_factor != 1.0:
        w = w * cfg.routed_scaling_factor
    return w, idx.astype(jnp.int32)


# The grouped matmul that jax ships takes a whole [in, out] kernel of one
# group as its tile (the products then add up in the order XLA's own
# grouped product adds them: bit-equal results on the chip); two
# buffers of it have to fit the kernel's VMEM.
_GMM_ROW_TILE = 128
_GMM_MAX_KERNEL = 2 * 1024 * 1024  # elements: 2048 x 768 is 1.5 M
_GMM_TILE = 1024  # k and n tile of a kernel over that


def _grouped_dot(rows: jnp.ndarray, kernels: jnp.ndarray,
                 groups: jnp.ndarray, impl: str) -> jnp.ndarray:
    """rows [M, in], sorted by group, x kernels [G, in, out] -> [M, out]:
    row r times the kernel of the group it lies in (`groups` [G] row
    counts, summing to M). `jax.lax.ragged_dot` everywhere; under
    impl="pallas", at widths its tiles divide, the grouped matmul jax
    ships (`megablox.gmm`). XLA's grouped product on the chip reads the
    kernels at a quarter of the HBM rate when a group holds 8 rows (1.98
    ms for 128 kernels of 2048 x 768 where gmm takes 0.64; PERF.md
    section 6, PR 26), and a block step is bound by reading them."""
    M, K = rows.shape
    N = kernels.shape[-1]
    tk, tn = K, N
    if impl != "pallas" or K % 128 or N % 128:
        return jax.lax.ragged_dot(rows, kernels, groups)
    if K * N > _GMM_MAX_KERNEL:
        # A kernel too large for one tile (6144 x 2048): tiles of
        # 1024 x 1024, accumulated over the k tiles in float32; where
        # 1024 does not divide a side (2048 x 1536), the largest whole
        # number of 128 lanes under it that does (768).
        tk, tn = (
            max(t for t in range(128, _GMM_TILE + 1, 128) if n % t == 0)
            for n in (K, N))
    import importlib

    from oryx_tpu.ops.pallas.flash_attention import _use_interpret

    # (the package's `gmm` attribute is its differentiable wrapper, which
    # has no tiling of a whole kernel; the module holds the forward.)
    gmm = importlib.import_module(
        "jax.experimental.pallas.ops.tpu.megablox.gmm").gmm
    pad = -M % _GMM_ROW_TILE  # rows past the last group: sliced off again
    out = gmm(
        jnp.pad(rows, ((0, pad), (0, 0))) if pad else rows, kernels, groups,
        preferred_element_type=rows.dtype, tiling=(_GMM_ROW_TILE, tk, tn),
        interpret=_use_interpret(),
    )
    return out[:M] if pad else out


def _moe(cfg: LLMConfig, x: jnp.ndarray, router_kernel: jnp.ndarray,
         experts: Params, layer: jnp.ndarray, impl: str = "xla",
         router_bias: jnp.ndarray | None = None,
         shared: Params | None = None,
         logits: jnp.ndarray | None = None,
         latent: Params | None = None):
    """Sparse expert MLP on x [N, H]: dropless, no capacity factor, no
    padding to a capacity. The N*K (token, expert) pairs are sorted by
    expert and the gate, up and down products run as grouped products
    over the sorted rows (`_grouped_dot`: `jax.lax.ragged_dot`, or under
    impl="pallas" the grouped matmul jax ships); the K results of a
    token are gathered back and summed with the router's weights in
    float32.

    `experts` holds EVERY layer's kernels, flat [L*E, in, out], and the
    products run over L*E groups of which only layer `layer`'s E have
    rows. The grouped product is a kernel call, so its operand has to
    exist in memory: a layer's [E, in, out] slice of the stacked weights
    would be copied out in every layer of every forward (read, written
    and read again: three times the bytes of a step that is bound by
    reading them once), where the whole stack is passed as it lies.

    The chip's share (cfg.experts_held = (first, count), with
    cfg.zero_experts identity experts behind the routed ones): the router
    keeps its E + Z outputs and its K a token, `experts` holds the HELD
    experts only, flat [L*count, in, out], and the layer returns
    sum_{k zero} w_k x + sum_{k held} w_k E_k(x); what an absent expert
    would add is left out. Pairs are sorted held experts first, so the
    grouped products' groups end where the live rows end and an absent
    pair never enters a product; the zero-compute term is a scaled copy
    of x, no product. With every expert held and none zero-compute this
    is the plain layer, operation for operation.

    `shared` (cfg.n_shared_experts): one layer's gate / up / down of the
    shared expert, ONE SwiGLU (`_swiglu`) on every row, unweighted and
    whole on every chip, added to the routed sum in float32: a decode
    step reads its kernels once whatever the lanes.

    `logits` [N, E]: the router's logits where they were taken
    elsewhere (cfg.router_input "layer_input": `_block` reads them off
    the layer's raw input); None: the router reads x. The experts' gate
    activation is cfg.moe_activation; under "relu2" an expert is NOT
    gated, two grouped products (`up`, `down`) with relu(.)^2 between.

    `latent` (cfg.moe_latent_size): one layer's `down` [H, latent] and
    `up` [latent, H]; the experts' kernels are then latent-wide.

    Returns (y [N, H], routing: {"counts": rows of each held expert
    [count] int32, "ids": the chosen experts [N, K] int32})."""
    xl = x
    if latent is not None:
        # Latent experts (cfg.moe_latent_size): the routed experts read
        # x W_dn, and W_up is applied ONCE, to the weighted sum of a
        # token's held experts (what an absent expert would add is left
        # out before it). The router and the shared expert read x.
        with jax.named_scope("moe_latent"):
            xl = x @ latent["down"]["kernel"].astype(x.dtype)
    with jax.named_scope("moe_routed"):
        if latent is not None and logits is None:
            logits = router_logits(x, router_kernel)
        y, idx, counts = _moe_routed(
            cfg, xl, router_kernel, experts, layer, impl, router_bias, logits)
    if latent is not None:
        with jax.named_scope("moe_latent"):
            y = jnp.matmul(
                y.astype(x.dtype), latent["up"]["kernel"].astype(x.dtype),
                preferred_element_type=jnp.float32)
    if shared is not None:
        with jax.named_scope("moe_shared"):
            y = y + _expert_mlp(cfg, x, shared).astype(jnp.float32)
    return y.astype(x.dtype), {"counts": counts, "ids": idx}


def _expert_mlp(cfg: LLMConfig, x, p: Params):
    """The shared expert: `_swiglu`, or under cfg.moe_activation "relu2"
    the two-matrix relu(x V1)^2 V2."""
    if cfg.moe_activation != "relu2":
        return _swiglu(x, p)
    up = x @ p["up_proj"]["kernel"].astype(x.dtype)
    return jnp.square(jax.nn.relu(up)) @ p["down_proj"]["kernel"].astype(
        x.dtype)


def _moe_routed(cfg: LLMConfig, x, router_kernel, experts, layer, impl,
                router_bias, logits=None):
    """The routed (and zero-compute) part of `_moe`, float32:
    (y [N, H], ids [N, K], held experts' row counts [count])."""
    N, K, E = x.shape[0], cfg.num_experts_per_tok, cfg.num_experts
    first, count = cfg.held
    whole = count == E and not cfg.zero_experts
    if logits is None:
        w, idx = moe_route(cfg, x, router_kernel, router_bias)
    else:
        w, idx = moe_select(cfg, logits, router_bias)
    flat = idx.reshape(N * K)
    if not whole:
        # Group id of a pair: its held expert's place, or `count`, a
        # tail group that has no kernel and enters no product.
        live = (idx >= first) & (idx < first + count)
        flat = jnp.where(live.reshape(N * K), flat - first, count)
    order = jnp.argsort(flat)  # stable: pairs of one expert stay in row order
    counts = jnp.bincount(flat, length=count).astype(jnp.int32)
    groups = jax.lax.dynamic_update_slice(
        jnp.zeros((experts["up"].shape[0],), jnp.int32), counts,
        (layer * count,),
    )
    xs = x[order // K]
    if cfg.moe_activation == "relu2":
        mid = jnp.square(jax.nn.relu(_grouped_dot(
            xs, experts["up"].astype(x.dtype), groups, impl)))
    else:
        act = jax.nn.relu if cfg.moe_activation == "relu" else jax.nn.silu
        gate = _grouped_dot(
            xs, experts["gate"].astype(x.dtype), groups, impl)
        up = _grouped_dot(xs, experts["up"].astype(x.dtype), groups, impl)
        mid = act(gate) * up
    ys = _grouped_dot(mid, experts["down"].astype(x.dtype), groups, impl)
    # Unsort: pair p = token * K + slot sits at sorted row inv[p].
    inv = jnp.zeros((N * K,), jnp.int32).at[order].set(
        jnp.arange(N * K, dtype=jnp.int32)
    )
    yk = ys[inv].reshape(N, K, -1).astype(jnp.float32)
    if not whole:
        # A row past the last group is whatever the product left there.
        yk = jnp.where(live[..., None], yk, 0.0)
    y = jnp.einsum("nk,nkh->nh", w, yk)
    if cfg.zero_experts:
        zero_w = jnp.sum(jnp.where(idx >= E, w, 0.0), axis=-1)
        y = y + zero_w[:, None] * x.astype(jnp.float32)
    return y, idx, counts


def _block(
    cfg: LLMConfig,
    h: jnp.ndarray,
    lp: Params,
    cos: jnp.ndarray,
    sin: jnp.ndarray,
    *,
    positions: jnp.ndarray,
    cache_k: jnp.ndarray | None,
    cache_v: jnp.ndarray | None,
    write_slots: jnp.ndarray | None,
    kv_mask: jnp.ndarray | None,
    attn_fn,
    block_tables: jnp.ndarray | None = None,
    write_mask: jnp.ndarray | None = None,
    kv_lengths: jnp.ndarray | None = None,
    q_segments: jnp.ndarray | None = None,
    attn_impl: str = "xla",
    mask_positions: jnp.ndarray | None = None,
    experts: tuple | None = None,
    window: int = 0,
):
    """One decoder block. h: [B, T, H]. Returns (h, new_k, new_v), and
    with an expert layer its routing as a fourth value.
    `experts`: an expert layer's (flat kernels of every layer, this
    layer's index), see `_moe`; None: the layer's FFN is the dense
    SwiGLU in `lp`.

    `positions` place the token: its RoPE angle and its cache slot.
    `mask_positions` (default: the same) are what its query is masked
    at, `kv_pos <= mask_position`: the last position of the token's
    block under the block-diffusion mask (see `forward`).

    `window` > 0: a WINDOW layer (cfg.windowed), whose query at t sees
    the keys at t - window < u <= t; its cache, tables, positions and
    lengths are then the window plane's, relative to the table's base
    (`_window_layers`), and cos / sin are still the absolute
    positions'."""
    B, T, _ = h.shape
    if mask_positions is None:
        mask_positions = positions
    win = {"window": window} if window else {}
    moe_in = {}
    if cfg.router_input == "layer_input":
        # The router reads the residual stream as the layer found it.
        with jax.named_scope("moe"):
            moe_in["logits"] = router_logits(
                h.reshape(B * T, -1), lp["router"]["kernel"])
    with jax.named_scope("attn"):
        x = rms_norm(h, lp["input_norm"]["weight"], cfg.rms_norm_eps)
        q = _linear(x, lp["q_proj"]).reshape(
            B, T, cfg.num_heads, cfg.head_dim)
        k = _linear(x, lp["k_proj"]).reshape(
            B, T, cfg.num_kv_heads, cfg.head_dim)
        v = _linear(x, lp["v_proj"]).reshape(
            B, T, cfg.num_kv_heads, cfg.head_dim)
        if cfg.qk_norm:
            q = rms_norm(q, lp["q_norm"]["weight"], cfg.rms_norm_eps)
            k = rms_norm(k, lp["k_norm"]["weight"], cfg.rms_norm_eps)
        if cos is not None:  # None: attention without a position term
            q, k = apply_rope(q, k, cos, sin)
        # Post-rope tags for the "attn_qkv" remat policy
        # (utils/remat.py): saving here spares the backward both the
        # projections and the rope.
        q = checkpoint_name(q, "attn_q")
        k = checkpoint_name(k, "attn_k")
        v = checkpoint_name(v, "attn_v")

        with jax.named_scope("attn_window" if window else "attn_global"):
            attn_out, cache_k, cache_v = _block_attention(
                q, k, v, positions=positions, cache_k=cache_k,
                cache_v=cache_v, write_slots=write_slots, kv_mask=kv_mask,
                attn_fn=attn_fn, block_tables=block_tables,
                write_mask=write_mask, kv_lengths=kv_lengths,
                q_segments=q_segments, attn_impl=attn_impl,
                mask_positions=mask_positions, win=win, kv_pack=cfg.kv_pack,
            )
        attn_out = attn_out.reshape(B, T, -1)
        # "attn_o" tag: with remat_policy="attn_o" the residual-stream
        # value h_mid = h + o_out is rebuilt from this saved projection,
        # so the backward recomputes neither the attention nor o_proj.
        h = h + checkpoint_name(_linear(attn_out, lp["o_proj"]), "attn_o")

    if "post_attn_norm" not in lp:
        # One sublayer a layer (cfg.hybrid_override_pattern): attention
        # alone, no FFN behind it.
        return h, cache_k, cache_v
    if experts is not None:
        with jax.named_scope("moe"):
            x = rms_norm(h, lp["post_attn_norm"]["weight"], cfg.rms_norm_eps)
            y, routing = _moe(
                cfg, x.reshape(B * T, -1), lp["router"]["kernel"], *experts,
                impl=attn_impl, router_bias=lp["router"].get("bias"),
                **moe_in,
            )
            return h + y.reshape(B, T, -1), cache_k, cache_v, routing
    with jax.named_scope("ffn"):
        x = rms_norm(h, lp["post_attn_norm"]["weight"], cfg.rms_norm_eps)
        return h + _swiglu(x, lp), cache_k, cache_v


def _block_attention(q, k, v, *, positions, cache_k, cache_v, write_slots,
                     kv_mask, attn_fn, block_tables, write_mask, kv_lengths,
                     q_segments, attn_impl, mask_positions, win, kv_pack=1):
    """`_block`'s attention over whichever cache it was given: writes
    this call's K/V, returns (attention output [B, T, Hq, D], the
    cache's two planes). `win`: {"window": W} on a window layer, else
    {}. `kv_pack`: heads a row of the paged pool holds side by side
    (`LLMConfig.kv_pack`)."""
    T = q.shape[1]
    if cache_k is not None and block_tables is not None and (
        q_segments is not None
    ):
        # Packed RAGGED paged mode (one dispatch, mixed query lengths):
        # the T axis is a PACKED buffer of rows from many sequences —
        # block_tables is [num_slots, max_pages] (not per batch row) and
        # each token routes by (q_segments, positions). write_mask here
        # is PER TOKEN [B, T]. See ops/paged_kv.write_pages_packed /
        # ragged_paged_attention and models/generate.paged_ragged_step.
        from oryx_tpu.ops import paged_kv

        seg = q_segments[0]
        pos = positions[0]
        mpos = pos if mask_positions is positions else mask_positions[0]
        wm = None if write_mask is None else write_mask[0]
        cache_k = paged_kv.write_pages_packed(
            cache_k, k[0], block_tables, seg, pos, write_mask=wm
        )
        cache_v = paged_kv.write_pages_packed(
            cache_v, v[0], block_tables, seg, pos, write_mask=wm
        )
        if attn_impl == "pallas":
            from oryx_tpu.ops.pallas import paged_attention as _ppa

            attn_out = _ppa.ragged_paged_attention(
                q[0], cache_k, cache_v, block_tables, seg, mpos
            )[None]
        else:
            attn_out = paged_kv.ragged_paged_attention(
                q[0], cache_k, cache_v, block_tables, seg, mpos
            )[None]
    elif cache_k is not None and block_tables is not None:
        # Paged cache: this layer's K/V pool is [P, page, Hk, D] and the
        # row's logical stream is addressed through its block table.
        from oryx_tpu.ops import paged_kv

        # A pool whose rows hold r heads side by side ([P, page, Hk / r,
        # r * D], `LLMConfig.kv_pack`): the same bytes in the same
        # order, so K and V are written as they lie.
        B, _, Hk, D = k.shape
        r = kv_pack
        if r > 1:
            k, v = (a.reshape(B, T, Hk // r, r * D) for a in (k, v))
        cache_k = paged_kv.write_pages(
            cache_k, k, block_tables, write_slots, write_mask=write_mask
        )
        cache_v = paged_kv.write_pages(
            cache_v, v, block_tables, write_slots, write_mask=write_mask
        )
        if attn_impl == "pallas" and T == 1 and kv_lengths is not None:
            # In-place ragged decode: pages are read through the block
            # table, no contiguous gather.
            from oryx_tpu.ops.pallas import paged_attention as _ppa

            if r > 1:
                # The walk sees Hk / r heads of r * D lanes. A query of
                # head h lies in ITS key head's lanes of the row and is
                # zero in the others', so its scores are its own head's;
                # of the output row it keeps its value head's lanes.
                Hq = q.shape[2]
                lane = (jnp.arange(Hq) // (Hq // Hk)) % r  # [Hq]
                mine = lane[:, None] == jnp.arange(r)[None, :]  # [Hq, r]
                q = jnp.where(mine[..., None], q[..., None, :], 0).reshape(
                    B, T, Hq, r * D)
                win = dict(win, scale=D ** -0.5)
            attn_out = _ppa.ragged_decode_attention(
                q, cache_k, cache_v, block_tables, kv_lengths, **win
            )
            if r > 1:
                attn_out = jnp.sum(jnp.where(
                    mine[..., None], attn_out.reshape(B, T, Hq, r, D), 0
                ), axis=-2)
        else:
            # Reference path (and any T > 1 paged prefill): materialize
            # the logical stream, then the stock cached-attention call —
            # bit-identical math to the dense cache at equal KV width.
            kc = paged_kv.gather_pages(cache_k, block_tables)
            vc = paged_kv.gather_pages(cache_v, block_tables)
            if r > 1:  # back to a head a row, [B, K, Hk, D]
                kc, vc = (a.reshape(a.shape[:2] + (Hk, D)) for a in (kc, vc))
            attn_out = attn_fn(
                q, kc, vc,
                q_positions=mask_positions,
                kv_positions=None,
                kv_mask=kv_mask, **win,
            )
    elif cache_k is not None:
        cache_k = _cache_write(cache_k, k, write_slots)
        cache_v = _cache_write(cache_v, v, write_slots)
        attn_out = attn_fn(
            q, cache_k, cache_v,
            q_positions=mask_positions,
            kv_positions=None,  # arange over cache slots == absolute positions
            kv_mask=kv_mask, **win,
        )
    else:
        # Right-padded prefill: every valid token's position equals its
        # slot index, which lets the Pallas kernel skip causally-dead kv
        # tiles (DMA + compute) despite the explicit position arrays.
        # (Under a block mask a query sees past its own slot, so the
        # promise is not made; no serving path runs that case.)
        attn_out = attn_fn(
            q, k, v,
            q_positions=mask_positions,
            kv_positions=positions,
            kv_mask=kv_mask,
            slot_positions=mask_positions is positions, **win,
        )
    return attn_out, cache_k, cache_v


def _swiglu(x: jnp.ndarray, lp: Params) -> jnp.ndarray:
    """The dense FFN: down(silu(gate(x)) * up(x))."""
    gate = jax.nn.silu(_linear(x, lp["gate_proj"]))
    return _linear(gate * _linear(x, lp["up_proj"]), lp["down_proj"])


def _mla_expanded(cfg: LLMConfig, q_nope, q_rope, c, kr, w_uk, w_uv, *,
                  q_positions, kv_positions, kv_mask, impl: str):
    """Latent attention in its expanded form: the latents c [B, K, R]
    are up-projected to per-head keys and values through `w_uk`
    [Hq, dn, R] and `w_uv` [Hq, R, dv], the one roped key kr [B, K, dr]
    is shared by every head, and ordinary causal attention runs with
    (dn + dr)-wide keys and dv-wide values. 2 (dn + dr + dv) FLOP a query-key pair a
    head where the absorbed form costs 2 (2 R + dr): the cheaper one
    whenever many queries meet the same keys (prefill). Returns
    [B, T, Hq, dv]."""
    dn, dr = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    k, v = _expand_keys(c, kr, w_uk, w_uv)
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    scale = cfg.softmax_scale
    if impl == "pallas":
        from oryx_tpu.ops.pallas import flash_attention as _fa

        # Whole lane tiles for the kernel's q/k blocks (192 -> 256): the
        # zero columns add nothing to a score.
        pad = -(dn + dr) % 128
        if pad:
            widen = ((0, 0), (0, 0), (0, 0), (0, pad))
            q, k = jnp.pad(q, widen), jnp.pad(k, widen)
        return _fa.flash_attention(
            q, k, v, causal=True, q_positions=q_positions,
            kv_positions=kv_positions, kv_mask=kv_mask, scale=scale,
        )
    s = jnp.einsum(
        "bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32
    ) * scale
    if kv_positions is None:
        kv_positions = jnp.arange(k.shape[1], dtype=jnp.int32)[None, :]
    seen = q_positions[:, :, None] >= kv_positions[:, None, :]
    if kv_mask is not None:
        seen = seen & kv_mask[:, None, :].astype(bool)
    s = jnp.where(seen[:, None], s, jnp.finfo(jnp.float32).min)
    p = jax.nn.softmax(s, axis=-1).astype(v.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v).astype(q.dtype)


def _mla(cfg: LLMConfig, a: jnp.ndarray, p: Params, cos, sin, *,
         positions, pool, tables, write_slots, kv_mask, write_mask,
         kv_lengths, attn_impl: str, selected: dict | None = None):
    """One latent-attention sublayer on the normed input a [B, T, H].
    Returns (its output [B, T, H], the pool). What a token leaves in
    the cache is (its kv latent after norm and scale, its ONE roped
    key): cfg.latent_dim values, written to `pool` [P, page, Dp] through
    `tables` before anything reads it.

    Two paths, chosen from shapes. A decode step (T == 1 with
    kv_lengths) runs the ABSORBED form over the paged latents in place:
    q_lat = q_nope W_uk^T, scores q_lat . c + q_rope . kr against the
    page rows as they lie, o_lat = sum p c, o = o_lat W_uv; a page is
    read once for both products and no per-head key or value ever
    exists (ops/pallas/paged_attention._latent_paged, or its XLA twin).
    Anything longer (a prefill chunk over its cached prefix, a forward
    with no cache) runs the EXPANDED form, `_mla_expanded`.

    With an indexer (cfg.indexed) `pool` is the pair (latents, index
    keys), a token's index key is written where its latent is, and both
    paths read the keys the indexer selected a query
    (`_sparse_decode`, `_sparse_prefill`). `selected` (the comparison's
    twin): a dict that takes what was selected under "selected", a
    decode step's indices [B, k] (ascending, the first min(length, k)
    real) or a chunk's mask packed along the keys [B, T, K / 8]."""
    from oryx_tpu.ops.rope import apply_rope_interleaved

    B, T, H = a.shape
    Hq, R = cfg.num_heads, cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    eps = cfg.rms_norm_eps
    cq = rms_norm(_linear(a, p["q_a_proj"]), p["q_a_norm"]["weight"], eps)
    if cfg.mla_scale_q_lora:
        # (cq Wq_b) * sqrt(H / Rq), the factor taken on the narrow side.
        cq = (cq * (H / cfg.q_lora_rank) ** 0.5).astype(a.dtype)
    q_nope = _linear(cq, p["q_b_nope"]).reshape(B, T, Hq, dn)
    q_rope = _linear(cq, p["q_b_rope"]).reshape(B, T, Hq, dr)
    c = rms_norm(_linear(a, p["kv_a_proj"]), p["kv_a_norm"]["weight"], eps)
    if cfg.mla_scale_kv_lora:
        c = (c * (H / R) ** 0.5).astype(a.dtype)
    rope = apply_rope_interleaved if cfg.rope_interleaved else (
        lambda x, cos, sin: apply_rope(x, x, cos, sin)[0])
    q_rope = rope(q_rope, cos, sin)
    q_scale = cfg.query_position_scale(positions)
    if q_scale is not None:
        # The query's scale by position, on both parts of every head.
        q_scale = q_scale[:, :, None, None]
        q_nope = (q_nope * q_scale).astype(a.dtype)
        q_rope = (q_rope * q_scale).astype(a.dtype)
    kr = rope(_linear(a, p["k_rope_proj"])[:, :, None], cos, sin)[:, :, 0]
    w_uk, w_uv = p["w_uk"].astype(a.dtype), p["w_uv"].astype(a.dtype)
    ipool = None
    if cfg.indexed:
        with jax.named_scope("dsa_index"):
            qi, ki, wi = _index_inputs(cfg, a, cq, p["indexer"], cos, sin)
        if pool is not None:
            pool, ipool = pool
    if pool is None and cfg.indexed:
        o = _sparse_dense(
            cfg, q_nope, q_rope, c, kr, w_uk, w_uv, qi, ki, wi,
            positions=positions, kv_mask=kv_mask)
    elif pool is None:
        o = _mla_expanded(
            cfg, q_nope, q_rope, c, kr, w_uk, w_uv, q_positions=positions,
            kv_positions=positions, kv_mask=kv_mask, impl=attn_impl,
        )
    else:
        from oryx_tpu.ops import paged_kv

        Dp = pool.shape[-1]
        row = jnp.concatenate(
            [c, kr, jnp.zeros((B, T, Dp - R - dr), c.dtype)], axis=-1
        )
        pool = paged_kv.write_pages(
            pool[:, :, None, :], row[:, :, None, :], tables, write_slots,
            write_mask=write_mask,
        )[:, :, 0, :]
        if ipool is not None:
            ipool = paged_kv.write_pages(
                ipool[:, :, None, :], ki[:, :, None, :], tables, write_slots,
                write_mask=write_mask,
            )[:, :, 0, :]
        if T == 1 and kv_lengths is not None:
            q_lat = jnp.einsum("bhd,hdc->bhc", q_nope[:, 0], w_uk)
            qf = jnp.concatenate([
                q_lat, q_rope[:, 0],
                jnp.zeros((B, Hq, Dp - R - dr), q_lat.dtype),
            ], axis=-1)
            if attn_impl == "pallas":
                from oryx_tpu.ops.pallas import paged_attention as _ppa

                decode = _ppa.latent_decode_attention
            else:
                decode = paged_kv.latent_decode_attention
            if ipool is not None:
                o_lat, idx = _sparse_decode(
                    cfg, qf, qi[:, 0], wi[:, 0], pool, ipool, tables,
                    kv_lengths, decode, attn_impl)
                if selected is not None:
                    selected["selected"] = idx
            else:
                o_lat = decode(
                    qf, pool, tables, kv_lengths, scale=cfg.softmax_scale,
                    value_dim=R,
                )
            o = jnp.einsum("bhc,hcd->bhd", o_lat, w_uv)[:, None]
        elif ipool is not None:
            o, seen = _sparse_prefill(
                cfg, q_nope, q_rope, w_uk, w_uv, qi, wi, pool, ipool, tables,
                positions=positions, kv_mask=kv_mask, attn_impl=attn_impl)
            if selected is not None:
                selected["selected"] = jnp.packbits(seen, axis=-1)
        else:
            lat = paged_kv.gather_pages(pool[:, :, None, :], tables)[:, :, 0]
            o = _mla_expanded(
                cfg, q_nope, q_rope, lat[..., :R], lat[..., R:R + dr],
                w_uk, w_uv, q_positions=positions, kv_positions=None,
                kv_mask=kv_mask, impl=attn_impl,
            )
        if ipool is not None:
            pool = (pool, ipool)
    return _linear(o.reshape(B, T, Hq * dv), p["o_proj"]), pool


def _index_inputs(cfg: LLMConfig, a, cq, p: Params, cos, sin):
    """The indexer's view of a layer's tokens, from what `_mla` already
    has (the normed input a [B, T, H], the normed query latent cq):
    (index queries [B, T, Hi, Di], ONE index key a token [B, T, Di],
    the heads' weights [B, T, Hi] float32, scaled by Hi^-1/2 Di^-1/2).
    The first qk_rope_head_dim columns of a query head and of the key
    are roped at the token's position (interleaved pairs)."""
    from oryx_tpu.ops.norms import layer_norm
    from oryx_tpu.ops.rope import apply_rope_interleaved

    B, T, _ = a.shape
    Hi, Di, dr = cfg.index_heads, cfg.index_head_dim, cfg.qk_rope_head_dim

    def rope_head(x):  # [B, T, n, Di]
        return jnp.concatenate(
            [apply_rope_interleaved(x[..., :dr], cos, sin), x[..., dr:]],
            axis=-1)

    qi = rope_head(_linear(cq, p["q_b"]).reshape(B, T, Hi, Di))
    ki = layer_norm(
        _linear(a, p["k_proj"]), p["k_norm"]["weight"], p["k_norm"]["bias"],
        eps=1e-6)
    ki = rope_head(ki[:, :, None])[:, :, 0]
    wi = jnp.matmul(
        a.astype(jnp.float32), p["head_weights"]["kernel"].astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST,
    ) * (Hi ** -0.5 * Di ** -0.5)
    return qi, ki, wi


# Keys one step of the sparse prefill's two loops handles (the engine
# counts the tiles a chunk visits: prefill_masked_tiles_total).
SPARSE_TILE_TOKENS = 1024


def _masked_attend(q, k, v, seen, scale):
    """softmax(q k^T * scale over `seen`) v with NO query-key pair
    outside `seen` [B, T, K]: q [B, T, Hq, d], k [B, K, Hq, d],
    v [B, K, Hq, dv] -> (o [B, T, Hq, dv] float32 unnormalised, the
    row maxima and sums [B, Hq, T]): one tile of an online softmax."""
    s = jnp.einsum(
        "bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32) * scale
    s = jnp.where(seen[:, None], s, jnp.finfo(jnp.float32).min)
    m = jnp.max(s, axis=-1)
    p = jnp.where(seen[:, None], jnp.exp(s - m[..., None]), 0.0)
    o = jnp.einsum(
        "bhqk,bkhd->bqhd", p.astype(v.dtype), v,
        preferred_element_type=jnp.float32)
    return o, m, jnp.sum(p, axis=-1)


def _attend_tile(carry, q, k, v, seen, scale):
    """One tile of keys merged into an online softmax: `carry` is the
    running (row maxima and sums [B, Hq, T], unnormalised output
    [B, T, Hq, dv]) of the tiles before; `_masked_attend` over this
    tile, then the merge. The XLA twin of
    ops/pallas/masked_attention.masked_attend."""
    m, l, acc = carry
    o, m_t, l_t = _masked_attend(q, k, v, seen, scale)
    m_new = jnp.maximum(m, m_t)
    a_old, a_new = jnp.exp(m - m_new), jnp.exp(m_t - m_new)
    scale_o = lambda x: jnp.moveaxis(x, 1, 2)[..., None]  # noqa: E731
    return (m_new, l * a_old + l_t * a_new,
            acc * scale_o(a_old) + o * scale_o(a_new))


def _expand_keys(c, kr, w_uk, w_uv):
    """Latents c [B, K, R] and shared roped keys kr [B, K, dr] -> per-head
    keys [B, K, Hq, dn + dr] and values [B, K, Hq, dv]."""
    k_nope = jnp.einsum("bkc,hdc->bkhd", c, w_uk)
    v = jnp.einsum("bkc,hcd->bkhd", c, w_uv)
    k = jnp.concatenate([
        k_nope,
        jnp.broadcast_to(kr[:, :, None, :], (*k_nope.shape[:3], kr.shape[-1])),
    ], axis=-1)
    return k, v


def _sparse_dense(cfg: LLMConfig, q_nope, q_rope, c, kr, w_uk, w_uv, qi, ki,
                  wi, *, positions, kv_mask):
    """Learned sparse attention with no cache (a whole sequence at
    once): index scores of every causal pair, the top k a query, the
    expanded form over what was selected."""
    from oryx_tpu.ops import paged_kv

    seen = positions[:, :, None] >= positions[:, None, :]
    if kv_mask is not None:
        seen = seen & kv_mask[:, None, :].astype(bool)
    with jax.named_scope("dsa_index"):
        scores = jnp.where(
            seen, paged_kv.index_tile(qi, wi, ki), -jnp.inf)
    with jax.named_scope("dsa_select"):
        seen = seen & paged_kv.topk_mask(scores, cfg.index_topk)
    with jax.named_scope("dsa_attend"):
        k, v = _expand_keys(c, kr, w_uk, w_uv)
        o, _, l = _masked_attend(
            jnp.concatenate([q_nope, q_rope], axis=-1), k, v, seen,
            cfg.softmax_scale)
    l = jnp.moveaxis(l, 1, 2)[..., None]
    return (o / jnp.where(l == 0.0, 1.0, l)).astype(q_nope.dtype)


def _sparse_decode(cfg: LLMConfig, qf, qi, wi, pool, ipool, tables,
                   kv_lengths, decode, attn_impl: str):
    """One decode row a lane: index scores over the lane's paged index
    keys, the exact top k (ascending), and the absorbed latent attention
    (`decode`, the dense path's own) over the SELECTED rows alone, which
    are gathered out of the pool. A lane at length n reads n index keys
    and min(n, k) latent rows; under k it reads rows 0..n-1 in their
    order, what the dense walk reads. Returns (o_lat [B, Hq, R], the
    selected indices [B, k])."""
    from oryx_tpu.ops import paged_kv

    B, ps = qf.shape[0], pool.shape[1]
    with jax.named_scope("dsa_index"):
        if attn_impl == "pallas":
            from oryx_tpu.ops.pallas import paged_attention as _ppa

            scores = _ppa.index_scores(qi, wi, ipool, tables, kv_lengths)
        else:
            scores = paged_kv.index_scores(qi, wi, ipool, tables, kv_lengths)
    with jax.named_scope("dsa_select"):
        idx = paged_kv.topk_indices(scores, cfg.index_topk)
    k = idx.shape[1]
    if k % ps:
        raise ValueError(
            f"learned sparse attention: a page of {ps} positions does not "
            f"divide the {k} selected rows of a decode step")
    with jax.named_scope("dsa_attend"):
        rows = paged_kv.gather_rows(pool, tables, idx)
        return decode(
            qf, rows.reshape(B * k // ps, ps, -1),
            jnp.arange(B * k // ps, dtype=jnp.int32).reshape(B, k // ps),
            jnp.minimum(kv_lengths, k), scale=cfg.softmax_scale,
            value_dim=cfg.kv_lora_rank,
        ), idx


def _sparse_prefill(cfg: LLMConfig, q_nope, q_rope, w_uk, w_uv, qi, wi, pool,
                    ipool, tables, *, positions, kv_mask, attn_impl: str):
    """A chunk of queries [B, T] over its rows' paged prefix and itself
    (both already in the pool): index scores against the index keys a
    tile of `SPARSE_TILE_TOKENS` at a time, the top k a query as a
    MASK (`paged_kv.topk_mask`), then the expanded form a tile of keys
    at a time under an online softmax, so that per-head keys and values
    exist for ONE tile and the temporaries that grow with the table are
    the scores and the mask alone; a tile's step is `_attend_tile`, or
    under "pallas" its kernel (ops/pallas/masked_attention._dsa_attend),
    which keeps the tile's attention scores in VMEM and merges in
    place. Tiles past the chunk's last position
    are not visited; a chunk that ends inside the first k positions
    selects everything and scores nothing. Returns ([B, T, Hq, dv],
    the pairs attended [B, T, K] bool)."""
    from oryx_tpu.ops import paged_kv

    B, T, Hq, _ = q_nope.shape
    R, dr, dv = cfg.kv_lora_rank, cfg.qk_rope_head_dim, cfg.v_head_dim
    ps = pool.shape[1]
    tp = max(1, SPARSE_TILE_TOKENS // ps)  # pages a tile
    sentinel = pool.shape[0]
    tables = jnp.pad(
        tables, ((0, 0), (0, -tables.shape[1] % tp)),
        constant_values=sentinel)
    Kt, K = tp * ps, tables.shape[1] * ps
    u = jnp.arange(K, dtype=jnp.int32)
    seen = positions[:, :, None] >= u[None, None, :]
    if kv_mask is not None:
        seen = seen & jnp.pad(
            kv_mask.astype(bool), ((0, 0), (0, K - kv_mask.shape[1]))
        )[:, None, :]
    end = jnp.max(positions) + 1
    tiles = jnp.minimum(-(-end // Kt), K // Kt)

    def tile_rows(plane, i):
        pages = jax.lax.dynamic_slice_in_dim(tables, i * tp, tp, axis=1)
        return plane[jnp.clip(pages, 0, sentinel - 1)].reshape(B, Kt, -1)

    def selected():
        def score(i, scores):
            tile = paged_kv.index_tile(qi, wi, tile_rows(ipool, i))
            return jax.lax.dynamic_update_slice_in_dim(
                scores, tile, i * Kt, axis=2)

        with jax.named_scope("dsa_index"):
            scores = jax.lax.fori_loop(
                0, tiles, score, jnp.full((B, T, K), -jnp.inf, jnp.float32))
            scores = jnp.where(seen, scores, -jnp.inf)
        with jax.named_scope("dsa_select"):
            return seen & paged_kv.topk_mask(scores, cfg.index_topk)

    # Under k visible keys the selection is everything a query sees.
    seen = jax.lax.cond(end <= cfg.index_topk, lambda: seen, selected)
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    if attn_impl == "pallas":
        from oryx_tpu.ops.pallas.masked_attention import (
            masked_attend as attend_tile,
        )
    else:
        attend_tile = _attend_tile

    def attend(i, carry):
        lat = tile_rows(pool, i)
        k, v = _expand_keys(lat[..., :R], lat[..., R:R + dr], w_uk, w_uv)
        return attend_tile(
            carry, q, k, v,
            jax.lax.dynamic_slice_in_dim(seen, i * Kt, Kt, axis=2),
            cfg.softmax_scale)

    with jax.named_scope("dsa_attend"):
        m, l, acc = jax.lax.fori_loop(0, tiles, attend, (
            jnp.full((B, Hq, T), jnp.finfo(jnp.float32).min, jnp.float32),
            jnp.zeros((B, Hq, T), jnp.float32),
            jnp.zeros((B, T, Hq, dv), jnp.float32),
        ))
    l = jnp.moveaxis(l, 1, 2)[..., None]
    return (acc / jnp.where(l == 0.0, 1.0, l)).astype(q_nope.dtype), seen


def _double_block(cfg: LLMConfig, h, lp: Params, cos, sin, *, pool, tables,
                  experts: tuple, attn_impl: str, **attn):
    """One shortcut-connected double layer on h [B, T, H]: two
    (latent attention, dense FFN) sublayers in series and ONE expert
    layer whose input is the normed state after the first attention and
    whose output is added after the second FFN, so the expert layer
    runs beside a whole attention and two dense FFNs. `tables`: the two
    sublayers' block tables (cache layers 2l and 2l + 1), or None with
    no pool. Returns (h, pool, the expert layer's routing)."""
    B, T, _ = h.shape
    eps = cfg.rms_norm_eps
    for i in (0, 1):
        sub = lp[f"sub{i}"]
        with jax.named_scope("attn"):
            a = rms_norm(h, sub["input_norm"]["weight"], eps)
            with jax.named_scope("mla"):
                att, pool = _mla(
                    cfg, a, sub, cos, sin, pool=pool,
                    tables=None if tables is None else tables[i],
                    attn_impl=attn_impl, **attn,
                )
            h = h + att
        # (one normed state feeds the expert layer and the dense FFN.)
        with jax.named_scope("ffn"):
            x = rms_norm(h, sub["post_attn_norm"]["weight"], eps)
        if i == 0:
            with jax.named_scope("moe"):
                s, routing = _moe(
                    cfg, x.reshape(B * T, -1), lp["router"]["kernel"],
                    *experts, impl=attn_impl,
                    router_bias=lp["router"].get("bias"),
                )
        with jax.named_scope("ffn"):
            h = h + _swiglu(x, sub)
    with jax.named_scope("moe"):
        return h + s.reshape(B, T, -1), pool, routing


def _latent_block(cfg: LLMConfig, h, lp: Params, cos, sin, *, pool, tables,
                  experts: tuple, attn_impl: str, **attn):
    """One single latent block on h [B, T, H]: latent attention, then
    the expert layer (the shared expert on every token beside the
    routed experts) on ONE normed input. `tables`: a one-element list,
    the layer's block table (cache layer l), or None with no pool.
    `experts` None: a leading dense layer, whose FFN is `lp`'s own.
    Returns (h, pool, the expert layer's routing or None)."""
    B, T, _ = h.shape
    eps = cfg.rms_norm_eps
    # What the twin asked to see of the selection (`_mla`), if anything.
    seen = {} if attn.pop("return_selected", False) else None
    with jax.named_scope("attn"):
        a = rms_norm(h, lp["input_norm"]["weight"], eps)
        with jax.named_scope("mla"):
            att, pool = _mla(
                cfg, a, lp, cos, sin, pool=pool,
                tables=None if tables is None else tables[0],
                attn_impl=attn_impl, **attn,
                **({} if seen is None else {"selected": seen}),
            )
        h = h + att
    if experts is None:
        # A leading dense layer (cfg.dense_layers): one SwiGLU of
        # intermediate_size where the expert layer would be.
        with jax.named_scope("ffn"), jax.named_scope("dense_ffn"):
            x = rms_norm(h, lp["post_attn_norm"]["weight"], eps)
            return h + _swiglu(x, lp), pool, seen
    with jax.named_scope("moe"):
        x = rms_norm(h, lp["post_attn_norm"]["weight"], eps)
        y, routing = _moe(
            cfg, x.reshape(B * T, -1), lp["router"]["kernel"], *experts,
            impl=attn_impl, router_bias=lp["router"].get("bias"),
            shared=lp.get("shared"),
        )
        return (h + y.reshape(B, T, -1), pool,
                dict(routing, **(seen or {})))


def _hybrid_layers(cfg: LLMConfig, layers: Params, h, *, block, kv_cache,
                   block_tables, positions, kv_lengths, kv_mask,
                   state_slots, attn_impl: str, remat, experts_flat=None):
    """The layer stack of a config with state layers, run off ONE table
    of layer kinds (`cfg.layer_plan`: what `cfg.layer_kinds` and
    `cfg.ffn_kinds` say of each layer, as lead, period, repeats, tail).
    The repeated stretch is a scan over PERIODS whose body runs the
    period's layers in order; the ragged ends ahead of and behind it are
    the same body unrolled. Within a stretch a run of state layers of
    one FFN kind is an inner scan, an attention layer is `block`
    (forward's own closure over `_block`). A state layer is a Mamba
    mixer (`models/mamba.py`) or a gated short convolution
    (`models/short_conv.py`), by `cfg.state_kind`; its FFN, by
    position, the dense SwiGLU in its own stack, a leading dense one
    (`layers["dense"]`) or the expert layer (`_moe`).

    Every pool plane is CARRIED through both levels, never scanned
    (forward's "THE POOL IS CARRIED" rule): `k` / `v` flat [La*P, ...]
    behind layer-offset tables, `conv` / `ssm` whole, a layer reading
    and writing its [S, ...] row in place, and `conv_edge` whole: a
    layer of a prefill chunk writes the window after every page's last
    token the chunk holds into that page's row, a layer of a decode
    step the lane's window where its token was the last of its page.
    Layer weights are indexed by
    the layer's number within its kind, which is what a scan does with
    its xs. See forward's `state_slots` for whose state a row starts
    from and leaves. Returns (h, the pool or None, the expert layers'
    routing [moe layers, ...] in layer order or None)."""
    from oryx_tpu.models import mamba, mamba2, short_conv
    from oryx_tpu.ops import paged_kv
    from oryx_tpu.ops.pallas import ssd_step

    kind = cfg.state_kind
    is_ssd = kind == "mamba2"
    is_mamba = kind in ("mamba", "mamba2")  # an `ssm` plane beside `conv`
    lead, period_kinds, reps, tail = cfg.layer_plan()
    B, T, _ = h.shape
    K1 = (cfg.mamba_d_conv if is_mamba else cfg.conv_L_cache) - 1
    d = cfg.conv_state_width // K1
    N = cfg.mamba_d_state
    CONV, SSM = paged_kv.SLOT_PLANES
    EDGE = paged_kv.CONV_EDGE
    paged = kv_cache is not None
    pl = {}
    if paged:
        valid = positions < kv_lengths[:, None]
        La, P = kv_cache["k"].shape[:2]
        pl = {n: kv_cache[n].reshape((La * P,) + kv_cache[n].shape[2:])
              for n in ("k", "v")}
        pl.update({n: kv_cache[n] for n in (CONV, SSM, EDGE)
                   if n in kv_cache})
        fresh = (positions[:, 0] == 0)[:, None, None]
    elif kv_mask is not None:
        valid = kv_mask.astype(bool)
    else:
        valid = jnp.ones((B, T), bool)
    # One token a lane, lane b slot b. Under "pallas" a Mamba mixer's
    # step is two kernels that take the planes whole and update layer
    # li's rows in place (`mamba.mixer_step_inplace`); every other path,
    # and a shape their tiles do not fit, slices the layer's rows out
    # and writes them back around the mixer.
    decode = paged and state_slots is None and T == 1
    inplace = (is_mamba and decode and attn_impl == "pallas"
               and (mamba2 if is_ssd else mamba).step_fits(cfg, B))
    # A Mamba-2 prefill row's state passes through copies of its own
    # (`ssd_step.read_rows`): as a gather and a scatter XLA re-laid the
    # whole plane out around every chunk.
    rows_io = (is_ssd and paged and state_slots is not None
               and attn_impl == "pallas"
               and mamba2.step_fits(cfg, kv_cache[SSM].shape[1]))
    with jax.named_scope("mixer"):
        if inplace and not is_ssd:
            step_inv = mamba.step_invariants(
                layers["mamba"]["mixer"], valid[:, 0], h.dtype)
        edges = None
        if EDGE in pl and decode:
            # A lane whose token is the last of its page leaves its
            # window in that page's row; any other writes out of bounds
            # (dropped).
            ps = kv_cache["k"].shape[2]
            pos = positions[:, 0]
            page = jnp.take_along_axis(
                block_tables, jnp.minimum(
                    pos // ps, block_tables.shape[1] - 1)[:, None],
                axis=1)[:, 0]
            edges = jnp.where(
                valid[:, 0] & (pos % ps == ps - 1) & (page < P), page, P)
        elif EDGE in pl:
            # The page edges this chunk's rows cross, once for every
            # layer.
            edges = short_conv.page_edges(
                positions, kv_lengths, block_tables, P,
                kv_cache["k"].shape[2])

    def at(tree, i):
        with jax.named_scope("stack"):
            return jax.tree_util.tree_map(
                lambda a: jax.lax.dynamic_index_in_dim(
                    a, i, keepdims=False),
                tree)

    def ffn(which: str, h, lp, fi):
        """h + the layer's FFN on its normed state -> (h, routing); h as
        it is where the layer has none ("none": one sublayer a layer)."""
        if which == "none":
            return h, None
        with jax.named_scope("moe" if which == "moe" else "ffn"):
            x = rms_norm(
                h, lp["post_attn_norm"]["weight"], cfg.rms_norm_eps)
            if which == "own":
                return h + _swiglu(x, lp), None
            if which == "dense":
                return h + _swiglu(x, at(layers["dense"], fi)), None
            router = at(layers["router"], fi)
            more = {n: at(layers[n], fi) for n in ("shared", "latent")
                    if n in layers}
            y, routing = _moe(
                cfg, x.reshape(B * T, -1), router["kernel"], experts_flat,
                fi, impl=attn_impl, router_bias=router.get("bias"), **more)
            return h + y.reshape(B, T, -1), routing

    def ffn_layer(carry, fi):
        """A layer that is its expert layer alone: no stack of its own,
        its norm lies with the expert layers'."""
        h, pl = carry
        h, routing = ffn(
            "moe", h, {"post_attn_norm": at(layers["ffn_norm"], fi)}, fi)
        return (h, pl), routing

    def state_layer(which: str, carry, idx):
        h, pl = carry
        li, fi = idx
        lp = at(layers[kind], li)
        conv_pl, ssm_pl = pl.get(CONV), pl.get(SSM)
        with jax.named_scope("mixer"):
            if not paged:
                conv0 = jnp.zeros((B, K1, d), h.dtype)
                h0 = jnp.zeros((B, N, cfg.mamba_d_inner), jnp.float32)
            elif not inplace:  # (the kernels index the planes by li)
                conv0 = at(conv_pl, li)  # [S, ...]
                h0 = at(ssm_pl, li) if is_mamba and not rows_io else None
                if state_slots is None:
                    conv0 = conv0.reshape(B, K1, d)
                elif is_mamba and not rows_io:
                    conv0, h0 = mamba.rows_state(
                        conv0, h0, state_slots, fresh, (B, K1, d))
                else:
                    conv0 = jnp.where(
                        fresh, 0, conv0[state_slots].reshape(B, K1, d))
                    if rows_io:
                        h0 = jnp.where(fresh, 0, ssd_step.read_rows(
                            ssm_pl, li, state_slots))
            u = rms_norm(h, lp["input_norm"]["weight"], cfg.rms_norm_eps)
            h1 = win = None
            with jax.named_scope(kind if is_mamba else "short_conv"):
                if is_ssd and inplace:
                    out, (conv_pl, ssm_pl) = mamba2.mixer_step_inplace(
                        cfg, lp["mixer"], valid[:, 0], li, u,
                        (conv_pl, ssm_pl))
                elif is_ssd and decode:
                    out, (conv1, h1) = mamba2.mixer_step(
                        cfg, lp["mixer"], u, (conv0, h0), valid[:, 0])
                elif is_ssd:
                    out, (conv1, h1) = mamba2.mixer_prefill(
                        cfg, lp["mixer"], u, (conv0, h0), valid)
                elif inplace:
                    out, (conv_pl, ssm_pl) = mamba.mixer_step_inplace(
                        cfg, lp["mixer"], step_inv, li, u,
                        (conv_pl, ssm_pl))
                elif is_mamba and decode:
                    out, (conv1, h1) = mamba.mixer_step(
                        cfg, lp["mixer"], u, (conv0, h0), valid[:, 0])
                elif is_mamba:
                    out, (conv1, h1) = mamba.mixer_prefill(
                        cfg, lp["mixer"], u, (conv0, h0), valid,
                        impl=attn_impl)
                elif decode:
                    out, conv1 = short_conv.mixer_step(
                        cfg, lp["mixer"], u, conv0, valid[:, 0])
                else:
                    out, conv1, win = short_conv.mixer_prefill(
                        cfg, lp["mixer"], u, conv0, valid)
            h = h + out
        h, routing = ffn(which, h, lp, fi)
        with jax.named_scope("mixer"):  # the state's write-back
            if paged and not inplace:
                conv1 = conv1.reshape(B, K1 * d)
                if state_slots is None:
                    conv_pl = jax.lax.dynamic_update_index_in_dim(
                        conv_pl, conv1, li, 0)
                    if is_mamba:
                        ssm_pl = jax.lax.dynamic_update_index_in_dim(
                            ssm_pl, h1, li, 0)
                else:
                    conv_pl = conv_pl.at[li, state_slots].set(conv1)
                    if rows_io:
                        ssm_pl = ssd_step.write_rows(
                            ssm_pl, li, state_slots, h1)
                    elif is_mamba:
                        ssm_pl = ssm_pl.at[li, state_slots].set(h1)
            if paged:
                pl = dict(pl, **{CONV: conv_pl},
                          **({SSM: ssm_pl} if is_mamba else {}))
                if edges is not None and decode:
                    pl[EDGE] = pl[EDGE].at[li, edges].set(
                        conv1, mode="drop")
                elif edges is not None:
                    pages, n = edges
                    pl[EDGE] = pl[EDGE].at[li, pages.reshape(-1)].set(
                        short_conv.edge_rows(win, n, K1 + 1).astype(
                            pl[EDGE].dtype), mode="drop")
        return (h, pl), routing

    def attn_layer(which: str, carry, ai, fi):
        h, pl = carry
        lp = at(layers["attn"], ai)
        if which == "dense":
            lp = dict(lp, **at(layers["dense"], fi))
        elif which == "moe":
            lp = dict(lp, router=at(layers["router"], fi))
        tables = None
        if paged:
            tables = jnp.where(
                block_tables >= P, La * P,
                block_tables + jnp.asarray(ai, block_tables.dtype) * P)
        h, ck, cv, routing = block(
            h, lp, pl.get("k"), pl.get("v"), tables,
            fi if which == "moe" else None)
        if paged:
            pl = dict(pl, k=ck, v=cv)
        return (h, pl), routing

    def stretch(carry, kinds, first, p=None, per=None):
        """The layers `kinds` in order. `first`: how many layers of each
        kind ("attn", "state") and FFN kind lie ahead of the stretch's
        first pass; pass p (traced) of a period with `per` of each lies
        p * per further. Returns (carry, [routing a run, in order])."""
        seen = dict(first)

        def number(c):
            n = jnp.asarray(seen[c], jnp.int32)
            return n if p is None else n + p * per[c]

        routes, j = [], 0
        while j < len(kinds):
            k, which = kinds[j]
            if k == "none":
                carry, r = ffn_layer(carry, number(which))
                r = jax.tree_util.tree_map(lambda a: a[None], r)
                run = 1
            elif k == "attn":
                carry, r = attn_layer(
                    which, carry, number("attn"), number(which))
                r = jax.tree_util.tree_map(lambda a: a[None], r)
                run = 1
            else:
                run = 1
                while j + run < len(kinds) and kinds[j + run] == kinds[j]:
                    run += 1
                ar = jnp.arange(run, dtype=jnp.int32)
                carry, r = jax.lax.scan(
                    partial(state_layer, which), carry,
                    (number("state") + ar, number(which) + ar))
            if r is not None:
                routes.append(r)
            seen[k if k in ("attn", "none") else "state"] += run
            seen[which] += run
            j += run
        return carry, routes

    def count(kinds):
        # ("none" counts the layers without a mixer and those without
        # an FFN alike; nothing is indexed by it.)
        out = dict.fromkeys(
            ("attn", "state", "own", "dense", "moe", "none"), 0)
        for k, which in kinds:
            out[k if k in ("attn", "none") else "state"] += 1
            out[which] += 1
        return out

    def joined(routes):
        return jax.tree_util.tree_map(
            lambda *a: jnp.concatenate(a), *routes) if routes else None

    carry, routes = stretch((h, pl), lead, count(()))
    if reps:
        per = count(period_kinds)

        def period(carry, p):
            carry, r = stretch(carry, period_kinds, count(lead), p, per)
            return carry, joined(r)

        carry, r = jax.lax.scan(
            wrap_remat(period, remat), carry,
            jnp.arange(reps, dtype=jnp.int32))
        if r is not None:  # [reps, a period's moe layers, ...]
            routes.append(jax.tree_util.tree_map(
                lambda a: a.reshape((-1,) + a.shape[2:]), r))
        carry, more = stretch(
            carry, tail, count(lead + period_kinds * reps))
        routes += more
    h, pl = carry
    routing = joined(routes)
    if not paged:
        return h, None, routing
    return h, dict(
        pl,
        k=pl["k"].reshape((La, P) + pl["k"].shape[1:]),
        v=pl["v"].reshape((La, P) + pl["v"].shape[1:]),
    ), routing


def lm_head(params: Params, cfg: LLMConfig, h: jnp.ndarray,
            logits_dtype: jnp.dtype = jnp.float32) -> jnp.ndarray:
    """Logits of final hidden states h [..., H] (`forward`'s
    return_hidden): the tied embedding, transposed, or the head."""
    with jax.named_scope("head"):
        if cfg.tie_word_embeddings:
            return (h @ params["embed"]["weight"].astype(h.dtype).T).astype(
                logits_dtype
            )
        return (h @ params["lm_head"]["kernel"].astype(h.dtype)).astype(
            logits_dtype
        )


def _window_layers(cfg: LLMConfig, layers: Params, h, *, block, kv_cache,
                   block_tables, window_tables, window_view: dict, remat):
    """The layer stack of a config with window layers: a scan over
    PERIODS of cfg.global_layer_period layers whose body runs the
    period's layers in order through `block` (forward's closure over
    `_block`), the window layers ahead of and behind the global one
    each as an inner scan (`_hybrid_layers`' shape): the global layer
    sees the whole context (and has no position term under
    cfg.rope_window_only), the others the last cfg.sliding_window
    positions. Both paged planes are CARRIED
    (forward's "THE POOL IS CARRIED" rule), each flat behind tables
    offset by the layer's number WITHIN ITS KIND: `k` / `v` behind
    `block_tables`, `wk` / `wv` behind `window_tables`, whose slot 0 is
    the lane's `window_base` position: `window_view` holds a window
    layer's positions, write slots, lengths and mask relative to it, so
    that neither `_block` nor a kernel knows a page was ever given back.
    Layer weights stay stacked [L, ...], scanned as [L / period, period,
    ...]. Returns (h, the pool or None, the expert layers' routing
    [L, ...] or None)."""
    from oryx_tpu.ops import paged_kv

    per, off = cfg.global_layer_period, cfg.global_layer_offset
    W = cfg.sliding_window
    paged = kv_cache is not None
    gk = gv = wk = wv = None
    if paged:
        wkn, wvn = paged_kv.WINDOW_PLANES
        Lg, Pg = kv_cache["k"].shape[:2]
        Lw, Pw = kv_cache[wkn].shape[:2]
        gk, gv, wk, wv = (
            kv_cache[n].reshape((-1,) + kv_cache[n].shape[2:])
            for n in ("k", "v", wkn, wvn))
    no_rope = {"rope": (None, None)} if cfg.rope_window_only else {}

    def offset(tables, P, L, n):
        return jnp.where(tables >= P, L * P, tables + n.astype(tables.dtype) * P)

    def window_layer(carry, xs):
        h, wk, wv = carry
        lp, w, layer = xs
        tables = offset(window_tables, Pw, Lw, w) if paged else None
        h, wk, wv, r = block(
            h, lp, wk, wv, tables, layer, window=W, **window_view)
        return (h, wk, wv), r

    def window_run(h, wk, wv, lp_all, p, lo, hi):
        """The period's layers lo .. hi - 1, all window layers: an inner
        scan, so that a plane is written and read ONCE an iteration
        (three writes and reads of one carried plane in one body made
        the prefill program keep a copy of it)."""
        if lo == hi:
            return h, wk, wv, None
        js = jnp.arange(lo, hi, dtype=jnp.int32)
        with jax.named_scope("stack"):
            lp_run = jax.tree_util.tree_map(lambda a: a[lo:hi], lp_all)
        (h, wk, wv), r = jax.lax.scan(
            window_layer, (h, wk, wv),
            (lp_run, p * (per - 1) + js - (lo > off), p * per + js))
        return h, wk, wv, r

    def period(carry, xs):
        h, gk, gv, wk, wv = carry
        lp_all, p = xs
        h, wk, wv, before = window_run(h, wk, wv, lp_all, p, 0, off)
        tables = offset(block_tables, Pg, Lg, p) if paged else None
        with jax.named_scope("stack"):
            lp = jax.tree_util.tree_map(lambda a: a[off], lp_all)
        h, gk, gv, r = block(
            h, lp, gk, gv, tables, p * per + off, **no_rope)
        h, wk, wv, after = window_run(h, wk, wv, lp_all, p, off + 1, per)
        routes = [x for x in (
            before, jax.tree_util.tree_map(lambda a: a[None], r), after)
            if x is not None]
        return (h, gk, gv, wk, wv), jax.tree_util.tree_map(
            lambda *a: jnp.concatenate(a), *routes)

    n_per = cfg.num_layers // per
    with jax.named_scope("stack"):
        periods = jax.tree_util.tree_map(
            lambda a: a.reshape((n_per, per) + a.shape[1:]), layers)
    (h, gk, gv, wk, wv), routing = jax.lax.scan(
        wrap_remat(period, remat), (h, gk, gv, wk, wv),
        (periods, jnp.arange(n_per, dtype=jnp.int32)))
    routing = jax.tree_util.tree_map(
        lambda a: a.reshape((cfg.num_layers,) + a.shape[2:]), routing)
    if not paged:
        return h, None, routing
    return h, {
        "k": gk.reshape((Lg, Pg) + gk.shape[1:]),
        "v": gv.reshape((Lg, Pg) + gv.shape[1:]),
        wkn: wk.reshape((Lw, Pw) + wk.shape[1:]),
        wvn: wv.reshape((Lw, Pw) + wv.shape[1:]),
    }, routing


def forward(
    params: Params,
    cfg: LLMConfig,
    *,
    input_ids: jnp.ndarray | None = None,
    inputs_embeds: jnp.ndarray | None = None,
    positions: jnp.ndarray | None = None,
    kv_cache: Params | None = None,
    write_slots: jnp.ndarray | None = None,
    kv_mask: jnp.ndarray | None = None,
    block_tables: jnp.ndarray | None = None,
    write_mask: jnp.ndarray | None = None,
    kv_lengths: jnp.ndarray | None = None,
    q_segments: jnp.ndarray | None = None,
    remat: bool | str = False,
    attn_impl: str = "xla",
    mesh=None,
    sp_axis: str = "sp",
    compute_dtype: jnp.dtype | None = None,
    logits_dtype: jnp.dtype = jnp.float32,
    return_hidden: bool = False,
    segment_ids: jnp.ndarray | None = None,
    return_routing: bool = False,
    return_selected: bool = False,
    state_slots: jnp.ndarray | None = None,
    window_tables: jnp.ndarray | None = None,
    window_base: jnp.ndarray | None = None,
) -> tuple[jnp.ndarray, Params | None]:
    """Full decoder forward.

    Args:
      input_ids / inputs_embeds: exactly one; ids [B, T] or embeds [B, T, H].
        (Multimodal calls pass pre-spliced `inputs_embeds`; SURVEY.md §3.4.)
      positions: [B, T] absolute positions (RoPE + causal mask). Defaults to
        arange when no cache is used. CONSTRAINT (no-cache path): every
        valid token's position must equal its slot index (right-padded
        rows with per-row arange — the build_mm_batch layout). The Pallas
        path asserts this statically (slot_positions=True) to skip
        causally-dead kv tiles; left-padded or offset layouts would be
        silently mis-skipped. Use the kv_cache path for offset prefill.
      kv_cache: pytree from `init_kv_cache`; when present, k/v are written at
        `write_slots` ([B] first-slot indices, default positions[:, 0]) and
        attention runs over the whole cache with `kv_mask` [B, S] validity.
      kv_mask: with no cache, [B, T] padding mask; with cache, [B, S] slot
        validity — caller maintains it (see models/generate.py).
      block_tables: paged-cache mode — kv_cache is from `init_paged_kv_cache`
        ([L, P, page, Hk, D]) and each row's logical slots map through
        block_tables [B, max_pages] (ops/paged_kv.py). kv_mask then spans
        the LOGICAL stream [B, max_pages*page]. write_mask [B] gates rows'
        cache writes (finished/empty serving slots). kv_lengths [B] (valid
        kv count incl. the current token) enables the in-place Pallas
        ragged decode kernel for single-token steps under attn_impl=pallas.
        THE POOL IS CARRIED, NOT SCANNED: whenever kv_cache and
        block_tables are both given, the layer scan carries each plane
        as one flat [L*P, page, Hk, D] buffer (a free reshape; the
        returned cache has the stored [L, P, ...] layout again) and
        layer l reads and writes it through tables offset by l*P, so a
        donated pool is updated in place: no per-layer slice, no second
        stacked buffer, no copy back. Callers pass tables over ONE
        layer's pages (entries 0..P-1, sentinel P) exactly as the
        allocator hands them out; the offset is forward's own. A
        sentinel entry (>= P) becomes L*P at every layer, one past the
        whole pool: its write drops out of bounds as it did past one
        layer's P pages (it must not become page 0 of layer l+1), and
        its read clamps to a page that is masked or ignored.
      q_segments: packed RAGGED paged mode ([B=1, T] int32, requires
        block_tables): the T axis is a packed buffer of query rows from
        many sequences with MIXED query lengths — q_segments names each
        token's owning slot, `positions` its absolute position, and
        block_tables is [num_slots, max_pages]. Every token writes its
        K/V through its own slot's table and attends that slot's pages
        causally at its own position (ops/paged_kv.write_pages_packed /
        ragged_paged_attention; Pallas twin under attn_impl=pallas).
        write_mask is then PER TOKEN [1, T]; kv_mask/kv_lengths are
        unused (the causal mask at each row's position IS the validity
        mask). This is the one-dispatch mixed prefill+decode serving
        path (models/generate.paged_ragged_step).
      state_slots: a config with state-space layers (`cfg.recurrent`)
        and a paged kv_cache only. [B] int32: row b's recurrent state
        is row state_slots[b] of the pool's per-slot planes, a row
        whose first position is 0 starts from zeros whatever the slot
        held, and the state written back is the one after the row's
        last REAL token (positions < kv_lengths). None: the batch IS the
        slot array (B = S, row b = slot b; the decode chunk), a row
        with kv_lengths 0 (a finished, empty or still-prefilling lane)
        keeps its state untouched, and nothing is zeroed.
      window_tables, window_base: a config with window layers
        (`cfg.windowed`) and a paged kv_cache only, both required
        there. `block_tables` [B, max_pages] address the GLOBAL layers'
        planes from position 0, as ever; window_tables [B, window
        pages] address the window layers' planes from position
        window_base[b] [B] int32 (a multiple of the page size): logical
        slot s of row b's window table holds position window_base[b] +
        s. The caller keeps every position a query of this call can
        see, t - sliding_window < u <= t, inside the table; what lies
        before the base was given back to the window plane's allocator.
        kv_lengths is required (a window layer's validity mask is made
        from it).
      segment_ids: [B, T] int32 SAMPLE ids for sequence-packed training
        (0 = pad): attention is causal in SLOT order and masked on
        segment equality, so samples packed into one row never attend
        each other, while `positions` (restarting per sample) still
        drives RoPE. Training-only: incompatible with kv_cache and the
        ring impls.

    Returns (logits [B, T, V] in logits_dtype, updated kv_cache or None).
    """
    assert (input_ids is None) != (inputs_embeds is None)
    with jax.named_scope("embed"):
        if inputs_embeds is None:
            # All-gather the (fsdp-sharded) table before the lookup so
            # the gather output doesn't inherit the table layout and
            # force an involuntary full rematerialization to hs_spec
            # (see splice.embed_spliced).
            inputs_embeds = constrain(
                params["embed"]["weight"], None, None
            )[input_ids]
        if compute_dtype is not None:
            inputs_embeds = inputs_embeds.astype(compute_dtype)
    # Pin the hidden-state sharding so GSPMD doesn't guess intermediates:
    # batch over the data axes, sequence over sp only in ring mode.
    seq_axis = "sp" if attn_impl.startswith("ring") else None
    hs_spec = (("dp", "fsdp"), seq_axis, None)
    h = constrain(inputs_embeds, *hs_spec)
    B, T, _ = h.shape

    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (B, T))
    rope_dim = cfg.qk_rope_head_dim if cfg.latent else cfg.head_dim
    scaling = {}
    cos = sin = None  # attention without a position term
    with jax.named_scope("attn"):  # every layer's angles, once
        if cfg.yarn:
            scaling = dict(
                inv_freq=yarn_frequencies(
                    rope_dim, cfg.rope_theta,
                    factor=cfg.rope_scaling_factor,
                    original=cfg.rope_original_max_position,
                    beta_fast=cfg.rope_beta_fast,
                    beta_slow=cfg.rope_beta_slow,
                ),
                scale=cfg.rope_cos_sin_scale,
            )
        if cfg.use_rope:
            cos, sin = rope_cos_sin(
                positions, rope_dim, cfg.rope_theta, **scaling)  # [B,T,D]

    if kv_cache is not None and write_slots is None:
        write_slots = positions[:, 0]

    if segment_ids is not None and (
        kv_cache is not None or attn_impl not in ("xla", "pallas")
    ):
        raise ValueError(
            "segment_ids (packed training) requires attn_impl xla|pallas "
            "and no kv_cache"
        )
    if q_segments is not None:
        if block_tables is None or kv_cache is None:
            raise ValueError(
                "q_segments (packed ragged serving) requires a paged "
                "kv_cache with block_tables"
            )
        if B != 1:
            raise ValueError(
                f"q_segments packs many sequences into ONE row; got B={B}"
            )

    # NOTE for new attn impls: every branch's implementation must tag its
    # output `checkpoint_name(out, "flash_out")` (plus "flash_lse" where a
    # logsumexp residual exists) or the "attn"/"attn_qkv"/"attn_o" remat
    # policies (utils/remat.py) silently degrade for it — the attention
    # forward gets recomputed in the backward despite the policy.
    # Tagged per-impl rather than here so the custom-VJP kernels save the
    # exact residuals their backward needs without double-tagging.
    if attn_impl == "pallas":
        from oryx_tpu.ops.pallas import flash_attention as _fa

        def attn_fn(q, k, v, **kw):
            return _fa.flash_attention(q, k, v, causal=True, **kw)
    elif attn_impl == "xla":
        def attn_fn(q, k, v, slot_positions=False, **kw):
            return attention(q, k, v, causal=True, **kw)
    elif attn_impl in ("ring", "ring_flash"):
        # Sequence parallelism over the `sp` mesh axis (training/prefill;
        # decode with a KV cache is not sequence-sharded). "ring_flash"
        # runs the Pallas kernel per visiting block — O(tile) logits
        # memory, the long-context configuration.
        from oryx_tpu.ops.ring_attention import ring_attention

        if kv_cache is not None:
            raise ValueError(f"attn_impl={attn_impl!r} needs no kv_cache")
        ring_impl = "flash" if attn_impl == "ring_flash" else "xla"

        def attn_fn(q, k, v, *, q_positions, kv_positions, kv_mask,
                    slot_positions=False):
            return ring_attention(
                q, k, v, mesh=mesh, axis_name=sp_axis,
                batch_axes=("dp", "fsdp"), causal=True,
                positions=q_positions, kv_mask=kv_mask, impl=ring_impl,
            )
    else:
        raise ValueError(f"unknown attn_impl {attn_impl!r}")

    # Packed rows: causal order is the SLOT order (within a sample the
    # two coincide; across samples the segment mask rules) — which also
    # keeps the Pallas slot_positions DMA clamp valid despite the
    # restarting RoPE positions.
    attn_positions = positions
    mask_positions = None
    if cfg.block_length:
        if segment_ids is not None:
            raise ValueError(
                "segment_ids (packed training) is not built for a "
                "block-diffusion config (cfg.block_length > 0)"
            )
        Bl = cfg.block_length
        mask_positions = positions - positions % Bl + (Bl - 1)
    if return_routing and not cfg.num_experts:
        raise ValueError("return_routing needs an expert config")
    if segment_ids is not None:
        attn_positions = jnp.broadcast_to(
            jnp.arange(T, dtype=jnp.int32), (B, T)
        )
        base_attn_fn = attn_fn

        def attn_fn(q, k, v, **kw):  # noqa: F811 - deliberate wrap
            return base_attn_fn(
                q, k, v,
                q_segment_ids=segment_ids, kv_segment_ids=segment_ids,
                **kw,
            )

    # An expert config's kernels stay out of the scan's xs: they are
    # passed whole, flat [L*E, in, out], with the layer's index (`_moe`).
    layers, experts_flat = params["layers"], None
    if cfg.num_experts:
        layers = {k: v for k, v in layers.items() if k != "experts"}
        experts_flat = jax.tree_util.tree_map(
            lambda a: a.reshape((-1,) + a.shape[2:]),
            params["layers"]["experts"],
        )

    def block(h, lp, ck, cv, tables, layer=None, rope=(cos, sin), **view):
        # `view` (a window layer's, `_window_layers`): its own
        # positions, write slots, lengths and mask, and its window.
        kw = dict(
            positions=attn_positions, write_slots=write_slots,
            kv_mask=kv_mask, kv_lengths=kv_lengths,
            mask_positions=mask_positions,
        )
        kw.update(view)
        h, ck, cv, *routing = _block(
            cfg, h, lp, *rope,
            cache_k=ck, cache_v=cv,
            attn_fn=attn_fn,
            block_tables=tables,
            write_mask=write_mask,
            q_segments=q_segments,
            attn_impl=attn_impl,
            experts=(None if experts_flat is None or layer is None
                     else (experts_flat, layer)),
            **kw,
        )
        return constrain(h, *hs_spec), ck, cv, routing[0] if routing else None

    new_cache = None
    if cfg.latent:
        from oryx_tpu.ops import paged_kv

        if (q_segments is not None or segment_ids is not None
                or attn_impl not in ("xla", "pallas")
                or (kv_cache is not None and (
                    block_tables is None
                    or not paged_kv.is_latent_pool(kv_cache)))):
            raise ValueError(unsupported_for_latent(
                "a packed ragged step, packed training, ring attention or "
                "a dense / per-head cache"
            ))
        attn = dict(
            positions=positions, write_slots=write_slots, kv_mask=kv_mask,
            write_mask=write_mask, kv_lengths=kv_lengths,
        )
        if return_selected:
            # With return_routing, a paged cache and an indexer: every
            # layer's selection under routing["selected"] [L, ...].
            attn["return_selected"] = True
        num_l = jnp.arange(cfg.moe_layers, dtype=jnp.int32)
        # Cache layers a model layer, and the block that reads them.
        per = 2 if cfg.shortcut_double_layer else 1
        latent_block = _double_block if per == 2 else _latent_block
        Ld = cfg.dense_layers
        if kv_cache is None:
            def body(h, xs, experts=True):
                lp, layer = xs
                h, _, routing = latent_block(
                    cfg, h, lp, cos, sin, pool=None, tables=None,
                    experts=(experts_flat, layer) if experts else None,
                    attn_impl=attn_impl, **attn,
                )
                return constrain(h, *hs_spec), routing

            if Ld:
                # The leading dense layers, then the expert layers.
                h, _ = jax.lax.scan(
                    wrap_remat(partial(body, experts=False), remat), h,
                    (params["dense_layers"], num_l[:Ld]))
            h, expert_counts = jax.lax.scan(
                wrap_remat(body, remat), h, (layers, num_l)
            )
        else:
            # The pool is the scan's carry, one flat [Lc*P, page, Dp]
            # buffer behind layer-offset tables, as below; cache layer
            # per * l + i belongs to sublayer i of model layer l. With
            # an indexer the carry is the pair (latents, index keys),
            # both behind the same tables.
            names = (paged_kv.LATENT,) + (
                (paged_kv.INDEX_K,) if cfg.indexed else ())
            planes = tuple(kv_cache[n] for n in names)
            Lc, P = planes[0].shape[:2]
            pool = tuple(
                a.reshape((Lc * P,) + a.shape[2:]) for a in planes)
            if not cfg.indexed:
                pool = pool[0]

            def body(carry, xs, experts=True, first=Ld):
                h, pool = carry
                lp, layer = xs
                # (an expert layer's cache layer lies behind the dense.)
                cl = layer + first if first else layer
                tables = [
                    jnp.where(block_tables >= P, Lc * P,
                              block_tables + (per * cl + i) * P)
                    for i in range(per)
                ]
                h, pool, routing = latent_block(
                    cfg, h, lp, cos, sin, pool=pool, tables=tables,
                    experts=(experts_flat, layer) if experts else None,
                    attn_impl=attn_impl, **attn,
                )
                return (constrain(h, *hs_spec), pool), routing

            num_l = num_l.astype(block_tables.dtype)
            led = None
            if Ld:
                (h, pool), led = jax.lax.scan(
                    wrap_remat(partial(body, experts=False, first=0), remat),
                    (h, pool), (params["dense_layers"], num_l[:Ld]))
            (h, pool), expert_counts = jax.lax.scan(
                wrap_remat(body, remat), (h, pool), (layers, num_l),
            )
            if led:  # the dense layers' selections ahead of the others'
                expert_counts["selected"] = jnp.concatenate(
                    [led["selected"], expert_counts["selected"]])
            new_cache = {
                n: a.reshape((Lc, P) + a.shape[1:])
                for n, a in zip(names, pool if cfg.indexed else (pool,))
            }
    elif cfg.recurrent:
        from oryx_tpu.ops import paged_kv

        if (q_segments is not None or segment_ids is not None
                or (kv_cache is not None and (
                    block_tables is None or kv_lengths is None
                    or paged_kv.paged_planes(kv_cache) is kv_cache))):
            raise ValueError(unsupported_for_recurrent(
                "a packed ragged step, packed training or a dense cache"
            ))
        h, new_cache, expert_counts = _hybrid_layers(
            cfg, layers, h, block=block, kv_cache=kv_cache,
            block_tables=block_tables, positions=positions,
            kv_lengths=kv_lengths, kv_mask=kv_mask,
            state_slots=state_slots, attn_impl=attn_impl, remat=remat,
            experts_flat=experts_flat,
        )
    elif cfg.windowed:
        from oryx_tpu.ops import paged_kv

        window_view = {}
        if kv_cache is not None:
            if (q_segments is not None or block_tables is None
                    or window_tables is None or window_base is None
                    or kv_lengths is None
                    or paged_kv.WINDOW_PLANES[0] not in kv_cache):
                raise ValueError(unsupported_for_window(
                    "a packed ragged step, a dense cache or a pool of "
                    "one plane (forward needs block_tables, "
                    "window_tables, window_base and kv_lengths)"
                ))
            base = window_base.astype(jnp.int32)
            w_len = jnp.where(kv_lengths > 0, kv_lengths - base, 0)
            Kw = window_tables.shape[1] * kv_cache["k"].shape[2]
            window_view = dict(
                positions=positions - base[:, None],
                write_slots=write_slots - base, kv_lengths=w_len,
                kv_mask=(jnp.arange(Kw, dtype=jnp.int32)[None, :]
                         < w_len[:, None]).astype(jnp.int32),
            )
        elif segment_ids is not None:
            raise ValueError(unsupported_for_window("packed training"))
        h, new_cache, expert_counts = _window_layers(
            cfg, layers, h, block=block, kv_cache=kv_cache,
            block_tables=block_tables, window_tables=window_tables,
            window_view=window_view, remat=remat,
        )
    elif kv_cache is not None and block_tables is not None:
        # Paged pool: the scan's CARRY, one flat [L*P, page, ...] buffer a
        # plane behind layer-offset tables (the `block_tables` contract
        # above), so XLA updates the donated pool in place.
        L, P = kv_cache["k"].shape[:2]
        flat = jax.tree_util.tree_map(
            lambda a: a.reshape((L * P,) + a.shape[2:]), kv_cache
        )

        def body(carry, xs):
            h, ck, cv = carry
            lp, layer = xs
            tables = jnp.where(
                block_tables >= P, L * P, block_tables + layer * P
            )
            h, ck, cv, counts = block(h, lp, ck, cv, tables, layer)
            return (h, ck, cv), counts

        (h, ck, cv), expert_counts = jax.lax.scan(
            wrap_remat(body, remat), (h, flat["k"], flat["v"]),
            (layers, jnp.arange(L, dtype=block_tables.dtype)),
        )
        new_cache = jax.tree_util.tree_map(
            lambda a: a.reshape((L, P) + a.shape[1:]), {"k": ck, "v": cv}
        )
    else:
        # Dense [L, B, S, Hk, D] cache (generate / generate_stream) as
        # the scan's xs/ys, or no cache at all (training).
        def body(h, xs):
            layer = None
            if experts_flat is not None:
                xs, layer = xs
            lp, ck, cv = (xs, None, None) if kv_cache is None else xs
            h, ck, cv, counts = block(h, lp, ck, cv, None, layer)
            return h, (None if kv_cache is None else (ck, cv), counts)

        xs = layers
        if kv_cache is not None:
            xs = (xs, kv_cache["k"], kv_cache["v"])
        if experts_flat is not None:
            xs = (xs, jnp.arange(cfg.num_layers, dtype=jnp.int32))
        h, (ys, expert_counts) = jax.lax.scan(wrap_remat(body, remat), h, xs)
        if kv_cache is not None:
            new_cache = {"k": ys[0], "v": ys[1]}

    with jax.named_scope("head"):
        h = rms_norm(h, params["final_norm"]["weight"], cfg.rms_norm_eps)
    if return_hidden:
        # Final hidden states pre-lm_head: the chunked-CE training path
        # (train/loss.chunked_causal_lm_loss) projects to the vocab
        # per-chunk instead of materializing [B, T, V] logits.
        out = h
    else:
        out = lm_head(params, cfg, h, logits_dtype)
    if return_routing:
        return out, new_cache, expert_counts
    return out, new_cache
