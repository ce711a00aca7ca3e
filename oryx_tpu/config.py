"""Single dataclass-tree configuration for the whole framework.

Replaces the reference's three-layer config (HfArgumentParser dataclasses +
DeepSpeed JSON + bash scripts; SURVEY.md §5 "Config / flag system") with one
serializable tree. Every component takes its sub-config explicitly; presets
below pin the published model geometries.
"""

from __future__ import annotations

import dataclasses
import json
import math
import typing
from dataclasses import dataclass, field
from typing import Any


def unsupported_for_recurrent(mode: str) -> str:
    """The one refusal of a mode that is not built for a config with
    state layers (`LLMConfig.recurrent`: Mamba mixers or gated short
    convolutions): beside the paged K/V of its attention layers its
    pool holds ONE fixed-size state a slot a state layer, which no block
    table addresses and which only the split engine's `paged_prefill` /
    `paged_decode_chunk` carry. A prefix-cache hit hands a state over
    where the pool keeps a snapshot a page (`conv_edge`: a gated short
    convolution's two rows); a Mamba layer's [d_state, d_inner] state
    has none."""
    return (
        f"state-space layers (attn_layer_period > 0, layer_types or "
        f"hybrid_override_pattern): "
        f"{mode} is not built "
        "for a recurrent state beside the paged pool (one conv window and, "
        "a Mamba layer, one [d_state, d_inner] state a slot a layer, "
        "addressed by slot and not through a block table); it serves "
        "through the continuous split engine with a bf16 pool only, and "
        "with a prefix cache only where the state is a gated short "
        "convolution's (a snapshot a page)"
    )


def unsupported_for_window(mode: str) -> str:
    """The one refusal of a mode that is not built for a config with
    window layers (`LLMConfig.windowed`): its pool is TWO paged planes,
    one a layer kind, each behind its own allocator and block table,
    and a window layer's table is re-based as the lane moves on, which
    only the split engine's `paged_prefill` / `paged_decode_chunk`
    carry."""
    return (
        f"window layers (sliding_window > 0): {mode} is not built for a "
        "pool of two paged planes (one block table a layer kind, the "
        "window plane's re-based as pages older than the window are "
        "given back); it serves through the continuous split engine "
        "with a bf16 pool and no prefix cache only"
    )


def unsupported_for_indexer(mode: str) -> str:
    """The one refusal of a mode that learned sparse attention
    (`LLMConfig.index_topk` > 0) is not built beside."""
    return (
        f"learned sparse attention (index_topk > 0): {mode} is not built "
        "beside an indexer; it selects rows of a latent (MLA) pool in "
        "the single latent block with unscaled interleaved RoPE only"
    )


@dataclass(frozen=True)
class LLMConfig:
    """Qwen2/Yi-class decoder geometry.

    Defaults are Qwen2-7B-Instruct (the Oryx-7B backbone).
    """

    vocab_size: int = 152064
    hidden_size: int = 3584
    intermediate_size: int = 18944
    num_layers: int = 28
    num_heads: int = 28
    num_kv_heads: int = 4
    head_dim: int = 128
    rope_theta: float = 1_000_000.0
    rms_norm_eps: float = 1e-6
    max_position_embeddings: int = 32768
    tie_word_embeddings: bool = False
    # Qwen2 uses bias on q/k/v projections (not o); Yi/Llama-class uses none.
    attention_bias: bool = True
    # Per-head RMSNorm of q and k over head_dim, before RoPE (the
    # qwen3_moe lineage); adds q_norm/k_norm [L, D] weights.
    qk_norm: bool = False
    # Sparse expert MLP in EVERY layer when num_experts > 0 (the dense
    # gate/up/down of `intermediate_size` is then absent): a float32
    # router over num_experts, the num_experts_per_tok largest softmax
    # probabilities (renormalized to sum 1 when norm_topk_prob), SwiGLU
    # experts of width moe_intermediate_size, no shared expert.
    num_experts: int = 0
    num_experts_per_tok: int = 0
    moe_intermediate_size: int = 0
    norm_topk_prob: bool = True
    # Generation by diffusion over blocks when > 0: attention is full
    # inside a block of block_length positions and causal across blocks
    # (M[i, j] = j // B <= i // B), logits at position i predict token i
    # itself, and masked positions carry mask_token_id
    # (models/generate.paged_block_step). A power of two, so that every
    # page, prefill chunk and attention tile edge is a block edge.
    block_length: int = 0
    mask_token_id: int = 0
    # Latent attention (MLA) when kv_lora_rank > 0: queries go through a
    # q_lora_rank bottleneck, keys and values through one kv_lora_rank
    # latent a token plus ONE roped key of qk_rope_head_dim shared by
    # every head; a head's key is [qk_nope_head_dim | qk_rope_head_dim]
    # wide and its value v_head_dim. The cache keeps (latent, roped key)
    # a token, never per-head K or V (`qwen2._mla`); num_kv_heads and
    # head_dim are then unused. mla_scale_*: the family's modelling code
    # multiplies the normed q latent by sqrt(hidden / q_lora_rank) and
    # the normed kv latent by sqrt(hidden / kv_lora_rank). A latent
    # model's layer is one of two. With shortcut_double_layer: two
    # (attention, dense FFN) sublayers in series with ONE expert layer
    # whose input is taken after the first attention and whose output is
    # added after the second FFN (`qwen2._double_block`); the dense FFNs
    # are `intermediate_size` wide, the experts moe_intermediate_size,
    # and a model layer is two cache layers. Without it: ONE latent
    # attention and then the expert layer on one normed input
    # (`qwen2._latent_block`), one cache layer a model layer.
    kv_lora_rank: int = 0
    q_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    mla_scale_q_lora: bool = False
    mla_scale_kv_lora: bool = False
    shortcut_double_layer: bool = False
    # RoPE pairs (x[2j], x[2j+1]) (the DeepSeek-V3 lineage) instead of
    # (x[j], x[j + D/2]).
    rope_interleaved: bool = False
    # Zero-compute experts: router outputs num_experts ..
    # num_experts + zero_experts - 1 are identity experts, E(x) = x.
    zero_experts: int = 0
    # Weights of the chosen experts are routed_scaling_factor * p.
    routed_scaling_factor: float = 1.0
    # A per-expert correction bias [num_experts + zero_experts] added to
    # the probabilities for SELECTION only; the weights stay p.
    router_bias: bool = False
    # (first, count) of the routed experts this chip holds, None = all.
    # The router keeps its width and its experts per token; the expert
    # layer computes the held experts' and the zero-compute experts'
    # part of the result and leaves out what absent experts would add.
    experts_held: tuple[int, int] | None = None
    # Shared experts: ONE SwiGLU of width n_shared_experts *
    # moe_intermediate_size on every token, unweighted, added to the
    # routed experts' sum (`qwen2._moe`); whole on every chip.
    n_shared_experts: int = 0
    # YaRN RoPE scaling (latent attention only) when rope_scaling_factor
    # > 1: `ops/rope.yarn_frequencies` blends inv_freq / factor with
    # inv_freq by a linear ramp between the correction dims of
    # rope_beta_fast and rope_beta_slow rotations over
    # rope_original_max_position positions. The three conventions the
    # published keys do not settle live HERE and nowhere else
    # (`softmax_scale`, `rope_cos_sin_scale`, `query_position_scale`).
    rope_scaling_factor: float = 1.0
    rope_original_max_position: int = 0
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_mscale: float = 1.0
    rope_mscale_all_dim: float = 0.0
    # q <- q * (1 + beta * ln(1 + floor(pos / original))) when > 0.
    llama4_scaling_beta: float = 0.0
    # Layer kinds by position when attn_layer_period > 0 (the Jamba
    # lineage): layer i attends iff i % attn_layer_period ==
    # attn_layer_offset, every other layer is a Mamba-1 mixer
    # (`models/mamba.py`: d_inner = mamba_expand * hidden_size channels,
    # a depthwise causal conv of mamba_d_conv taps, a [mamba_d_state]
    # state a channel, dt through a mamba_dt_rank bottleneck, RMSNorm on
    # dt, B and C); every layer keeps the dense FFN. num_layers is a
    # whole number of periods. 0 = every layer attends.
    attn_layer_period: int = 0
    attn_layer_offset: int = 0
    # Layer kinds by position from the source's own LIST (the LFM2
    # lineage's `layer_types`), where no period and offset give them:
    # entry i is "full_attention" or "conv", a gated short convolution
    # (`models/short_conv.py`: conv_L_cache taps, its whole state the
    # last conv_L_cache - 1 gated inputs [hidden_size] a lane). The
    # first num_layers entries are the model's, so a list may be longer
    # than a depth cut. `layer_kinds` is the one table both rules fill;
    # the FFN kind is by position too (`ffn_kinds`).
    layer_types: tuple[str, ...] = ()
    conv_L_cache: int = 3
    mamba_d_state: int = 16
    mamba_d_conv: int = 4
    mamba_expand: int = 2
    mamba_dt_rank: int = 0
    mamba_conv_bias: bool = True
    mamba_proj_bias: bool = False
    # Layer kinds by position from the source's PATTERN string (the
    # Nemotron-H lineage's `hybrid_override_pattern`), one character a
    # layer, each layer ONE sublayer: `M` a Mamba-2 mixer alone
    # (`models/mamba2.py`: mamba_num_heads heads of mamba_head_dim
    # channels, ONE decay a head, B and C shared by the heads of each of
    # mamba_n_groups groups, a [mamba_d_state] state a channel, the
    # prefill in its chunked matmul form at mamba_chunk_size), `*`
    # attention alone, `E` the expert layer alone (`layer_kinds` "none",
    # `ffn_kinds` "moe"; every other layer's FFN kind is "none"). The
    # first num_layers characters are the model's.
    hybrid_override_pattern: str = ""
    mamba_num_heads: int = 0
    mamba_head_dim: int = 0
    mamba_n_groups: int = 1
    mamba_chunk_size: int = 128
    # False: attention without rotary (or any other) position term; the
    # state-space layers carry the order.
    use_rope: bool = True
    # Attention layer kinds by position when sliding_window > 0: layer i
    # is GLOBAL (sees the whole context) iff i % global_layer_period ==
    # global_layer_offset, every other layer is a WINDOW layer whose
    # query at t sees the keys u <= t with t - u < sliding_window.
    # num_layers is a whole number of periods. The paged pool then holds
    # one plane a kind (`qwen2.init_paged_kv_cache`), so that a window
    # layer keeps no page its queries can no longer see. With
    # rope_window_only the position term is applied on window layers
    # alone (a global layer has none). 0 = every layer is global.
    sliding_window: int = 0
    global_layer_period: int = 0
    global_layer_offset: int = 0
    rope_window_only: bool = False
    # The experts' gate activation: "silu" (SwiGLU) or "relu" (ReGLU);
    # "relu2": NOT gated, an expert is two matrices, relu(x W1)^2 W2
    # (the Nemotron-H lineage's `mlp_hidden_act`), and so is the shared
    # expert.
    moe_activation: str = "silu"
    # Latent experts when > 0: the routed experts read and write a
    # moe_latent_size-wide latent, x W_dn on the way in (once a token)
    # and W_up on the way out, applied ONCE to the weighted sum of a
    # token's experts; the router and the shared expert read the hidden
    # state. An expert's kernels are then [moe_latent_size,
    # moe_intermediate_size] and back.
    moe_latent_size: int = 0
    # The shared expert's own width where the source gives one (else
    # n_shared_experts * moe_intermediate_size).
    moe_shared_expert_intermediate_size: int = 0
    # What the router reads: "post_attn" (the normed state after
    # attention, the expert layer's own input) or "layer_input" (the
    # residual stream at the layer's input, before its norm).
    router_input: str = "post_attn"
    # How the router turns its logits into probabilities: "softmax" over
    # the experts, or "sigmoid" of each logit on its own (the
    # DeepSeek-V3 lineage's `scoring_func`); selection, renormalising
    # and the scaling factor are the same for both (`qwen2.moe_select`).
    router_scoring: str = "softmax"
    # Leading dense layers of an expert model (`first_k_dense_replace`,
    # `num_dense_layers`): the first dense_layers of num_layers have ONE
    # SwiGLU of intermediate_size in the expert layer's place; the rest
    # are expert layers. Built for the single latent block
    # (`qwen2._latent_block` with no experts) and for a config with
    # state layers (`qwen2._hybrid_layers`).
    dense_layers: int = 0
    # Added to the sum of the chosen experts' probabilities before
    # norm_topk_prob divides by it (the LFM2 lineage's 1e-6; 0: the bare
    # sum).
    norm_topk_eps: float = 0.0
    # Learned sparse attention over the latent pool when index_topk > 0
    # (`qwen2._mla`, ops/paged_kv.py): an indexer of index_heads
    # heads of index_head_dim scores every visible key (a weighted sum
    # of the heads' relu'd products, float32), each query attends the
    # index_topk best alone, and a cached token keeps its index key
    # [index_head_dim] in a plane of its own beside its latent. The
    # first qk_rope_head_dim columns of an index head are roped.
    index_heads: int = 0
    index_head_dim: int = 0
    index_topk: int = 0

    @property
    def indexed(self) -> bool:
        """Attention reads the keys its indexer selected."""
        return self.index_topk > 0

    @property
    def moe_layers(self) -> int:
        """Layers with an expert layer: all but the leading dense ones,
        or the pattern's `E` layers."""
        if self.hybrid_override_pattern:
            return self.ffn_kinds.count("moe")
        return self.num_layers - self.dense_layers

    @property
    def windowed(self) -> bool:
        """Some attention layers see a sliding window only."""
        return self.sliding_window > 0

    @property
    def num_global_layers(self) -> int:
        if not self.windowed:
            return self.num_layers
        return self.num_layers // self.global_layer_period

    @property
    def num_window_layers(self) -> int:
        return self.num_layers - self.num_global_layers

    @property
    def latent(self) -> bool:
        return self.kv_lora_rank > 0

    @property
    def layer_kinds(self) -> tuple[str, ...]:
        """THE table of layer kinds, one entry a layer: "attn", "mamba",
        "conv", "mamba2", or "none" (no mixer here: the layer is its FFN
        alone). From `hybrid_override_pattern` or `layer_types` where
        the source lists them, from the period and offset where it
        gives those, else every layer attends."""
        if self.hybrid_override_pattern:
            return tuple(
                {"M": "mamba2", "*": "attn", "E": "none"}[c]
                for c in self.hybrid_override_pattern[:self.num_layers])
        if self.layer_types:
            return tuple(
                "attn" if t == "full_attention" else t
                for t in self.layer_types[:self.num_layers])
        if self.attn_layer_period:
            return tuple(
                "attn" if i % self.attn_layer_period == self.attn_layer_offset
                else "mamba" for i in range(self.num_layers))
        return ("attn",) * self.num_layers

    @property
    def ffn_kinds(self) -> tuple[str, ...]:
        """The FFN kind of each layer of a config with state layers:
        "own" (no experts anywhere: the dense SwiGLU's kernels lie in
        the layer's own stack), else "dense" for the leading
        dense_layers and "moe" behind them. Under a pattern a layer is
        ONE sublayer: "moe" at an `E`, "none" (no FFN here) at a mixer."""
        if self.hybrid_override_pattern:
            return tuple(
                "moe" if c == "E" else "none"
                for c in self.hybrid_override_pattern[:self.num_layers])
        if not self.num_experts:
            return ("own",) * self.num_layers
        return tuple("dense" if i < self.dense_layers else "moe"
                     for i in range(self.num_layers))

    @property
    def state_kind(self) -> str | None:
        """The kind of the layers that keep a per-slot state: "mamba",
        "conv", "mamba2", or None where every layer attends. ONE kind a
        model."""
        kinds = set(self.layer_kinds) - {"attn", "none"}
        return next(iter(kinds)) if kinds else None

    @property
    def recurrent(self) -> bool:
        """Some layers keep a per-slot state and no K/V."""
        return self.state_kind is not None

    @property
    def mamba_d_inner(self) -> int:
        if self.mamba_num_heads:  # Mamba-2: heads x head size
            return self.mamba_num_heads * self.mamba_head_dim
        return self.mamba_expand * self.hidden_size

    @property
    def mamba2_conv_dim(self) -> int:
        """Channels a Mamba-2 mixer's conv runs over: x | B | C."""
        return self.mamba_d_inner + 2 * self.mamba_n_groups * self.mamba_d_state

    @property
    def shared_expert_width(self) -> int:
        return (self.moe_shared_expert_intermediate_size
                or self.n_shared_experts * self.moe_intermediate_size)

    @property
    def num_attn_layers(self) -> int:
        return self.layer_kinds.count("attn")

    @property
    def num_state_layers(self) -> int:
        """Layers that keep a per-slot state (of whichever kind) and no
        K/V."""
        return self.layer_kinds.count(self.state_kind)

    @property
    def kv_pack(self) -> int:
        """Key/value heads a cached token's ROW holds side by side in a
        hybrid's pool (`qwen2.init_paged_kv_cache`): the most that
        divide num_kv_heads and fit 128 lanes, so that a head of 64
        leaves no lane of a row unused (the chip pads a row to 128
        lanes, and the page walk copies nothing narrower). 1 at a head
        of 128 and for every model that is no hybrid: the pool as it
        always was. The ONE place the layout is decided: the pool is
        built from it and `qwen2._block` hands it to its attention."""
        if not self.recurrent:
            return 1
        fits = [r for r in range(1, self.num_kv_heads + 1)
                if self.num_kv_heads % r == 0 and r * self.head_dim <= 128]
        return max(fits, default=1)

    @property
    def conv_state_width(self) -> int:
        """Values of ONE slot's row of the pool's `conv` plane, a state
        layer: the last taps - 1 conv inputs, flat."""
        if self.state_kind == "conv":
            return (self.conv_L_cache - 1) * self.hidden_size
        if self.state_kind == "mamba2":
            return (self.mamba_d_conv - 1) * self.mamba2_conv_dim
        return (self.mamba_d_conv - 1) * self.mamba_d_inner

    def state_bytes_per_slot(self, dtype_bytes: int = 2) -> int:
        """Bytes of recurrent state ONE slot holds: the conv window
        (`conv_state_width`) in the compute dtype a state layer, and for
        a Mamba layer (of either generation) a float32 [d_state,
        d_inner] state beside it."""
        per = dtype_bytes * self.conv_state_width
        if self.state_kind in ("mamba", "mamba2"):
            per += 4 * self.mamba_d_state * self.mamba_d_inner
        return self.num_state_layers * per

    @property
    def cache_layers(self) -> int:
        """Cache layers of the paged pool: two a model layer in the
        shortcut-connected double layer, the attention layers alone
        where the others are state-space mixers, else one a layer."""
        if self.recurrent:
            return self.num_attn_layers
        return self.num_layers * (2 if self.shortcut_double_layer else 1)

    def layer_plan(self):
        """`layer_kinds` with `ffn_kinds` as (lead, period, repeats,
        tail): the longest stretch that is one run of layers repeated
        (scanned, `qwen2._hybrid_layers`), with what lies ahead of and
        behind it (unrolled). Each of lead, period and tail is a tuple
        of (kind, ffn kind) a layer."""
        layers = tuple(zip(self.layer_kinds, self.ffn_kinds))
        L = len(layers)
        best = (0, 0, L, 1)  # (covered, -period, lead, repeats)
        for a in range(L):
            for P in range(1, (L - a) // 2 + 1):
                n = 1
                while layers[a + n * P:a + (n + 1) * P] == layers[a:a + P]:
                    n += 1
                if n > 1 and (n * P, -P) > best[:2]:
                    best = (n * P, -P, a, n)
        covered, negP, a, n = best
        if not covered:
            return layers, (), 0, ()
        return (layers[:a], layers[a:a - negP], n, layers[a + covered:])

    @property
    def yarn(self) -> bool:
        return self.rope_scaling_factor > 1.0

    @staticmethod
    def _yarn_mscale(factor: float, mscale: float) -> float:
        return 1.0 if factor <= 1.0 else 0.1 * mscale * math.log(factor) + 1.0

    @property
    def softmax_scale(self) -> float:
        """Latent attention's score scale: 1 / sqrt(dn + dr), times m * m
        under YaRN with rope_mscale_all_dim, m = 0.1 * mscale_all_dim *
        ln(factor) + 1 (the DeepSeek-V3 lineage's convention)."""
        scale = (self.qk_nope_head_dim + self.qk_rope_head_dim) ** -0.5
        if self.yarn and self.rope_mscale_all_dim:
            m = self._yarn_mscale(
                self.rope_scaling_factor, self.rope_mscale_all_dim)
            scale *= m * m
        return scale

    @property
    def rope_cos_sin_scale(self) -> float:
        """Multiplier of YaRN's cos / sin tables: mscale(factor, mscale)
        / mscale(factor, mscale_all_dim)."""
        if not self.yarn:
            return 1.0
        f = self.rope_scaling_factor
        return self._yarn_mscale(f, self.rope_mscale) / self._yarn_mscale(
            f, self.rope_mscale_all_dim)

    def query_position_scale(self, positions):
        """The query's scale by position, float32, or None without
        llama4_scaling_beta: 1 + beta * ln(1 + floor(pos / original)),
        1 below the original length and stepping at its multiples.
        `positions` is a numpy or jax integer array."""
        if not self.llama4_scaling_beta:
            return None
        import jax.numpy as jnp

        steps = (positions // self.rope_original_max_position).astype(
            jnp.float32)
        return 1.0 + self.llama4_scaling_beta * jnp.log1p(steps)

    @property
    def held(self) -> tuple[int, int]:
        return self.experts_held or (0, self.num_experts)

    @property
    def latent_dim(self) -> int:
        """Values a cached token holds: the latent and the roped key."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def latent_page_dim(self) -> int:
        """`latent_dim` padded to whole 128-lane tiles, which is what a
        page row occupies in device memory either way."""
        return -(-self.latent_dim // 128) * 128

    def __post_init__(self):
        B = self.block_length
        if B < 0 or B & (B - 1):
            raise ValueError(
                f"block_length must be 0 or a power of two, got {B}"
            )
        if self.num_experts and not (
            0 < self.num_experts_per_tok <= self.num_experts
            and self.moe_intermediate_size > 0
        ):
            raise ValueError(
                f"num_experts={self.num_experts} needs 0 < "
                f"num_experts_per_tok ({self.num_experts_per_tok}) <= "
                "num_experts and moe_intermediate_size > 0 "
                f"({self.moe_intermediate_size})"
            )
        if self.experts_held is not None:
            object.__setattr__(
                self, "experts_held", tuple(int(v) for v in self.experts_held)
            )
        first, count = self.held
        if self.num_experts and not (
            0 <= first and 0 < count and first + count <= self.num_experts
        ):
            raise ValueError(
                f"experts_held={self.experts_held} is not a range of the "
                f"{self.num_experts} routed experts"
            )
        if (self.zero_experts or self.router_bias
                or self.experts_held is not None) and not self.num_experts:
            raise ValueError(
                "zero_experts, router_bias and experts_held need an "
                "expert config (num_experts > 0)"
            )
        if self.latent and not (
            self.q_lora_rank > 0 and self.qk_nope_head_dim > 0
            and self.qk_rope_head_dim > 0 and self.v_head_dim > 0
            and self.qk_rope_head_dim % 2 == 0
        ):
            raise ValueError(
                "latent attention (kv_lora_rank > 0) needs q_lora_rank, "
                "qk_nope_head_dim, v_head_dim > 0 and an even "
                f"qk_rope_head_dim, got {self}"
            )
        if self.latent and (
            not self.num_experts or self.block_length or self.qk_norm
            or self.attention_bias or self.tie_word_embeddings
        ):
            raise ValueError(
                "latent attention is built for an expert decoder "
                "(num_experts > 0: the shortcut-connected double layer or "
                "the single latent block), without attention bias, q/k "
                "norm, tied embeddings or block diffusion"
            )
        if not self.latent and (
            self.shortcut_double_layer or self.yarn
            or self.llama4_scaling_beta
        ):
            raise ValueError(
                "shortcut_double_layer, RoPE scaling (rope_scaling_factor "
                "> 1) and llama4_scaling_beta are built for latent "
                "attention only (kv_lora_rank > 0)"
            )
        if self.n_shared_experts < 0 or (
            self.n_shared_experts and not (
                self.latent and not self.shortcut_double_layer
                or self.hybrid_override_pattern and self.num_experts)
        ):
            raise ValueError(
                "n_shared_experts is built for the single latent block "
                "(kv_lora_rank > 0, num_experts > 0, no "
                "shortcut_double_layer) and for the expert layers of a "
                "hybrid_override_pattern, got "
                f"n_shared_experts={self.n_shared_experts}"
            )
        if (self.yarn or self.llama4_scaling_beta) and not (
            self.rope_original_max_position > 0
        ):
            raise ValueError(
                "RoPE scaling and llama4_scaling_beta need "
                "rope_original_max_position > 0"
            )
        if self.layer_types:
            object.__setattr__(self, "layer_types", tuple(self.layer_types))
            if self.attn_layer_period or len(
                    self.layer_types) < self.num_layers or not set(
                    self.layer_types) <= {"full_attention", "conv"} or (
                    self.conv_L_cache < 2):
                raise ValueError(
                    "layer_types lists 'full_attention' or 'conv' for at "
                    "least num_layers layers, without attn_layer_period, "
                    f"and conv_L_cache > 1, got {self.layer_types}"
                )
        if self.hybrid_override_pattern:
            pat = self.hybrid_override_pattern[:self.num_layers]
            heads, G = self.mamba_num_heads, self.mamba_n_groups
            if (self.attn_layer_period or self.layer_types
                    or len(pat) < self.num_layers or not set(pat) <= set("M*E")
                    or "M" not in pat or ("E" in pat) != bool(self.num_experts)
                    or self.dense_layers
                    or not (heads > 0 and self.mamba_head_dim > 0
                            and G > 0 and heads % G == 0
                            and self.mamba_d_state > 0
                            and self.mamba_d_conv > 1
                            and self.mamba_chunk_size > 0)):
                raise ValueError(
                    "hybrid_override_pattern gives 'M', '*' or 'E' for at "
                    "least num_layers layers (an 'M' among them, an 'E' iff "
                    "num_experts > 0), without attn_layer_period, "
                    "layer_types or dense_layers, and needs "
                    "mamba_num_heads a multiple of mamba_n_groups, "
                    "mamba_head_dim, mamba_d_state, mamba_chunk_size > 0 "
                    f"and mamba_d_conv > 1, got {self}"
                )
        elif self.mamba_num_heads or self.moe_latent_size or (
                self.moe_activation == "relu2"):
            raise ValueError(
                "mamba_num_heads (a Mamba-2 mixer), moe_latent_size and "
                "moe_activation 'relu2' are built for a layer table from "
                "hybrid_override_pattern"
            )
        if self.attn_layer_period:
            P = self.attn_layer_period
            if not (0 <= self.attn_layer_offset < P
                    and self.num_layers % P == 0 and P > 1
                    and self.mamba_dt_rank > 0 and self.mamba_d_state > 0
                    and self.mamba_d_conv > 1 and self.mamba_expand > 0):
                raise ValueError(
                    "state-space layers need 0 <= attn_layer_offset < "
                    "attn_layer_period > 1, num_layers a whole number of "
                    "periods, mamba_dt_rank, mamba_d_state, mamba_expand "
                    f"> 0 and mamba_d_conv > 1, got {self}"
                )
        if self.recurrent:
            for bad, mode in (
                (self.block_length, "generation by diffusion over blocks "
                 "(block_length > 0: the block step program)"),
                (self.latent, "latent attention (kv_lora_rank > 0)"),
                (self.attention_bias, "attention bias"),
                (self.zero_experts or self.router_input != "post_attn"
                 or self.moe_activation == "relu",
                 "zero-compute experts, a router on the layer's input or "
                 "ReGLU experts"),
            ):
                if bad:
                    raise ValueError(unsupported_for_recurrent(mode))
        if self.moe_activation not in ("silu", "relu", "relu2") or (
                self.router_input not in ("post_attn", "layer_input")):
            raise ValueError(
                "moe_activation is 'silu', 'relu' or 'relu2' and router_input "
                f"'post_attn' or 'layer_input', got {self.moe_activation!r}"
                f", {self.router_input!r}"
            )
        if (self.moe_activation != "silu"
                or self.router_input != "post_attn") and (
                not self.num_experts or self.latent):
            raise ValueError(
                "moe_activation and router_input are built for the "
                "per-head expert decoder (num_experts > 0, no latent "
                "attention)"
            )
        if self.windowed:
            P = self.global_layer_period
            if not (P > 1 and 0 <= self.global_layer_offset < P
                    and self.num_layers % P == 0):
                raise ValueError(
                    "window layers need 0 <= global_layer_offset < "
                    "global_layer_period > 1 and num_layers a whole "
                    f"number of periods, got {self}"
                )
            for bad, mode in (
                (self.block_length, "generation by diffusion over blocks "
                 "(block_length > 0: the block step program)"),
                (self.latent, "latent attention (kv_lora_rank > 0)"),
                (self.recurrent,
                 "state-space layers (attn_layer_period > 0)"),
            ):
                if bad:
                    raise ValueError(unsupported_for_window(mode))
        elif self.global_layer_period or self.rope_window_only:
            raise ValueError(
                "global_layer_period and rope_window_only need window "
                "layers (sliding_window > 0)"
            )
        if self.router_scoring not in ("softmax", "sigmoid") or (
                self.router_scoring != "softmax" and not self.num_experts):
            raise ValueError(
                "router_scoring is 'softmax' or 'sigmoid' and needs an "
                f"expert config, got {self.router_scoring!r}"
            )
        if self.dense_layers and not (
                (self.recurrent and self.num_experts
                 or self.latent and not self.shortcut_double_layer)
                and 0 < self.dense_layers < self.num_layers
                and self.intermediate_size > 0):
            raise ValueError(
                "dense_layers (leading dense layers) is built for the "
                "single latent block (kv_lora_rank > 0, no "
                "shortcut_double_layer) and for an expert config with "
                "state layers, and needs 0 < dense_layers < "
                f"num_layers, got {self.dense_layers} of {self.num_layers}"
            )
        if self.indexed or self.index_heads or self.index_head_dim:
            for bad, mode in (
                (not self.latent, "per-head K/V attention (the indexer "
                 "reads the query latent and selects rows of the latent "
                 "pool; kv_lora_rank = 0)"),
                (self.shortcut_double_layer,
                 "the shortcut-connected double layer"),
                (self.yarn or self.llama4_scaling_beta,
                 "RoPE scaling (rope_scaling_factor > 1, "
                 "llama4_scaling_beta)"),
                (not self.rope_interleaved,
                 "RoPE over split halves (rope_interleaved=False)"),
            ):
                if bad:
                    raise ValueError(unsupported_for_indexer(mode))
            if not (self.index_topk > 0 and self.index_heads > 0
                    and self.index_head_dim >= self.qk_rope_head_dim):
                raise ValueError(
                    "learned sparse attention needs index_topk, "
                    "index_heads > 0 and index_head_dim >= "
                    f"qk_rope_head_dim, got {self.index_topk}, "
                    f"{self.index_heads}, {self.index_head_dim}"
                )
        if not self.recurrent and not self.use_rope:
            raise ValueError(
                "use_rope=False (attention without a position term) is "
                "built for a config with state layers only "
                "(attn_layer_period > 0 or layer_types)"
            )


@dataclass(frozen=True)
class VisionConfig:
    """OryxViT-equivalent geometry: SigLIP-so400m-patch14 derived encoder
    that accepts arbitrary (h, w) patch grids (SURVEY.md §2 "OryxViT")."""

    hidden_size: int = 1152
    intermediate_size: int = 4304
    num_layers: int = 27
    num_heads: int = 16
    head_dim: int = 72
    patch_size: int = 14
    # Side of the square grid the learned position embedding is stored at;
    # arbitrary grids are bilinearly interpolated from this (384px / 14).
    base_grid: int = 27
    layer_norm_eps: float = 1e-6
    num_channels: int = 3
    # Cap on patches per image (see ops/packing.py buckets). 4096 covers a
    # ~896x896 image at patch 14; larger inputs are resized down to fit.
    max_patches_per_image: int = 4096


@dataclass(frozen=True)
class CompressorConfig:
    """Dynamic Compressor: region pooling + cross-attention + MLP projector
    into the LLM embedding space (SURVEY.md §2 "Dynamic Compressor")."""

    num_heads: int = 16
    # Hidden size is taken from VisionConfig; output dim from LLMConfig.
    # Downsample factors *per spatial side* available at runtime; area
    # compression is the square (1 -> 1x, 2 -> 4x, 4 -> 16x).
    side_factors: tuple[int, ...] = (1, 2, 4)
    projector_hidden_layers: int = 2  # mlp2x_gelu-equivalent


@dataclass(frozen=True)
class MeshConfig:
    """Logical device mesh. Axes: dp (pure data parallel across slices),
    fsdp (param/optimizer sharding, ZeRO-3-equivalent), tp (tensor parallel),
    sp (sequence/context parallel for ring attention). Sizes of 1 collapse an
    axis; product must equal the device count."""

    dp: int = 1
    fsdp: int = 1
    tp: int = 1
    sp: int = 1

    @property
    def num_devices(self) -> int:
        return self.dp * self.fsdp * self.tp * self.sp


@dataclass(frozen=True)
class TrainConfig:
    learning_rate: float = 1e-5
    projector_lr: float | None = None  # separate LR for projector, ref-style
    vision_lr: float | None = None
    warmup_ratio: float = 0.03
    lr_schedule: str = "cosine"
    weight_decay: float = 0.0
    adam_b1: float = 0.9
    adam_b2: float = 0.999
    adam_eps: float = 1e-8
    max_grad_norm: float = 1.0
    # Skip the optimizer update on steps whose loss or global grad norm
    # is non-finite (DeepSpeed skip-on-overflow analog for bf16 spikes):
    # params/moments keep their previous values, metrics gain a
    # "skipped" flag, and training continues. Off by default — skipping
    # can mask real divergence; turn on for long unattended pod runs.
    skip_nonfinite_steps: bool = False
    # With the guard on, abort after this many CONSECUTIVE skipped steps
    # — persistently poisoned data must kill the run, not silently no-op
    # a pod forever (Trainer.fit raises RuntimeError).
    max_consecutive_skipped: int = 20
    # Dtype for Adam's first moment ("float32" | "bfloat16"). bf16 halves
    # the m buffer (~1.4 GB at the 0.7B bench geometry) at negligible
    # quality cost — the variance buffer stays fp32 because its tiny
    # squared gradients need mantissa precision near eps, which bf16's
    # 7-bit mantissa can't represent.
    moment_dtype: str = "float32"
    global_batch_size: int = 128
    grad_accum_steps: int = 1
    num_train_steps: int = 1000
    # Length-grouped batching within modality groups (reference
    # LengthGroupedSampler): megabatches of this many batches sort by a
    # per-record length proxy before splitting; 0/1 disables.
    length_group_size: int = 8
    seed: int = 0
    remat: bool = True  # gradient checkpointing (see remat_policy)
    # What remat saves when enabled (utils/remat.py): "block" recomputes
    # the whole block in the backward (reference gradient_checkpointing
    # semantics, lowest memory); "attn" additionally saves the
    # flash-attention outputs + logsumexp so the backward skips the
    # forward-kernel recompute (measured +4% step time on v5e where the
    # saved ~2 B/token/layer/head-dim fits); "dots" saves all MXU
    # outputs — fastest backward, highest memory. To disable
    # checkpointing set remat=False ("none" is rejected here to keep one
    # knob authoritative).
    remat_policy: str = "block"

    def __post_init__(self):
        from oryx_tpu.utils.remat import POLICIES

        allowed = tuple(p for p in POLICIES if p != "none")
        if self.remat_policy not in allowed:
            raise ValueError(
                f"remat_policy={self.remat_policy!r}: use "
                f"{'|'.join(allowed)} (disable checkpointing with "
                "remat=False, not a policy)"
            )
        if self.moment_dtype not in ("float32", "bfloat16"):
            raise ValueError(
                f"moment_dtype={self.moment_dtype!r}: use float32|bfloat16"
            )
    # Sequence-chunk size for the memory-efficient CE loss (0 = dense
    # [B, T, V] logits). At 152k vocab the dense path needs ~10 GB fp32
    # logits per 8x2048 batch — chunking is what fits a 16 GB v5e.
    loss_chunk: int = 128
    # Which parameter groups train: "full", "projector_only" (stage-1
    # pretraining of the compressor/projector), "no_vision", "lora"
    # (adapters + projector; requires lora.enable).
    tune: str = "full"
    lora: "LoraConfig" = field(default_factory=lambda: LoraConfig())
    max_seq_len: int = 8192
    checkpoint_every: int = 500
    checkpoint_dir: str = "checkpoints"
    log_every: int = 10


@dataclass(frozen=True)
class LoraConfig:
    """LoRA adapter training (the reference train.py's `lora_enable`
    path). Adapters attach to the stacked decoder projections; base
    weights freeze (tune='lora' selects lora_a/lora_b + projector)."""

    enable: bool = False
    r: int = 16
    alpha: float = 32.0
    # PEFT-compatible rank-stabilized scaling: alpha/sqrt(r) vs alpha/r.
    use_rslora: bool = False
    targets: tuple = ("q_proj", "k_proj", "v_proj", "o_proj")

    @property
    def scaling(self) -> float:
        return self.alpha / (self.r**0.5 if self.use_rslora else self.r)


REMASKING_RULES = ("low_confidence_static", "low_confidence_dynamic")


@dataclass(frozen=True)
class GenerationConfig:
    max_new_tokens: int = 128
    temperature: float = 0.0  # 0 => greedy
    top_p: float = 1.0
    top_k: int = 0
    eos_token_id: int = 151645  # <|im_end|> for Qwen2-Instruct
    # Block-diffusion decoding (LLMConfig.block_length > 0), server side:
    # denoising forwards a block (0 = block_length) and the unmasking
    # rule. "low_confidence_static" unmasks an even ceil-spread share of
    # the still-masked positions each step, most confident first;
    # "low_confidence_dynamic" unmasks every masked position whose
    # confidence passes confidence_threshold, and always the best one.
    denoising_steps: int = 0
    remasking: str = "low_confidence_dynamic"
    confidence_threshold: float = 0.9

    def __post_init__(self):
        if self.remasking not in REMASKING_RULES:
            raise ValueError(
                f"remasking={self.remasking!r}: use "
                f"{'|'.join(REMASKING_RULES)}"
            )
        if self.denoising_steps < 0:
            raise ValueError(
                f"denoising_steps must be >= 0, got {self.denoising_steps}"
            )


@dataclass(frozen=True)
class OryxConfig:
    """Root config for the multimodal model + runtime."""

    llm: LLMConfig = field(default_factory=LLMConfig)
    # None = a text-only model: no vit/compressor parameter subtrees, and
    # the server answers a request that carries media with a 400.
    vision: VisionConfig | None = field(default_factory=VisionConfig)
    compressor: CompressorConfig = field(default_factory=CompressorConfig)
    mesh: MeshConfig = field(default_factory=MeshConfig)
    train: TrainConfig = field(default_factory=TrainConfig)
    generation: GenerationConfig = field(default_factory=GenerationConfig)
    # Compute dtype for matmuls/activations; params kept fp32 for training.
    dtype: str = "bfloat16"
    # "xla" (portable, CPU-testable) or "pallas" (TPU kernels).
    attn_impl: str = "xla"
    # Reference parity hook (SURVEY.md §3.4): optional text separator
    # (e.g. "\n") tokenized and spliced after EACH video frame's visual
    # span. None/"" = off — the plain contiguous-sentinel layout. See
    # models/splice.expand_video_sentinels.
    frame_separator: str | None = None

    def __post_init__(self):
        if self.llm.recurrent:
            m = self.mesh
            for bad, mode in (
                (m.num_devices > 1, f"a mesh ({m})"),
                (self.attn_impl not in ("xla", "pallas"),
                 f"attn_impl={self.attn_impl!r} (ring attention)"),
            ):
                if bad:
                    raise ValueError(unsupported_for_recurrent(mode))
        if self.llm.windowed:
            m = self.mesh
            for bad, mode in (
                (m.num_devices > 1, f"a mesh ({m}: tp, fsdp or dp)"),
                (self.attn_impl not in ("xla", "pallas"),
                 f"attn_impl={self.attn_impl!r} (ring attention)"),
            ):
                if bad:
                    raise ValueError(unsupported_for_window(mode))

    # ---- (de)serialization -------------------------------------------------

    def to_dict(self) -> dict[str, Any]:
        return dataclasses.asdict(self)

    def to_json(self) -> str:
        return json.dumps(self.to_dict(), indent=2)

    @classmethod
    def from_dict(cls, d: dict[str, Any]) -> "OryxConfig":
        def build(tp, val):
            if dataclasses.is_dataclass(tp) and isinstance(val, dict):
                fields = {f.name: f for f in dataclasses.fields(tp)}
                unknown = set(val) - set(fields)
                if unknown:
                    raise ValueError(
                        f"unknown config key(s) for {tp.__name__}: "
                        f"{sorted(unknown)}"
                    )
                kwargs = {}
                for k, v in val.items():
                    ftype = _FIELD_TYPES.get((tp, k), None)
                    if ftype is not None:
                        v = build(ftype, v)
                    elif isinstance(v, list):
                        v = tuple(v)
                    kwargs[k] = v
                return tp(**kwargs)
            return val

        return build(cls, d)

    @classmethod
    def from_json(cls, s: str) -> "OryxConfig":
        return cls.from_dict(json.loads(s))


# Nested dataclass field types for from_dict, derived from type hints so
# new nested-config fields are picked up automatically (string annotations
# under `from __future__ import annotations` resolve fine at module level).
# Collected recursively so arbitrarily nested configs (e.g.
# TrainConfig.lora) round-trip as dataclasses, not dicts.
def _collect_field_types(root):
    out, stack, seen = {}, [root], set()
    while stack:
        tp = stack.pop()
        if tp in seen:
            continue
        seen.add(tp)
        for name, hint in typing.get_type_hints(tp).items():
            # `VisionConfig | None`: the dataclass inside the union.
            for arg in typing.get_args(hint):
                if dataclasses.is_dataclass(arg):
                    hint = arg
            if dataclasses.is_dataclass(hint):
                out[(tp, name)] = hint
                stack.append(hint)
    return out


_FIELD_TYPES = _collect_field_types(OryxConfig)


# ---- Presets ---------------------------------------------------------------

def qwen2_7b() -> LLMConfig:
    """Qwen2-7B-Instruct geometry (Oryx-7B backbone)."""
    return LLMConfig()


def yi_34b() -> LLMConfig:
    """Yi-34B geometry (Oryx-34B backbone): Llama-class, no attention bias."""
    return LLMConfig(
        vocab_size=64000,
        hidden_size=7168,
        intermediate_size=20480,
        num_layers=60,
        num_heads=56,
        num_kv_heads=8,
        head_dim=128,
        rope_theta=5_000_000.0,
        rms_norm_eps=1e-5,
        max_position_embeddings=32768,
        attention_bias=False,
    )


def qwen2_5_7b() -> LLMConfig:
    """Qwen2.5-7B-Instruct geometry (Oryx-1.5-7B backbone).

    Tensor-identical to Qwen2-7B (same hidden/intermediate/layers/GQA/
    vocab/bias); kept as a named preset so Oryx-1.5 configs say what they
    mean and survive any future divergence.
    """
    return LLMConfig()


def qwen2_5_32b() -> LLMConfig:
    """Qwen2.5-32B-Instruct geometry (Oryx-1.5-32B backbone)."""
    return LLMConfig(
        vocab_size=152064,
        hidden_size=5120,
        intermediate_size=27648,
        num_layers=64,
        num_heads=40,
        num_kv_heads=8,
        head_dim=128,
        rope_theta=1_000_000.0,
        rms_norm_eps=1e-5,
        max_position_embeddings=32768,
        attention_bias=True,
    )


def tiny_llm(vocab_size: int = 512) -> LLMConfig:
    """Tiny geometry for tests (CPU-fast, GQA exercised)."""
    return LLMConfig(
        vocab_size=vocab_size,
        hidden_size=64,
        intermediate_size=128,
        num_layers=2,
        num_heads=4,
        num_kv_heads=2,
        head_dim=16,
        rope_theta=10000.0,
        max_position_embeddings=512,
    )


def tiny_vision() -> VisionConfig:
    return VisionConfig(
        hidden_size=48,
        intermediate_size=96,
        num_layers=2,
        num_heads=4,
        head_dim=12,
        patch_size=14,
        base_grid=8,
        max_patches_per_image=256,
    )


def oryx_7b() -> OryxConfig:
    return OryxConfig(llm=qwen2_7b())


def oryx_34b() -> OryxConfig:
    return OryxConfig(llm=yi_34b())


def oryx_1_5_7b() -> OryxConfig:
    """Oryx-1.5-7B: Qwen2.5-7B backbone, same vision/compressor stack."""
    return OryxConfig(llm=qwen2_5_7b())


def oryx_1_5_32b() -> OryxConfig:
    """Oryx-1.5-32B: Qwen2.5-32B backbone, same vision/compressor stack."""
    return OryxConfig(llm=qwen2_5_32b())


def sdar_30b_a3b() -> OryxConfig:
    """SDAR-30B-A3B-Chat (JetLM, `model_type: sdar_moe`): a text-only
    128-expert top-8 decoder that generates by diffusion over blocks.
    Widths from its config.json; q/k norm, block length 4, the mask
    token and the unmasking defaults are the family's convention (the
    config has no key for them)."""
    return OryxConfig(
        llm=LLMConfig(
            vocab_size=151936,
            hidden_size=2048,
            intermediate_size=6144,  # published, unused: every layer is sparse
            num_layers=48,
            num_heads=32,
            num_kv_heads=4,
            head_dim=128,
            rope_theta=1_000_000.0,
            rms_norm_eps=1e-6,
            max_position_embeddings=32768,
            attention_bias=False,
            qk_norm=True,
            num_experts=128,
            num_experts_per_tok=8,
            moe_intermediate_size=768,
            norm_topk_prob=True,
            block_length=4,
            mask_token_id=151669,
        ),
        vision=None,
    )


def longcat_flash_chat() -> OryxConfig:
    """LongCat-Flash-Chat (meituan-longcat, config.json): 28 shortcut-
    connected double layers of latent attention, 512 routed experts of
    width 2048 plus 256 identity zero-compute experts, 12 a token,
    weights 6 * p, not renormalised. Text-only."""
    return OryxConfig(
        llm=LLMConfig(
            vocab_size=131072,
            hidden_size=6144,
            intermediate_size=12288,
            num_layers=28,
            num_heads=64,
            num_kv_heads=1,  # unused: one shared latent a token
            head_dim=192,  # unused; a head's key is 128 + 64 wide
            rope_theta=10_000_000.0,
            rms_norm_eps=1e-5,
            max_position_embeddings=131072,
            attention_bias=False,
            num_experts=512,
            num_experts_per_tok=12,
            moe_intermediate_size=2048,
            norm_topk_prob=False,
            kv_lora_rank=512,
            q_lora_rank=1536,
            qk_nope_head_dim=128,
            qk_rope_head_dim=64,
            v_head_dim=128,
            mla_scale_q_lora=True,
            mla_scale_kv_lora=True,
            shortcut_double_layer=True,
            rope_interleaved=True,
            zero_experts=256,
            routed_scaling_factor=6.0,
            router_bias=True,
        ),
        vision=None,
        generation=GenerationConfig(eos_token_id=2),
    )


def longcat_flash_chat_ep32() -> OryxConfig:
    """One chip's share of LongCat-Flash-Chat where 32 chips share each
    layer: 16 of the 512 routed experts held (the router keeps its 768
    outputs and its 12 a token), attention and the dense FFNs whole,
    an eighth of the vocabulary (rows 0..16383). Tokens are sampled
    over the rows held here, so the end-of-sequence id is given as one
    that another chip holds (the first row past this chip's): no lane
    ends by it, as none would on a rank whose slice lacks it."""
    cfg = longcat_flash_chat()
    return dataclasses.replace(
        cfg,
        llm=dataclasses.replace(
            cfg.llm, vocab_size=16384, experts_held=(0, 16)),
        generation=dataclasses.replace(cfg.generation, eos_token_id=16384),
    )


def longcat_tiny() -> OryxConfig:
    """Tiny latent-attention double-layer expert decoder for tests:
    8 routed experts of which 4 are held, 4 zero-compute experts."""
    return OryxConfig(
        llm=LLMConfig(
            vocab_size=512,
            hidden_size=64,
            intermediate_size=128,
            num_layers=2,
            num_heads=4,
            num_kv_heads=1,
            head_dim=24,
            rope_theta=10000.0,
            rms_norm_eps=1e-5,
            max_position_embeddings=512,
            attention_bias=False,
            num_experts=8,
            num_experts_per_tok=3,
            moe_intermediate_size=32,
            norm_topk_prob=False,
            kv_lora_rank=32,
            q_lora_rank=48,
            qk_nope_head_dim=16,
            qk_rope_head_dim=8,
            v_head_dim=16,
            mla_scale_q_lora=True,
            mla_scale_kv_lora=True,
            shortcut_double_layer=True,
            rope_interleaved=True,
            zero_experts=4,
            routed_scaling_factor=6.0,
            router_bias=True,
            experts_held=(2, 4),
        ),
        vision=None,
        # Past the vocabulary, as the share's preset: seeded weights
        # would sample a real id once in 512 tokens.
        generation=GenerationConfig(eos_token_id=512),
        dtype="float32",
    )


def mistral_small_4() -> OryxConfig:
    """Mistral-Small-4-119B-2603's language model (mistralai,
    config.json, `model_type: mistral4`): 36 layers alike, each ONE
    latent attention (latent 256 + a shared roped key of 64, 32 heads of
    64 | 64 keys and 128 values) and then a shared SwiGLU of 2048 beside
    128 routed experts of 2048, 4 a token, renormalised; YaRN positions
    (factor 128 over 8,192) to 1,048,576. Text-only: the catalog's row
    holds no size of the vision encoder. What the keys do not settle
    (softmax routing, the score scale's m * m, the query's scale by
    position) is `LLMConfig.softmax_scale` / `query_position_scale` and
    the configuration file's `assumed`."""
    return OryxConfig(
        llm=LLMConfig(
            vocab_size=131072,
            hidden_size=4096,
            intermediate_size=12288,  # published, unused: no dense layer
            num_layers=36,
            num_heads=32,
            num_kv_heads=32,  # published, unused: one shared latent a token
            head_dim=128,  # published qk_head_dim = 64 + 64
            rope_theta=10_000.0,
            rms_norm_eps=1e-6,
            max_position_embeddings=1_048_576,
            attention_bias=False,
            num_experts=128,
            num_experts_per_tok=4,
            moe_intermediate_size=2048,
            norm_topk_prob=True,
            kv_lora_rank=256,
            q_lora_rank=1024,
            qk_nope_head_dim=64,
            qk_rope_head_dim=64,
            v_head_dim=128,
            rope_interleaved=True,
            routed_scaling_factor=1.0,
            n_shared_experts=1,
            rope_scaling_factor=128.0,
            rope_original_max_position=8192,
            rope_beta_fast=32.0,
            rope_beta_slow=1.0,
            rope_mscale=1.0,
            rope_mscale_all_dim=1.0,
            llama4_scaling_beta=0.1,
        ),
        vision=None,
        generation=GenerationConfig(eos_token_id=2),
    )


def mistral_small_4_ep4() -> OryxConfig:
    """One chip's share of Mistral-Small-4 where 4 chips share each
    layer: 32 of the 128 routed experts held (the router keeps its 128
    outputs and its 4 a token), attention and the shared expert whole, a
    quarter of the vocabulary (rows 0..32767). The end-of-sequence id is
    the first row another chip holds, as in `longcat_flash_chat_ep32`."""
    cfg = mistral_small_4()
    return dataclasses.replace(
        cfg,
        llm=dataclasses.replace(
            cfg.llm, vocab_size=32768, experts_held=(0, 32)),
        generation=dataclasses.replace(cfg.generation, eos_token_id=32768),
    )


def mistral4_tiny() -> OryxConfig:
    """Tiny single-latent-block expert decoder for tests: 8 routed
    experts of which 4 are held, one shared expert, YaRN over an
    original length of 16 so that a prompt of a hundred tokens crosses
    several steps of the query's scale by position."""
    return OryxConfig(
        llm=LLMConfig(
            vocab_size=512,
            hidden_size=64,
            intermediate_size=128,  # unused
            num_layers=2,
            num_heads=4,
            num_kv_heads=4,
            head_dim=24,
            rope_theta=10000.0,
            rms_norm_eps=1e-6,
            max_position_embeddings=2048,
            attention_bias=False,
            num_experts=8,
            num_experts_per_tok=2,
            moe_intermediate_size=32,
            norm_topk_prob=True,
            kv_lora_rank=32,
            q_lora_rank=48,
            qk_nope_head_dim=16,
            qk_rope_head_dim=8,
            v_head_dim=16,
            rope_interleaved=True,
            experts_held=(2, 4),
            n_shared_experts=1,
            rope_scaling_factor=128.0,
            rope_original_max_position=16,
            rope_beta_fast=32.0,
            rope_beta_slow=1.0,
            rope_mscale=1.0,
            rope_mscale_all_dim=1.0,
            llama4_scaling_beta=0.1,
        ),
        vision=None,
        generation=GenerationConfig(eos_token_id=512),
        dtype="float32",
    )


def glm5() -> OryxConfig:
    """GLM-5's language model (zai-org, config.json, `model_type:
    glm_moe_dsa`, 744B-A40B): 78 layers, the first 3 dense (SwiGLU of
    12,288), the rest a shared SwiGLU of 2048 beside 256 routed experts
    of 2048, 8 a token, scored by SIGMOID, selected with a bias,
    renormalised and scaled by 2.5. Every layer's attention is latent
    (latent 512 + a shared roped key of 64, 64 heads of 192 | 64 keys
    and 256 values) and reads the 2,048 keys an indexer (32 heads of
    128) selected a query. RoPE theta 1e6 over interleaved pairs, no
    scaling. The multi-token-prediction module
    (`num_nextn_predict_layers` 1) is not built: speculation is refused
    over a latent pool. What the keys do not settle is the
    configuration file's `assumed`."""
    return OryxConfig(
        llm=LLMConfig(
            vocab_size=154880,
            hidden_size=6144,
            intermediate_size=12288,
            num_layers=78,
            num_heads=64,
            num_kv_heads=64,  # published, unused: one shared latent a token
            head_dim=64,  # published, unused: heads are 192 + 64 | 256
            rope_theta=1_000_000.0,
            rms_norm_eps=1e-5,
            max_position_embeddings=202752,
            attention_bias=False,
            num_experts=256,
            num_experts_per_tok=8,
            moe_intermediate_size=2048,
            norm_topk_prob=True,
            kv_lora_rank=512,
            q_lora_rank=2048,
            qk_nope_head_dim=192,
            qk_rope_head_dim=64,
            v_head_dim=256,
            rope_interleaved=True,
            routed_scaling_factor=2.5,
            router_bias=True,
            router_scoring="sigmoid",
            n_shared_experts=1,
            dense_layers=3,
            index_heads=32,
            index_head_dim=128,
            index_topk=2048,
        ),
        vision=None,
        generation=GenerationConfig(eos_token_id=154820),
    )


def glm5_ep16() -> OryxConfig:
    """One chip's share of GLM-5 where 16 chips share each layer: 16 of
    the 256 routed experts held (the router keeps its 256 outputs and
    its 8 a token), attention, the indexer and the shared expert whole,
    an eighth of the vocabulary (rows 0..19359), ONE leading dense layer
    (they count once; `num_layers` is the configuration file's). The
    end-of-sequence id is the first row another chip holds, as in
    `mistral_small_4_ep4`."""
    cfg = glm5()
    return dataclasses.replace(
        cfg,
        llm=dataclasses.replace(
            cfg.llm, vocab_size=19360, experts_held=(0, 16),
            dense_layers=1),
        generation=dataclasses.replace(cfg.generation, eos_token_id=19360),
    )


def glm5_tiny() -> OryxConfig:
    """Tiny learned-sparse-attention decoder for tests: 1 dense + 3
    expert layers, an indexer of 3 heads of 24 that keeps the 16 best
    keys, 8 routed experts (sigmoid, bias, scaled) of which 4 are held
    and one shared; no two widths equal. A page of 8 divides the
    top-k."""
    return OryxConfig(
        llm=LLMConfig(
            vocab_size=512,
            hidden_size=64,
            intermediate_size=96,
            num_layers=4,
            num_heads=4,
            num_kv_heads=4,
            head_dim=24,
            rope_theta=10000.0,
            rms_norm_eps=1e-5,
            max_position_embeddings=2048,
            attention_bias=False,
            num_experts=8,
            num_experts_per_tok=2,
            moe_intermediate_size=32,
            norm_topk_prob=True,
            kv_lora_rank=40,
            q_lora_rank=48,
            qk_nope_head_dim=16,
            qk_rope_head_dim=8,
            v_head_dim=20,
            rope_interleaved=True,
            routed_scaling_factor=2.5,
            router_bias=True,
            router_scoring="sigmoid",
            experts_held=(2, 4),
            n_shared_experts=1,
            dense_layers=1,
            index_heads=3,
            index_head_dim=24,
            index_topk=16,
        ),
        vision=None,
        generation=GenerationConfig(eos_token_id=512),
        dtype="float32",
    )


def jamba2_3b() -> OryxConfig:
    """AI21-Jamba2-3B (ai21labs, config.json, `model_type: jamba`): 28
    layers in two periods of 14, layers 7 and 21 attention (20 query
    heads of 128 over ONE key/value head, no position term), the other
    26 Mamba-1 mixers (5120 channels, state 16, conv 4, dt rank 160),
    every layer with a dense SwiGLU of 8192 (`num_experts` 1: no
    router), tied embedding of 65,536. Text-only. The layer order (i %
    14 == 7 attends) is the family's modelling code, as the
    configuration file's `assumed` says."""
    return OryxConfig(
        llm=LLMConfig(
            vocab_size=65536,
            hidden_size=2560,
            intermediate_size=8192,
            num_layers=28,
            num_heads=20,
            num_kv_heads=1,
            head_dim=128,
            rms_norm_eps=1e-6,
            max_position_embeddings=262144,
            tie_word_embeddings=True,
            attention_bias=False,
            attn_layer_period=14,
            attn_layer_offset=7,
            mamba_d_state=16,
            mamba_d_conv=4,
            mamba_expand=2,
            mamba_dt_rank=160,
            mamba_conv_bias=True,
            mamba_proj_bias=False,
            use_rope=False,
        ),
        vision=None,
        # Past the vocabulary: seeded weights would sample a real id
        # once in 65,536 tokens and end a request the traffic sized.
        generation=GenerationConfig(eos_token_id=65536),
    )


def jamba_tiny() -> OryxConfig:
    """Tiny state-space hybrid for tests: two periods of (2 Mamba, 1
    attention, 1 Mamba), one key/value head, no positions."""
    return OryxConfig(
        llm=LLMConfig(
            vocab_size=512,
            hidden_size=64,
            intermediate_size=128,
            num_layers=8,
            num_heads=4,
            num_kv_heads=1,
            head_dim=16,
            rms_norm_eps=1e-6,
            max_position_embeddings=2048,
            tie_word_embeddings=True,
            attention_bias=False,
            attn_layer_period=4,
            attn_layer_offset=2,
            mamba_d_state=8,
            mamba_d_conv=4,
            mamba_expand=2,
            mamba_dt_rank=8,
            use_rope=False,
        ),
        vision=None,
        generation=GenerationConfig(eos_token_id=512),
        dtype="float32",
    )


# LFM2-24B-A2B's published `layer_types` (40 entries): two gated short
# convolutions, then `A c c c` nine times, then `A c`.
_LFM2_LAYER_TYPES = ("conv", "conv") + 9 * (
    "full_attention", "conv", "conv", "conv") + ("full_attention", "conv")


def lfm2_24b_a2b() -> OryxConfig:
    """LFM2-24B-A2B (LiquidAI, config.json, `model_type: lfm2_moe`): 40
    layers by the published list, 30 gated short convolutions (3 taps,
    no bias: two rows of 2,048 of state a lane) and 10 GQA attention
    layers (32 query heads of 64 over 8 key/value heads, RMSNorm on q
    and k, RoPE at theta 1e6); layers 0 and 1 keep a dense SwiGLU of
    11,776, the other 38 have 64 experts of 1,536, 4 a token, behind a
    sigmoid router with a selection bias and weights divided by their
    sum + 1e-6. Tied embedding of 65,536. Text-only. 23.84 B
    parameters. What the keys do not settle (head size 64, the order
    of the conv's three thirds) is under `assumed` in the benchmark's
    configuration file."""
    return OryxConfig(
        llm=LLMConfig(
            vocab_size=65536,
            hidden_size=2048,
            intermediate_size=11776,
            num_layers=40,
            num_heads=32,
            num_kv_heads=8,
            head_dim=64,
            rope_theta=1_000_000.0,
            rms_norm_eps=1e-5,
            max_position_embeddings=128000,
            tie_word_embeddings=True,
            attention_bias=False,
            qk_norm=True,
            layer_types=_LFM2_LAYER_TYPES,
            conv_L_cache=3,
            num_experts=64,
            num_experts_per_tok=4,
            moe_intermediate_size=1536,
            norm_topk_prob=True,
            norm_topk_eps=1e-6,
            router_scoring="sigmoid",
            router_bias=True,
            routed_scaling_factor=1.0,
            dense_layers=2,
        ),
        vision=None,
        # Past the vocabulary: seeded weights would sample a real id
        # once in 65,536 tokens and end a request the traffic sized.
        generation=GenerationConfig(eos_token_id=65536),
    )


def lfm2_tiny() -> OryxConfig:
    """Tiny LFM2 for tests: the published 40-entry layer list at width
    64 (two dense conv layers, then experts: 8 of width 32, 2 a token),
    2 key/value heads of 16. `dataclasses.replace(llm, num_layers=10)`
    is the benchmark's depth cut."""
    return OryxConfig(
        llm=LLMConfig(
            vocab_size=512,
            hidden_size=64,
            intermediate_size=96,
            num_layers=40,
            num_heads=4,
            num_kv_heads=2,
            head_dim=16,
            rope_theta=1_000_000.0,
            rms_norm_eps=1e-5,
            max_position_embeddings=2048,
            tie_word_embeddings=True,
            attention_bias=False,
            qk_norm=True,
            layer_types=_LFM2_LAYER_TYPES,
            conv_L_cache=3,
            num_experts=8,
            num_experts_per_tok=2,
            moe_intermediate_size=32,
            norm_topk_prob=True,
            norm_topk_eps=1e-6,
            router_scoring="sigmoid",
            router_bias=True,
            dense_layers=2,
        ),
        vision=None,
        generation=GenerationConfig(eos_token_id=512),
        dtype="float32",
    )


# NVIDIA-Nemotron-3-Super-120B-A12B's published `hybrid_override_pattern`
# (88 characters: 40 `M`, 40 `E`, 8 `*`).
_NEMOTRON3_SUPER_PATTERN = (
    "MEMEMEM*EMEMEMEM*EMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*"
    "EMEMEMEMEM*EMEMEMEM*EMEMEMEME")


def nemotron3_super() -> OryxConfig:
    """NVIDIA-Nemotron-3-Super-120B-A12B-BF16 (nvidia, config.json,
    `model_type: nemotron_h`): 88 layers of ONE sublayer each by the
    published pattern, 40 Mamba-2 mixers (128 heads of 64, 8 groups,
    state 128, conv 4, chunk 128), 8 GQA attention layers (32 query
    heads of 128 over 2 key/value heads, no position term) and 40
    latent expert layers (512 experts of 2,688 in a 1,024-wide latent,
    22 a token behind a sigmoid router with a selection bias, weights
    over their sum times 5, non-gated relu^2, one shared expert of
    5,376 on the hidden state). Untied head of 131,072. Text-only.
    120.67 B parameters; the multi-token-prediction module is not
    built. What the keys do not settle (no position term, the router on
    the hidden state) is under `assumed` in the benchmark's
    configuration file."""
    return OryxConfig(
        llm=LLMConfig(
            vocab_size=131072,
            hidden_size=4096,
            intermediate_size=2688,
            num_layers=88,
            num_heads=32,
            num_kv_heads=2,
            head_dim=128,
            rope_theta=10000.0,
            rms_norm_eps=1e-5,
            max_position_embeddings=262144,
            tie_word_embeddings=False,
            attention_bias=False,
            hybrid_override_pattern=_NEMOTRON3_SUPER_PATTERN,
            mamba_num_heads=128,
            mamba_head_dim=64,
            mamba_n_groups=8,
            mamba_d_state=128,
            mamba_d_conv=4,
            mamba_chunk_size=128,
            mamba_conv_bias=True,
            use_rope=False,
            num_experts=512,
            num_experts_per_tok=22,
            moe_intermediate_size=2688,
            moe_latent_size=1024,
            moe_shared_expert_intermediate_size=5376,
            n_shared_experts=1,
            moe_activation="relu2",
            norm_topk_prob=True,
            router_scoring="sigmoid",
            router_bias=True,
            routed_scaling_factor=5.0,
        ),
        vision=None,
        # Past the vocabulary: seeded weights would sample a real id
        # once in 131,072 tokens and end a request the traffic sized.
        generation=GenerationConfig(eos_token_id=131072),
    )


def nemotron3_super_ep4() -> OryxConfig:
    """`nemotron3_super` as ONE of the 4 chips that share each layer of
    a pipeline stage holds it: experts 0..127 of the 512 (the router
    keeps its 512 outputs and its 22 a token; what the 384 absent
    experts would add is left out before `W_up`), the mixers, the
    attention, the latent projections and the shared expert whole, and
    32,768 of the 131,072 vocabulary rows. The benchmark's depth cut is
    `dataclasses.replace(llm, num_layers=11)`: the first stage of
    eight, `MEMEMEM*EME`."""
    cfg = nemotron3_super()
    return dataclasses.replace(
        cfg,
        llm=dataclasses.replace(
            cfg.llm, vocab_size=32768, experts_held=(0, 128)),
        generation=GenerationConfig(eos_token_id=32768),
    )


def nemotron3_tiny() -> OryxConfig:
    """Tiny Nemotron-H for tests: the published pattern's first 11
    layers (`MEMEMEM*EME`) at width 64: 4 Mamba-2 heads of 32 in 2
    groups, state 16, chunk 8; 8 latent experts of width 48 in a
    32-wide latent, 3 a token, experts 2..5 held; a shared expert of
    96; 2 key/value heads of 16."""
    return OryxConfig(
        llm=LLMConfig(
            vocab_size=512,
            hidden_size=64,
            intermediate_size=48,
            num_layers=11,
            num_heads=4,
            num_kv_heads=2,
            head_dim=16,
            rms_norm_eps=1e-5,
            max_position_embeddings=2048,
            tie_word_embeddings=False,
            attention_bias=False,
            hybrid_override_pattern=_NEMOTRON3_SUPER_PATTERN,
            mamba_num_heads=4,
            mamba_head_dim=32,
            mamba_n_groups=2,
            mamba_d_state=16,
            mamba_d_conv=4,
            mamba_chunk_size=8,
            use_rope=False,
            num_experts=8,
            num_experts_per_tok=3,
            moe_intermediate_size=48,
            moe_latent_size=32,
            moe_shared_expert_intermediate_size=96,
            n_shared_experts=1,
            moe_activation="relu2",
            norm_topk_prob=True,
            router_scoring="sigmoid",
            router_bias=True,
            routed_scaling_factor=5.0,
            experts_held=(2, 4),
        ),
        vision=None,
        generation=GenerationConfig(eos_token_id=512),
        dtype="float32",
    )


def smallthinker_21b() -> OryxConfig:
    """SmallThinker-21BA3B-Instruct (PowerInfer, config.json): 52
    layers in periods of 4, layer i GLOBAL iff i % 4 == 0 (no position
    term, the whole context), the other three WINDOW layers (RoPE,
    theta 1.5e6, the last 4,096 positions); 28 query heads of 128 over
    4 key/value heads, no bias; every layer 64 ReGLU experts of 768,
    top 6, softmax then renormalised, the router fed the layer's raw
    input; an untied head of 151,936. Text-only. The two conventions
    the keys do not settle (the router's input, no attention bias) are
    the configuration file's `assumed`."""
    return OryxConfig(
        llm=LLMConfig(
            vocab_size=151936,
            hidden_size=2560,
            intermediate_size=768,  # unused: every layer is sparse
            num_layers=52,
            num_heads=28,
            num_kv_heads=4,
            head_dim=128,
            rope_theta=1_500_000.0,
            rms_norm_eps=1e-6,
            max_position_embeddings=16384,
            tie_word_embeddings=False,
            attention_bias=False,
            num_experts=64,
            num_experts_per_tok=6,
            moe_intermediate_size=768,
            norm_topk_prob=True,
            sliding_window=4096,
            global_layer_period=4,
            global_layer_offset=0,
            rope_window_only=True,
            moe_activation="relu",
            router_input="layer_input",
        ),
        vision=None,
        # Past the vocabulary: seeded weights would sample a real id
        # once in 151,936 tokens and end a request the traffic sized.
        generation=GenerationConfig(eos_token_id=151936),
    )


def smallthinker_tiny() -> OryxConfig:
    """Tiny window / global hybrid for tests: two periods of (global,
    window, window, window), window 32, 8 ReGLU experts top 2."""
    return OryxConfig(
        llm=LLMConfig(
            vocab_size=512,
            hidden_size=64,
            intermediate_size=32,
            num_layers=8,
            num_heads=4,
            num_kv_heads=2,
            head_dim=16,
            rope_theta=10_000.0,
            rms_norm_eps=1e-6,
            max_position_embeddings=2048,
            tie_word_embeddings=False,
            attention_bias=False,
            num_experts=8,
            num_experts_per_tok=2,
            moe_intermediate_size=32,
            norm_topk_prob=True,
            sliding_window=32,
            global_layer_period=4,
            global_layer_offset=0,
            rope_window_only=True,
            moe_activation="relu",
            router_input="layer_input",
        ),
        vision=None,
        generation=GenerationConfig(eos_token_id=512),
        dtype="float32",
    )


def sdar_tiny() -> OryxConfig:
    """Tiny block-diffusion expert decoder for tests (CPU-fast)."""
    return OryxConfig(
        llm=dataclasses.replace(
            tiny_llm(),
            attention_bias=False,
            qk_norm=True,
            num_experts=8,
            num_experts_per_tok=2,
            moe_intermediate_size=32,
            block_length=4,
            mask_token_id=511,
        ),
        vision=None,
        dtype="float32",
    )


def oryx_tiny() -> OryxConfig:
    return OryxConfig(
        llm=tiny_llm(),
        vision=tiny_vision(),
        compressor=CompressorConfig(num_heads=4),
        dtype="float32",
    )
