"""Bytes and operations of a latent-attention (MLA) decoder whose layer
is ONE latent attention and then a shared expert beside routed experts
of which a chip holds a share (Mistral-Small-4), from shapes and from
the program's counters: what the algorithm needs, kept with the
benchmark so that a roofline share is always worked out the same way.
`c` is the configuration file (the source's own key names, plus
`experts_held`). costs_mla.py is the double layer's: it counts two cache
layers and two dense FFNs a model layer and zero-compute experts under
LongCat's key names.

Absorbed decode over the paged latents: a cached token is one row of
`kv_lora_rank + qk_rope_head_dim` values a layer (ONE cache layer a
model layer), read once a step for scores and values both. The pad that
fills the row to whole lane tiles is NOT counted (the kernel copies it,
the algorithm does not need it), nor is the rest of a lane's last page:
a share that counts too little reads low, never over 100 %.

Prefill (the expanded form): a chunk's real tokens go through every
matmul of the layer once; attention is taken over the causal query-key
PAIRS the chunk needs (`prefill_attn_pairs_total`), and the latents of
the chunk's live prefix are up-projected to per-head keys and values
once a chunk (`prefill_live_positions_total`). Padding of a chunk, the
part of a block table past the live prefix and the masked half of a
diagonal tile are work the program does and the algorithm does not
need: they read as a lower share.
"""

from __future__ import annotations


def latent_row_values(c: dict) -> int:
    return c["kv_lora_rank"] + c["qk_rope_head_dim"]


def cache_layers(c: dict) -> int:
    return c["num_hidden_layers"]


def latent_decode_bytes(c: dict, *, kv_tokens: float,
                        dtype_bytes: int = 2) -> float:
    """Latent bytes the decode steps had to read. kv_tokens: the sum
    over steps of the live lanes' lengths (`decode_kv_tokens_total`:
    what one cache layer's walk reads)."""
    return float(kv_tokens * cache_layers(c) * latent_row_values(c)
                 * dtype_bytes)


def latent_decode_flops(c: dict, *, kv_tokens: float) -> float:
    """Operations of the absorbed products over the same tokens: every
    head scores a token over the whole row (320) and sums its latent
    (256): 2 x (320 + 256) a (token, head)."""
    per = 2 * (latent_row_values(c) + c["kv_lora_rank"])
    return float(kv_tokens * cache_layers(c) * c["num_attention_heads"] * per)


def attention_params(c: dict) -> int:
    """One layer's attention: Wq_a, Wq_b, Wkv_a, Wkv_b, Wo, its two
    latent norms and the layer's two norms."""
    H, Hq = c["hidden_size"], c["num_attention_heads"]
    Rq, R = c["q_lora_rank"], c["kv_lora_rank"]
    dn, dr, dv = (c["qk_nope_head_dim"], c["qk_rope_head_dim"],
                  c["v_head_dim"])
    return (H * Rq + Rq * Hq * (dn + dr) + H * (R + dr)
            + R * Hq * (dn + dv) + Hq * dv * H + 2 * H + Rq + R)


def expert_params(c: dict) -> int:
    return 3 * c["hidden_size"] * c["moe_intermediate_size"]


def shared_params(c: dict) -> int:
    return c["n_shared_experts"] * expert_params(c)


def decode_weight_bytes(c: dict, *, steps: float, held_hit: float,
                        dtype_bytes: int = 2) -> float:
    """Weight bytes `steps` decode steps had to read: every layer's
    attention and shared expert and its router (float32), the final norm
    and the head's rows held here, once a step whatever the lanes; and
    the kernels of every HELD expert that took a row, once each
    (`held_hit`, summed over the steps' layer-forwards:
    `moe_held_experts_hit_total`)."""
    L, H = c["num_hidden_layers"], c["hidden_size"]
    per_step = L * (attention_params(c) + shared_params(c)) * dtype_bytes
    per_step += L * H * c["n_routed_experts"] * 4
    per_step += (H + H * c["vocab_size"]) * dtype_bytes
    return float(steps * per_step
                 + held_hit * expert_params(c) * dtype_bytes)


def held_expert_bytes(c: dict, *, held_hit: float,
                      dtype_bytes: int = 2) -> float:
    """Kernel bytes the grouped products had to read: gate, up and down
    of every HELD expert that took a row, once a layer-forward
    (`held_hit`: `moe_held_experts_hit_total` of the decode steps plus
    `moe_prefill_held_experts_hit_total` of the prefill chunks). The
    rows in and out are not counted: at 32 rows an expert they are a
    hundredth of its kernels."""
    return float(held_hit * expert_params(c) * dtype_bytes)


def prefill_flops(c: dict, *, tokens: float, attn_pairs: float,
                  live_positions: float, held_share: float) -> float:
    """Operations the prefill chunks needed. tokens: real prompt tokens
    prefilled; attn_pairs: causal query-key pairs; live_positions: the
    chunks' live prefixes, summed (each up-projected once a chunk);
    held_share: the share of a token's `num_experts_per_tok` picks that
    landed on an expert held here (`moe_prefill_held_rows_total /
    moe_prefill_pairs_total`; experts_held / n_routed_experts is what
    uniform routing gives)."""
    L, H, Hq = (c["num_hidden_layers"], c["hidden_size"],
                c["num_attention_heads"])
    Rq, R = c["q_lora_rank"], c["kv_lora_rank"]
    dn, dr, dv = (c["qk_nope_head_dim"], c["qk_rope_head_dim"],
                  c["v_head_dim"])
    per_token = (
        H * Rq + Rq * Hq * (dn + dr) + H * (R + dr) + Hq * dv * H  # MLA
        + H * c["n_routed_experts"]  # router
        + shared_params(c)
        + c["num_experts_per_tok"] * held_share * expert_params(c)
    )
    per_pair = Hq * (dn + dr + dv)  # scores and values, a head
    per_live = R * Hq * (dn + dv)  # latents -> keys and values
    return float(2 * L * (tokens * per_token + attn_pairs * per_pair
                          + live_positions * per_live))
