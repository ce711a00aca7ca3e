"""Bytes and operations of a latent-attention (MLA) decoder with a
shortcut-connected expert layer of which a chip holds a share, from
shapes and from the program's counters: what the algorithm needs, kept
with the benchmark so that a roofline share is always worked out the
same way. `c` is the configuration file (the source's own key names,
plus `experts_held`).

Absorbed decode over the paged latents: a cached token is one row of
`kv_lora_rank + qk_rope_head_dim` values a cache layer (two cache
layers a model layer), read once a step for scores and values both.
The pad that fills the row to whole lane tiles is NOT counted (the
kernel copies it, the algorithm does not need it), nor is the rest of a
lane's last page: a share that counts too little reads low, never over
100 %.
"""

from __future__ import annotations


def latent_row_values(c: dict) -> int:
    return c["kv_lora_rank"] + c["qk_rope_head_dim"]


def cache_layers(c: dict) -> int:
    return 2 * c["num_layers"]


def latent_decode_bytes(c: dict, *, kv_tokens: float,
                        dtype_bytes: int = 2) -> float:
    """Latent bytes the decode steps had to read. kv_tokens: the sum
    over steps of the live lanes' lengths (`decode_kv_tokens_total`:
    what one cache layer's walk reads)."""
    return float(kv_tokens * cache_layers(c) * latent_row_values(c)
                 * dtype_bytes)


def latent_decode_flops(c: dict, *, kv_tokens: float) -> float:
    """Operations of the absorbed products over the same tokens: every
    head scores a token over the whole row (576) and sums its latent
    (512): 2 x (576 + 512) a (token, head)."""
    per = 2 * (latent_row_values(c) + c["kv_lora_rank"])
    return float(kv_tokens * cache_layers(c) * c["num_attention_heads"] * per)


def attention_params(c: dict) -> int:
    """One sublayer: Wq_a, Wq_b, Wkv_a, Wkv_b, Wo and its norms."""
    H, Hq = c["hidden_size"], c["num_attention_heads"]
    Rq, R = c["q_lora_rank"], c["kv_lora_rank"]
    dn, dr, dv = (c["qk_nope_head_dim"], c["qk_rope_head_dim"],
                  c["v_head_dim"])
    return (H * Rq + Rq * Hq * (dn + dr) + H * (R + dr)
            + R * Hq * (dn + dv) + Hq * dv * H + 2 * H + Rq + R)


def dense_ffn_params(c: dict) -> int:
    return 3 * c["hidden_size"] * c["ffn_hidden_size"]


def expert_params(c: dict) -> int:
    return 3 * c["hidden_size"] * c["expert_ffn_hidden_size"]


def router_outputs(c: dict) -> int:
    return c["n_routed_experts"] + c["zero_expert_num"]


def decode_weight_bytes(c: dict, *, steps: float, held_hit: float,
                        dtype_bytes: int = 2) -> float:
    """Weight bytes `steps` decode steps had to read: every layer's two
    attention sublayers and two dense FFNs, its router (float32) with
    the selection bias, the final norm and the head's rows held here,
    once a step whatever the lanes; and the kernels of every HELD
    expert that took a row, once each (`held_hit`, summed over the
    steps' layer-forwards: `moe_held_experts_hit_total`)."""
    L, H = c["num_layers"], c["hidden_size"]
    per_step = L * 2 * (attention_params(c) + dense_ffn_params(c)) \
        * dtype_bytes
    per_step += L * (H + 1) * router_outputs(c) * 4
    per_step += (H + H * c["vocab_size"]) * dtype_bytes
    return float(steps * per_step
                 + held_hit * expert_params(c) * dtype_bytes)
