"""Bytes and operations of a latent-attention (MLA) decoder with learned
sparse attention (an indexer and the top `index_topk` keys a query),
sigmoid-scored experts of which a chip holds a share, a shared expert
and leading dense layers (GLM-5), from shapes and from the program's
counters: what the ALGORITHM needs, kept with the benchmark so that a
roofline share is always worked out the same way. `c` is the
configuration file (the source's own key names, plus `experts_held`).

Decode: a step scores EVERY cached index key of a live lane
(`decode_kv_tokens_total`, a cache layer each: `index_head_dim` values)
and reads the latent rows it selected (`decode_selected_tokens_total`:
min(length, index_topk) a row; `kv_lora_rank + qk_rope_head_dim`
values). The pad of a latent row to whole lane tiles, a row that was
not selected and the rest of a table are not counted: a share that
counts too little reads low, never over 100 %.

Prefill: a chunk's real tokens go through every matmul of their layer
once (the dense layer's FFN or the shared expert and the held share of
a token's picks, the indexer's three projections); the indexer scores
the pairs `prefill_index_pairs_total` (a query that sees more keys than
it keeps scores them all: 2 x index_head_dim x index_n_heads a pair)
and attention reads the pairs `prefill_selected_pairs_total`
(min(visible, index_topk) a query), each once a layer, at the expanded
form's cost a pair (per-head keys of qk_nope + qk_rope, values of
v_head_dim). The up-projection of the keys is NOT counted: a masked
prefill expands every visible key once a chunk, a gathered one every
selected key once a query, and which is needed is the program's
choice; the chunk's head is one row. So the share reads low, honestly.
"""

from __future__ import annotations


def layers(c: dict) -> tuple[int, int]:
    """(leading dense layers, expert layers) of the file's depth."""
    dense = c["first_k_dense_replace"]
    return dense, c["num_hidden_layers"] - dense


def latent_row_values(c: dict) -> int:
    return c["kv_lora_rank"] + c["qk_rope_head_dim"]


def index_key_bytes(c: dict, *, kv_tokens: float,
                    dtype_bytes: int = 2) -> float:
    """Index-key bytes the decode steps had to read. kv_tokens: the sum
    over steps of the live lanes' lengths (`decode_kv_tokens_total`:
    what one cache layer's scoring reads)."""
    return float(kv_tokens * c["num_hidden_layers"] * c["index_head_dim"]
                 * dtype_bytes)


def selected_row_bytes(c: dict, *, selected_tokens: float,
                       dtype_bytes: int = 2) -> float:
    """Latent bytes the decode steps had to read: the selected rows
    alone (`decode_selected_tokens_total`, a cache layer each)."""
    return float(selected_tokens * c["num_hidden_layers"]
                 * latent_row_values(c) * dtype_bytes)


def indexer_params(c: dict) -> int:
    """One layer's indexer: WqI_b, WkI, the heads' weights, the key's
    LayerNorm."""
    Hi, Di = c["index_n_heads"], c["index_head_dim"]
    return (c["q_lora_rank"] * Hi * Di + c["hidden_size"] * Di
            + c["hidden_size"] * Hi + 2 * Di)


def attention_params(c: dict) -> int:
    """One layer's attention without its indexer: Wq_a, Wq_b, Wkv_a,
    Wkv_b, Wo, its two latent norms and the layer's two norms."""
    H, Hq = c["hidden_size"], c["num_attention_heads"]
    Rq, R = c["q_lora_rank"], c["kv_lora_rank"]
    dn, dr, dv = (c["qk_nope_head_dim"], c["qk_rope_head_dim"],
                  c["v_head_dim"])
    return (H * Rq + Rq * Hq * (dn + dr) + H * (R + dr)
            + R * Hq * (dn + dv) + Hq * dv * H + 2 * H + Rq + R)


def dense_ffn_params(c: dict) -> int:
    return 3 * c["hidden_size"] * c["intermediate_size"]


def expert_params(c: dict) -> int:
    return 3 * c["hidden_size"] * c["moe_intermediate_size"]


def shared_params(c: dict) -> int:
    return c["n_shared_experts"] * expert_params(c)


def step_weight_bytes(c: dict, dtype_bytes: int = 2) -> float:
    """Weight bytes ONE decode step reads whatever the lanes: every
    layer's attention and indexer, the dense layers' FFN, the expert
    layers' shared expert and router (float32, with its bias), the final
    norm and the head's rows held here."""
    dense, moe = layers(c)
    H = c["hidden_size"]
    per = (dense + moe) * (attention_params(c) + indexer_params(c))
    per += dense * dense_ffn_params(c) + moe * shared_params(c)
    per += H + H * c["vocab_size"]
    return float(per * dtype_bytes + moe * (H + 1) * c["n_routed_experts"] * 4)


def decode_steps(c: dict, *, held_slots: float) -> float:
    """Decode steps behind `moe_held_expert_slots_total`: an expert
    layer's forward offers `experts_held` slots."""
    return held_slots / (layers(c)[1] * c["experts_held"])


def decode_bytes(c: dict, *, steps: float, held_hit: float, kv_tokens: float,
                 selected_tokens: float, dtype_bytes: int = 2) -> float:
    """Bytes `steps` decode steps had to read: the weights of a step,
    the kernels of every HELD expert that took a row once each
    (`moe_held_experts_hit_total`), the index keys and the selected
    rows."""
    return (steps * step_weight_bytes(c, dtype_bytes)
            + held_hit * expert_params(c) * dtype_bytes
            + index_key_bytes(c, kv_tokens=kv_tokens, dtype_bytes=dtype_bytes)
            + selected_row_bytes(c, selected_tokens=selected_tokens,
                                 dtype_bytes=dtype_bytes))


def prefill_flops(c: dict, *, tokens: float, index_pairs: float,
                  selected_pairs: float, held_share: float) -> float:
    """Operations the prefill chunks needed. tokens: real prompt tokens
    prefilled; index_pairs, selected_pairs: `prefill_index_pairs_total`
    and `prefill_selected_pairs_total` (a layer each); held_share: the
    share of a token's `num_experts_per_tok` picks that landed on an
    expert held here (`moe_prefill_held_rows_total /
    moe_prefill_pairs_total`)."""
    dense, moe = layers(c)
    H, Hq = c["hidden_size"], c["num_attention_heads"]
    Rq, R = c["q_lora_rank"], c["kv_lora_rank"]
    dn, dr, dv = (c["qk_nope_head_dim"], c["qk_rope_head_dim"],
                  c["v_head_dim"])
    Hi, Di = c["index_n_heads"], c["index_head_dim"]
    attn = (H * Rq + Rq * Hq * (dn + dr) + H * (R + dr) + Hq * dv * H
            + Rq * Hi * Di + H * Di + H * Hi)
    per_token = (dense + moe) * attn + dense * dense_ffn_params(c) + moe * (
        H * c["n_routed_experts"] + shared_params(c)
        + c["num_experts_per_tok"] * held_share * expert_params(c))
    per_layer = (index_pairs * Hi * Di
                 + selected_pairs * Hq * (dn + dr + dv))
    return float(2 * (tokens * per_token + (dense + moe) * per_layer))
