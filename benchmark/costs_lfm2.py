"""Bytes and operations a gated-short-convolution hybrid with routed
experts (LFM2) has to move and do, from the configuration file's
published keys alone (the source's names; `num_hidden_layers` and
`layer_types` as the file holds them). Kept with the benchmark so that
a share of a roofline is always worked out the same way; every count is
what the ALGORITHM needs (a lower bound of what a program moves), so a
share cannot pass 100 %."""

from __future__ import annotations


def sizes(c: dict) -> dict:
    n = c["num_hidden_layers"]
    kinds = c["layer_types"][:n]
    heads = c["num_attention_heads"]
    return {
        "d": c["hidden_size"], "V": c["vocab_size"], "layers": n,
        "attn_layers": kinds.count("full_attention"),
        "conv_layers": kinds.count("conv"),
        "dense_layers": min(c["num_dense_layers"], n),
        "heads": heads, "kv_heads": c["num_key_value_heads"],
        "head": c.get("head_dim") or c["hidden_size"] // heads,
        "I": c["intermediate_size"], "E": c["num_experts"],
        "K": c["num_experts_per_tok"], "Ie": c["moe_intermediate_size"],
        "taps": c["conv_L_cache"],
    }


def layer_params(c: dict) -> dict:
    """One operator's, one FFN's and one layer's norms' parameters."""
    s = sizes(c)
    d = s["d"]
    dq, dkv = s["heads"] * s["head"], s["kv_heads"] * s["head"]
    return {
        "conv": 3 * d * d + s["taps"] * d + d * d,
        "attention": 2 * d * dq + 2 * d * dkv + 2 * s["head"],
        "dense_ffn": 3 * d * s["I"],
        "router": d * s["E"] + s["E"],  # with the selection bias
        "experts": s["E"] * 3 * d * s["Ie"],
        "norms": 2 * d,
    }


def total_params(c: dict) -> int:
    """Every parameter of the model as the file cuts it (the embedding
    tied to the head)."""
    s, per = sizes(c), layer_params(c)
    moe = s["layers"] - s["dense_layers"]
    return (s["conv_layers"] * per["conv"]
            + s["attn_layers"] * per["attention"]
            + s["dense_layers"] * per["dense_ffn"]
            + moe * (per["router"] + per["experts"])
            + s["layers"] * per["norms"] + s["d"] * s["V"] + s["d"])


def expert_bytes(c: dict, dtype_bytes: int = 2) -> int:
    """Gate, up and down of ONE expert."""
    s = sizes(c)
    return 3 * s["d"] * s["Ie"] * dtype_bytes


def kv_bytes_per_token(c: dict, dtype_bytes: int = 2) -> int:
    """K and V of one cached token over the attention layers."""
    s = sizes(c)
    return s["attn_layers"] * 2 * s["kv_heads"] * s["head"] * dtype_bytes


def state_bytes_per_lane(c: dict, dtype_bytes: int = 2) -> int:
    """The conv layers' whole state of one lane: taps - 1 rows of d a
    layer. A page-edge snapshot is as many bytes a page."""
    s = sizes(c)
    return s["conv_layers"] * (s["taps"] - 1) * s["d"] * dtype_bytes


def step_weight_bytes(c: dict, dtype_bytes: int = 2) -> int:
    """Weight bytes a decode step reads ONCE whatever the lanes: every
    conv and attention operator, the leading dense FFNs, the norms, the
    routers (float32) and the tied head; the experts are counted by
    those that took a row (`expert_bytes`)."""
    s, per = sizes(c), layer_params(c)
    moe = s["layers"] - s["dense_layers"]
    return ((s["conv_layers"] * per["conv"]
             + s["attn_layers"] * per["attention"]
             + s["dense_layers"] * per["dense_ffn"]
             + s["layers"] * per["norms"]
             + s["d"] * s["V"] + s["d"]) * dtype_bytes
            + moe * per["router"] * 4)


def decode_bytes(c: dict, *, steps: float, experts_hit: float,
                 kv_tokens: float, lane_steps: float) -> float:
    """Bytes `steps` decode steps have to move: `step_weight_bytes` once
    a step, gate / up / down of every expert that took a row once a
    layer-forward, K/V of `kv_tokens` cached tokens an attention layer
    (the live lanes' lengths, summed over steps), and every live lane's
    conv rows read and written once a step (`lane_steps`)."""
    return (steps * step_weight_bytes(c) + experts_hit * expert_bytes(c)
            + kv_tokens * kv_bytes_per_token(c)
            + 2 * lane_steps * state_bytes_per_lane(c))
