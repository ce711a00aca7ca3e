"""Bytes and operations a window / global hybrid with routed experts has
to move and do, from the configuration file's published keys alone (the
source's names; `num_hidden_layers` and the two layouts as the file
holds them). Kept with the benchmark so that a share of a roofline is
always worked out the same way; every count is what the ALGORITHM needs
(a lower bound of what a program moves), so a share cannot pass 100 %."""

from __future__ import annotations


def sizes(c: dict) -> dict:
    n = c["num_hidden_layers"]
    window = sum(1 for v in c["sliding_window_layout"][:n] if v)
    return {
        "d": c["hidden_size"], "V": c["vocab_size"], "layers": n,
        "heads": c["num_attention_heads"],
        "kv_heads": c["num_key_value_heads"], "head": c["head_dim"],
        "E": c["moe_num_primary_experts"],
        "K": c["moe_num_active_primary_experts"],
        "Ie": c["moe_ffn_hidden_size"], "W": c["sliding_window_size"],
        "window_layers": window, "global_layers": n - window,
    }


def layer_params(c: dict) -> dict:
    """{"attention", "router", "norms", "experts"}: one layer's."""
    s = sizes(c)
    d = s["d"]
    dq, dkv = s["heads"] * s["head"], s["kv_heads"] * s["head"]
    return {"attention": 2 * d * dq + 2 * d * dkv, "router": d * s["E"],
            "norms": 2 * d, "experts": s["E"] * 3 * d * s["Ie"]}


def total_params(c: dict) -> int:
    s = sizes(c)
    head = 0 if c["tie_word_embeddings"] else s["d"] * s["V"]
    return (s["layers"] * sum(layer_params(c).values())
            + s["d"] * s["V"] + head + s["d"])


def expert_bytes(c: dict, dtype_bytes: int = 2) -> int:
    """Gate, up and down of ONE expert."""
    s = sizes(c)
    return 3 * s["d"] * s["Ie"] * dtype_bytes


def kv_bytes_per_token(c: dict, dtype_bytes: int = 2) -> dict:
    """{"global", "window"}: K and V of one cached token over the
    layers of each kind."""
    s = sizes(c)
    one = 2 * s["kv_heads"] * s["head"] * dtype_bytes
    return {"global": s["global_layers"] * one,
            "window": s["window_layers"] * one}


def kv_read_bytes(c: dict, *, kv_tokens: float, window_kv_tokens: float,
                  dtype_bytes: int = 2) -> float:
    """K/V bytes decode steps had to read: `kv_tokens` cached tokens a
    global layer (the lanes' lengths, summed over steps), and
    `window_kv_tokens` a window layer (min(length, window))."""
    per = kv_bytes_per_token(c, dtype_bytes)
    return per["global"] * kv_tokens + per["window"] * window_kv_tokens


def step_weight_bytes(c: dict, dtype_bytes: int = 2) -> int:
    """Weight bytes a decode step reads ONCE whatever the lanes: every
    layer's attention, norms and router (float32) and the head; the
    experts are counted by those that took a row (`expert_bytes`)."""
    s, per = sizes(c), layer_params(c)
    return (s["layers"] * ((per["attention"] + per["norms"]) * dtype_bytes
                           + per["router"] * 4)
            + (s["d"] * s["V"] + s["d"]) * dtype_bytes)


def decode_bytes(c: dict, *, steps: float, experts_hit: float,
                 kv_tokens: float, window_kv_tokens: float) -> float:
    """Bytes `steps` decode steps have to move: `step_weight_bytes`
    once a step, gate / up / down of every expert that took a row once
    a layer-forward, both planes' K/V."""
    return (steps * step_weight_bytes(c) + experts_hit * expert_bytes(c)
            + kv_read_bytes(c, kv_tokens=kv_tokens,
                            window_kv_tokens=window_kv_tokens))


def prefill_attention_flops(c: dict, *, pairs: float,
                            window_pairs: float) -> float:
    """Operations of the prefill chunks' attention: QK^T and PV are 2 D
    each a visible (query, key) pair a query head; `pairs` visible
    pairs a chunk on a global layer, `window_pairs` on a window
    layer."""
    s = sizes(c)
    per_pair = 4 * s["head"] * s["heads"]
    return per_pair * (s["global_layers"] * pairs
                       + s["window_layers"] * window_pairs)
