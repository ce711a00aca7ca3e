"""Bytes a state-space hybrid's serving steps have to move, from the
configuration file's published keys alone (the source's names). Kept
with the benchmark so that a share of the HBM roofline is always worked
out the same way; every count is what the ALGORITHM needs (a lower
bound of what a program moves), so a share cannot pass 100 %."""

from __future__ import annotations


def sizes(c: dict) -> dict:
    d = c["hidden_size"]
    period = c["attn_layer_period"]
    attn = c["num_hidden_layers"] // period
    return {
        "d": d, "d_in": c["mamba_expand"] * d, "N": c["mamba_d_state"],
        "K": c["mamba_d_conv"], "R": c["mamba_dt_rank"],
        "I": c["intermediate_size"], "V": c["vocab_size"],
        "heads": c["num_attention_heads"], "kv_heads": c["num_key_value_heads"],
        "head": c.get("head_dim") or d // c["num_attention_heads"],
        "attn_layers": attn, "mamba_layers": c["num_hidden_layers"] - attn,
    }


def mixer_params(c: dict) -> int:
    """Parameters of one Mamba mixer: in, conv (+ bias), x, the three
    inner norms, dt (+ bias), A_log, D, out."""
    s = sizes(c)
    d, d_in, N, K, R = s["d"], s["d_in"], s["N"], s["K"], s["R"]
    return (d * 2 * d_in + K * d_in + (d_in if c["mamba_conv_bias"] else 0)
            + d_in * (R + 2 * N) + R + 2 * N + R * d_in + d_in
            + N * d_in + d_in + d_in * d
            + ((2 * d_in + d) if c["mamba_proj_bias"] else 0))


def layer_params(c: dict) -> dict:
    """{"mamba", "attn"}: parameters of a layer of each kind (mixer or
    attention, the dense FFN, the two norms)."""
    s = sizes(c)
    d = s["d"]
    ffn = 3 * d * s["I"] + 2 * d
    dq, dkv = s["heads"] * s["head"], s["kv_heads"] * s["head"]
    return {"mamba": mixer_params(c) + ffn,
            "attn": 2 * d * dq + 2 * d * dkv + ffn}


def total_params(c: dict) -> int:
    s, per = sizes(c), layer_params(c)
    head = 0 if c["tie_word_embeddings"] else s["d"] * s["V"]
    return (s["mamba_layers"] * per["mamba"] + s["attn_layers"] * per["attn"]
            + s["d"] * s["V"] + head + s["d"])


def decode_weight_bytes(c: dict, dtype_bytes: int = 2) -> int:
    """Weight bytes ONE decode step reads: every layer and the head (the
    tied embedding, read whole as the head; the lookup's rows are not
    counted) in the serving dtype; A_log, D and dt's bias float32."""
    s, per = sizes(c), layer_params(c)
    f32 = s["mamba_layers"] * (s["N"] * s["d_in"] + 2 * s["d_in"])
    n = (s["mamba_layers"] * per["mamba"] + s["attn_layers"] * per["attn"]
         + s["d"] * s["V"] + s["d"])
    return n * dtype_bytes + f32 * (4 - dtype_bytes)


def state_bytes_per_lane(c: dict, dtype_bytes: int = 2) -> int:
    """Recurrent state one lane holds: [N, d_in] float32 and the K - 1
    conv inputs in the serving dtype, a Mamba layer."""
    s = sizes(c)
    return s["mamba_layers"] * s["d_in"] * (
        4 * s["N"] + dtype_bytes * (s["K"] - 1))


def kv_bytes_per_token(c: dict, dtype_bytes: int = 2) -> int:
    """K and V of one cached token over the attention layers."""
    s = sizes(c)
    return 2 * s["attn_layers"] * s["kv_heads"] * s["head"] * dtype_bytes


def decode_bytes(c: dict, *, steps: float, lane_steps: float,
                 kv_tokens: float, dtype_bytes: int = 2) -> float:
    """Bytes `steps` decode steps have to move: the weights once a step,
    every LIVE lane's state read and written once a step, and the cached
    tokens the live lanes' attention read."""
    return (steps * decode_weight_bytes(c, dtype_bytes)
            + 2.0 * lane_steps * state_bytes_per_lane(c, dtype_bytes)
            + kv_tokens * kv_bytes_per_token(c, dtype_bytes))


def scan_bytes(c: dict, *, tokens: float, chunks: float,
               dtype_bytes: int = 2) -> float:
    """Bytes the selective scan has to move over `tokens` real prompt
    tokens in `chunks` prefill chunks, all Mamba layers: x and z in and
    y out in the serving dtype, dt float32, B and C float32 a token, and
    the [N, d_in] float32 state once in and once out a chunk."""
    s = sizes(c)
    per_token = s["d_in"] * (3 * dtype_bytes + 4) + 2 * s["N"] * 4
    per_chunk = 2 * s["N"] * s["d_in"] * 4
    return s["mamba_layers"] * (tokens * per_token + chunks * per_chunk)
