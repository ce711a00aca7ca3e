"""Bytes and operations a Mamba-2 hybrid with latent experts
(Nemotron-H) has to move and do, from the configuration file's published
keys alone (the source's names; `num_hidden_layers`, `vocab_size` and
`hybrid_override_pattern` as the file cuts them, `experts_held` the
file's own). Kept with the benchmark so that a share of a roofline is
always worked out the same way; every count is what the ALGORITHM needs
(a lower bound of what a program moves), so a share cannot pass 100 %."""

from __future__ import annotations


def sizes(c: dict) -> dict:
    pat = c["hybrid_override_pattern"][:c["num_hidden_layers"]]
    nh, P = c["mamba_num_heads"], c["mamba_head_dim"]
    G, N = c["n_groups"], c["ssm_state_size"]
    return {
        "d": c["hidden_size"], "V": c["vocab_size"],
        "mixers": pat.count("M"), "attn_layers": pat.count("*"),
        "moe_layers": pat.count("E"),
        "heads": c["num_attention_heads"],
        "kv_heads": c["num_key_value_heads"], "head": c["head_dim"],
        "nh": nh, "P": P, "G": G, "N": N, "d_in": nh * P,
        "conv_dim": nh * P + 2 * G * N, "taps": c["conv_kernel"],
        "Q": c["chunk_size"],
        "E": c["n_routed_experts"],
        "held": c.get("experts_held", c["n_routed_experts"]),
        "K": c["num_experts_per_tok"], "Ie": c["moe_intermediate_size"],
        "latent": c["moe_latent_size"],
        "Is": c["moe_shared_expert_intermediate_size"],
    }


def layer_params(c: dict) -> dict:
    """One sublayer's parameters, its one norm included."""
    s = sizes(c)
    d, di, cd = s["d"], s["d_in"], s["conv_dim"]
    dq, dkv = s["heads"] * s["head"], s["kv_heads"] * s["head"]
    return {
        # in, conv taps and bias, dt bias / A_log / D, gated norm, out
        "mixer": (d * (di + cd + s["nh"]) + (s["taps"] + 1) * cd
                  + 3 * s["nh"] + di + di * d + d),
        "attention": 2 * d * dq + 2 * d * dkv + d,
        # router with its selection bias, latent down and up, shared
        "expert_layer": (d * s["E"] + s["E"] + 2 * d * s["latent"]
                         + 2 * d * s["Is"] + d),
        "expert": 2 * s["latent"] * s["Ie"],
    }


def total_params(c: dict) -> int:
    """Every parameter of the model as the file cuts it: the held
    experts, the embedding and the untied head of `vocab_size` rows."""
    s, per = sizes(c), layer_params(c)
    return (s["mixers"] * per["mixer"] + s["attn_layers"] * per["attention"]
            + s["moe_layers"] * (
                per["expert_layer"] + s["held"] * per["expert"])
            + 2 * s["d"] * s["V"] + s["d"])


def expert_bytes(c: dict, dtype_bytes: int = 2) -> int:
    """The two kernels of ONE latent expert."""
    return layer_params(c)["expert"] * dtype_bytes


def kv_bytes_per_token(c: dict, dtype_bytes: int = 2) -> int:
    """K and V of one cached token over the attention layers."""
    s = sizes(c)
    return s["attn_layers"] * 2 * s["kv_heads"] * s["head"] * dtype_bytes


def ssd_step_bytes(c: dict) -> int:
    """What ONE live lane's decode step moves in ONE mixer's state: S
    [N, d_in] float32 read once and written once."""
    s = sizes(c)
    return 2 * 4 * s["N"] * s["d_in"]


def conv_bytes_per_lane(c: dict, dtype_bytes: int = 2) -> int:
    """One mixer's conv window of one lane: taps - 1 rows."""
    s = sizes(c)
    return (s["taps"] - 1) * s["conv_dim"] * dtype_bytes


def ssd_chunk_flops(c: dict) -> int:
    """Operations of ONE chunk of Q tokens through ONE mixer's chunked
    scan (2 a multiply-add; the projections, the conv and the norm are
    not the scan's): C B^T a group (Q Q N), its product with x a head
    (Q Q P), the chunk's state (Q P N) and the carried state's readout
    (Q N P) a head."""
    s = sizes(c)
    Q, N, P = s["Q"], s["N"], s["P"]
    return 2 * (s["G"] * Q * Q * N + s["nh"] * (Q * Q * P + 2 * Q * P * N))


def step_weight_bytes(c: dict, dtype_bytes: int = 2) -> int:
    """Weight bytes a decode step reads ONCE whatever the lanes: every
    mixer and attention layer, the routers (float32), latent
    projections, shared experts, norms and the head (the embedding is
    read a row a lane: left out); the routed experts are counted by
    those that took a row (`expert_bytes`)."""
    s, per = sizes(c), layer_params(c)
    router = s["d"] * s["E"] + s["E"]
    return ((s["mixers"] * per["mixer"] + s["attn_layers"] * per["attention"]
             + s["moe_layers"] * (per["expert_layer"] - router)
             + s["d"] * s["V"] + s["d"]) * dtype_bytes
            + s["moe_layers"] * router * 4)


def decode_bytes(c: dict, *, steps: float, experts_hit: float,
                 kv_tokens: float, lane_steps: float) -> float:
    """Bytes `steps` decode steps have to move: `step_weight_bytes` once
    a step, both kernels of every expert that took a row once a
    layer-forward, K/V of `kv_tokens` cached tokens an attention layer
    (the live lanes' lengths, summed over steps), and every live lane's
    state and conv rows of every mixer read and written once a step
    (`lane_steps`)."""
    s = sizes(c)
    return (steps * step_weight_bytes(c) + experts_hit * expert_bytes(c)
            + kv_tokens * kv_bytes_per_token(c)
            + lane_steps * s["mixers"] * (
                ssd_step_bytes(c) + 2 * conv_bytes_per_lane(c)))
