"""Bytes and operations of ONE forward of an expert decoder, from shapes
and from how many experts took a row: what the algorithm needs, kept
with the benchmark so that a roofline share is always worked out the
same way. `c` is the configuration file (the source's own key names).

A forward of R rows (lanes) reads, whatever R is: every layer's
attention projections, norms and router once; the kernels of every
expert that took at least one row, ONCE each however many rows it took
(`experts_hit`, summed over the forward's layers: the program counts
it); and the output head. The embedding is a lookup of R rows. KV pages
and activations are left out: at the cell's sizes they are under 2 % of
the weight bytes, and a share of a roofline that counts too little
reads low, never over 100 %.
"""

from __future__ import annotations


def attention_params(c: dict) -> int:
    """q, k, v, o projections, the two block norms, the q/k head norms."""
    H, D = c["hidden_size"], c["head_dim"]
    dq, dkv = c["num_attention_heads"] * D, c["num_key_value_heads"] * D
    return H * dq + 2 * H * dkv + dq * H + 2 * H + 2 * D


def expert_params(c: dict) -> int:
    """One expert: gate, up and down."""
    return 3 * c["hidden_size"] * c["moe_intermediate_size"]


def router_params(c: dict) -> int:
    return c["hidden_size"] * c["num_experts"]


def forward_weight_bytes(c: dict, *, experts_hit: float, rows: int,
                         head: bool = True, dtype_bytes: int = 2) -> float:
    """Weight bytes one forward of `rows` lanes has to read. experts_hit:
    experts with >= 1 row summed over the forward's layers (at most
    num_hidden_layers x num_experts). The router is float32."""
    L, H = c["num_hidden_layers"], c["hidden_size"]
    b = L * attention_params(c) * dtype_bytes
    b += L * router_params(c) * 4
    b += expert_bytes_read(c, experts_hit=experts_hit,
                           dtype_bytes=dtype_bytes)
    b += rows * H * dtype_bytes  # embedding rows
    b += H * dtype_bytes  # final norm
    if head:
        b += H * c["vocab_size"] * dtype_bytes
    return float(b)


def expert_bytes_read(c: dict, *, experts_hit: float,
                      dtype_bytes: int = 2) -> float:
    """Bytes of expert kernels the grouped products have to read:
    gate, up and down of every expert that took a row, once each
    (`experts_hit` summed over layer-forwards)."""
    return float(experts_hit * expert_params(c) * dtype_bytes)


def forward_flops(c: dict, *, rows: int, head: bool = True) -> float:
    """Matmul operations of one forward of `rows` lanes: 2 per parameter
    per row for attention projections, router and the top-K experts of
    a row, and the head. Attention's own QK^T and PV are left out (a
    block of 4 queries against a few hundred keys)."""
    L, K = c["num_hidden_layers"], c["num_experts_per_tok"]
    per_row = L * (attention_params(c) + router_params(c)
                   + K * expert_params(c))
    if head:
        per_row += c["hidden_size"] * c["vocab_size"]
    return 2.0 * rows * per_row
