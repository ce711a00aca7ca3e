"""Analytic model-FLOPs accounting shared by bench.py and the trainer
telemetry exporter (train/telemetry.py).

One definition of "model FLOPs" so the MFU a benchmark prints and the
MFU the trainer exports at /metrics can never drift apart: the standard
6*N FLOPs per token (fwd 2N + bwd 4N matmul work) for the decoder and
the ViT, plus the attention matmuls (QK^T and PV, fwd 2+2 flops/elem,
bwd 2x). Remat recompute is deliberately NOT counted — recompute is
overhead, not useful work, and counting it would let a worse remat
policy inflate MFU.
"""

from __future__ import annotations

# Peak dense bf16 FLOPs/s per chip, keyed by the exact
# `jax.devices()[0].device_kind` string. Only kinds a run of this repo
# has printed are listed ("TPU v5 lite" is what a v5e reports,
# chip_smoke.py PR 21; 197 TFLOP/s is Google Cloud's "TPU v5e" page).
# A new chip gets a row when a run has shown its string — never a
# neighbour's number.
PEAK_FLOPS = {
    "TPU v5 lite": 197e12,
}


def chip_peak_flops(device_kind: str) -> float | None:
    """Peak dense bf16 FLOPs/s for an exact device_kind, None when the
    kind is not in the table (CPU, a chip nobody has run on) — callers
    must then skip MFU rather than fake it."""
    return PEAK_FLOPS.get(device_kind)


def count_llm_params(c) -> int:
    """Parameter count of an LLMConfig-shaped decoder (embeddings
    included)."""
    h, i, v, d = c.hidden_size, c.intermediate_size, c.vocab_size, c.head_dim
    qo = h * c.num_heads * d * 2
    kv = h * c.num_kv_heads * d * 2
    bias = (c.num_heads + 2 * c.num_kv_heads) * d if c.attention_bias else 0
    mlp = 3 * h * i
    per_layer = qo + kv + bias + mlp + 2 * h
    embeds = v * h * (1 if c.tie_word_embeddings else 2)
    return c.num_layers * per_layer + embeds + h


def train_step_flops(
    cfg,
    n_llm_params: int,
    *,
    batch: int,
    seq_len: int,
    patch_tokens: int,
) -> float:
    """Model FLOPs for one SFT step over a [batch, seq_len] token batch
    with `patch_tokens` packed visual patches through the vision tower.

    Dense-matmul dominated: 6*N_dense per token for the decoder (the
    embedding gather excluded, lm_head included), 6*N_vit per patch for
    the tower, plus quadratic attention matmul FLOPs for both.
    """
    lc, vc = cfg.llm, cfg.vision
    tok = float(batch * seq_len)
    # Decoder dense matmuls (exclude the embedding gather, include lm_head).
    n_dense = n_llm_params - lc.vocab_size * lc.hidden_size
    f = 6.0 * n_dense * tok
    # Decoder attention: per layer fwd 4*T^2*heads*d flops (QK+PV), x3 bwd.
    f += 12.0 * lc.num_layers * batch * seq_len * seq_len \
        * lc.num_heads * lc.head_dim
    # Vision tower over the packed patch buffer.
    P = float(patch_tokens)
    n_vit = vc.num_layers * (
        4 * vc.hidden_size * vc.num_heads * vc.head_dim
        + 2 * vc.hidden_size * vc.intermediate_size
    ) + (vc.patch_size**2 * 3) * vc.hidden_size
    f += 6.0 * n_vit * P
    f += 12.0 * vc.num_layers * P * P * vc.num_heads * vc.head_dim
    return f
