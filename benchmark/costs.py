"""Operations and bytes the algorithm needs, from shapes alone. Kept
with the benchmark so that a roofline share is always worked out the
same way. Recomputed (remat) operations never count."""

from __future__ import annotations


def llm_matmul_params(c: dict) -> int:
    """Parameters that sit in a matmul on every token: the decoder
    stack and the output head (the embedding is a lookup)."""
    H, I = c["hidden_size"], c["intermediate_size"]
    D = c["head_dim"]
    dq, dkv = c["num_attention_heads"] * D, c["num_key_value_heads"] * D
    per_layer = H * dq + 2 * H * dkv + dq * H + 3 * H * I
    return c["num_hidden_layers"] * per_layer + H * c["vocab_size"]


def attention_flops_causal(seq_lens, *, hq: int, d: int,
                           layers: int = 1, backward: bool = False) -> float:
    """Causal self-attention over sequences of the given lengths:
    QK^T and PV are 2*T*T*D each per head, halved by the causal mask;
    the backward pass is 2.5x the forward (dQ, dK, dV, and dP, with the
    scores recomputed inside the kernel not counted)."""
    fwd = sum(2 * 2 * t * t * d * hq / 2 for t in seq_lens)
    return layers * fwd * (3.5 if backward else 1.0)


def attention_flops_full(seq_lens, *, h: int, d: int, layers: int = 1,
                         backward: bool = False) -> float:
    """Bidirectional self-attention within each sequence (the ViT: an
    image's patches attend to each other and to nothing else): QK^T and
    PV, 2*n*n*D each per head; backward 2.5x the forward."""
    fwd = sum(2 * 2 * n * n * d * h for n in seq_lens)
    return layers * fwd * (3.5 if backward else 1.0)


def train_step_model_flops(c: dict, tokens: int, seq_lens,
                           vision_flops: float = 0.0) -> float:
    """Model FLOPs of one training step: 6 per matmul parameter per
    token (2 forward, 4 backward) plus causal attention forward and
    backward, plus whatever the caller counts for the vision tower.
    LoRA's adapters are < 1 % and left out; the frozen base's weight
    gradients are NOT needed by the algorithm (only by the program
    today), so a LoRA step is counted at 4 per parameter per token:
    forward, and the backward pass through activations only."""
    per_param = 4 if c.get("tune") == "lora" else 6
    return (
        per_param * llm_matmul_params(c) * tokens
        + attention_flops_causal(
            seq_lens, hq=c["num_attention_heads"], d=c["head_dim"],
            layers=c["num_hidden_layers"], backward=True)
        + vision_flops
    )


def vit_flops(v: dict, image_patches, *, backward: bool) -> float:
    """OryxViT over images of the given patch counts: 2 per matmul
    parameter per patch over its blocks plus attention within each
    image; 3x (3.5x for attention) when the tower trains. When it is
    frozen (LoRA) the algorithm needs its forward only."""
    H, I, L = v["hidden_size"], v["intermediate_size"], v["num_layers"]
    per_layer = 4 * H * H + 2 * H * I
    mm = 2 * L * per_layer * sum(image_patches) * (3 if backward else 1)
    return mm + attention_flops_full(
        image_patches, h=v["num_heads"], d=v["head_dim"], layers=L,
        backward=backward)


def paged_kv_bytes(kv_lens, *, hk: int, d: int, page_size: int,
                   layers: int, dtype_bytes: int = 2) -> float:
    """Bytes of KV the lanes' pages hold, read once per decode step:
    K and V, every page touched counted whole."""
    pages = sum(-(-int(n) // page_size) for n in kv_lens)
    return 2.0 * layers * pages * page_size * hk * d * dtype_bytes
