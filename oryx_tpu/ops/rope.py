"""Rotary position embeddings (RoPE).

Reference parity: HF Qwen2 rotary embedding (`apply_rotary_pos_emb`,
half-rotation layout), fused into attention in the CUDA path (SURVEY.md §2a
"RoPE"). Here it is a pure jnp function — XLA fuses it into the surrounding
attention computation, so a dedicated Pallas kernel is unnecessary on TPU
(the op is bandwidth-trivial next to the matmuls).

Angles are always computed in float32 (bf16 position*inv_freq products lose
precision catastrophically past ~4k positions).
"""

from __future__ import annotations

import jax.numpy as jnp


def rope_frequencies(head_dim: int, theta: float) -> jnp.ndarray:
    """inv_freq vector, shape [head_dim // 2], float32."""
    exponents = jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim
    return 1.0 / (theta**exponents)


def rope_cos_sin(
    positions: jnp.ndarray, head_dim: int, theta: float
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """cos/sin tables for integer positions.

    positions: [...], int32. Returns (cos, sin) each [..., head_dim] in
    float32, with the HF "duplicated halves" layout: angles repeated as
    concat([freqs, freqs]) along the last dim.
    """
    inv_freq = rope_frequencies(head_dim, theta)
    angles = positions.astype(jnp.float32)[..., None] * inv_freq  # [..., hd/2]
    angles = jnp.concatenate([angles, angles], axis=-1)  # [..., hd]
    return jnp.cos(angles), jnp.sin(angles)


def _rotate_half(x: jnp.ndarray) -> jnp.ndarray:
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([-x2, x1], axis=-1)


def apply_rope(
    q: jnp.ndarray,
    k: jnp.ndarray,
    cos: jnp.ndarray,
    sin: jnp.ndarray,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Apply rotary embedding to q/k.

    q: [B, T, Hq, D], k: [B, T, Hk, D]; cos/sin: [B, T, D] (or broadcastable).
    Rotation computed in fp32, output cast back to the input dtype.
    """
    cos = cos[..., None, :]  # [B, T, 1, D] — broadcast over heads
    sin = sin[..., None, :]

    def rot(x):
        xf = x.astype(jnp.float32)
        out = xf * cos + _rotate_half(xf) * sin
        return out.astype(x.dtype)

    return rot(q), rot(k)


def apply_rope_interleaved(
    x: jnp.ndarray, cos: jnp.ndarray, sin: jnp.ndarray
) -> jnp.ndarray:
    """Rotary embedding over the pairs (x[2j], x[2j+1]) (the DeepSeek-V3
    lineage's layout), each pair rotated in place by angle j.

    x: [B, T, H, D]; cos/sin: [B, T, D] from `rope_cos_sin` (angle j in
    column j of the first half). Rotation in fp32, output in x.dtype."""
    half = x.shape[-1] // 2
    c = cos[..., None, :half]
    s = sin[..., None, :half]
    xf = x.astype(jnp.float32).reshape(*x.shape[:-1], half, 2)
    a, b = xf[..., 0], xf[..., 1]
    out = jnp.stack([a * c - b * s, b * c + a * s], axis=-1)
    return out.reshape(x.shape).astype(x.dtype)
