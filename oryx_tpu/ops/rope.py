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

import math

import jax.numpy as jnp


def rope_frequencies(head_dim: int, theta: float) -> jnp.ndarray:
    """inv_freq vector, shape [head_dim // 2], float32."""
    exponents = jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim
    return 1.0 / (theta**exponents)


def yarn_correction_range(
    head_dim: int, theta: float, original: int, beta_fast: float,
    beta_slow: float,
) -> tuple[int, int]:
    """(low, high) pair indices of YaRN's ramp: the pairs that make
    beta_fast and beta_slow whole rotations over `original` positions,
    floored and ceiled and held inside 0..head_dim - 1."""

    def dim_of(rotations: float) -> float:
        return head_dim * math.log(
            original / (rotations * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(dim_of(beta_fast)), 0)
    high = min(math.ceil(dim_of(beta_slow)), head_dim - 1)
    return low, high


def yarn_frequencies(
    head_dim: int, theta: float, *, factor: float, original: int,
    beta_fast: float = 32.0, beta_slow: float = 1.0,
) -> jnp.ndarray:
    """YaRN inv_freq, shape [head_dim // 2], float32: pair j keeps its
    own frequency below the ramp's low index (it turns beta_fast times
    or more over the original length), takes frequency / factor above
    the high index (it turns less than beta_slow times), and the linear
    blend of the two between."""
    inv_freq = rope_frequencies(head_dim, theta)
    low, high = yarn_correction_range(
        head_dim, theta, original, beta_fast, beta_slow)
    ramp = (jnp.arange(head_dim // 2, dtype=jnp.float32) - low) / max(
        high - low, 0.001)
    keep = 1.0 - jnp.clip(ramp, 0.0, 1.0)  # 1: the pair's own frequency
    return inv_freq / factor * (1.0 - keep) + inv_freq * keep


def rope_cos_sin(
    positions: jnp.ndarray, head_dim: int, theta: float, *,
    inv_freq: jnp.ndarray | None = None, scale: float = 1.0,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """cos/sin tables for integer positions.

    positions: [...], int32. Returns (cos, sin) each [..., head_dim] in
    float32, with the HF "duplicated halves" layout: angles repeated as
    concat([freqs, freqs]) along the last dim. `inv_freq` replaces the
    plain frequencies (`yarn_frequencies`), `scale` multiplies both
    tables (YaRN's mscale ratio).
    """
    if inv_freq is None:
        inv_freq = rope_frequencies(head_dim, theta)
    angles = positions.astype(jnp.float32)[..., None] * inv_freq  # [..., hd/2]
    angles = jnp.concatenate([angles, angles], axis=-1)  # [..., hd]
    if scale != 1.0:
        return jnp.cos(angles) * scale, jnp.sin(angles) * scale
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
