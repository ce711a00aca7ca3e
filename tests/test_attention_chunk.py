"""Query-chunked XLA attention (ops/attention.py): the memory-bounded
`lax.map` path must equal the dense path bit-for-bit per chunk math, so the
biggest packed-video buckets (the 65536-bucket fallback) stay
serviceable without O(P^2) logits."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from oryx_tpu.ops import attention as attn_lib


def _rand(shape, seed):
    return jnp.asarray(
        np.random.default_rng(seed).standard_normal(shape), jnp.float32
    )


@pytest.mark.parametrize("causal", [False, True])
def test_chunked_matches_dense(monkeypatch, causal):
    B, Tq, Tk, Hq, Hk, D = 2, 32, 32, 4, 2, 8
    q = _rand((B, Tq, Hq, D), 0)
    k = _rand((B, Tk, Hk, D), 1)
    v = _rand((B, Tk, Hk, D), 2)
    seg_q = jnp.asarray(
        np.repeat(np.arange(1, 5), Tq // 4)[None].repeat(B, 0), jnp.int32
    )
    kw = dict(causal=causal, q_segment_ids=seg_q, kv_segment_ids=seg_q)
    dense = attn_lib.attention(q, k, v, **kw)
    # Force chunking: cap → chunk of 8 queries (4 chunks).
    monkeypatch.setattr(attn_lib, "MAX_LOGITS_ELEMS", B * Hq * Tk * 8)
    chunked = attn_lib.attention(q, k, v, **kw)
    np.testing.assert_allclose(
        np.asarray(dense), np.asarray(chunked), rtol=1e-6, atol=1e-6
    )


def test_chunked_kv_mask_and_decode_shape(monkeypatch):
    B, Tq, Tk, H, D = 1, 16, 16, 2, 4
    q = _rand((B, Tq, H, D), 3)
    k = _rand((B, Tk, H, D), 4)
    v = _rand((B, Tk, H, D), 5)
    kv_mask = jnp.asarray((np.arange(Tk) < 10)[None].repeat(B, 0), jnp.int32)
    dense = attn_lib.attention(q, k, v, causal=True, kv_mask=kv_mask)
    monkeypatch.setattr(attn_lib, "MAX_LOGITS_ELEMS", B * H * Tk * 4)
    chunked = attn_lib.attention(q, k, v, causal=True, kv_mask=kv_mask)
    np.testing.assert_allclose(
        np.asarray(dense), np.asarray(chunked), rtol=1e-6, atol=1e-6
    )
    # Decode shape (Tq=1) never chunks below one query.
    out = attn_lib.attention(q[:, :1], k, v, kv_mask=kv_mask)
    assert out.shape == (B, 1, H, D)
