"""Attention ops — XLA reference path.

This is the portable (CPU-testable) attention used for parity work; the
Pallas TPU kernels in `oryx_tpu/ops/pallas/` are drop-in replacements
selected by `OryxConfig.attn_impl` (SURVEY.md §2a: flash-attn CUDA →
Pallas flash attention; flash-attn varlen → segment-id attention).

Conventions:
  q: [B, Tq, Hq, D]   k/v: [B, Tk, Hk, D]   with Hq % Hk == 0 (GQA).
  Logits and softmax are computed in float32 regardless of input dtype
  (the bit-closeness policy, SURVEY.md §7 hard part 2); the probs·V matmul
  runs in the input dtype so the MXU stays in bf16 on TPU.

Masking model (all optional, combined by logical AND):
  * causal        — query position i attends to key positions <= i + offset.
  * segment ids   — packed varlen: token i attends to token j iff
                    q_segment_ids[b, i] == kv_segment_ids[b, j]. This is the
                    TPU-native replacement for cu_seqlens varlen attention:
                    many images packed into one sequence, each attending only
                    within itself. Padding uses segment id 0 by convention
                    (still self-consistent; pad outputs are discarded).
  * kv_mask       — explicit boolean key validity [B, Tk] (KV-cache length
                    masking during decode, padding masks).

Memory: the dense path materializes [B, Hq, Tq, Tk] fp32 logits. Above
MAX_LOGITS_ELEMS (256 MB fp32) the wrapper switches to a sequential
`lax.map` over query chunks so the largest packed-video buckets (e.g.
P=65536, which would need ~16 GB per head group dense) stay serviceable on
this path; the Pallas kernel is the fast path for those shapes.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

NEG_INF = float(jnp.finfo(jnp.float32).min)

# Cap on materialized fp32 logits elements (B * Hq * Tq_chunk * Tk).
MAX_LOGITS_ELEMS = 2**26  # 64M elems = 256 MB fp32


def _attention_dense(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    causal: bool,
    q_positions: jnp.ndarray | None,
    kv_positions: jnp.ndarray | None,
    q_segment_ids: jnp.ndarray | None,
    kv_segment_ids: jnp.ndarray | None,
    kv_mask: jnp.ndarray | None,
    scale: float,
    window: int = 0,
) -> jnp.ndarray:
    B, Tq, Hq, D = q.shape
    _, Tk, Hk, _ = k.shape
    G = Hq // Hk

    # [B, Tk, Hk, G, ...] grouped layout so k/v are never materialized
    # repeated (XLA keeps the broadcast virtual on TPU).
    qg = q.reshape(B, Tq, Hk, G, D)
    logits = jnp.einsum(
        "bqhgd,bkhd->bhgqk", qg, k, preferred_element_type=jnp.float32
    )
    logits = logits * scale  # [B, Hk, G, Tq, Tk] fp32

    mask = None  # [B, Tq, Tk] broadcastable

    def _and(m, new):
        return new if m is None else jnp.logical_and(m, new)

    if causal:
        mask = _and(
            mask, q_positions[:, :, None] >= kv_positions[:, None, :]
        )
        if window:
            mask = _and(
                mask,
                q_positions[:, :, None] - kv_positions[:, None, :] < window,
            )
    if q_segment_ids is not None:
        assert kv_segment_ids is not None
        mask = _and(
            mask, q_segment_ids[:, :, None] == kv_segment_ids[:, None, :]
        )
    if kv_mask is not None:
        mask = _and(mask, kv_mask[:, None, :].astype(bool))

    if mask is not None:
        logits = jnp.where(mask[:, None, None, :, :], logits, NEG_INF)

    # fp32 softmax; rows that are fully masked (e.g. cache slots past the
    # current length for padded queries) produce uniform probs over masked
    # slots — harmless because those outputs are themselves discarded.
    probs = jnp.exp(logits - jnp.max(logits, axis=-1, keepdims=True))
    probs = probs / jnp.sum(probs, axis=-1, keepdims=True)
    probs = probs.astype(v.dtype)

    out = jnp.einsum("bhgqk,bkhd->bqhgd", probs, v)
    return out.reshape(B, Tq, Hq, D).astype(q.dtype)


def attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    causal: bool = False,
    q_positions: jnp.ndarray | None = None,
    kv_positions: jnp.ndarray | None = None,
    q_segment_ids: jnp.ndarray | None = None,
    kv_segment_ids: jnp.ndarray | None = None,
    kv_mask: jnp.ndarray | None = None,
    scale: float | None = None,
    window: int = 0,
) -> jnp.ndarray:
    """General GQA attention. Returns [B, Tq, Hq, D] in q.dtype.

    For causal masking with a KV cache, pass `q_positions`/`kv_positions`
    (absolute token positions, int32 [B, T*]); without them, positions
    default to arange (pure prefill). `window` > 0 (causal only): a
    query at position t sees the keys at u <= t with t - u < window.
    """
    B, Tq, Hq, D = q.shape
    _, Tk, Hk, _ = k.shape
    assert Hq % Hk == 0, f"GQA requires Hq % Hk == 0, got {Hq=} {Hk=}"
    if scale is None:
        scale = D**-0.5
    if causal:
        if q_positions is None:
            q_positions = jnp.arange(Tq, dtype=jnp.int32)[None, :]
        if kv_positions is None:
            kv_positions = jnp.arange(Tk, dtype=jnp.int32)[None, :]

    kwargs = dict(
        causal=causal, kv_positions=kv_positions,
        kv_segment_ids=kv_segment_ids, kv_mask=kv_mask, scale=scale,
    )
    if window:
        assert causal, "a sliding window is a bound beside the causal one"
        kwargs["window"] = int(window)

    # Pick the largest power-of-two query chunk that keeps the logits
    # buffer under MAX_LOGITS_ELEMS and divides Tq (buckets are powers of
    # two); chunk == Tq means one dense call.
    chunk = max(1, MAX_LOGITS_ELEMS // max(1, B * Hq * Tk))
    chunk = 2 ** int(math.floor(math.log2(chunk)))
    while Tq % chunk:
        chunk //= 2
    if chunk >= Tq:
        out = _attention_dense(
            q, k, v, q_positions=q_positions,
            q_segment_ids=q_segment_ids, **kwargs,
        )
        # Same tag the Pallas kernel gives its output, so the "attn"/
        # "attn_qkv" remat policies (utils/remat.py) save the attention
        # output on this path too. There is no explicit logsumexp here, so
        # dq/dk/dv still recompute the softmax internals under remat; the
        # saved output cuts the recompute tree for everything downstream
        # (o_proj and the MLP backward).
        return checkpoint_name(out, "flash_out")

    nc = Tq // chunk

    def split_q(x):  # [Bx, Tq, ...] → [nc, Bx, chunk, ...]
        if x is None:
            return None
        xs = x.reshape(x.shape[0], nc, chunk, *x.shape[2:])
        return jnp.moveaxis(xs, 1, 0)

    def body(args):
        qc, qp, qs = args
        return _attention_dense(
            qc, k, v, q_positions=qp, q_segment_ids=qs, **kwargs
        )

    # checkpoint: without it reverse-mode saves every chunk's probs —
    # O(Tq·Tk) residuals, exactly the memory this path exists to avoid
    # (451 GB at the 131072-patch long-video bucket). Recompute per chunk
    # in backward instead (flash-style tradeoff). prevent_cse barriers are
    # unnecessary under lax.map/scan.
    body = jax.checkpoint(body, prevent_cse=False)

    # Sequential over chunks: peak memory = one chunk's logits.
    outs = jax.lax.map(
        body, (split_q(q), split_q(q_positions), split_q(q_segment_ids))
    )  # [nc, B, chunk, Hq, D]
    out = jnp.moveaxis(outs, 0, 1).reshape(B, Tq, Hq, D)
    return checkpoint_name(out, "flash_out")
