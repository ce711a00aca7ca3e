"""Pallas TPU flash attention (causal GQA + segments + KV masking).

The TPU-native replacement for the reference's flash-attn CUDA kernels
(SURVEY.md §2a): one kernel serves the decoder (causal, GQA, KV-cache
decode) and — via segment ids — the packed arbitrary-resolution ViT
(`flash_attn_varlen_func`-equivalent; see segment_attention.py).

Design:
  * Grid (B, Hq, nq, nk); the innermost kv dimension runs sequentially on
    the core, accumulating online-softmax state (m, l, acc) in VMEM
    scratch and finalizing the output block at the last kv step.
  * Logits/softmax in fp32 (matching ops/attention.py's bit-closeness
    policy); the probs·V matmul in the value dtype so the MXU runs bf16.
  * Masking is the same model as ops/attention.attention: causal on
    absolute positions, segment-id equality, explicit kv validity — all
    folded into one predicate per tile. With arange kv positions (the
    prefill and KV-cache layouts), causally-dead kv tiles are skipped.
  * Backward: Pallas flash backward (custom VJP). The forward saves the
    per-row logsumexp; `_dq_kernel` accumulates dq over kv tiles and
    `_dkv_kernel` accumulates dk/dv over (group-head, q-tile) steps with
    the GQA reduction in VMEM scratch — O(T) memory, no O(T²) recompute.
    Per-row lse/Δ scalars ride in an 8-sublane layout and are broadcast
    against logit tiles via a rank-1 MXU outer product (no relayouts).

Interpret mode runs the same kernel on CPU for tests.
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG = -0.7 * float(jnp.finfo(jnp.float32).max)

# Tile sizes. 512×512 keeps the fp32 logits tile at 1 MB of VMEM while
# amortizing DMA and per-tile softmax state updates; q/k/v/acc tiles add
# ~0.8 MB — comfortably inside the ~16 MB VMEM budget with double
# buffering. Tile choice not measured on the current chip.
# Env-overridable for sweeps.
BLOCK_Q = int(os.environ.get("ORYX_FLASH_BLOCK_Q", "512"))
BLOCK_K = int(os.environ.get("ORYX_FLASH_BLOCK_K", "512"))
# Backward kernels take independent tile sizes: the dq/dkv kernels
# stream three extra operands (do, lse, Δ) per tile and accumulate into
# VMEM scratch, so their DMA/compute balance differs. The 1024×1024
# backward default is not measured on the current chip;
# shorter/indivisible sequences fall back to the forward tiling
# (_bwd_block). Env: unset → the 1024 default; 0 → None = inherit the
# forward value AT CALL TIME; any other value → itself.
def _bwd_env(name: str) -> int | None:
    raw = os.environ.get(name)
    if raw is None:
        return 1024
    return int(raw) or None


BWD_BLOCK_Q = _bwd_env("ORYX_FLASH_BWD_BLOCK_Q")
BWD_BLOCK_K = _bwd_env("ORYX_FLASH_BWD_BLOCK_K")


def _bwd_block(pref: int | None, fwd: int, T: int) -> int:
    """Backward tile size: the preferred bwd block when set and dividing
    the padded length (which was padded to FORWARD-block multiples), else
    fall back to the forward choice (always a divisor)."""
    if pref is None:
        return min(fwd, T)
    b = min(pref, T)
    return b if T % b == 0 else min(fwd, T)


def _causal_kv_clamp(block_q: int, block_k: int, enabled: bool):
    """Grid-level kv skipping for causal PREFILL layouts (q AND kv
    positions both arange from 0 — `enabled` must encode that): map every
    causally-dead kv tile index to the LAST live tile for its q tile.
    Pallas elides the DMA when an input block's index map repeats the
    previous grid step's value, so dead tiles cost neither bandwidth nor
    compute (the kernels' `run` predicate — keyed on the unclamped
    program id — already skips their math). Invalid for the decode layout
    (arbitrary q positions): tile index no longer bounds position there."""
    if not enabled:
        return lambda iq, ik: ik

    def clamp(iq, ik):
        return jnp.minimum(ik, ((iq + 1) * block_q - 1) // block_k)

    return clamp


def _causal_q_clamp(block_q: int, block_k: int, enabled: bool):
    """dkv-kernel mirror of _causal_kv_clamp: q tiles entirely before a kv
    tile are dead; map them to the FIRST live q tile."""
    if not enabled:
        return lambda ik, iq: iq

    def clamp(ik, iq):
        return jnp.maximum(iq, (ik * block_k) // block_q)

    return clamp


def _kernel(
    qpos_ref, kpos_ref, qseg_ref, kseg_ref, kvalid_ref,
    q_ref, k_ref, v_ref,
    o_ref, lse_ref,  # lse_ref is None when with_lse=False (inference)
    m_scr, l_scr, acc_scr,
    *,
    scale: float,
    causal: bool,
    has_segments: bool,
    kv_arange: bool,
    block_k: int,
    window: int = 0,
):
    ik, nk = pl.program_id(3), pl.num_programs(3)

    @pl.when(ik == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    # q-side int refs are lane-broadcast [1, bq, LANES]; kv-side are
    # sublane-broadcast [1, SUBLANES, bk] (TPU tiling wants the last two
    # dims (8k, 128m)-aligned; a bare [1, bk] block is not lowerable).
    if causal and kv_arange:
        # kv positions are arange ⇒ tiles entirely after the largest query
        # position contribute nothing; skip their compute (data is still
        # prefetched — grid-level skipping is a later optimization).
        run = ik * block_k <= jnp.max(qpos_ref[0])
        if window:
            # ... and tiles wholly older than every query's window.
            run = run & (
                (ik + 1) * block_k - 1 + window > jnp.min(qpos_ref[0]))
    else:
        run = True

    @pl.when(run)
    def _step():
        q = q_ref[0, 0]  # [bq, D]
        k = k_ref[0, 0]  # [bk, D]
        v = v_ref[0, 0]  # [bk, D]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale  # [bq, bk] fp32

        mask = kvalid_ref[0, :1, :] > 0  # [1, bk]
        if causal:
            mask = jnp.logical_and(
                mask, qpos_ref[0, :, :1] >= kpos_ref[0, :1, :]
            )
            if window:  # the lower bound beside the causal one
                mask = jnp.logical_and(
                    mask,
                    qpos_ref[0, :, :1] - kpos_ref[0, :1, :] < window,
                )
        if has_segments:
            mask = jnp.logical_and(
                mask, qseg_ref[0, :, :1] == kseg_ref[0, :1, :]
            )
        s = jnp.where(mask, s, NEG)

        m_prev = m_scr[:, :1]  # [bq, 1] (m/l live lane-broadcast in VMEM)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)  # [bq, bk] fp32
        l_new = l_scr[:, :1] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        l_scr[:] = jnp.broadcast_to(l_new, l_scr.shape)
        m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)
        pv = jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        acc_scr[:] = acc_scr[:] * alpha + pv

    @pl.when(ik == nk - 1)
    def _finalize():
        l = l_scr[:, :1]
        out = acc_scr[:] / jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = out.astype(o_ref.dtype)
        if lse_ref is not None:
            # logsumexp for the backward pass. Fully-masked rows (l == 0,
            # e.g. padding) get +inf so exp(s - lse) underflows to 0 there.
            lse = jnp.where(
                l == 0.0,
                jnp.float32(jnp.finfo(jnp.float32).max),
                m_scr[:, :1] + jnp.log(jnp.where(l == 0.0, 1.0, l)),
            )
            lse_ref[0, 0] = jnp.broadcast_to(lse, lse_ref.shape[2:])


def _round_up(n: int, m: int) -> int:
    return ((n + m - 1) // m) * m


def _pad_axis(x, axis: int, target: int, fill=0):
    pad = target - x.shape[axis]
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=fill)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "has_segments", "kv_arange", "q_arange",
                     "scale", "interpret", "with_lse", "window"),
)
def _mha_forward(
    q, k, v, q_pos, kv_pos, q_seg, kv_seg, kv_valid,
    *,
    causal: bool,
    has_segments: bool,
    kv_arange: bool,
    q_arange: bool,
    scale: float,
    interpret: bool,
    with_lse: bool = False,
    window: int = 0,
):
    """Core pallas call. Layouts: q [B, Hq, Tq, D]; k/v [B, Hk, Tk, D];
    int arrays [B, T*] (already padded to block multiples). with_lse emits
    the logsumexp residual for the backward pass (skipped at inference —
    its lane-broadcast output buffer is the price of the grad path only).
    window > 0 (causal): a query at t sees the keys u with t - u <
    window only; forward alone (serving), no backward kernel has it.
    """
    B, Hq, Tq, D = q.shape
    _, Hk, Tk, _ = k.shape
    Dv = v.shape[-1]  # values may be narrower than keys (latent attention)
    G = Hq // Hk
    block_q = min(BLOCK_Q, Tq)
    block_k = min(BLOCK_K, Tk)
    nq = Tq // block_q
    nk = Tk // block_k

    # Lane/sublane broadcast layouts for the per-token int arrays (see
    # kernel comment): q-side [B, Tq, LANES], kv-side [B, SUBLANES, Tk].
    LANES, SUB = 128, 8
    q_pos = jnp.broadcast_to(q_pos[:, :, None], (B, Tq, LANES))
    q_seg = jnp.broadcast_to(q_seg[:, :, None], (B, Tq, LANES))
    kv_pos = jnp.broadcast_to(kv_pos[:, None, :], (B, SUB, Tk))
    kv_seg = jnp.broadcast_to(kv_seg[:, None, :], (B, SUB, Tk))
    kv_valid = jnp.broadcast_to(kv_valid[:, None, :], (B, SUB, Tk))

    grid = (B, Hq, nq, nk)
    kern_full = functools.partial(
        _kernel, scale=scale, causal=causal, has_segments=has_segments,
        kv_arange=kv_arange, block_k=block_k,
        **({"window": window} if window else {}),
    )
    if with_lse:
        kern = kern_full
    else:
        def kern(qp, kp, qs, ks, kvd, q_, k_, v_, o_, m_, l_, a_):
            kern_full(qp, kp, qs, ks, kvd, q_, k_, v_, o_, None, m_, l_, a_)

    ck = _causal_kv_clamp(block_q, block_k, causal and kv_arange and q_arange)

    o_spec = pl.BlockSpec(
        (1, 1, block_q, Dv), lambda b, h, iq, ik: (b, h, iq, 0)
    )
    o_shape = jax.ShapeDtypeStruct((B, Hq, Tq, Dv), q.dtype)
    lse_spec = pl.BlockSpec(
        (1, 1, block_q, LANES), lambda b, h, iq, ik: (b, h, iq, 0)
    )
    lse_shape = jax.ShapeDtypeStruct((B, Hq, Tq, LANES), jnp.float32)

    res = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, LANES), lambda b, h, iq, ik: (b, iq, 0)),
            pl.BlockSpec(
                (1, SUB, block_k), lambda b, h, iq, ik: (b, 0, ck(iq, ik))
            ),
            pl.BlockSpec((1, block_q, LANES), lambda b, h, iq, ik: (b, iq, 0)),
            pl.BlockSpec(
                (1, SUB, block_k), lambda b, h, iq, ik: (b, 0, ck(iq, ik))
            ),
            pl.BlockSpec(
                (1, SUB, block_k), lambda b, h, iq, ik: (b, 0, ck(iq, ik))
            ),
            pl.BlockSpec(
                (1, 1, block_q, D), lambda b, h, iq, ik: (b, h, iq, 0)
            ),
            pl.BlockSpec(
                (1, 1, block_k, D),
                lambda b, h, iq, ik: (b, h // G, ck(iq, ik), 0),
            ),
            pl.BlockSpec(
                (1, 1, block_k, Dv),
                lambda b, h, iq, ik: (b, h // G, ck(iq, ik), 0),
            ),
        ],
        out_specs=[o_spec, lse_spec] if with_lse else [o_spec],
        out_shape=[o_shape, lse_shape] if with_lse else [o_shape],
        scratch_shapes=[
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, Dv), jnp.float32),
        ],
        interpret=interpret,
    )(q_pos, kv_pos, q_seg, kv_seg, kv_valid, q, k, v)
    if with_lse:
        return res[0], res[1][..., 0]
    return res[0], None


def _row_outer(row, n: int):
    """[1, bq] per-q-row scalars → [bq, n] tile with the scalar repeated
    along lanes: rank-1 outer product rowᵀ·1 on the MXU. Avoids a
    sublane↔lane relayout of the scalar vector."""
    ones = jnp.ones((1, n), jnp.float32)
    return jax.lax.dot_general(
        row, ones, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )


def _dq_kernel(
    qpos_ref, kpos_ref, qseg_ref, kseg_ref, kvalid_ref,
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
    dq_ref,
    dq_scr,
    *,
    scale: float,
    causal: bool,
    has_segments: bool,
    kv_arange: bool,
    block_k: int,
):
    """dq = (p ∘ (do·vᵀ − Δ)) · k · scale, accumulated over kv tiles.
    Same grid/masking layout as the forward kernel."""
    ik, nk = pl.program_id(3), pl.num_programs(3)

    @pl.when(ik == 0)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    if causal and kv_arange:
        run = ik * block_k <= jnp.max(qpos_ref[0])
    else:
        run = True

    @pl.when(run)
    def _step():
        q = q_ref[0, 0]
        k = k_ref[0, 0]
        v = v_ref[0, 0]
        do = do_ref[0, 0]
        bk = k.shape[0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale

        mask = kvalid_ref[0, :1, :] > 0
        if causal:
            mask = jnp.logical_and(
                mask, qpos_ref[0, :, :1] >= kpos_ref[0, :1, :]
            )
        if has_segments:
            mask = jnp.logical_and(
                mask, qseg_ref[0, :, :1] == kseg_ref[0, :1, :]
            )
        s = jnp.where(mask, s, NEG)
        lse_mat = _row_outer(lse_ref[0, 0, :1, :], bk)  # [bq, bk]
        p = jnp.exp(s - lse_mat)  # [bq, bk] fp32

        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [bq, bk]
        ds = p * (dp - _row_outer(delta_ref[0, 0, :1, :], bk)) * scale
        dq_scr[:] += jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(ik == nk - 1)
    def _finalize():
        dq_ref[0, 0] = dq_scr[:].astype(dq_ref.dtype)


def _dkv_kernel(
    qpos_ref, kpos_ref, qseg_ref, kseg_ref, kvalid_ref,
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
    dk_ref, dv_ref,
    dk_scr, dv_scr,
    *,
    scale: float,
    causal: bool,
    has_segments: bool,
    kv_arange: bool,
    q_arange: bool,
    block_q: int,
    block_k: int,
):
    """dk/dv for one kv tile, accumulated over all (group-head, q-tile)
    steps. Grid (B, Hk, nk, G, nq): the two innermost dims revisit the same
    kv/output blocks, so GQA head-group reduction happens in VMEM scratch.
    """
    g, iq = pl.program_id(3), pl.program_id(4)
    nG, nq = pl.num_programs(3), pl.num_programs(4)

    @pl.when(jnp.logical_and(g == 0, iq == 0))
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    ik = pl.program_id(2)
    if causal and kv_arange and q_arange:
        # Prefill: q tiles entirely before this kv tile contribute
        # nothing. Keyed on program ids (NOT qpos_ref — its index map
        # aliases dead q tiles onto live ones for the DMA skip). Padded q
        # rows past the real length still run but contribute zeros (do is
        # zero there).
        run = ik * block_k <= (iq + 1) * block_q - 1
    elif causal and kv_arange:
        # Arbitrary q positions (decode layout): no q-side aliasing, so
        # the actual positions bound the live kv range.
        run = ik * block_k <= jnp.max(qpos_ref[0])
    else:
        run = True

    @pl.when(run)
    def _step():
        q = q_ref[0, 0]    # [bq, D]
        k = k_ref[0, 0]    # [bk, D]
        v = v_ref[0, 0]
        do = do_ref[0, 0]  # [bq, D]
        bk = k.shape[0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale  # [bq, bk]

        mask = kvalid_ref[0, :1, :] > 0
        if causal:
            mask = jnp.logical_and(
                mask, qpos_ref[0, :, :1] >= kpos_ref[0, :1, :]
            )
        if has_segments:
            mask = jnp.logical_and(
                mask, qseg_ref[0, :, :1] == kseg_ref[0, :1, :]
            )
        s = jnp.where(mask, s, NEG)
        p = jnp.exp(s - _row_outer(lse_ref[0, 0, :1, :], bk))  # [bq, bk]

        dv_scr[:] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [bk, D]
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [bq, bk]
        ds = p * (dp - _row_outer(delta_ref[0, 0, :1, :], bk)) * scale
        dk_scr[:] += jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [bk, D]

    @pl.when(jnp.logical_and(g == nG - 1, iq == nq - 1))
    def _finalize():
        dk_ref[0, 0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_scr[:].astype(dv_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "has_segments", "kv_arange", "q_arange",
                     "scale", "interpret"),
)
def _mha_backward(
    q, k, v, do, lse, delta, q_pos, kv_pos, q_seg, kv_seg, kv_valid,
    *,
    causal: bool,
    has_segments: bool,
    kv_arange: bool,
    q_arange: bool,
    scale: float,
    interpret: bool,
):
    """Layouts as _mha_forward, plus do [B, Hq, Tq, D] and lse/delta
    [B, Hq, Tq] (all padded to block multiples)."""
    B, Hq, Tq, D = q.shape
    _, Hk, Tk, _ = k.shape
    G = Hq // Hk
    block_q = _bwd_block(BWD_BLOCK_Q, BLOCK_Q, Tq)
    block_k = _bwd_block(BWD_BLOCK_K, BLOCK_K, Tk)
    nq = Tq // block_q
    nk = Tk // block_k

    LANES, SUB = 128, 8
    q_pos_l = jnp.broadcast_to(q_pos[:, :, None], (B, Tq, LANES))
    q_seg_l = jnp.broadcast_to(q_seg[:, :, None], (B, Tq, LANES))
    kv_pos_s = jnp.broadcast_to(kv_pos[:, None, :], (B, SUB, Tk))
    kv_seg_s = jnp.broadcast_to(kv_seg[:, None, :], (B, SUB, Tk))
    kv_valid_s = jnp.broadcast_to(kv_valid[:, None, :], (B, SUB, Tk))
    # Per-q-row scalars in the compact 8-sublane layout ([B, Hq, 8, Tq],
    # 16x smaller than lane-broadcast); kernels re-expand per tile with a
    # rank-1 outer product (_row_outer).
    lse_s = jnp.broadcast_to(lse[:, :, None, :], (B, Hq, SUB, Tq))
    delta_s = jnp.broadcast_to(delta[:, :, None, :], (B, Hq, SUB, Tq))

    common = dict(
        scale=scale, causal=causal, has_segments=has_segments,
        kv_arange=kv_arange,
    )

    ckv = _causal_kv_clamp(block_q, block_k, causal and kv_arange and q_arange)
    cq = _causal_q_clamp(block_q, block_k, causal and kv_arange and q_arange)

    dq = pl.pallas_call(
        functools.partial(_dq_kernel, block_k=block_k, **common),
        grid=(B, Hq, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, LANES), lambda b, h, iq, ik: (b, iq, 0)),
            pl.BlockSpec(
                (1, SUB, block_k), lambda b, h, iq, ik: (b, 0, ckv(iq, ik))
            ),
            pl.BlockSpec((1, block_q, LANES), lambda b, h, iq, ik: (b, iq, 0)),
            pl.BlockSpec(
                (1, SUB, block_k), lambda b, h, iq, ik: (b, 0, ckv(iq, ik))
            ),
            pl.BlockSpec(
                (1, SUB, block_k), lambda b, h, iq, ik: (b, 0, ckv(iq, ik))
            ),
            pl.BlockSpec(
                (1, 1, block_q, D), lambda b, h, iq, ik: (b, h, iq, 0)
            ),
            pl.BlockSpec(
                (1, 1, block_k, D),
                lambda b, h, iq, ik: (b, h // G, ckv(iq, ik), 0),
            ),
            pl.BlockSpec(
                (1, 1, block_k, D),
                lambda b, h, iq, ik: (b, h // G, ckv(iq, ik), 0),
            ),
            pl.BlockSpec(
                (1, 1, block_q, D), lambda b, h, iq, ik: (b, h, iq, 0)
            ),
            pl.BlockSpec(
                (1, 1, SUB, block_q), lambda b, h, iq, ik: (b, h, 0, iq)
            ),
            pl.BlockSpec(
                (1, 1, SUB, block_q), lambda b, h, iq, ik: (b, h, 0, iq)
            ),
        ],
        out_specs=pl.BlockSpec(
            (1, 1, block_q, D), lambda b, h, iq, ik: (b, h, iq, 0)
        ),
        out_shape=jax.ShapeDtypeStruct((B, Hq, Tq, D), jnp.float32),
        scratch_shapes=[pltpu.VMEM((block_q, D), jnp.float32)],
        interpret=interpret,
    )(q_pos_l, kv_pos_s, q_seg_l, kv_seg_s, kv_valid_s,
      q, k, v, do, lse_s, delta_s)

    dk, dv = pl.pallas_call(
        functools.partial(
            _dkv_kernel, block_q=block_q, block_k=block_k,
            q_arange=q_arange, **common
        ),
        grid=(B, Hk, nk, G, nq),
        in_specs=[
            pl.BlockSpec(
                (1, block_q, LANES),
                lambda b, hk, ik, g, iq: (b, cq(ik, iq), 0),
            ),
            pl.BlockSpec(
                (1, SUB, block_k), lambda b, hk, ik, g, iq: (b, 0, ik)
            ),
            pl.BlockSpec(
                (1, block_q, LANES),
                lambda b, hk, ik, g, iq: (b, cq(ik, iq), 0),
            ),
            pl.BlockSpec(
                (1, SUB, block_k), lambda b, hk, ik, g, iq: (b, 0, ik)
            ),
            pl.BlockSpec(
                (1, SUB, block_k), lambda b, hk, ik, g, iq: (b, 0, ik)
            ),
            pl.BlockSpec(
                (1, 1, block_q, D),
                lambda b, hk, ik, g, iq: (b, hk * G + g, cq(ik, iq), 0),
            ),
            pl.BlockSpec(
                (1, 1, block_k, D), lambda b, hk, ik, g, iq: (b, hk, ik, 0)
            ),
            pl.BlockSpec(
                (1, 1, block_k, D), lambda b, hk, ik, g, iq: (b, hk, ik, 0)
            ),
            pl.BlockSpec(
                (1, 1, block_q, D),
                lambda b, hk, ik, g, iq: (b, hk * G + g, cq(ik, iq), 0),
            ),
            pl.BlockSpec(
                (1, 1, SUB, block_q),
                lambda b, hk, ik, g, iq: (b, hk * G + g, 0, cq(ik, iq)),
            ),
            pl.BlockSpec(
                (1, 1, SUB, block_q),
                lambda b, hk, ik, g, iq: (b, hk * G + g, 0, cq(ik, iq)),
            ),
        ],
        out_specs=[
            pl.BlockSpec(
                (1, 1, block_k, D), lambda b, hk, ik, g, iq: (b, hk, ik, 0)
            ),
            pl.BlockSpec(
                (1, 1, block_k, D), lambda b, hk, ik, g, iq: (b, hk, ik, 0)
            ),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, Hk, Tk, D), jnp.float32),
            jax.ShapeDtypeStruct((B, Hk, Tk, D), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, D), jnp.float32),
            pltpu.VMEM((block_k, D), jnp.float32),
        ],
        interpret=interpret,
    )(q_pos_l, kv_pos_s, q_seg_l, kv_seg_s, kv_valid_s,
      q, k, v, do, lse_s, delta_s)
    return dq, dk, dv


def _use_interpret() -> bool:
    return jax.default_backend() != "tpu"


def flash_attention(
    q, k, v,
    *,
    causal: bool = True,
    q_positions=None,
    kv_positions=None,
    q_segment_ids=None,
    kv_segment_ids=None,
    kv_mask=None,
    scale: float | None = None,
    slot_positions: bool = False,
    window: int = 0,
):
    """Drop-in for ops.attention.attention with identical masking model.

    q: [B, Tq, Hq, D]; k/v: [B, Tk, Hk, D]. Returns [B, Tq, Hq, D].

    slot_positions: static caller promise that every VALID token's
    position equals its slot index (the right-padded prefill layout:
    positions are per-row arange with masked pads). Enables the causal
    tile skips (compute + DMA) that plain arange layouts get, while the
    mask math still uses the explicit position arrays.

    window > 0 (causal): the sliding-window lower bound, t - u < window,
    in the forward kernel alone: the serving path of a config with
    window layers. It has no backward kernel (train such a config under
    attn_impl="xla").
    """
    if scale is None:
        scale = q.shape[-1] ** -0.5
    if window:
        assert causal, "a sliding window is a bound beside the causal one"
        return _flash_attention_impl(
            q, k, v, q_positions, kv_positions, q_segment_ids,
            kv_segment_ids, kv_mask, causal, float(scale),
            slot_positions=slot_positions, window=int(window),
        )[0]
    return _flash_vjp(
        q, k, v, q_positions, kv_positions, q_segment_ids, kv_segment_ids,
        kv_mask, causal, float(scale), slot_positions,
    )


@functools.partial(jax.custom_vjp, nondiff_argnums=(8, 9, 10))
def _flash_vjp(
    q, k, v, q_positions, kv_positions, q_segment_ids, kv_segment_ids,
    kv_mask, causal, scale, slot_positions,
):
    return _flash_attention_impl(
        q, k, v, q_positions, kv_positions, q_segment_ids, kv_segment_ids,
        kv_mask, causal, scale, slot_positions=slot_positions,
    )[0]


def _prepare(q, k, v, q_positions, kv_positions, q_segment_ids,
             kv_segment_ids, kv_mask, causal, scale,
             slot_positions=False):
    """Normalize/pad every operand to the kernel layouts. Returns the
    padded tensors plus the static flags shared by forward and backward."""
    B, Tq, Hq, D = q.shape
    _, Tk, Hk, _ = k.shape
    if scale is None:
        scale = D**-0.5

    block_q = min(BLOCK_Q, _round_up(Tq, 16))
    block_k = min(BLOCK_K, _round_up(Tk, 16))
    Tq_p = _round_up(Tq, block_q)
    Tk_p = _round_up(Tk, block_k)

    kv_arange = kv_positions is None or slot_positions
    q_arange = q_positions is None or slot_positions
    if q_positions is None:
        q_positions = jnp.broadcast_to(jnp.arange(Tq, dtype=jnp.int32), (B, Tq))
    if kv_positions is None:
        kv_positions = jnp.broadcast_to(
            jnp.arange(Tk, dtype=jnp.int32), (B, Tk)
        )
    has_segments = q_segment_ids is not None
    if has_segments:
        assert kv_segment_ids is not None
        q_seg = jnp.broadcast_to(q_segment_ids, (B, Tq)).astype(jnp.int32)
        kv_seg = jnp.broadcast_to(kv_segment_ids, (B, Tk)).astype(jnp.int32)
    else:
        q_seg = jnp.zeros((B, Tq), jnp.int32)
        kv_seg = jnp.zeros((B, Tk), jnp.int32)
    kv_valid = (
        jnp.broadcast_to(kv_mask, (B, Tk)).astype(jnp.int32)
        if kv_mask is not None
        else jnp.ones((B, Tk), jnp.int32)
    )

    # Pad sequence dims to block multiples. Padded kv is invalid; padded q
    # rows produce garbage that is sliced off. Padded q positions stay 0 so
    # the causal-skip bound never extends the loop.
    qt = _pad_axis(q.swapaxes(1, 2), 2, Tq_p)  # [B, Hq, Tq_p, D]
    kt = _pad_axis(k.swapaxes(1, 2), 2, Tk_p)
    vt = _pad_axis(v.swapaxes(1, 2), 2, Tk_p)
    q_pos = _pad_axis(q_positions.astype(jnp.int32), 1, Tq_p)
    kv_pos = _pad_axis(kv_positions.astype(jnp.int32), 1, Tk_p)
    q_seg = _pad_axis(q_seg, 1, Tq_p, fill=-1)
    kv_seg = _pad_axis(kv_seg, 1, Tk_p, fill=-2)
    kv_valid = _pad_axis(kv_valid, 1, Tk_p)
    flags = dict(
        causal=causal, has_segments=has_segments, kv_arange=kv_arange,
        q_arange=q_arange, scale=float(scale), interpret=_use_interpret(),
    )
    return (qt, kt, vt, q_pos, kv_pos, q_seg, kv_seg, kv_valid), flags, Tq


def _flash_attention_impl(
    q, k, v, q_positions, kv_positions, q_segment_ids, kv_segment_ids,
    kv_mask, causal, scale, with_lse=False, slot_positions=False, window=0,
):
    padded, flags, Tq = _prepare(
        q, k, v, q_positions, kv_positions, q_segment_ids, kv_segment_ids,
        kv_mask, causal, scale, slot_positions=slot_positions,
    )
    if window:
        flags["window"] = window
    out, lse = _mha_forward(*padded, with_lse=with_lse, **flags)
    return out[:, :, :Tq].swapaxes(1, 2), lse


def _fwd(q, k, v, q_positions, kv_positions, q_segment_ids, kv_segment_ids,
         kv_mask, causal, scale, slot_positions):
    out, lse = _flash_attention_impl(
        q, k, v, q_positions, kv_positions, q_segment_ids, kv_segment_ids,
        kv_mask, causal, scale, with_lse=True, slot_positions=slot_positions,
    )
    # Under block remat, a policy that saves these names (utils/remat.py
    # "attn") keeps the kernel output + softmax stats across the forward,
    # so the backward's block recompute reuses them instead of re-running
    # the forward kernel — the single most expensive recomputed op.
    out = checkpoint_name(out, "flash_out")
    lse = checkpoint_name(lse, "flash_lse")
    res = (q, k, v, out, lse, q_positions, kv_positions, q_segment_ids,
           kv_segment_ids, kv_mask)
    return out, res


def _bwd(causal, scale, slot_positions, res, g):
    """Flash backward: Pallas dq and dk/dv kernels using the saved
    logsumexp — O(T) memory (vs the O(T²) recompute fallback)."""
    (q, k, v, out, lse, q_positions, kv_positions, q_segment_ids,
     kv_segment_ids, kv_mask) = res
    B, Tq, Hq, D = q.shape

    padded, flags, _ = _prepare(
        q, k, v, q_positions, kv_positions, q_segment_ids, kv_segment_ids,
        kv_mask, causal, scale, slot_positions=slot_positions,
    )
    qt = padded[0]
    Tq_p = qt.shape[2]
    # Δ_i = Σ_d dOᵢ·Oᵢ in fp32, padded like q (zeros: padded do is zero).
    delta = jnp.einsum(
        "bqhd,bqhd->bhq", g.astype(jnp.float32), out.astype(jnp.float32)
    )
    delta = _pad_axis(delta, 2, Tq_p)
    do = _pad_axis(g.swapaxes(1, 2), 2, Tq_p)

    dq, dk, dv = _mha_backward(
        padded[0], padded[1], padded[2], do, lse, delta,
        padded[3], padded[4], padded[5], padded[6], padded[7],
        **flags,
    )
    Tk = k.shape[1]
    dq = dq[:, :, :Tq].swapaxes(1, 2).astype(q.dtype)
    dk = dk[:, :, :Tk].swapaxes(1, 2).astype(k.dtype)
    dv = dv[:, :, :Tk].swapaxes(1, 2).astype(v.dtype)
    return (dq, dk, dv, None, None, None, None, None)


_flash_vjp.defvjp(_fwd, _bwd)
