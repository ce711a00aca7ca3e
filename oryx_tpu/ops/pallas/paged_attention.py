"""Pallas TPU paged attention (ragged KV through block tables).

One kernel, two entry points:

  * `ragged_decode_attention` — the single-token decode twin of
    `ops.paged_kv.ragged_decode_attention`: [B, 1] queries, one
    sequence per batch row. A decode row is a length-1 ragged lane,
    so it runs the packed kernel with segment b, position len-1.
  * `ragged_paged_attention` — the PACKED ragged kernel (arXiv
    2604.15464): R query rows drawn from many sequences with MIXED
    query lengths (decode steps and chunked-prefill suffix tokens side
    by side), each walking its OWN sequence's block table via
    scalar-prefetched (segment, position) metadata and causally masked
    at its own position. This is the kernel behind the serving
    engine's one-dispatch-per-step path
    (models/generate.paged_ragged_step); its kv-head tile comes from
    `ragged_heads_per_block`, which offers only sizes the TPU lowering
    accepts.

    Speculative decoding rides the SAME kernel unchanged: a slot's 1+k
    verify lanes (ops/paged_kv.spec_lane_metadata) are just 1+k more
    (segment, position) rows of the R-row grid — consecutive positions
    of one segment, exactly the shape a chunked-prefill suffix already
    exercises, so the R axis grows from S+pf to S*(1+k)+pf and nothing
    else moves. The grid stays static per (S, k, pf_width) class; the
    per-row page walk, dead-tile DMA elision and tail masking are
    position-driven and need no notion of "draft".

The point of both: attention over a sequence's pages happens IN
PLACE — the block table is a scalar-prefetch operand, so each kv
tile's DMA source address is computed from it before the tile runs,
and no [B, max_len] contiguous copy of the cache is ever materialized
(the XLA reference gathers one per layer per step; at 7B serving
shapes that gather IS the decode bandwidth bill).

Shares the flash-attention kernel skeleton (ops/pallas/
flash_attention.py): pages innermost and sequential, online-softmax
(m, l, acc) state in VMEM scratch, fp32 logits/softmax, probs·V in the
value dtype. The GQA group dimension rides INSIDE the tile (q is
reshaped [R, Hk, G, D]), so every grid step issues one [G, page_size]
logit matmul per kv head — the decode-shaped analogue of the prefill
kernel's [block_q, block_k] tiles. Raggedness is handled per packed
row (see the comment above `_ragged_kernel`).

Interpret mode runs the same kernel on CPU for tests; on a TPU the
Mosaic kernel runs (tests/test_pallas_topology_compile.py compiles it
at Oryx-7B geometry for a described v5e).
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from oryx_tpu.ops.pallas import flash_attention as _flash

NEG = -0.7 * float(jnp.finfo(jnp.float32).max)


def ragged_decode_attention(
    q,  # [B, 1, Hq, D] or [B, Hq, D]
    k_pages,  # [P, page_size, Hk, D]
    v_pages,
    block_tables,  # [B, max_pages] int32 (sentinel >= P for unallocated)
    kv_lengths,  # [B] valid kv count INCLUDING the current token
    *,
    scale: float | None = None,
    interpret: bool | None = None,
):
    """Drop-in for ops.paged_kv.ragged_decode_attention (same contract).
    A decode row is a length-1 ragged lane: row b is packed row b of
    segment b at position kv_lengths[b] - 1, so this runs the packed
    kernel below (a row with kv_lengths == 0 sees no tile and returns
    zeros)."""
    squeezed = q.ndim == 3
    if squeezed:
        q = q[:, None]
    B, Tq, Hq, D = q.shape
    assert Tq == 1, f"paged decode is single-token (got Tq={Tq})"
    out = ragged_paged_attention(
        q[:, 0], k_pages, v_pages, block_tables,
        jnp.arange(B, dtype=jnp.int32),
        kv_lengths.astype(jnp.int32) - 1,
        scale=scale, interpret=interpret,
    )
    return out if squeezed else out[:, None]


def _is_quant(k_pages) -> bool:
    from oryx_tpu.ops import paged_kv as _pk

    return isinstance(k_pages, _pk.QuantPages)


def _split_quant(k_pages, v_pages):
    """(k_codes, k_scale, v_codes, v_scale, dequant_dtype_str) of a
    quantized pool pair — both planes must be quantized together (a
    mixed pool would silently misread one side's bytes)."""
    from oryx_tpu.ops import paged_kv as _pk

    if not isinstance(v_pages, _pk.QuantPages):
        raise ValueError(
            "quantized K pages with dense V pages: the pool must "
            "quantize both planes (qwen2.init_paged_kv_cache kv_dtype=)"
        )
    return (
        k_pages.q, k_pages.scale, v_pages.q, v_pages.scale,
        str(k_pages.dequant_dtype),
    )


# ---------------------------------------------------------------------------
# Packed ragged kernel: mixed query lengths, one grid, per-row block tables
# ---------------------------------------------------------------------------
#
# Grid (R, Hk // HB, maxp): packed row outermost, kv-head tile, pages
# innermost and sequential so the online-softmax scratch carries across
# a row's page walk. Each grid step DMAs ONE page tile of HB kv heads
# ([1, ps, HB, D], contiguous in the pool) and issues HB [G, ps] logit
# matmuls. Raggedness per packed row r (seg = q_segments[r],
# pos = q_positions[r]):
#   * tiles wholly past pos skip compute AND DMA (index map clamps dead
#     page ids onto the last live page; Pallas elides the repeat DMA);
#   * the tail tile masks slots > pos to -inf before the softmax —
#     the causal mask and the validity mask are the SAME mask here,
#     which is what lets decode rows (pos = len-1) and prefill-suffix
#     rows (consecutive pos) share the kernel;
#   * sentinel block-table entries clip into the pool for address
#     safety (only reachable masked).

# heads_per_block (HB, kv heads per page tile) trades DMA count against
# VMEM residency. The TPU lowering only takes a kv tile
# [1, ps, HB, D] over the [P, ps, Hk, D] pool when HB is the whole kv
# head axis or a multiple of 8 that divides it, so those are the only
# tile sizes ever offered; anything else is an error naming the value,
# on every backend, so an interpret-mode test cannot pass on a tile the
# chip's compiler would refuse.

# Keep the double-buffered kv tile (2 * ps * HB * D * 4B fp32) within a
# conservative slice of VMEM alongside q/out/scratch.
_RAGGED_KV_TILE_BUDGET = 1 << 21  # 2 MiB


def legal_heads_per_block(num_kv_heads: int) -> tuple[int, ...]:
    """The kv-head tile sizes the TPU lowering accepts, ascending."""
    Hk = int(num_kv_heads)
    return tuple(
        hb for hb in range(1, Hk + 1)
        if Hk % hb == 0 and (hb == Hk or hb % 8 == 0)
    )


def check_heads_per_block(
    heads_per_block: int, num_kv_heads: int, origin: str = "heads_per_block"
) -> int:
    hb = int(heads_per_block)
    legal = legal_heads_per_block(num_kv_heads)
    if hb not in legal:
        raise ValueError(
            f"{origin}={hb} cannot compile for num_kv_heads="
            f"{int(num_kv_heads)}: the TPU lowering takes only the whole "
            f"kv head axis or a multiple of 8 dividing it (legal: "
            f"{list(legal)})"
        )
    return hb


def ragged_heads_per_block(
    head_dim: int, page_size: int, num_kv_heads: int
) -> int:
    """kv heads per tile for the ragged kernel: the
    $ORYX_RPA_HEADS_PER_BLOCK operator pin if set (an illegal pin is an
    error, never clamped), else the largest legal tile inside the VMEM
    budget (the smallest legal one when none fits)."""
    env = os.environ.get("ORYX_RPA_HEADS_PER_BLOCK")
    if env:
        return check_heads_per_block(
            int(env), num_kv_heads, "$ORYX_RPA_HEADS_PER_BLOCK"
        )
    legal = legal_heads_per_block(num_kv_heads)
    fits = [
        hb for hb in legal
        if 2 * page_size * hb * head_dim * 4 <= _RAGGED_KV_TILE_BUDGET
    ]
    return fits[-1] if fits else legal[0]


def _scale_column(row):
    """[1, ps] scale row -> [ps, 1] column. The scale tile arrives
    lane-major (one contiguous DMA per page); Mosaic has no
    lane-to-sublane reshape, so select the diagonal of the broadcast
    row and reduce over lanes — exact: each sum has one nonzero term."""
    n = row.shape[1]
    diag = jax.lax.broadcasted_iota(
        jnp.int32, (n, n), 0
    ) == jax.lax.broadcasted_iota(jnp.int32, (n, n), 1)
    return jnp.sum(jnp.where(diag, row, 0.0), axis=1, keepdims=True)


def _ragged_kernel(
    bt_ref,  # [S, maxp] SMEM (scalar prefetch)
    seg_ref,  # [R] SMEM
    pos_ref,  # [R] SMEM
    *refs,  # q, k, [k_scale], v, [v_scale], o, scratch x3
    scale: float,
    page_size: int,
    num_groups: int,
    heads_per_block: int,
    dequant_dtype: str | None = None,
):
    # Quantized pool: each page tile arrives as storage-dtype codes
    # plus its [1, 1, ps] scale block (fetched through the SAME
    # block-table-driven index map), and the dequant happens HERE, in
    # the page walk — int8 is what crossed HBM. The multiply matches
    # ops.paged_kv.gather_pages' dequant elementwise (same dtype, same
    # broadcast), preserving the kernels' bit-parity contract.
    if dequant_dtype is None:
        q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr = refs
        ks_ref = vs_ref = None
    else:
        (q_ref, k_ref, ks_ref, v_ref, vs_ref, o_ref,
         m_scr, l_scr, acc_scr) = refs
    r, ik = pl.program_id(0), pl.program_id(2)
    nk = pl.num_programs(2)
    G, HB = num_groups, heads_per_block
    Gp = m_scr.shape[0] // HB

    @pl.when(ik == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    length = pos_ref[r] + 1  # visible kv count for this packed row
    run = ik * page_size < length

    @pl.when(run)
    def _step():
        slot = ik * page_size + jax.lax.broadcasted_iota(
            jnp.int32, (1, page_size), 1
        )
        if ks_ref is not None:
            dq = jnp.dtype(dequant_dtype)
            k_sc = _scale_column(ks_ref[0]).astype(dq)  # [ps, 1]
            v_sc = _scale_column(vs_ref[0]).astype(dq)
        for h in range(HB):  # static unroll over the kv-head tile
            q = q_ref[0, h]  # [G, D]
            k = k_ref[0, :, h, :]  # [ps, D]
            v = v_ref[0, :, h, :]
            if ks_ref is not None:
                k = k.astype(dq) * k_sc
                v = v.astype(dq) * v_sc
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) * scale  # [G, ps] fp32
            # Causal == validity: slots past this row's own position
            # are invisible, whether they belong to its future tokens
            # (prefill-suffix packing) or to nobody yet (decode).
            s = jnp.where(slot < length, s, NEG)
            lo = h * Gp
            m_prev = m_scr[lo:lo + G, :1]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(s - m_new)  # [G, ps] fp32
            l_new = l_scr[lo:lo + G, :1] * alpha + jnp.sum(
                p, axis=-1, keepdims=True
            )
            m_scr[lo:lo + G, :] = jnp.broadcast_to(
                m_new, (G, m_scr.shape[1])
            )
            l_scr[lo:lo + G, :] = jnp.broadcast_to(
                l_new, (G, l_scr.shape[1])
            )
            pv = jax.lax.dot_general(
                p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            acc_scr[lo:lo + G, :] = acc_scr[lo:lo + G, :] * alpha + pv

    @pl.when(ik == nk - 1)
    def _finalize():
        for h in range(HB):
            lo = h * Gp
            l = l_scr[lo:lo + G, :1]
            out = acc_scr[lo:lo + G, :] / jnp.where(l == 0.0, 1.0, l)
            o_ref[0, h] = out.astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=(
        "scale", "page_size", "heads_per_block", "interpret",
        "dequant_dtype",
    ),
)
def _ragged_paged(
    q,  # [R, Hk, G, D]
    k_pages,  # [P, ps, Hk, D] (codes when quantized)
    v_pages,
    block_tables,  # [S, maxp] int32
    q_segments,  # [R] int32
    q_positions,  # [R] int32
    k_scale=None,  # [P, 1, ps] fp32 per-page scale blocks (quantized pool)
    v_scale=None,
    *,
    scale: float,
    page_size: int,
    heads_per_block: int,
    interpret: bool,
    dequant_dtype: str | None = None,
):
    R, Hk, G, D = q.shape
    P = k_pages.shape[0]
    S, maxp = block_tables.shape
    HB = heads_per_block

    def _page(r, ik, bt_ref, seg_ref, pos_ref):
        # Clamp dead tiles onto the row's last live page (DMA elision)
        # and sentinel entries into the pool; the segment picks WHICH
        # sequence's table this row walks.
        s = jnp.clip(seg_ref[r], 0, S - 1)
        last = jnp.maximum(pos_ref[r], 0) // page_size
        page = bt_ref[s, jnp.minimum(ik, last)]
        return jnp.minimum(page, P - 1)

    def kv_map(r, hb, ik, bt_ref, seg_ref, pos_ref):
        return (_page(r, ik, bt_ref, seg_ref, pos_ref), 0, hb, 0)

    def sc_map(r, hb, ik, bt_ref, seg_ref, pos_ref):
        # The scale block rides the same block-table stream as its
        # code tile.
        return (_page(r, ik, bt_ref, seg_ref, pos_ref), 0, 0)

    grid = (R, Hk // HB, maxp)
    Gp = max(G, 8)  # scratch sublane floor
    quant = dequant_dtype is not None
    in_specs = [
        pl.BlockSpec((1, HB, G, D), lambda r, hb, ik, *_: (r, hb, 0, 0)),
        pl.BlockSpec((1, page_size, HB, D), kv_map),
    ]
    operands = [q, k_pages]
    if quant:
        in_specs.append(pl.BlockSpec((1, 1, page_size), sc_map))
        operands.append(k_scale)
    in_specs.append(pl.BlockSpec((1, page_size, HB, D), kv_map))
    operands.append(v_pages)
    if quant:
        in_specs.append(pl.BlockSpec((1, 1, page_size), sc_map))
        operands.append(v_scale)
    out = pl.pallas_call(
        functools.partial(
            _ragged_kernel, scale=scale, page_size=page_size,
            num_groups=G, heads_per_block=HB,
            dequant_dtype=dequant_dtype,
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=grid,
            in_specs=in_specs,
            out_specs=pl.BlockSpec(
                (1, HB, G, D), lambda r, hb, ik, *_: (r, hb, 0, 0)
            ),
            scratch_shapes=[
                pltpu.VMEM((HB * Gp, 128), jnp.float32),
                pltpu.VMEM((HB * Gp, 128), jnp.float32),
                pltpu.VMEM((HB * Gp, D), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((R, Hk, G, D), q.dtype),
        interpret=interpret,
    )(block_tables.astype(jnp.int32), q_segments.astype(jnp.int32),
      q_positions.astype(jnp.int32), *operands)
    return out


def ragged_paged_attention(
    q,  # [R, Hq, D] packed query rows
    k_pages,  # [P, page_size, Hk, D]
    v_pages,
    block_tables,  # [S, max_pages] int32 (sentinel >= P for unallocated)
    q_segments,  # [R] owning slot per packed row
    q_positions,  # [R] absolute position per packed row
    *,
    scale: float | None = None,
    interpret: bool | None = None,
    heads_per_block: int | None = None,
):
    """Drop-in for ops.paged_kv.ragged_paged_attention (same contract):
    R packed query rows with mixed query lengths, each reading its own
    sequence's pages in place through the block table. The kv-head
    tile is `ragged_heads_per_block`'s unless pinned; a pin the TPU
    lowering would refuse raises. A quantized pool
    (ops.paged_kv.QuantPages planes) is read as codes + per-page scale
    blocks and dequantized inside the page walk."""
    R, Hq, D = q.shape
    Hk = k_pages.shape[2]
    assert Hq % Hk == 0, f"GQA requires Hq % Hk == 0, got {Hq=} {Hk=}"
    G = Hq // Hk
    if scale is None:
        scale = D**-0.5
    if interpret is None:
        interpret = _flash._use_interpret()
    if heads_per_block is None:
        heads_per_block = ragged_heads_per_block(
            D, int(k_pages.shape[1]), Hk
        )
    else:
        heads_per_block = check_heads_per_block(heads_per_block, Hk)
    k_scale = v_scale = None
    dequant = None
    if _is_quant(k_pages):
        k_pages, k_scale, v_pages, v_scale, dequant = _split_quant(
            k_pages, v_pages
        )
        # The stored scale plane stays [P, ps]; the lowering needs a
        # block whose last two dims are whole, so present it [P, 1, ps]
        # (a free reshape: same bytes, same order).
        P, ps = k_scale.shape
        k_scale = k_scale.reshape(P, 1, ps)
        v_scale = v_scale.reshape(P, 1, ps)
    # h = hk * G + g (the repo's GQA head order: h // G == hk).
    qg = q.reshape(R, Hk, G, D)
    out = _ragged_paged(
        qg, k_pages, v_pages, block_tables, q_segments, q_positions,
        k_scale, v_scale,
        scale=float(scale), page_size=int(k_pages.shape[1]),
        heads_per_block=int(heads_per_block), interpret=bool(interpret),
        dequant_dtype=dequant,
    )
    return out.reshape(R, Hq, D)
