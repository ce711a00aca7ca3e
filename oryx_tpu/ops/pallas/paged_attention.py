"""Pallas TPU paged attention (ragged KV through block tables).

One kernel, two entry points:

  * `ragged_decode_attention` — the single-token decode twin of
    `ops.paged_kv.ragged_decode_attention`: [B, 1] queries, one
    sequence per batch row. A decode row is a length-1 ragged lane,
    so it runs the packed kernel with segment b, position len-1; a
    finished or empty lane is a row of length 0.
  * `ragged_paged_attention` — the PACKED ragged kernel (arXiv
    2604.15464): R query rows drawn from many sequences with MIXED
    query lengths (decode steps and chunked-prefill suffix tokens side
    by side), each walking its OWN sequence's block table via
    scalar-prefetched (segment, position) metadata and causally masked
    at its own position. This is the kernel behind the serving
    engine's step programs (models/generate.paged_decode_chunk,
    paged_block_step, paged_ragged_step).

    Speculative decoding and block diffusion ride the SAME kernel
    unchanged: a slot's 1+k verify lanes (ops/paged_kv.
    spec_lane_metadata) or its B block lanes are just more (segment,
    position) rows — consecutive or shared positions of one segment,
    exactly the shape a chunked-prefill suffix already exercises. The
    grid is static per row count; the walk and the tail masking are
    position-driven and need no notion of "draft" or "block".

The point of both: attention over a sequence's pages happens IN
PLACE — the pool stays in HBM, the kernel copies a row's live pages
out of it through the block table, and no [B, max_len] contiguous copy
of the cache is ever materialized (the XLA reference gathers one per
layer per step; at 7B serving shapes that gather IS the decode
bandwidth bill). What a call costs follows the KV its rows can see:
the walk takes a step for every `ragged_pages_per_block` pages a row
reads and none for a page nobody reads (see the comment above
`_ragged_kernel`).

Shares the flash-attention kernel's mathematics (ops/pallas/
flash_attention.py): online-softmax (m, l, acc) state in VMEM scratch
carried across a row's walk, fp32 logits/softmax, probs·V in the value
dtype, fp32 accumulation.

Interpret mode runs the same kernel on CPU for tests; on a TPU the
Mosaic kernel runs (tests/test_pallas_topology_compile.py compiles it
at both serving geometries for a described v5e).
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
    window: int = 0,
):
    """Drop-in for ops.paged_kv.ragged_decode_attention (same contract).
    A decode row is a length-1 ragged lane: row b is packed row b of
    segment b at position kv_lengths[b] - 1, so this runs the packed
    kernel below (a row with kv_lengths == 0 sees no tile and returns
    zeros). window > 0: the row sees its last `window` keys only, its
    first visible position max(0, kv_lengths - window)."""
    squeezed = q.ndim == 3
    if squeezed:
        q = q[:, None]
    B, Tq, Hq, D = q.shape
    assert Tq == 1, f"paged decode is single-token (got Tq={Tq})"
    lengths = kv_lengths.astype(jnp.int32)
    out = ragged_paged_attention(
        q[:, 0], k_pages, v_pages, block_tables,
        jnp.arange(B, dtype=jnp.int32), lengths - 1,
        scale=scale, interpret=interpret,
        **({"q_first": jnp.maximum(lengths - window, 0)} if window else {}),
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
# Grid (R,): one step a packed row, in order. The pool stays in HBM
# (memory space HBM), viewed [P, ps * Hk, D] — a free reshape, same
# bytes in the same order — so a page is ONE contiguous copy into fully
# used VMEM tiles, its rows interleaved token-major (row t * Hk + h is
# kv head h of token t). The kernel fetches pages itself, through the
# scalar-prefetched block table, into a double-buffered VMEM scratch.
# The walk of packed row r (seg = q_segments[r], pos = q_positions[r],
# length = pos + 1):
#   * it takes ceil(length / (npb * ps)) steps of a loop whose bound is
#     read from the prefetched positions — none for a row of length 0,
#     which only zeroes its output. No step exists for a page nobody
#     reads, and within a step only live pages are copied;
#   * a step handles a block of npb pages. While it computes, the next
#     block is in flight: the row's own next block or, on its last, the
#     NEXT ROW's first block (the buffer slot is handed across grid
#     steps in SMEM), so a row's first copy is hidden behind the row
#     before it;
#   * a step is one [HB * G, npb * ps * Hk] logit product against the
#     block as it lies in VMEM, one softmax update and one p.V product
#     for a tile of HB kv heads: a q head sees the columns of its own
#     kv head and of tokens <= pos, every other column is masked before
#     the softmax and so meets V as an exact zero. The interleaved rows
#     are never unpacked, the MXU loads each K and V row as a weight
#     once either way, and the causal mask and the validity mask are
#     the SAME mask — which is what lets decode rows (pos = len - 1),
#     prefill-suffix / verify rows (consecutive pos) and block rows
#     share the kernel. Every packed row keeps its own mask and its own
#     (m, l, acc);
#   * sentinel block-table entries clip into the pool for address
#     safety (only reachable masked). The V buffers are zeroed once a
#     call: a masked column multiplies stale but finite VMEM.

# heads_per_block (HB) is the tile of kv heads whose q heads share one
# logit product; it bounds the [HB * G, npb * ps * Hk] fp32 logit tile
# and nothing about the copies (a page always arrives whole). The sizes
# offered stay the ones the TPU lowering takes as a sublane tile: the
# whole kv head axis or a multiple of 8 that divides it; anything else
# is an error naming the value, on every backend, so an interpret-mode
# test cannot pass on a tile the chip's compiler would refuse.

# Keep the double-buffered kv block (2 * npb * ps * Hk * D * 4B, counted
# as fp32: a quantized block is dequantized whole) within a conservative
# slice of VMEM alongside q/out/scratch. It sizes both the head tile's
# default and the pages a step handles.
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


def ragged_pages_per_block(
    head_dim: int, page_size: int, num_kv_heads: int, max_pages: int
) -> int:
    """Pages one step of a row's walk handles (npb), from shapes alone:
    as many whole pages as the double-buffered block may hold under the
    VMEM budget, at least one, at most the block table's width. At the
    serving geometries (page 64, 4 kv heads, D 128) that is 8 pages,
    512 tokens."""
    page = 2 * page_size * num_kv_heads * head_dim * 4
    return max(1, min(int(max_pages), _RAGGED_KV_TILE_BUDGET // page))


def _block_scales(scale, block_tables, num_kv_heads: int, npb: int):
    """[P, ps] scale plane -> [S, nblk, 1, npb * ps * Hk]: for every
    block of every segment's walk, the scale of each row of the packed
    block (a token's scale once a kv head, as the page interleaves
    them), gathered through the SAME block table the code pages are
    copied by. Mosaic copies nothing narrower than 128 lanes and a
    page's scale row is ps wide, so the rows are laid out a block at a
    time ahead of the kernel (4 bytes a token a segment; the codes are
    Hk * D a token) and the walk copies a block's row beside its
    pages."""
    P = scale.shape[0]
    S, maxp = block_tables.shape
    nblk = -(-maxp // npb)
    bt = jnp.pad(block_tables, ((0, 0), (0, nblk * npb - maxp)))
    rows = jnp.repeat(scale[jnp.clip(bt, 0, P - 1)], num_kv_heads, axis=-1)
    return rows.reshape(S, nblk, 1, -1)


def _scale_column(row):
    """[1, n] scale row -> [n, 1] column (Mosaic transposes whole
    sublane tiles: broadcast to one, transpose, keep a lane)."""
    return jnp.broadcast_to(row, (8, row.shape[1])).T[:, :1]


def _walk_row(
    bt_ref,  # [S, maxp] SMEM (scalar prefetch)
    seg_ref,  # [R] SMEM
    pos_ref,  # [R] SMEM
    slot_ref,  # [1] SMEM scratch: the buffer slot of the block in flight
    scratch,  # (m, l, acc) VMEM: the row's running softmax
    *,
    num_pages: int,
    page_size: int,
    pages_per_block: int,
    page_copies,  # (page, j, slot) -> copies of page j of a block
    block_copies,  # (seg, blk, slot) -> a block's further copies, or None
    zero,  # () -> None: buffers a masked column must find finite
    block_step,  # (length) -> ((i, slot) -> None): fold block i in
    first_ref=None,  # [R] SMEM: a row's first visible position, or None
):
    """One grid step of a page walk: packed row r reads its own live
    pages, `pages_per_block` at a time, double-buffered. A block's
    pages are copied through the scalar-prefetched block table while
    the block before computes; a row's last block (or a row of length
    0) starts the NEXT row's first copy, the buffer slot handed across
    grid steps in `slot_ref`. What a page is and what a block adds to
    the running softmax are the caller's (`_ragged_kernel`,
    `_latent_kernel`). With `first_ref` the walk starts at the block
    that holds the row's first visible position and copies no page
    wholly before it (the caller masks the keys before it)."""
    r, R = pl.program_id(0), pl.num_programs(0)
    S, maxp = bt_ref.shape
    P, ps, npb = num_pages, page_size, pages_per_block
    m_scr, l_scr, acc_scr = scratch

    def visible(row):
        """(kv tokens, pages) packed row `row` reads."""
        length = jnp.clip(pos_ref[row] + 1, 0, maxp * ps)
        return length, pl.cdiv(length, ps)

    def first_page(row):
        """The page that holds `row`'s first visible position."""
        return jnp.clip(first_ref[row], 0, maxp * ps - 1) // ps

    def first_block(row):
        return 0 if first_ref is None else first_page(row) // npb

    def copies(row, blk, slot):
        """[(live, copies)] of block `blk` of `row`'s walk into buffer
        `slot`: an entry a page, and one for a block's further copies.
        Rebuilt with the same arguments to wait."""
        seg = jnp.clip(seg_ref[row], 0, S - 1)
        _, pages = visible(row)
        out = []
        for j in range(npb):
            pg = blk * npb + j
            page = jnp.clip(bt_ref[seg, jnp.minimum(pg, maxp - 1)], 0, P - 1)
            cps = page_copies(page, j, slot)
            live = pg < pages
            if first_ref is not None:
                live = live & (pg >= first_page(row))
            out.append((live, cps))
        if block_copies is not None:
            cps = block_copies(seg, blk, slot)
            out.append((blk * npb < pages, cps))
        return out

    def each_copy(row, blk, slot, do):
        for live, cps in copies(row, blk, slot):
            @pl.when(live)
            def _():
                for cp in cps:
                    do(cp)

    def start(row, blk, slot):
        each_copy(row, blk, slot, lambda cp: cp.start())

    def wait(row, blk, slot):
        each_copy(row, blk, slot, lambda cp: cp.wait())

    @pl.when(r == 0)
    def _first():
        zero()
        slot_ref[0] = 0
        start(0, first_block(0), 0)

    m_scr[...] = jnp.full_like(m_scr, NEG)
    l_scr[...] = jnp.zeros_like(l_scr)
    acc_scr[...] = jnp.zeros_like(acc_scr)

    length, pages = visible(r)
    nblk = pl.cdiv(pages, npb)
    nxt = jnp.minimum(r + 1, R - 1)
    has_next = r + 1 < R
    slot0 = slot_ref[0]
    fold = block_step(length)

    def step(i, slot):
        # Next in flight: this row's next block, else the next row's
        # first, into the buffer the block before this one has left.
        in_row = i + 1 < nblk

        @pl.when(in_row | has_next)
        def _():
            start(
                jnp.where(in_row, r, nxt),
                jnp.where(in_row, i + 1, first_block(nxt)),
                1 - slot,
            )

        wait(r, i, slot)
        fold(i, slot)
        return 1 - slot

    # A row with nothing to read still hands the walk on.
    @pl.when((nblk == 0) & has_next)
    def _():
        start(nxt, first_block(nxt), slot0)

    slot_ref[0] = jax.lax.fori_loop(first_block(r), nblk, step, slot0)


def _fold_block(s, v, rows, scratch):
    """Masked logits s [n, brow] (fp32) and values v [brow, Dv] of one
    block folded into rows `rows` of the running (m, l, acc)."""
    m_scr, l_scr, acc_scr = scratch
    n = s.shape[0]
    m_prev = m_scr[rows, :1]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)  # exact 0 in every masked column
    l_new = l_scr[rows, :1] * alpha + jnp.sum(p, axis=-1, keepdims=True)
    m_scr[rows, :] = jnp.broadcast_to(m_new, (n, m_scr.shape[1]))
    l_scr[rows, :] = jnp.broadcast_to(l_new, (n, l_scr.shape[1]))
    pv = jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    acc_scr[rows, :] = acc_scr[rows, :] * alpha + pv


def _write_row(o_ref, scratch):
    """The row's output from its running (m, l, acc): 0 where it read
    nothing."""
    _, l_scr, acc_scr = scratch
    Hq = o_ref.shape[1]
    l = l_scr[:Hq, :1]
    out = acc_scr[:Hq, :] / jnp.where(l == 0.0, 1.0, l)
    o_ref[0] = out.astype(o_ref.dtype)


def _ragged_kernel(
    bt_ref,  # [S, maxp] SMEM (scalar prefetch)
    seg_ref,  # [R] SMEM
    pos_ref,  # [R] SMEM
    *refs,  # q, k, [k_scale], v, [v_scale], o, scratch
    scale: float,
    page_size: int,
    num_kv_heads: int,
    pages_per_block: int,
    heads_per_block: int,
    dequant_dtype: str | None = None,
    windowed: bool = False,
):
    # `windowed`: a fourth scalar-prefetched array leads `refs`, the
    # rows' first visible positions.
    first_ref = None
    if windowed:
        first_ref, *refs = refs
    # Quantized pool: a page arrives as storage-dtype codes, a block's
    # scales as one [1, brow] row (`_block_scales`), and the dequant
    # happens HERE, in the page walk — int8 is what crossed HBM. The
    # multiply matches ops.paged_kv.gather_pages' dequant elementwise
    # (same dtype, same broadcast).
    quant = dequant_dtype is not None
    if quant:
        (q_ref, k_hbm, ks_hbm, v_hbm, vs_hbm, o_ref, k_buf, v_buf,
         ks_buf, vs_buf, sems, slot_ref, *scratch) = refs
    else:
        (q_ref, k_hbm, v_hbm, o_ref, k_buf, v_buf,
         sems, slot_ref, *scratch) = refs
        ks_hbm = vs_hbm = ks_buf = vs_buf = None
    ps, Hk, npb, HB = page_size, num_kv_heads, pages_per_block, heads_per_block
    G = q_ref.shape[1] // Hk
    prow = ps * Hk  # rows of one page in the packed view
    brow = npb * prow  # rows of one block

    def page_copies(page, j, slot):
        dst = pl.ds(j * prow, prow)
        return [
            pltpu.make_async_copy(
                k_hbm.at[page], k_buf.at[slot, dst], sems.at[slot, 0]
            ),
            pltpu.make_async_copy(
                v_hbm.at[page], v_buf.at[slot, dst], sems.at[slot, 1]
            ),
        ]

    def scale_copies(seg, blk, slot):
        b = jnp.minimum(blk, ks_hbm.shape[1] - 1)
        return [
            pltpu.make_async_copy(
                ks_hbm.at[seg, b], ks_buf.at[slot], sems.at[slot, 2]
            ),
            pltpu.make_async_copy(
                vs_hbm.at[seg, b], vs_buf.at[slot], sems.at[slot, 3]
            ),
        ]

    def zero():
        # A masked column multiplies stale but finite VMEM.
        v_buf[...] = jnp.zeros_like(v_buf)

    def block_step(length):
        # Column c of a block is token c // Hk of the block, kv head
        # c % Hk.
        col = jax.lax.broadcasted_iota(jnp.int32, (1, brow), 1)
        col_tok, col_head = col // Hk, col % Hk
        if windowed:
            first = first_ref[pl.program_id(0)]

        def fold(i, slot):
            k, v = k_buf[slot], v_buf[slot]  # [brow, D]
            if quant:
                dq = jnp.dtype(dequant_dtype)
                k = k.astype(dq) * _scale_column(ks_buf[slot]).astype(dq)
                v = v.astype(dq) * _scale_column(vs_buf[slot]).astype(dq)
            seen = i * (npb * ps) + col_tok < length
            if windowed:  # the keys before the row's window
                seen = seen & (i * (npb * ps) + col_tok >= first)
            for t in range(Hk // HB):  # static unroll over kv-head tiles
                lo, n = t * HB * G, HB * G
                row_head = t * HB + jax.lax.broadcasted_iota(
                    jnp.int32, (n, 1), 0
                ) // G
                s = jax.lax.dot_general(
                    q_ref[0, lo:lo + n], k, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32,
                ) * scale  # [HB * G, brow] fp32
                # Causal == validity: slots past this row's own
                # position are invisible, whether they belong to its
                # future tokens (prefill-suffix packing) or to nobody
                # yet (decode).
                s = jnp.where(seen & (col_head == row_head), s, NEG)
                _fold_block(s, v, slice(lo, lo + n), scratch)

        return fold

    _walk_row(
        bt_ref, seg_ref, pos_ref, slot_ref, scratch,
        num_pages=k_hbm.shape[0], page_size=ps, pages_per_block=npb,
        page_copies=page_copies,
        block_copies=scale_copies if quant else None,
        zero=zero, block_step=block_step,
        **({"first_ref": first_ref} if windowed else {}),
    )
    _write_row(o_ref, scratch)


@functools.partial(
    jax.jit,
    static_argnames=(
        "scale", "heads_per_block", "interpret", "dequant_dtype", "windowed",
    ),
)
def _ragged_paged(
    q,  # [R, Hq, D]
    k_pages,  # [P, ps, Hk, D] (codes when quantized)
    v_pages,
    block_tables,  # [S, maxp] int32
    q_segments,  # [R] int32
    q_positions,  # [R] int32
    k_scale=None,  # [P, ps] fp32 per-token scales (quantized pool)
    v_scale=None,
    q_first=None,  # [R] int32 first visible position (window layers)
    *,
    scale: float,
    heads_per_block: int,
    interpret: bool,
    dequant_dtype: str | None = None,
    windowed: bool = False,  # q_first is given
):
    R, Hq, D = q.shape
    P, ps, Hk, _ = k_pages.shape
    maxp = block_tables.shape[1]
    npb = ragged_pages_per_block(D, ps, Hk, maxp)
    brow = npb * ps * Hk
    Hp = -(-Hq // 8) * 8  # scratch sublane floor
    quant = dequant_dtype is not None
    hbm = pl.BlockSpec(memory_space=pltpu.HBM)
    row = pl.BlockSpec((1, Hq, D), lambda r, *_: (r, 0, 0))
    # The packed view of the pool: same bytes, same order.
    k_pages = k_pages.reshape(P, ps * Hk, D)
    v_pages = v_pages.reshape(P, ps * Hk, D)
    in_specs, operands = [row, hbm], [q, k_pages]
    scratch = [
        pltpu.VMEM((2, brow, D), k_pages.dtype),
        pltpu.VMEM((2, brow, D), v_pages.dtype),
    ]
    if quant:
        in_specs += [hbm, hbm, hbm]
        operands += [
            _block_scales(k_scale, block_tables, Hk, npb), v_pages,
            _block_scales(v_scale, block_tables, Hk, npb),
        ]
        scratch += [
            pltpu.VMEM((2, 1, brow), k_scale.dtype),
            pltpu.VMEM((2, 1, brow), v_scale.dtype),
        ]
    else:
        in_specs.append(hbm)
        operands.append(v_pages)
    scratch += [
        pltpu.SemaphoreType.DMA((2, 4 if quant else 2)),
        pltpu.SMEM((1,), jnp.int32),  # buffer slot of the block in flight
        pltpu.VMEM((Hp, 128), jnp.float32),
        pltpu.VMEM((Hp, 128), jnp.float32),
        pltpu.VMEM((Hp, D), jnp.float32),
    ]
    prefetch = [block_tables, q_segments, q_positions] + (
        [q_first] if windowed else [])
    out = pl.pallas_call(
        functools.partial(
            _ragged_kernel, scale=scale, page_size=ps, num_kv_heads=Hk,
            pages_per_block=npb, heads_per_block=heads_per_block,
            dequant_dtype=dequant_dtype,
            **({"windowed": True} if windowed else {}),
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(prefetch),
            grid=(R,),
            in_specs=in_specs,
            out_specs=row,
            scratch_shapes=scratch,
        ),
        out_shape=jax.ShapeDtypeStruct((R, Hq, D), q.dtype),
        # Rows run in order: a row starts the next row's first copy.
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)
        ),
        interpret=interpret,
    )(*(a.astype(jnp.int32) for a in prefetch), *operands)
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
    q_first=None,  # [R] first visible position per packed row
):
    """Drop-in for ops.paged_kv.ragged_paged_attention (same contract):
    R packed query rows with mixed query lengths, each reading its own
    sequence's pages in place through the block table. The kv-head
    tile is `ragged_heads_per_block`'s unless pinned; a pin the TPU
    lowering would refuse raises. A quantized pool
    (ops.paged_kv.QuantPages planes) is read as codes + per-token
    scales and dequantized inside the page walk. `q_first` (window
    layers): row r sees positions q_first[r] .. q_positions[r] only; its
    walk starts at the block that holds q_first[r], a page wholly
    before it is not copied and the keys before it are masked. None:
    every row sees from position 0, the kernel as it was."""
    R, Hq, D = q.shape
    Hk = k_pages.shape[2]
    assert Hq % Hk == 0, f"GQA requires Hq % Hk == 0, got {Hq=} {Hk=}"
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
    # h = hk * G + g (the repo's GQA head order: h // G == hk).
    return _ragged_paged(
        q, k_pages, v_pages, block_tables, q_segments, q_positions,
        k_scale, v_scale,
        *(() if q_first is None else (q_first,)),
        **({} if q_first is None else {"windowed": True}),
        scale=float(scale), heads_per_block=int(heads_per_block),
        interpret=bool(interpret),
        dequant_dtype=dequant,
    )


# ---------------------------------------------------------------------------
# Latent (MLA) pool: absorbed decode over one shared key a token
# ---------------------------------------------------------------------------

# Tokens one step of a latent row's walk handles (whole pages): 512 x
# 640 bf16 values are 640 KiB a buffer, two buffers in flight.
_LATENT_BLOCK_TOKENS = 512


def _latent_kernel(
    bt_ref,  # [S, maxp] SMEM (scalar prefetch)
    seg_ref,  # [R] SMEM
    pos_ref,  # [R] SMEM
    q_ref,  # [1, Hq, Dp] absorbed queries (q_lat | q_rope | 0)
    c_hbm,  # [P, ps, Dp] the cache layer's pages
    o_ref,  # [1, Hq, Dv]
    c_buf, sems, slot_ref, *scratch,
    scale: float,
    page_size: int,
    pages_per_block: int,
    value_dim: int,
):
    """`_walk_row` over a pool with ONE plane and no head axis: every
    query head scores against the whole page row, and the row's first
    `value_dim` columns are the value, so a page is copied once for
    both products."""
    ps, npb = page_size, pages_per_block
    brow = npb * ps

    def page_copies(page, j, slot):
        return [pltpu.make_async_copy(
            c_hbm.at[page], c_buf.at[slot, pl.ds(j * ps, ps)], sems.at[slot],
        )]

    def zero():
        # Rows of a buffer no copy has reached yet are weighed by an
        # exact 0; they must not hold a NaN.
        c_buf[...] = jnp.zeros_like(c_buf)

    def block_step(length):
        col = jax.lax.broadcasted_iota(jnp.int32, (1, brow), 1)

        def fold(i, slot):
            c = c_buf[slot]  # [brow, Dp]
            s = jax.lax.dot_general(
                q_ref[0], c, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) * scale  # [Hq, brow] fp32
            s = jnp.where(i * brow + col < length, s, NEG)
            _fold_block(s, c[:, :value_dim], slice(None), scratch)

        return fold

    _walk_row(
        bt_ref, seg_ref, pos_ref, slot_ref, scratch,
        num_pages=c_hbm.shape[0], page_size=ps, pages_per_block=npb,
        page_copies=page_copies, block_copies=None,
        zero=zero, block_step=block_step,
    )
    _write_row(o_ref, scratch)


@functools.partial(
    jax.jit, static_argnames=("scale", "value_dim", "interpret"),
)
def _latent_paged(
    q,  # [R, Hq, Dp]
    pages,  # [P, ps, Dp]
    block_tables,  # [S, maxp] int32
    q_segments,  # [R] int32
    q_positions,  # [R] int32
    *,
    scale: float,
    value_dim: int,
    interpret: bool,
):
    R, Hq, Dp = q.shape
    P, ps, _ = pages.shape
    npb = max(1, min(block_tables.shape[1], _LATENT_BLOCK_TOKENS // ps))
    row = lambda d: pl.BlockSpec((1, Hq, d), lambda r, *_: (r, 0, 0))  # noqa: E731
    return pl.pallas_call(
        functools.partial(
            _latent_kernel, scale=scale, page_size=ps, pages_per_block=npb,
            value_dim=value_dim,
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(R,),
            in_specs=[row(Dp), pl.BlockSpec(memory_space=pltpu.HBM)],
            out_specs=row(value_dim),
            scratch_shapes=[
                pltpu.VMEM((2, npb * ps, Dp), pages.dtype),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.SMEM((1,), jnp.int32),  # buffer slot in flight
                pltpu.VMEM((Hq, 128), jnp.float32),
                pltpu.VMEM((Hq, 128), jnp.float32),
                pltpu.VMEM((Hq, value_dim), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((R, Hq, value_dim), q.dtype),
        # Rows run in order: a row starts the next row's first copy.
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)
        ),
        interpret=interpret,
    )(block_tables.astype(jnp.int32), q_segments.astype(jnp.int32),
      q_positions.astype(jnp.int32), q, pages)


def latent_decode_attention(
    q,  # [B, Hq, Dp] absorbed queries
    pages,  # [P, page_size, Dp]
    block_tables,  # [B, max_pages]
    kv_lengths,  # [B] valid kv count INCLUDING the current token
    *,
    scale: float,
    value_dim: int,
    interpret: bool | None = None,
):
    """Drop-in for ops.paged_kv.latent_decode_attention: each row walks
    its own live pages in place, once for scores and values both; a row
    of length 0 takes no step of the walk and returns 0."""
    if interpret is None:
        interpret = _flash._use_interpret()
    B = q.shape[0]
    return _latent_paged(
        q, pages, block_tables, jnp.arange(B, dtype=jnp.int32),
        kv_lengths.astype(jnp.int32) - 1,
        scale=float(scale), value_dim=int(value_dim),
        interpret=bool(interpret),
    )


# ---------------------------------------------------------------------------
# Learned sparse attention: the indexer's scores over a row's paged keys
# ---------------------------------------------------------------------------


def _index_kernel(
    bt_ref,  # [S, maxp] SMEM (scalar prefetch)
    seg_ref,  # [R] SMEM
    pos_ref,  # [R] SMEM
    q_ref,  # [1, Hi, Di] index queries
    w_ref,  # [1, Hi, 128] float32 head weights, one value a row of lanes
    k_hbm,  # [P, ps, Di] the cache layer's index keys
    o_ref,  # [1, 1, maxp * ps] float32
    k_buf, sems, slot_ref, *scratch,
    page_size: int,
    pages_per_block: int,
):
    """`_walk_row` with no softmax: block i of a row's live pages gives
    sum_j w_j relu(q_j . k) for its tokens, written to the row's
    columns of the output as they come; what the walk never reaches, and
    the columns past the row's length, are -inf."""
    ps, npb = page_size, pages_per_block
    brow = npb * ps

    def page_copies(page, j, slot):
        return [pltpu.make_async_copy(
            k_hbm.at[page], k_buf.at[slot, pl.ds(j * ps, ps)], sems.at[slot],
        )]

    def zero():
        k_buf[...] = jnp.zeros_like(k_buf)

    o_ref[...] = jnp.full(o_ref.shape, -jnp.inf, o_ref.dtype)

    def block_step(length):
        col = jax.lax.broadcasted_iota(jnp.int32, (1, brow), 1)

        def fold(i, slot):
            s = jax.lax.dot_general(
                q_ref[0], k_buf[slot], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )  # [Hi, brow] fp32
            s = jnp.sum(
                jnp.maximum(s, 0.0) * w_ref[0][:, :1], axis=0, keepdims=True)
            o_ref[0, :, pl.ds(pl.multiple_of(i * brow, brow), brow)] = (
                jnp.where(i * brow + col < length, s, -jnp.inf))

        return fold

    _walk_row(
        bt_ref, seg_ref, pos_ref, slot_ref, scratch,
        num_pages=k_hbm.shape[0], page_size=ps, pages_per_block=npb,
        page_copies=page_copies, block_copies=None,
        zero=zero, block_step=block_step,
    )


@functools.partial(jax.jit, static_argnames=("interpret",))
def _dsa_index(q, w, pages, block_tables, q_segments, q_positions, *,
               interpret: bool):
    R, Hi, Di = q.shape
    P, ps, _ = pages.shape
    maxp = block_tables.shape[1]
    npb = max(1, min(maxp, _LATENT_BLOCK_TOKENS // ps))
    while maxp % npb:  # whole blocks: a block's columns are one store
        npb -= 1
    row = lambda d: pl.BlockSpec((1, Hi, d), lambda r, *_: (r, 0, 0))  # noqa: E731
    out = pl.pallas_call(
        functools.partial(_index_kernel, page_size=ps, pages_per_block=npb),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(R,),
            in_specs=[row(Di), row(128),
                      pl.BlockSpec(memory_space=pltpu.HBM)],
            out_specs=pl.BlockSpec(
                (1, 1, maxp * ps), lambda r, *_: (r, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((2, npb * ps, Di), pages.dtype),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.SMEM((1,), jnp.int32),  # buffer slot in flight
                # `_walk_row`'s running softmax, which this walk has not.
                pltpu.VMEM((8, 128), jnp.float32),
                pltpu.VMEM((8, 128), jnp.float32),
                pltpu.VMEM((8, 128), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((R, 1, maxp * ps), jnp.float32),
        # Rows run in order: a row starts the next row's first copy.
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)
        ),
        interpret=interpret,
    )(block_tables.astype(jnp.int32), q_segments.astype(jnp.int32),
      q_positions.astype(jnp.int32), q,
      jnp.broadcast_to(w.astype(jnp.float32)[..., None], (R, Hi, 128)),
      pages)
    return out[:, 0]


def index_scores(
    q,  # [B, Hi, Di] index queries of one decode row a lane
    w,  # [B, Hi] float32 head weights
    pages,  # [P, page_size, Di] index keys
    block_tables,  # [B, max_pages]
    kv_lengths,  # [B] valid kv count INCLUDING the current token
    *,
    interpret: bool | None = None,
):
    """Drop-in for ops.paged_kv.index_scores: each row walks its own live
    pages of index keys in place; a row of length 0 takes no step and
    returns -inf everywhere."""
    if interpret is None:
        interpret = _flash._use_interpret()
    B = q.shape[0]
    return _dsa_index(
        q, w, pages, block_tables, jnp.arange(B, dtype=jnp.int32),
        kv_lengths.astype(jnp.int32) - 1, interpret=bool(interpret),
    )
