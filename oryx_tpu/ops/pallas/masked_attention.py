"""Pallas TPU attention over one tile of keys under a mask every head
shares (learned sparse attention's prefill: models/qwen2._sparse_prefill).

`masked_attend` is the drop-in for `qwen2._attend_tile`, one step of an
online softmax over tiles of keys: softmax(q k^T * scale over `seen`) v
with no query-key pair outside `seen`, merged into the running
(maxima, sums, unnormalised output) of the tiles before. The XLA twin
(`qwen2._masked_attend` and the merge) writes a [heads, queries, keys]
float32 block of scores to HBM and reads it back for each of its
passes, then reads and writes the running output once more to merge;
here a block of scores lives in VMEM from the first product to the
second and the running state passes through the kernel, aliased in and
out.

The grid is (row, head), heads innermost: the mask's block does not
depend on the head, so it is copied once a row and turned into an
additive float32 bias (0 where seen, finfo.min where not) in scratch at
the row's first head. A head's step holds its queries, the tile's keys
and values whole, and takes the queries a block at a time against ALL
the tile's keys, so the arithmetic is the twin's: scores in float32, one
maximum a row over the tile, `p` cast to the values' dtype before the
second product, then the twin's merge. `s + bias` is `where(seen, s,
finfo.min)` to the bit (|s| is far under half an ulp of finfo.min), and
exp(finfo.min - m) is an exact 0 wherever a row has a key; a row with
none has m = finfo.min and adds l = 0 and o = 0, as the twin's second
`where` gives it.

Operands are head-major ([B, heads, ...]; the keys a key a COLUMN):
what XLA's expansion of the tile's latents writes at no cost, where a
[B, keys, heads x width] view cost a copy of the keys and a relayout of
the output a tile. The row statistics cross the kernel's edge as
lane-major rows [1, T] and are turned to columns and back inside it.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from oryx_tpu.ops.pallas import flash_attention as _flash

_MIN = float(jnp.finfo(jnp.float32).min)

# Query rows one pass of a head's step takes against the tile's keys.
_BLOCK_Q = 256


def _as_row(col):
    """[n, 1] -> [1, n]: a column of row statistics as the lane-major
    row the carry holds."""
    return jnp.transpose(jnp.broadcast_to(col, (col.shape[0], 128)))[:1]


def _as_col(row):
    """[1, n] -> [n, 1]: the carry's row as a column beside the scores."""
    return jnp.transpose(jnp.broadcast_to(row, (128, row.shape[1])))[:, :1]


def _attend_kernel(
    seen_ref,  # [1, T, Kt] int8, the same block for every head
    q_ref,  # [1, 1, T, d]
    k_ref,  # [1, 1, d, Kt] the head's keys, a key a column
    v_ref,  # [1, 1, Kt, dv]
    m_in, l_in,  # [1, 1, 1, T] float32: the tiles before
    acc_in,  # [1, 1, T, dv] float32
    m_ref, l_ref, acc_ref,  # the same, this tile merged in (aliased)
    bias,  # [T, Kt] float32 scratch
    *,
    scale: float,
    block_q: int,
):
    @pl.when(pl.program_id(1) == 0)
    def _():
        bias[...] = jnp.where(
            seen_ref[0].astype(jnp.int32) != 0, 0.0, _MIN)

    k, v = k_ref[0, 0], v_ref[0, 0]
    blocks = q_ref.shape[2] // block_q

    def block(i, _):
        # One block (a short chunk) sits at a static offset: a lane
        # offset into the statistics' rows must be provably 128-aligned.
        start = i * block_q
        rows = pl.ds(
            start if blocks == 1 else pl.multiple_of(start, block_q), block_q)
        s = jax.lax.dot_general(
            q_ref[0, 0, rows, :], k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale + bias[rows, :]  # [block_q, Kt] fp32
        m_t = jnp.max(s, axis=-1, keepdims=True)
        p = jnp.exp(s - m_t)
        some = m_t > _MIN  # the row has a key in this tile
        l_t = jnp.where(some, jnp.sum(p, axis=-1, keepdims=True), 0.0)
        o = jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_old = _as_col(m_in[0, 0, :, rows])
        m_new = jnp.maximum(m_old, m_t)
        a_old, a_new = jnp.exp(m_old - m_new), jnp.exp(m_t - m_new)
        acc_ref[0, 0, rows, :] = (
            acc_in[0, 0, rows, :] * a_old + jnp.where(some, o, 0.0) * a_new)
        m_ref[0, 0, :, rows] = _as_row(m_new)
        l_ref[0, 0, :, rows] = _as_row(
            _as_col(l_in[0, 0, :, rows]) * a_old + l_t * a_new)

    if blocks == 1:
        block(0, None)
    else:
        jax.lax.fori_loop(0, blocks, block, None)


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def _dsa_attend(m, l, acc, q, k, v, seen, *, scale: float, interpret: bool):
    B, T, Hq, d = q.shape
    Kt, dv = k.shape[1], v.shape[-1]
    block_q = min(T, _BLOCK_Q)
    if T % block_q or (not interpret and (T % 16 or Kt % 128)):
        raise ValueError(
            f"_dsa_attend: cannot tile {T} queries against {Kt} keys: it "
            f"takes the queries in blocks of {_BLOCK_Q} rows (or one "
            f"block of 16s) and the keys in 128s")
    head = lambda n, w: pl.BlockSpec(  # noqa: E731
        (1, 1, n, w), lambda b, h: (b, h, 0, 0))
    stats = jax.ShapeDtypeStruct((B, Hq, 1, T), jnp.float32)
    m, l, acc = pl.pallas_call(
        functools.partial(_attend_kernel, scale=scale, block_q=block_q),
        grid=(B, Hq),
        in_specs=[
            pl.BlockSpec((1, T, Kt), lambda b, h: (b, 0, 0)),
            head(T, d), head(d, Kt), head(Kt, dv),
            head(1, T), head(1, T), head(T, dv),
        ],
        out_specs=[head(1, T), head(1, T), head(T, dv)],
        out_shape=[stats, stats,
                   jax.ShapeDtypeStruct((B, Hq, T, dv), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((T, Kt), jnp.float32)],
        input_output_aliases={4: 0, 5: 1, 6: 2},
        # A row's heads run in order: the first one builds the bias.
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=64 * 1024 * 1024,
        ),
        interpret=interpret,
    )(seen.astype(jnp.int8), jnp.transpose(q, (0, 2, 1, 3)),
      jnp.transpose(k, (0, 2, 3, 1)), jnp.transpose(v, (0, 2, 1, 3)),
      m[:, :, None], l[:, :, None], jnp.transpose(acc, (0, 2, 1, 3)))
    return m[:, :, 0], l[:, :, 0], jnp.transpose(acc, (0, 2, 1, 3))


def masked_attend(carry, q, k, v, seen, scale, *,
                  interpret: bool | None = None):
    """Drop-in for models.qwen2._attend_tile: the running (row maxima
    and sums [B, Hq, T], unnormalised output [B, T, Hq, dv], float32)
    of the tiles before, q [B, T, Hq, d], one tile's expanded k
    [B, Kt, Hq, d] and v [B, Kt, Hq, dv], `seen` [B, T, Kt] (one mask
    for all heads) -> the same with this tile merged in. A row with no
    key in the tile leaves its state as it was."""
    if interpret is None:
        interpret = _flash._use_interpret()
    return _dsa_attend(
        *carry, q, k, v, seen, scale=float(scale), interpret=bool(interpret))
