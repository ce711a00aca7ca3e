"""The one-token state update of a Mamba-2 mixer, on the pool's `ssm`
plane WHOLE and in place.

A decode step of layer `li` over the B lanes (lane b IS slot b), with
the state S_b [N, d] float32 (channels in the lanes, head h the P
channels from h P, group g the d / G channels from g d / G):

    S_b <- a_b * S_b + dtx_b * B_b,g      a, dtx [d] a lane (the head's
                                          decay and D x, a channel)
    y_b  = sum_n S_b[n] * C_b,g[n]        B, C [G, N] a lane

which is all of the step that touches the state; the projections, the
conv, the skip term, the gate and the norm stay XLA's (`models/
mamba2.py`). THE PLANE GOES IN WHOLE AND COMES BACK ALIASED (`ssm`
[Lm, S, N, d], `input_output_aliases`), the layer's number a
scalar-prefetch argument, for the reason `ssm_step.py` gives: a kernel
handed the layer's [S, N, d] slice would have XLA copy 400 MB in and out
around it a layer-step. The unit of work is one GROUP of one LIVE lane
([N, d / G], 512 KB at the published widths), DEPTH reads and DEPTH
writes in flight; a dead lane's rows are neither read nor written, its y
is zeros. 2 x 4 N d bytes a live lane a layer is the whole of the
kernel's HBM traffic.

The kernel runs under the name `_ssd_step`, which is how a device trace
shows it, and only where its tiles fit (`fits`); every other shape, the
CPU's default and `attn_impl="xla"` keep `ssd_step_xla` on the layer's
rows sliced out.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from oryx_tpu.ops.pallas import flash_attention as _flash

DEPTH = 4  # units' states in flight each way
TILE = 512  # lanes of a unit the update handles at a time
VMEM_LIMIT = 64 * 1024 * 1024

f32 = jnp.float32


def fits(S: int, d: int, N: int, G: int) -> bool:
    """Whether the kernel's tiles fit: a group's channels and the
    state's rows in whole lane tiles."""
    return bool(S > 0 and d % G == 0 and (d // G) % 128 == 0 and N % 128 == 0)


def ssd_step_xla(a, dtx, bc, S0, G: int):
    """The update in XLA ops. a, dtx [B, d] float32; bc [B, 2 G N]
    float32 (B | C); S0 [B, N, d] float32. Returns (y [B, d] float32,
    S1)."""
    B, N, d = S0.shape
    Bm = jnp.swapaxes(bc[:, :G * N].reshape(B, G, N), 1, 2)[..., None]
    Cm = jnp.swapaxes(bc[:, G * N:].reshape(B, G, N), 1, 2)[..., None]
    S1 = (a.reshape(B, 1, G, -1) * S0.reshape(B, N, G, -1)
          + dtx.reshape(B, 1, G, -1) * Bm)
    y = jnp.sum(S1 * Cm, axis=1)
    return y.reshape(B, d), S1.reshape(B, N, d)


def _ssd_step(li_ref, live_ref, a_ref, dtx_ref, bc_ref, ssm_hbm, y_ref,
              out_hbm, lanes, hin, hout, sem_in, sem_out):
    """The whole step of one layer, one grid step. a, dtx, y [B, G, dg];
    bc [B, 2 G, N]; ssm_hbm, out_hbm [Lm, S, N, d], ONE buffer, of which
    the LIVE lanes' rows of layer li pass through `hin` / `hout` [DEPTH,
    N, dg] a group at a time; `lanes` [B] int32 in SMEM, the live lanes'
    numbers."""
    del ssm_hbm  # out_hbm is the same buffer
    B, G, dg = a_ref.shape
    N = hin.shape[1]
    li = li_ref[0]

    def count(b, n):
        @pl.when(live_ref[b] != 0)
        def _():
            lanes[n] = b
        return n + (live_ref[b] != 0).astype(jnp.int32)

    units = jax.lax.fori_loop(0, B, count, jnp.int32(0)) * G

    def rows(k):
        return out_hbm.at[li, lanes[k // G], :, pl.ds((k % G) * dg, dg)]

    def read(k):
        s = k % DEPTH
        return pltpu.make_async_copy(rows(k), hin.at[s], sem_in.at[s])

    def write(k):
        s = k % DEPTH
        return pltpu.make_async_copy(hout.at[s], rows(k), sem_out.at[s])

    for k in range(DEPTH):
        @pl.when(k < units)
        def _():
            read(k).start()

    y_ref[...] = jnp.zeros(y_ref.shape, y_ref.dtype)  # a dead lane's y
    eye = (jax.lax.broadcasted_iota(jnp.int32, (N, N), 0)
           == jax.lax.broadcasted_iota(jnp.int32, (N, N), 1))

    def unit(k, carry):
        b, g, s = lanes[k // G], k % G, k % DEPTH
        # B and C of the lane's group, lanes to sublanes by a mask.
        Bc = jnp.sum(jnp.where(eye, bc_ref[b, pl.ds(g, 1), :], 0.0),
                     axis=1, keepdims=True)
        Cc = jnp.sum(jnp.where(eye, bc_ref[b, pl.ds(G + g, 1), :], 0.0),
                     axis=1, keepdims=True)
        read(k).wait()

        @pl.when(k >= DEPTH)
        def _():
            write(k - DEPTH).wait()

        for lo in range(0, dg, TILE):
            at = slice(lo, min(lo + TILE, dg))
            h1 = (a_ref[b, pl.ds(g, 1), at] * hin[s, :, at]
                  + dtx_ref[b, pl.ds(g, 1), at] * Bc)
            hout[s, :, at] = h1
            y_ref[b, pl.ds(g, 1), at] = jnp.sum(
                h1 * Cc, axis=0, keepdims=True)
        write(k).start()

        @pl.when(k + DEPTH < units)
        def _():
            read(k + DEPTH).start()

        return carry

    jax.lax.fori_loop(0, units, unit, 0)
    for k in range(DEPTH):  # the last writes, one a buffer at most
        @pl.when(k < units)
        def _():
            write(k).wait()


def ssd_step(a, dtx, bc, live, ssm_pl, li, G: int):
    """a, dtx [B, d] float32; bc [B, 2 G N] float32; live [B] int32;
    ssm_pl [Lm, S, N, d] float32 with S == B; li the layer's number
    (int32 scalar). Returns (y [B, d] float32, zeros for a dead lane;
    ssm_pl with layer li's rows of the live lanes advanced)."""
    B, d = a.shape
    N = ssm_pl.shape[2]
    dg = d // G
    whole = lambda *shape: pl.BlockSpec(  # noqa: E731
        shape, lambda i, li, lv: (0,) * len(shape))
    hbm = pl.BlockSpec(memory_space=pltpu.HBM)
    y, ssm_pl = pl.pallas_call(
        _ssd_step,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(1,),
            in_specs=[whole(B, G, dg), whole(B, G, dg), whole(B, 2 * G, N),
                      hbm],
            out_specs=[whole(B, G, dg), hbm],
            scratch_shapes=[
                pltpu.SMEM((B,), jnp.int32),
                pltpu.VMEM((DEPTH, N, dg), f32),
                pltpu.VMEM((DEPTH, N, dg), f32),
                pltpu.SemaphoreType.DMA((DEPTH,)),
                pltpu.SemaphoreType.DMA((DEPTH,)),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((B, G, dg), f32),
            jax.ShapeDtypeStruct(ssm_pl.shape, ssm_pl.dtype),
        ],
        input_output_aliases={5: 1},  # the plane in place
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=VMEM_LIMIT,
        ),
        interpret=_flash._use_interpret(),
        name="_ssd_step",
    )(jnp.reshape(li, (1,)).astype(jnp.int32), live,
      a.reshape(B, G, dg), dtx.reshape(B, G, dg), bc.reshape(B, 2 * G, N),
      ssm_pl)
    return y.reshape(B, d), ssm_pl


# --- a prefill row's state, read and written by copies of its own -------
#
# The chunked prefill reads and leaves ONE slot's [N, d] a layer. As XLA
# ops (a gather and a scatter on the plane) the compiler is free to lay
# the whole plane out the way the chunk's products like it (N minor) and
# did: two transposing copies of all of it, 2 GB of temporaries and 8 GB
# of traffic a prefill chunk at the published widths. A kernel's operand
# keeps the layout it was declared with, so the plane passes through
# these two, whole and aliased, and only the row moves.


def _read_rows(li_ref, slots_ref, plane_hbm, out_hbm, sem):
    for b in range(out_hbm.shape[0]):
        copy = pltpu.make_async_copy(
            plane_hbm.at[li_ref[0], slots_ref[b]], out_hbm.at[b], sem)
        copy.start()
        copy.wait()


def read_rows(ssm_pl, li, slots):
    """ssm_pl [Lm, S, N, d]; li int32 scalar; slots [B] int32 ->
    ssm_pl[li, slots] [B, N, d]."""
    hbm = pl.BlockSpec(memory_space=pltpu.HBM)
    B = slots.shape[0]
    return pl.pallas_call(
        _read_rows,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(1,), in_specs=[hbm], out_specs=hbm,
            scratch_shapes=[pltpu.SemaphoreType.DMA(())]),
        out_shape=jax.ShapeDtypeStruct((B,) + ssm_pl.shape[2:], ssm_pl.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=_flash._use_interpret(),
        name="_ssd_read_rows",
    )(jnp.reshape(li, (1,)).astype(jnp.int32), slots.astype(jnp.int32),
      ssm_pl)


def _write_rows(li_ref, slots_ref, rows_hbm, plane_hbm, out_hbm, sem):
    del plane_hbm  # out_hbm is the same buffer
    for b in range(rows_hbm.shape[0]):
        copy = pltpu.make_async_copy(
            rows_hbm.at[b], out_hbm.at[li_ref[0], slots_ref[b]], sem)
        copy.start()
        copy.wait()


def write_rows(ssm_pl, li, slots, rows):
    """ssm_pl with rows [B, N, d] at [li, slots], in place."""
    hbm = pl.BlockSpec(memory_space=pltpu.HBM)
    return pl.pallas_call(
        _write_rows,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(1,), in_specs=[hbm, hbm],
            out_specs=hbm, scratch_shapes=[pltpu.SemaphoreType.DMA(())]),
        out_shape=jax.ShapeDtypeStruct(ssm_pl.shape, ssm_pl.dtype),
        input_output_aliases={3: 0},  # the plane in place
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=_flash._use_interpret(),
        name="_ssd_write_rows",
    )(jnp.reshape(li, (1,)).astype(jnp.int32), slots.astype(jnp.int32),
      rows.astype(ssm_pl.dtype), ssm_pl)
