"""The one-token step of a Mamba-1 mixer between its matmuls, on the
pool's per-slot planes WHOLE and in place.

A decode step of layer `li` over the B lanes (lane b IS slot b):

    win_b     = (conv[li, b] | x_b)                           [K, d]
    xc_b      = silu(b_c + sum_k w_c[k] * win_b[k])           `ssm_conv`
    conv[li, b] <- win_b[1:]
    [r|B|C]_b = W_x xc_b                                       (XLA, between)
    dt_b      = softplus(W_dt rms_norm(r_b) + b_dt)           `ssm_step`
    h_b       = exp(dt_b A) * ssm[li, b] + (dt_b xc_b) rms_norm(B_b)
    y_b       = h_b rms_norm(C_b) + D xc_b ;  out_b = y_b * silu(z_b)
    ssm[li, b] <- h_b

`x_proj` needs every channel of xc before any channel's dt exists, so
the step is TWO kernels around that one matmul. As XLA ops it was about
17 small fusions a layer-step, each a launch and a trip through HBM for a
[B, d] activation; as kernels the activations stay in VMEM and the only
HBM traffic left is the state's own (read once, written once).

THE PLANES GO IN WHOLE AND COME BACK ALIASED (`conv` [Lm, S, (K-1) d],
`ssm` [Lm, S, N, d] float32, `input_output_aliases`), the layer's number
a scalar-prefetch argument: the kernels touch layer li's rows alone. A
kernel handed the layer's [S, N, d] slice would have XLA copy 42 MB in
and out around it a layer-step. The stacked per-layer weights go in
whole the same way (a dynamic-slice in front of a custom call is a
device op of its own): `wdt` [Lm, R, d] as the parameters hold it, the
small ones as ONE float32 plane `chan` [Lm, rows, d] that the model
packs (`mamba.step_weights`, which knows the parameters' names; this
module sees arrays alone): rows DT_BIAS, D_SKIP, CONV_BIAS, NORM (the
three inner norms' weights laid out like a row of `x_proj`'s output),
the conv's K taps from CONV_W, zeros up to a whole sublane tile, then
A = -exp(A_log), N rows.

A lane with `live` false keeps its conv rows and its state BIT for bit:
`_ssm_conv` selects on what it stores, `_ssm_step` neither reads nor
writes a dead lane's state (its y is zeros, read by nobody), which also
saves the state traffic of the lanes that have finished. Layout as in
`selective_scan`: the state [N, d] float32, channels in the lanes; B_b,
C_b [N] along the sublanes, dt_b, xc_b along the lanes. dt, A, the state
and the update are float32, exp is exact; xc, z, y and the two matmuls'
products round to the serving dtype where `mamba.mixer_step` rounds
them.

The kernels run under the names `_ssm_conv` and `_ssm_step`, which is
how a device trace shows them, and only where their tiles fit (`fits`);
every other shape, the CPU's default and `attn_impl="xla"` keep
`mamba.mixer_step` on the layer's rows sliced out.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from oryx_tpu.ops.pallas import flash_attention as _flash
from oryx_tpu.ops.pallas.selective_scan import _tile

SLOTS = 16  # lanes a grid step of `_ssm_conv`: a bfloat16 sublane tile
DEPTH = 8  # lanes' states in flight each way in `_ssm_step`
VMEM_LIMIT = 64 * 1024 * 1024

# Rows of the per-channel plane `chan`; the conv taps w_c[0..K-1] follow
# CONV_W, A the first `head_rows(K)`.
DT_BIAS, D_SKIP, CONV_BIAS, NORM, CONV_W = 0, 1, 2, 3, 4

f32 = jnp.float32


def head_rows(K: int) -> int:
    """Rows of `chan` ahead of A: whole sublane tiles."""
    return -(-(CONV_W + K) // 8) * 8


def _lanes(S: int) -> int:
    """Lanes a grid step: SLOTS where they divide the pool's slots, a
    small pool (the comparison's twin, a test) whole, else 0."""
    if S % SLOTS == 0:
        return SLOTS
    return S if S < SLOTS else 0


def fits(S: int, d: int, N: int, W: int) -> bool:
    """Whether the kernels' tiles fit a pool of S slots, d channels, a
    state of N rows and `x_proj`'s W = R + 2 N outputs: channels in
    whole lane tiles, N in whole sublane tiles."""
    return bool(_tile(d) and N % 8 == 0 and W <= d and _lanes(S))


# --- the conv window ---------------------------------------------------


def _ssm_conv(li_ref, x_ref, live_ref, chan_hbm, conv_ref, xc_ref, out_ref,
              chan, sem, *, tile: int):
    """One block of lanes. x, xc [sb, d]; live [sb, 1] int32; chan_hbm
    [Lm, rows, d] float32, layer li's rows copied to `chan` in the
    first grid step; conv, out [sb, (K-1) d] (the same rows of the
    plane)."""
    @pl.when(pl.program_id(0) == 0)
    def _weights():
        copy = pltpu.make_async_copy(
            chan_hbm.at[li_ref[0], pl.ds(0, chan.shape[0])], chan, sem)
        copy.start()
        copy.wait()

    d = x_ref.shape[1]
    K = conv_ref.shape[1] // d + 1
    keep = live_ref[...] != 0
    for lo in range(0, d, tile):
        at = lambda k: slice(k * d + lo, k * d + lo + tile)  # noqa: E731
        x = x_ref[:, at(0)]
        taps = [conv_ref[:, at(k)] for k in range(K - 1)]
        new = taps[1:] + [x.astype(conv_ref.dtype)]
        win = [t.astype(x.dtype).astype(f32) for t in taps] + [x.astype(f32)]
        acc = chan[CONV_W:CONV_W + 1, at(0)] * win[0]
        for k in range(1, K):
            acc = acc + chan[CONV_W + k:CONV_W + k + 1, at(0)] * win[k]
        acc = acc + chan[CONV_BIAS:CONV_BIAS + 1, at(0)]
        xc_ref[:, at(0)] = (acc * jax.nn.sigmoid(acc)).astype(xc_ref.dtype)
        for k in range(K - 1):
            out_ref[:, at(k)] = jnp.where(
                keep, new[k].astype(f32), taps[k].astype(f32)
            ).astype(out_ref.dtype)


def ssm_conv(xz, chan, live, conv_pl, li):
    """The conv half of the step. xz [B, 2 d] (`in_proj`'s output, x in
    the first half); chan [Lm, rows, d] float32 (the module's
    docstring); live [B, 1] int32; conv_pl [Lm, S, (K-1) d] with
    S == B; li the layer's number (int32 scalar). Returns (xc [B, d] in
    xz's dtype, conv_pl with layer li's rows of the live lanes shifted
    by one input)."""
    B, d = xz.shape[0], xz.shape[1] // 2
    sb = _lanes(B)
    head = head_rows(conv_pl.shape[2] // d + 1)
    plane = pl.BlockSpec(
        (None, sb, conv_pl.shape[2]), lambda i, li: (li[0], i, 0))
    xc, conv_pl = pl.pallas_call(
        partial(_ssm_conv, tile=_tile(d)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B // sb,),
            in_specs=[
                pl.BlockSpec((sb, d), lambda i, li: (i, 0)),
                pl.BlockSpec((sb, 1), lambda i, li: (i, 0)),
                pl.BlockSpec(memory_space=pltpu.HBM),
                plane,
            ],
            out_specs=[pl.BlockSpec((sb, d), lambda i, li: (i, 0)), plane],
            scratch_shapes=[
                pltpu.VMEM((head, d), f32), pltpu.SemaphoreType.DMA(())],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((B, d), xz.dtype),
            jax.ShapeDtypeStruct(conv_pl.shape, conv_pl.dtype),
        ],
        input_output_aliases={4: 1},  # the plane in place
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=VMEM_LIMIT,
        ),
        interpret=_flash._use_interpret(),
        name="_ssm_conv",
    )(jnp.reshape(li, (1,)).astype(jnp.int32), xz, live, chan, conv_pl)
    return xc, conv_pl


# --- selection, state update, readout, gate -----------------------------


def _ssm_step(li_ref, live_ref, xc_ref, z_ref, rbc_ref, wdt_ref, chan_hbm,
              ssm_hbm, y_ref, out_hbm,
              chan, dt_scr, x_scr, g_scr, bc_scr, y_scr, lanes, hin, hout,
              sem, sem_in, sem_out, *, eps: float, tile: int):
    """The whole step of one layer, one grid step. xc, z, y [B, d]; rbc
    [B, R + 2 N]; wdt [R, d]; chan_hbm [Lm, rows, d], layer li's rows
    copied to `chan` (A its last N); ssm_hbm, out_hbm [Lm, S, N, d],
    ONE buffer, of which the LIVE lanes' [N, d] rows of layer li pass
    through `hin` / `hout` [DEPTH, N, d], DEPTH reads and DEPTH writes
    in flight: a dead lane's rows are neither read nor written. Further
    scratch, float32: dt, x, g = silu(z), y [B, d]; bc (the normed row)
    [B, R + 2 N]; `lanes` [B] int32 in SMEM, the live lanes' numbers."""
    del ssm_hbm  # out_hbm is the same buffer
    _, N, d = hin.shape
    B, W = rbc_ref.shape
    R = W - 2 * N
    dtype = rbc_ref.dtype
    chunks = range(0, d, tile)
    li = li_ref[0]

    def count(b, n):
        @pl.when(live_ref[b] != 0)
        def _():
            lanes[n] = b
        return n + (live_ref[b] != 0).astype(jnp.int32)

    n_live = jax.lax.fori_loop(0, B, count, jnp.int32(0))

    def read(k):
        s = k % DEPTH
        return pltpu.make_async_copy(
            out_hbm.at[li, lanes[k]], hin.at[s], sem_in.at[s])

    def write(k):
        s = k % DEPTH
        return pltpu.make_async_copy(
            hout.at[s], out_hbm.at[li, lanes[k]], sem_out.at[s])

    for k in range(DEPTH):  # the first states fly under the selection
        @pl.when(k < n_live)
        def _():
            read(k).start()

    copy = pltpu.make_async_copy(chan_hbm.at[li], chan, sem)
    copy.start()
    rbc = rbc_ref[...].astype(f32)
    col = jax.lax.broadcasted_iota(jnp.int32, (B, W), 1)
    sq = rbc * rbc
    scale = jnp.zeros_like(rbc)
    for lo, n in ((0, R), (R, N), (R + N, N)):  # r | B | C
        part = (col >= lo) & (col < lo + n)
        var = jnp.sum(jnp.where(part, sq, 0.0), axis=1, keepdims=True) / n
        scale = jnp.where(part, 1.0 / jnp.sqrt(var + eps), scale)
    normed = (rbc * scale).astype(dtype).astype(f32)
    copy.wait()
    normed = (chan[NORM:NORM + 1, :W] * normed).astype(dtype)
    bc_scr[...] = normed.astype(f32)
    r = normed[:, :R]
    for lo in chunks:
        at = slice(lo, lo + tile)
        dt = jnp.dot(r, wdt_ref[:, at].astype(dtype),
                     preferred_element_type=f32)
        dt = dt.astype(dtype).astype(f32) + chan[DT_BIAS:DT_BIAS + 1, at]
        dt_scr[:, at] = jax.nn.softplus(dt)
        x_scr[:, at] = xc_ref[:, at].astype(f32)
        zf = z_ref[:, at].astype(f32)
        g_scr[:, at] = zf * jax.nn.sigmoid(zf)
        y_scr[:, at] = jnp.zeros((B, tile), f32)  # a dead lane's y

    A0 = chan.shape[0] - N
    row = jax.lax.broadcasted_iota(jnp.int32, (N, W), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (N, W), 1)

    def lane(k, carry):
        b, s = lanes[k], k % DEPTH
        bc = bc_scr[pl.ds(b, 1), :]  # [1, W]: lanes to sublanes by a mask
        Bc = jnp.sum(jnp.where(col == row + R, bc, 0.0), axis=1, keepdims=True)
        Cc = jnp.sum(
            jnp.where(col == row + R + N, bc, 0.0), axis=1, keepdims=True)
        read(k).wait()

        @pl.when(k >= DEPTH)
        def _():
            write(k - DEPTH).wait()

        for lo in chunks:
            at = slice(lo, lo + tile)
            dt = dt_scr[pl.ds(b, 1), at]
            x = x_scr[pl.ds(b, 1), at]
            h1 = jnp.exp(dt * chan[A0:, at]) * hin[s, :, at] + (dt * x) * Bc
            hout[s, :, at] = h1
            y = jnp.sum(h1 * Cc, axis=0, keepdims=True) \
                + chan[D_SKIP:D_SKIP + 1, at] * x
            y_scr[pl.ds(b, 1), at] = y * g_scr[pl.ds(b, 1), at]
        write(k).start()

        @pl.when(k + DEPTH < n_live)
        def _():
            read(k + DEPTH).start()

        return carry

    jax.lax.fori_loop(0, n_live, lane, 0)
    for k in range(DEPTH):  # the last writes, one a buffer at most
        @pl.when(k < n_live)
        def _():
            write(k).wait()

    y_ref[...] = y_scr[...].astype(y_ref.dtype)


def ssm_step(xc, xz, rbc, wdt, chan, live, ssm_pl, li, *, eps: float):
    """The other half. xc [B, d] (`ssm_conv`'s); xz [B, 2 d] (z in the
    second half); rbc [B, R + 2 N] = xc @ W_x; wdt [Lm, R, d] (the
    stacked `dt_proj`); chan, li as in `ssm_conv`; live [B] int32;
    ssm_pl [Lm, S, N, d] float32 with S == B. Returns (y * silu(z)
    [B, d] in xc's dtype, zeros for a dead lane; ssm_pl with layer li's
    rows of the live lanes advanced)."""
    B, d = xc.shape
    N, W = ssm_pl.shape[2], rbc.shape[1]
    whole = lambda *shape: pl.BlockSpec(  # noqa: E731
        shape, lambda i, li, lv: (0,) * len(shape))
    hbm = pl.BlockSpec(memory_space=pltpu.HBM)
    y, ssm_pl = pl.pallas_call(
        partial(_ssm_step, eps=eps, tile=_tile(d)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(1,),
            in_specs=[
                whole(B, d),
                pl.BlockSpec((B, d), lambda i, li, lv: (0, 1)),
                whole(B, W),
                pl.BlockSpec(
                    (None, W - 2 * N, d), lambda i, li, lv: (li[0], 0, 0)),
                hbm, hbm,
            ],
            out_specs=[whole(B, d), hbm],
            scratch_shapes=[
                pltpu.VMEM(chan.shape[1:], f32),
                pltpu.VMEM((B, d), f32), pltpu.VMEM((B, d), f32),
                pltpu.VMEM((B, d), f32), pltpu.VMEM((B, W), f32),
                pltpu.VMEM((B, d), f32),
                pltpu.SMEM((B,), jnp.int32),
                pltpu.VMEM((DEPTH, N, d), f32),
                pltpu.VMEM((DEPTH, N, d), f32),
                pltpu.SemaphoreType.DMA(()),
                pltpu.SemaphoreType.DMA((DEPTH,)),
                pltpu.SemaphoreType.DMA((DEPTH,)),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((B, d), xc.dtype),
            jax.ShapeDtypeStruct(ssm_pl.shape, ssm_pl.dtype),
        ],
        input_output_aliases={7: 1},  # the plane in place
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=VMEM_LIMIT,
        ),
        interpret=_flash._use_interpret(),
        name="_ssm_step",
    )(jnp.reshape(li, (1,)).astype(jnp.int32), live, xc, xz, rbc, wdt, chan,
      ssm_pl)
    return y, ssm_pl
