"""The selective scan of a Mamba-1 mixer over a prefill chunk.

    h_t = exp(dt_t * A) * h_{t-1} + (dt_t * x_t) * B_t        [N, d]
    y_t = sum_n h_t[n] * C_t[n] + D * x_t                     [d]
    out_t = y_t * silu(z_t)

for t over the chunk's T tokens, from a carried state h_0 to h_T, which
the chunk after it (or the decode step) starts from. Every channel of
the d = d_inner is its own recurrence; B_t and C_t [N] are shared by the
channels of a token.

Layout: the state is [N, d], channels in the lanes. `lax.
associative_scan` would materialise [T, d, N] float32 twice a layer (168
MB at T = 512, d = 5120, N = 16) and pass over it ~log T times; the
Pallas kernel keeps ONE [N, d_tile] state in registers across the
chunk's tokens and reads x, dt, z and writes y once: the recurrence is
VPU work (an exp and five multiply-adds a state element a token), not
memory traffic.

Two forms with the same arguments: `_scan_xla` (a `lax.scan` over
tokens; the CPU's and `attn_impl="xla"`'s) and the Pallas kernel under
the name `_selective_scan`, which is how a device trace shows it.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from oryx_tpu.ops.pallas import flash_attention as _flash

LANES = 128
ROWS = 8  # tokens a loop step: one sublane tile of x, dt and y


def _scan_xla(x, dt, z, Bm, Cm, A, D, h0):
    """x, dt, z [B, T, d] float32; Bm, Cm [B, T, N]; A [N, d]; D [d];
    h0 [B, N, d]. Returns (out [B, T, d], h_T [B, N, d]), float32."""

    def step(h, xs):
        x_t, dt_t, B_t, C_t = xs  # [B, d], [B, d], [B, N], [B, N]
        dA = jnp.exp(dt_t[:, None, :] * A[None])
        h = dA * h + (dt_t * x_t)[:, None, :] * B_t[:, :, None]
        y = jnp.sum(h * C_t[:, :, None], axis=1) + D[None] * x_t
        return h, y

    tm = lambda a: jnp.moveaxis(a, 1, 0)  # noqa: E731
    hT, ys = jax.lax.scan(step, h0, (tm(x), tm(dt), tm(Bm), tm(Cm)))
    return jnp.moveaxis(ys, 0, 1) * jax.nn.silu(z), hT


def _selective_scan(x_ref, dt_ref, z_ref, b_ref, c_ref, a_ref, d_ref,
                    h0_ref, y_ref, hT_ref):
    """One (row, channel tile) of the scan. x, dt, z, y [T, dt_] blocks;
    b, c [T, N, 128] (B_t and C_t broadcast along the lanes outside, so
    that a token's [N, 128] slab multiplies a 128-lane slice of the
    state as it lies); a [N, dt_]; d [1, dt_]; h0, hT [N, dt_]."""
    T, tile = x_ref.shape
    sub = tile // LANES
    a = a_ref[...]
    dvec = d_ref[...]

    def rows(i, hs):
        t0 = pl.multiple_of(i * ROWS, ROWS)
        xb = x_ref[pl.ds(t0, ROWS), :]
        db = dt_ref[pl.ds(t0, ROWS), :]
        ys = []
        for r in range(ROWS):
            bt = b_ref[t0 + r]  # [N, 128]
            ct = c_ref[t0 + r]
            xt, dtt = xb[r:r + 1, :], db[r:r + 1, :]
            dx = dtt * xt
            new, yrow = [], []
            for j in range(sub):
                lo, hi = j * LANES, (j + 1) * LANES
                h = jnp.exp(dtt[:, lo:hi] * a[:, lo:hi]) * hs[j] \
                    + dx[:, lo:hi] * bt
                new.append(h)
                yrow.append(jnp.sum(h * ct, axis=0, keepdims=True))
            hs = tuple(new)
            ys.append(jnp.concatenate(yrow, axis=1) + dvec * xt)
        y_ref[pl.ds(t0, ROWS), :] = jnp.concatenate(ys, axis=0)
        return hs

    h0 = h0_ref[...]
    hs = jax.lax.fori_loop(
        0, T // ROWS, rows,
        tuple(h0[:, j * LANES:(j + 1) * LANES] for j in range(sub)),
    )
    hT_ref[...] = jnp.concatenate(hs, axis=1)
    zb = z_ref[...]
    y_ref[...] = y_ref[...] * (zb * jax.nn.sigmoid(zb))


def _tile(d: int) -> int:
    """Channels a grid step: the widest of 512, 256, 128 that divides d
    (a [N, tile] state is N * tile / 1024 vector registers; at N = 16
    and 512 that is 8 of the 64, beside a token's B and C slabs)."""
    for t in (512, 256, 128):
        if d % t == 0:
            return t
    return 0


@partial(jax.jit, static_argnames=("impl",))
def selective_scan(x, dt, z, Bm, Cm, A, D, h0, *, impl: str = "xla"):
    """The scan above over a batch of rows. x, dt, z [B, T, d]; Bm, Cm
    [B, T, N]; A [N, d]; D [d]; h0 [B, N, d]; all computed in float32.
    Returns (out [B, T, d] float32, h_T [B, N, d] float32). A padded
    position carries dt = 0: exp(0) = 1 and dt * x = 0, so the state
    does not move there. impl="pallas" runs the kernel where the
    shapes fit its tiles (T a multiple of 8, d of 128) and the `xla`
    twin elsewhere (a single decode token, a tiny test model)."""
    f32 = jnp.float32
    x, dt, z, Bm, Cm, A, D, h0 = (
        a.astype(f32) for a in (x, dt, z, Bm, Cm, A, D, h0))
    B, T, d = x.shape
    N = A.shape[0]
    tile = _tile(d)
    if impl != "pallas" or not tile or T % ROWS or N % 8:
        return _scan_xla(x, dt, z, Bm, Cm, A, D, h0)
    wide = lambda m: jnp.broadcast_to(  # noqa: E731
        m[..., None], (B, T, N, LANES))
    seq = pl.BlockSpec((None, T, tile), lambda b, j: (b, 0, j))
    slab = pl.BlockSpec((None, T, N, LANES), lambda b, j: (b, 0, 0, 0))
    state = pl.BlockSpec((None, N, tile), lambda b, j: (b, 0, j))
    y, hT = pl.pallas_call(
        _selective_scan,
        grid=(B, d // tile),
        in_specs=[
            seq, seq, seq, slab, slab,
            pl.BlockSpec((N, tile), lambda b, j: (0, j)),
            pl.BlockSpec((1, tile), lambda b, j: (0, j)),
            state,
        ],
        out_specs=[seq, state],
        out_shape=[
            jax.ShapeDtypeStruct((B, T, d), f32),
            jax.ShapeDtypeStruct((B, N, d), f32),
        ],
        input_output_aliases={7: 1},  # the state in place
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=64 * 1024 * 1024,
        ),
        interpret=_flash._use_interpret(),
        name="_selective_scan",
    )(x, dt, z, wide(Bm), wide(Cm), A, D[None], h0)
    return y, hT
