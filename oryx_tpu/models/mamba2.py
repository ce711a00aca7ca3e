"""The Mamba-2 mixer of a state-space hybrid (the Nemotron-H lineage):
ONE scalar decay a head, B and C shared by the heads of a group, a gated
group norm on the way out.

On the normed layer input u [B, T, H], with nh = cfg.mamba_num_heads
heads of P = cfg.mamba_head_dim channels (d = nh P), G = cfg.mamba_n_groups
groups, N = cfg.mamba_d_state, K = cfg.mamba_d_conv:

    [z | xBC | dt] = W_in u                   (widths d | d + 2 G N | nh)
    xBC_t     = silu(b_c + sum_{k<K} w_c[k] * xBC_{t-K+1+k})  (depthwise)
    [x | B | C]_t = xBC_t          x [nh, P], B, C [G, N], head h in group
                                   h // (nh / G)
    D_t,h     = softplus(dt_t,h + dt_bias_h) ;  a_t,h = exp(D_t,h A_h),
                A_h = -exp(A_log_h)
    S_t,h     = a_t,h S_t-1,h + D_t,h x_t,h (outer) B_t,g      [P, N]
    y_t,h     = S_t,h C_t,g + Dskip_h x_t,h
    out_t     = W_out ( group_rms_norm(y_t * silu(z_t)) * w_norm )

(gate first, norm second; the norm is over each of the G groups of d / G
channels). dt, A, the decay products and the state are float32 whatever
the compute dtype. What a row carries from one call to the next is its
STATE: the last K - 1 conv inputs [K - 1, d + 2 G N] and S, kept as
[N, d] (channels in the lanes, as the pool keeps it and as the decode
kernel wants it: `qwen2.init_paged_kv_cache`, `ops/pallas/ssd_step.py`).
`valid` masks padding: a padded position has D = 0, so a = 1 and S does
not move, and the window a call leaves behind is the last K - 1 REAL
inputs.

`mixer_prefill` is the CHUNK form (cfg.mamba_chunk_size, Q): within a
chunk the output is ((C B^T) * L) (D x) with L_ts = prod_{s<r<=t} a_r for
s <= t, plus C_t (prod_{r<=t} a_r) S_chunk-start; the chunk-end states
are passed on in sequence. Matrix products, not a scan over T; the plain
scan is the benchmark's reference's.
"""

from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp

from oryx_tpu.config import LLMConfig
from oryx_tpu.models.mamba import window_after
from oryx_tpu.ops.pallas import ssd_step

Params = dict[str, Any]

f32 = jnp.float32


def init_mixer_params(cfg: LLMConfig, key: jax.Array, L: int, dtype) -> Params:
    """L stacked mixers. Kernels random-normal 0.02 like every other;
    A_log = log(uniform 1..16) a head, D = 1 and dt's bias the inverse
    softplus of a log-uniform 1e-3..1e-1, as the family initialises
    them and for the reason `mamba.init_mixer_params` gives. A_log, D
    and dt's bias stay float32."""
    H, d, nh = cfg.hidden_size, cfg.mamba_d_inner, cfg.mamba_num_heads
    cd, K = cfg.mamba2_conv_dim, cfg.mamba_d_conv
    keys = iter(jax.random.split(key, 6))

    def dense(shape):
        return (
            jax.random.normal(next(keys), (L, *shape), f32) * 0.02
        ).astype(dtype)

    dt0 = jnp.exp(
        jax.random.uniform(next(keys), (L, nh), f32)
        * (math.log(1e-1) - math.log(1e-3)) + math.log(1e-3)
    )
    p: Params = {
        "in_proj": {"kernel": dense((H, d + cd + nh))},
        "conv": {"kernel": dense((K, cd))},
        # softplus^-1(dt0) = dt0 + log(1 - exp(-dt0))
        "dt_bias": dt0 + jnp.log(-jnp.expm1(-dt0)),
        "A_log": jnp.log(jax.random.uniform(
            next(keys), (L, nh), f32, minval=1.0, maxval=16.0)),
        "D": jnp.ones((L, nh), f32),
        "norm": {"weight": jnp.ones((L, d), dtype)},
        "out_proj": {"kernel": dense((d, H))},
    }
    if cfg.mamba_conv_bias:
        p["conv"]["bias"] = jnp.zeros((L, cd), dtype)
    return p


def state_shapes(cfg: LLMConfig, B: int):
    """A row's state: (conv window [B, K-1, d + 2 G N], S [B, N, d])."""
    return ((B, cfg.mamba_d_conv - 1, cfg.mamba2_conv_dim),
            (B, cfg.mamba_d_state, cfg.mamba_d_inner))


def _split(cfg: LLMConfig, lp: Params, u):
    """u [..., H] -> (z [..., d], xBC [..., cd], dt [..., nh] float32,
    softplus'd)."""
    d, cd = cfg.mamba_d_inner, cfg.mamba2_conv_dim
    zxd = u @ lp["in_proj"]["kernel"].astype(u.dtype)
    dt = jax.nn.softplus(zxd[..., d + cd:].astype(f32) + lp["dt_bias"])
    return zxd[..., :d], zxd[..., d:d + cd], dt


def _heads(cfg: LLMConfig, xc):
    """The conv's output [..., cd] -> (x [..., nh, P], B, C [..., G, N])."""
    d, G, N = cfg.mamba_d_inner, cfg.mamba_n_groups, cfg.mamba_d_state
    lead = xc.shape[:-1]
    return (xc[..., :d].reshape(*lead, cfg.mamba_num_heads, cfg.mamba_head_dim),
            xc[..., d:d + G * N].reshape(*lead, G, N),
            xc[..., d + G * N:].reshape(*lead, G, N))


def gated_norm(cfg: LLMConfig, lp: Params, y, z):
    """y, z [..., d] -> rms_norm over each group of (y * silu(z)), times
    the learned weight, in y's dtype (the gate and the mean float32)."""
    G = cfg.mamba_n_groups
    g = y.astype(f32) * jax.nn.silu(z.astype(f32))
    gg = g.reshape(*g.shape[:-1], G, -1)
    gg = gg * jax.lax.rsqrt(
        jnp.mean(gg * gg, axis=-1, keepdims=True) + cfg.rms_norm_eps)
    return (gg.reshape(g.shape).astype(y.dtype)
            * lp["norm"]["weight"].astype(y.dtype))


def _conv(lp: Params, win, T: int):
    """win [B, K-1+T, cd] -> silu(conv) [B, T, cd]."""
    w = lp["conv"]["kernel"].astype(win.dtype)
    xc = sum(w[k] * win[:, k:k + T] for k in range(w.shape[0]))
    if "bias" in lp["conv"]:
        xc = xc + lp["conv"]["bias"].astype(win.dtype)
    return jax.nn.silu(xc)


def ssd_chunked(x, dt, A, Bm, Cm, S0, Q: int):
    """The scan in its chunked matmul form. x [B, T, nh, P] (compute
    dtype); dt [B, T, nh] float32 (0 at padding); A [nh] float32; Bm, Cm
    [B, T, G, N]; S0 [B, N, nh, P] float32 (the pool's [N, d]: the
    state's rows stay the major axis all through, so that no product
    wants the PLANE laid out another way: with N minor XLA transposed
    all 2 GB of it on the way in and out of every prefill chunk).
    Returns (y [B, T, nh, P] float32 without the skip term, S after the
    last token). The products' operands are in x's dtype, their sums
    float32."""
    B, T, nh, P = x.shape
    G, N = Bm.shape[2:]
    pad = -T % Q
    if pad:  # dt = 0: the state stands still and nothing is added
        x, dt, Bm, Cm = (
            jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
            for a in (x, dt, Bm, Cm))
    nc = (T + pad) // Q
    hg = nh // G
    dtype = x.dtype
    x = x.reshape(B, nc, Q, G, hg, P)
    Bm = Bm.reshape(B, nc, Q, G, N)
    Cm = Cm.reshape(B, nc, Q, G, N)
    # Time innermost: [B, nc, G, hg, Q], and [.., t, s] below, so that
    # the two minor axes of every temporary are whole (Q, Q) tiles.
    dt = jnp.transpose(dt.reshape(B, nc, Q, G, hg), (0, 1, 3, 4, 2))
    cum = jnp.cumsum(dt * A.reshape(G, hg, 1), axis=-1)  # <= 0
    # Within a chunk: (C B^T) * L * D, then times x.
    cb = jnp.einsum("bctgn,bcsgn->bcgts", Cm, Bm,
                    preferred_element_type=f32)  # [B, nc, G, t, s]
    tri = jnp.tril(jnp.ones((Q, Q), bool))
    seg = jnp.where(tri, cum[..., :, None] - cum[..., None, :], 0.0)
    m = jnp.where(tri, jnp.exp(seg), 0.0) * cb[:, :, :, None] \
        * dt[..., None, :]  # [B, nc, G, hg, t, s]
    y = jnp.einsum("bcghts,bcsghp->bctghp", m.astype(dtype), x,
                   preferred_element_type=f32)
    # What each chunk adds to the state by its end.
    to_end = jnp.exp(cum[..., -1:] - cum) * dt  # [B, nc, G, hg, s]
    xw = jnp.transpose(to_end, (0, 1, 4, 2, 3))[..., None] * x
    local = jnp.einsum(
        "bcsgn,bcsghp->bcnghp", Bm, xw.astype(dtype),
        preferred_element_type=f32)  # [B, nc, N, G, hg, P]
    whole = jnp.exp(cum[..., -1])  # [B, nc, G, hg]: a chunk's decay

    def pass_on(S, c):
        loc, dec = c
        return dec[:, None, :, :, None] * S + loc, S  # ys: the chunk's START

    S1, starts = jax.lax.scan(
        pass_on, S0.reshape(B, N, G, hg, P),
        (jnp.moveaxis(local, 1, 0), jnp.moveaxis(whole, 1, 0)))
    starts = jnp.moveaxis(starts, 0, 1)  # [B, nc, N, G, hg, P]
    # The state a chunk started from, read by its C and decayed to t.
    y = y + jnp.transpose(jnp.exp(cum), (0, 1, 4, 2, 3))[..., None] \
        * jnp.einsum("bctgn,bcnghp->bctghp", Cm, starts.astype(dtype),
                     preferred_element_type=f32)
    y = y.reshape(B, nc * Q, nh, P)[:, :T]
    return y, S1.reshape(B, N, nh, P)


def mixer_prefill(cfg: LLMConfig, lp: Params, u, state, valid):
    """The mixer over a chunk. u [B, T, H]; state (conv [B, K-1, cd],
    S [B, N, d] float32) as the chunk before left it (zeros for a chunk
    that starts a sequence); valid [B, T] bool, true at real tokens,
    which lie first (right padding). Returns (out [B, T, H], the state
    after the row's last REAL token)."""
    conv0, S0 = state
    B, T, _ = u.shape
    nh, P, N = cfg.mamba_num_heads, cfg.mamba_head_dim, cfg.mamba_d_state
    z, xBC, dt = _split(cfg, lp, u)
    win = jnp.concatenate([conv0.astype(xBC.dtype), xBC], axis=1)
    xc = _conv(lp, win, T)
    n = jnp.sum(valid, axis=1).astype(jnp.int32)
    conv1 = window_after(win, n, cfg.mamba_d_conv).astype(conv0.dtype)
    x, Bm, Cm = _heads(cfg, xc)
    dt = jnp.where(valid[..., None], dt, 0.0)
    with jax.named_scope("ssd_chunk"):
        y, S1 = ssd_chunked(
            x, dt, -jnp.exp(lp["A_log"]), Bm, Cm, S0.reshape(B, N, nh, P),
            cfg.mamba_chunk_size)
        S1 = S1.reshape(B, N, nh * P)
        y = y + lp["D"][:, None] * x.astype(f32)
    y = gated_norm(cfg, lp, y.reshape(B, T, nh * P).astype(u.dtype), z)
    return y @ lp["out_proj"]["kernel"].astype(u.dtype), (conv1, S1)


def _step_inputs(cfg: LLMConfig, lp: Params, u, conv0):
    """One token a row, up to the state update. u [B, H]; conv0 [B, K-1,
    cd]. Returns (z [B, d], win [B, K, cd], x [B, d] float32, a, dtx
    [B, d] float32 (the head's decay and D x, a channel), bc [B, 2 G N]
    float32)."""
    P, d = cfg.mamba_head_dim, cfg.mamba_d_inner
    z, xBC, dt = _split(cfg, lp, u)
    win = jnp.concatenate([conv0.astype(xBC.dtype), xBC[:, None]], axis=1)
    xc = _conv(lp, win, 1)[:, 0]
    x = xc[:, :d].astype(f32)
    a = jnp.repeat(jnp.exp(dt * -jnp.exp(lp["A_log"])), P, axis=1)
    return z, win, x, a, jnp.repeat(dt, P, axis=1) * x, xc[:, d:].astype(f32)


def _step_output(cfg: LLMConfig, lp: Params, y, x, z, dtype):
    """y [B, d] float32 (S C) -> out [B, 1, H]."""
    y = y + jnp.repeat(lp["D"], cfg.mamba_head_dim)[None] * x
    y = gated_norm(cfg, lp, y.astype(dtype), z)
    return (y @ lp["out_proj"]["kernel"].astype(dtype))[:, None]


def mixer_step(cfg: LLMConfig, lp: Params, u, state, live):
    """One token a row. u [B, 1, H]; state as in `mixer_prefill`; live
    [B] bool: a row that is not live (a finished or empty lane) keeps
    its state. Returns (out [B, 1, H], state)."""
    conv0, S0 = state
    z, win, x, a, dtx, bc = _step_inputs(cfg, lp, u[:, 0], conv0)
    with jax.named_scope("ssd_step"):
        y, S1 = ssd_step.ssd_step_xla(
            a, dtx, bc, S0, cfg.mamba_n_groups)
    keep = live[:, None, None]
    conv1 = jnp.where(keep, win[:, 1:].astype(conv0.dtype), conv0)
    S1 = jnp.where(keep, S1, S0)
    return _step_output(cfg, lp, y, x, z, u.dtype), (conv1, S1)


def step_fits(cfg: LLMConfig, S: int) -> bool:
    """Whether `mixer_step_inplace`'s kernel takes a pool of S slots."""
    return ssd_step.fits(
        S, cfg.mamba_d_inner, cfg.mamba_d_state, cfg.mamba_n_groups)


def mixer_step_inplace(cfg: LLMConfig, lp: Params, live, li, u, planes):
    """`mixer_step` on the pool's `ssm` plane WHOLE, which is the decode
    step's form under `attn_impl="pallas"` where `step_fits`: the kernel
    `_ssd_step` reads and writes the live lanes' rows of layer li of
    ssm [Lm, S, N, d] in place; the conv window ([Lm, S, (K-1) cd], 60
    KB a lane) is sliced out and written back around it by XLA. Lane b
    is slot b (S == B); live [B] bool. Returns (out [B, 1, H], the
    planes)."""
    conv_pl, ssm_pl = planes
    B = u.shape[0]
    conv0 = jax.lax.dynamic_index_in_dim(
        conv_pl, li, keepdims=False).reshape(state_shapes(cfg, B)[0])
    z, win, x, a, dtx, bc = _step_inputs(cfg, lp, u[:, 0], conv0)
    conv1 = jnp.where(
        live[:, None, None], win[:, 1:].astype(conv0.dtype), conv0)
    conv_pl = jax.lax.dynamic_update_index_in_dim(
        conv_pl, conv1.reshape(B, -1), li, 0)
    with jax.named_scope("ssd_step"):
        y, ssm_pl = ssd_step.ssd_step(
            a, dtx, bc, live.astype(jnp.int32), ssm_pl, li,
            cfg.mamba_n_groups)
    return _step_output(cfg, lp, y, x, z, u.dtype), (conv_pl, ssm_pl)
