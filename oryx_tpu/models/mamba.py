"""The Mamba-1 mixer of a state-space hybrid (the Jamba lineage: three
inner RMSNorms, on dt's bottleneck and on B and C).

On the normed layer input u [B, T, H], with d = cfg.mamba_d_inner,
N = cfg.mamba_d_state, R = cfg.mamba_dt_rank, K = cfg.mamba_d_conv:

    [x | z]   = W_in u
    x_t       = silu(b_c + sum_{k<K} w_c[k] * x_{t-K+1+k})    (depthwise)
    [r|B|C]_t = W_x x_t
    dt_t      = softplus(W_dt rms_norm(r_t) + b_dt)
    B_t, C_t  = rms_norm(B_t), rms_norm(C_t)
    A         = -exp(A_log)
    h_t       = exp(dt_t A) * h_{t-1} + (dt_t x_t) B_t
    y_t       = h_t C_t + D x_t ;  out_t = W_out (y_t * silu(z_t))

dt, A and the state are float32 whatever the compute dtype. What a row
carries from one call to the next is its STATE: the last K - 1 conv
inputs [K - 1, d] and h [N, d] (channels in the lanes, which is how the
pool keeps them: `qwen2.init_paged_kv_cache`). `valid` masks padding: a
padded position has dt = 0, so h does not move, and the window a call
leaves behind is the last K - 1 REAL inputs.
"""

from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp

from oryx_tpu.config import LLMConfig
from oryx_tpu.ops.norms import rms_norm
from oryx_tpu.ops.paged_kv import SLOT_PLANES
from oryx_tpu.ops.pallas import ssm_step
from oryx_tpu.ops.pallas.selective_scan import selective_scan

Params = dict[str, Any]

CONV, SSM = SLOT_PLANES  # the pool's per-slot planes


def init_mixer_params(cfg: LLMConfig, key: jax.Array, L: int, dtype) -> Params:
    """L stacked mixers. Kernels random-normal 0.02 like every other;
    A_log = log(1..N) a channel, D = 1, and dt's bias the inverse
    softplus of a log-uniform 1e-3..1e-1, as the family initialises
    them: seeded 0.02-normal values there would make every dt
    softplus(~0) = 0.69 and every channel forget alike. A_log, D and
    dt's bias stay float32."""
    H, d = cfg.hidden_size, cfg.mamba_d_inner
    N, R, K = cfg.mamba_d_state, cfg.mamba_dt_rank, cfg.mamba_d_conv
    keys = iter(jax.random.split(key, 8))

    def dense(shape):
        return (
            jax.random.normal(next(keys), (L, *shape), jnp.float32) * 0.02
        ).astype(dtype)

    dt0 = jnp.exp(
        jax.random.uniform(next(keys), (L, d), jnp.float32)
        * (math.log(1e-1) - math.log(1e-3)) + math.log(1e-3)
    )
    p: Params = {
        "in_proj": {"kernel": dense((H, 2 * d))},
        "conv": {"kernel": dense((K, d))},
        "x_proj": {"kernel": dense((d, R + 2 * N))},
        "dt_norm": {"weight": jnp.ones((L, R), dtype)},
        "b_norm": {"weight": jnp.ones((L, N), dtype)},
        "c_norm": {"weight": jnp.ones((L, N), dtype)},
        "dt_proj": {
            "kernel": dense((R, d)),
            # softplus^-1(dt0) = dt0 + log(1 - exp(-dt0))
            "bias": dt0 + jnp.log(-jnp.expm1(-dt0)),
        },
        "A_log": jnp.broadcast_to(
            jnp.log(jnp.arange(1, N + 1, dtype=jnp.float32))[None, :, None],
            (L, N, d),
        ),
        "D": jnp.ones((L, d), jnp.float32),
        "out_proj": {"kernel": dense((d, H))},
    }
    if cfg.mamba_conv_bias:
        p["conv"]["bias"] = jnp.zeros((L, d), dtype)
    if cfg.mamba_proj_bias:
        p["in_proj"]["bias"] = jnp.zeros((L, 2 * d), dtype)
        p["out_proj"]["bias"] = jnp.zeros((L, H), dtype)
    return p


def _dense(x, p):
    y = x @ p["kernel"].astype(x.dtype)
    return y + p["bias"].astype(x.dtype) if "bias" in p else y


def _selection(cfg: LLMConfig, lp: Params, xc):
    """xc [..., d] -> (dt [..., d] float32, B, C [..., N] float32)."""
    N, R = cfg.mamba_d_state, cfg.mamba_dt_rank
    rbc = xc @ lp["x_proj"]["kernel"].astype(xc.dtype)
    r, Bm, Cm = rbc[..., :R], rbc[..., R:R + N], rbc[..., R + N:]
    eps = cfg.rms_norm_eps
    r = rms_norm(r, lp["dt_norm"]["weight"], eps)
    dt = jax.nn.softplus(
        (r @ lp["dt_proj"]["kernel"].astype(r.dtype)).astype(jnp.float32)
        + lp["dt_proj"]["bias"].astype(jnp.float32)
    )
    Bm = rms_norm(Bm, lp["b_norm"]["weight"], eps).astype(jnp.float32)
    Cm = rms_norm(Cm, lp["c_norm"]["weight"], eps).astype(jnp.float32)
    return dt, Bm, Cm


def rows_state(conv_l, ssm_l, slots, fresh, shape):
    """The state rows a prefill starts from: rows `slots` [B] of one
    layer's planes (conv_l [S, (K-1) * d], ssm_l [S, N, d]), zeros where
    `fresh` [B, 1, 1] (the row's chunk starts a sequence: whatever the
    slot's last occupant left is not read). shape: (B, K - 1, d)."""
    conv0 = jnp.where(fresh, 0, conv_l[slots].reshape(shape))
    return conv0, jnp.where(fresh, 0, ssm_l[slots])


def window_after(win, n, K: int):
    """The conv window a row leaves behind: inputs n-K+1 .. n-1 of its
    n real ones, out of win [B, K-1+T, d] = (the window before | the
    chunk's inputs); the old window's tail where the chunk holds fewer
    than K - 1."""
    return jax.vmap(
        lambda a, i: jax.lax.dynamic_slice_in_dim(a, i, K - 1, axis=0)
    )(win, n)


def mixer_prefill(cfg: LLMConfig, lp: Params, u, state, valid, *,
                  impl: str = "xla"):
    """The mixer over a chunk. u [B, T, H]; state (conv [B, K-1, d],
    ssm [B, N, d] float32) as the chunk before left it (zeros for a
    chunk that starts a sequence); valid [B, T] bool, true at real
    tokens, which lie first (right padding). Returns (out [B, T, H],
    the state after the row's last REAL token)."""
    conv0, h0 = state
    B, T, _ = u.shape
    d, K = cfg.mamba_d_inner, cfg.mamba_d_conv
    xz = _dense(u, lp["in_proj"])
    x, z = xz[..., :d], xz[..., d:]
    win = jnp.concatenate([conv0.astype(x.dtype), x], axis=1)  # [B, K-1+T, d]
    w = lp["conv"]["kernel"].astype(x.dtype)
    xc = sum(w[k] * win[:, k:k + T] for k in range(K))
    if "bias" in lp["conv"]:
        xc = xc + lp["conv"]["bias"].astype(x.dtype)
    xc = jax.nn.silu(xc)
    n = jnp.sum(valid, axis=1).astype(jnp.int32)
    conv1 = window_after(win, n, K).astype(conv0.dtype)
    dt, Bm, Cm = _selection(cfg, lp, xc)
    dt = jnp.where(valid[..., None], dt, 0.0)
    with jax.named_scope("ssm_scan"):
        y, h1 = selective_scan(
            xc, dt, z, Bm, Cm, -jnp.exp(lp["A_log"]), lp["D"], h0, impl=impl)
    return _dense(y.astype(u.dtype), lp["out_proj"]), (conv1, h1)


def mixer_step(cfg: LLMConfig, lp: Params, u, state, live):
    """One token a row. u [B, 1, H]; state as in `mixer_prefill`; live
    [B] bool: a row that is not live (a finished or empty lane) keeps
    its state. Returns (out [B, 1, H], state)."""
    conv0, h0 = state
    d, K = cfg.mamba_d_inner, cfg.mamba_d_conv
    xz = _dense(u[:, 0], lp["in_proj"])
    x, z = xz[..., :d], xz[..., d:]
    win = jnp.concatenate([conv0.astype(x.dtype), x[:, None]], axis=1)
    w = lp["conv"]["kernel"].astype(x.dtype)
    xc = jnp.sum(w[None] * win, axis=1)
    if "bias" in lp["conv"]:
        xc = xc + lp["conv"]["bias"].astype(x.dtype)
    xc = jax.nn.silu(xc)
    dt, Bm, Cm = _selection(cfg, lp, xc)
    with jax.named_scope("ssm_step"):
        xf = xc.astype(jnp.float32)
        A = -jnp.exp(lp["A_log"])
        h1 = jnp.exp(dt[:, None, :] * A[None]) * h0 \
            + (dt * xf)[:, None, :] * Bm[:, :, None]
        y = jnp.sum(h1 * Cm[:, :, None], axis=1) + lp["D"][None] * xf
        y = y * jax.nn.silu(z.astype(jnp.float32))
    keep = live[:, None, None]
    conv1 = jnp.where(keep, win[:, 1:].astype(conv0.dtype), conv0)
    h1 = jnp.where(keep, h1, h0)
    return _dense(y.astype(u.dtype), lp["out_proj"])[:, None], (conv1, h1)


def step_fits(cfg: LLMConfig, S: int) -> bool:
    """Whether `mixer_step_inplace`'s kernels take a pool of S slots."""
    N = cfg.mamba_d_state
    return ssm_step.fits(S, cfg.mamba_d_inner, N, cfg.mamba_dt_rank + 2 * N)


def step_invariants(mp: Params, live, dtype) -> Params:
    """What `mixer_step_inplace` reads that no layer's number enters,
    made ONCE a step from the STACKED mixers `mp`, outside the scan
    over layers (XLA leaves such ops inside a loop's body: there they
    were eight device ops a layer): `chan` [Lm, rows, d] float32, the
    per-channel plane of `ops/pallas/ssm_step.py`, the conv's bias and
    taps rounded to `dtype` as `mixer_step` rounds them; `wdt`, the
    stacked `dt_proj` as it is; `live` [B] bool as int32, [B] and
    [B, 1]."""
    conv = mp["conv"]
    L, K, d = conv["kernel"].shape
    bias = conv["bias"] if "bias" in conv else jnp.zeros((L, d), dtype)
    norm = jnp.concatenate(
        [mp[n]["weight"] for n in ("dt_norm", "b_norm", "c_norm")], axis=-1)
    rows = [  # ssm_step.DT_BIAS, D_SKIP, CONV_BIAS, NORM, CONV_W.., A
        mp["dt_proj"]["bias"][:, None], mp["D"][:, None],
        bias.astype(dtype)[:, None],
        jnp.pad(norm, ((0, 0), (0, d - norm.shape[1])))[:, None],
        conv["kernel"].astype(dtype),
        jnp.zeros((L, ssm_step.head_rows(K) - ssm_step.CONV_W - K, d), dtype),
        -jnp.exp(mp["A_log"]),
    ]
    live = live.astype(jnp.int32)
    return {
        "chan": jnp.concatenate(
            [r.astype(jnp.float32) for r in rows], axis=1),
        "wdt": mp["dt_proj"]["kernel"], "live": live,
        "live_col": live[:, None],
    }


def mixer_step_inplace(cfg: LLMConfig, lp: Params, inv: Params, li, u,
                       planes):
    """`mixer_step` on the pool's planes WHOLE, which is the decode
    step's form under `attn_impl="pallas"` where `step_fits`: between
    `in_proj`, `x_proj` and `out_proj` (layer li's slices `lp`, XLA's
    matmuls) run the two kernels of `ops/pallas/ssm_step.py`, which
    read and write layer li's rows of planes = (conv [Lm, S, (K-1) d],
    ssm [Lm, S, N, d]) in place and take their small weights from the
    stacked ones in `inv` (`step_invariants`) by the layer's number.
    Lane b is slot b (S == B). Returns (out [B, 1, H], the planes)."""
    conv_pl, ssm_pl = planes
    xz = _dense(u[:, 0], lp["in_proj"])
    xc, conv_pl = ssm_step.ssm_conv(
        xz, inv["chan"], inv["live_col"], conv_pl, li)
    rbc = xc @ lp["x_proj"]["kernel"].astype(xc.dtype)
    with jax.named_scope("ssm_step"):
        y, ssm_pl = ssm_step.ssm_step(
            xc, xz, rbc, inv["wdt"], inv["chan"], inv["live"], ssm_pl, li,
            eps=cfg.rms_norm_eps)
    return _dense(y.astype(u.dtype), lp["out_proj"])[:, None], (
        conv_pl, ssm_pl)
