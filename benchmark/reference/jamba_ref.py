"""Plain reference of AI21-Jamba2-3B's decoder (`model_type: jamba`):
`jax.numpy`, float32, matmul precision "highest", a token-by-token
`lax.scan` for the recurrence, no kernels, no cache, no chunks, no
batching. Imports nothing from `oryx_tpu` and takes NOTHING from its
`LLMConfig`: `sizes` is a plain dict of the source's own keys (the
configuration file's, `sizes_from_keys`). One layer's weights are made
float32 at a time, so the whole fits beside the served bfloat16 ones.

With d = hidden_size, d_in = mamba_expand * d, N = mamba_d_state,
R = mamba_dt_rank, K = mamba_d_conv, for hidden state h [T, d], layer
i of num_hidden_layers (i % attn_layer_period == attn_layer_offset
attends, every other layer is a Mamba mixer):

    h = h + mixer_i(rms_norm(h, w_in))
    u = rms_norm(h, w_pre_ff);  h = h + W_down(silu(W_gate u) * (W_up u))

Mamba mixer (float32 throughout):

    [x | z]   = W_in u
    x_t       = silu(b_c + sum_{k<K} w_c[:, k] * x_{t-K+1+k})   (zeros before 0)
    [r|B|C]_t = W_x x_t
    dt_t      = softplus(W_dt rms_norm(r_t) + b_dt)
    B_t, C_t  = rms_norm(B_t), rms_norm(C_t)
    A         = -exp(A_log)                                     [d_in, N]
    h_t       = exp(dt_t[:, None] * A) * h_{t-1} + (dt_t * x_t)[:, None] * B_t[None, :]
    y_t       = h_t C_t + D * x_t ;   out_t = W_out (y_t * silu(z_t))

Attention: q = W_q u (num_attention_heads x head), k, v = W_k u, W_v u
(num_key_value_heads x head; query head j reads key/value head j //
group), NO position term of any kind, causal softmax at 1 / sqrt(head),
W_o. Then rms_norm and logits over the tied embedding.

The params are the program's pytree (`qwen2._init_recurrent_params`):
`layers["attn"]` / `layers["mamba"]` stacked by kind in layer order,
linear kernels [in, out]; the mixer's `A_log` is stored [N, d_in] and
its conv kernel [K, d_in] (channels last), transposed here to the
published orientation.

Departures from the published description: none known. What the
source's keys do not settle is listed under `assumed` in the
configuration file (the layer order rule above, the inner norms' eps =
rms_norm_eps).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

F32 = jnp.float32


def sizes_from_keys(keys: dict) -> dict:
    """The source's config.json keys -> the sizes this file reads."""
    d = keys["hidden_size"]
    heads = keys["num_attention_heads"]
    return {
        "layers": keys["num_hidden_layers"],
        "period": keys["attn_layer_period"],
        "offset": keys["attn_layer_offset"],
        "d_inner": keys["mamba_expand"] * d,
        "d_state": keys["mamba_d_state"],
        "d_conv": keys["mamba_d_conv"],
        "dt_rank": keys["mamba_dt_rank"],
        "heads": heads,
        "kv_heads": keys["num_key_value_heads"],
        "head_dim": keys.get("head_dim") or d // heads,
        "eps": keys["rms_norm_eps"],
    }


def _f32(x):
    return jnp.asarray(x).astype(F32)


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * _f32(w)


def _lin(x, p):
    y = x @ _f32(p["kernel"])
    return y + _f32(p["bias"]) if "bias" in p else y


def swiglu(u, lp):
    return _lin(jax.nn.silu(_lin(u, lp["gate_proj"])) * _lin(u, lp["up_proj"]),
                lp["down_proj"])


def mixer(u, mp, sz, *, h0=None, window=None):
    """u [T, d] -> out [T, d], from a zero state (or h0 [d_in, N] and
    the K - 1 conv inputs before token 0, for the tests of a carried
    state). Returns (out, final h)."""
    T = u.shape[0]
    d_in, N, R, K = sz["d_inner"], sz["d_state"], sz["dt_rank"], sz["d_conv"]
    xz = _lin(u, mp["in_proj"])
    x, z = xz[:, :d_in], xz[:, d_in:]
    before = jnp.zeros((K - 1, d_in), F32) if window is None else _f32(window)
    padded = jnp.concatenate([before, x], axis=0)
    w = _f32(mp["conv"]["kernel"]).T  # [d_in, K]
    xc = sum(w[:, k] * padded[k:k + T] for k in range(K))
    if "bias" in mp["conv"]:
        xc = xc + _f32(mp["conv"]["bias"])
    xc = jax.nn.silu(xc)
    rbc = xc @ _f32(mp["x_proj"]["kernel"])
    r, Bm, Cm = rbc[:, :R], rbc[:, R:R + N], rbc[:, R + N:]
    eps = sz["eps"]
    dt = jax.nn.softplus(
        _lin(rms_norm(r, mp["dt_norm"]["weight"], eps), mp["dt_proj"]))
    Bm = rms_norm(Bm, mp["b_norm"]["weight"], eps)
    Cm = rms_norm(Cm, mp["c_norm"]["weight"], eps)
    A = -jnp.exp(_f32(mp["A_log"]).T)  # [d_in, N]
    D = _f32(mp["D"])

    def token(h, xs):
        x_t, dt_t, B_t, C_t = xs
        h = jnp.exp(dt_t[:, None] * A) * h \
            + (dt_t * x_t)[:, None] * B_t[None, :]
        return h, h @ C_t + D * x_t

    h = jnp.zeros((d_in, N), F32) if h0 is None else _f32(h0)
    h, y = jax.lax.scan(token, h, (xc, dt, Bm, Cm))
    return _lin(y * jax.nn.silu(z), mp["out_proj"]), h


def attention(u, ap, sz):
    """u [T, d] -> [T, d]: causal, no positions, a head at a time."""
    T = u.shape[0]
    Hq, Hk, Dh = sz["heads"], sz["kv_heads"], sz["head_dim"]
    q = _lin(u, ap["q_proj"]).reshape(T, Hq, Dh)
    k = _lin(u, ap["k_proj"]).reshape(T, Hk, Dh)
    v = _lin(u, ap["v_proj"]).reshape(T, Hk, Dh)
    seen = jnp.arange(T)[:, None] >= jnp.arange(T)[None, :]

    def head(j):
        kv = j // (Hq // Hk)
        s = (q[:, j] @ k[:, kv].T) * Dh ** -0.5
        p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
        return p @ v[:, kv]

    o = jax.lax.map(head, jnp.arange(Hq))  # [Hq, T, Dh]
    return _lin(jnp.moveaxis(o, 0, 1).reshape(T, Hq * Dh), ap["o_proj"])


def _at(tree, i):
    return jax.tree_util.tree_map(lambda a: a[i], tree)


@functools.partial(jax.jit, static_argnames=("kind", "sz"))
def _layer(h, lp, *, kind: str, sz):
    sz = dict(sz)
    with jax.default_matmul_precision("highest"):
        u = rms_norm(h, lp["input_norm"]["weight"], sz["eps"])
        if kind == "attn":
            h = h + attention(u, lp, sz)
        else:
            h = h + mixer(u, lp["mixer"], sz)[0]
        u = rms_norm(h, lp["post_attn_norm"]["weight"], sz["eps"])
        return h + swiglu(u, lp)


def layer_kinds(sz) -> list[str]:
    return ["attn" if i % sz["period"] == sz["offset"] else "mamba"
            for i in range(sz["layers"])]


def logits(params, sizes: dict, ids, *, rows=None):
    """ids [T] -> float32 logits [T, V] (or at positions `rows` only),
    every layer in order over the whole sequence."""
    sz = tuple(sorted(sizes.items()))
    h = _f32(params["embed"]["weight"][jnp.asarray(ids)])
    seen = {"attn": 0, "mamba": 0}
    for kind in layer_kinds(sizes):
        lp = _at(params["layers"][kind], seen[kind])
        h = _layer(h, lp, kind=kind, sz=sz)
        seen[kind] += 1
    tied = "lm_head" not in params
    return _head(
        h if rows is None else h[jnp.asarray(rows)],
        params["final_norm"]["weight"],
        params["embed"]["weight"] if tied else params["lm_head"]["kernel"],
        eps=sizes["eps"], tied=tied)


@functools.partial(jax.jit, static_argnames=("eps", "tied"))
def _head(h, norm, kernel, *, eps, tied):
    """Logits over the tied embedding [V, d] or an untied head [d, V]."""
    with jax.default_matmul_precision("highest"):
        h = rms_norm(h, norm, eps)
        return h @ (_f32(kernel).T if tied else _f32(kernel))
