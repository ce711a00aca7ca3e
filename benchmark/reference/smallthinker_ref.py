"""Plain reference of SmallThinker-21BA3B-Instruct's decoder: `jax.numpy`,
float32, matmul precision "highest", no kernels, no cache, no pages, no
chunks of the served kind (attention is computed a block of QUERIES at
a time against every key, the experts one expert at a time, so that a
14k-token stream fits beside the served weights). Imports nothing from
`oryx_tpu` and takes NOTHING from its `LLMConfig`: `sizes` is a plain
dict made from the source's own keys (the configuration file's,
`sizes_from_keys`). A layer's weights are made float32 a layer (an
expert) at a time.

`h` is the residual stream at the layer's input, `i` the layer's index,
positions `t`, window `W = sliding_window_size`:

    r   = h W_r                          float32 [E]; the router reads the
                                         layer's INPUT, before its norm
    x   = rms_norm(h; w_in)
    q, k, v = x W_q, x W_k, x W_v        no bias
    if rope_layout[i]:  q, k = rope(q, k; theta, t), pairs (x[j], x[j + D/2])
    s[t, u] = q_t . k_u / sqrt(D), visible iff u <= t and
              (not sliding_window_layout[i] or t - u < W)
    h'  = h + softmax(s) v W_o
    x'  = rms_norm(h'; w_post)
    idx = top_k(softmax(r));  w = softmax(r)[idx] / sum   (norm_topk_prob)
    y   = sum_k w_k (relu(x' G_k) * (x' U_k)) D_k
    out = h' + y

then rms_norm and an untied head. Query head j reads key/value head
j // group.

The params are the program's pytree (`qwen2.init_params`): `layers`
stacked [L, ...] in layer order, linear kernels [in, out], the experts
[L, E, in, out].

The two conventions the source's keys do not settle have their own
copy here: the router's input (`r = h W_r` above, not `x` or `x'`) and
no attention bias. They are listed under `assumed` in the
configuration file.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

F32 = jnp.float32
Q_BLOCK = 512  # queries a block of the attention


def sizes_from_keys(keys: dict) -> dict:
    """The source's config.json keys -> the sizes this file reads. The
    two layouts are cut to num_hidden_layers."""
    n = keys["num_hidden_layers"]
    if not keys.get("moe_primary_router_apply_softmax", True):
        raise ValueError("the reference holds the softmax router only")
    return {
        "layers": n,
        "heads": keys["num_attention_heads"],
        "kv_heads": keys["num_key_value_heads"],
        "head_dim": keys["head_dim"],
        "eps": keys["rms_norm_eps"],
        "theta": float(keys["rope_theta"]),
        "window": keys["sliding_window_size"],
        "windowed": tuple(bool(v) for v in keys["sliding_window_layout"][:n]),
        "roped": tuple(bool(v) for v in keys["rope_layout"][:n]),
        "experts": keys["moe_num_primary_experts"],
        "top_k": keys["moe_num_active_primary_experts"],
        "norm_topk": bool(keys["norm_topk_prob"]),
    }


def _f32(x):
    return jnp.asarray(x).astype(F32)


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * _f32(w)


def rope(x, pos, theta):
    """x [T, H, D] rotated at positions pos [T]: pairs (x[j], x[j + D/2])
    by the angle pos * theta ** (-2j / D)."""
    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=F32) * 2.0 / x.shape[-1])
    ang = pos.astype(F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def attention(q, k, v, *, window: int):
    """q [T, Hq, D], k / v [T, Hk, D] -> [T, Hq, D]: causal softmax,
    with `window` > 0 over the last `window` keys alone; a block of
    queries at a time against every key."""
    T, Hq, D = q.shape
    Hk = k.shape[1]
    pad = -T % Q_BLOCK
    qb = jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(
        -1, Q_BLOCK, Hk, Hq // Hk, D)
    t0 = jnp.arange(qb.shape[0]) * Q_BLOCK
    u = jnp.arange(T)[None, :]

    def block(args):
        qq, start = args
        t = (start + jnp.arange(Q_BLOCK))[:, None]
        seen = u <= t
        if window:
            seen = seen & (t - u < window)
        s = jnp.einsum("qhgd,khd->hgqk", qq, k) * D ** -0.5
        s = jnp.where(seen[None, None], s, -jnp.inf)
        s = s - jnp.max(s, axis=-1, keepdims=True)
        p = jnp.exp(s)
        p = p / jnp.sum(p, axis=-1, keepdims=True)
        return jnp.einsum("hgqk,khd->qhgd", p, v)

    out = jax.lax.map(block, (qb, t0))
    return out.reshape(-1, Hq, D)[:T]


def experts(x, r, ex, *, top_k: int, norm_topk: bool):
    """x [T, d] the expert layer's input, r [T, E] the router's logits,
    ex the layer's gate / up / down [E, in, out] -> [T, d]. Every
    expert over every token, weighted 0 where the token did not choose
    it: the same sum, no sorting, no groups."""
    p = jax.nn.softmax(r, axis=-1)
    w, idx = jax.lax.top_k(p, top_k)  # ties: the lower expert id first
    if norm_topk:
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    E = r.shape[-1]
    dense = jnp.sum(jax.nn.one_hot(idx, E, dtype=F32) * w[..., None], axis=1)

    def one(y, e):
        g = _f32(ex["gate"][e])
        up = _f32(ex["up"][e])
        down = _f32(ex["down"][e])
        out = (jnp.maximum(x @ g, 0.0) * (x @ up)) @ down
        return y + dense[:, e, None] * out, None

    y, _ = jax.lax.scan(one, jnp.zeros_like(x), jnp.arange(E))
    return y


def _at(tree, i):
    return jax.tree_util.tree_map(lambda a: a[i], tree)


@functools.partial(jax.jit, static_argnames=("windowed", "roped", "sz"))
def _layer(h, lp, *, windowed: bool, roped: bool, sz):
    sz = dict(sz)
    eps, D = sz["eps"], sz["head_dim"]
    with jax.default_matmul_precision("highest"):
        T = h.shape[0]
        r = h @ _f32(lp["router"]["kernel"])
        x = rms_norm(h, lp["input_norm"]["weight"], eps)
        q = (x @ _f32(lp["q_proj"]["kernel"])).reshape(T, sz["heads"], D)
        k = (x @ _f32(lp["k_proj"]["kernel"])).reshape(T, sz["kv_heads"], D)
        v = (x @ _f32(lp["v_proj"]["kernel"])).reshape(T, sz["kv_heads"], D)
        if roped:
            pos = jnp.arange(T)
            q, k = rope(q, pos, sz["theta"]), rope(k, pos, sz["theta"])
        att = attention(q, k, v, window=sz["window"] if windowed else 0)
        h = h + att.reshape(T, -1) @ _f32(lp["o_proj"]["kernel"])
        x = rms_norm(h, lp["post_attn_norm"]["weight"], eps)
        return h + experts(x, r, lp["experts"], top_k=sz["top_k"],
                           norm_topk=sz["norm_topk"])


def logits(params, sizes: dict, ids, *, rows=None):
    """ids [T] -> float32 logits [T, V] (or at positions `rows` only),
    every layer in order over the whole sequence."""
    sz = tuple(sorted(sizes.items()))
    h = _f32(params["embed"]["weight"][jnp.asarray(ids)])
    for i in range(sizes["layers"]):
        h = _layer(h, _at(params["layers"], i), windowed=sizes["windowed"][i],
                   roped=sizes["roped"][i], sz=sz)
    return _head(
        h if rows is None else h[jnp.asarray(rows)],
        params["final_norm"]["weight"], params["lm_head"]["kernel"],
        eps=sizes["eps"])


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(h, norm, kernel, *, eps):
    with jax.default_matmul_precision("highest"):
        return rms_norm(h, norm, eps) @ _f32(kernel)
