"""Plain reference of LFM2-24B-A2B's decoder (`model_type: lfm2_moe`):
`jax.numpy`, float32, matmul precision "highest", no kernels, no cache,
no chunks, no batching. Imports nothing from `oryx_tpu` and takes
NOTHING from its `LLMConfig`: `sizes` is a plain dict made of the
source's own keys (the configuration file's, `sizes_from_keys`). One
layer's weights are made float32 at a time, so the whole fits beside the
served bfloat16 ones.

With d = hidden_size and hidden state h [T, d], layer i of
num_hidden_layers, kind layer_types[i]:

    h = h + Op_i(rms_norm(h, w_op));   h = h + FFN_i(rms_norm(h, w_ffn))
    logits = rms_norm(h, w_final) @ E^T            (E the tied embedding)

`conv` (a gated short convolution, conv_L_cache = K taps, no bias):

    [B | C | X] = u W_in           (three d-wide thirds, in that order)
    z_t = B_t * X_t
    c_t = sum_{j<K} w[:, j] * z_{t-K+1+j}          (zeros before token 0)
    Op  = (C * c) W_out

`full_attention`: q = u W_q (num_attention_heads x head), k, v = u W_k,
u W_v (num_key_value_heads x head; query head j reads key/value head
j // group); RMSNorm over the head on q and on k, each with a learned
[head] weight; rotate-half RoPE at rope_theta; causal softmax at
1 / sqrt(head); W_o. No bias anywhere.

FFN of layers 0 .. num_dense_layers - 1: W2(silu(W1 x) * W3 x) at
intermediate_size. FFN of the others: s = sigmoid(x W_r) in float32; the
num_experts_per_tok experts are the top of s + b (b the expert bias,
for the SELECTION only; ties: the lower expert first); their weights are
s at those experts, divided by (their sum + 1e-6) when norm_topk_prob,
times routed_scaling_factor; each expert the same SwiGLU at
moe_intermediate_size. Every expert is computed on every token and the
unchosen ones weighed 0: no sorting, no grouping. `forced`: the experts
are HANDED IN (a program's own choice at every position and layer), the
weights still worked out here: the same function in two precisions,
which a choice that flips at a near-tie does not cloud.

The params are the program's pytree (`qwen2._init_recurrent_params`):
`layers["attn"]` / `layers["conv"]` stacked by kind in layer order,
`layers["dense"]` the leading dense FFNs, `layers["router"]` /
`layers["experts"]` the expert layers' in layer order; linear kernels
[in, out]; the conv taps are stored [K, d] (channels last), transposed
here to the published [d, K].

Departures from the published description: none known. What the
source's keys do not settle is listed under `assumed` in the
configuration file (the order B, C, X of the thirds and the
gate-conv-gate form, head size hidden / heads, q/k norm, silu, the tied
head).
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
    n = keys["num_hidden_layers"]
    return {
        "kinds": tuple(keys["layer_types"][:n]),
        "dense": keys["num_dense_layers"],
        "taps": keys["conv_L_cache"],
        "heads": heads,
        "kv_heads": keys["num_key_value_heads"],
        "head_dim": keys.get("head_dim") or d // heads,
        "theta": float(keys["rope_theta"]),
        "eps": keys["norm_eps"],
        "top_k": keys["num_experts_per_tok"],
        "norm_topk": bool(keys["norm_topk_prob"]),
        "scale": float(keys["routed_scaling_factor"]),
        "use_bias": bool(keys["use_expert_bias"]),
    }


def _f32(x):
    return jnp.asarray(x).astype(F32)


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * _f32(w)


def swiglu(x, w1, w3, w2):
    return (jax.nn.silu(x @ _f32(w1)) * (x @ _f32(w3))) @ _f32(w2)


def short_conv(u, mp, sz, *, before=None):
    """u [T, d] -> [T, d], from zeros before token 0 (or `before`
    [K - 1, d], the gated inputs z of the K - 1 tokens ahead, for the
    tests of a carried state)."""
    T, d = u.shape
    K = sz["taps"]
    bcx = u @ _f32(mp["in_proj"]["kernel"])
    B, C, X = bcx[:, :d], bcx[:, d:2 * d], bcx[:, 2 * d:]
    z = B * X
    head = jnp.zeros((K - 1, d), F32) if before is None else _f32(before)
    padded = jnp.concatenate([head, z], axis=0)
    w = _f32(mp["conv"]["kernel"]).T  # [d, K]
    c = sum(w[:, j] * padded[j:j + T] for j in range(K))
    return (C * c) @ _f32(mp["out_proj"]["kernel"])


def rope(x, theta):
    """x [T, heads, D]: rotate-half at positions 0 .. T - 1."""
    T, _, D = x.shape
    inv = theta ** (-jnp.arange(0, D, 2, dtype=F32) / D)
    ang = jnp.arange(T, dtype=F32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], axis=-1)[:, None]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], axis=-1)[:, None]
    rot = jnp.concatenate([-x[..., D // 2:], x[..., :D // 2]], axis=-1)
    return x * cos + rot * sin


def attention(u, ap, sz):
    """u [T, d] -> [T, d]: causal, a head at a time."""
    T = u.shape[0]
    Hq, Hk, Dh = sz["heads"], sz["kv_heads"], sz["head_dim"]
    q = (u @ _f32(ap["q_proj"]["kernel"])).reshape(T, Hq, Dh)
    k = (u @ _f32(ap["k_proj"]["kernel"])).reshape(T, Hk, Dh)
    v = (u @ _f32(ap["v_proj"]["kernel"])).reshape(T, Hk, Dh)
    q = rope(rms_norm(q, ap["q_norm"]["weight"], sz["eps"]), sz["theta"])
    k = rope(rms_norm(k, ap["k_norm"]["weight"], sz["eps"]), sz["theta"])
    seen = jnp.arange(T)[:, None] >= jnp.arange(T)[None, :]

    def head(j):
        kv = j // (Hq // Hk)
        s = (q[:, j] @ k[:, kv].T) * Dh ** -0.5
        p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
        return p @ v[:, kv]

    o = jax.lax.map(head, jnp.arange(Hq))  # [Hq, T, Dh]
    return jnp.moveaxis(o, 0, 1).reshape(T, Hq * Dh) @ _f32(
        ap["o_proj"]["kernel"])


def route(x, router, sz, chosen=None):
    """x [T, d] -> (the experts' weights [T, E] float32, 0 at the
    experts that were not chosen; the chosen experts [T, K]). `chosen`
    [T, K]: experts handed in (a program's own choice, for a comparison
    of the same function in two precisions) in place of the top of
    s + b; their weights are still this file's."""
    s = jax.nn.sigmoid(x @ _f32(router["kernel"]))
    pick = s + _f32(router["bias"]) if sz["use_bias"] else s
    _, own = jax.lax.top_k(pick, sz["top_k"])
    idx = own if chosen is None else chosen
    w = jnp.take_along_axis(s, idx, axis=-1)
    if sz["norm_topk"]:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-6)
    w = w * sz["scale"]
    return jnp.zeros_like(s).at[
        jnp.arange(x.shape[0])[:, None], idx].set(w), own


def experts(x, router, ex, sz, chosen=None):
    """Every expert on every token, weighed by `route`. Returns (y, the
    experts this file would choose [T, K])."""
    w, own = route(x, router, sz, chosen)  # [T, E]

    def one(carry, e):
        gate, up, down, we = e
        return carry + we[:, None] * swiglu(x, gate, up, down), None

    y, _ = jax.lax.scan(
        one, jnp.zeros_like(x), (ex["gate"], ex["up"], ex["down"], w.T))
    return y, own


def _at(tree, i):
    return jax.tree_util.tree_map(lambda a: a[i], tree)


@functools.partial(jax.jit, static_argnames=("kind", "moe", "sz"))
def _layer(h, lp, ffn, chosen=None, *, kind: str, moe: bool, sz):
    sz = dict(sz)
    with jax.default_matmul_precision("highest"):
        u = rms_norm(h, lp["input_norm"]["weight"], sz["eps"])
        if kind == "full_attention":
            h = h + attention(u, lp, sz)
        else:
            h = h + short_conv(u, lp["mixer"], sz)
        x = rms_norm(h, lp["post_attn_norm"]["weight"], sz["eps"])
        if moe:
            y, own = experts(x, ffn["router"], ffn["experts"], sz, chosen)
            return h + y, own
        return h + swiglu(x, ffn["gate_proj"]["kernel"],
                          ffn["up_proj"]["kernel"],
                          ffn["down_proj"]["kernel"]), None


def hidden(params, sizes: dict, ids, forced=None):
    """ids [T] -> (the last layer's output [T, d], the experts chosen
    [expert layers, T, K]), every layer in order over the whole
    sequence. `forced` [expert layers, T, K]: the experts each expert
    layer is handed (`route`); what is returned is still what this file
    WOULD choose, on the states the forced forward reaches."""
    sz = tuple(sorted(sizes.items()))
    h = _f32(params["embed"]["weight"][jnp.asarray(ids)])
    layers = params["layers"]
    seen = {"full_attention": 0, "conv": 0}
    chose = []
    for i, kind in enumerate(sizes["kinds"]):
        stack = layers["attn" if kind == "full_attention" else "conv"]
        lp = _at(stack, seen[kind])
        seen[kind] += 1
        chosen = None
        if i < sizes["dense"]:
            ffn = _at(layers["dense"], i)
        else:
            m = i - sizes["dense"]
            ffn = {"router": _at(layers["router"], m),
                   "experts": _at(layers["experts"], m)}
            if forced is not None:
                chosen = jnp.asarray(forced[m], jnp.int32)
        h, own = _layer(h, lp, ffn, chosen, kind=kind,
                        moe=i >= sizes["dense"], sz=sz)
        if own is not None:
            chose.append(own)
    return h, chose


def logits(params, sizes: dict, ids, *, rows=None, forced=None,
           return_chosen: bool = False):
    """ids [T] -> float32 logits [T, V] (or at positions `rows` only);
    with return_chosen also `hidden`'s second value."""
    h, chose = hidden(params, sizes, ids, forced)
    out = _head(
        h if rows is None else h[jnp.asarray(rows)],
        params["final_norm"]["weight"], params["embed"]["weight"],
        eps=sizes["eps"])
    return (out, chose) if return_chosen else out


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(h, norm, embed, *, eps):
    """Logits over the tied embedding [V, d]."""
    with jax.default_matmul_precision("highest"):
        return rms_norm(h, norm, eps) @ _f32(embed).T
