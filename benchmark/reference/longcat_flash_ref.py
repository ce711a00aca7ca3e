"""Plain reference of the LongCat-Flash decoder: `jax.numpy`, float32,
matmul precision "highest", no cache, no kernels, no batching, and the
NON-absorbed latent attention (per-head keys and values are made from
the latents), so that the program's absorbed paged decode is checked
against an independent form. Computed in blocks (a layer at a time, a
few heads at a time) so that it fits at the published widths.

For hidden state h [T, H], model layer l, sublayer i in (0, 1):

    for i in (0, 1):
        a = rms_norm(h, input_norm[i])
        h = h + MLA[i](a)
        x = rms_norm(h, post_attn_norm[i])
        if i == 0: s = MoE(x)            # the shortcut: taken here ...
        h = h + down[i](silu(gate[i](x)) * up[i](x))
        if i == 1: h = h + s             # ... added here

MLA(a) at position p: cq = rms_norm(a Wq_a); q = (cq Wq_b) *
sqrt(H / q_lora_rank), a head [q_nope | q_rope]; (c, kr) = split(a
Wkv_a); c = rms_norm(c) * sqrt(H / kv_lora_rank); kr is ONE key shared
by all heads, not scaled; (k_nope, v) = split(c Wkv_b) a head; RoPE over
the pairs (x[2j], x[2j+1]) on q_rope and kr; score = (q_nope . k_nope +
q_rope . kr) / sqrt(dn + dr); causal softmax; o = sum p v; MLA =
concat(o) Wo. The two scale factors are the family's modelling code
(the config holds two booleans).

MoE(x): p = softmax(float32(x) Wr) over E + Z outputs; the K experts
with the largest p + b (ties to the lower id); w_k = factor * p[e_k],
not renormalised; MoE(x) = sum_k w_k E_{e_k}(x), E_e a SwiGLU of width
`moe_intermediate_size` for e < E and the identity for e >= E.

The params keep the fused projections as the parts they are used in
(`qwen2._init_latent_params`): Wq_b by columns as `q_b_nope` and
`q_b_rope`, Wkv_a as `kv_a_proj` (latent) and `k_rope_proj` (shared
key), Wkv_b by head as `w_uk` [Hq, dn, R] (k_nope = c W_uk^T) and `w_uv`
[Hq, R, dv] (v = c W_uv).

The chip's share: `cfg.experts_held = (first, count)`. The params hold
the held experts' kernels only, and MoE leaves out what the other
E - count routed experts would add (zero-compute experts are counted
here). `held=` overrides the range for the share test: expert
first + j uses kernel j of `lp["experts"]`, and `zero=False` leaves the
zero-compute part out. Logits are over the rows of the vocabulary the
params hold.

Departures from the published description: none known; what the config
does not state (the scale formulas, no renormalisation, the RoPE
pairing) is listed under `assumed` in the configuration file.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

F32 = jnp.float32
HEAD_BLOCK = 8


def _f32(x):
    return jnp.asarray(x).astype(F32)


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * _f32(w)


def rope_pairs(x, positions, theta):
    """x [T, ..., D], pairs (x[2j], x[2j+1]) rotated by position * theta^(-2j/D)."""
    D = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, D, 2, dtype=F32) / D))
    ang = positions.astype(F32)[:, None] * inv  # [T, D/2]
    ang = ang.reshape((x.shape[0],) + (1,) * (x.ndim - 2) + (D // 2,))
    a, b = x[..., 0::2], x[..., 1::2]
    out = jnp.stack([a * jnp.cos(ang) - b * jnp.sin(ang),
                     b * jnp.cos(ang) + a * jnp.sin(ang)], axis=-1)
    return out.reshape(x.shape)


def mla(a, p, cfg, positions):
    """a [T, H] -> [T, H]; p: one sublayer's weights."""
    T, H = a.shape
    Hq, R = cfg.num_heads, cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    eps = cfg.rms_norm_eps
    cq = rms_norm(a @ _f32(p["q_a_proj"]["kernel"]), p["q_a_norm"]["weight"],
                  eps)
    s_q = (H / cfg.q_lora_rank) ** 0.5 if cfg.mla_scale_q_lora else 1.0
    q_nope = (cq @ _f32(p["q_b_nope"]["kernel"])).reshape(T, Hq, dn) * s_q
    q_rope = (cq @ _f32(p["q_b_rope"]["kernel"])).reshape(T, Hq, dr) * s_q
    c = rms_norm(a @ _f32(p["kv_a_proj"]["kernel"]), p["kv_a_norm"]["weight"],
                 eps)
    if cfg.mla_scale_kv_lora:
        c = c * (H / R) ** 0.5
    kr = rope_pairs(a @ _f32(p["k_rope_proj"]["kernel"]), positions,
                    cfg.rope_theta)  # [T, dr], one key for all heads
    q_rope = rope_pairs(q_rope, positions, cfg.rope_theta)
    w_uk, w_uv = _f32(p["w_uk"]), _f32(p["w_uv"])  # [Hq,dn,R], [Hq,R,dv]
    causal = positions[:, None] >= positions[None, :]
    outs = []
    for h0 in range(0, Hq, HEAD_BLOCK):
        hb = slice(h0, h0 + HEAD_BLOCK)
        k_nope = jnp.einsum("tc,hdc->thd", c, w_uk[hb])
        v = jnp.einsum("tc,hcd->thd", c, w_uv[hb])
        s = jnp.einsum("qhd,khd->hqk", q_nope[:, hb], k_nope)
        s = s + jnp.einsum("qhd,kd->hqk", q_rope[:, hb], kr)
        s = jnp.where(causal[None], s * (dn + dr) ** -0.5, -jnp.inf)
        outs.append(jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1),
                               v))
    o = jnp.concatenate(outs, axis=1).reshape(T, Hq * dv)
    return o @ _f32(p["o_proj"]["kernel"])


def route(x, router, cfg, forced_ids=None):
    """x [T, H] -> (weights [T, K], ids [T, K]). forced_ids: the experts
    to use instead of the router's own choice; the weights stay the
    router's own probabilities of them."""
    p = jax.nn.softmax(x @ _f32(router["kernel"]), axis=-1)
    if forced_ids is None:
        score = p + _f32(router["bias"]) if "bias" in router else p
        _, ids = jax.lax.top_k(score, cfg.num_experts_per_tok)
    else:
        ids = jnp.asarray(forced_ids)
    w = jnp.take_along_axis(p, ids, axis=-1)
    if cfg.norm_topk_prob:
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    return w * cfg.routed_scaling_factor, ids.astype(jnp.int32)


def moe_layer(x, lp, cfg, *, held=None, zero=True, forced_ids=None):
    """The expert layer's part computed here: (y [T, H], ids [T, K])."""
    w, ids = route(x, lp["router"], cfg, forced_ids)
    first, count = cfg.held if held is None else held
    E = cfg.num_experts
    y = jnp.zeros_like(x)
    ex = lp["experts"]
    for j in range(count):
        w_e = jnp.sum(jnp.where(ids == first + j, w, 0.0), axis=-1)
        g = jax.nn.silu(x @ _f32(ex["gate"][j])) * (x @ _f32(ex["up"][j]))
        y = y + w_e[:, None] * (g @ _f32(ex["down"][j]))
    if zero and cfg.zero_experts:
        y = y + jnp.sum(jnp.where(ids >= E, w, 0.0), axis=-1)[:, None] * x
    return y, ids


def swiglu(x, p):
    g = jax.nn.silu(x @ _f32(p["gate_proj"]["kernel"]))
    return (g * (x @ _f32(p["up_proj"]["kernel"]))) @ _f32(
        p["down_proj"]["kernel"])


def _at(tree, l):
    return jax.tree.map(lambda a: a[l], tree)


# A layer in three jitted pieces, each slicing the one layer's weights
# it needs out of the stacked params INSIDE the program: at the
# published widths a layer's share is 2.5 GB in bf16, and a float32
# copy of it beside the program's own weights does not fit the chip.
@functools.partial(jax.jit, static_argnums=(3,))
def _attention(h, sub, l, cfg, positions):
    sub = _at(sub, l)
    a = rms_norm(h, sub["input_norm"]["weight"], cfg.rms_norm_eps)
    h = h + mla(a, sub, cfg, positions)
    return h, rms_norm(h, sub["post_attn_norm"]["weight"], cfg.rms_norm_eps)


@jax.jit
def _dense_ffn(h, x, sub, l):
    return h + swiglu(x, _at(sub, l))


@functools.partial(jax.jit, static_argnums=(3,))
def _experts(x, layers, l, cfg, forced_ids):
    lp = {"router": _at(layers["router"], l),
          "experts": _at(layers["experts"], l)}
    return moe_layer(x, lp, cfg, forced_ids=forced_ids)


def double_layer(h, layers, l, cfg, positions, forced_ids=None):
    """Model layer l on h [T, H]: (h, expert ids [T, K])."""
    for i in (0, 1):
        sub = layers[f"sub{i}"]
        h, x = _attention(h, sub, l, cfg, positions)
        if i == 0:
            s, ids = _experts(x, layers, l, cfg, forced_ids)
        h = _dense_ffn(h, x, sub, l)
    return h + s, ids


def logits(params, cfg, ids, *, rows=None, forced_experts=None,
           return_experts=False):
    """Full forward of the token ids [T], no cache: logits [len(rows), V]
    float32 at `rows` (default every position), and with return_experts
    the chosen experts [L, T, K]. forced_experts [L, T, K]: the experts
    each layer uses (the program's), see `route`."""
    with jax.default_matmul_precision("highest"):
        ids = jnp.asarray(ids, jnp.int32)
        positions = jnp.arange(ids.shape[0], dtype=jnp.int32)
        h = _f32(params["embed"]["weight"][ids])
        chosen = []
        for l in range(cfg.num_layers):
            forced = None if forced_experts is None else jnp.asarray(
                forced_experts[l], jnp.int32)
            h, e = double_layer(h, params["layers"], l, cfg, positions,
                                forced)
            chosen.append(e)
        if rows is not None:
            h = h[jnp.asarray(rows)]
        h = rms_norm(h, params["final_norm"]["weight"], cfg.rms_norm_eps)
        out = h @ _f32(params["lm_head"]["kernel"])
    if return_experts:
        return out, jnp.stack(chosen)
    return out
