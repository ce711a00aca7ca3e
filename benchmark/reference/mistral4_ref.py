"""Plain reference of Mistral-Small-4's language model (`model_type:
mistral4`): `jax.numpy`, float32, matmul precision "highest", no cache,
no kernels, no batching, and the NON-absorbed latent attention (per-head
keys and values are made from the latents), so that the program's
absorbed paged decode is checked against an independent form. Computed
in blocks (a layer at a time, a few heads and QUERY_BLOCK queries at a
time) so that 20,000 positions fit at the published widths. Imports
nothing from `oryx_tpu`; `cfg` is read by attribute.

For hidden state h [T, H], every layer alike:

    a = rms_norm(h, input_norm);      h = h + MLA(a)
    x = rms_norm(h, post_attn_norm);  h = h + Shared(x) + MoE(x)

MLA(a) at position p: cq = rms_norm(a Wq_a); q = cq Wq_b, a head
[q_nope | q_rope] (64 | 64); (c, kr) = split(a Wkv_a) (256 | 64); c =
rms_norm(c); kr is ONE key shared by all heads; (k_nope, v) = split(c
Wkv_b) a head (64 | 128); RoPE over the pairs (x[2j], x[2j+1]) on q_rope
and kr at YaRN's frequencies (`yarn_inv_freq`: pair j keeps theta^(-2j /
D) below the ramp, takes it / factor above, the linear blend between;
cos and sin times `cos_sin_scale`, which is 1 here); q = q *
`query_scale(p)`; score = (q_nope . k_nope + q_rope . kr) *
`softmax_scale`; causal softmax; o = sum p v; MLA = concat(o) Wo.

MoE(x): p = softmax(float32(x) Wr) over the 128 routed experts; the 4
largest (ties to the lower id); w = p / their sum (`norm_topk_prob`)
times `routed_scaling_factor`; MoE(x) = sum_k w_k E_{e_k}(x), E a SwiGLU
of `moe_intermediate_size`. Shared(x): one SwiGLU of `n_shared_experts *
moe_intermediate_size` on every token, unweighted.

The params keep the fused projections as the parts they are used in
(`qwen2._init_latent_params`): Wq_b by columns as `q_b_nope` and
`q_b_rope`, Wkv_a as `kv_a_proj` (latent) and `k_rope_proj` (shared
key), Wkv_b by head as `w_uk` [Hq, dn, R] (k_nope = c W_uk^T) and `w_uv`
[Hq, R, dv] (v = c W_uv); the shared expert under `shared`.

The chip's share: `cfg.experts_held = (first, count)`. The params hold
the held experts' kernels only, and MoE leaves out what the other
routed experts would add; the shared expert is whole on every chip.
`held=` overrides the range for the share test (expert first + j uses
kernel j of `lp["experts"]`), `shared=False` leaves the shared expert
out (it is counted ONCE when the shares' routed parts are added up).
Logits are over the rows of the vocabulary the params hold.

Departures from the published description: three scalar conventions
that the config's keys do not settle (`assumed` in the configuration
file) are worked out HERE, from the raw fields, in `softmax_scale`,
`cos_sin_scale`, `query_scale` and `route`: the program has its own
arithmetic in `oryx_tpu/config.LLMConfig`, so a slip in either shows as
a difference, and a correction of a convention is two edits
(tests/test_mistral4.py holds the two to each other). The vision
encoder is left out (the catalog's row gives none of its sizes). None
other known.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

F32 = jnp.float32
HEAD_BLOCK = 8
QUERY_BLOCK = 512


def _f32(x):
    return jnp.asarray(x).astype(F32)


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * _f32(w)


def yarn_inv_freq(cfg):
    """The D/2 rotary frequencies of the rope columns under the
    config's YaRN parameters (plain theta^(-2j/D) without scaling)."""
    D, theta = cfg.qk_rope_head_dim, cfg.rope_theta
    inv = 1.0 / (theta ** (jnp.arange(0, D, 2, dtype=F32) / D))
    factor = cfg.rope_scaling_factor
    if factor <= 1.0:
        return inv
    orig = cfg.rope_original_max_position

    def pair_of(rotations):  # the pair that turns `rotations` times
        return D * math.log(orig / (rotations * 2 * math.pi)) / (
            2 * math.log(theta))

    low = max(math.floor(pair_of(cfg.rope_beta_fast)), 0)
    high = min(math.ceil(pair_of(cfg.rope_beta_slow)), D - 1)
    ramp = jnp.clip(
        (jnp.arange(D // 2, dtype=F32) - low) / max(high - low, 0.001), 0, 1)
    return inv / factor * ramp + inv * (1.0 - ramp)


def yarn_mscale(factor, mscale):
    return 1.0 if factor <= 1.0 else 0.1 * mscale * math.log(factor) + 1.0


def softmax_scale(cfg):
    """(dn + dr)^-0.5 times m * m, m = 0.1 * mscale_all_dim * ln(factor)
    + 1 (assumed (ii): 128^-0.5 x 1.4852^2 at the published values)."""
    m = yarn_mscale(cfg.rope_scaling_factor, cfg.rope_mscale_all_dim) \
        if cfg.rope_mscale_all_dim else 1.0
    return m * m / math.sqrt(cfg.qk_nope_head_dim + cfg.qk_rope_head_dim)


def cos_sin_scale(cfg):
    """YaRN's multiplier of cos and sin: mscale(factor, mscale) /
    mscale(factor, mscale_all_dim), 1 at the published values."""
    f = cfg.rope_scaling_factor
    return yarn_mscale(f, cfg.rope_mscale) / yarn_mscale(
        f, cfg.rope_mscale_all_dim)


def query_scale(cfg, positions):
    """Assumed (iii): 1 + beta * ln(1 + floor(p / original length)), 1
    below the original length and stepping at each multiple of it."""
    whole = jnp.floor_divide(positions, cfg.rope_original_max_position)
    return 1.0 + cfg.llama4_scaling_beta * jnp.log(1.0 + whole.astype(F32))


def rope_pairs(x, positions, inv, scale=1.0):
    """x [T, ..., D], pairs (x[2j], x[2j+1]) rotated by position * inv[j]."""
    D = x.shape[-1]
    ang = positions.astype(F32)[:, None] * inv  # [T, D/2]
    ang = ang.reshape((x.shape[0],) + (1,) * (x.ndim - 2) + (D // 2,))
    cos, sin = jnp.cos(ang) * scale, jnp.sin(ang) * scale
    a, b = x[..., 0::2], x[..., 1::2]
    out = jnp.stack([a * cos - b * sin, b * cos + a * sin], axis=-1)
    return out.reshape(x.shape)


def mla(a, p, cfg, positions):
    """a [T, H] -> [T, H]; p: one layer's attention weights."""
    T, H = a.shape
    Hq = cfg.num_heads
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    eps = cfg.rms_norm_eps
    cq = rms_norm(a @ _f32(p["q_a_proj"]["kernel"]), p["q_a_norm"]["weight"],
                  eps)
    q_nope = (cq @ _f32(p["q_b_nope"]["kernel"])).reshape(T, Hq, dn)
    q_rope = (cq @ _f32(p["q_b_rope"]["kernel"])).reshape(T, Hq, dr)
    c = rms_norm(a @ _f32(p["kv_a_proj"]["kernel"]), p["kv_a_norm"]["weight"],
                 eps)
    inv, cs = yarn_inv_freq(cfg), cos_sin_scale(cfg)
    kr = rope_pairs(a @ _f32(p["k_rope_proj"]["kernel"]), positions, inv, cs)
    q_rope = rope_pairs(q_rope, positions, inv, cs)
    q_scale = query_scale(cfg, positions)[:, None, None]
    q_nope, q_rope = q_nope * q_scale, q_rope * q_scale
    scale = softmax_scale(cfg)
    w_uk, w_uv = _f32(p["w_uk"]), _f32(p["w_uv"])  # [Hq,dn,R], [Hq,R,dv]
    # Queries in blocks of QUERY_BLOCK (padded; a padded query sits at
    # position 0, sees key 0 and is sliced off), heads HEAD_BLOCK at a
    # time: a block's scores are [HEAD_BLOCK, QUERY_BLOCK, T].
    nb = -(-T // QUERY_BLOCK)
    pad = nb * QUERY_BLOCK - T

    def blocks(x):
        x = jnp.pad(x, ((0, pad),) + ((0, 0),) * (x.ndim - 1))
        return x.reshape((nb, QUERY_BLOCK) + x.shape[1:])

    outs = []
    for h0 in range(0, Hq, HEAD_BLOCK):
        hb = slice(h0, h0 + HEAD_BLOCK)
        k_nope = jnp.einsum("tc,hdc->thd", c, w_uk[hb])
        v = jnp.einsum("tc,hcd->thd", c, w_uv[hb])

        def block(args):
            qn, qr, pos = args
            s = jnp.einsum("qhd,khd->hqk", qn, k_nope)
            s = s + jnp.einsum("qhd,kd->hqk", qr, kr)
            causal = pos[:, None] >= positions[None, :]
            s = jnp.where(causal[None], s * scale, -jnp.inf)
            return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v)

        rows = jax.lax.map(block, (
            blocks(q_nope[:, hb]), blocks(q_rope[:, hb]), blocks(positions)))
        outs.append(rows.reshape((nb * QUERY_BLOCK,) + rows.shape[2:])[:T])
    o = jnp.concatenate(outs, axis=1).reshape(T, Hq * dv)
    return o @ _f32(p["o_proj"]["kernel"])


def route(x, router, cfg, forced_ids=None):
    """x [T, H] -> (weights [T, K], ids [T, K]). forced_ids: the experts
    to use instead of the router's own choice; the weights stay the
    router's own probabilities of them (renormalised over them)."""
    p = jax.nn.softmax(x @ _f32(router["kernel"]), axis=-1)
    if forced_ids is None:
        _, ids = jax.lax.top_k(p, cfg.num_experts_per_tok)
    else:
        ids = jnp.asarray(forced_ids)
    w = jnp.take_along_axis(p, ids, axis=-1)
    if cfg.norm_topk_prob:
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    return w * cfg.routed_scaling_factor, ids.astype(jnp.int32)


def swiglu(x, p):
    g = jax.nn.silu(x @ _f32(p["gate_proj"]["kernel"]))
    return (g * (x @ _f32(p["up_proj"]["kernel"]))) @ _f32(
        p["down_proj"]["kernel"])


def moe_layer(x, lp, cfg, *, held=None, shared=True, forced_ids=None):
    """The expert layer's part computed here: (y [T, H], ids [T, K])."""
    w, ids = route(x, lp["router"], cfg, forced_ids)
    first, count = cfg.held if held is None else held
    y = jnp.zeros_like(x)
    ex = lp["experts"]
    for j in range(count):
        w_e = jnp.sum(jnp.where(ids == first + j, w, 0.0), axis=-1)
        g = jax.nn.silu(x @ _f32(ex["gate"][j])) * (x @ _f32(ex["up"][j]))
        y = y + w_e[:, None] * (g @ _f32(ex["down"][j]))
    if shared and "shared" in lp:
        y = y + swiglu(x, lp["shared"])
    return y, ids


def _at(tree, l):
    return jax.tree.map(lambda a: a[l], tree)


_ATTENTION = ("input_norm", "post_attn_norm", "q_a_proj", "q_a_norm",
              "q_b_nope", "q_b_rope", "kv_a_proj", "k_rope_proj",
              "kv_a_norm", "w_uk", "w_uv", "o_proj")


# A layer in two jitted pieces, each slicing the one layer's weights it
# needs out of the stacked params INSIDE the program: a float32 copy of
# a layer's share beside the program's own weights does not fit the
# chip.
@functools.partial(jax.jit, static_argnums=(3,))
def _attention(h, att, l, cfg, positions):
    att = _at(att, l)
    a = rms_norm(h, att["input_norm"]["weight"], cfg.rms_norm_eps)
    h = h + mla(a, att, cfg, positions)
    return h, rms_norm(h, att["post_attn_norm"]["weight"], cfg.rms_norm_eps)


@functools.partial(jax.jit, static_argnums=(3,))
def _experts(x, layers, l, cfg, forced_ids):
    lp = {k: _at(layers[k], l) for k in ("router", "experts", "shared")
          if k in layers}
    return moe_layer(x, lp, cfg, forced_ids=forced_ids)


def layer(h, layers, l, cfg, positions, forced_ids=None):
    """Model layer l on h [T, H]: (h, expert ids [T, K])."""
    h, x = _attention(h, {k: layers[k] for k in _ATTENTION}, l, cfg,
                      positions)
    y, ids = _experts(x, layers, l, cfg, forced_ids)
    return h + y, ids


def logits(params, cfg, ids, *, rows=None, forced_experts=None,
           return_experts=False, pad_to=1):
    """Full forward of the token ids [T], no cache: logits [len(rows), V]
    float32 at `rows` (default every position), and with return_experts
    the chosen experts [L, T, K]. forced_experts [L, T, K]: the experts
    each layer uses (the program's), see `route`. pad_to: T is filled up
    to a multiple of it with further tokens, which no earlier row sees
    (attention is causal, every other step is a row's own), so that
    streams of nearly one length share a compiled layer."""
    with jax.default_matmul_precision("highest"):
        ids = jnp.asarray(ids, jnp.int32)
        T = ids.shape[0]
        pad = -T % pad_to
        ids = jnp.pad(ids, (0, pad))
        positions = jnp.arange(T + pad, dtype=jnp.int32)
        h = _f32(params["embed"]["weight"][ids])
        chosen = []
        for l in range(cfg.num_layers):
            forced = None if forced_experts is None else jnp.pad(
                jnp.asarray(forced_experts[l], jnp.int32),
                ((0, pad), (0, 0)))
            h, e = layer(h, params["layers"], l, cfg, positions, forced)
            chosen.append(e[:T])
        h = h[:T]
        if rows is not None:
            h = h[jnp.asarray(rows)]
        h = rms_norm(h, params["final_norm"]["weight"], cfg.rms_norm_eps)
        out = h @ _f32(params["lm_head"]["kernel"])
    if return_experts:
        return out, jnp.stack(chosen)
    return out
