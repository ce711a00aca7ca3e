"""Plain reference of GLM-5's language model (`model_type: glm_moe_dsa`):
`jax.numpy`, float32, matmul precision "highest", no cache, no kernels,
no batching, the NON-absorbed latent attention (per-head keys and values
are made from the latents) with every query's softmax taken over the
keys its indexer selected and no others. Computed in blocks (a layer at
a time, HEAD_BLOCK heads and QUERY_BLOCK queries at a time, a layer's
selection kept as packed bits) so that a stream of 50,000 positions fits
the chip at the published widths. Imports nothing from `oryx_tpu`; `cfg`
is read by attribute.

For hidden state h [T, H], layer l (0-based), t a query's position, u a
key's, eps `rms_norm_eps`:

    a  = rms_norm(h; input_norm)
    cq = rms_norm(a Wq_a; q_a_norm)
    q  = cq Wq_b, a head [q_nope | q_rope];  q_rope = rope(q_rope, t)
    c  = rms_norm(a Wkv_a[latent]; kv_a_norm);  kr = rope(a Wkv_a[rope], u)
    [k_nope | v] = c Wkv_b a head
    -- the indexer --
    qI = cq WqI_b, a head of `index_head_dim`, its first `qk_rope_head_dim`
         columns roped at t
    kI = layer_norm(a WkI; weight, bias, eps 1e-6), the same columns roped
    wI = (a Ww) * index_heads^-1/2 * index_head_dim^-1/2
    I[t, u] = sum_j wI[t, j] relu(qI[t, j] . kI[u]),  u <= t
    S_t = the `index_topk` largest I[t, .] (ties to the lower u; every
          u <= t while t < index_topk)
    -- attention over S_t --
    s = (q_nope . k_nope[u] + q_rope . kr[u]) / sqrt(dn + dr), u in S_t
    h = h + concat_heads(softmax_{S_t}(s) v) Wo
    x = rms_norm(h; post_attn_norm)
    l < `dense_layers`: h = h + SwiGLU(x; intermediate_size)
    else: p = sigmoid(float32(x) Wr); ids = top-K of p + b;
          w = routed_scaling_factor * p[ids] / sum p[ids]
          h = h + Shared(x) + sum_k w_k E_{ids_k}(x)

then the final rms_norm and the untied head. RoPE rotates the pairs
(x[2j], x[2j+1]) by t * theta^(-2j / D), D = `qk_rope_head_dim`, no
scaling.

The params are the program's (`qwen2._init_latent_params`): the leading
dense layers stacked under `dense_layers`, the expert layers under
`layers`, each with the attention's leaves, `indexer` (`q_b`, `k_proj`,
`k_norm`, `head_weights`) and its FFN (`gate_proj` / `up_proj` /
`down_proj`, or `router`, `experts`, `shared`); the fused projections
kept as the parts they are used in, as reference/mistral4_ref.py says.

A selection can be handed in (`forced_selection`: a layer's [T, >= T/8]
uint8, a query's keys as bits, `numpy.packbits` order), as the experts
can (`forced_experts`); the index scores the reference itself gives the
queries `score_rows` come back with `return_scores`.

The chip's share: `cfg.experts_held = (first, count)`; `held=` overrides
the range for the share test, `shared=False` leaves the shared expert
out (counted once when shares are added up). Logits are over the rows of
the vocabulary the params hold.

Departures from the published description, each in the configuration
file: the published inference code rotates qI and kI by a Hadamard
matrix and quantizes both to fp8 before their product; the rotation is
orthogonal and leaves qI . kI as it is, and this configuration's dtype
is bfloat16, so neither is done. The multi-token-prediction module is
not run. What the config's keys do not settle (`assumed`: which columns
of an index head are roped, the LayerNorm's eps, the softmax scale) is
written out HERE in its own arithmetic.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

F32 = jnp.float32
HEAD_BLOCK = 8
QUERY_BLOCK = 512
INDEX_LN_EPS = 1e-6  # assumed


def _f32(x):
    return jnp.asarray(x).astype(F32)


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * _f32(w)


def layer_norm(x, w, b, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * _f32(w) + _f32(b)


def rope_pairs(x, positions, theta):
    """x [T, ..., D], pairs (x[2j], x[2j+1]) rotated by t * theta^(-2j/D)."""
    D = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, D, 2, dtype=F32) / D))
    ang = positions.astype(F32)[:, None] * inv  # [T, D/2]
    ang = ang.reshape((x.shape[0],) + (1,) * (x.ndim - 2) + (D // 2,))
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    a, b = x[..., 0::2], x[..., 1::2]
    out = jnp.stack([a * cos - b * sin, b * cos + a * sin], axis=-1)
    return out.reshape(x.shape)


def softmax_scale(cfg):
    return 1.0 / math.sqrt(cfg.qk_nope_head_dim + cfg.qk_rope_head_dim)


def _rope_head(x, positions, cfg):
    """The first qk_rope_head_dim columns of the last axis roped (assumed)."""
    dr = cfg.qk_rope_head_dim
    return jnp.concatenate(
        [rope_pairs(x[..., :dr], positions, cfg.rope_theta), x[..., dr:]],
        axis=-1)


def indexer(a, cq, p, cfg, positions):
    """(qI [T, Hi, Di], kI [T, Di], wI [T, Hi]) of one layer."""
    T = a.shape[0]
    Hi, Di = cfg.index_heads, cfg.index_head_dim
    qi = _rope_head(
        (cq @ _f32(p["q_b"]["kernel"])).reshape(T, Hi, Di), positions, cfg)
    ki = layer_norm(a @ _f32(p["k_proj"]["kernel"]), p["k_norm"]["weight"],
                    p["k_norm"]["bias"], INDEX_LN_EPS)
    ki = _rope_head(ki, positions, cfg)
    wi = (a @ _f32(p["head_weights"]["kernel"])) / math.sqrt(Hi) / math.sqrt(Di)
    return qi, ki, wi


def index_scores(qi, wi, ki, q_pos, k_pos):
    """I [Q, T] of queries (qi [Q, Hi, Di], wi [Q, Hi]) against every
    key ki [T, Di]; -inf where the key lies after the query."""
    out = jnp.zeros((qi.shape[0], ki.shape[0]), F32)
    for h0 in range(0, qi.shape[1], HEAD_BLOCK):
        s = jnp.einsum("qhd,kd->qhk", qi[:, h0:h0 + HEAD_BLOCK], ki)
        out = out + jnp.einsum(
            "qhk,qh->qk", jax.nn.relu(s), wi[:, h0:h0 + HEAD_BLOCK])
    return jnp.where(q_pos[:, None] >= k_pos[None, :], out, -jnp.inf)


def select(scores, k):
    """[Q, T] -> bool [Q, T]: the k largest of a row (ties to the lower
    index), among the finite ones."""
    Q, T = scores.shape
    _, idx = jax.lax.top_k(scores, min(k, T))
    mask = jnp.zeros((Q, T), bool).at[jnp.arange(Q)[:, None], idx].set(True)
    return mask & jnp.isfinite(scores)


def _blocks(x, nb, pad):
    x = jnp.pad(x, ((0, pad),) + ((0, 0),) * (x.ndim - 1))
    return x.reshape((nb, QUERY_BLOCK) + x.shape[1:])


def mla(a, p, cfg, positions, forced=None, real=None, score_rows=None):
    """a [T, H] -> (out [T, H], the selection packed [T, ceil(T / 8)]
    uint8, index scores of `score_rows` [len, T] or None). forced: the
    selection to use instead of the indexer's own, packed alike (wider
    is fine); rows at or past `real` (padding) see every key before
    them whatever it says."""
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
    kr = rope_pairs(a @ _f32(p["k_rope_proj"]["kernel"]), positions,
                    cfg.rope_theta)
    q_rope = rope_pairs(q_rope, positions, cfg.rope_theta)
    scale = softmax_scale(cfg)
    w_uk, w_uv = _f32(p["w_uk"]), _f32(p["w_uv"])  # [Hq,dn,R], [Hq,R,dv]
    nb = -(-T // QUERY_BLOCK)
    pad = nb * QUERY_BLOCK - T
    blocks = functools.partial(_blocks, nb=nb, pad=pad)
    cols = -(-T // 8)

    qi, ki, wi = indexer(a, cq, p["indexer"], cfg, positions)
    if forced is None:
        def own(args):
            q, w, pos = args
            return jnp.packbits(
                select(index_scores(q, w, ki, pos, positions),
                       cfg.index_topk), axis=-1)

        packed = jax.lax.map(
            own, (blocks(qi), blocks(wi), blocks(positions))
        ).reshape(nb * QUERY_BLOCK, cols)[:T]
    else:
        packed = jnp.asarray(forced, jnp.uint8)[:T, :cols]
    scores = None
    if score_rows is not None:
        scores = index_scores(qi[score_rows], wi[score_rows], ki,
                              positions[score_rows], positions)
    if real is None:
        real = T

    outs = []
    for h0 in range(0, Hq, HEAD_BLOCK):
        hb = slice(h0, h0 + HEAD_BLOCK)
        k_nope = jnp.einsum("tc,hdc->thd", c, w_uk[hb])
        v = jnp.einsum("tc,hcd->thd", c, w_uv[hb])

        def block(args):
            qn, qr, pos, bits = args
            s = jnp.einsum("qhd,khd->hqk", qn, k_nope)
            s = s + jnp.einsum("qhd,kd->hqk", qr, kr)
            causal = pos[:, None] >= positions[None, :]
            sel = jnp.unpackbits(bits, axis=-1)[:, :T].astype(bool) & causal
            sel = jnp.where((pos >= real)[:, None], causal, sel)
            s = jnp.where(sel[None], s * scale, -jnp.inf)
            return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v)

        rows = jax.lax.map(block, (
            blocks(q_nope[:, hb]), blocks(q_rope[:, hb]), blocks(positions),
            blocks(packed)))
        outs.append(rows.reshape((nb * QUERY_BLOCK,) + rows.shape[2:])[:T])
    o = jnp.concatenate(outs, axis=1).reshape(T, Hq * dv)
    return o @ _f32(p["o_proj"]["kernel"]), packed, scores


def route(x, router, cfg, forced_ids=None):
    """x [T, H] -> (weights [T, K], ids [T, K]): sigmoid scores, the K
    largest of score + bias, the scores of those renormalised and
    scaled. forced_ids: the experts to use instead; the weights stay
    the router's own scores of them."""
    p = jax.nn.sigmoid(x @ _f32(router["kernel"]))
    if forced_ids is None:
        pick = p + _f32(router["bias"]) if "bias" in router else p
        _, ids = jax.lax.top_k(pick, cfg.num_experts_per_tok)
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
              "kv_a_norm", "w_uk", "w_uv", "o_proj", "indexer")
_DENSE = ("gate_proj", "up_proj", "down_proj")


# A layer in two jitted pieces, each slicing the one layer's weights it
# needs out of the stacked params INSIDE the program: a float32 copy of
# a layer's share beside the program's own weights does not fit the chip.
@functools.partial(jax.jit, static_argnums=(3,))
def _attention(h, att, l, cfg, positions, forced, real, score_rows):
    att = _at(att, l)
    a = rms_norm(h, att["input_norm"]["weight"], cfg.rms_norm_eps)
    out, packed, scores = mla(a, att, cfg, positions, forced, real,
                              score_rows)
    h = h + out
    return (h, rms_norm(h, att["post_attn_norm"]["weight"], cfg.rms_norm_eps),
            packed, scores)


@functools.partial(jax.jit, static_argnums=(3,))
def _experts(x, layers, l, cfg, forced_ids):
    lp = {k: _at(layers[k], l) for k in ("router", "experts", "shared")
          if k in layers}
    return moe_layer(x, lp, cfg, forced_ids=forced_ids)


@jax.jit
def _dense(x, ffn, l):
    return swiglu(x, _at(ffn, l))


def logits(params, cfg, ids, *, rows=None, forced_experts=None,
           forced_selection=None, return_experts=False,
           return_selection=False, score_rows=None, pad_to=1):
    """Full forward of the token ids [T], no cache: logits [len(rows), V]
    float32 at `rows` (default every position). Further values, in this
    order, as asked: return_experts the chosen experts [Lm, T, K] of the
    expert layers; return_selection every layer's selection, a list of
    [T, ceil(T / 8)] uint8; score_rows (positions) the reference's own
    index scores of those queries [L, len, T]. forced_experts
    [Lm, T, K] and forced_selection (a list a layer, packed alike): what
    each layer uses in place of its own choice (the program's). pad_to:
    T is filled up to a multiple of it with further tokens, which no
    earlier row sees, so that streams of nearly one length share a
    compiled layer."""
    with jax.default_matmul_precision("highest"):
        ids = jnp.asarray(ids, jnp.int32)
        T = ids.shape[0]
        pad = -T % pad_to
        ids = jnp.pad(ids, (0, pad))
        positions = jnp.arange(T + pad, dtype=jnp.int32)
        h = _f32(params["embed"]["weight"][ids])
        sr = None if score_rows is None else jnp.asarray(score_rows, jnp.int32)
        chosen, selected, scores = [], [], []
        Ld = cfg.dense_layers
        for l in range(cfg.num_layers):
            stack, j = (params["dense_layers"], l) if l < Ld else (
                params["layers"], l - Ld)
            forced = None
            if forced_selection is not None:
                import numpy as np

                f = np.asarray(forced_selection[l], np.uint8)
                cols = -(-(T + pad) // 8)
                forced = np.zeros((T + pad, cols), np.uint8)
                forced[:f.shape[0], :min(cols, f.shape[1])] = f[:, :cols]
            h, x, packed, sc = _attention(
                h, {k: stack[k] for k in _ATTENTION}, j, cfg, positions,
                forced, jnp.asarray(T, jnp.int32), sr)
            if return_selection:
                selected.append(packed[:T, : -(-T // 8)])
            if sr is not None:
                scores.append(sc[:, :T])
            if l < Ld:
                h = h + _dense(x, {k: stack[k] for k in _DENSE}, j)
                continue
            fe = None if forced_experts is None else jnp.pad(
                jnp.asarray(forced_experts[j], jnp.int32),
                ((0, pad), (0, 0)))
            y, e = _experts(x, stack, j, cfg, fe)
            h = h + y
            chosen.append(e[:T])
        h = h[:T]
        if rows is not None:
            h = h[jnp.asarray(rows)]
        h = rms_norm(h, params["final_norm"]["weight"], cfg.rms_norm_eps)
        out = (h @ _f32(params["lm_head"]["kernel"]),)
    if return_experts:
        out += (jnp.stack(chosen),)
    if return_selection:
        out += (selected,)
    if score_rows is not None:
        out += (jnp.stack(scores),)
    return out[0] if len(out) == 1 else out
