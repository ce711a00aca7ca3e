"""Plain reference forward of the Qwen2 decoder, independent of the
program's model code: straightforward jax.numpy in float32, no cache,
no kernels, no batching tricks, `default_matmul_precision("highest")`
(on a TPU a float32 matmul otherwise runs in bf16 passes).

Follows the published Qwen2 description (HF `modeling_qwen2.py`):
pre-norm blocks, RMSNorm, q/k/v projections with bias, rotary position
embedding in the "rotate-half" layout at theta from the config,
grouped-query attention (query head i reads kv head i // (Hq / Hk)),
causal softmax attention scaled by 1/sqrt(D), SwiGLU MLP, final norm,
untied output head.

Departures, each deliberate:
  * weights arrive in the dtype they are served in (bf16) and are
    upcast to float32 ONE LAYER AT A TIME, so a depth-16 stack at
    published widths fits beside the served weights;
  * it reads the program's parameter tree layout (stacked `[L, ...]`
    kernels, `[in, out]` orientation) — layout, not mathematics;
  * logits are returned only for the positions asked for.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32


def _rms(x, w, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w.astype(F32)


def _rope(x, positions, theta):
    """x [T, H, D], positions [T] -> rotated, HF rotate-half layout."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=F32) / d))
    ang = positions.astype(F32)[:, None] * inv[None, :]
    ang = jnp.concatenate([ang, ang], axis=-1)[:, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    rot = jnp.concatenate([-x2, x1], axis=-1)
    return x * jnp.cos(ang) + rot * jnp.sin(ang)


def _layer(x, lw, positions, mask, *, hq, hk, d, eps, theta):
    """One decoder block on x [T, H] float32; lw = this layer's weights
    (any dtype, upcast here)."""
    w = jax.tree.map(lambda a: a.astype(F32), lw)
    T = x.shape[0]
    h = _rms(x, w["input_norm"]["weight"], eps)
    q = h @ w["q_proj"]["kernel"]
    k = h @ w["k_proj"]["kernel"]
    v = h @ w["v_proj"]["kernel"]
    if "bias" in w["q_proj"]:
        q = q + w["q_proj"]["bias"]
        k = k + w["k_proj"]["bias"]
        v = v + w["v_proj"]["bias"]
    q = _rope(q.reshape(T, hq, d), positions, theta)
    k = _rope(k.reshape(T, hk, d), positions, theta)
    v = v.reshape(T, hk, d)
    g = hq // hk
    k = jnp.repeat(k, g, axis=1)
    v = jnp.repeat(v, g, axis=1)
    s = jnp.einsum("qhd,khd->hqk", q, k) / jnp.sqrt(F32(d))
    s = jnp.where(mask[None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    a = jnp.einsum("hqk,khd->qhd", p, v).reshape(T, hq * d)
    x = x + a @ w["o_proj"]["kernel"]
    h = _rms(x, w["post_attn_norm"]["weight"], eps)
    m = jax.nn.silu(h @ w["gate_proj"]["kernel"]) * (h @ w["up_proj"]["kernel"])
    return x + m @ w["down_proj"]["kernel"]


_BASE = ("input_norm", "post_attn_norm", "q_proj", "k_proj", "v_proj",
         "o_proj", "gate_proj", "up_proj", "down_proj")


def hidden_states(llm_params, llm_cfg, x, positions, valid):
    """x [T, H] input embeddings (float32) -> final-norm hidden [T, H].
    `valid` [T] bool marks real (non-padding) positions. LoRA leaves in
    the tree are ignored: at initialisation the adapters' B is zero."""
    T = x.shape[0]
    mask = (jnp.tril(jnp.ones((T, T), bool)) & valid[None, :])
    layer = jax.jit(_layer, static_argnames=(
        "hq", "hk", "d", "eps", "theta"))
    layers = llm_params["layers"]
    with jax.default_matmul_precision("highest"):
        for i in range(llm_cfg.num_layers):
            lw = {
                name: {k: a[i] for k, a in layers[name].items()
                       if not k.startswith("lora_")}
                for name in _BASE
            }
            x = layer(
                x, lw, positions, mask, hq=llm_cfg.num_heads,
                hk=llm_cfg.num_kv_heads, d=llm_cfg.head_dim,
                eps=llm_cfg.rms_norm_eps, theta=llm_cfg.rope_theta,
            )
        return _rms(x, llm_params["final_norm"]["weight"],
                    llm_cfg.rms_norm_eps)


def _head(llm_params, llm_cfg):
    if llm_cfg.tie_word_embeddings:
        return llm_params["embed"]["weight"].T
    return llm_params["lm_head"]["kernel"]


def logits_tail(llm_params, llm_cfg, token_ids, tail: int):
    """float32 logits [tail, V] of the last `tail` positions of one
    prompt, from a full causal forward over all of it."""
    ids = jnp.asarray(token_ids, jnp.int32)
    x = llm_params["embed"]["weight"][ids].astype(F32)
    pos = jnp.arange(ids.shape[0], dtype=jnp.int32)
    h = hidden_states(llm_params, llm_cfg, x, pos,
                      jnp.ones(ids.shape, bool))
    with jax.default_matmul_precision("highest"):
        return h[-tail:] @ _head(llm_params, llm_cfg).astype(F32)


def causal_lm_nll(llm_params, llm_cfg, token_ids, targets, positions,
                  valid, *, ignore_index: int, block: int = 512):
    """(sum of next-token NLL, count) over one row. `targets[t]` is the
    token the prediction made AT position t is held to (the batch's
    labels arrive already shifted), `ignore_index` where unsupervised."""
    ids = jnp.asarray(token_ids, jnp.int32)
    x = llm_params["embed"]["weight"][ids].astype(F32)
    h = hidden_states(llm_params, llm_cfg, x, jnp.asarray(positions),
                      jnp.asarray(valid, bool))
    head = _head(llm_params, llm_cfg)
    tgt = jnp.asarray(targets)
    total, count = 0.0, 0
    with jax.default_matmul_precision("highest"):
        for s in range(0, ids.shape[0], block):
            t = tgt[s:s + block]
            lg = h[s:s + block] @ head.astype(F32)
            lp = jax.nn.log_softmax(lg, axis=-1)
            keep = t != ignore_index
            nll = -jnp.take_along_axis(
                lp, jnp.where(keep, t, 0)[:, None].astype(jnp.int32), axis=1
            )[:, 0]
            total += float(jnp.sum(jnp.where(keep, nll, 0.0)))
            count += int(jnp.sum(keep))
    return total, count
