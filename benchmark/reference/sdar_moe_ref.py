"""Plain reference of the SDAR-MoE decoder (`model_type: sdar_moe`) and
of its generation loop, independent of the program's model code:
straightforward jax.numpy in float32, no cache, no kernels, no sorting
of tokens by expert, `default_matmul_precision("highest")` (on a TPU a
float32 matmul otherwise runs in bf16 passes).

The equations, per layer (hidden h, eps from the config, no biases):

  x = RMSNorm(h; g1); q = W_q x, k = W_k x, v = W_v x
  q <- RMSNorm_D(q; g_q), k <- RMSNorm_D(k; g_k)   per head, over head_dim
  RoPE (rotate-half layout) at the token's own position
  attention, scale 1/sqrt(D), GQA, mask M[i, j] = [j // B <= i // B]:
      full inside a block of B positions, causal across blocks
  h <- h + W_o attn
  x = RMSNorm(h; g2); r = W_r x in float32; p = softmax(r)
  I = the K largest p (ties: the lower expert id); w_e = p_e / sum_I p
  h <- h + sum_{e in I} w_e W_down^e (silu(W_gate^e x) * W_up^e x)

then the final RMSNorm and the untied head. The logits at position i are
the distribution of token i ITSELF (masked-token prediction, no shift).

Generation (`generate`): the published block-diffusion loop without a
cache. A block starts as its known tokens + mask_token_id elsewhere; a
denoising forward over everything so far + the block gives x0 = argmax
and c = softmax probability of x0; `low_confidence_static` with T steps
unmasks, at step t, the ceil(m / (T - t)) most confident of the m
still-masked positions; `low_confidence_dynamic` unmasks every masked
position with c > threshold and always the most confident one.

Departures from the published description, each deliberate and each
listed under `assumed` in the configuration file: q/k norm (the
qwen3_moe lineage; config.json has no key), no shift, B, the mask id,
the schedule. Of layout, not mathematics: weights arrive in the dtype
they are served in and are upcast ONE LAYER AT A TIME (seven layers in
float32 are 20 GB); it reads the program's parameter tree (stacked
`[L, ...]` kernels, `[in, out]` orientation).

`forced_experts` ([L, T, K] expert ids) replaces the reference's own
top-K SET by the given one (the weights are still the reference's own
softmax over ITS router logits, renormalized over the forced set): the
comparison's second run, see benchmark/correctness_sdar.py.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

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


def block_mask(T: int, B: int):
    """M[i, j] = [j // B <= i // B] over T positions."""
    blk = jnp.arange(T) // B
    return blk[None, :] <= blk[:, None]


def route(p, k: int, norm: bool, forced=None):
    """p [T, E] router probabilities -> (ids [T, k], weights [T, k]):
    the k largest (a stable descending sort: ties go to the lower
    expert id), or the `forced` ids with the reference's own p."""
    if forced is None:
        ids = jnp.argsort(-p, axis=-1, stable=True)[:, :k]
    else:
        ids = jnp.asarray(forced)
    w = jnp.take_along_axis(p, ids, axis=-1)
    if norm:
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    return ids, w


def expert_mlp(h, experts, ids, wts):
    """sum over a token's experts, as a loop over ALL experts, every
    token through each, with a dense per-token weight (0 where the
    expert was not chosen). An expert's kernels are upcast inside the
    loop, one expert at a time."""
    E = experts["gate"].shape[0]
    dense_w = jnp.zeros((h.shape[0], E), F32)
    dense_w = dense_w.at[jnp.arange(h.shape[0])[:, None], ids].add(wts)

    def one(out, xs):
        gate, up, down, we = xs
        g = jax.nn.silu(h @ gate.astype(F32))
        y = (g * (h @ up.astype(F32))) @ down.astype(F32)
        return out + we[:, None] * y, None

    out, _ = jax.lax.scan(
        one, jnp.zeros_like(h),
        (experts["gate"], experts["up"], experts["down"], dense_w.T),
    )
    return out


def moe_layer(h, w, cfg, forced=None):
    """The expert half of a block on normed h [T, H] float32 (w: this
    layer's weights, any dtype). Returns (y [T, H], chosen ids [T, K])."""
    r = h @ w["router"]["kernel"].astype(F32)
    ids, wts = route(jax.nn.softmax(r, axis=-1), cfg.num_experts_per_tok,
                     cfg.norm_topk_prob, forced)
    return expert_mlp(h, w["experts"], ids, wts), ids


def _layer(x, lw, positions, mask, cfg, forced=None):
    w = {k: v if k == "experts" else jax.tree.map(
        lambda a: a.astype(F32), v) for k, v in lw.items()}
    T, hq, hk, d = x.shape[0], cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    eps = cfg.rms_norm_eps
    h = _rms(x, w["input_norm"]["weight"], eps)
    q = (h @ w["q_proj"]["kernel"]).reshape(T, hq, d)
    k = (h @ w["k_proj"]["kernel"]).reshape(T, hk, d)
    v = (h @ w["v_proj"]["kernel"]).reshape(T, hk, d)
    q = _rms(q, w["q_norm"]["weight"], eps)
    k = _rms(k, w["k_norm"]["weight"], eps)
    q = _rope(q, positions, cfg.rope_theta)
    k = _rope(k, positions, cfg.rope_theta)
    g = hq // hk
    k = jnp.repeat(k, g, axis=1)
    v = jnp.repeat(v, g, axis=1)
    s = jnp.einsum("qhd,khd->hqk", q, k) / jnp.sqrt(F32(d))
    s = jnp.where(mask[None], s, -jnp.inf)
    a = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v)
    x = x + a.reshape(T, hq * d) @ w["o_proj"]["kernel"]
    h = _rms(x, w["post_attn_norm"]["weight"], eps)
    y, ids = moe_layer(h, w, cfg, forced)
    return x + y, ids


def logits(llm_params, cfg, ids, *, rows=None, forced_experts=None,
           return_experts: bool = False):
    """Logits [len(rows) or T, V] (float32) of token ids [T] under the
    block mask M, at positions 0..T-1. `rows`: the positions wanted
    (default all). return_experts: also the chosen expert ids
    [L, T, K]."""
    with jax.default_matmul_precision("highest"):
        ids = jnp.asarray(ids, jnp.int32)
        T = ids.shape[0]
        x = llm_params["embed"]["weight"][ids].astype(F32)
        pos = jnp.arange(T, dtype=jnp.int32)
        mask = block_mask(T, cfg.block_length)
        layer = jax.jit(_layer, static_argnums=(4,))
        chosen = []
        for l in range(cfg.num_layers):
            lw = jax.tree.map(lambda a: a[l], llm_params["layers"])
            forced = None if forced_experts is None else forced_experts[l]
            x, e = layer(x, lw, pos, mask, cfg, forced)
            chosen.append(np.asarray(e))
        x = _rms(x, llm_params["final_norm"]["weight"], cfg.rms_norm_eps)
        if rows is not None:
            x = x[jnp.asarray(rows)]
        out = x @ llm_params["lm_head"]["kernel"].astype(F32)
    if return_experts:
        return out, np.stack(chosen)
    return out


def unmask_count(masked: int, step: int, steps: int) -> int:
    """Static schedule: how many of `masked` positions step `step` of
    `steps` unmasks, an even ceil-spread (4 over 3 steps: 2, 1, 1)."""
    return -(-masked // (steps - step))


def generate(llm_params, cfg, prompt_ids, max_new_tokens: int, *,
             steps: int = 0, remasking: str = "low_confidence_dynamic",
             threshold: float = 0.9, eos: int | None = None):
    """The published block loop without a cache, greedy. Returns
    (tokens generated, a list cut at EOS / max_new_tokens; per-token
    margin between the two largest logits at the step that fixed it)."""
    B, mask_id = cfg.block_length, cfg.mask_token_id
    steps = steps or B
    seq = [int(t) for t in prompt_ids]
    n = len(seq)
    done = (n // B) * B
    out, margins = [], []
    while len(out) < max_new_tokens:
        known = seq[done:]
        blk = known + [mask_id] * (B - len(known))
        is_masked = [False] * len(known) + [True] * (B - len(known))
        marg = [0.0] * B
        max_steps = steps if remasking == "low_confidence_static" else B
        for t in range(max_steps):
            m = sum(is_masked)
            if m == 0:
                break
            lg = np.asarray(logits(
                llm_params, cfg, seq[:done] + blk,
                rows=list(range(done, done + B)),
            ))
            x0 = lg.argmax(-1)
            lse = np.log(np.exp(lg - lg.max(-1, keepdims=True)).sum(-1))
            conf = np.exp(-lse)  # softmax probability of the argmax
            top2 = np.sort(lg, axis=-1)[:, -2:]
            cand = [i for i in range(B) if is_masked[i]]
            cand.sort(key=lambda i: (-conf[i], i))
            if remasking == "low_confidence_static":
                take = cand[: unmask_count(m, t, steps)]
            else:
                take = [i for i in cand if conf[i] > threshold] or cand[:1]
            for i in take:
                blk[i], is_masked[i] = int(x0[i]), False
                marg[i] = float(top2[i, 1] - top2[i, 0])
        new = blk[len(known):]
        seq = seq[:done] + blk
        done += B
        for tok, mg in zip(new, marg[len(known):]):
            if len(out) >= max_new_tokens:
                break
            if eos is not None and tok == eos:
                return out, margins
            out.append(tok)
            margins.append(mg)
    return out, margins
