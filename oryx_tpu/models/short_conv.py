"""The gated short convolution of the LFM2 lineage (`layer_types` entry
"conv"): the state layer beside `models/mamba.py`'s, with no scan state.

On the normed layer input u [B, T, H], with d = cfg.hidden_size and
K = cfg.conv_L_cache taps:

    [B | C | X] = W_in u              (three d-wide thirds, in that order)
    z_t         = B_t * X_t
    c_t         = sum_{j<K} w[j] * z_{t-K+1+j}      (depthwise, causal,
                                                     no bias, no activation)
    out_t       = W_out (C_t * c_t)

What a row carries from one call to the next is its STATE: the last
K - 1 gated inputs z, [K - 1, d] (two rows of d at the published K = 3),
which is how the pool keeps them a slot (`conv` [Lc, S, (K-1) * d],
`qwen2.init_paged_kv_cache`) and, as a snapshot after a page's last
token, a page (`conv_edge` [Lc, P, (K-1) * d]). `valid` masks padding:
the window a call leaves behind is the last K - 1 REAL inputs.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from oryx_tpu.config import LLMConfig
from oryx_tpu.models.mamba import window_after

Params = dict[str, Any]


def init_mixer_params(cfg: LLMConfig, key: jax.Array, L: int, dtype) -> Params:
    """L stacked mixers, kernels random-normal 0.02 like every other;
    the taps [K, d] (channels last) at 0.5, so that a tap in the wrong
    place moves the output as a projection in the wrong place would."""
    H, K = cfg.hidden_size, cfg.conv_L_cache
    k_in, k_conv, k_out = jax.random.split(key, 3)

    def dense(k, shape, scale=0.02):
        return (
            jax.random.normal(k, (L, *shape), jnp.float32) * scale
        ).astype(dtype)

    return {
        "in_proj": {"kernel": dense(k_in, (H, 3 * H))},
        "conv": {"kernel": dense(k_conv, (K, H), 0.5)},
        "out_proj": {"kernel": dense(k_out, (H, H))},
    }


def _gates(cfg: LLMConfig, lp: Params, u):
    """u [..., H] -> (z = B * X, C), each [..., d]."""
    d = cfg.hidden_size
    bcx = u @ lp["in_proj"]["kernel"].astype(u.dtype)
    return bcx[..., :d] * bcx[..., 2 * d:], bcx[..., d:2 * d]


def mixer_prefill(cfg: LLMConfig, lp: Params, u, conv0, valid):
    """The mixer over a chunk. u [B, T, H]; conv0 [B, K-1, d], the
    window the chunk before left (zeros for a chunk that starts a
    sequence); valid [B, T] bool, true at real tokens, which lie first.
    Returns (out [B, T, H], the window after the row's last REAL token
    [B, K-1, d], win [B, K-1+T, d] = (conv0 | the chunk's z): the state
    after the chunk's token i is win[:, i + 1:i + K])."""
    T, K = u.shape[1], cfg.conv_L_cache
    z, C = _gates(cfg, lp, u)
    win = jnp.concatenate([conv0.astype(z.dtype), z], axis=1)
    w = lp["conv"]["kernel"].astype(z.dtype)
    c = sum(w[j] * win[:, j:j + T] for j in range(K))
    n = jnp.sum(valid, axis=1).astype(jnp.int32)
    out = (C * c) @ lp["out_proj"]["kernel"].astype(u.dtype)
    return out, window_after(win, n, K).astype(conv0.dtype), win


def mixer_step(cfg: LLMConfig, lp: Params, u, conv0, live):
    """One token a row. u [B, 1, H]; conv0 [B, K-1, d]; live [B] bool: a
    row that is not live (a finished or empty lane) keeps its window.
    Returns (out [B, 1, H], the window [B, K-1, d])."""
    z, C = _gates(cfg, lp, u[:, 0])
    win = jnp.concatenate([conv0.astype(z.dtype), z[:, None]], axis=1)
    w = lp["conv"]["kernel"].astype(z.dtype)
    c = jnp.sum(w[None] * win, axis=1)
    out = (C * c) @ lp["out_proj"]["kernel"].astype(u.dtype)
    conv1 = jnp.where(
        live[:, None, None], win[:, 1:].astype(conv0.dtype), conv0)
    return out[:, None], conv1


def page_edges(positions, kv_lengths, block_tables, num_pages: int,
               page_size: int):
    """Which page edges a prefill chunk crosses, a row: positions [B, T]
    (contiguous from positions[:, 0]), kv_lengths [B] the rows' real
    totals. Returns (pages [B, E] int32: the page whose LAST token is
    the e-th edge the row's chunk holds, `num_pages` (out of bounds, so
    a write there is dropped) where it holds fewer or the token is
    padding; n [B, E]: `window_after`'s index of the state after that
    token in the chunk's `win`)."""
    T = positions.shape[1]
    start = positions[:, :1]
    E = -(-T // page_size)
    first = start + (page_size - 1 - start) % page_size
    p = first + page_size * jnp.arange(E, dtype=positions.dtype)[None]
    ok = (p < start + T) & (p < kv_lengths[:, None])
    at = jnp.minimum(p // page_size, block_tables.shape[1] - 1)
    pages = jnp.take_along_axis(block_tables, at.astype(jnp.int32), axis=1)
    pages = jnp.where(ok & (pages < num_pages), pages, num_pages)
    return pages.astype(jnp.int32), (p - start + 1).astype(jnp.int32)


def edge_rows(win, n, K: int):
    """The windows after the tokens `page_edges` named: win [B, K-1+T,
    d], n [B, E] -> [B * E, (K-1) * d], flat as the planes keep them."""
    B, E = n.shape
    idx = (n[..., None] + jnp.arange(K - 1, dtype=n.dtype)).reshape(B, -1)
    rows = jnp.take_along_axis(win, idx[..., None], axis=1, mode="clip")
    return rows.reshape(B * E, -1)
