"""Autoregressive generation: jitted prefill + lax.scan decode loop.

Reference parity: HF `generate()` as driven by `OryxQwenForCausalLM`
(SURVEY.md §3.2): greedy or sampled decoding with a KV cache, stopping on
EOS. TPU-first: the whole decode loop is ONE compiled program with no
host round-trip per token — a `lax.while_loop` over the step body that
exits as soon as every row has finished (`_decode_while`; the streaming
path scans fixed-size chunks instead and exits between chunks);
right-padded batches advance with per-row positions, so mixed-length
multimodal prefills need no left-padding shuffle.
"""

from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from oryx_tpu.config import GenerationConfig, LLMConfig
from oryx_tpu.models import qwen2
from oryx_tpu.ops import paged_kv as paged_kv_lib
from oryx_tpu.utils import numerics as numerics_lib


def sample_token(
    logits: jnp.ndarray,
    key: jax.Array,
    *,
    temperature: float,
    top_p: float,
    top_k: int,
) -> jnp.ndarray:
    """Sample next token ids from [B, V] logits. temperature==0 → greedy."""
    if temperature == 0.0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    logits = logits / temperature
    if top_k > 0:
        # Clamp to the vocab dimension: top_k >= V keeps everything (the
        # kth value is the row minimum); unclamped it would index out of
        # range on the sorted axis.
        kth = jnp.sort(logits, axis=-1)[
            :, -min(top_k, logits.shape[-1])
        ][:, None]
        logits = jnp.where(logits < kth, -jnp.inf, logits)
    if top_p < 1.0:
        sorted_logits = jnp.sort(logits, axis=-1)[:, ::-1]
        probs = jax.nn.softmax(sorted_logits, axis=-1)
        cum = jnp.cumsum(probs, axis=-1)
        # Keep the smallest prefix with cumulative prob >= top_p (always
        # keeps the top token).
        cutoff_idx = jnp.sum(cum < top_p, axis=-1)
        cutoff = jnp.take_along_axis(sorted_logits, cutoff_idx[:, None], axis=-1)
        logits = jnp.where(logits < cutoff, -jnp.inf, logits)
    return jax.random.categorical(key, logits, axis=-1).astype(jnp.int32)


def truncate_logits_rows(
    logits: jnp.ndarray,  # [S, V]
    *,
    temperature: jnp.ndarray,  # [S] float (0 => greedy for that row)
    top_p: jnp.ndarray,  # [S] float
    top_k: jnp.ndarray,  # [S] int
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Per-row temperature scale + top-k + top-p truncation — the
    distribution-shaping half of `sample_token_rows`, factored out so
    speculative verification (`spec_verify_rows`) accepts and resamples
    against EXACTLY the distribution the non-speculative sampler draws
    from. Returns (truncated logits [S, V] with -inf outside the
    nucleus, is_greedy [S] bool). Greedy rows pass through at t=1 (the
    caller overrides them with argmax, as `sample_token_rows` does)."""
    V = logits.shape[-1]
    is_greedy = temperature <= 0.0
    t = jnp.where(is_greedy, 1.0, temperature)[:, None]
    l = logits / t
    tk = jnp.clip(top_k.astype(jnp.int32), 0, V)
    # ONE sort serves both cuts: the k-th largest is srt_d[tk - 1], and
    # the top-k-masked row sorted descending is srt_d with everything
    # under the k-th value at -inf (the same multiset in the same
    # order, ties at the k-th place kept). Only the sorted VALUES are
    # read, and equal values are interchangeable, so the sort need not
    # be stable: a stable one drags an index operand along and takes
    # twice as long on the TPU.
    srt_d = jnp.sort(l, axis=-1, stable=False)[:, ::-1]
    kth = jnp.take_along_axis(
        srt_d, jnp.clip(tk - 1, 0, V - 1)[:, None], axis=-1
    )
    has_k = (tk > 0)[:, None]
    l = jnp.where(has_k & (l < kth), -jnp.inf, l)
    srt_d = jnp.where(has_k & (srt_d < kth), -jnp.inf, srt_d)
    probs = jax.nn.softmax(srt_d, axis=-1)
    cum = jnp.cumsum(probs, axis=-1)
    # Smallest prefix with cumulative prob >= top_p (keeps the top token).
    cutoff_idx = jnp.sum(cum < top_p[:, None], axis=-1)
    cutoff = jnp.take_along_axis(srt_d, cutoff_idx[:, None], axis=-1)
    l = jnp.where((top_p < 1.0)[:, None] & (l < cutoff), -jnp.inf, l)
    return l, is_greedy


def sample_token_rows(
    logits: jnp.ndarray,  # [S, V]
    keys: jax.Array,  # [S] per-row PRNG keys
    *,
    temperature: jnp.ndarray,  # [S] float (0 => greedy for that row)
    top_p: jnp.ndarray,  # [S] float
    top_k: jnp.ndarray,  # [S] int
) -> jnp.ndarray:
    """Per-ROW sampling for continuous batching (`sample_token` treats
    its knobs as batch-wide statics; one compiled program per distinct
    value). Every slot carries its own (temperature, top_p, top_k) as
    traced arrays and its own key, so a row's draw is a function of that
    row alone — admitting or finishing a neighbor never perturbs an
    in-flight request's sample stream, and mixed sampling configs share
    ONE compiled decode.

    The cost follows what the rows ask for: a call whose rows are ALL
    greedy (empty and retired slots carry temperature 0) takes the
    conditional's argmax branch, and only a call that holds a sampled
    row pays for the sort and the noise. Callers split `keys` outside,
    so the branch taken never moves a row's random stream."""
    V = logits.shape[-1]
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)

    def sampled_rows():
        l, is_greedy = truncate_logits_rows(
            logits, temperature=temperature, top_p=top_p, top_k=top_k
        )
        # Per-row Gumbel-max with per-row keys (categorical over one
        # shared key would couple a row's draw to its batch position).
        u = jax.vmap(lambda k: jax.random.uniform(k, (V,)))(keys)
        g = -jnp.log(-jnp.log(jnp.maximum(u, jnp.finfo(jnp.float32).tiny)))
        sampled = jnp.argmax(l + g, axis=-1).astype(jnp.int32)
        return jnp.where(is_greedy, greedy, sampled)

    return jax.lax.cond(
        jnp.all(temperature <= 0.0), lambda: greedy, sampled_rows
    )


def make_stop_sequences(
    stop_strs: list[str], tokenizer
) -> jnp.ndarray | None:
    """Encode stop strings to a [S, L] int32 array, left-padded with -1.

    Reference parity: `KeywordsStoppingCriteria` in `oryx/mm_utils.py`
    (SURVEY.md §2 "MM utils") encodes each keyword once and compares the
    trailing generated ids — here the comparison happens inside the jitted
    decode scan so multi-token stops end rows without burning decode steps.

    Shapes are bucketed (S to a power of two, L to a multiple of 4) so
    per-request stop lists share compiled programs: -1 left-padding is a
    wildcard (matches any id), and filler ROWS are -3 throughout — -3
    equals neither real ids (>= 0), the -2 window init, nor the -1
    wildcard, so a filler row can never fire.
    """
    seqs = []
    for s in stop_strs:
        if not s:
            continue
        ids = tokenizer.encode(s, add_special_tokens=False)
        if ids:
            seqs.append(np.asarray(ids, np.int32))
    if not seqs:
        return None
    L = -(-max(len(s) for s in seqs) // 4) * 4
    S = 1 << (len(seqs) - 1).bit_length()
    out = np.full((S, L), -3, np.int32)
    for i, s in enumerate(seqs):
        out[i, : L - len(s)] = -1
        out[i, L - len(s):] = s
    return jnp.asarray(out)


@partial(
    jax.jit,
    static_argnames=(
        "cfg", "gen_cfg", "max_new_tokens", "cache_len", "attn_impl",
        "compute_dtype", "return_cache",
    ),
)
def generate(
    params,
    cfg: LLMConfig,
    gen_cfg: GenerationConfig,
    *,
    inputs_embeds: jnp.ndarray,  # [B, T, H] (pre-spliced; right-padded)
    lengths: jnp.ndarray,  # [B] real TOTAL lengths (incl. cached prefix)
    max_new_tokens: int,
    cache_len: int,
    key: jax.Array | None = None,
    attn_impl: str = "xla",
    compute_dtype=None,
    stop_sequences: jnp.ndarray | None = None,  # [S, L], left-pad -1
    kv_cache: dict | None = None,
    start: jnp.ndarray | None = None,  # [] int32 first slot to write
    return_cache: bool = False,
):
    """Returns (tokens [B, max_new_tokens] int32, num_generated [B] int32,
    finished [B] bool) — plus the KV cache when return_cache.

    Slots after EOS are filled with eos_token_id. cache_len must be a bucket
    >= T + max_new_tokens. A row also finishes when its trailing tokens
    match any stop sequence (num_generated then includes the stop tokens;
    the caller trims the decoded text). finished=False marks a row cut off
    by max_new_tokens (the OpenAI "length" finish reason) rather than by
    EOS/stop.

    kv_cache/start (prefix reuse, serve/pipeline.ChatSession): a cache
    whose slots [0, start) already hold a previous turn's K/V — only the
    suffix embeds are prefilled (written at `start`, positions absolute)
    and `lengths` counts prefix + suffix. The caller guarantees
    cache_len >= lengths + max_new_tokens.
    """
    if kv_cache is None:
        assert cache_len >= inputs_embeds.shape[1] + max_new_tokens, (
            cache_len, inputs_embeds.shape[1], max_new_tokens
        )
    if key is None:
        key = jax.random.key(0)
    carry, key = _prefill_carry(
        params, cfg, gen_cfg, inputs_embeds, lengths, key,
        cache_len=cache_len, attn_impl=attn_impl,
        compute_dtype=compute_dtype,
        stop_L=0 if stop_sequences is None else stop_sequences.shape[1],
        kv_cache=kv_cache, start=start,
    )
    step = _make_decode_step(
        params, cfg, gen_cfg, stop_sequences,
        cache_len=cache_len, attn_impl=attn_impl,
        compute_dtype=compute_dtype,
    )
    carry, toks, fin = _decode_while(
        step, carry, jax.random.split(key, max_new_tokens),
        max_new_tokens, gen_cfg.eos_token_id,
    )
    # num generated = tokens up to and including the finishing token (EOS
    # or the last token of a stop sequence).
    num = jnp.where(
        jnp.any(fin, axis=1), jnp.argmax(fin, axis=1) + 1, max_new_tokens
    )
    out = (toks, num.astype(jnp.int32), jnp.any(fin, axis=1))
    return out + (carry[0],) if return_cache else out


def _decode_while(step, carry, step_keys, max_new_tokens: int, eos: int):
    """Run the decode step to completion OR until every row finished —
    a `lax.while_loop` over the scan body, so a batch of short answers
    inside a long decode window (bucketed serving, MCQ eval) stops
    paying for the unused steps. Unexecuted slots keep the same values
    the scan would have produced (tokens: EOS fill; finished: True —
    the loop only exits early when ALL rows are finished).

    Returns (final carry, toks [B, max_new], fin [B, max_new])."""
    nB = carry[1].shape[0]  # carry = (cache, tok, lengths, finished, recent)
    toks0 = jnp.full((nB, max_new_tokens), eos, jnp.int32)
    fin0 = jnp.ones((nB, max_new_tokens), bool)

    def cond(state):
        i, c, _, _ = state
        return (i < max_new_tokens) & ~jnp.all(c[3])  # c[3] = finished

    def body(state):
        i, c, toks, fin = state
        c, (tok, f) = step(c, step_keys[i])
        toks = jax.lax.dynamic_update_index_in_dim(toks, tok, i, axis=1)
        fin = jax.lax.dynamic_update_index_in_dim(fin, f, i, axis=1)
        return i + 1, c, toks, fin

    _, carry, toks, fin = jax.lax.while_loop(
        cond, body, (jnp.zeros((), jnp.int32), carry, toks0, fin0)
    )
    return carry, toks, fin


def _prefill_carry(
    params, cfg: LLMConfig, gen_cfg: GenerationConfig, inputs_embeds,
    lengths, key, *, cache_len: int, attn_impl: str, compute_dtype,
    stop_L: int, kv_cache: dict | None = None,
    start: jnp.ndarray | None = None,
):
    """Prefill + first sampled token → the decode-scan carry
    (cache, next token, per-row lengths, finished flags, rolling
    stop-match window). Shared by `generate` and the streaming path.

    With kv_cache/start, only the suffix embeds are prefilled into an
    existing cache at slot `start` (absolute positions; `lengths` counts
    prefix + suffix) — the prefix-reuse path."""
    B, T, _ = inputs_embeds.shape
    start_vec = (
        jnp.zeros((B,), jnp.int32)
        if start is None
        else jnp.broadcast_to(start.astype(jnp.int32), (B,))
    )
    positions = start_vec[:, None] + jnp.arange(T, dtype=jnp.int32)[None, :]
    slot_ar = jnp.arange(cache_len, dtype=jnp.int32)[None, :]
    kv_mask = (slot_ar < lengths[:, None]).astype(jnp.int32)

    cache = kv_cache if kv_cache is not None else qwen2.init_kv_cache(
        cfg, B, cache_len, dtype=compute_dtype or jnp.float32
    )
    logits, cache = qwen2.forward(
        params, cfg,
        inputs_embeds=inputs_embeds, positions=positions,
        kv_cache=cache, write_slots=start_vec,
        kv_mask=kv_mask, attn_impl=attn_impl, compute_dtype=compute_dtype,
    )
    # Last real logit per row: suffix-local index of the final token.
    last = jnp.take_along_axis(
        logits, (lengths - 1 - start_vec)[:, None, None].astype(jnp.int32),
        axis=1,
    )[:, 0]
    key, sk = jax.random.split(key)
    tok0 = sample_token(
        last, sk, temperature=gen_cfg.temperature, top_p=gen_cfg.top_p,
        top_k=gen_cfg.top_k,
    )
    # Rolling last-L-token window per row for stop-sequence matching; -2
    # init can match neither real ids nor the -1 stop padding.
    recent0 = jnp.full((B, stop_L), -2, jnp.int32)
    return (cache, tok0, lengths, jnp.zeros((B,), bool), recent0), key


def _make_decode_step(
    params, cfg: LLMConfig, gen_cfg: GenerationConfig, stop_sequences,
    *, cache_len: int, attn_impl: str, compute_dtype,
):
    """One decode-scan step over the `_prefill_carry` state — the single
    definition both `generate` and `_stream_chunk` scan over."""
    slot_ar = jnp.arange(cache_len, dtype=jnp.int32)[None, :]

    def stop_hit(recent):
        if stop_sequences is None:
            return jnp.zeros((recent.shape[0],), bool)
        # [B, S, L]: pad positions (-1) match anything.
        m = (stop_sequences[None] == -1) | (
            recent[:, None, :] == stop_sequences[None]
        )
        return jnp.any(jnp.all(m, axis=-1), axis=-1)

    def step(carry, step_key):
        cache, tok, cur_len, finished, recent = carry
        pos = cur_len[:, None]  # [B, 1] absolute position of tok
        kv_mask = (slot_ar <= cur_len[:, None]).astype(jnp.int32)
        logits, cache = qwen2.forward(
            params, cfg,
            input_ids=tok[:, None], positions=pos,
            kv_cache=cache, write_slots=cur_len,
            kv_mask=kv_mask, attn_impl=attn_impl,
            compute_dtype=compute_dtype,
        )
        nxt = sample_token(
            logits[:, 0], step_key, temperature=gen_cfg.temperature,
            top_p=gen_cfg.top_p, top_k=gen_cfg.top_k,
        )
        if recent.shape[1]:
            recent = jnp.concatenate([recent[:, 1:], tok[:, None]], axis=1)
        finished = (
            finished | (tok == gen_cfg.eos_token_id) | stop_hit(recent)
        )
        nxt = jnp.where(finished, gen_cfg.eos_token_id, nxt)
        return (cache, nxt, cur_len + 1, finished, recent), (tok, finished)

    return step


# ---------------------------------------------------------------------------
# Streaming
# ---------------------------------------------------------------------------


# The streaming path jits the shared prefill directly (generate traces
# it inline inside its own jit).
_stream_prefill = partial(
    jax.jit,
    static_argnames=(
        "cfg", "gen_cfg", "cache_len", "attn_impl", "compute_dtype",
        "stop_L",
    ),
)(_prefill_carry)


@partial(
    jax.jit,
    static_argnames=(
        "cfg", "gen_cfg", "cache_len", "attn_impl", "compute_dtype",
    ),
    donate_argnames=("carry",),
)
def _stream_chunk(
    params, cfg: LLMConfig, gen_cfg: GenerationConfig, carry, step_keys,
    stop_sequences, *, cache_len: int, attn_impl: str, compute_dtype,
):
    step = _make_decode_step(
        params, cfg, gen_cfg, stop_sequences,
        cache_len=cache_len, attn_impl=attn_impl,
        compute_dtype=compute_dtype,
    )
    carry, (toks, fin) = jax.lax.scan(init=carry, f=step, xs=step_keys)
    return carry, jnp.moveaxis(toks, 0, 1), jnp.moveaxis(fin, 0, 1)


# hot-path
def generate_stream(
    params,
    cfg: LLMConfig,
    gen_cfg: GenerationConfig,
    *,
    inputs_embeds: jnp.ndarray,
    lengths: jnp.ndarray,
    max_new_tokens: int,
    cache_len: int,
    key: jax.Array | None = None,
    attn_impl: str = "xla",
    compute_dtype=None,
    stop_sequences: jnp.ndarray | None = None,
    chunk: int = 8,
    kv_cache: dict | None = None,
    start: jnp.ndarray | None = None,
    yield_cache: bool = False,
):
    """Streaming twin of `generate` (HF TextIteratorStreamer parity):
    yields np int32 token blocks [B, <=chunk] as they decode, with the
    same semantics (EOS fill after finish, stop sequences end rows) AND
    the same RNG stream — the post-prefill key is pre-split into one key
    per step (jax.random.split is prefix-stable), so sampled outputs
    match `generate` token-for-token at any temperature.
    The decode runs WHOLE `chunk`-token compiled dispatches (a shrunken
    final chunk would compile a second decode program); overshoot
    tokens past max_new_tokens are computed and dropped, so cache_len
    must cover T + ceil(max_new/chunk)*chunk. Larger chunks amortize
    host round-trips, smaller ones lower first-token latency.

    kv_cache/start: prefix reuse as in `generate`. With yield_cache the
    generator yields (block, cache) pairs — the cache reference is valid
    until the NEXT block is requested (the chunk dispatch donates it),
    so a consumer breaking out of the loop may keep the last one.
    """
    padded_new = -(-max_new_tokens // chunk) * chunk
    if kv_cache is None:
        assert cache_len >= inputs_embeds.shape[1] + padded_new, (
            cache_len, inputs_embeds.shape[1], padded_new
        )
    if key is None:
        key = jax.random.key(0)
    stop_L = 0 if stop_sequences is None else stop_sequences.shape[1]
    common = dict(
        cache_len=cache_len, attn_impl=attn_impl,
        compute_dtype=compute_dtype,
    )
    carry, key = _stream_prefill(
        params, cfg, gen_cfg, inputs_embeds, lengths, key,
        stop_L=stop_L, kv_cache=kv_cache, start=start, **common,
    )
    step_keys = jax.random.split(key, padded_new)
    done = 0
    while done < max_new_tokens:
        carry, toks, fin = _stream_chunk(
            params, cfg, gen_cfg, carry, step_keys[done:done + chunk],
            stop_sequences, **common,
        )
        n = min(chunk, max_new_tokens - done)
        # The per-chunk harvest IS the yield surface (and the early-exit
        # test below needs host booleans) — the one deliberate sync.
        toks, fin = np.asarray(toks)[:, :n], np.asarray(fin)[:, :n]  # oryxlint: disable=host-sync
        yield (toks, carry[0]) if yield_cache else toks
        done += n
        if fin[:, -1].all():
            break


# ---------------------------------------------------------------------------
# Paged chunked decode (continuous-batching serving path)
# ---------------------------------------------------------------------------


def _window_planes(window_tables, window_base, what: str) -> dict:
    """`qwen2.forward`'s two arguments for the window plane, which a
    config with window layers requires of `what`."""
    if window_tables is None or window_base is None:
        raise ValueError(qwen2.unsupported_for_window(
            f"{what} without the window plane's tables (window_tables=, "
            "window_base=)"))
    return {"window_tables": window_tables,
            "window_base": window_base.astype(jnp.int32)}


@partial(
    jax.jit,
    static_argnames=("cfg", "attn_impl", "compute_dtype", "return_routing",
                     "held_stats", "return_logits"),
    donate_argnames=("kv_pages",),
)
def paged_prefill(
    params,
    cfg: LLMConfig,
    inputs_embeds: jnp.ndarray,  # [B, T, H] right-padded
    lengths: jnp.ndarray,  # [B] real TOTAL lengths (incl. cached prefix)
    block_tables: jnp.ndarray,  # [B, max_pages] int32
    kv_pages: dict,  # qwen2.init_paged_kv_cache pytree (donated)
    start: jnp.ndarray,  # [B] int32 first logical slot to write
    keys: jax.Array,  # [B] per-row PRNG keys
    temperature: jnp.ndarray,  # [B]
    top_p: jnp.ndarray,  # [B]
    top_k: jnp.ndarray,  # [B]
    *,
    attn_impl: str = "xla",
    compute_dtype=None,
    return_routing: bool = False,
    held_stats: bool = False,
    slots: jnp.ndarray | None = None,  # [B] int32, see below
    return_logits: bool = False,
    window_tables: jnp.ndarray | None = None,  # [B, window pages] int32
    window_base: jnp.ndarray | None = None,  # [B] int32
):
    """Prompt prefill into a PAGED cache + first sampled token.

    The paged twin of `_prefill_carry`: K/V land in the rows' pages
    (through their block tables) instead of a dense per-batch buffer.
    With `start` > 0 only the suffix is prefilled at absolute positions
    (prefix KV reuse). Sampling is per-row (`sample_token_rows`) so one
    compiled prefill serves every sampling config at a given prompt
    bucket. Returns (kv_pages, tok0 [B], advanced keys [B]), and with
    return_routing (a static twin for the benchmark's comparison) the
    expert layers' routing, `qwen2.forward`'s, as a fourth value, with
    the [B, V] logits the first token was sampled from under
    "logits". held_stats (static; a config whose expert layer holds a
    share, `cfg.experts_held`) appends, before that, the
    [len(SHARE_STATS)] int32 `share_stats` of the chunk's REAL rows
    (`kv_tokens` counts them), which the scheduler's moe_prefill_*
    counters read.

    `slots` (a config with state-space layers, `cfg.recurrent`, which
    requires it): the engine slot each row's recurrent state lives at.
    A chunk with start 0 begins from a zero state whatever the slot
    held, any other from what the chunk before left there, and a
    right-padded chunk leaves the state of its last REAL token
    (`qwen2.forward`'s `state_slots`). return_logits (static; a twin
    for the benchmark's comparison on a config without experts) appends
    the [B, V] logits the first token was sampled from, last.

    `window_tables`, `window_base` (a config with window layers,
    `cfg.windowed`, which requires both): the window plane's block
    table a row and the position its slot 0 holds (`qwen2.forward`);
    `block_tables` is then the global plane's. The caller keeps
    positions max(0, start - sliding_window + 1) .. start + T inside the
    window table. held_stats is taken for such a config too (every
    expert is held)."""
    B, T, _ = inputs_embeds.shape
    state = {}
    if cfg.windowed:
        state = _window_planes(window_tables, window_base, "a prefill")
    if cfg.recurrent:
        if slots is None:
            raise ValueError(qwen2.unsupported_for_recurrent(
                "a prefill without the rows' slot indices (slots=)"))
        state = {"state_slots": slots.astype(jnp.int32)}
    start = jnp.broadcast_to(start.astype(jnp.int32), (B,))
    positions = start[:, None] + jnp.arange(T, dtype=jnp.int32)[None, :]
    page_size = paged_kv_lib.pool_plane(kv_pages).shape[2]
    K = block_tables.shape[1] * page_size
    kv_mask = (
        jnp.arange(K, dtype=jnp.int32)[None, :] < lengths[:, None]
    ).astype(jnp.int32)
    logits, kv_pages, *routing = qwen2.forward(
        params, cfg,
        inputs_embeds=inputs_embeds, positions=positions,
        kv_cache=kv_pages, write_slots=start, kv_mask=kv_mask,
        block_tables=block_tables, kv_lengths=lengths,
        attn_impl=attn_impl, compute_dtype=compute_dtype,
        return_routing=return_routing or held_stats, **state,
        # The twin of a config with an indexer sees every layer's
        # selection too, packed, under routing["selected"].
        **({"return_selected": True} if return_routing and cfg.indexed
           else {}),
        # A config with window layers projects the ONE row it samples
        # from: its head is 151,936 wide and a chunk 1,024 rows, whose
        # logits would be 0.9 GB of temporaries and as many operations
        # again as the layers'.
        **({"return_hidden": True} if cfg.windowed else {}),
    )
    with jax.named_scope("head"):
        last = jnp.take_along_axis(
            logits, (lengths - 1 - start)[:, None, None].astype(jnp.int32),
            axis=1,
        )[:, 0]
        if cfg.windowed:
            # (eight copies of the row: a product of ONE row is computed
            # on a float32 copy of the whole head, 1.5 GB of
            # temporaries.)
            last = qwen2.lm_head(
                params, cfg,
                jnp.broadcast_to(last[:, None], (B, 8, last.shape[-1]))
            )[:, 0]
    with jax.named_scope("sample"):
        pair = jax.vmap(lambda k: jax.random.split(k, 2))(keys)
        tok0 = sample_token_rows(
            last, pair[:, 1], temperature=temperature, top_p=top_p,
            top_k=top_k,
        )
        out = (kv_pages, tok0, pair[:, 0])
        if held_stats:
            # The router saw rows [B * T]; a row past the prompt is
            # padding.
            real = (positions < lengths[:, None]).reshape(-1).astype(
                jnp.int32)
            out = out + (share_stats(cfg, routing[0]["ids"], real),)
    if return_routing:
        out = out + (dict(routing[0], logits=last),)
    if return_logits:
        out = out + (last,)
    return out


@partial(jax.jit, static_argnames=("width",))
def slice_embeds(embeds: jnp.ndarray, start, *, width: int) -> jnp.ndarray:
    """[B, T, H] → the [B, width, H] window at traced offset `start`.

    One compiled program per (T, width) pair — the chunked-prefill
    slicer (a host-side `embeds[:, a:b]` would compile one slice per
    distinct offset). dynamic_slice CLAMPS out-of-range starts, which
    would silently misalign tokens: callers pad `embeds` so that every
    chunk start satisfies start + width <= T (`pad_embeds_for_chunks`).
    """
    return jax.lax.dynamic_slice_in_dim(embeds, start, width, axis=1)


def pad_embeds_for_chunks(embeds: jnp.ndarray, chunk: int) -> jnp.ndarray:
    """Zero-pad [B, T, H] on the token axis so every `chunk`-wide window
    starting at an offset < T stays in bounds (see `slice_embeds`). The
    padded columns prefill garbage KV past each row's real length —
    slots the decode loop overwrites before reading or masks out,
    exactly like the right-padding of a bucketed single-shot prefill."""
    return jnp.pad(embeds, ((0, 0), (0, chunk), (0, 0)))


def paged_prefill_chunks(
    params,
    cfg: LLMConfig,
    inputs_embeds: jnp.ndarray,  # [B, T, H] right-padded
    lengths: jnp.ndarray,  # [B] real TOTAL lengths (incl. cached prefix)
    block_tables: jnp.ndarray,  # [B, max_pages] int32
    kv_pages: dict,  # donated through the per-chunk calls
    start: int,  # shared first logical slot to write (cached prefix end)
    keys: jax.Array,  # [B] per-row PRNG keys
    temperature: jnp.ndarray,  # [B]
    top_p: jnp.ndarray,  # [B]
    top_k: jnp.ndarray,  # [B]
    *,
    prefill_chunk: int,
    attn_impl: str = "xla",
    compute_dtype=None,
):
    """`paged_prefill` in bounded windows: a host loop dispatching the
    SAME compiled program over `prefill_chunk`-token embed slices, so a
    long prompt never occupies the device in one monolithic dispatch
    (the admission path interleaves these with decode chunks).

    Bit-parity with the single-shot call: valid-slot KV and the sampled
    first token are identical — chunk grouping only changes the masked
    garbage past each row's length, and every chunk is seeded with the
    ORIGINAL per-row key (only the final real chunk's sample and
    advanced key are kept, which is exactly the single-shot RNG
    contract: tok0 ~ split(key)[1], key' = split(key)[0]).

    Returns (kv_pages, tok0 [B], advanced keys [B])."""
    B, T, _ = inputs_embeds.shape
    host_len = [int(x) for x in np.asarray(lengths)]
    max_len = max(host_len)
    embeds = pad_embeds_for_chunks(inputs_embeds, prefill_chunk)
    tok0 = np.zeros((B,), np.int32)
    out_keys = list(keys)
    lengths = jnp.asarray(lengths, jnp.int32)
    off = start
    while off < max_len:
        end = off + prefill_chunk
        sl = slice_embeds(
            embeds, jnp.asarray(off - start, jnp.int32),
            width=prefill_chunk,
        )
        # Every chunk DELIBERATELY consumes the same original per-row
        # keys: only the final real chunk's sample + advanced key are
        # kept (see docstring), which is exactly the single-shot RNG
        # contract. Re-deriving per chunk would make tok0 depend on
        # prefill_chunk — a replay-breaking divergence.
        kv_pages, tok, nkeys = paged_prefill(  # oryxlint: disable=key-linearity
            params, cfg, sl, jnp.minimum(lengths, end), block_tables,
            kv_pages, jnp.asarray([off], np.int32), keys,
            temperature, top_p, top_k,
            attn_impl=attn_impl, compute_dtype=compute_dtype,
        )
        for b, L in enumerate(host_len):
            if off <= L - 1 < end:  # row b's final real chunk
                tok0[b] = int(np.asarray(tok)[b])
                out_keys[b] = nkeys[b]
        off = end
    return kv_pages, jnp.asarray(tok0), jnp.stack(out_keys)


@partial(
    jax.jit,
    static_argnames=(
        "cfg", "chunk", "eos", "attn_impl", "compute_dtype", "numerics",
        "return_routing", "return_logits",
    ),
    donate_argnames=("kv_pages",),
)
def paged_decode_chunk(
    params,
    cfg: LLMConfig,
    kv_pages: dict,  # donated
    block_tables: jnp.ndarray,  # [S, max_pages] int32
    tok: jnp.ndarray,  # [S] next token to feed per slot
    lengths: jnp.ndarray,  # [S] kv tokens held per slot (frozen on finish)
    finished: jnp.ndarray,  # [S] bool (True for finished AND empty slots)
    recent: jnp.ndarray,  # [S, stop_L] rolling stop window (-2 init)
    keys: jax.Array,  # [S] per-slot PRNG keys
    temperature: jnp.ndarray,  # [S]
    top_p: jnp.ndarray,  # [S]
    top_k: jnp.ndarray,  # [S]
    stop_sequences: jnp.ndarray | None = None,  # [Sq, L] (shared, static)
    *,
    chunk: int,
    eos: int,
    attn_impl: str = "xla",
    compute_dtype=None,
    numerics: bool = False,
    return_routing: bool = False,
    return_logits: bool = False,
    window_tables: jnp.ndarray | None = None,  # [S, window pages] int32
    window_base: jnp.ndarray | None = None,  # [S] int32
):
    """`chunk` decode steps over a FIXED-SLOT batch with a paged cache —
    the continuous-batching inner loop. One compiled program per
    (num_slots, max_pages, chunk) regardless of which slots are live:
    finished/empty slots still flow through the math but their cache
    writes are dropped (write_mask) and their lengths freeze, so the
    scheduler can retire and admit requests BETWEEN chunks by editing
    the small host-side state arrays — never recompiling, never touching
    other rows' streams (per-row keys + per-row sampling).

    Step semantics mirror `_make_decode_step` exactly (greedy token ids
    are bit-identical to the dense path at equal logical KV width).
    Returns (kv_pages, tok, lengths, finished, recent, keys,
    toks [S, chunk], fin [S, chunk]).

    numerics=True (STATIC — one extra stable compiled program, never a
    per-step recompile) appends ONE more output: the [6] float32 logit
    -stat accumulator (utils/numerics.py) folded over the chunk's live
    rows inside this same dispatch — token streams and every other
    output are bit-identical to the numerics=False program (the probe
    only reads the logits the sampler already computed).

    A config whose expert layer holds a share (`cfg.experts_held`) or has
    zero-compute experts appends the [len(SHARE_STATS)] int32 sums of
    `share_stats` over the chunk's steps, which the scheduler's moe_*
    and decode_kv_tokens counters read. return_routing=True (the static
    twin for the benchmark's comparison, as `paged_prefill`'s) appends
    every step's logits [S, chunk, V] and expert ids [chunk, L, S, K],
    last (and behind them, for a config with an indexer, the rows each
    layer selected [chunk, L, S, k]: ascending, the first
    min(length, k) real). return_logits=True (the same twin for a config
    without experts) appends the logits alone.

    A config with state-space layers (`cfg.recurrent`): lane s IS slot
    s, so its recurrent state is row s of the pool's per-slot planes; a
    live lane's state advances a token a step, a lane with `finished`
    (ended, empty, or still prefilling) keeps its state untouched.

    A config with window layers (`cfg.windowed`): `window_tables` and
    `window_base` are the window plane's block table a lane and the
    position its slot 0 holds, fixed for the chunk (the caller keeps
    lengths - sliding_window + 1 .. lengths + chunk inside the table);
    an expert config of that kind appends the `share_stats` sums too
    (held = every expert), and so does an expert config with state
    layers."""
    page_size = paged_kv_lib.pool_plane(kv_pages).shape[2]
    shared = bool(cfg.experts_held or cfg.zero_experts
                  or ((cfg.windowed or cfg.recurrent) and cfg.num_experts))
    planes = {}
    if cfg.windowed:
        planes = _window_planes(window_tables, window_base, "a decode chunk")
    K = block_tables.shape[1] * page_size
    slot_ar = jnp.arange(K, dtype=jnp.int32)[None, :]

    def stop_hit(recent):
        if stop_sequences is None:
            return jnp.zeros((recent.shape[0],), bool)
        m = (stop_sequences[None] == -1) | (
            recent[:, None, :] == stop_sequences[None]
        )
        return jnp.any(jnp.all(m, axis=-1), axis=-1)

    def step(carry, _):
        kv_pages, tok, cur_len, finished, recent, keys, *more = carry
        if numerics:
            nstats = more[0]
        with jax.named_scope("sample"):
            pair = jax.vmap(lambda k: jax.random.split(k, 2))(keys)
        pos = cur_len[:, None]
        kv_mask = (slot_ar <= cur_len[:, None]).astype(jnp.int32)
        logits, kv_pages, *routing = qwen2.forward(
            params, cfg,
            input_ids=tok[:, None], positions=pos,
            kv_cache=kv_pages, write_slots=cur_len, kv_mask=kv_mask,
            block_tables=block_tables, write_mask=~finished,
            # A finished or empty lane reads nothing (its token is
            # replaced below): length 0 is no step of the page walk.
            kv_lengths=(kv_lengths := jnp.where(finished, 0, cur_len + 1)),
            attn_impl=attn_impl, compute_dtype=compute_dtype,
            **({"return_routing": True} if shared or return_routing
               else {}), **planes,
            **({"return_selected": True} if return_routing and cfg.indexed
               else {}),
        )
        with jax.named_scope("sample"):
            if numerics:
                # Live-row logit probe on the logits the sampler is about
                # to consume — same dispatch, zero extra device calls.
                nstats = numerics_lib.accumulate_logit_stats(
                    nstats, logits[:, 0], ~finished
                )
            nxt = sample_token_rows(
                logits[:, 0], pair[:, 1],
                temperature=temperature, top_p=top_p, top_k=top_k,
            )
            if recent.shape[1]:
                recent = jnp.concatenate([recent[:, 1:], tok[:, None]], axis=1)
            finished = finished | (tok == eos) | stop_hit(recent)
            nxt = jnp.where(finished, eos, nxt)
            cur_len = cur_len + (~finished).astype(jnp.int32)
            out = (kv_pages, nxt, cur_len, finished, recent, pair[:, 0])
            if numerics:
                out = out + (nstats,)
            if shared:
                # `finished` here is this step's, as the forward saw it.
                out = out + (more[-1] + share_stats(
                    cfg, routing[0]["ids"], kv_lengths),)
        ys = (tok, finished)
        if return_routing:
            ys = ys + (logits[:, 0], routing[0]["ids"])
            if cfg.indexed:
                ys = ys + (routing[0]["selected"],)
        if return_logits:
            ys = ys + (logits[:, 0],)
        return out, ys

    carry0 = (kv_pages, tok, lengths, finished, recent, keys)
    if numerics:
        carry0 = carry0 + (numerics_lib.init_logit_stats(),)
    if shared:
        carry0 = carry0 + (jnp.zeros((len(SHARE_STATS),), jnp.int32),)
    carry, (toks, fin, *seen) = jax.lax.scan(
        step, carry0, None, length=chunk)
    out = carry[:6] + (jnp.moveaxis(toks, 0, 1), jnp.moveaxis(fin, 0, 1))
    out = out + carry[6:]
    if return_routing:
        out = out + (jnp.moveaxis(seen[0], 0, 1), seen[1])
        if cfg.indexed:  # every step's selection [chunk, L, S, k], last
            out = out + (seen[2],)
    if return_logits:
        out = out + (jnp.moveaxis(seen[-1], 0, 1),)
    return out


# What a decode step of a config with a share of the experts counts
# (`share_stats`), in this order; per layer-forward like the block
# step's moe_* statistics.
SHARE_STATS = (
    "layer_forwards", "pairs", "zero_pairs", "held_rows", "held_rows_max",
    "held_hit", "kv_tokens",
)


def share_stats(cfg: LLMConfig, ids: jnp.ndarray, kv_lengths: jnp.ndarray):
    """SHARE_STATS of one forward: ids [L, S, K] the chosen experts,
    kv_lengths [S] what each lane read (0 = a lane that is not live).
    pairs: (token, expert) pairs of live lanes; zero_pairs: those that
    went to a zero-compute expert; held_rows: those that went to an
    expert held here, held_rows_max the busiest held expert's a layer
    summed over layers, held_hit the held experts with a row;
    kv_tokens: cached tokens the live lanes' attention read a cache
    layer."""
    live = kv_lengths > 0
    first, count = cfg.held
    ids = jnp.where(live[None, :, None], ids, -1)
    rows = jnp.sum(
        ids[..., None] == first + jnp.arange(count, dtype=ids.dtype),
        axis=(1, 2),
    )  # [L, count]
    any_live = jnp.any(live).astype(jnp.int32)
    return jnp.stack([
        ids.shape[0] * any_live,
        jnp.sum(ids >= 0), jnp.sum(ids >= cfg.num_experts),
        jnp.sum(rows), jnp.sum(jnp.max(rows, axis=1)), jnp.sum(rows > 0),
        jnp.sum(kv_lengths),
    ]).astype(jnp.int32)


@jax.jit
def overlay_lanes(
    lengths: jnp.ndarray,  # [S] as the last decode chunk left them
    finished: jnp.ndarray,  # [S]
    recent: jnp.ndarray,  # [S, stop_L]
    edited: jnp.ndarray,  # [S] bool: the host changed this lane since
    host_lengths: jnp.ndarray,  # [S] int32
    host_finished: jnp.ndarray,  # [S] bool
):
    """The host's edits since the last enqueue laid over the lane state
    a decode chunk left ON THE DEVICE, so that the next chunk can be
    enqueued before the last one is read: a lane the host activated,
    cleared or counted off by `max_tokens` takes the host's length and
    flag and an empty stop window, every other lane goes on from where
    the chunk in flight leaves it. Operands of one shape and dtype at
    every call (a mask and values, never a list of slots): one compiled
    program, met by the first decode dispatch a server runs."""
    return (
        jnp.where(edited, host_lengths, lengths),
        jnp.where(edited, host_finished, finished),
        jnp.where(edited[:, None], -2, recent),
    )


@jax.jit
def seat_first_token(tok, keys, slot, tok0, key):
    """A prompt's first token and its advanced key (`paged_prefill`'s
    outputs, [1] each) into lane `slot` ([] int32, traced) of the next
    decode chunk's `tok` and `keys`, on the device: the chunk is
    enqueued behind the prefill without the host reading either."""
    return tok.at[slot].set(tok0[0]), keys.at[slot].set(key[0])


# ---------------------------------------------------------------------------
# Ragged fused prefill+decode step (one dispatch per engine step)
# ---------------------------------------------------------------------------


def pack_prefill_window(
    embeds_np: "np.ndarray",  # [1, T, H] HOST prompt embeds
    off: int,
    width: int,
) -> "np.ndarray":
    """Host-side packing helper: the [1, width, H] prefill window at
    logical offset `off` of a prompt whose embeds live on the HOST,
    zero-padded past the prompt end. The window — not the whole prompt
    — is the ragged dispatch's operand, so the dispatch shape is STATIC
    regardless of prompt length (the split path's `slice_embeds`
    compiles one device slicer per (T, width) pair instead; here the
    slice is free numpy)."""
    T, H = embeds_np.shape[1], embeds_np.shape[2]
    out = np.zeros((1, width, H), embeds_np.dtype)
    n = max(0, min(width, T - off))
    if n:
        out[0, :n] = embeds_np[0, off:off + n]
    return out


def unpack_ragged_rows(
    toks: "np.ndarray",  # [S, chunk] harvested decode tokens
    live: list[int],
) -> dict[int, list[int]]:
    """Host-side unpacking helper: per-slot token streams from the
    ragged harvest, restricted to the slots that were live DURING the
    dispatch (a slot activated after harvest must not consume this
    dispatch's frozen rows)."""
    return {s: [int(t) for t in toks[s]] for s in live}


@partial(
    jax.jit,
    static_argnames=(
        "cfg", "chunk", "pf_width", "eos", "attn_impl", "compute_dtype",
        "numerics",
    ),
    donate_argnames=("kv_pages",),
)
def paged_ragged_step(
    params,
    cfg: LLMConfig,
    kv_pages: dict,  # donated
    block_tables: jnp.ndarray,  # [S, max_pages] int32
    tok: jnp.ndarray,  # [S] next token to feed per slot
    lengths: jnp.ndarray,  # [S] kv tokens held per slot (frozen on finish)
    finished: jnp.ndarray,  # [S] bool (True for finished AND empty slots)
    recent: jnp.ndarray,  # [S, stop_L] rolling stop window (-2 init)
    keys: jax.Array,  # [S] per-slot PRNG keys
    temperature: jnp.ndarray,  # [S]
    top_p: jnp.ndarray,  # [S]
    top_k: jnp.ndarray,  # [S]
    stop_sequences: jnp.ndarray | None,  # [Sq, L] (shared, static)
    pf_embeds: jnp.ndarray,  # [1, chunk*pf_width, H] prefill window
    pf_slot: jnp.ndarray,  # [] int32 slot the prefill belongs to
    pf_off: jnp.ndarray,  # [] int32 logical offset of the window start
    pf_len: jnp.ndarray,  # [] int32 total prompt length (incl. prefix)
    pf_active: jnp.ndarray,  # [] bool — a prefill rides this dispatch
    pf_key: jax.Array,  # [1] the admitting request's key0
    pf_temp: jnp.ndarray,  # [1]
    pf_top_p: jnp.ndarray,  # [1]
    pf_top_k: jnp.ndarray,  # [1]
    *,
    chunk: int,
    pf_width: int,
    eos: int,
    attn_impl: str = "xla",
    compute_dtype=None,
    numerics: bool = False,
):
    """ONE device dispatch for a mixed prefill+decode engine step — the
    fusion of `paged_prefill` (chunked) and `paged_decode_chunk`.

    Each of the `chunk` scan iterations runs a single packed forward
    over R = S + pf_width query rows: rows 0..S-1 are the decode lanes
    (one token per slot, exactly `paged_decode_chunk`'s step semantics
    — finished/empty slots ride masked), rows S.. are `pf_width`
    consecutive suffix tokens of the one admitting slot's prompt, so a
    dispatch advances the prefill by chunk*pf_width tokens while every
    resident stream decodes `chunk` tokens. The packed buffer's shape
    is STATIC: which slot is admitting, where its window starts, and
    how much of it is real are all traced scalars
    (`recompile_watchdog`-proven — varying live/prefill mixes share one
    compiled program per pf_width shape class).

    Bit-parity contract: decode lanes reproduce `paged_decode_chunk`
    exactly (same per-row math, same RNG stream); the prefill lanes
    reproduce `paged_prefill_chunks` (every window implicitly seeded
    with the request's own key0, only the window containing the prompt
    's final token samples tok0 ~ split(key0)[1], advanced key
    split(key0)[0]) — so an engine step through this program emits the
    same tokens as the split prefill-then-decode step pair.

    Returns (kv_pages, tok, lengths, finished, recent, keys,
    toks [S, chunk], fin [S, chunk], pf_tok0 [] int32, pf_key_next [1]).
    With pf_width=0 this is a pure packed decode step (the shape class
    dispatched when no admission is in flight).

    numerics=True (STATIC) appends the [6] float32 logit-stat
    accumulator (utils/numerics.py) over the decode lanes' live rows —
    same contract as paged_decode_chunk: one extra stable compiled
    program, bit-identical tokens, zero extra dispatches."""
    from oryx_tpu.parallel.sharding import constrain

    S = tok.shape[0]
    W = pf_width

    def stop_hit(recent):
        return paged_kv_lib.stop_window_hit(recent, stop_sequences)

    def embed(ids):
        # The exact lookup `forward(input_ids=...)` performs, so decode
        # lanes stay bit-identical to the split path's embeds.
        e = constrain(params["embed"]["weight"], None, None)[ids]
        return e.astype(compute_dtype) if compute_dtype is not None else e

    def step(carry, i):
        if numerics:
            (kv_pages, tok, cur_len, finished, recent, keys, pf_tok0,
             nstats) = carry
        else:
            kv_pages, tok, cur_len, finished, recent, keys, pf_tok0 = carry
        pair = jax.vmap(lambda k: jax.random.split(k, 2))(keys)
        dec_emb = embed(tok)  # [S, H]
        seg = jnp.arange(S, dtype=jnp.int32)
        pos = cur_len
        wm = ~finished
        if W:
            pf_win = jax.lax.dynamic_slice_in_dim(
                pf_embeds, i * W, W, axis=1
            )[0]
            pf_pos = pf_off + i * W + jnp.arange(W, dtype=jnp.int32)
            emb = jnp.concatenate(
                [dec_emb, pf_win.astype(dec_emb.dtype)], axis=0
            )
            pos = jnp.concatenate([pos, pf_pos])
            seg = jnp.concatenate(
                [seg, jnp.full((W,), 1, jnp.int32) * pf_slot]
            )
            # Prefill lanes write whenever a prefill rides the dispatch
            # (window overshoot past the prompt writes the same
            # never-read-before-overwritten garbage the split chunked
            # prefill writes — parity includes the pool bytes).
            wm = jnp.concatenate(
                [wm, jnp.broadcast_to(pf_active, (W,))]
            )
        else:
            emb = dec_emb
        logits, kv_pages = qwen2.forward(
            params, cfg,
            inputs_embeds=emb[None], positions=pos[None],
            kv_cache=kv_pages, block_tables=block_tables,
            q_segments=seg[None], write_mask=wm[None],
            attn_impl=attn_impl, compute_dtype=compute_dtype,
        )
        lg = logits[0]  # [R, V]
        if numerics:
            # Decode lanes only: the prefill lanes' logits are
            # intermediate prompt positions, not sampling inputs.
            nstats = numerics_lib.accumulate_logit_stats(
                nstats, lg[:S], ~finished
            )
        nxt = sample_token_rows(
            lg[:S], pair[:, 1],
            temperature=temperature, top_p=top_p, top_k=top_k,
        )
        if recent.shape[1]:
            recent = jnp.concatenate([recent[:, 1:], tok[:, None]], axis=1)
        finished = finished | (tok == eos) | stop_hit(recent)
        nxt = jnp.where(finished, eos, nxt)
        cur_len = cur_len + (~finished).astype(jnp.int32)
        if W:
            # Did the prompt's final real token land in THIS window?
            pf_pair = jax.vmap(lambda k: jax.random.split(k, 2))(pf_key)
            j = pf_len - 1 - pf_off - i * W
            present = pf_active & (j >= 0) & (j < W)
            row = jax.lax.dynamic_index_in_dim(
                lg, S + jnp.clip(j, 0, W - 1), axis=0, keepdims=True
            )  # [1, V]
            cand = sample_token_rows(
                row, pf_pair[:, 1],
                temperature=pf_temp, top_p=pf_top_p, top_k=pf_top_k,
            )[0]
            pf_tok0 = jnp.where(present, cand, pf_tok0)
        out = (
            kv_pages, nxt, cur_len, finished, recent, pair[:, 0], pf_tok0
        )
        if numerics:
            out = out + (nstats,)
        return out, (tok, finished)

    carry0 = (
        kv_pages, tok, lengths, finished, recent, keys,
        jnp.zeros((), jnp.int32),
    )
    if numerics:
        carry0 = carry0 + (numerics_lib.init_logit_stats(),)
    carry, (toks, fin) = jax.lax.scan(
        step, carry0, jnp.arange(chunk, dtype=jnp.int32),
    )
    kv_pages, tok, lengths, finished, recent, keys, pf_tok0 = carry[:7]
    pf_key_next = jax.vmap(lambda k: jax.random.split(k, 2))(pf_key)[:, 0]
    out = (
        kv_pages, tok, lengths, finished, recent, keys,
        jnp.moveaxis(toks, 0, 1), jnp.moveaxis(fin, 0, 1),
        pf_tok0, pf_key_next,
    )
    if numerics:
        out = out + (carry[7],)
    return out


# ---------------------------------------------------------------------------
# Speculative decoding: self-drafted multi-token steps, verified in one
# packed dispatch (docs/DESIGN.md "Speculative decoding")
# ---------------------------------------------------------------------------


class Drafter:
    """Pluggable draft-token proposer for speculative decoding.

    `propose(context, k)` returns UP TO `k` token ids predicted to
    continue `context` (the request's own confirmed stream: prompt ids
    + device-confirmed reply tokens + the pending fed token). Fewer —
    or zero — proposals are always legal: unproposed lanes of the
    verify dispatch ride masked, and a zero-draft step degenerates to
    the plain one-token decode. Implementations MUST be deterministic
    functions of `context` (eviction replay re-proposes from the same
    context and must re-derive the same accept pattern, or the replayed
    sample stream diverges from what the client already saw).

    The reference implementation is `NgramDrafter` (self-drafting — no
    second model); a small draft MODEL slots in by implementing this
    same method (propose = draft-model decode of k tokens).

    `window` (None = unbounded) declares how much context TAIL the
    drafter actually reads: the scheduler then materializes only that
    suffix per step instead of concatenating the full prompt + reply
    history — without a bound, proposal cost grows O(context) per slot
    per engine step, eroding the sequential-latency win speculation
    exists to buy. A fixed tail is still a deterministic function of
    the context, so replay stability is unaffected."""

    window: int | None = None

    def propose(self, context, k: int) -> list[int]:
        raise NotImplementedError


class NgramDrafter(Drafter):
    """Prompt-lookup / n-gram self-drafting (arXiv 2605.25645's cheap
    lever for repetitive serving workloads — code, RAG, chat with
    quoting): find the MOST RECENT earlier occurrence of the longest
    suffix n-gram of the context and propose the tokens that followed
    it. No second model, no extra device work — the proposal is a pure
    host-side lookup against the request's own tokens, and the packed
    verify dispatch prices every proposal at one extra lane."""

    def __init__(self, max_ngram: int = 3, min_ngram: int = 1,
                 window: int | None = 2048):
        if not 1 <= min_ngram <= max_ngram:
            raise ValueError(
                f"need 1 <= min_ngram <= max_ngram, got "
                f"{min_ngram=} {max_ngram=}"
            )
        if window is not None and window < max_ngram + 1:
            raise ValueError(
                f"window must cover at least one n-gram + continuation "
                f"(>= max_ngram + 1), got {window=} {max_ngram=}"
            )
        self.max_ngram = max_ngram
        self.min_ngram = min_ngram
        # Lookup window (tokens of context tail searched): bounds the
        # per-step host cost at O(window) regardless of prompt/reply
        # length. Deterministic — replay sees the same tail at the
        # same confirmed position.
        self.window = window

    def propose(self, context, k: int) -> list[int]:
        a = np.asarray(context, np.int64).reshape(-1)
        if self.window is not None and a.shape[0] > self.window:
            a = a[-self.window:]
        n_ctx = int(a.shape[0])
        if k <= 0 or n_ctx < self.min_ngram + 1:
            return []
        for n in range(min(self.max_ngram, n_ctx - 1), self.min_ngram - 1,
                       -1):
            suf = a[-n:]
            w = n_ctx - n  # candidate starts 0..w-1 (w == the suffix itself)
            m = np.ones(w, bool)
            for j in range(n):
                m &= a[j: j + w] == suf[j]
            idx = np.nonzero(m)[0]
            if idx.size:
                i = int(idx[-1])  # most recent earlier occurrence
                cont = a[i + n: i + n + k]
                if cont.size:
                    return [int(x) for x in cont]
        return []


def spec_verify_rows(
    lg: jnp.ndarray,  # [S, k+1, V] verify-lane logits
    tok: jnp.ndarray,  # [S] fed token per slot (lane 0's input)
    drafts: jnp.ndarray,  # [S, k] proposed tokens (garbage past draft_len)
    draft_len: jnp.ndarray,  # [S] real proposals per slot (0..k)
    keys: jax.Array,  # [S] per-slot PRNG keys
    *,
    temperature: jnp.ndarray,  # [S]
    top_p: jnp.ndarray,  # [S]
    top_k: jnp.ndarray,  # [S]
    eos: int,
) -> tuple[jnp.ndarray, jnp.ndarray, jax.Array]:
    """Accept/resample core of speculative decoding over verify-lane
    logits — pure math, shared by `paged_spec_step` and the
    distribution tests. Returns (acc [S], cand [S], keys_next [S]).

    Lane j's logits lg[s, j] are the model's distribution for the token
    at position len_s+j+1 (after feeding [tok, d_0..d_{k-1}]); drafts
    [s, j] is the proposal for that same position. Acceptance is the
    longest matching prefix:

      * greedy rows (temperature <= 0): d_j accepted iff it EQUALS the
        raw argmax target — accepted tokens are bit-identical to what
        sequential decode would have produced, which is the whole
        byte-parity claim.
      * sampled rows: point-mass rejection sampling. The drafter is
        deterministic, so the proposal distribution is q = delta(d_j);
        accept d_j with probability p'(d_j) where p' is the TRUNCATED
        target (same temperature/top-k/top-p shaping as
        `sample_token_rows`, via `truncate_logits_rows`); on rejection
        the bonus token is drawn from the residual max(p' - q, 0)/Z —
        for a point mass that is p' with d_j masked out, renormalized —
        so the marginal of the emitted token at every position is
        EXACTLY p' (the spec-vs-plain distribution test pins this).

    `acc` counts accepted drafts, truncated at the first accepted EOS
    (tokens "accepted" past an EOS never existed — the sequential path
    would have frozen the row) and forced to 0 when the fed token is
    itself EOS. `cand` is the bonus token at lane `acc` — the model's
    own next token at the first mismatch (or after all accepts), which
    becomes the next step's fed token. Key consumption is a FIXED
    2k+3 split per slot per step regardless of the accept pattern, so
    a row's RNG stream depends only on its own step count — the same
    per-row independence contract as `sample_token_rows`."""
    S, k = drafts.shape
    lanes = k + 1
    V = lg.shape[-1]
    tgt = jnp.argmax(lg, axis=-1).astype(jnp.int32)  # [S, lanes] raw greedy
    rep = lambda x: jnp.repeat(x, lanes)  # noqa: E731 — slot-major repeat
    l_t, _ = truncate_logits_rows(
        lg.reshape(S * lanes, V),
        temperature=rep(temperature), top_p=rep(top_p), top_k=rep(top_k),
    )
    l_t = l_t.reshape(S, lanes, V)
    is_greedy = temperature <= 0.0
    ks = jax.vmap(lambda key: jax.random.split(key, 2 * k + 3))(keys)
    if k:
        # Accept draws: one uniform per draft lane (ks[:, 2j]).
        u = jax.vmap(
            jax.vmap(lambda key: jax.random.uniform(key, ()))
        )(ks[:, 0:2 * k:2])  # [S, k]
        p = jax.nn.softmax(l_t[:, :k], axis=-1)
        p_d = jnp.take_along_axis(
            p, drafts[..., None].astype(jnp.int32), axis=-1
        )[..., 0]  # [S, k]
        ok = jnp.where(
            is_greedy[:, None], drafts == tgt[:, :k], u < p_d
        )
        jr = jnp.arange(k, dtype=jnp.int32)[None, :]
        ok = ok & (jr < draft_len[:, None])
        cum = jnp.cumprod(ok.astype(jnp.int32), axis=1)  # leading accepts
        # Truncate at the first ACCEPTED eos (inclusive): lanes after it
        # would extend a row the sequential path already froze.
        hit_eos = cum * (drafts == eos).astype(jnp.int32)
        eos_before = jnp.cumsum(hit_eos, axis=1) - hit_eos
        acc = jnp.sum(cum * (eos_before == 0), axis=1).astype(jnp.int32)
    else:
        acc = jnp.zeros_like(tok)
    acc = jnp.where(tok == eos, 0, acc)
    # Bonus lane b = acc: the model's own token at the first mismatch
    # (or the free extra token after a full accept).
    b = acc
    l_sel = jnp.take_along_axis(l_t, b[:, None, None], axis=1)[:, 0]
    tgt_sel = jnp.take_along_axis(tgt, b[:, None], axis=1)[:, 0]
    # Residual for a point-mass rejection: mask the rejected draft out
    # of the bonus draw (only when lane b actually carried a proposal).
    d_pad = jnp.concatenate(
        [drafts.astype(jnp.int32), jnp.full((S, 1), -1, jnp.int32)], axis=1
    )
    d_b = jnp.take_along_axis(d_pad, b[:, None], axis=1)[:, 0]
    rejected = b < draft_len
    l_res = jnp.where(
        rejected[:, None]
        & (jnp.arange(V, dtype=jnp.int32)[None] == d_b[:, None]),
        -jnp.inf, l_sel,
    )
    key_sel = jax.vmap(lambda row, i: row[i])(ks, 2 * b + 1)
    u2 = jax.vmap(lambda key: jax.random.uniform(key, (V,)))(key_sel)
    g = -jnp.log(-jnp.log(jnp.maximum(u2, jnp.finfo(jnp.float32).tiny)))
    cand_sample = jnp.argmax(l_res + g, axis=-1).astype(jnp.int32)
    cand = jnp.where(is_greedy, tgt_sel, cand_sample)
    return acc, cand, ks[:, -1]


@partial(
    jax.jit,
    static_argnames=(
        "cfg", "k", "pf_width", "eos", "attn_impl", "compute_dtype",
    ),
    donate_argnames=("kv_pages",),
)
def paged_spec_step(
    params,
    cfg: LLMConfig,
    kv_pages: dict,  # donated
    block_tables: jnp.ndarray,  # [S, max_pages] int32
    tok: jnp.ndarray,  # [S] next token to feed per slot
    lengths: jnp.ndarray,  # [S] kv tokens held per slot (frozen on finish)
    finished: jnp.ndarray,  # [S] bool (True for finished AND empty slots)
    keys: jax.Array,  # [S] per-slot PRNG keys
    temperature: jnp.ndarray,  # [S]
    top_p: jnp.ndarray,  # [S]
    top_k: jnp.ndarray,  # [S]
    drafts: jnp.ndarray,  # [S, k] proposed draft tokens
    draft_len: jnp.ndarray,  # [S] real proposals per slot
    pf_embeds: jnp.ndarray,  # [1, pf_width, H] prefill window
    pf_slot: jnp.ndarray,  # [] int32 slot the prefill belongs to
    pf_off: jnp.ndarray,  # [] int32 logical offset of the window start
    pf_len: jnp.ndarray,  # [] int32 total prompt length (incl. prefix)
    pf_active: jnp.ndarray,  # [] bool — a prefill rides this dispatch
    pf_key: jax.Array,  # [1] the admitting request's key0
    pf_temp: jnp.ndarray,  # [1]
    pf_top_p: jnp.ndarray,  # [1]
    pf_top_k: jnp.ndarray,  # [1]
    *,
    k: int,
    pf_width: int,
    eos: int,
    attn_impl: str = "xla",
    compute_dtype=None,
):
    """ONE device dispatch for a SPECULATIVE mixed prefill+decode
    engine step: every live slot contributes 1+k packed verify lanes
    (its fed token plus k self-drafted continuations at consecutive
    positions) and the one admitting slot contributes `pf_width`
    prefill-suffix lanes — the whole fleet's drafts verified in a
    single packed forward through the SAME (segment, position) ragged
    kernel as `paged_ragged_step` (drafts are just extra packed rows;
    ops/paged_kv.spec_lane_metadata builds the routing).

    Unlike `paged_ragged_step`'s chunk-iteration scan, this is a single
    forward: the drafter is HOST-side (it needs the token history the
    device never holds), so each engine step proposes, verifies in one
    dispatch, and harvests — a slot advances 1..k+1 tokens per
    sequential step instead of 1, which is the whole latency lever
    (arXiv 2605.25645: interactive SLOs are bound by sequential steps,
    not per-step cost).

    KV discipline: all 1+k lanes write KV at positions len..len+k —
    always into the slot's EXCLUSIVELY-OWNED pages (the COW-at-splice
    invariant: shared prefix pages end strictly below the prompt, the
    partial boundary page is copy-on-written at admission, and finish-
    time donation is capped at the device-confirmed length — so a
    "scratch" region past cur_len needs no extra pages). Accepted
    drafts splice by advancing cur_len over KV already written;
    rejected drafts leave dead bytes past cur_len that causal masking
    never reads and the next real token overwrites before its first
    read. Rollback therefore frees nothing and copies nothing.

    The dispatch shape is STATIC per (S, k, pf_width) class — two
    compiled programs total (prefill lanes present/absent), exactly the
    ragged engine's contract; drafts/draft_len are traced operands.

    Returns (kv_pages, nxt, lengths, finished, keys, toks [S, k+1],
    n_new [S], acc [S], pf_tok0, pf_key_next): toks[s, :n_new[s]] are
    the tokens slot s emitted this step (fed token + accepted drafts,
    EOS-fill past n_new); nxt is the bonus token each slot feeds next
    step. Greedy rows are bit-identical to running `paged_ragged_step`
    n_new times (accept == argmax match, bonus == the argmax the
    sequential path would sample); see `spec_verify_rows` for the
    temperature>0 rejection-sampling contract."""
    from oryx_tpu.parallel.sharding import constrain

    S = tok.shape[0]
    lanes = k + 1
    W = pf_width

    def embed(ids):
        e = constrain(params["embed"]["weight"], None, None)[ids]
        return e.astype(compute_dtype) if compute_dtype is not None else e

    ids = jnp.concatenate(
        [tok[:, None], drafts.astype(jnp.int32)], axis=1
    )  # [S, lanes]
    dec_emb = embed(ids.reshape(S * lanes))
    seg, pos = paged_kv_lib.spec_lane_metadata(lengths, k)
    lane_j = jnp.tile(jnp.arange(lanes, dtype=jnp.int32), (S,))
    wm = (
        jnp.repeat(~finished, lanes)
        & (lane_j <= jnp.repeat(draft_len.astype(jnp.int32), lanes))
    )
    if W:
        pf_pos = pf_off + jnp.arange(W, dtype=jnp.int32)
        emb = jnp.concatenate(
            [dec_emb, pf_embeds[0].astype(dec_emb.dtype)], axis=0
        )
        pos = jnp.concatenate([pos, pf_pos])
        seg = jnp.concatenate(
            [seg, jnp.full((W,), 1, jnp.int32) * pf_slot]
        )
        wm = jnp.concatenate([wm, jnp.broadcast_to(pf_active, (W,))])
    else:
        emb = dec_emb
    logits, kv_pages = qwen2.forward(
        params, cfg,
        inputs_embeds=emb[None], positions=pos[None],
        kv_cache=kv_pages, block_tables=block_tables,
        q_segments=seg[None], write_mask=wm[None],
        attn_impl=attn_impl, compute_dtype=compute_dtype,
    )
    lg_all = logits[0]
    lg = lg_all[: S * lanes].reshape(S, lanes, -1)
    acc, cand, keys_next = spec_verify_rows(
        lg, tok, drafts, draft_len, keys,
        temperature=temperature, top_p=top_p, top_k=top_k, eos=eos,
    )
    jr = jnp.arange(k, dtype=jnp.int32)[None, :]
    accepted = jr < acc[:, None]
    out_toks = jnp.concatenate(
        [tok[:, None], jnp.where(accepted, drafts, eos)], axis=1
    )
    acc_eos = jnp.any(accepted & (drafts == eos), axis=1)
    fed_eos = tok == eos
    new_finished = finished | fed_eos | acc_eos
    n_new = jnp.where(finished, 0, 1 + acc)
    # cur_len counts confirmed non-EOS KV tokens, mirroring the
    # sequential step's `cur_len + ~finished` (EOS never increments).
    inc = jnp.where(
        finished | fed_eos, 0, 1 + acc - acc_eos.astype(jnp.int32)
    )
    nxt = jnp.where(new_finished, eos, cand)
    if W:
        # Prefill-lane sampling: the exact `paged_ragged_step` contract
        # (window seeded with the request's key0; only the window
        # containing the prompt's final token samples tok0).
        pf_pair = jax.vmap(lambda key: jax.random.split(key, 2))(pf_key)
        j = pf_len - 1 - pf_off
        present = pf_active & (j >= 0) & (j < W)
        row = jax.lax.dynamic_index_in_dim(
            lg_all, S * lanes + jnp.clip(j, 0, W - 1), axis=0,
            keepdims=True,
        )
        pf_cand = sample_token_rows(
            row, pf_pair[:, 1],
            temperature=pf_temp, top_p=pf_top_p, top_k=pf_top_k,
        )[0]
        pf_tok0 = jnp.where(present, pf_cand, jnp.zeros((), jnp.int32))
    else:
        pf_tok0 = jnp.zeros((), jnp.int32)
    pf_key_next = jax.vmap(lambda key: jax.random.split(key, 2))(
        pf_key
    )[:, 0]
    return (
        kv_pages, nxt, lengths + inc, new_finished, keys_next,
        out_toks, n_new, acc, pf_tok0, pf_key_next,
    )


# ---------------------------------------------------------------------------
# Block diffusion: a dispatch commits one BLOCK of tokens per slot
# ---------------------------------------------------------------------------


# What `paged_block_step`'s counts["stats"] holds, in order.
BLOCK_STATS = (
    "forwards", "unmasked", "moe_rows_routed", "moe_rows_max",
    "moe_experts_hit",
)


def block_unmask(
    masked: jnp.ndarray,  # [S, B] bool: positions still masked
    conf: jnp.ndarray,  # [S, B] float32 confidence of each position's x0
    step: jnp.ndarray,  # [] int32 denoising step, from 0
    *,
    steps: int,
    remasking: str,
    threshold: float,
) -> jnp.ndarray:
    """Which masked positions this denoising step fixes ([S, B] bool).
    "low_confidence_static": the ceil(m / (steps - step)) most confident
    of a slot's m masked positions (ties: the lower position), so any m
    is used up in `steps` steps (4 over 3: 2, 1, 1).
    "low_confidence_dynamic": every masked position whose confidence
    passes `threshold`, and always the most confident one."""
    c = jnp.where(masked, conf, -jnp.inf)
    order = jnp.argsort(-c, axis=-1)  # stable: ties to the lower position
    rank = jnp.argsort(order, axis=-1)
    if remasking == "low_confidence_static":
        m = jnp.sum(masked, axis=-1)
        left = jnp.maximum(steps - step, 1)
        take = -(-m // left)
    else:
        take = jnp.maximum(jnp.sum(masked & (conf > threshold), axis=-1), 1)
    return masked & (rank < take[:, None])


def _block_lanes_forward(
    params, cfg: LLMConfig, kv_pages, block_tables, ids, lengths, write,
    *, attn_impl, compute_dtype, before: int = 0,
):
    """One forward of S x W packed lanes, slot-major: lane j of slot s
    holds `ids[s, j]` at position lengths[s] - before + j of the slot's
    own pages (never under 0: a lane that would lie there is a dead
    one), through the (segment, position) ragged path; the lanes that
    `write` [S, W] marks write their K/V there. The head runs over the
    lanes from `lengths` on alone. Returns (their logits
    [S * (W - before), V], kv_pages, routing of all S * W lanes or
    None on a dense config)."""
    from oryx_tpu.parallel.sharding import constrain

    S, W = ids.shape
    seg = jnp.repeat(jnp.arange(S, dtype=jnp.int32), W)
    pos = jnp.maximum(
        lengths[:, None].astype(jnp.int32)
        + jnp.arange(-before, W - before, dtype=jnp.int32)[None, :], 0
    ).reshape(-1)
    with jax.named_scope("embed"):
        e = constrain(params["embed"]["weight"], None, None)[ids.reshape(-1)]
        if compute_dtype is not None:
            e = e.astype(compute_dtype)
    h, kv_pages, *routing = qwen2.forward(
        params, cfg,
        inputs_embeds=e[None], positions=pos[None],
        kv_cache=kv_pages, block_tables=block_tables,
        q_segments=seg[None], write_mask=write.reshape(1, -1),
        attn_impl=attn_impl, compute_dtype=compute_dtype,
        return_hidden=True, return_routing=bool(cfg.num_experts),
    )
    # The head, as `qwen2.forward` ends, over the lanes that are read.
    with jax.named_scope("head"):
        h = h[0].reshape(S, W, -1)[:, before:].reshape(S * (W - before), -1)
        head = (
            params["embed"]["weight"].T if cfg.tie_word_embeddings
            else params["lm_head"]["kernel"]
        )
        logits = (h @ head.astype(h.dtype)).astype(jnp.float32)
    return logits, kv_pages, routing[0] if routing else None


@partial(
    jax.jit,
    static_argnames=("cfg", "attn_impl", "compute_dtype"),
    donate_argnames=("kv_pages",),
)
def paged_block_forward(
    params, cfg: LLMConfig, kv_pages, block_tables, ids, lengths, live,
    *, attn_impl: str = "xla", compute_dtype=None,
):
    """`paged_block_step`'s plain forward as a program of its own, for
    the comparisons that need a forward's logits or a stand-alone commit
    (benchmark/correctness_sdar.py, the tests): slot s's block `ids[s]`
    at positions lengths[s]..lengths[s]+B-1, live slots' lanes writing
    their K/V. Returns (logits [S*B, V], kv_pages, routing or None)."""
    return _block_lanes_forward(
        params, cfg, kv_pages, block_tables, ids, lengths,
        jnp.broadcast_to(live[:, None], ids.shape),
        attn_impl=attn_impl, compute_dtype=compute_dtype,
    )


@partial(
    jax.jit,
    static_argnames=(
        "cfg", "steps", "remasking", "threshold", "eos", "attn_impl",
        "compute_dtype",
    ),
    donate_argnames=("kv_pages",),
)
def paged_block_step(
    params,
    cfg: LLMConfig,
    kv_pages: dict,  # donated
    block_tables: jnp.ndarray,  # [S, max_pages] int32
    block: jnp.ndarray,  # [S, B] the block's known tokens, then anything
    n_known: jnp.ndarray,  # [S] int32 known tokens at the block's head
    lengths: jnp.ndarray,  # [S] kv tokens before the block, a multiple of B
    finished: jnp.ndarray,  # [S] bool (True for finished AND empty slots)
    keys: jax.Array,  # [S] per-slot PRNG keys
    temperature: jnp.ndarray,  # [S]
    top_p: jnp.ndarray,  # [S]
    top_k: jnp.ndarray,  # [S]
    pending: jnp.ndarray | None = None,  # [S, B] the block before, final
    pending_live: jnp.ndarray | None = None,  # [S] bool: commit it
    *,
    steps: int,
    remasking: str,
    threshold: float,
    eos: int,
    attn_impl: str = "xla",
    compute_dtype=None,
):
    """ONE device dispatch that generates one block of
    B = cfg.block_length tokens for every live slot by diffusion: the
    sixth step program, and the only one whose forward yields something
    other than one next token a sequence.

    Every slot rides B packed lanes (slot-major, through the same
    (segment, position) ragged path as `paged_spec_step`) at positions
    lengths..lengths+B-1 of its own pages, which the scheduler grew
    beforehand. A block starts as its `n_known` known tokens (a
    prompt's tail `len % B`) and cfg.mask_token_id elsewhere. An
    on-device loop runs denoising forwards, at most `steps` of them
    under the static rule and at most B under the dynamic one, and
    stops when no live slot has a masked position left; a slot that has none rides along unchanged. Each forward
    takes x0 (argmax, or a sample) and its softmax probability in
    float32 as confidence at every lane, and `block_unmask` fixes some
    of the masked ones. Greedy rows cost an argmax and a logsumexp:
    `sample_token_rows` sorts only in a dispatch in which some row has
    temperature > 0 (retired rows carry 0).

    **The commit is deferred.** Every forward writes the lanes' K/V at
    their positions, past `lengths`, and the last denoising forward
    still saw masks, so what a block leaves in the pages is NOT its
    K/V. The block's final tokens are an output (`tokens`), and the
    NEXT dispatch takes them as `pending`: its first forward carries
    2B lanes a slot, `pending[s]` at lengths[s]-B..lengths[s]-1 with
    their write on where `pending_live[s]` (and no head), then the
    opening block. The block mask is a function of position alone and
    every layer writes the lanes' K/V before its attention reads the
    pages, so the new block's lanes see the block before them as
    committed, layer by layer: each row is what a commit forward
    followed by a denoising forward gives, and a block costs T
    forwards where it cost T + 1. A slot's first block has nothing
    pending, and a request's last block is never committed (nothing
    reads its K/V; docs/DESIGN.md "Block diffusion"). Without
    `pending` (the comparisons, which commit forward by forward
    themselves) no forward carries commit lanes and NOTHING commits
    the block: the loop alone, a smaller program.

    Returns (kv_pages, tokens [S, B], n_new [S] = B - n_known for live
    slots, lengths + B for live slots, finished | a new token is EOS,
    keys, counts). counts holds what the counters read: `stats` [5]
    int32 in the order of BLOCK_STATS (forwards run; tokens fixed by
    denoising; (token, expert) pairs routed; the fullest expert's rows
    and the experts that took a row, each summed over layer-forwards
    and over ALL the lanes a forward carried, commit lanes and dead
    ones included, since the grouped products read an expert for
    them; the last three 0 on a dense config), one array so that the
    host reads them in one copy; `slot_forwards` [S], the forwards a
    live slot took part in with a mask left (once a forward, whatever
    else the forward did for it); `expert_rows` [L, E], the rows every
    expert took over all forwards."""
    S, B = block.shape
    if B != cfg.block_length:
        raise ValueError(f"block is {B} wide, cfg.block_length is "
                         f"{cfg.block_length}")
    L, E = cfg.num_layers, max(cfg.num_experts, 1)
    live = ~finished
    lane = jnp.arange(B, dtype=jnp.int32)
    masked0 = (lane[None, :] >= n_known[:, None]) & live[:, None]
    block = jnp.where(masked0, cfg.mask_token_id, block).astype(jnp.int32)
    write = jnp.broadcast_to(live[:, None], (S, B))

    def forward(kv, ids, write, **lanes):
        lg, kv, routing = _block_lanes_forward(
            params, cfg, kv, block_tables, ids, lengths, write,
            attn_impl=attn_impl, compute_dtype=compute_dtype, **lanes,
        )
        rows = (
            jnp.zeros((L, E), jnp.int32) if routing is None
            else routing["counts"]
        )
        return lg, kv, rows

    # Called after the first forward and in the loop's body: an inner
    # jit, so that the sampler is traced and lowered once.
    @jax.jit
    def pick(lg, keys):
        """x0 and its confidence at every lane: [S*B, V] -> [S, B] x2."""
        pair = jax.vmap(lambda k: jax.random.split(k, 2))(keys)
        lane_keys = jax.vmap(lambda k: jax.random.split(k, B))(
            pair[:, 1]
        ).reshape(S * B)
        x0 = sample_token_rows(
            lg, lane_keys, temperature=jnp.repeat(temperature, B),
            top_p=jnp.repeat(top_p, B), top_k=jnp.repeat(top_k, B),
        )
        conf = jnp.exp(
            jnp.take_along_axis(lg, x0[:, None], axis=-1)[:, 0]
            - jax.nn.logsumexp(lg, axis=-1)
        )
        return x0.reshape(S, B), conf.reshape(S, B), pair[:, 0]

    max_steps = steps if remasking == "low_confidence_static" else B

    @jax.named_scope("sample")
    def denoise(c, lg, kv, rows):
        """The carry after one forward that gave the open block's lanes
        the logits `lg`."""
        x0, conf, keys = pick(lg, c["keys"])
        fix = block_unmask(
            c["masked"], conf, c["t"],
            steps=steps, remasking=remasking, threshold=threshold,
        )
        return {
            "t": c["t"] + 1, "kv": kv, "keys": keys,
            "block": jnp.where(fix, x0, c["block"]),
            "masked": c["masked"] & ~fix,
            "slot_forwards": c["slot_forwards"] + jnp.any(
                c["masked"], axis=-1),
            "expert_rows": c["expert_rows"] + rows,
            "stats": c["stats"] + jnp.stack([
                1, jnp.sum(fix), jnp.sum(rows),
                jnp.sum(jnp.max(rows, axis=-1)), jnp.sum(rows > 0),
            ]).astype(jnp.int32),
        }

    def cond(c):
        return (c["t"] < max_steps) & jnp.any(c["masked"])

    def body(c):
        return denoise(c, *forward(c["kv"], c["block"], write))

    c = {
        "t": jnp.zeros((), jnp.int32), "kv": kv_pages, "keys": keys,
        "block": block, "masked": masked0,
        "slot_forwards": jnp.zeros((S,), jnp.int32),
        "expert_rows": jnp.zeros((L, E), jnp.int32),
        "stats": jnp.zeros((len(BLOCK_STATS),), jnp.int32),
    }
    if pending is not None:
        # The first forward: the pending block's commit lanes before
        # the open block's, and the head over the open block's alone.
        commit = jnp.broadcast_to((pending_live & live)[:, None], (S, B))
        c = denoise(c, *forward(
            kv_pages,
            jnp.concatenate([pending.astype(jnp.int32), block], axis=1),
            jnp.concatenate([commit, write], axis=1), before=B,
        ))
    c = jax.lax.while_loop(cond, body, c)
    with jax.named_scope("sample"):
        toks = c["block"]
        n_new = jnp.where(live, B - n_known, 0).astype(jnp.int32)
        new_eos = jnp.any(
            (toks == eos) & (lane[None, :] >= n_known[:, None]), axis=-1
        )
        counts = {
            k: c[k] for k in ("stats", "slot_forwards", "expert_rows")}
        return (
            c["kv"], toks, n_new, lengths + jnp.where(live, B, 0),
            finished | (live & new_eos), c["keys"], counts,
        )


# ---------------------------------------------------------------------------
# Trained draft model: a tiny proposer behind the Drafter seam
# (docs/DESIGN.md "Speculative decoding")
# ---------------------------------------------------------------------------

# Positional decay of the context-mixing weights: token at distance d
# from the window's right edge contributes DRAFT_DECAY**d. Part of the
# checkpoint contract — changing it invalidates trained drafters.
DRAFT_DECAY = 0.9


def _draft_logits(params, buf: jnp.ndarray, n: jnp.ndarray) -> jnp.ndarray:
    """Next-token logits of the decayed-bag draft model.

    `buf` [S, W] is a RIGHT-ALIGNED token window (left-padded with
    anything; `n` [S] counts the valid tail entries). The model embeds
    the window, mixes it with exponentially-decayed weights anchored at
    the right edge, and projects to the vocabulary — one matmul pair,
    cheap enough to run k times per proposal. Pure function of (params,
    valid tail), which is the Drafter replay contract."""
    W = buf.shape[1]
    idx = jnp.arange(W, dtype=jnp.int32)[None, :]
    valid = idx >= (W - n[:, None].astype(jnp.int32))
    w = jnp.power(
        jnp.float32(DRAFT_DECAY), (W - 1 - idx).astype(jnp.float32)
    ) * valid.astype(jnp.float32)  # [S, W]
    emb = params["embed"][jnp.clip(buf, 0)]  # [S, W, D] f32
    h = jnp.sum(w[..., None] * emb, axis=1) / jnp.maximum(
        jnp.sum(w, axis=1, keepdims=True), 1e-6
    )
    return h @ params["proj"]  # [S, V]


def _draft_chain(params, buf: jnp.ndarray, n: jnp.ndarray, *, k: int):
    """Greedy k-token draft chain: argmax, shift-append, repeat.

    Greedy by design — a deterministic proposer is what the Drafter
    replay contract requires, and speculative acceptance treats the
    proposal as a point mass regardless of how it was picked.
    Returns [S, k] int32 drafts."""

    def step(carry, _):
        buf, n = carry
        nxt = jnp.argmax(_draft_logits(params, buf, n), axis=-1)
        nxt = nxt.astype(jnp.int32)
        buf = jnp.concatenate([buf[:, 1:], nxt[:, None]], axis=1)
        n = jnp.minimum(n + 1, buf.shape[1])
        return (buf, n), nxt

    _, drafts = jax.lax.scan(step, (buf, n), None, length=k)
    return jnp.moveaxis(drafts, 0, 1)  # [S, k]


_draft_chain_jit = jax.jit(_draft_chain, static_argnames=("k",))


class NeuralDrafter(Drafter):
    """Tiny trained draft model (decayed-bag-of-embeddings -> vocab
    projection) behind the Drafter seam: `propose()` runs the jitted
    greedy `_draft_chain` on the context's right-aligned tail.

    Checkpoints are .npz files (embed [V, D] f32, proj [D, V] f32,
    window). `from_spec` accepts either a checkpoint path or
    "init:V:D:W:SEED" for a randomly-initialized model (useful for
    parity tests and smoke benches; a random drafter just accepts
    ~never, which is slow but CORRECT)."""

    def __init__(self, params: dict, window: int = 16,
                 source: str | None = None):
        embed = np.asarray(params["embed"], np.float32)
        proj = np.asarray(params["proj"], np.float32)
        if embed.ndim != 2 or proj.ndim != 2 or embed.shape[1] != \
                proj.shape[0] or embed.shape[0] != proj.shape[1]:
            raise ValueError(
                f"drafter params must be embed [V, D] / proj [D, V], got "
                f"{embed.shape} / {proj.shape}"
            )
        if window < 1:
            raise ValueError(f"drafter window must be >= 1, got {window}")
        self.params = {"embed": embed, "proj": proj}
        self.window = int(window)
        self.source = source

    @classmethod
    def init(cls, vocab_size: int, dim: int = 16, *, window: int = 16,
             seed: int = 0) -> "NeuralDrafter":
        k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
        return cls(
            {
                "embed": 0.02 * jax.random.normal(
                    k1, (vocab_size, dim), jnp.float32
                ),
                "proj": 0.02 * jax.random.normal(
                    k2, (dim, vocab_size), jnp.float32
                ),
            },
            window=window,
            source=f"init:{vocab_size}:{dim}:{window}:{seed}",
        )

    @classmethod
    def load(cls, path: str) -> "NeuralDrafter":
        with np.load(path) as z:
            return cls(
                {"embed": z["embed"], "proj": z["proj"]},
                window=int(z["window"]), source=str(path),
            )

    def save(self, path: str) -> None:
        np.savez(
            path, embed=self.params["embed"], proj=self.params["proj"],
            window=np.int64(self.window),
        )

    @classmethod
    def from_spec(cls, spec: str) -> "NeuralDrafter":
        """"init:V:D:W:SEED" -> random init; anything else -> npz path.
        The spec string is what gets stamped into the journal header
        (`draft_model`), so replay can rebuild the identical drafter."""
        if spec.startswith("init:"):
            parts = spec.split(":")
            if len(parts) != 5:
                raise ValueError(
                    f"drafter init spec must be init:V:D:W:SEED, got "
                    f"{spec!r}"
                )
            v, d, w, s = (int(p) for p in parts[1:])
            return cls.init(v, d, window=w, seed=s)
        return cls.load(spec)

    def propose(self, context, k: int) -> list[int]:
        a = np.asarray(context, np.int64).reshape(-1)[-self.window:]
        if k <= 0 or a.size == 0:
            return []
        buf = np.zeros((1, self.window), np.int32)
        buf[0, self.window - a.size:] = a
        drafts = _draft_chain_jit(
            self.params, jnp.asarray(buf),
            jnp.asarray([a.size], jnp.int32), k=k,
        )
        return [int(x) for x in np.asarray(drafts)[0]]


def fit_neural_drafter(
    streams,
    vocab_size: int,
    *,
    dim: int = 16,
    window: int = 16,
    epochs: int = 30,
    lr: float = 0.5,
    seed: int = 0,
) -> tuple["NeuralDrafter", list[float]]:
    """Train a NeuralDrafter on token streams (next-token cross-entropy,
    full-batch gradient descent). Deliberately tiny — the draft model's
    job is to beat n-gram lookup on non-repetitive tails, not to be a
    language model. Returns (drafter, per-epoch losses)."""
    bufs, ns, tgts = [], [], []
    for stream in streams:
        a = np.asarray(stream, np.int64).reshape(-1)
        for t in range(1, a.size):
            ctx = a[max(0, t - window): t]
            row = np.zeros((window,), np.int32)
            row[window - ctx.size:] = ctx
            bufs.append(row)
            ns.append(ctx.size)
            tgts.append(a[t])
    if not bufs:
        raise ValueError("fit_neural_drafter needs at least one 2-token "
                         "stream")
    buf = jnp.asarray(np.stack(bufs))
    n = jnp.asarray(np.asarray(ns, np.int32))
    tgt = jnp.asarray(np.asarray(tgts, np.int32))
    drafter = NeuralDrafter.init(
        vocab_size, dim, window=window, seed=seed
    )
    params = {k: jnp.asarray(v) for k, v in drafter.params.items()}

    def loss_fn(p):
        lg = _draft_logits(p, buf, n)
        return -jnp.mean(
            jnp.take_along_axis(
                jax.nn.log_softmax(lg, axis=-1), tgt[:, None], axis=1
            )
        )

    step = jax.jit(
        lambda p: (loss_fn(p), jax.grad(loss_fn)(p))
    )
    losses = []
    for _ in range(epochs):
        loss, g = step(params)
        params = {k: v - lr * g[k] for k, v in params.items()}
        losses.append(float(loss))
    out = NeuralDrafter(
        {k: np.asarray(v) for k, v in params.items()}, window=window,
        source=f"fit:{vocab_size}:{dim}:{window}:{seed}",
    )
    return out, losses


@dataclasses.dataclass
class PagedState:
    """Host half of a paged decode: the device page pool plus the
    block tables and free-list that address it. Returned by
    `generate_paged(return_state=True)` for cross-turn prefix reuse;
    owned by serve/scheduler.py for continuous batching."""

    kv_pages: dict
    block_tables: np.ndarray  # [B, max_pages] int32 (sentinel-padded)
    allocator: "paged_kv_lib.PageAllocator"

    @property
    def page_size(self) -> int:
        return self.allocator.page_size


def _grow_block_tables(
    state: PagedState, row_tokens: list[int], max_pages: int
) -> np.ndarray:
    """Ensure each row's block table covers row_tokens[b] logical slots,
    allocating from the state's free list; widens the table to
    `max_pages` columns (sentinel-padded). Raises OutOfPagesError with
    nothing allocated if the pool cannot satisfy the TOTAL ask."""
    alloc = state.allocator
    bt = state.block_tables
    B, old = bt.shape
    out = np.full((B, max_pages), alloc.sentinel, np.int32)
    out[:, : min(old, max_pages)] = bt[:, : min(old, max_pages)]
    if old > max_pages:
        # Narrowing (a later turn with a smaller window): pages past the
        # new width would silently vanish from the table — return them
        # to the free list instead of leaking them.
        dropped = [
            int(p) for b in range(B) for p in bt[b, max_pages:]
            if p != alloc.sentinel
        ]
        if dropped:
            alloc.free(dropped)
    held = [int((out[b] != alloc.sentinel).sum()) for b in range(B)]
    need = [
        max(0, alloc.pages_for(row_tokens[b]) - held[b]) for b in range(B)
    ]
    if sum(need) > alloc.num_free:
        raise paged_kv_lib.OutOfPagesError(
            f"need {sum(need)} pages, {alloc.num_free} free"
        )
    for b in range(B):
        pages = alloc.alloc(need[b])
        out[b, held[b]: held[b] + need[b]] = pages
    state.block_tables = out
    return out


# hot-path
def generate_paged(
    params,
    cfg: LLMConfig,
    gen_cfg: GenerationConfig,
    *,
    inputs_embeds: jnp.ndarray,  # [B, T, H] (suffix only when `start`)
    lengths: jnp.ndarray,  # [B] real TOTAL lengths (incl. cached prefix)
    max_new_tokens: int,
    page_size: int = 64,
    chunk: int = 8,
    kv_capacity: int | None = None,
    num_pages: int | None = None,
    key: jax.Array | None = None,
    attn_impl: str = "xla",
    compute_dtype=None,
    stop_sequences: jnp.ndarray | None = None,
    state: PagedState | None = None,
    start: jnp.ndarray | None = None,
    return_state: bool = False,
    prefill_chunk: int | None = None,
    mesh=None,
    ragged: bool = False,
    kv_dtype: str | None = None,
):
    """`generate`, but over a paged KV cache in `chunk`-step compiled
    dispatches — the reference driver for the continuous-batching path
    (the scheduler runs the same `paged_prefill`/`paged_decode_chunk`
    programs with slots owned by different requests).

    kv_dtype: None/"bf16" = dense pages in the compute dtype (today's
    byte-exact path); "int8" = quantized pool with per-page scale
    blocks (qwen2.init_paged_kv_cache kv_dtype=) — quantize on page
    write, dequantize in the page walk; replies drift within the
    utils/quant.roundtrip_error_stats envelope instead of matching the
    dense path bit-for-bit. Ignored when a prior `state` is passed
    (the pool already exists).

    ragged: route every decode chunk through `paged_ragged_step` — the
    PACKED one-dispatch program (all rows ride one [1, B] query buffer
    with per-token segments instead of a [B, 1] batch) the continuous
    engine uses to fuse prefill and decode. Greedy token ids are
    bit-identical to ragged=False (per-row math is batch-layout
    independent); this is the standalone parity hook for the fused
    serving path (tests/test_ragged_attention.py).

    Greedy token ids are bit-identical to `generate` when `kv_capacity`
    matches the dense call's `cache_len` (identical fp32 reductions;
    masked kv columns contribute exact zeros either way). Sampled
    streams draw from per-row keys and so differ from the dense batch
    sampler by construction.

    kv_capacity: logical KV width per row (max_pages = kv_capacity /
    page_size); defaults to the bucket of max(lengths) + the chunk-
    padded decode window. num_pages: pool size; defaults to the exact
    ragged need — sum over rows of ceil((length + window) / page_size),
    which is the whole point: a short row costs its own pages, not the
    batch max. state/start: prefix KV reuse as in `generate`
    (kv_cache/start); pass the state from the previous turn and prefill
    only the suffix embeds. prefill_chunk: prefill in bounded windows
    via `paged_prefill_chunks` (bit-identical to single-shot; requires a
    uniform `start` across rows).

    mesh: tensor-parallel decode. A fresh page pool is placed with KV
    heads sharded over the mesh's tp axis
    (parallel/sharding.shard_paged_kv) and every dispatch runs inside
    the mesh scope, so GSPMD partitions attention by heads against
    tp-sharded params (builder.serving_param_shardings). Greedy token
    ids stay bit-identical to the single-device paged path: each shard
    computes its own heads' attention exactly as before, and the only
    cross-shard reduction (o_proj over heads) is the contraction the
    sharded dense path already proves. Callers passing a prior `state`
    own its placement."""
    from oryx_tpu.parallel.sharding import mesh_scope, shard_paged_kv

    def scope():
        return mesh_scope(mesh)  # fresh context manager per dispatch

    B, T, _ = inputs_embeds.shape
    if key is None:
        key = jax.random.key(0)
    padded_new = -(-max_new_tokens // chunk) * chunk
    lengths = jnp.asarray(lengths, jnp.int32)
    # Page-geometry decisions (block-table growth) are host-side by
    # design; one pre-loop copy of the row lengths, not a per-step sync.
    host_len = [int(x) for x in np.asarray(lengths)]  # oryxlint: disable=host-sync
    row_tokens = [n + padded_new for n in host_len]
    if kv_capacity is None:
        from oryx_tpu.ops.packing import round_up_bucket

        kv_capacity = round_up_bucket(max(row_tokens))
    if kv_capacity % page_size:
        raise ValueError(f"{kv_capacity=} not a multiple of {page_size=}")
    max_pages = kv_capacity // page_size
    dtype = compute_dtype or jnp.float32

    if state is None:
        if num_pages is None:
            alloc_probe = paged_kv_lib.PageAllocator(1, page_size)
            num_pages = sum(alloc_probe.pages_for(n) for n in row_tokens)
        allocator = paged_kv_lib.PageAllocator(num_pages, page_size)
        kv_pages = qwen2.init_paged_kv_cache(
            cfg, num_pages, page_size, dtype=dtype, kv_dtype=kv_dtype
        )
        if mesh is not None:
            kv_pages = shard_paged_kv(kv_pages, mesh)
        state = PagedState(
            kv_pages=kv_pages,
            block_tables=np.full((B, max_pages), allocator.sentinel,
                                 np.int32),
            allocator=allocator,
        )
    elif state.block_tables.shape[0] != B:
        raise ValueError(
            f"state holds {state.block_tables.shape[0]} rows, batch has {B}"
        )
    bt_host = _grow_block_tables(state, row_tokens, max_pages)
    bt = jnp.asarray(bt_host)

    start_vec = (
        jnp.zeros((B,), jnp.int32)
        if start is None
        else jnp.broadcast_to(jnp.asarray(start, jnp.int32), (B,))
    )
    temp = jnp.full((B,), gen_cfg.temperature, jnp.float32)
    top_p = jnp.full((B,), gen_cfg.top_p, jnp.float32)
    top_k = jnp.full((B,), gen_cfg.top_k, jnp.int32)
    key, sk = jax.random.split(key)
    row_keys = jax.random.split(sk, B)
    if prefill_chunk:
        # One admission-time validation read, outside the decode loop.
        starts = set(int(x) for x in np.asarray(start_vec))  # oryxlint: disable=host-sync
        if len(starts) != 1:
            raise ValueError(
                f"prefill_chunk needs one shared start, got {sorted(starts)}"
            )
        with scope():
            state.kv_pages, tok, row_keys = paged_prefill_chunks(
                params, cfg, inputs_embeds, lengths, bt, state.kv_pages,
                starts.pop(), row_keys, temp, top_p, top_k,
                prefill_chunk=prefill_chunk, attn_impl=attn_impl,
                compute_dtype=compute_dtype,
            )
    else:
        with scope():
            state.kv_pages, tok, row_keys = paged_prefill(
                params, cfg, inputs_embeds, lengths, bt, state.kv_pages,
                start_vec, row_keys, temp, top_p, top_k,
                attn_impl=attn_impl, compute_dtype=compute_dtype,
            )
    stop_L = 0 if stop_sequences is None else stop_sequences.shape[1]
    recent = jnp.full((B, stop_L), -2, jnp.int32)
    finished = jnp.zeros((B,), bool)
    cur_len = lengths
    eos = gen_cfg.eos_token_id
    toks_out = np.full((B, padded_new), eos, np.int32)
    fin_out = np.ones((B, padded_new), bool)
    H = inputs_embeds.shape[2]
    ragged_blanks = dict(
        pf_embeds=jnp.zeros((1, 0, H), inputs_embeds.dtype),
        pf_slot=jnp.asarray(0, jnp.int32),
        pf_off=jnp.asarray(0, jnp.int32),
        pf_len=jnp.asarray(0, jnp.int32),
        pf_active=jnp.asarray(False),
        pf_temp=jnp.zeros((1,), jnp.float32),
        pf_top_p=jnp.ones((1,), jnp.float32),
        pf_top_k=jnp.zeros((1,), jnp.int32),
    )
    done = 0
    while done < max_new_tokens:
        with scope():
            if ragged:
                (state.kv_pages, tok, cur_len, finished, recent,
                 row_keys, toks, fin, _, _) = paged_ragged_step(
                    params, cfg, state.kv_pages, bt, tok, cur_len,
                    finished, recent, row_keys, temp, top_p, top_k,
                    stop_sequences, pf_key=row_keys[:1],
                    **ragged_blanks,
                    chunk=chunk, pf_width=0, eos=eos,
                    attn_impl=attn_impl, compute_dtype=compute_dtype,
                )
            else:
                (state.kv_pages, tok, cur_len, finished, recent,
                 row_keys, toks, fin) = paged_decode_chunk(
                    params, cfg, state.kv_pages, bt, tok, cur_len,
                    finished, recent, row_keys, temp, top_p, top_k,
                    stop_sequences,
                    chunk=chunk, eos=eos, attn_impl=attn_impl,
                    compute_dtype=compute_dtype,
                )
        # The once-per-chunk harvest this loop exists to amortize (and
        # the early-exit below needs host booleans).
        # oryxlint: off=host-sync
        toks_out[:, done:done + chunk] = np.asarray(toks)
        fin_out[:, done:done + chunk] = np.asarray(fin)
        # oryxlint: on=host-sync
        done += chunk
        if fin_out[:, done - 1].all():
            break
    toks_out = toks_out[:, :max_new_tokens]
    fin_out = fin_out[:, :max_new_tokens]
    any_fin = fin_out.any(axis=1)
    num = np.where(
        any_fin, fin_out.argmax(axis=1) + 1, max_new_tokens
    ).astype(np.int32)
    out = (jnp.asarray(toks_out), jnp.asarray(num), jnp.asarray(any_fin))
    return out + (state,) if return_state else out
