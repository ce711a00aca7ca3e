"""End-to-end inference pipeline: media + question → answer text.

Reference parity: the README inference flow (SURVEY.md §3.2) — sample video
frames, preprocess at native resolution, build the conversation prompt with
`<image>` placeholders, `tokenizer_image_token()`, then `generate()` with a
KV cache and EOS stopping. Here the whole device side (ViT → compressor →
splice → prefill → lax.scan decode) is one compiled program per
(patch-bucket, seq-bucket, cache-bucket) triple; the host side below is
plain numpy glue.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from oryx_tpu.config import OryxConfig
from oryx_tpu.constants import (
    COMPRESSOR_RATIO,
    DEFAULT_IMAGE_TOKEN,
    IMAGE_TOKEN_INDEX,
    MODALITY_IMAGE,
    MODALITY_MULTI_IMAGE,
    MODALITY_VIDEO,
)
from oryx_tpu.conversation import conv_templates
from oryx_tpu.data import mm_utils
from oryx_tpu.models import generate as generate_lib
from oryx_tpu.models import oryx, qwen2, splice
from oryx_tpu.ops import packing
from oryx_tpu.utils import trace as trace_lib

Params = dict[str, Any]


def infer_modality(num_images: int, is_video: bool) -> str:
    if is_video:
        return MODALITY_VIDEO
    return MODALITY_MULTI_IMAGE if num_images > 1 else MODALITY_IMAGE


def stop_cut(text: str, stops: Sequence[str]) -> tuple[str, bool]:
    """Cut `text` at the earliest full stop-string occurrence. Returns
    (trimmed text, whether a stop fired). Shared by the streaming path
    and the continuous-batching scheduler."""
    cut = min(
        (i for s in stops if (i := text.find(s)) >= 0),
        default=-1,
    )
    return (text[:cut], True) if cut >= 0 else (text, False)


def stop_token_count(
    tokenizer, emitted: Sequence[int], stops: Sequence[str],
    chunk_start: int, offset: int = 0, skip: int = 0, before: str = "",
) -> int:
    """Minimal token-prefix length of `emitted` whose decoded text
    contains a stop string — the usage convention ("completion counts
    through the token completing the stop"), shared by chat_stream and
    the continuous scheduler. The stop completed somewhere in the tokens
    from `chunk_start` on (earlier prefixes were checked and clean), so
    only that tail is scanned. A caller that keeps the reply's text as
    it goes (`ReplyText`) has each prefix decoded from token `offset`
    on: the first `skip` characters of that text are context it already
    holds, and `before` is the text it holds in front of the rest."""
    for k in range(chunk_start + 1, len(emitted) + 1):
        text = tokenizer.decode(
            list(emitted[offset:k]), skip_special_tokens=True
        )
        if stop_cut(before + text[skip:], stops)[1]:
            return k
    return len(emitted)


def stable_text_end(text: str, stops: Sequence[str]) -> int:
    """Length of the prefix of `text` that can never change as more
    tokens decode, read off the END of the text alone: hold back an
    incomplete UTF-8 tail (U+FFFD), any suffix that could grow into a
    stop string, and trailing whitespace (chat() strips both ends;
    rstripped text re-emits once non-whitespace follows)."""
    end = len(text)
    while end and text[end - 1] == "\ufffd":
        end -= 1
    held = 0
    for s in stops:
        for i in range(min(len(s) - 1, end), 0, -1):
            if text.endswith(s[:i], 0, end):
                held = max(held, i)
                break
    end -= held
    while end and text[end - 1].isspace():
        end -= 1
    return end


def stable_text_prefix(text: str, stops: Sequence[str]) -> str:
    """The prefix of `text` that can never change as more tokens decode:
    `stable_text_end` of the text less its leading whitespace (lstrip
    is consistent across calls)."""
    text = text.lstrip()
    return text[: stable_text_end(text, stops)]


# Tokens of context a chunk's text is decoded behind: what a token
# decodes to can depend on the tokens just left of it (a sentencepiece
# word's leading space, the first bytes of a character), not on the
# reply before them.
TEXT_CONTEXT_TOKENS = 4


@dataclasses.dataclass
class ReplyText:
    """One reply's text, kept while its tokens arrive in chunks, under
    chat_stream's emission rules (stop trim, stable prefix, both ends
    stripped) at a cost bounded by the chunk: `advance` decodes the new
    tokens behind TEXT_CONTEXT_TOKENS of context (the prefix-offset /
    read-offset scheme: decode `emitted[p:r]` and `emitted[p:]`, the
    difference is the text past token r), scans for a stop string only
    where one could have completed, and applies the hold-back rules to
    the unsent end. Nothing reads the reply so far (what grows with it
    is the copy CPython makes for `done += out`: 0.3 us at 21,000
    characters).

    The text past token r stays undecided, and is decoded again with
    the next chunk, while it ends in U+FFFD (a character whose bytes
    are not all there yet): r moves at the first chunk that ends on a
    character."""

    # emitted[:tokens]'s text is decided; `buf` is its END: from the
    # first unsent character on, and never less than the
    # max(len(stop)) - 1 characters a stop string completing later can
    # reach back over.
    tokens: int = 0
    buf: str = ""
    # Offset, in `buf` + the undecided text, of the first character
    # not sent (leading whitespace counts as sent: it never will be).
    sent: int = 0
    done: str = ""  # what the client has

    # hot-path
    def advance(
        self, tokenizer, emitted: list[int], stops: Sequence[str],
        chunk_start: int, final: bool,
    ) -> tuple[str, int | None]:
        """Take in the tokens `emitted` gained since the last call
        (`emitted[chunk_start:]`). Returns (the text to send now, the
        number of tokens through the one that completed a stop string
        or None). The reply ends with this chunk when `final` (an EOS,
        the length cap) or at a stop: what was held back is flushed,
        stripped, as chat_stream does on finish."""
        p = max(0, self.tokens - TEXT_CONTEXT_TOKENS)
        skip = len(tokenizer.decode(
            emitted[p:self.tokens], skip_special_tokens=True
        )) if self.tokens > p else 0
        tail = tokenizer.decode(emitted[p:], skip_special_tokens=True)[skip:]
        # Earlier chunks were checked clean, so a stop completes in the
        # tail or not at all, and starts inside `buf` if before it.
        text, hit = stop_cut(self.buf + tail, stops)
        stop_tokens = None
        if hit:
            stop_tokens = stop_token_count(
                tokenizer, emitted, stops, chunk_start, p, skip, self.buf
            )
        unsent = text[self.sent:]
        if not self.done:
            unsent = unsent.lstrip()
            self.sent = len(text) - len(unsent)
        if final or hit:
            out = unsent.rstrip()
        else:
            out = unsent[: stable_text_end(unsent, stops)]
            if not tail.endswith("\ufffd"):
                self.tokens, self.buf = len(emitted), text
            self.sent += len(out)
            reach = max(map(len, stops), default=1) - 1
            drop = min(self.sent, len(self.buf) - reach)
            if drop > 0:
                self.buf, self.sent = self.buf[drop:], self.sent - drop
        self.done += out
        return out, stop_tokens


@partial(
    jax.jit, static_argnames=("cfg", "max_new_tokens", "cache_len")
)
def _jit_text_generate(
    params, cfg: OryxConfig, token_ids, lengths, max_new_tokens: int,
    cache_len: int, key, stop_sequences=None,
):
    embeds = params["llm"]["embed"]["weight"][token_ids]
    return generate_lib.generate(
        params["llm"], cfg.llm, cfg.generation,
        inputs_embeds=embeds, lengths=lengths,
        max_new_tokens=max_new_tokens, cache_len=cache_len, key=key,
        attn_impl=cfg.attn_impl, compute_dtype=oryx.compute_dtype(cfg),
        stop_sequences=stop_sequences,
    )


@partial(jax.jit, static_argnames=("cfg", "cache_len"))
def _jit_ll_prefill(params, cfg: OryxConfig, embeds, length, cache_len: int):
    """Prompt prefill for log-likelihood scoring → (log-softmax of the
    next-token logits at the prompt's last real position, KV cache)."""
    from oryx_tpu.models import qwen2 as qwen2_lib

    B, T, _ = embeds.shape
    positions = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (B, T))
    slot_ar = jnp.arange(cache_len, dtype=jnp.int32)[None, :]
    kv_mask = (slot_ar < length).astype(jnp.int32)
    cache = qwen2_lib.init_kv_cache(
        cfg.llm, B, cache_len, dtype=oryx.compute_dtype(cfg)
    )
    logits, cache = qwen2_lib.forward(
        params["llm"], cfg.llm,
        inputs_embeds=embeds, positions=positions,
        kv_cache=cache, write_slots=jnp.zeros((B,), jnp.int32),
        kv_mask=kv_mask, attn_impl=cfg.attn_impl,
        compute_dtype=oryx.compute_dtype(cfg),
    )
    last = jnp.take_along_axis(
        logits, (length - 1)[None, None, None].astype(jnp.int32), axis=1
    )[0, 0]
    return jax.nn.log_softmax(last.astype(jnp.float32)), cache


@partial(
    jax.jit, static_argnames=("cfg", "cache_len"),
    donate_argnames=("cache",),
)
def _jit_ll_suffix(params, cfg: OryxConfig, cache, cont_ids, length, k,
                   cache_len: int):
    """Teacher-force one option's tokens against the prompt cache →
    (log-softmax over the suffix positions [Kb, V], cache)."""
    from oryx_tpu.models import qwen2 as qwen2_lib

    B, Kb = cont_ids.shape
    embeds = params["llm"]["embed"]["weight"][cont_ids]
    positions = length + jnp.broadcast_to(
        jnp.arange(Kb, dtype=jnp.int32), (B, Kb)
    )
    slot_ar = jnp.arange(cache_len, dtype=jnp.int32)[None, :]
    kv_mask = (slot_ar < length + k).astype(jnp.int32)
    logits, cache = qwen2_lib.forward(
        params["llm"], cfg.llm,
        inputs_embeds=embeds, positions=positions,
        kv_cache=cache,
        write_slots=jnp.broadcast_to(length.astype(jnp.int32), (B,)),
        kv_mask=kv_mask, attn_impl=cfg.attn_impl,
        compute_dtype=oryx.compute_dtype(cfg),
    )
    # Gather ON DEVICE: position j's log-prob of continuation token j+1.
    # Returning the full [Kb, V] log-softmax would ship ~Kb x vocab
    # floats to the host per option just to read a handful of scalars.
    lp = jax.nn.log_softmax(logits[0].astype(jnp.float32))
    nxt = jnp.concatenate(
        [cont_ids[0, 1:], jnp.zeros((1,), cont_ids.dtype)]
    )
    vec = jnp.take_along_axis(lp, nxt[:, None].astype(jnp.int32), axis=1)
    return vec[:, 0], cache


TEXT_ONLY_MESSAGE = (
    "this model is text-only (the configuration has no vision tower): "
    "a request may not carry images or video"
)


class OryxInference:
    """Stateless-per-call chat interface over a loaded model.

    `answer = OryxInference(tokenizer, params, cfg).chat("what is this?",
    images=[img])`; `chat_video(frames, q)` applies 16x compression and one
    shared patch budget across frames (matching the training-side policy in
    train/data.SupervisedDataset).
    """

    def __init__(
        self,
        tokenizer,
        params: Params,
        cfg: OryxConfig,
        *,
        template: str = "qwen",
        mesh=None,
        sharding_mode: str = "tp",
    ) -> None:
        self.tokenizer = tokenizer
        self._frame_sep_cache = None
        self._session_cache = None
        # Ring attention is a TRAINING/prefill configuration (sequence
        # parallelism, no KV cache); decode needs the cached path. Models
        # trained under a ring config serve with the equivalent dense
        # kernel instead of crashing in generate().
        if cfg.attn_impl.startswith("ring"):
            import dataclasses

            impl = "pallas" if jax.default_backend() == "tpu" else "xla"
            cfg = dataclasses.replace(cfg, attn_impl=impl)
        self.cfg = cfg
        self.mesh = mesh
        if mesh is not None:
            # Multi-chip serving (the reference's 34B device_map): place
            # params per the serving shardings (no-op for params already
            # restored sharded by builder.load_pretrained_model(mesh=...))
            # and run every device call under this mesh so GSPMD inserts
            # the collectives.
            from oryx_tpu.parallel.sharding import shard_params
            from oryx_tpu.serve.builder import serving_param_shardings

            params = shard_params(
                params, serving_param_shardings(mesh, params, sharding_mode)
            )
        self.params = params
        self.conv = conv_templates[template]
        # In-loop stop matching (KeywordsStoppingCriteria parity): rows end
        # as soon as the template's stop string is emitted instead of
        # burning the rest of max_new_tokens.
        self.stop_sequences = generate_lib.make_stop_sequences(
            [self.conv.stop_str] if self.conv.stop_str else [], tokenizer
        )

    def _mesh_scope(self):
        from oryx_tpu.parallel.sharding import mesh_scope

        return mesh_scope(self.mesh)

    def _causal_only(self, what: str) -> None:
        """The pipe's own decode loops (dense cache, one causal token a
        step) are not a block-diffusion model's generation: refuse them
        rather than run a wrong loop under a real model's name."""
        if self.cfg.llm.block_length:
            raise NotImplementedError(
                f"{what} decodes one causal token a step; a block-"
                f"diffusion model (block_length="
                f"{self.cfg.llm.block_length}) generates through the "
                "continuous engine only (api_server.build_server("
                "engine='continuous'))"
            )

    def session_prefix_cache(self, capacity: int = 4):
        """Pipe-level cross-SESSION prefix cache (lazily created): pass
        it as `ChatSession(pipe, shared=pipe.session_prefix_cache())` —
        or just `shared=True` — and fresh sessions over the same media +
        system prompt seed their KV from a finished session's state
        instead of cold-prefilling it. Same index discipline as the
        continuous engine's page cache (serve/prefix_cache.py): block-
        aligned token-id matching, media-fingerprint rooted, LRU."""
        if self._session_cache is None:
            from oryx_tpu.serve.prefix_cache import SessionPrefixCache

            self._session_cache = SessionPrefixCache(capacity=capacity)
        return self._session_cache

    # ---- host-side prompt/media prep ------------------------------------

    def build_prompt(
        self,
        question: str,
        num_media: int,
        history: Sequence[tuple[str, str]] | None = None,
    ) -> str:
        """Conversation-templated prompt with one `<image>` placeholder per
        media item prepended to the FIRST user turn (reference multi-turn
        CLI style: media ride with the opening message, later turns are
        text against the same visual context)."""
        conv = self.conv.copy()
        prefix = (DEFAULT_IMAGE_TOKEN + "\n") * num_media
        turns = list(history or [])
        for i, (user, assistant) in enumerate(turns):
            conv.append_message(conv.roles[0], (prefix if i == 0 else "") + user)
            conv.append_message(conv.roles[1], assistant)
        conv.append_message(
            conv.roles[0], question if turns else prefix + question
        )
        conv.append_message(conv.roles[1], None)
        return conv.get_prompt()

    # ---- entry points ----------------------------------------------------

    def chat(
        self,
        question: str,
        *,
        images: Sequence[np.ndarray] | None = None,
        is_video: bool = False,
        history: Sequence[tuple[str, str]] | None = None,
        max_new_tokens: int | None = None,
        seed: int = 0,
        temperature: float | None = None,
        top_p: float | None = None,
        stop: Sequence[str] | None = None,
    ) -> str:
        """QA over optional images / video frames. history: prior
        (user, assistant) turns of the same conversation (media stay
        attached to the first turn)."""
        return self.chat_batch(
            [{
                "question": question,
                "images": list(images or []),
                "is_video": is_video,
                "history": list(history or []),
            }],
            max_new_tokens=max_new_tokens,
            seed=seed,
            temperature=temperature,
            top_p=top_p,
            stop=stop,
        )[0]

    def _sampling_cfg(
        self, temperature: float | None, top_p: float | None
    ) -> OryxConfig:
        """Config with per-request sampling overrides. The returned cfg is
        a static jit argument — equal values hit the same compiled
        program, so overrides cost at most one compile per distinct
        (temperature, top_p) pair."""
        if temperature is None and top_p is None:
            return self.cfg
        import dataclasses

        gen = self.cfg.generation
        updates = {}
        if temperature is not None:
            updates["temperature"] = float(temperature)
        if top_p is not None:
            updates["top_p"] = float(top_p)
        return dataclasses.replace(
            self.cfg, generation=dataclasses.replace(gen, **updates)
        )

    def _frame_sep_ids(self) -> tuple[int, ...]:
        """Tokenized cfg.frame_separator (parity hook, default off),
        cached — it never changes for a pipe."""
        if self._frame_sep_cache is None:
            self._frame_sep_cache = splice.frame_separator_ids(
                self.tokenizer, self.cfg.frame_separator
            )
        return self._frame_sep_cache

    def _stop_for(self, stop: Sequence[str] | None):
        """Stop-id matrix for the template stop plus request stops."""
        if not stop:
            return self.stop_sequences
        strs = [self.conv.stop_str] if self.conv.stop_str else []
        return generate_lib.make_stop_sequences(
            strs + list(stop), self.tokenizer
        )

    def _prepare_request(
        self, req: dict[str, Any]
    ) -> tuple[np.ndarray, list[np.ndarray], list[int], list[int]]:
        """One request dict → (token ids with per-frame sentinels, raw
        images, per-image side factors, per-image patch caps). The single
        source of the prep policy for batch AND streaming paths."""
        cfgv = self.cfg.vision
        images = list(req.get("images") or [])
        if images and cfgv is None:
            raise ValueError(TEXT_ONLY_MESSAGE)
        is_video = bool(req.get("is_video")) and len(images) > 0
        modality = infer_modality(len(images), is_video)
        prompt = self.build_prompt(
            req["question"],
            (1 if is_video else len(images)) if images else 0,
            history=req.get("history"),
        )
        ids = mm_utils.tokenizer_image_token(prompt, self.tokenizer)
        if is_video and len(images) > 1:
            ids, _ = splice.expand_video_sentinels(
                ids, len(images), sep_ids=self._frame_sep_ids()
            )
        if not images:
            return ids, [], [], []
        per_img_cap = (
            max(1, cfgv.max_patches_per_image // len(images))
            if modality == MODALITY_VIDEO
            else cfgv.max_patches_per_image
        )
        factor = int(COMPRESSOR_RATIO[modality] ** 0.5)
        return (
            ids, images, [factor] * len(images), [per_img_cap] * len(images)
        )

    def chat_batch(
        self,
        requests: Sequence[dict[str, Any]],
        *,
        max_new_tokens: int | None = None,
        seed: int = 0,
        return_finish_reasons: bool = False,
        return_token_counts: bool = False,
        temperature: float | None = None,
        top_p: float | None = None,
        stop: Sequence[str] | None = None,
        per_row_max: Sequence[int] | None = None,
    ) -> list[str] | tuple:
        """Batched single-turn QA: one ViT + compressor + decode scan for
        the whole batch (the batching win the reference gets from varlen
        flash-attn plus HF batched generate; SURVEY.md §3.5).

        requests: dicts with "question" (str), optional "images"
        (list of np arrays, pre-sampled for video), optional "is_video".
        Mixed text-only / image / multi-image / video rows are fine.
        return_finish_reasons: also return per-row "stop" (EOS or stop
        string) vs "length" (cut off by max_new_tokens).
        temperature/top_p override the config defaults for this call;
        stop adds request stop strings on top of the template's.
        per_row_max caps each row's OUTPUT length individually while the
        batch decodes max_new_tokens steps together (how the API server
        batches mixed-max_tokens traffic): a row's reply trims to its
        cap, and its finish reason reflects the cap, not the shared
        decode window. Greedy/sampled tokens are unchanged by the longer
        window (the step-key split is prefix-stable).
        return_token_counts: also return per-row (prompt_tokens,
        completion_tokens) — prompt counts the REAL spliced row length
        (text + visual tokens, no padding), the OpenAI usage convention.
        Return shape grows in flag order:
        replies[, reasons][, counts].
        """
        self._causal_only("chat_batch")
        cfg = self._sampling_cfg(temperature, top_p)
        stop_seqs = self._stop_for(stop)
        max_new = max_new_tokens or cfg.generation.max_new_tokens
        if per_row_max is not None:
            if len(per_row_max) != len(requests):
                raise ValueError(
                    f"per_row_max has {len(per_row_max)} entries for "
                    f"{len(requests)} requests"
                )
            if any(m < 1 or m > max_new for m in per_row_max):
                raise ValueError(
                    f"per_row_max entries must be in [1, {max_new}]"
                )
        key = jax.random.key(seed)
        all_images: list[np.ndarray] = []
        side_factors: list[int] = []
        max_patches: list[int] = []
        ids_rows: list[np.ndarray] = []
        for req in requests:
            ids, images, factors, caps = self._prepare_request(req)
            ids_rows.append(ids)
            all_images.extend(images)
            side_factors.extend(factors)
            max_patches.extend(caps)

        if not all_images:
            toks, num, fin = self._text_batch(
                ids_rows, max_new, key, cfg=cfg, stop_seqs=stop_seqs
            )
            prompt_lens = [len(r) for r in ids_rows]
        else:
            packed = packing.pack_raw_images(
                all_images,
                patch_size=cfg.vision.patch_size,
                base_grid=cfg.vision.base_grid,
                side_factors=side_factors,
                max_patches=max_patches,
            )
            batch = splice.build_mm_batch(
                ids_rows, splice.query_slots(packed)
            )
            with self._mesh_scope():
                toks, num, fin = oryx.mm_generate(
                    self.params, cfg, packed, batch,
                    max_new_tokens=max_new, key=key,
                    stop_sequences=stop_seqs,
                )
            prompt_lens = [
                int(np.sum(np.asarray(batch.attn_mask)[b]))
                for b in range(len(requests))
            ]
        caps = per_row_max or [max_new] * len(toks)
        replies = [
            self._decode(
                toks[b], min(int(num[b]), caps[b]), extra_stops=stop
            )
            for b in range(len(toks))
        ]
        out: tuple = (replies,)
        if return_finish_reasons:
            # A row "stopped" only if its EOS/stop landed within ITS cap.
            out += ([
                "stop" if bool(f) and int(n) <= c else "length"
                for f, n, c in zip(fin, num, caps)
            ],)
        if return_token_counts:
            out += ([
                (prompt_lens[b], min(int(num[b]), caps[b]))
                for b in range(len(toks))
            ],)
        return out[0] if len(out) == 1 else out

    def _text_batch(self, ids_rows, max_new: int, key, *, cfg=None,
                    stop_seqs=None):
        cfg = cfg or self.cfg
        stop_seqs = stop_seqs if stop_seqs is not None else self.stop_sequences
        B = len(ids_rows)
        T = packing.round_up_bucket(max(len(r) for r in ids_rows))
        rows = np.zeros((B, T), np.int32)
        lengths = np.zeros((B,), np.int32)
        for b, ids in enumerate(ids_rows):
            rows[b, : len(ids)] = ids
            lengths[b] = len(ids)
        cache_len = packing.round_up_bucket(T + max_new)
        with self._mesh_scope():
            toks, num, fin = _jit_text_generate(
                self.params, cfg, jnp.asarray(rows),
                jnp.asarray(lengths), max_new, cache_len, key,
                stop_seqs,
            )
        return np.asarray(toks), np.asarray(num), np.asarray(fin)

    def chat_stream(
        self,
        question: str,
        *,
        images: Sequence[np.ndarray] | None = None,
        is_video: bool = False,
        history: Sequence[tuple[str, str]] | None = None,
        max_new_tokens: int | None = None,
        seed: int = 0,
        chunk: int = 8,
        temperature: float | None = None,
        top_p: float | None = None,
        stop: Sequence[str] | None = None,
        cache_state: "PrefixCacheState | None" = None,
        usage_out: dict | None = None,
        shared: "Any | None" = None,
    ):
        """Streaming `chat` (HF TextIteratorStreamer parity): yields text
        DELTAS as tokens decode; ''.join(deltas) equals chat()'s reply
        exactly (incomplete UTF-8 tails, stop-string prefixes and
        leading/trailing whitespace are held back until resolvable).
        Single request; decode runs `chunk` tokens per device dispatch.
        The generator's RETURN value (StopIteration.value) is the finish
        reason: "stop" (EOS/stop string) or "length" (max_new_tokens).
        temperature/top_p/stop override per request as in `chat_batch`.

        With cache_state (ChatSession.ask_stream), the shared token
        prefix is served from the session's KV cache (_prefix_plan) and
        the RETURN value becomes (reason, new PrefixCacheState) — the
        new state's ids cover the PROMPT only (streamed reply tokens are
        re-prefilled next turn; the visual prefill is still one-time).

        usage_out: a dict the generator fills with prompt_tokens (real
        spliced prompt length incl. visual tokens and any cached prefix)
        and completion_tokens before returning — the streaming half of
        chat_batch's return_token_counts. The finishing token is counted
        (EOS, or the token that completes a stop string), matching the
        batch path; tokens decoded past a host-side stop cut are not.

        shared: cross-session SessionPrefixCache, as in `chat_cached` —
        a COLD cache_state seeds from the index's longest stored prefix
        and the post-turn state is donated back.
        """
        self._causal_only("chat_stream")
        cfg = self._sampling_cfg(temperature, top_p)
        stop_seqs = self._stop_for(stop)
        max_new = max_new_tokens or cfg.generation.max_new_tokens
        key = jax.random.key(seed)
        cfgv = cfg.vision
        ids, images, factors, caps = self._prepare_request({
            "question": question, "images": list(images or []),
            "is_video": is_video, "history": list(history or []),
        })
        if (
            shared is not None and cache_state is not None
            and cache_state.cache is None and not images
        ):
            cand = shared.lookup(
                np.asarray(ids, np.int64), _media_fingerprint(images)
            )
            if cand is not None:
                cache_state = cand

        # Decode always runs whole chunks (a shrunken final chunk would
        # compile a second decode program); overshoot tokens are dropped
        # and the cache is sized for the padded length.
        padded_new = -(-max_new // chunk) * chunk
        kv_cache = start = flat = None
        media_key = ()
        # Spans land on the context-active trace (the API server's
        # flight recorder) and cost nothing outside one — the window
        # engine's streams get the same prefill/decode_chunk/emission
        # attribution as the continuous scheduler's requests.
        if cache_state is not None:
            with self._mesh_scope(), trace_lib.span("prefill", cached=True):
                flat, L, common, embeds, kv_cache, cache_len, media_key = (
                    self._prefix_plan(
                        cache_state, cfg, ids, images, factors, caps,
                        padded_new,
                    )
                )
            lengths = jnp.asarray([L], np.int32)
            start = jnp.asarray(common, jnp.int32)
        else:
            with self._mesh_scope(), trace_lib.span("prefill"):
                embeds, L = self._prompt_embeds(
                    cfg, ids, images, factors, caps
                )
            lengths = jnp.asarray([L], np.int32)
            cache_len = packing.round_up_bucket(embeds.shape[1] + padded_new)
        eos = cfg.generation.eos_token_id
        stops = ([self.conv.stop_str] if self.conv.stop_str else []) + [
            s for s in (stop or []) if s  # "" would truncate everything
        ]
        emitted: list[int] = []
        text_done = ""
        finished = eos_hit = False
        stop_tok_count: int | None = None

        def trim_stops(text: str) -> tuple[str, bool]:
            return stop_cut(text, stops)

        def stable_prefix(text: str) -> str:
            return stable_text_prefix(text, stops)

        final_cache = None

        def result(reason):
            """Return value: bare reason, or (reason, new state) when the
            caller passed a cache_state."""
            if usage_out is not None:
                usage_out["prompt_tokens"] = int(lengths[0])
                # A stop-string finish counts through the token that
                # completed the stop (stop_tok_count), not the whole
                # in-flight decode chunk; the stop cut sits inside
                # `emitted`, so it always precedes an EOS seen in the
                # same chunk. Otherwise +1 counts the finishing EOS,
                # matching chat_batch's num ("up to and including the
                # finishing token"); `emitted` excludes it (the loop
                # breaks before appending).
                if stop_tok_count is not None:
                    usage_out["completion_tokens"] = stop_tok_count
                elif eos_hit:
                    usage_out["completion_tokens"] = len(emitted) + 1
                else:
                    usage_out["completion_tokens"] = len(emitted)
            if cache_state is None:
                return reason
            new_state = PrefixCacheState(
                ids=flat, cache=final_cache, cache_len=cache_len,
                prompt_ids=np.asarray(ids, np.int64), prompt_flat=flat,
                media_key=media_key,
            )
            if shared is not None and final_cache is not None:
                shared.insert(new_state)
            return reason, new_state

        def traced_blocks(gen):
            """Time each device chunk (the window between successive
            yields) as a decode_chunk span on the active trace."""
            n = 0
            while True:
                t0 = trace_lib.now_ns()
                try:
                    b = next(gen)
                except StopIteration:
                    return
                trace_lib.add_complete("decode_chunk", t0, chunk=n)
                n += 1
                yield b

        with self._mesh_scope():
            for block in traced_blocks(generate_lib.generate_stream(
                self.params["llm"], cfg.llm, cfg.generation,
                inputs_embeds=embeds, lengths=lengths,
                max_new_tokens=max_new, cache_len=cache_len, key=key,
                attn_impl=cfg.attn_impl,
                compute_dtype=oryx.compute_dtype(cfg),
                stop_sequences=stop_seqs, chunk=chunk,
                kv_cache=kv_cache, start=start,
                yield_cache=cache_state is not None,
            )):
                if cache_state is not None:
                    block, final_cache = block
                t_emit = trace_lib.now_ns()
                chunk_start = len(emitted)
                for t in block[0]:
                    if int(t) == eos:
                        finished = eos_hit = True
                        break
                    emitted.append(int(t))
                text = self.tokenizer.decode(
                    emitted, skip_special_tokens=True
                )
                text, hit = trim_stops(text)
                if usage_out is not None and hit and stop_tok_count is None:
                    # The stop string completed somewhere in THIS chunk
                    # (earlier chunks were trimmed and didn't hit).
                    stop_tok_count = stop_token_count(
                        self.tokenizer, emitted, stops, chunk_start
                    )
                finished = finished or hit
                safe = text.strip() if finished else stable_prefix(text)
                trace_lib.add_complete("emission", t_emit, chars=len(safe))
                if len(safe) > len(text_done):
                    yield safe[len(text_done):]
                    text_done = safe
                if finished:
                    return result("stop")
        # Decode window exhausted without EOS/stop: flush the held-back
        # tail (chat() would return it) and report the truncation.
        tail = text.strip() if emitted else ""
        if len(tail) > len(text_done):
            yield tail[len(text_done):]
        return result("length")

    def _prefix_plan(
        self, state: "PrefixCacheState", cfg, ids, imgs, factors, caps,
        new_budget: int,
    ):
        """Host-side half of prefix-cached generation: match the new
        prompt's post-splice token stream against the cache, build the
        suffix embeds and a (possibly grown) cache. `new_budget` is the
        number of decode slots to reserve past the prompt (max_new, or
        the chunk-padded window for streaming).

        Returns (flat, L, common, embeds, cache, cache_len, media_key)."""
        cfgv = cfg.vision
        ids = np.asarray(ids, np.int64)

        # Visual slots match positionally, not by content — a cache built
        # over DIFFERENT media must not be matched against at all.
        media_key = _media_fingerprint(imgs)
        reusable = state.cache is not None and state.media_key == media_key

        # A turn that merely EXTENDS the previous prompt (the normal
        # multi-turn case: same media, appended history) reuses the
        # stored post-splice stream — no host-side image re-packing.
        packed = batch = None
        np_prev = state.prompt_ids
        extend = (
            reusable
            and 0 < len(np_prev) < len(ids)
            and np.array_equal(ids[: len(np_prev)], np_prev)
            and not np.any(ids[len(np_prev):] == IMAGE_TOKEN_INDEX)
        )
        if extend:
            flat = np.concatenate([state.prompt_flat, ids[len(np_prev):]])
            L = len(flat)
        elif imgs:
            packed = packing.pack_raw_images(
                imgs, patch_size=cfgv.patch_size, base_grid=cfgv.base_grid,
                side_factors=factors, max_patches=caps,
            )
            batch = splice.build_mm_batch([ids], splice.query_slots(packed))
            L = int(batch.lengths[0])
            row = np.asarray(batch.token_ids[0][:L], np.int64)
            isv = np.asarray(batch.is_visual[0][:L])
            flat = np.where(isv, -7, row)
        else:
            L = len(ids)
            flat = ids

        # Longest shared prefix with the cache's token stream. Keep at
        # least one token in the suffix (the prefill must produce the
        # next-token logit), and never split a visual region (-7 marks
        # visual slots in the flat stream).
        common = 0
        if reusable and len(state.ids):
            m = min(len(state.ids), L - 1)
            neq = flat[:m] != state.ids[:m]
            common = int(np.argmax(neq)) if neq.any() else m
        if np.any(flat[common:] == -7):
            if extend:  # shouldn't happen (visuals live in the prefix)
                raise RuntimeError("visual slot escaped the shared prefix")
            common = 0  # visual tokens in the suffix -> full mm prefill

        suffix = flat[common:]
        s_buck = packing.round_up_bucket(len(suffix))
        # Never shrink below the live cache's capacity: generate's masks
        # are built at cache_len and must span every slot the reused
        # cache actually has.
        cache_len = max(
            packing.round_up_bucket(max(L + new_budget, common + s_buck)),
            state.cache_len,
        )
        dtype = oryx.compute_dtype(cfg)
        if common == 0 and packed is not None:
            arrays = oryx.stage_mm_arrays(packed, batch)
            embeds = oryx.mm_embeds(self.params, cfg, arrays)
            s_buck = embeds.shape[1]
            cache_len = max(
                packing.round_up_bucket(max(L + new_budget, s_buck)),
                state.cache_len,
            )
        else:
            rows = np.zeros((1, s_buck), np.int32)
            rows[0, : len(suffix)] = np.where(
                suffix == -7, 0, suffix
            )  # (-7 never reaches here: common==0 has no cache hits)
            embeds = self.params["llm"]["embed"]["weight"][
                jnp.asarray(rows)
            ]
        cache = state.cache
        if cache is None or state.cache_len < cache_len:
            fresh = qwen2.init_kv_cache(cfg.llm, 1, cache_len, dtype=dtype)
            if cache is not None:
                # Grow: carry the existing slots into the new buffer.
                fresh = jax.tree.map(
                    lambda f, c: jax.lax.dynamic_update_slice(
                        f, c.astype(f.dtype), (0, 0, 0, 0, 0)
                    ),
                    fresh, cache,
                )
            cache = fresh
        return flat, L, common, embeds, cache, cache_len, media_key

    def chat_cached(
        self,
        state: "PrefixCacheState",
        question: str,
        *,
        images: Sequence[np.ndarray] | None = None,
        is_video: bool = False,
        history: Sequence[tuple[str, str]] | None = None,
        max_new_tokens: int | None = None,
        seed: int = 0,
        temperature: float | None = None,
        top_p: float | None = None,
        stop: Sequence[str] | None = None,
        shared: "Any | None" = None,
    ) -> tuple[str, "PrefixCacheState"]:
        """`chat` for one conversation with cross-turn KV prefix reuse:
        the longest token-id prefix shared with `state.ids` is NOT
        re-prefilled — only the new suffix runs through the model, at
        absolute positions, writing into the session's cache. Matching
        is on ids (vLLM-style), so a tokenizer boundary merge or a
        template quirk just shortens the reuse, never changes the reply;
        a visual token inside the unshared suffix falls back to a full
        multimodal prefill. Returns (reply, new state).

        shared: a SessionPrefixCache (serve/prefix_cache.py). A COLD
        `state` first seeds itself from the cache's longest stored
        prefix of this prompt (cross-session reuse of e.g. a shared
        system prompt), and the new state is donated back after the
        turn. Text-only lookup (pre-splice ids == the flat stream);
        multimodal turns still donate and reuse within a session."""
        self._causal_only("chat_cached")
        cfg = self._sampling_cfg(temperature, top_p)
        stop_seqs = self._stop_for(stop)
        max_new = max_new_tokens or cfg.generation.max_new_tokens
        key = jax.random.key(seed)
        ids, imgs, factors, caps = self._prepare_request({
            "question": question, "images": list(images or []),
            "is_video": is_video, "history": list(history or []),
        })
        if shared is not None and state.cache is None and not imgs:
            cand = shared.lookup(
                np.asarray(ids, np.int64), _media_fingerprint(imgs)
            )
            if cand is not None:
                state = cand
        with self._mesh_scope():
            flat, L, common, embeds, cache, cache_len, media_key = (
                self._prefix_plan(
                    state, cfg, ids, imgs, factors, caps, max_new
                )
            )
            toks, num, fin, cache = generate_lib.generate(
                self.params["llm"], cfg.llm, cfg.generation,
                inputs_embeds=embeds,
                lengths=jnp.asarray([L], np.int32),
                max_new_tokens=max_new, cache_len=cache_len, key=key,
                attn_impl=cfg.attn_impl,
                compute_dtype=oryx.compute_dtype(cfg),
                stop_sequences=stop_seqs,
                kv_cache=cache,
                start=jnp.asarray(common, jnp.int32),
                return_cache=True,
            )
        toks, num = np.asarray(toks), np.asarray(num)
        reply = self._decode(toks[0], int(num[0]), extra_stops=stop)
        new_ids = np.concatenate(
            [flat, toks[0][: int(num[0])].astype(np.int64)]
        )
        new_state = PrefixCacheState(
            ids=new_ids, cache=cache, cache_len=cache_len,
            prompt_ids=np.asarray(ids, np.int64), prompt_flat=flat,
            media_key=media_key,
        )
        if shared is not None:
            shared.insert(new_state)
        return reply, new_state

    def _prompt_embeds(self, cfg, ids, imgs, factors, caps):
        """One prompt row → (decoder input embeds [1, T_bucket, H], real
        length). The single owner of the prompt prep policy for the
        streaming, scoring and prefix-cache paths (call under
        `_mesh_scope`)."""
        if imgs:
            packed = packing.pack_raw_images(
                imgs, patch_size=cfg.vision.patch_size,
                base_grid=cfg.vision.base_grid,
                side_factors=factors, max_patches=caps,
            )
            batch = splice.build_mm_batch([ids], splice.query_slots(packed))
            embeds = oryx.mm_embeds(
                self.params, cfg, oryx.stage_mm_arrays(packed, batch)
            )
            return embeds, int(batch.lengths[0])
        L = len(ids)
        rows = np.zeros((1, packing.round_up_bucket(L)), np.int32)
        rows[0, :L] = ids
        return self.params["llm"]["embed"]["weight"][jnp.asarray(rows)], L

    def score_options(
        self,
        question: str,
        options: Sequence[str],
        *,
        images: Sequence[np.ndarray] | None = None,
        is_video: bool = False,
        history: Sequence[tuple[str, str]] | None = None,
    ) -> np.ndarray:
        """Log-likelihood of each candidate continuation given the
        prompt (lmms-eval's `loglikelihood` model API): the prompt —
        including any visual prefill — runs ONCE into a KV cache, then
        each option's tokens are teacher-forced against it, summing
        next-token log-probs. Returns [len(options)] float64 sums.

        One device prefill + one tiny suffix forward per option; options
        longer than the suffix bucket share a compiled program.

        Caveat (lmms-eval encodes context+continuation jointly and
        splits): options are tokenized STANDALONE, so a BPE tokenizer
        that would merge across the prompt/option boundary scores a
        token split the model may never emit there. Single-letter or
        newline-separated continuations (the harness's MCQ protocol)
        are unaffected; for free-text options include any leading
        space/punctuation in the option string itself."""
        self._causal_only("score_options")
        ids, imgs, factors, caps = self._prepare_request({
            "question": question, "images": list(images or []),
            "is_video": is_video, "history": list(history or []),
        })
        cfg = self.cfg
        opt_ids = [
            np.asarray(
                self.tokenizer.encode(o, add_special_tokens=False),
                np.int32,
            )
            for o in options
        ]
        if any(len(o) == 0 for o in opt_ids):
            raise ValueError("every option must encode to >= 1 token")
        kb = packing.round_up_bucket(max(len(o) for o in opt_ids))

        with self._mesh_scope():
            embeds, L = self._prompt_embeds(cfg, ids, imgs, factors, caps)
            cache_len = packing.round_up_bucket(L + kb)
            first_lp, cache = _jit_ll_prefill(
                self.params, cfg, embeds, jnp.asarray(L, jnp.int32),
                cache_len,
            )
            first_lp = np.asarray(first_lp, np.float64)
            scores = np.zeros(len(options), np.float64)
            for i, o in enumerate(opt_ids):
                row = np.zeros((1, kb), np.int32)
                row[0, : len(o)] = o
                scores[i] = first_lp[int(o[0])]
                if len(o) > 1:
                    vec, cache = _jit_ll_suffix(
                        self.params, cfg, cache, jnp.asarray(row),
                        jnp.asarray(L, jnp.int32),
                        jnp.asarray(len(o), jnp.int32), cache_len,
                    )
                    # vec[j] = log P(token j+1 | ... token j).
                    scores[i] += float(
                        np.asarray(vec, np.float64)[: len(o) - 1].sum()
                    )
        return scores

    def chat_video(
        self,
        frames: Sequence[np.ndarray],
        question: str,
        *,
        num_frames: int | None = None,
        **kw,
    ) -> str:
        """Video QA: uniform frame sampling then 16x-compressed chat."""
        frames = list(frames)
        if num_frames is not None and len(frames) > num_frames:
            idx = mm_utils.sample_frames(len(frames), num_frames)
            frames = [frames[i] for i in idx]
        return self.chat(question, images=frames, is_video=True, **kw)

    def _decode(
        self, tokens: np.ndarray, num: int,
        extra_stops: Sequence[str] | None = None,
    ) -> str:
        ids = [int(t) for t in tokens[:num]]
        eos = self.cfg.generation.eos_token_id
        while ids and ids[-1] == eos:
            ids.pop()
        text = self.tokenizer.decode(ids, skip_special_tokens=True)
        stops = ([self.conv.stop_str] if self.conv.stop_str else []) + [
            s for s in (extra_stops or []) if s  # "" would match at 0
        ]
        cut = min(
            (i for s in stops if (i := text.find(s)) >= 0), default=-1
        )
        if cut >= 0:
            text = text[:cut]
        return text.strip()


@dataclasses.dataclass
class PrefixCacheState:
    """Cross-turn KV prefix cache for a single conversation: `ids` is
    the token stream whose K/V currently occupy cache slots [0, len)
    (visual slots marked -7 — they match positionally, never by id),
    `cache` the device K/V, `cache_len` its slot capacity.
    `prompt_ids`/`prompt_flat` record the previous turn's pre-splice and
    post-splice prompt streams so a turn that merely EXTENDS the prompt
    skips the host-side image packing entirely."""

    ids: np.ndarray = dataclasses.field(
        default_factory=lambda: np.zeros((0,), np.int64)
    )
    cache: dict | None = None
    cache_len: int = 0
    prompt_ids: np.ndarray = dataclasses.field(
        default_factory=lambda: np.zeros((0,), np.int64)
    )
    prompt_flat: np.ndarray = dataclasses.field(
        default_factory=lambda: np.zeros((0,), np.int64)
    )
    # Content fingerprint of the session's media: visual slots match
    # POSITIONALLY in the id stream, so swapped same-shape images would
    # otherwise silently reuse the old images' K/V.
    media_key: tuple = ()


def _media_fingerprint(imgs) -> tuple:
    """Cheap content key for the media list (crc32 per image + shape)."""
    import zlib

    return tuple(
        (im.shape, zlib.crc32(np.ascontiguousarray(im).tobytes()))
        for im in imgs
    )


class ChatSession:
    """Stateful multi-turn conversation over one media context (the
    reference's interactive CLI loop: media attach to the first turn).

    With cache=True (default) the session keeps the KV cache across
    turns and each `ask` / `ask_stream` prefills only the token suffix
    the cache has not seen (vLLM-style longest-common-prefix matching
    over token ids — robust to tokenizer boundary effects, and the
    expensive video/image prefill happens once per session instead of
    every turn; a media-content fingerprint guards against positional
    false matches). Replies and streamed deltas are identical either
    way.

    shared routes the session through the pipe-level CROSS-session
    prefix index (serve/prefix_cache.py — the same index discipline the
    continuous engine's page cache uses): True uses
    `pipe.session_prefix_cache()`, or pass a SessionPrefixCache
    directly. A fresh session then inherits the KV of the longest
    stored prefix (shared system prompt, repeated opener) instead of
    cold-prefilling it, and donates its state back after each turn."""

    def __init__(
        self,
        pipe: OryxInference,
        *,
        images: Sequence[np.ndarray] | None = None,
        is_video: bool = False,
        cache: bool = True,
        shared=None,
    ) -> None:
        self.pipe = pipe
        self.images = list(images or [])
        self.is_video = is_video and bool(self.images)
        self.history: list[tuple[str, str]] = []
        self._cache_state = PrefixCacheState() if cache else None
        if shared is True:
            shared = pipe.session_prefix_cache()
        self.shared = shared if cache else None

    def ask(self, question: str, **kw) -> str:
        if self._cache_state is not None:
            reply, self._cache_state = self.pipe.chat_cached(
                self._cache_state, question, images=self.images,
                is_video=self.is_video, history=self.history,
                shared=self.shared, **kw,
            )
        else:
            reply = self.pipe.chat(
                question, images=self.images, is_video=self.is_video,
                history=self.history, **kw,
            )
        self.history.append((question, reply))
        return reply

    def ask_stream(self, question: str, **kw):
        """Streamed `ask`: yields text deltas; records the turn in
        history once the stream is consumed. With the session cache on,
        the prompt prefix (including the visual prefill) is served from
        the KV cache like `ask`."""
        parts: list[str] = []
        gen = self.pipe.chat_stream(
            question, images=self.images, is_video=self.is_video,
            history=self.history, cache_state=self._cache_state,
            shared=self.shared, **kw,
        )
        while True:
            try:
                delta = next(gen)
            except StopIteration as s:
                if self._cache_state is not None and s.value is not None:
                    _, self._cache_state = s.value
                break
            parts.append(delta)
            yield delta
        self.history.append((question, "".join(parts).strip()))

    def reset(self) -> None:
        self.history.clear()
        if self._cache_state is not None:
            self._cache_state = PrefixCacheState()
