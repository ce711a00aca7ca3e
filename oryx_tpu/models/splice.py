"""Multimodal splicing: interleave visual embeddings into the token stream.

Reference parity: `prepare_inputs_labels_for_multimodal` in
`oryx/model/oryx_arch.py` (SURVEY.md §2 "Multimodal arch / splicing", §3.4)
— the reference's single biggest function, a per-sample Python loop that
splits `input_ids` at IMAGE_TOKEN_INDEX sentinels and concatenates text and
visual embeddings. That formulation is shape-dynamic and cannot jit.

TPU-first formulation (SURVEY.md §7 hard part 4): the *host* computes an
index map once per batch (cheap numpy bookkeeping — visual token counts are
known from packing metadata before any model runs), and the *device* builds
`inputs_embeds` with a single static-shape select-gather:

    embeds[b, t] = is_visual[b, t] ? visual_buffer[visual_idx[b, t]]
                                   : embed_table[token_ids[b, t]]

The visual buffer is the Dynamic Compressor's packed output [Q, H_llm] for
the whole batch (one ViT + one compressor call for all images of all
samples — the same batching win the reference gets from varlen flash-attn).
"""

from __future__ import annotations

import dataclasses

import jax
import numpy as np
import jax.numpy as jnp

from oryx_tpu.constants import IGNORE_INDEX, IMAGE_TOKEN_INDEX
from oryx_tpu.ops.packing import DEFAULT_BUCKETS, PackedVisual, round_up_bucket
from oryx_tpu.parallel.sharding import constrain


def frame_separator_ids(tokenizer, frame_separator: str | None) -> tuple[int, ...]:
    """Tokenize OryxConfig.frame_separator into the sep_ids tuple for
    expand_video_sentinels. The ONE tokenization policy for the hook —
    serving (pipeline) and training (train/cli) both call this, so a
    policy tweak can never skew train vs serve layout."""
    if not frame_separator:
        return ()
    return tuple(
        int(t)
        for t in tokenizer.encode(frame_separator, add_special_tokens=False)
    )


def expand_video_sentinels(
    ids: np.ndarray,
    n_frames: int,
    *,
    labels: np.ndarray | None = None,
    sep_ids: tuple[int, ...] = (),
) -> tuple[np.ndarray, np.ndarray | None]:
    """Expand a video's single IMAGE_TOKEN_INDEX placeholder into one
    sentinel per frame, optionally followed by separator token ids after
    EACH frame (the LLaVA-NeXT image-newline convention).

    Reference parity hook (SURVEY.md §3.4 "optional per-frame
    separators/newlines", exp `oryx/model/oryx_arch.py`): default OFF
    (`sep_ids=()` reproduces the plain contiguous-sentinel layout). The
    flag is `OryxConfig.frame_separator` — a string tokenized by the
    caller — so reference behavior can be matched without surgery once
    the real checkpoint/template is readable.

    Inserted positions get IGNORE_INDEX labels. Shared by the serving
    path (pipeline._prepare_request) and the training collator
    (train/data.collate) so train and serve always agree on layout.
    """
    ids = np.asarray(ids)
    idx = int(np.where(ids == IMAGE_TOKEN_INDEX)[0][0])
    per_frame = [IMAGE_TOKEN_INDEX, *sep_ids]
    mid = np.asarray(per_frame * n_frames, ids.dtype)
    out = np.concatenate([ids[:idx], mid, ids[idx + 1:]])
    out_labels = None
    if labels is not None:
        labels = np.asarray(labels)
        out_labels = np.concatenate(
            [labels[:idx],
             np.full(len(mid), IGNORE_INDEX, labels.dtype),
             labels[idx + 1:]]
        )
    return out, out_labels


def query_slots(packed: PackedVisual) -> list[tuple[int, int]]:
    """Per-image (start, count) slots in the packed query buffer, in pack
    order. Derived from q_grids (queries are image-major, contiguous)."""
    slots = []
    start = 0
    for hq, wq in packed.q_grids:
        slots.append((start, hq * wq))
        start += hq * wq
    return slots


@dataclasses.dataclass
class MMBatch:
    """Static-shape spliced batch (host numpy; feed to device as-is).

    token_ids  [B, T] int32 — text token id per slot (0 at visual/pad slots)
    visual_idx [B, T] int32 — index into the packed visual buffer (0 if n/a)
    is_visual  [B, T] bool
    attn_mask  [B, T] int32 — 1 on real (text or visual) slots
    positions  [B, T] int32 — 0..len-1 per row (0 on pads)
    labels     [B, T] int32 — next-token targets aligned to slots
                               (IGNORE_INDEX on visual spans, prompt & pads)
    lengths    [B] int32 — real length per row
    """

    token_ids: np.ndarray
    visual_idx: np.ndarray
    is_visual: np.ndarray
    attn_mask: np.ndarray
    positions: np.ndarray
    labels: np.ndarray
    lengths: np.ndarray


def build_mm_batch(
    input_ids: list[np.ndarray],
    image_slots: list[tuple[int, int]],
    *,
    labels: list[np.ndarray] | None = None,
    max_len: int | None = None,
    buckets: tuple[int, ...] = DEFAULT_BUCKETS,
) -> MMBatch:
    """Build the spliced index map for a batch.

    input_ids: per-sample int arrays containing IMAGE_TOKEN_INDEX sentinels;
      sentinels are consumed left-to-right against `image_slots` (the global
      per-image (start, count) ranges from `query_slots`, ordered across the
      whole batch: sample 0's images first, then sample 1's, ...).
    labels: optional per-sample arrays aligned with input_ids (sentinel
      positions ignored); visual spans and pads become IGNORE_INDEX.
    max_len: truncate rows to this many slots (model_max_length-equivalent).
    """
    img_iter = iter(image_slots)
    rows = []
    for si, ids in enumerate(input_ids):
        ids = np.asarray(ids)
        lab = None if labels is None else np.asarray(labels[si])
        tok, vidx, isv, lb = [], [], [], []
        for j, t in enumerate(ids):
            if t == IMAGE_TOKEN_INDEX:
                start, count = next(img_iter)
                tok.extend([0] * count)
                vidx.extend(range(start, start + count))
                isv.extend([True] * count)
                lb.extend([IGNORE_INDEX] * count)
            else:
                tok.append(int(t))
                vidx.append(0)
                isv.append(False)
                lb.append(IGNORE_INDEX if lab is None else int(lab[j]))
        if max_len is not None:
            tok, vidx, isv, lb = (x[:max_len] for x in (tok, vidx, isv, lb))
        rows.append((tok, vidx, isv, lb))

    remaining = sum(1 for _ in img_iter)
    if remaining:
        raise ValueError(f"{remaining} image slot(s) had no sentinel consumer")

    B = len(rows)
    T = round_up_bucket(max(len(r[0]) for r in rows), buckets)
    out = MMBatch(
        token_ids=np.zeros((B, T), np.int32),
        visual_idx=np.zeros((B, T), np.int32),
        is_visual=np.zeros((B, T), bool),
        attn_mask=np.zeros((B, T), np.int32),
        positions=np.zeros((B, T), np.int32),
        labels=np.full((B, T), IGNORE_INDEX, np.int32),
        lengths=np.zeros((B,), np.int32),
    )
    for b, (tok, vidx, isv, lb) in enumerate(rows):
        n = len(tok)
        out.token_ids[b, :n] = tok
        out.visual_idx[b, :n] = vidx
        out.is_visual[b, :n] = isv
        out.attn_mask[b, :n] = 1
        out.positions[b, :n] = np.arange(n)
        out.labels[b, :n] = lb
        out.lengths[b] = n
    # Shift labels: label[t] supervises the prediction made AT slot t for
    # slot t+1 (standard causal LM shift, done once here so the loss is a
    # plain masked CE with no further shifting).
    out.labels = np.concatenate(
        [out.labels[:, 1:], np.full((B, 1), IGNORE_INDEX, np.int32)], axis=1
    )
    return out


def embed_spliced(
    embed_table: jnp.ndarray,
    visual_buffer: jnp.ndarray,
    token_ids: jnp.ndarray,
    visual_idx: jnp.ndarray,
    is_visual: jnp.ndarray,
) -> jnp.ndarray:
    """Device-side: build [B, T, H] inputs_embeds with one select-gather.

    embed_table: [V, H]; visual_buffer: [Q, H] (compressor output).

    The gathers read from replicated tables: without the constraints GSPMD
    lets the gather output inherit the fsdp/tp-sharded table layout and
    then full-rematerializes it to the batch-sharded activation spec
    ("[SPMD] Involuntary full rematerialization"). All-gathering the
    tables first (standard FSDP use-site gather) makes the downstream
    reshard a local slice.
    """
    with jax.named_scope("embed"):
        text = constrain(embed_table, None, None)[token_ids]
        vis = constrain(
            visual_buffer, None, None)[visual_idx].astype(text.dtype)
        out = jnp.where(is_visual[..., None], vis, text)
        return constrain(out, ("dp", "fsdp"), None, None)
