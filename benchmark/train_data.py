"""Seed-made SFT batches in the trainer's own layout (train/data.py
Examples -> collate), from a workload file's `batch` parameters.

Every row is filled to exactly `seq_len` positions — image rows carry
one image (1 visual token a patch) and text documents behind it, text
rows are packed documents — so a step's non-padding positions are
rows x seq_len whatever the seed, and every batch has one shape."""

from __future__ import annotations

import random

import numpy as np

from benchmark import traffic


def plan(params: dict, seed: int, n_batches: int) -> list[list[dict]]:
    """Row specs for n_batches (x grad_accum microbatches each): pure
    data, no arrays. Image sides and document lengths are quantiles of
    the stated distributions, shuffled by the seed."""
    rng = random.Random(seed)
    rows, accum = params["rows"], params.get("grad_accum_steps", 1)
    every = params.get("image_every", 2)
    n_rows = n_batches * accum * rows
    n_img = sum(1 for i in range(n_rows) if i % every == 0)
    sides = traffic.shuffled(
        traffic.quantile_values(params["image_side"], max(1, n_img)), rng)
    docs = traffic.shuffled(
        traffic.quantile_values(params["doc_tokens"], 64 * n_rows), rng)
    out, si, di = [], 0, 0
    for b in range(n_batches):
        batch = []
        for r in range(accum * rows):
            i = b * accum * rows + r
            side = 0
            if i % every == 0:
                side = sides[si % len(sides)]
                side -= side % params.get("patch", 14)
                si += 1
            text = params["seq_len"] - (side // params.get("patch", 14)) ** 2
            lens = []
            while sum(lens) < text:
                lens.append(min(docs[di % len(docs)], text - sum(lens)))
                di += 1
            batch.append({"side": side, "docs": lens,
                          "seed": seed * 1000003 + i})
        out.append(batch)
    return out


def tokens_of(batch_plan: list[dict], seq_len: int) -> int:
    return len(batch_plan) * seq_len


def build(batch_plan: list[dict], cfg, params: dict) -> dict:
    """One host batch (stacked per microbatch when the recipe
    accumulates), through the trainer's own collate."""
    from oryx_tpu.constants import (
        IGNORE_INDEX, IMAGE_TOKEN_INDEX, MODALITY_IMAGE,
    )
    from oryx_tpu.train import data as data_lib

    V = cfg.llm.vocab_size
    examples = []
    for row in batch_plan:
        rng = np.random.default_rng(row["seed"])
        ids, labels = [], []
        images = []
        if row["side"]:
            ids.append(np.asarray([IMAGE_TOKEN_INDEX]))
            labels.append(np.asarray([IGNORE_INDEX]))
            images = [traffic.block_image(
                rng, row["side"], params.get("block", 28))]
        for n in row["docs"]:
            doc = rng.integers(3, V, size=n)
            lab = np.full(n, IGNORE_INDEX, np.int64)
            lab[n // 2:] = doc[n // 2:]  # the answer half is supervised
            ids.append(doc)
            labels.append(lab)
        examples.append(data_lib.Example(
            np.concatenate(ids).astype(np.int64),
            np.concatenate(labels).astype(np.int64), images, MODALITY_IMAGE,
        ))
    kw = dict(patch_size=cfg.vision.patch_size,
              base_grid=cfg.vision.base_grid)
    accum = cfg.train.grad_accum_steps
    if accum > 1:
        return data_lib.collate_microbatches(examples, accum, **kw)
    return data_lib.collate(examples, **kw)
