"""The comparisons that decide `correct`, kept with the benchmark."""

from __future__ import annotations

import numpy as np

# Logits of the served path (bf16 weights and activations, Pallas
# kernels, paged cache) against the float32 reference agree to bf16
# rounding accumulated over the stack. The bound is a share of the
# reference's largest |logit| (floored at 1): 5 %, as the bring-up
# smoke used between Pallas and XLA attention (observed there 0.119 of
# 6.41 = 1.9 %). Computing in a lower precision than bf16 (an int8
# matmul, fp8 KV) moves logits by more than that and fails; so does a
# dropped bias, a wrong rope layout or a wrong GQA grouping (each is
# O(1) of the logit scale).
LOGIT_REL_TOL = 5e-2
# Train: step-0 loss against forward-only losses, as a share of it.
LOSS_REL_TOL = 1e-2


def serve_logit_check(params, cfg, seed: int, *, page_size: int,
                      prompt_tokens: int = 256, tail: int = 8) -> dict:
    """Logits of the last `tail` positions of one seeded prompt through
    the served path (paged_prefill into a private paged pool, then one
    audit_decode_step per remaining token, the configuration's own
    attn_impl) against the plain reference's full forward."""
    import jax
    import jax.numpy as jnp

    from oryx_tpu.models import generate as generate_lib
    from oryx_tpu.models import oryx, qwen2
    from oryx_tpu.serve import audit

    from benchmark.reference import qwen2_ref

    rng = np.random.default_rng(seed)
    ids = rng.integers(3, cfg.llm.vocab_size, prompt_tokens).astype(np.int32)
    ref = np.asarray(qwen2_ref.logits_tail(params["llm"], cfg.llm, ids, tail))

    dtype = oryx.compute_dtype(cfg)
    maxp = -(-(prompt_tokens + page_size) // page_size)
    head = prompt_tokens - tail
    kv = qwen2.init_paged_kv_cache(cfg.llm, maxp, page_size, dtype=dtype)
    emb = params["llm"]["embed"]["weight"][jnp.asarray(ids)][None]
    emb = emb.at[:, head:].set(0).astype(dtype)  # right padding
    bt = jnp.arange(maxp, dtype=jnp.int32)[None]
    one = dict(
        temperature=jnp.zeros((1,), jnp.float32),
        top_p=jnp.ones((1,), jnp.float32),
        top_k=jnp.zeros((1,), jnp.int32),
    )
    keys = jax.random.split(jax.random.key(0), 1)
    kv, _, keys = generate_lib.paged_prefill(
        params["llm"], cfg.llm, emb, jnp.asarray([head], jnp.int32), bt, kv,
        jnp.asarray([0], jnp.int32), keys, *one.values(),
        attn_impl=cfg.attn_impl, compute_dtype=dtype,
    )
    rows = []
    for i in range(tail):
        kv, _, row, keys = audit.audit_decode_step(
            params["llm"], cfg.llm, kv, bt,
            jnp.asarray(ids[head + i: head + i + 1]),
            jnp.asarray([head + i], jnp.int32), keys, **one,
            attn_impl=cfg.attn_impl, compute_dtype=dtype,
        )
        rows.append(np.asarray(row[0]))
    got = np.stack(rows)
    diff = float(np.max(np.abs(got - ref)))
    absmax = float(np.max(np.abs(ref)))
    tol = LOGIT_REL_TOL * max(1.0, absmax)
    return {
        "ok": bool(np.isfinite(diff) and diff <= tol),
        "logit_max_abs_diff": diff, "ref_absmax": absmax, "tol": tol,
        "argmax_agree": int(np.sum(got.argmax(-1) == ref.argmax(-1))),
        "positions": tail,
    }


def train_reference_check(params, cfg, batch: dict, *, ignore_index: int,
                          rows: int = 1) -> dict:
    """The program's forward-only loss (train/step.microbatch_loss: bf16,
    the configuration's kernels, remat, chunked loss) against the plain
    float32 reference, on the text-only rows of `batch` (up to `rows` of
    them): both are the mean next-token NLL over the same supervised
    positions, the program's with every other row's labels masked out.
    The reference has the decoder only; rows that carry an image need a
    reference vision tower, which the benchmark does not have yet. Also
    returns the program's forward-only loss of the whole batch, which
    the trainer's step-0 loss is held to."""
    import jax
    import jax.numpy as jnp

    from oryx_tpu.train import step as step_lib

    from benchmark.reference import qwen2_ref

    accum = cfg.train.grad_accum_steps
    mb = {k: np.asarray(v[0] if accum > 1 else v) for k, v in batch.items()}
    is_vis = mb["is_visual"].any(axis=1)
    text_rows = [int(i) for i in np.nonzero(~is_vis)[0]][:rows]
    loss_fn = jax.jit(step_lib.microbatch_loss, static_argnums=(1,))
    dev = {k: jnp.asarray(v) for k, v in mb.items()}
    # The step reports the mean of its microbatches' losses.
    full = float(np.mean([
        float(loss_fn(params, cfg, {
            k: jnp.asarray(v[i] if accum > 1 else v)
            for k, v in batch.items()
        })[0])
        for i in range(accum)
    ]))
    out = {"program_loss_full": full, "text_rows": text_rows, "ok": True}
    if not text_rows:
        return out
    masked = mb["labels"].copy()
    keep = np.zeros(masked.shape[0], bool)
    keep[text_rows] = True
    masked[~keep] = ignore_index
    prog = float(loss_fn(params, cfg, {**dev, "labels": jnp.asarray(masked)})[0])
    total, count = 0.0, 0
    for r in text_rows:
        t, c = qwen2_ref.causal_lm_nll(
            params["llm"], cfg.llm, mb["token_ids"][r], mb["labels"][r],
            mb["positions"][r], mb["attn_mask"][r] > 0,
            ignore_index=ignore_index,
        )
        total, count = total + t, count + c
    ref = total / max(1, count)
    out.update(
        program_loss_text=prog, reference_loss_text=ref,
        abs_diff=abs(prog - ref), tol=LOSS_REL_TOL * abs(ref),
        ok=bool(np.isfinite(prog) and abs(prog - ref) <= LOSS_REL_TOL * abs(ref)),
    )
    return out
