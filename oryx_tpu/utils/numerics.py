"""Numerics sentinels: tensor-stat probes for serving and training.

The observability planes so far watch *where time and memory go* (PR 12
traces, PR 13 page/device observatory); this module watches *what the
model computes*. Two halves:

  * **In-dispatch logit probes** (`init_logit_stats` /
    `accumulate_logit_stats` / `finalize_logit_stats`): a tiny
    fixed-shape accumulator that rides INSIDE an existing jitted engine
    step (models/generate.paged_decode_chunk / paged_ragged_step under
    `numerics=True`) — finite fraction, absmax, rms, softmax entropy,
    top-1 margin over the step's live decode rows. The stats are a [6]
    float32 extra OUTPUT of the same dispatch: zero additional
    dispatches, token streams untouched (the probe reads the logits the
    sampler already computed), and the `numerics` flag is a STATIC
    argument, so arming it adds exactly one more stable compiled
    program per shape class — recompile-watchdog-clean.
  * **Tree probes for the trainer** (`tree_absmax` /
    `stacked_layer_absmax`): grad/activation absmax — over the tree
    and per-stacked-layer; the gradient tree is the trainable leaves'
    (train/step.py) — computed inside `train_step_fn` under the same
    static `numerics` flag and returned through the step's metrics
    dict.

Both feed the raw-named ``oryx_numerics_*`` metric families (the same
series names from the train and serve registries, like
``oryx_anomaly_total``) and the utils/anomaly.py sentinels
(`entropy_collapse`, `absmax_explosion`): a logits distribution
collapsing to a delta function or an activation/grad blowing up pages
the moment it happens instead of surfacing as a bad eval days later.

Dependency-light: jax + numpy only, no engine imports (the scheduler
and trainer import THIS module, never the reverse).
"""

from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

# Order of the scalar slots in the [6] accumulator / the finalized
# dict. `finite_frac` is a fraction in [0, 1]; `absmax` is a max (not a
# mean) across every observed row; the rest are per-row means.
NUMERICS_STAT_KEYS = (
    "rows", "finite_frac", "absmax", "rms", "entropy", "top1_margin",
)

# The raw-named gauge families the probes feed (one list so serve,
# train, docs and the CI family assertions agree; the `oryx_` prefix
# is part of the name — raw_name=True, shared across registries).
NUMERICS_GAUGES = (
    "oryx_numerics_logits_finite_frac",
    "oryx_numerics_logits_absmax",
    "oryx_numerics_logits_rms",
    "oryx_numerics_logits_entropy",
    "oryx_numerics_logits_top1_margin",
)


def init_logit_stats() -> jnp.ndarray:
    """Fresh accumulator: [rows, finite_sum, absmax, rms_sum,
    entropy_sum, margin_sum] in float32 (sums are over rows; the
    finalizer divides)."""
    return jnp.zeros((len(NUMERICS_STAT_KEYS),), jnp.float32)


def accumulate_logit_stats(
    acc: jnp.ndarray,  # [6] float32 (init_logit_stats)
    logits: jnp.ndarray,  # [S, V]
    live: jnp.ndarray,  # [S] bool — rows that really decoded this step
) -> jnp.ndarray:
    """Fold one step's live-row logit stats into the accumulator
    (traced; rides inside the engine step's scan). Dead rows contribute
    nothing — their logits are frozen filler and would poison every
    mean. Non-finite values are sanitized to 0 INSIDE each reduction so
    one NaN row reports a finite_frac < 1 instead of NaN-ing the whole
    accumulator (the probe must survive the exact corruption it
    exists to detect)."""
    x = logits.astype(jnp.float32)
    finite = jnp.isfinite(x)
    safe = jnp.where(finite, x, 0.0)
    w = live.astype(jnp.float32)  # [S]
    rows = jnp.sum(w)
    finite_frac = jnp.mean(finite.astype(jnp.float32), axis=-1)  # [S]
    absmax_row = jnp.max(jnp.abs(safe), axis=-1)  # [S]
    rms_row = jnp.sqrt(jnp.mean(safe * safe, axis=-1))  # [S]
    # Entropy/margin on the sanitized logits: the softmax of a NaN row
    # is meaningless either way, and finite_frac already flags it.
    p = jax.nn.softmax(safe, axis=-1)
    ent_row = -jnp.sum(
        p * jnp.log(jnp.maximum(p, jnp.finfo(jnp.float32).tiny)), axis=-1
    )
    top2 = jax.lax.top_k(safe, 2)[0]  # [S, 2]
    margin_row = top2[:, 0] - top2[:, 1]
    return acc + jnp.stack([
        rows,
        jnp.sum(w * finite_frac),
        # absmax is a MAX, not a sum: keep the running max in its slot
        # (acc slot 2 minus itself plus the new max = new max).
        jnp.maximum(jnp.max(jnp.where(live, absmax_row, 0.0)), acc[2])
        - acc[2],
        jnp.sum(w * rms_row),
        jnp.sum(w * ent_row),
        jnp.sum(w * margin_row),
    ])


def finalize_logit_stats(acc: Any) -> dict[str, float] | None:
    """Host-side: the accumulator (device or numpy) -> a stat dict
    keyed by NUMERICS_STAT_KEYS. None when no live row was observed
    (a prefill-only or idle dispatch has nothing to report)."""
    a = np.asarray(acc, np.float64)
    rows = float(a[0])
    if rows <= 0:
        return None
    return {
        "rows": rows,
        "finite_frac": float(a[1] / rows),
        "absmax": float(a[2]),
        "rms": float(a[3] / rows),
        "entropy": float(a[4] / rows),
        "top1_margin": float(a[5] / rows),
    }


# ---------------------------------------------------------------------------
# Tree probes (trainer grads / activations)
# ---------------------------------------------------------------------------


def tree_absmax(tree: Any) -> jnp.ndarray:
    """Scalar absmax over every leaf of a pytree (traced — rides inside
    the jitted train step). Empty tree -> 0."""
    leaves = [
        jnp.max(jnp.abs(leaf.astype(jnp.float32)))
        for leaf in jax.tree_util.tree_leaves(tree)
        if hasattr(leaf, "dtype") and jnp.issubdtype(leaf.dtype, jnp.floating)
    ]
    if not leaves:
        return jnp.zeros((), jnp.float32)
    return jnp.max(jnp.stack(leaves))


def stacked_layer_absmax(layers: Any) -> jnp.ndarray | None:
    """Per-layer absmax over a STACKED-layer subtree (every leaf
    carries the [L, ...] leading scan axis, the qwen2 decoder layout):
    reduces each leaf over its non-leading axes and maxes across
    leaves -> [L] float32. None when the subtree has no stacked float
    leaves (e.g. LoRA-frozen trees with scalars mixed in)."""
    per_leaf = []
    L = None
    for leaf in jax.tree_util.tree_leaves(layers):
        if not (
            hasattr(leaf, "dtype")
            and jnp.issubdtype(leaf.dtype, jnp.floating)
            and getattr(leaf, "ndim", 0) >= 2
        ):
            continue
        if L is None:
            L = leaf.shape[0]
        if leaf.shape[0] != L:
            continue  # not on the shared stacked axis
        x = jnp.abs(leaf.astype(jnp.float32))
        per_leaf.append(jnp.max(x.reshape(L, -1), axis=-1))
    if not per_leaf:
        return None
    return jnp.max(jnp.stack(per_leaf), axis=0)


def is_finite(value: Any) -> bool:
    try:
        return math.isfinite(float(value))
    except (TypeError, ValueError):
        return False
