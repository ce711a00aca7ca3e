"""Training state + jitted SFT step (single program over the mesh).

Reference parity: the HF Trainer + DeepSpeed step loop (SURVEY.md §3.1):
forward (ViT → compressor → splice → decoder), masked CE, backward,
AdamW — but compiled as ONE XLA program per microbatch group. Gradient
reduction, ZeRO sharding collectives and the fused optimizer all come out
of GSPMD given the shardings from parallel/sharding.py; remat
(gradient_checkpointing) is applied per scan-block inside the model.

Grad accumulation: a `lax.scan` over leading-axis microbatches, averaging
losses/grads in fp32 — equivalent to DeepSpeed's accumulate-then-step with
no Python-side loop.

What is differentiated: the leaves the recipe trains, and nothing else.
`trainable(params, cfg.train.tune)` is `params` with every leaf that
optimizer.trainable_mask freezes replaced by None; the loss takes that
tree as its argument and reads the frozen leaves as constants. So under
`tune="lora"` no weight gradient of a base kernel, of the head or of the
embedding is traced (the activation gradients through them stay), and
with no differentiated leaf upstream of the compressor the frozen vision
tower has no backward at all; `projector_only` and `no_vision` follow by
the same rule, and `tune="full"` (nothing frozen) traces the program it
always did. The gradient tree, the accumulators of the microbatch scan,
`grad_norm`, the non-finite guard and the numerics probes are over those
leaves: `grad_norm` is the norm of what is trained (what HF Trainer logs
under PEFT), the norm the optimizer's clip already saw. Frozen leaves
leave the step as the buffers they came in as.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
import optax

from oryx_tpu.config import OryxConfig
from oryx_tpu.models import oryx
from oryx_tpu.train.loss import chunked_causal_lm_loss
from oryx_tpu.train.optimizer import trainable_mask

Params = dict[str, Any]

BATCH_FIELDS = (
    "patches", "segment_ids", "pos_coords", "region_ids", "q_region_ids",
    "token_ids", "visual_idx", "is_visual", "attn_mask", "positions",
    "labels",
)


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class TrainState:
    step: jnp.ndarray
    params: Params
    opt_state: Any


def init_state(
    cfg: OryxConfig, tx: optax.GradientTransformation, key: jax.Array
) -> TrainState:
    params = oryx.init_params(cfg, key)
    return TrainState(
        step=jnp.zeros((), jnp.int32),
        params=params,
        opt_state=tx.init(params),
    )


def trainable(params: Params, tune: str) -> Params:
    """`params` with every leaf `tune` freezes replaced by None (an empty
    subtree): the tree train_step_fn differentiates."""
    return jax.tree.map(
        lambda m, p: p if m else None, trainable_mask(params, tune), params
    )


def _over(params: Params, leaves: Params) -> Params:
    """A trainable()-shaped tree laid back over `params`: where it holds
    None the result holds `params`' own leaf."""
    return jax.tree.map(
        lambda p, new: p if new is None else new, params, leaves
    )


def microbatch_loss(
    params: Params, cfg: OryxConfig, mb: dict[str, jnp.ndarray],
    sharding_mode: str = "fsdp",
    numerics: bool = False,
) -> tuple[jnp.ndarray, dict[str, jnp.ndarray]]:
    # One sharded-constrained cast of the whole tree to the compute
    # dtype (sharding.cast_params_for_compute): ZeRO-3 use-site
    # all-gathers and the grad reduce-scatter then ride bf16, not fp32
    # — half the ICI bytes and gather temps. The per-use .astype casts
    # inside the model become no-ops; grads convert back to fp32 here.
    compute_dtype = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[
        cfg.dtype
    ]
    if compute_dtype != jnp.float32:
        from oryx_tpu.parallel.sharding import cast_params_for_compute

        params = cast_params_for_compute(
            params, compute_dtype, sharding_mode
        )
    hidden = oryx.forward(
        params, cfg,
        patches=mb["patches"], segment_ids=mb["segment_ids"],
        pos_coords=mb["pos_coords"], region_ids=mb["region_ids"],
        q_region_ids=mb["q_region_ids"],
        token_ids=mb["token_ids"], visual_idx=mb["visual_idx"],
        is_visual=mb["is_visual"], attn_mask=mb["attn_mask"],
        positions=mb["positions"],
        text_segment_ids=mb.get("text_segment_ids"),
        remat=cfg.train.remat_policy if cfg.train.remat else "none",
        compute_dtype=compute_dtype,
        return_hidden=True,
    )
    llm_p = params["llm"]
    if cfg.llm.tie_word_embeddings:
        w, transpose = llm_p["embed"]["weight"], True
    else:
        w, transpose = llm_p["lm_head"]["kernel"], False
    with jax.named_scope("loss"):
        loss, metrics = chunked_causal_lm_loss(
            hidden, w, mb["labels"],
            chunk=cfg.train.loss_chunk, transpose=transpose,
            sharding_mode=sharding_mode,
        )
    if numerics:
        # Activation absmax (the final hidden state — the residual
        # stream every layer feeds): an fp16/bf16 range excursion shows
        # here before the loss goes non-finite.
        from oryx_tpu.utils import numerics as numerics_lib

        metrics = dict(metrics, act_absmax=numerics_lib.tree_absmax(hidden))
    return loss, metrics


def train_step_fn(
    state: TrainState,
    batch: dict[str, jnp.ndarray],
    cfg: OryxConfig,
    tx: optax.GradientTransformation,
    sharding_mode: str = "fsdp",
    numerics: bool = False,
) -> tuple[TrainState, dict[str, jnp.ndarray]]:
    """One optimizer step over `accum` microbatches (unjitted body).

    numerics=True (STATIC — the Trainer samples it every
    `--numerics-every` steps, so at most two stable compiled programs
    exist) adds the utils/numerics.py probes to the metrics dict:
    `act_absmax` (final hidden state), `grad_absmax` (the gradients of
    the trainable leaves), `param_absmax`, and `grad_layer_absmax` ([L]
    over the stacked decoder layers' trainable leaves — the "which layer
    is exploding" vector; absent when the recipe trains none of them).
    Params/opt-state updates are bit-identical either way (the probes
    only read values the step already computed).

    batch: each leaf has leading [accum, ...] microbatch axis (accum == 1
    for plain steps); visual buffers are packed per-microbatch.

    sharding_mode: the parallel/sharding.py mode the params are placed
    under — used to constrain the compute-dtype cast of the params (see
    microbatch_loss) so weight all-gathers ride bf16, and read by the
    loss (train/loss.chunked_causal_lm_loss): under an ambient mesh whose
    axes shard the vocabulary matrix's `embed` dimension in this mode
    ("fsdp", over the fsdp x sp width n), the chunk scan runs under the
    scope `loss/vocab_parallel` with the matrix re-laid [H, V/n] once a
    microbatch, instead of GSPMD's gather of the whole matrix twice a
    chunk; `zero2` / `ddp` (the matrix is whole on every device), a
    vocabulary or batch the mesh does not divide, and T <= loss_chunk
    run the one-device loss under plain `loss`. Harmless when it merely
    mismatches the actual placement off-mesh (constrain no-ops, the
    loss sees no mesh).

    tx: optimizer.make_optimizer(cfg.train, ...)'s — its freeze mask is
    the one this step differentiates by, so it takes a gradient tree that
    holds None at the frozen leaves.

    Callers with explicit state shardings (Trainer) jit this with
    out_shardings pinned to the input state's shardings — otherwise GSPMD
    may re-shard updated params to the optimizer-state sharding (e.g.
    ZeRO-2's replicated params silently become fsdp-sharded after step 1).
    """
    train_p = trainable(state.params, cfg.train.tune)
    grad_fn = jax.value_and_grad(
        lambda p, c, m: microbatch_loss(
            _over(state.params, p), c, m, sharding_mode, numerics
        ),
        has_aux=True,
    )
    accum = jax.tree.leaves(batch)[0].shape[0]
    act_absmax = None

    # named_scope: phase names land in the XLA op metadata, so a
    # profiler capture can attribute device time to forward/backward
    # vs optimizer — the device-side half of the trainer's host-side
    # phases (oryx.train.*, in the same capture).
    if accum == 1:
        # No accumulation: skip the scan and its fp32 zeros buffer (a full
        # param-sized temp — ~17 GB/device for 34B on an 8-way mesh).
        with jax.named_scope("forward_backward"):
            (loss_sum, metrics), grads = grad_fn(
                train_p, cfg, jax.tree.map(lambda x: x[0], batch)
            )
            grads = jax.tree.map(lambda g: g.astype(jnp.float32), grads)
        ntok = metrics["num_tokens"]
        if numerics:
            act_absmax = metrics["act_absmax"]
    else:
        def one_micro(carry, mb):
            grads_acc, loss_acc, ntok_acc = carry
            (loss, metrics), grads = grad_fn(train_p, cfg, mb)
            grads_acc = jax.tree.map(
                lambda a, g: a + g.astype(jnp.float32), grads_acc, grads
            )
            return (
                grads_acc, loss_acc + loss, ntok_acc + metrics["num_tokens"]
            ), metrics

        with jax.named_scope("forward_backward_accum"):
            zeros = jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), train_p
            )
            (grads, loss_sum, ntok), micro_metrics = jax.lax.scan(
                one_micro,
                (zeros, jnp.zeros((), jnp.float32),
                 jnp.zeros((), jnp.int32)),
                batch,
            )
            grads = jax.tree.map(lambda g: g / accum, grads)
        if numerics:
            # The scan stacked each microbatch's probe: the step's
            # activation absmax is the max across them.
            act_absmax = jnp.max(micro_metrics["act_absmax"])

    with jax.named_scope("optimizer_update"):
        updates, opt_state = tx.update(grads, state.opt_state, state.params)
        new_p = optax.apply_updates(train_p, updates)
        gnorm = optax.global_norm(grads)
    metrics = {
        "loss": loss_sum / accum,
        "grad_norm": gnorm,
        "num_tokens": ntok,
    }
    if numerics:
        from oryx_tpu.utils import numerics as numerics_lib

        metrics["act_absmax"] = act_absmax
        metrics["grad_absmax"] = numerics_lib.tree_absmax(grads)
        metrics["param_absmax"] = numerics_lib.tree_absmax(state.params)
        layer_absmax = numerics_lib.stacked_layer_absmax(
            grads.get("llm", {}).get("layers", {})
        )
        if layer_absmax is not None:
            metrics["grad_layer_absmax"] = layer_absmax
    if cfg.train.skip_nonfinite_steps:
        # Anomalous-step guard (DeepSpeed's skip-on-overflow analog for
        # bf16: a poisoned batch or data-driven spike must not write NaNs
        # into params/moments). The update is computed regardless and
        # SELECTED against — a lax.cond would re-shard both branches'
        # state under GSPMD for no real saving, while the select fuses.
        with jax.named_scope("nonfinite_guard"):
            ok = jnp.isfinite(loss_sum) & jnp.isfinite(gnorm)
            new_p = jax.tree.map(
                lambda new, old: jnp.where(ok, new, old), new_p, train_p
            )
            opt_state = jax.tree.map(
                lambda new, old: (
                    jnp.where(ok, new, old) if hasattr(new, "dtype")
                    else new
                ),
                opt_state, state.opt_state,
            )
            metrics["skipped"] = (~ok).astype(jnp.int32)
    return (
        TrainState(
            step=state.step + 1, params=_over(state.params, new_p),
            opt_state=opt_state,
        ),
        metrics,
    )


train_step = partial(
    jax.jit, static_argnames=("cfg", "tx", "sharding_mode", "numerics"),
    donate_argnames=("state",),
)(train_step_fn)
