"""Parameter/activation sharding rules (GSPMD under jit).

Reference parity: DeepSpeed ZeRO partitioning + NCCL collectives
(SURVEY.md §2b). Here sharding is declarative: every param leaf gets a
logical-axis tuple from path-pattern rules, logical axes map to mesh axes,
and XLA inserts the all-gathers / reduce-scatters (the "kernels" the
reference gets from DeepSpeed's C++ runtime).

  ZeRO-3 / FSDP  → mode="fsdp":  params sharded on the fsdp axis
  ZeRO-2         → mode="zero2": params replicated, optimizer state sharded
  DDP            → mode="ddp":   everything replicated over dp

Tensor parallelism composes orthogonally: head/mlp/vocab logical axes map
to "tp" whenever cfg.mesh.tp > 1.
"""

from __future__ import annotations

import fnmatch
import math
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

Params = dict[str, Any]

# path-pattern → logical axes (matched with fnmatch on "/"-joined paths;
# first match wins; patterns cover llm/vit/compressor subtrees).
LOGICAL_RULES: tuple[tuple[str, tuple[str | None, ...]], ...] = (
    # LLM (stacked layers: leading "layer" axis)
    ("llm/embed/weight", ("vocab", "embed")),
    ("llm/layers/*_norm/weight", ("layer", None)),
    ("llm/layers/q_proj/kernel", ("layer", "embed", "heads")),
    ("llm/layers/k_proj/kernel", ("layer", "embed", "heads")),
    ("llm/layers/v_proj/kernel", ("layer", "embed", "heads")),
    ("llm/layers/o_proj/kernel", ("layer", "heads", "embed")),
    ("llm/layers/*_proj/bias", ("layer", "heads")),
    ("llm/layers/gate_proj/kernel", ("layer", "embed", "mlp")),
    ("llm/layers/up_proj/kernel", ("layer", "embed", "mlp")),
    ("llm/layers/down_proj/kernel", ("layer", "mlp", "embed")),
    ("llm/final_norm/weight", (None,)),
    ("llm/lm_head/kernel", ("embed", "vocab")),
    # Vision tower
    ("vit/patch_embed/kernel", (None, "embed")),
    ("vit/patch_embed/bias", ("embed",)),
    # Replicated on purpose: interp_pos_embed gathers 4 corners per patch
    # and its backward scatter-adds into the table; with the table
    # embed-sharded GSPMD pays involuntary-remat reshards between the
    # data-sharded patch axis and the sharded table on every step. The
    # table is ~3.4 MB fp32 at SigLIP scale — replication is free.
    ("vit/pos_embed/weight", (None, None)),
    ("vit/layers/norm*/weight", ("layer", None)),
    ("vit/layers/norm*/bias", ("layer", None)),
    ("vit/layers/?_proj/kernel", ("layer", "embed", "heads")),
    ("vit/layers/o_proj/kernel", ("layer", "heads", "embed")),
    ("vit/layers/?_proj/bias", ("layer", "heads")),
    ("vit/layers/o_proj/bias", ("layer", "embed")),
    ("vit/layers/fc1/kernel", ("layer", "embed", "mlp")),
    ("vit/layers/fc1/bias", ("layer", "mlp")),
    ("vit/layers/fc2/kernel", ("layer", "mlp", "embed")),
    ("vit/layers/fc2/bias", ("layer", "embed")),
    ("vit/post_norm/*", (None,)),
    # Compressor (small; shard the projector matmuls only)
    ("compressor/projector/fc1/kernel", ("embed", "mlp")),
    ("compressor/projector/fc2/kernel", ("mlp", "embed")),
    ("compressor/*/kernel", (None, None)),
    ("compressor/*/bias", (None,)),
    ("compressor/*/weight", (None,)),
)

# logical axis → mesh axis (or tuple of axes), per mode.
def mesh_rules(mode: str) -> dict[str, str | tuple[str, ...] | None]:
    base = {"layer": None, "vocab": None, "heads": "tp", "mlp": "tp",
            "embed": None}
    if mode == "fsdp":
        # ZeRO-3 shards over the COMBINED fsdp x sp width: sequence-
        # parallel devices hold param shards too (ring attention only
        # shard_maps activations; weights are use-site all-gathered
        # across both axes). On an sp=1 mesh this is plain fsdp; on a
        # long-video mesh like fsdp=16 x sp=4 it keeps the full 64-way
        # state sharding — fsdp-only sharding there quadruples per-chip
        # state (the 34B/v5e-64 sp=4 compile runs out of memory without
        # this).
        base["embed"] = ("fsdp", "sp")
    elif mode not in ("zero2", "ddp"):
        raise ValueError(f"unknown sharding mode {mode!r}")
    return base


def _path_str(path) -> str:
    return "/".join(
        p.key if hasattr(p, "key") else str(getattr(p, "idx", p))
        for p in path
    )


def logical_axes(params: Params) -> Params:
    """Pytree of logical-axis tuples, same structure as params."""

    def lookup(path, leaf):
        s = _path_str(path)
        for pat, axes in LOGICAL_RULES:
            if fnmatch.fnmatch(s, pat):
                if len(axes) != leaf.ndim:
                    raise ValueError(
                        f"rule {pat} has {len(axes)} axes but {s} is "
                        f"rank {leaf.ndim}"
                    )
                return axes
        return (None,) * leaf.ndim  # replicate unknown leaves

    return jax.tree_util.tree_map_with_path(lookup, params)


def param_specs(params: Params, mode: str = "fsdp") -> Params:
    """Pytree of PartitionSpecs for params (also correct for same-shaped
    optimizer-state leaves)."""
    rules = mesh_rules(mode)

    def to_spec(axes):
        return P(*(rules.get(a) if a is not None else None for a in axes))

    return jax.tree.map(
        to_spec, logical_axes(params),
        is_leaf=lambda x: isinstance(x, tuple),
    )


def param_shardings(mesh: Mesh, params: Params, mode: str = "fsdp") -> Params:
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s), param_specs(params, mode),
        is_leaf=lambda x: isinstance(x, P),
    )


def shard_params(params: Params, shardings: Params) -> Params:
    """Place (or re-place) a param pytree onto the mesh."""
    return jax.tree.map(jax.device_put, params, shardings)


def batch_spec() -> P:
    """Activations/batch shard over the full data-parallel width."""
    return P(("dp", "fsdp"))


# Packed visual buffer fields of the training batch (ops/packing +
# splice.query_slots layout): their second axis is the packing axis.
VISUAL_BATCH_FIELDS = (
    "patches", "segment_ids", "pos_coords", "region_ids", "q_region_ids",
)


def batch_field_spec(name: str) -> P:
    """Per-field placement for a [accum, ...] training batch leaf.

    Packed visual buffers ride the FULL (dp, fsdp, sp) width — their
    packing axis is pure data to the vision tower, which pins its
    intermediates to the same spec (oryx_vit/compressor), so sequence-
    parallel devices take patch shards instead of idling through the
    visual encode. Row-shaped token-stream fields ride the data width
    only (the decoder's sp axis splits the SEQUENCE dim, not rows).
    Must stay in lockstep with the AOT memory proofs
    (scripts/estimate_7b_mesh_memory.py) — the proven program's
    argument placement is the trainer's.
    """
    if name in VISUAL_BATCH_FIELDS:
        return P(None, ("dp", "fsdp", "sp"))
    return P(None, ("dp", "fsdp"))


def cast_params_for_compute(params: Params, dtype, mode: str = "fsdp"):
    """Cast float param leaves to the compute dtype, each cast output
    CONSTRAINED to the param's own sharding spec.

    The constraint is the point: without it GSPMD propagates the
    use-site "replicated" requirement back THROUGH the convert, so
    ZeRO-3's weight all-gathers move fp32 and convert afterwards —
    verified in the compiled 7B/16-mesh HLO (all-gathers of
    f32[3584,18944], f32[3584,152064], …). Pinning the convert output to
    the param's sharded spec makes every use-site all-gather (and the
    backward's grad reduce-scatter at the same boundary) move
    compute-dtype bytes: half the ICI traffic and half the gather temps
    of fp32. Gradients convert back to fp32 at this boundary (cast VJP)
    and are accumulated fp32 in train/step.py.

    No-op sharding-wise off-mesh (constrain passes through); numerically
    identical to the per-use `.astype(x.dtype)` casts in the model,
    which become no-ops on the cast tree.
    """
    specs = param_specs(params, mode)  # THE spec derivation, not a copy
    leaves, treedef = jax.tree.flatten(params)
    spec_leaves = treedef.flatten_up_to(specs)
    out = []
    for w, spec in zip(leaves, spec_leaves):
        if jnp.issubdtype(w.dtype, jnp.floating) and w.dtype != dtype:
            # A PartitionSpec unpacks into constrain's per-dim axes form;
            # constrain drops axes absent from the ambient mesh and
            # no-ops entirely off-mesh.
            w = constrain(w.astype(dtype), *spec)
        out.append(w)
    return jax.tree.unflatten(treedef, out)


def paged_kv_spec(mesh, kv_pages=None) -> P | None:
    """PartitionSpec for a paged KV pool leaf
    ([layers, pages, page_size, kv_heads, head_dim]) on `mesh`:
    sharded along KV HEADS over the tp axis, replicated otherwise.

    Heads is the one KV axis tensor parallelism can split without
    changing any reduction: each tp shard holds its own heads' pages
    end to end (write, gather, attention), and the only cross-shard
    collective is o_proj's existing contraction over heads — so paged
    decode on a tp mesh stays bit-identical per head to the
    single-device path. The packed RAGGED path inherits this for free:
    `write_pages_packed` scatters and `ragged_paged_attention` gathers
    along the (unsharded) page axis with the head axis untouched, and
    the reference pins its gathered per-row view to the same head
    split (ops/paged_kv.py) so one fused mixed prefill+decode dispatch
    partitions by heads exactly like the split dispatches did. Pages/page_size must NOT shard: block tables
    index pages globally and a page-axis split would turn every
    table-addressed write into a cross-device scatter. Returns None
    (replicate) when the mesh has no tp axis or tp == 1 — an fsdp-only
    serving mesh gathers weights but keeps the pool whole.

    `kv_pages`: the pool to be placed, when the caller has it. A latent
    (MLA) pool has no head axis to split and is refused on a tp mesh,
    never replicated or split along its latent silently."""
    if mesh is None or "tp" not in mesh.axis_names:
        return None
    if mesh.shape["tp"] <= 1:
        return None
    if kv_pages is not None:
        from oryx_tpu.models.qwen2 import unsupported_for_latent
        from oryx_tpu.ops.paged_kv import is_latent_pool

        if is_latent_pool(kv_pages):
            raise ValueError(unsupported_for_latent(
                "a pool sharded over KV heads (tp > 1)"))
    return P(None, None, None, "tp", None)


def shard_paged_kv(kv_pages, mesh, *, num_kv_heads: int | None = None):
    """Place a paged KV pytree (qwen2.init_paged_kv_cache leaves) on
    `mesh` with heads sharded over tp (see `paged_kv_spec`). No-op —
    the same pytree back — when the mesh doesn't split heads or the
    head count doesn't divide (a 2-kv-head model on tp=4 serves with a
    replicated pool rather than failing)."""
    spec = paged_kv_spec(mesh, kv_pages)
    if spec is None:
        return kv_pages
    heads = num_kv_heads
    if heads is None:
        # A quantized pool carries 3-D per-page scale leaves next to
        # the 5-D code leaves; the head count lives on the 5-D ones.
        heads = next(
            leaf.shape[3]
            for leaf in jax.tree_util.tree_leaves(kv_pages)
            if getattr(leaf, "ndim", 0) == 5
        )
    if heads % mesh.shape["tp"]:
        return kv_pages
    sharding = NamedSharding(mesh, spec)
    replicated = NamedSharding(mesh, P())

    def place(a):
        # Only the [L, P, ps, Hk, D] code/value leaves split by heads;
        # per-page scale blocks ([L, P, ps]) have no head axis and
        # replicate — they are <1% of the pool's bytes.
        return jax.device_put(
            a, sharding if getattr(a, "ndim", 0) == 5 else replicated
        )

    return jax.tree.map(place, kv_pages)


def ambient_mesh():
    """The ambient abstract mesh (set via `jax.sharding.set_mesh`);
    empty when none is ambient."""
    return jax.sharding.get_abstract_mesh()


def mesh_scope(mesh):
    """Context manager making `mesh` ambient for `constrain`/jit calls.
    `mesh=None` is a no-op scope."""
    from contextlib import nullcontext

    if mesh is None:
        return nullcontext()
    return jax.sharding.set_mesh(mesh)


def constrain(x, *axes):
    """`with_sharding_constraint` iff a named mesh is ambient, else no-op.

    Model code annotates its main activations with this so GSPMD stops
    guessing intermediate shardings (guessing shows up as "[SPMD]
    Involuntary full rematerialization" resharding warnings). Single-device
    jit (bench, tests without a mesh) passes through untouched. Axis names
    absent from the ambient mesh are dropped (e.g. calling with "sp" on a
    dp/fsdp-only mesh).
    """
    mesh = ambient_mesh()
    if mesh is None or mesh.empty or not mesh.axis_names:
        return x

    def keep(a):
        if a is None:
            return None
        if isinstance(a, tuple):
            kept = tuple(x_ for x_ in a if x_ in mesh.axis_names)
            return kept or None
        return a if a in mesh.axis_names else None

    spec = P(*(keep(a) for a in axes))
    return jax.lax.with_sharding_constraint(x, spec)


def embed_shard_axes(mode: str | None) -> tuple[tuple[str, ...], int]:
    """(axes, n): the ambient mesh's axes that shard the `embed` logical
    axis under `mode`, in `mesh_rules`' order, and their total width.
    ((), 1) off-mesh, under a mode that leaves `embed` whole (`zero2`,
    `ddp`, None) and on a mesh that lacks those axes. The same filter
    `constrain` applies, so it names the layout the compute-dtype
    weights really have (`cast_params_for_compute`).

    Its one reader is the trainer's loss (train/loss.py): a product
    that CONTRACTS over `embed`, the vocabulary matrix's, is served by
    GSPMD with a gather of the matrix at its every use; the loss
    re-lays that matrix over these axes instead."""
    mesh = ambient_mesh()
    if mode is None or mesh is None or mesh.empty:
        return (), 1
    axes = tuple(
        a for a in mesh_rules(mode)["embed"] or ()
        if a in mesh.axis_names
    )
    return axes, math.prod(mesh.shape[a] for a in axes)


def opt_state_specs(opt_state, params: Params, mode: str = "fsdp"):
    """Shardings for optax state: leaves with a param-shaped counterpart
    inherit that param's spec; scalars/steps replicate.

    For ZeRO-2 the optimizer state shards over fsdp even though params
    replicate — pass mode="fsdp" here with mode="zero2" for params.
    """
    specs = param_specs(params, mode)
    flat_specs = {
        tuple(str(p) for p in path): s
        for path, s in jax.tree_util.tree_flatten_with_path(
            specs, is_leaf=lambda x: isinstance(x, P)
        )[0]
    }

    def match(path, leaf):
        suffix = tuple(str(p) for p in path)
        for ppath, spec in flat_specs.items():
            if suffix[-len(ppath):] == ppath:
                if hasattr(leaf, "ndim") and leaf.ndim == len(spec):
                    return spec
        return P()

    return jax.tree_util.tree_map_with_path(match, opt_state)
