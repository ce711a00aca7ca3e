"""Ring attention — sequence/context parallelism over the `sp` mesh axis.

Long-context scaling beyond one chip's HBM (SURVEY.md §5 "Long-context"):
the sequence is sharded over `sp`; each device keeps its local Q block
resident and K/V blocks rotate around the ring via `lax.ppermute` (ICI
neighbor exchange), merging each visiting block into an online-softmax
accumulator. Peak memory is O(T/sp) per device while computing exact
(non-approximate) attention over the full sequence — the XLA-collective
equivalent of Ring Attention (Liu et al., 2023), built with shard_map so
the collective schedule is explicit.

Masking model matches ops/attention.py: causal on absolute positions
(positions travel with the K/V blocks), plus explicit kv validity.
Compute follows the same policy: fp32 logits/softmax state, input-dtype
probs·V matmuls.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.sharding import Mesh, PartitionSpec as P
from jax import shard_map

NEG = -0.7 * float(jnp.finfo(jnp.float32).max)


def _chunk_logits(q, k, qpos, kpos, kvalid, *, causal, scale):
    """[B,Tq,Hk,G,D] x [B,Tc,Hk,D] → masked fp32 logits [B,Hk,G,Tq,Tc]."""
    logits = jnp.einsum(
        "bqhgd,bkhd->bhgqk", q, k, preferred_element_type=jnp.float32
    ) * scale
    mask = kvalid[:, None, :].astype(bool)  # [B, 1, Tc]
    if causal:
        mask = jnp.logical_and(
            mask, qpos[:, :, None] >= kpos[:, None, :]
        )
    return jnp.where(mask[:, None, None, :, :], logits, NEG)


def ring_attention_shard(
    q, k, v, q_pos, kv_pos, kv_valid,
    *,
    axis_name: str,
    causal: bool = False,
    scale: float | None = None,
    impl: str = "xla",
):
    """Per-shard body (call inside shard_map over `axis_name`).

    q/k/v: local blocks [B, Tl, H*, D] (GQA: Hq % Hk == 0);
    q_pos/kv_pos: absolute positions [B, Tl]; kv_valid: [B, Tl] int.
    Returns [B, Tl, Hq, D] in q.dtype — exact attention over the global
    sequence.

    impl: "xla" materializes [Tl, Tc] fp32 logits per visiting block;
    "flash" runs the Pallas flash kernel per block and merges the
    per-block normalized outputs via their logsumexp — O(tile) memory,
    which is what makes Tl in the tens-of-thousands feasible.
    """
    if impl == "flash":
        return _ring_shard_flash(
            q, k, v, q_pos, kv_pos, kv_valid,
            axis_name=axis_name, causal=causal, scale=scale,
        )
    B, Tl, Hq, D = q.shape
    _, _, Hk, _ = k.shape
    G = Hq // Hk
    if scale is None:
        scale = D**-0.5
    n = jax.lax.psum(1, axis_name)
    perm = [(j, (j + 1) % n) for j in range(n)]

    qg = q.reshape(B, Tl, Hk, G, D)
    acc = jnp.zeros((B, Hk, G, Tl, D), jnp.float32)
    m = jnp.full((B, Hk, G, Tl, 1), NEG, jnp.float32)
    l = jnp.zeros((B, Hk, G, Tl, 1), jnp.float32)

    def merge(acc, m, l, k_cur, v_cur, kpos_cur, kvalid_cur):
        s = _chunk_logits(
            qg, k_cur, q_pos, kpos_cur, kvalid_cur, causal=causal,
            scale=scale,
        )  # [B, Hk, G, Tl, Tc]
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new)
        l = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
        pv = jnp.einsum(
            "bhgqk,bkhd->bhgqd", p.astype(v_cur.dtype), v_cur,
            preferred_element_type=jnp.float32,
        )
        return acc * alpha + pv, m_new, l

    def body(_, carry):
        acc, m, l, k_cur, v_cur, kpos_cur, kvalid_cur = carry
        if causal:
            # Skip blocks that are entirely in this shard's causal future
            # (every kv position > every local q position): with causal
            # sharding, about half the ring steps merge nothing — cond
            # saves the logits+softmax compute (the ppermute still runs).
            live = jnp.min(kpos_cur) <= jnp.max(q_pos)
            acc, m, l = jax.lax.cond(
                live, merge, lambda a, mm, ll, *_: (a, mm, ll),
                acc, m, l, k_cur, v_cur, kpos_cur, kvalid_cur,
            )
        else:
            acc, m, l = merge(acc, m, l, k_cur, v_cur, kpos_cur, kvalid_cur)
        # Rotate the K/V block (and its metadata) one step around the ring.
        k_cur, v_cur, kpos_cur, kvalid_cur = jax.tree.map(
            lambda x: jax.lax.ppermute(x, axis_name, perm),
            (k_cur, v_cur, kpos_cur, kvalid_cur),
        )
        return acc, m, l, k_cur, v_cur, kpos_cur, kvalid_cur

    acc, m, l, *_ = jax.lax.fori_loop(
        0, n, body, (acc, m, l, k, v, kv_pos, kv_valid)
    )
    out = acc / jnp.where(l == 0.0, 1.0, l)
    out = jnp.moveaxis(out, 3, 1).reshape(B, Tl, Hq, D)  # [B,Tl,Hk,G,D]
    # Tag for the "attn"/"attn_qkv" remat policies (utils/remat.py): the
    # saved output spares the backward a full second ring pass for the
    # downstream (o_proj/MLP) gradients.
    return checkpoint_name(out.astype(q.dtype), "flash_out")


def _ring_shard_flash(
    q, k, v, q_pos, kv_pos, kv_valid,
    *,
    axis_name: str,
    causal: bool,
    scale: float | None,
):
    if scale is None:
        scale = q.shape[-1] ** -0.5
    return _ring_flash_vjp(
        q, k, v, q_pos, kv_pos, kv_valid, axis_name, causal, float(scale)
    )


def _ring_flash_forward(
    q, k, v, q_pos, kv_pos, kv_valid, axis_name, causal, scale
):
    """Flash-inner ring forward: per visiting block, run the Pallas kernel
    (fp32 softmax inside, O(tile) memory) and fold its normalized output
    into a running LSE-weighted sum:

        LSE' = logaddexp(LSE, lse_i)
        out' = out·exp(LSE − LSE') + out_i·exp(lse_i − LSE')

    Returns (out [B,Tl,Hq,D] in q.dtype, global lse [B,Hq,Tl] fp32). The
    kernel marks fully-masked rows with lse = +FLT_MAX (a backward-pass
    convention); those are re-mapped to the NEG sentinel so empty blocks
    merge with weight 0 (NEG-NEG arithmetic stays finite, no NaNs).
    """
    from oryx_tpu.ops.pallas.flash_attention import _flash_attention_impl

    B, Tl, Hq, D = q.shape
    n = jax.lax.psum(1, axis_name)
    perm = [(j, (j + 1) % n) for j in range(n)]

    out = jnp.zeros((B, Tl, Hq, D), jnp.float32)
    lse = jnp.full((B, Hq, Tl), NEG, jnp.float32)

    def merge(out, lse, k_cur, v_cur, kpos_cur, kvalid_cur):
        o_i, lse_i = _flash_attention_impl(
            q, k_cur, v_cur, q_pos, kpos_cur, None, None, kvalid_cur,
            causal, scale, with_lse=True,
        )
        lse_i = lse_i[:, :, :Tl]  # kernel pads to block multiples
        lse_i = jnp.where(lse_i > -0.5 * NEG, NEG, lse_i)  # empty rows
        lse_new = jnp.logaddexp(lse, lse_i)
        w_old = jnp.exp(lse - lse_new)  # [B, Hq, Tl]
        w_new = jnp.exp(lse_i - lse_new)
        wo = jnp.moveaxis(w_old, 1, 2)[..., None]  # [B, Tl, Hq, 1]
        wn = jnp.moveaxis(w_new, 1, 2)[..., None]
        out = out * wo + o_i.astype(jnp.float32) * wn
        return out, lse_new

    def body(_, carry):
        out, lse, k_cur, v_cur, kpos_cur, kvalid_cur = carry
        if causal:
            live = jnp.min(kpos_cur) <= jnp.max(q_pos)
            out, lse = jax.lax.cond(
                live, merge, lambda o, s, *_: (o, s),
                out, lse, k_cur, v_cur, kpos_cur, kvalid_cur,
            )
        else:
            out, lse = merge(out, lse, k_cur, v_cur, kpos_cur, kvalid_cur)
        k_cur, v_cur, kpos_cur, kvalid_cur = jax.tree.map(
            lambda x: jax.lax.ppermute(x, axis_name, perm),
            (k_cur, v_cur, kpos_cur, kvalid_cur),
        )
        return out, lse, k_cur, v_cur, kpos_cur, kvalid_cur

    out, lse, *_ = jax.lax.fori_loop(
        0, n, body, (out, lse, k, v, kv_pos, kv_valid)
    )
    # Same tags as the Pallas kernel: with remat_policy="attn"/"attn_qkv"
    # these are saved, so the checkpointed backward reuses the ring
    # backward's residuals instead of re-running the forward ring pass.
    out = checkpoint_name(out.astype(q.dtype), "flash_out")
    lse = checkpoint_name(lse, "flash_lse")
    return out, lse


@partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8))
def _ring_flash_vjp(
    q, k, v, q_pos, kv_pos, kv_valid, axis_name, causal, scale
):
    return _ring_flash_forward(
        q, k, v, q_pos, kv_pos, kv_valid, axis_name, causal, scale
    )[0]


def _ring_flash_fwd(q, k, v, q_pos, kv_pos, kv_valid, axis_name, causal,
                    scale):
    out, lse = _ring_flash_forward(
        q, k, v, q_pos, kv_pos, kv_valid, axis_name, causal, scale
    )
    return out, (q, k, v, q_pos, kv_pos, kv_valid, out, lse)


def _ring_flash_bwd(axis_name, causal, scale, res, g):
    """Ring backward: a second pass around the ring. dq accumulates
    locally; each visiting block's dk/dv partials travel WITH the block
    (n rotations = full circle, so they arrive home at loop end). Per
    block, the Pallas flash backward kernels run against the GLOBAL
    logsumexp saved from the forward — the standard ring-attention
    backward, O(Tl) memory per device.
    """
    from oryx_tpu.ops.pallas.flash_attention import (
        _mha_backward, _pad_axis, _prepare,
    )

    q, k, v, q_pos, kv_pos, kv_valid, out, lse = res
    B, Tl, Hq, D = q.shape
    n = jax.lax.psum(1, axis_name)
    perm = [(j, (j + 1) % n) for j in range(n)]

    # Restore the kernel's empty-row convention (+MAX ⇒ p underflows to 0)
    # for rows that saw no valid key anywhere in the ring.
    lse_bwd = jnp.where(
        lse <= 0.5 * NEG, jnp.float32(jnp.finfo(jnp.float32).max), lse
    )
    delta = jnp.einsum(
        "bqhd,bqhd->bhq", g.astype(jnp.float32), out.astype(jnp.float32)
    )  # [B, Hq, Tl]

    dq0 = jnp.zeros((B, Tl, Hq, D), jnp.float32)
    dkv0 = jnp.zeros(k.shape, jnp.float32), jnp.zeros(v.shape, jnp.float32)

    def block_grads(dq, dk_t, dv_t, k_cur, v_cur, kpos_cur, kvalid_cur):
        padded, flags, _ = _prepare(
            q, k_cur, v_cur, q_pos, kpos_cur, None, None, kvalid_cur,
            causal, scale,
        )
        Tq_p = padded[0].shape[2]
        do = _pad_axis(g.swapaxes(1, 2), 2, Tq_p)
        lse_p = _pad_axis(lse_bwd, 2, Tq_p)
        delta_p = _pad_axis(delta, 2, Tq_p)
        dq_i, dk_i, dv_i = _mha_backward(
            padded[0], padded[1], padded[2], do, lse_p, delta_p,
            padded[3], padded[4], padded[5], padded[6], padded[7],
            **flags,
        )
        dq = dq + dq_i[:, :, :Tl].swapaxes(1, 2)
        dk_t = dk_t + dk_i[:, :, :Tl].swapaxes(1, 2)
        dv_t = dv_t + dv_i[:, :, :Tl].swapaxes(1, 2)
        return dq, dk_t, dv_t

    def body(_, carry):
        dq, k_cur, v_cur, kpos_cur, kvalid_cur, dk_t, dv_t = carry
        if causal:
            live = jnp.min(kpos_cur) <= jnp.max(q_pos)
            dq, dk_t, dv_t = jax.lax.cond(
                live, block_grads, lambda a, b, c, *_: (a, b, c),
                dq, dk_t, dv_t, k_cur, v_cur, kpos_cur, kvalid_cur,
            )
        else:
            dq, dk_t, dv_t = block_grads(
                dq, dk_t, dv_t, k_cur, v_cur, kpos_cur, kvalid_cur
            )
        k_cur, v_cur, kpos_cur, kvalid_cur, dk_t, dv_t = jax.tree.map(
            lambda x: jax.lax.ppermute(x, axis_name, perm),
            (k_cur, v_cur, kpos_cur, kvalid_cur, dk_t, dv_t),
        )
        return dq, k_cur, v_cur, kpos_cur, kvalid_cur, dk_t, dv_t

    dq, _, _, _, _, dk, dv = jax.lax.fori_loop(
        0, n, body, (dq0, k, v, kv_pos, kv_valid, *dkv0)
    )
    return (
        dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype),
        None, None, None,
    )


_ring_flash_vjp.defvjp(_ring_flash_fwd, _ring_flash_bwd)


def ring_attention(
    q, k, v,
    *,
    mesh: Mesh | None = None,
    axis_name: str = "sp",
    batch_axes: tuple[str, ...] = (),
    causal: bool = False,
    positions=None,
    kv_mask=None,
    scale: float | None = None,
    impl: str = "xla",
):
    """Global-array entry: shards the sequence over `axis_name` and runs the
    ring. q/k/v: [B, T, H*, D] with T divisible by the axis size.
    mesh=None uses the ambient mesh (jax.sharding.use_mesh / jit context).
    impl="flash" uses the Pallas kernel per visiting block (O(tile) logits
    memory — required once per-shard T reaches the tens of thousands).

    batch_axes: mesh axes the batch dim is sharded over (e.g.
    ("dp", "fsdp") in the trainer) — carried through the shard_map so the
    surrounding layers' batch sharding survives instead of forcing an
    all-gather/re-scatter at the shard_map boundary. Axes not present on
    the mesh are dropped.
    """
    B, T, _, _ = q.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (B, T))
    positions = positions.astype(jnp.int32)
    kv_valid = (
        jnp.broadcast_to(kv_mask, (B, T)).astype(jnp.int32)
        if kv_mask is not None
        else jnp.ones((B, T), jnp.int32)
    )
    from oryx_tpu.parallel.sharding import ambient_mesh

    resolved = mesh or ambient_mesh()
    names = getattr(resolved, "axis_names", ()) or ()
    batch = tuple(a for a in batch_axes if a in names) or None
    seq = P(batch, axis_name, None, None)
    tok = P(batch, axis_name)
    # Replication checking is off: the accumulator update is manual.
    fn = shard_map(
        partial(
            ring_attention_shard, axis_name=axis_name, causal=causal,
            scale=scale, impl=impl,
        ),
        mesh=resolved,
        in_specs=(seq, seq, seq, tok, tok, tok),
        out_specs=seq,
        check_vma=False,
    )
    return fn(q, k, v, positions, positions, kv_valid)
