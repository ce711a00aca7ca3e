"""Masked causal-LM loss.

Reference parity: HF Trainer's CE over shifted logits with labels ==
IGNORE_INDEX masked out (SURVEY.md §3.1 "loss = CE(shifted logits,
labels≠IGNORE_INDEX)"). Labels arrive PRE-SHIFTED from
splice.build_mm_batch (labels[t] is the target for the prediction at t),
so this is a pure masked softmax-CE. Accumulation in float32.

Under a mesh (PR 59): the trainer's loss is `chunked_causal_lm_loss`,
whose vocabulary product CONTRACTS over the matrix's `embed` dimension,
the one ZeRO-3 ("fsdp") shards. Left to GSPMD that costs a gather of
the whole matrix at every chunk, forward and recompute, and a
full-width weight gradient a chunk (six tenths of the fsdp=4 step on
the chip: PERF.md section 6, PR 59). So where the ambient mesh shards
`embed` over n > 1 devices that divide the vocabulary, the chunk scan
runs as one explicit shard_map with the matrix split by VOCABULARY
(`_vocab_parallel_sums`, under the scope `loss/vocab_parallel`);
everywhere else (no mesh, `zero2` / `ddp`, an indivisible vocabulary
or batch, T <= chunk) it is the one-device program, unchanged. The
choice is made at trace time from the mesh and the shapes alone.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from oryx_tpu.constants import IGNORE_INDEX
from oryx_tpu.parallel import sharding


def causal_lm_loss(
    logits: jnp.ndarray,  # [B, T, V] (any float dtype; promoted to fp32)
    labels: jnp.ndarray,  # [B, T] int32, IGNORE_INDEX where unsupervised
) -> tuple[jnp.ndarray, dict[str, jnp.ndarray]]:
    """Returns (mean CE over supervised tokens, metrics dict)."""
    logits = logits.astype(jnp.float32)
    mask = labels != IGNORE_INDEX
    safe_labels = jnp.where(mask, labels, 0)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(
        logits, safe_labels[..., None].astype(jnp.int32), axis=-1
    )[..., 0]
    tok_loss = (logz - gold) * mask
    num = jnp.maximum(jnp.sum(mask), 1)
    loss = jnp.sum(tok_loss) / num
    metrics = {
        "loss": loss,
        "num_tokens": jnp.sum(mask).astype(jnp.int32),
        "accuracy": jnp.sum(
            (jnp.argmax(logits, axis=-1) == safe_labels) * mask
        ) / num,
    }
    return loss, metrics


def _project(hidden: jnp.ndarray, w: jnp.ndarray, transpose: bool):
    w = w.astype(hidden.dtype)
    return hidden @ (w.T if transpose else w)


def vocab_parallel_axes(
    sharding_mode: str | None, vocab: int
) -> tuple[tuple[str, ...], int]:
    """(axes, n): the mesh axes the chunked loss splits a vocabulary of
    `vocab` over, and their width; ((), 1) where it runs the one-device
    program. Decided from what is observable at trace time (the ambient
    mesh and the mode's rule for `embed`); nothing configures it."""
    axes, n = sharding.embed_shard_axes(sharding_mode)
    return (axes, n) if n > 1 and vocab % n == 0 else ((), 1)


def _row_layout(mesh, axes):
    """(row_axes, seq_axes, rest) for `_vocab_parallel_sums` over `axes`:
    the mesh axes that shard hidden's rows (sharding.batch_spec: the
    trainer's), the axes of `axes` that shard none (the sequence is
    split over them: "sp"), and the row axes outside `axes` ("dp")."""
    (row_axes,) = sharding.batch_spec()
    row_axes = tuple(a for a in row_axes if a in mesh.axis_names)
    seq_axes = tuple(a for a in axes if a not in row_axes)
    rest = tuple(a for a in row_axes if a not in axes)
    return row_axes, seq_axes, rest


def _width(mesh, axes) -> int:
    return math.prod(mesh.shape[a] for a in axes)


def _sum_chunks(stats, hs, ls, vary=()):
    """(loss sum, tokens, correct) of `stats(a chunk's hidden, its
    labels)` summed over the leading chunk axis; a chunk's logits are
    rematerialized in the backward pass (jax.checkpoint). `vary`: inside
    a shard_map, the mesh axes the sums differ over."""
    stats = jax.checkpoint(stats)

    def body(carry, xs):
        dl, dn, dc = stats(*xs)
        return (carry[0] + dl, carry[1] + dn, carry[2] + dc), None

    init = (
        jnp.zeros((), jnp.float32),
        jnp.zeros((), jnp.int32),
        jnp.zeros((), jnp.int32),
    )
    if vary:
        init = jax.lax.pcast(init, vary, to="varying")
    return jax.lax.scan(body, init, (hs, ls))[0]


def _vocab_parallel_sums(
    hidden, lm_head, labels, *, chunk, transpose, axes, mesh
):
    """The chunked loss's three sums with the vocabulary matrix split by
    VOCABULARY over `axes` (the mesh axes that shard its `embed`
    dimension: sharding.embed_shard_axes), as one explicit shard_map.

    Per microbatch, ONE all-to-all re-lays the compute-dtype matrix from
    embed-sharded [H/n, V] to vocabulary-sharded [H, V/n] ([V, H/n] to
    [V/n, H] for the tied table). A chunk then gathers its ROWS over
    `axes` ([n, B/width, chunk, H]: megabytes, where GSPMD gathered the
    matrix, a gigabyte, twice a chunk), forms its [rows, chunk, V/n]
    block of the logits, and reduces across shards only [rows, chunk]
    vectors: the log-sum-exp by a max and a sum, the gold logit as a
    masked local pick (no take_along_axis over the split axis), the
    arg-max as the lowest global index among the shards that hold the
    maximum (jnp.argmax's tie rule). Autodiff gives the backward its
    mirror: dW is [H, V/n] from all the rows of a chunk and goes back
    to the embed-sharded layout once, after the scan (the all-to-all's
    transpose); dh is each shard's partial sum, reduce-scattered to the
    rows' owners (the row gather's transpose). Same dtypes as the
    one-device path: operands and dW's accumulator across chunks in
    the matrix's dtype, logits and every reduction in float32.

    Rows: hidden's batch axis is sharded as the trainer shards it
    (sharding.batch_spec); its sequence axis is split here over the
    axes of `axes` that shard no rows ("sp"), whether ring attention
    left it so or not, so every device of `axes` owns distinct rows.
    """
    row_axes, seq_axes, rest = _row_layout(mesh, axes)
    # The row axes come first in `axes` (mesh_rules: ("fsdp", "sp")), so
    # a gather over `axes` lists (row shard, sequence shard) row-major.
    assert axes == tuple(a for a in axes if a in row_axes) + seq_axes
    V = lm_head.shape[0 if transpose else 1]
    n, S = _width(mesh, axes), _width(mesh, seq_axes)
    F, Vl = n // S, V // n
    vdim, hdim = (0, 1) if transpose else (1, 0)

    def shard(h, w, lab):
        with jax.named_scope("vocab_parallel"):
            w = jax.lax.all_to_all(w, axes, vdim, hdim, tiled=True)
            if rest:
                # Differ over the other data axes HERE, not where the
                # matrix first meets a chunk's rows: the cast's
                # transpose is dW's sum over those axes, once after
                # the scan and not once a chunk.
                w = jax.lax.pcast(w, rest, to="varying")
            lo = jax.lax.axis_index(axes) * Vl
            Bl, Tl, H = h.shape
            nc = Tl // chunk
            hs = jnp.swapaxes(h.reshape(Bl, nc, chunk, H), 0, 1)
            # Labels come whole over `axes`; lay each chunk's out in the
            # row gather's order: [row shard, sequence shard, local row].
            ls = lab.reshape(F, Bl, S, nc, chunk).transpose(3, 0, 2, 1, 4)
            ls = ls.reshape(nc, n * Bl, chunk)
            cols = jnp.arange(Vl, dtype=jnp.int32)

            def stats(hc, lc):
                rows = jax.lax.all_gather(hc, axes, axis=0, tiled=True)
                logits = _project(rows, w, transpose).astype(jnp.float32)
                mask = lc != IGNORE_INDEX
                safe = jnp.where(mask, lc, 0).astype(jnp.int32)
                top = jnp.max(logits, axis=-1)
                m = jax.lax.pmax(jax.lax.stop_gradient(top), axes)
                logz = m + jnp.log(jax.lax.psum(
                    jnp.sum(jnp.exp(logits - m[..., None]), axis=-1), axes
                ))
                gold = jax.lax.psum(jnp.sum(jnp.where(
                    cols == (safe - lo)[..., None], logits, 0.0
                ), axis=-1), axes)
                pred = jax.lax.pmin(jnp.where(
                    top == m,
                    jnp.argmax(logits, axis=-1).astype(jnp.int32) + lo, V,
                ), axes)
                return (
                    jnp.sum((logz - gold) * mask),
                    jnp.sum(mask).astype(jnp.int32),
                    jnp.sum((pred == safe) * mask).astype(jnp.int32),
                )

            sums = _sum_chunks(stats, hs, ls, vary=rest)
            return jax.lax.psum(sums, rest) if rest else sums

    w_spec = P(None, axes) if transpose else P(axes, None)
    return shard_map(
        shard, mesh=mesh,
        in_specs=(
            P(row_axes or None, seq_axes or None, None), w_spec,
            P(rest or None, None),
        ),
        out_specs=(P(), P(), P()),
    )(hidden, lm_head, labels)


def chunked_causal_lm_loss(
    hidden: jnp.ndarray,   # [B, T, H] final decoder hidden states
    lm_head: jnp.ndarray,  # [H, V] kernel, or [V, H] embed if transpose
    labels: jnp.ndarray,   # [B, T] int32, IGNORE_INDEX where unsupervised
    *,
    chunk: int = 128,
    transpose: bool = False,
    sharding_mode: str | None = None,
) -> tuple[jnp.ndarray, dict[str, jnp.ndarray]]:
    """Masked CE without materializing [B, T, V] logits.

    Scans over sequence chunks; each chunk projects to the vocab, reduces
    to (sum loss, token count, correct count) and is rematerialized in the
    backward pass (jax.checkpoint), so peak memory is one [B, chunk, V]
    logits block instead of the full sequence. At Oryx-7B vocab (152064)
    and a 2048-token bucket this is the difference between ~10 GB of fp32
    logits (+ their gradient) and ~0.6 GB — required to train on a 16 GB
    v5e chip. Numerics match causal_lm_loss (same fp32 reductions).

    sharding_mode: the parallel/sharding.py mode the params are placed
    under (the trainer's; None for a caller that has none). Where an
    ambient mesh shards the matrix's `embed` dimension under it over n
    > 1 devices ("fsdp": the fsdp x sp width), n divides the vocabulary
    and the rows divide over the mesh, the chunks run vocabulary-
    parallel (`vocab_parallel_axes`, `_vocab_parallel_sums`). Everywhere
    else (off-mesh, `zero2` / `ddp` whose matrix is whole on every
    device, an indivisible vocabulary or batch, and the dense fallback
    for T <= chunk) the one-device program below runs unchanged.
    """
    B, T, _ = hidden.shape
    if chunk <= 0 or T <= chunk or T % chunk:
        return causal_lm_loss(_project(hidden, lm_head, transpose), labels)
    mesh = sharding.ambient_mesh()
    axes, _ = vocab_parallel_axes(
        sharding_mode, lm_head.shape[0 if transpose else 1]
    )
    if axes:
        # Rows over the data width, whole chunks of the sequence over
        # the axes that shard no rows: else the one-device program.
        row_axes, seq_axes, _ = _row_layout(mesh, axes)
        if B % _width(mesh, row_axes) or T % (
            _width(mesh, seq_axes) * chunk
        ):
            axes = ()
    if axes:
        tot, n, correct = _vocab_parallel_sums(
            hidden, lm_head, labels, chunk=chunk, transpose=transpose,
            axes=axes, mesh=mesh,
        )
    else:
        nc = T // chunk
        hs = jnp.swapaxes(hidden.reshape(B, nc, chunk, -1), 0, 1)
        ls = jnp.swapaxes(labels.reshape(B, nc, chunk), 0, 1)

        def stats(hc, lc):
            logits = _project(hc, lm_head, transpose).astype(jnp.float32)
            mask = lc != IGNORE_INDEX
            safe = jnp.where(mask, lc, 0)
            logz = jax.nn.logsumexp(logits, axis=-1)
            gold = jnp.take_along_axis(
                logits, safe[..., None].astype(jnp.int32), axis=-1
            )[..., 0]
            correct = jnp.sum((jnp.argmax(logits, axis=-1) == safe) * mask)
            return (
                jnp.sum((logz - gold) * mask),
                jnp.sum(mask).astype(jnp.int32),
                correct.astype(jnp.int32),
            )

        tot, n, correct = _sum_chunks(stats, hs, ls)
    num = jnp.maximum(n, 1)
    metrics = {
        "loss": tot / num,
        "num_tokens": n,
        "accuracy": correct / num,
    }
    return tot / num, metrics
