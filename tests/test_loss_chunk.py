"""Chunked (memory-efficient) CE loss vs the dense reference: values,
metrics AND gradients must match — it is the same fp32 math computed one
sequence chunk at a time (train/loss.chunked_causal_lm_loss)."""

import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from oryx_tpu.constants import IGNORE_INDEX
from oryx_tpu.train import loss as loss_lib


def _setup(seed=0, B=2, T=32, H=16, V=97):
    rng = np.random.default_rng(seed)
    hidden = jnp.asarray(rng.standard_normal((B, T, H)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((H, V)) * 0.1, jnp.float32)
    labels = rng.integers(0, V, size=(B, T))
    labels[:, : T // 3] = IGNORE_INDEX
    labels[0, -3:] = IGNORE_INDEX
    return hidden, w, jnp.asarray(labels, jnp.int32)


def test_chunked_matches_dense_values_and_grads():
    hidden, w, labels = _setup()

    def dense(h, w):
        return loss_lib.causal_lm_loss(h @ w, labels)[0]

    def chunked(h, w):
        return loss_lib.chunked_causal_lm_loss(
            h, w, labels, chunk=8
        )[0]

    ld, gd = jax.value_and_grad(dense, argnums=(0, 1))(hidden, w)
    lc, gc = jax.value_and_grad(chunked, argnums=(0, 1))(hidden, w)
    np.testing.assert_allclose(float(ld), float(lc), rtol=1e-6)
    for a, b in zip(gd, gc):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6
        )


def test_chunked_metrics_match_dense():
    hidden, w, labels = _setup(seed=1)
    _, md = loss_lib.causal_lm_loss(hidden @ w, labels)
    _, mc = loss_lib.chunked_causal_lm_loss(hidden, w, labels, chunk=4)
    assert int(md["num_tokens"]) == int(mc["num_tokens"])
    np.testing.assert_allclose(
        float(md["loss"]), float(mc["loss"]), rtol=1e-6
    )
    np.testing.assert_allclose(
        float(md["accuracy"]), float(mc["accuracy"]), rtol=1e-6
    )


def test_chunked_transpose_tied_embeddings():
    hidden, w, labels = _setup(seed=2)
    lt, _ = loss_lib.chunked_causal_lm_loss(
        hidden, w.T, labels, chunk=8, transpose=True
    )
    ld, _ = loss_lib.causal_lm_loss(hidden @ w, labels)
    np.testing.assert_allclose(float(lt), float(ld), rtol=1e-6)


def test_indivisible_chunk_falls_back_dense():
    hidden, w, labels = _setup(seed=3, T=30)
    lc, _ = loss_lib.chunked_causal_lm_loss(hidden, w, labels, chunk=8)
    ld, _ = loss_lib.causal_lm_loss(hidden @ w, labels)
    np.testing.assert_allclose(float(lc), float(ld), rtol=1e-6)


# ---------------------------------------------------------------------------
# Under a mesh (PR 59): where the mode shards the vocabulary matrix's
# `embed` dimension the chunks run vocabulary-parallel; everywhere else
# the program is the one above, to the jaxpr.
# ---------------------------------------------------------------------------


def _parent_chunked_loss(hidden, lm_head, labels, *, chunk, transpose=False):
    """`chunked_causal_lm_loss`'s chunk scan as it stood before PR 59
    (commit 92c4499, verbatim but for the names it imports): what the
    fall-backs must still trace to."""
    B, T, _ = hidden.shape
    nc = T // chunk
    hs = jnp.swapaxes(hidden.reshape(B, nc, chunk, -1), 0, 1)
    ls = jnp.swapaxes(labels.reshape(B, nc, chunk), 0, 1)

    def stats(hc, lc):
        logits = loss_lib._project(hc, lm_head, transpose).astype(jnp.float32)
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

    stats = jax.checkpoint(stats)

    def body(carry, xs):
        dl, dn, dc = stats(*xs)
        return (carry[0] + dl, carry[1] + dn, carry[2] + dc), None

    (tot, n, correct), _ = jax.lax.scan(
        body,
        (
            jnp.zeros((), jnp.float32),
            jnp.zeros((), jnp.int32),
            jnp.zeros((), jnp.int32),
        ),
        (hs, ls),
    )
    num = jnp.maximum(n, 1)
    metrics = {
        "loss": tot / num,
        "num_tokens": n,
        "accuracy": correct / num,
    }
    return tot / num, metrics


def _mesh_case(V, n, B=8, T=32, H=16, chunk=8):
    """Inputs that corner the cross-shard reductions: whole rows masked
    (so some row shards own no token), a label on the first and the
    last column of each of `n` vocabulary shards, and two positions
    whose two largest logits TIE across shards (the label once the
    lower column, which arg-max picks, once the higher)."""
    rng = np.random.default_rng(7)
    hidden = rng.standard_normal((B, T, H)).astype(np.float32)
    w = (rng.standard_normal((H, V)) * 0.1).astype(np.float32)
    labels = rng.integers(0, V, size=(B, T))
    labels[:, : T // 4] = IGNORE_INDEX
    labels[1] = IGNORE_INDEX
    labels[B - 2] = IGNORE_INDEX
    Vl = V // n
    edges = [c for k in range(n) for c in (k * Vl, (k + 1) * Vl - 1)]
    labels[0, T // 4: T // 4 + len(edges)] = edges
    lo, hi = Vl - 1, V - Vl  # shard 0's last column, shard n-1's first
    w[:, lo] = w[:, hi] = u = 3.0 * w[:, lo] / np.linalg.norm(w[:, lo])
    hidden[2, -1] = hidden[3, -2] = 4.0 * u
    labels[2, -1], labels[3, -2] = lo, hi
    return jnp.asarray(hidden), jnp.asarray(w), jnp.asarray(labels, jnp.int32)


MESH_CASES = {
    # id: (dp, fsdp, sp), mode, tied, V, runs vocabulary-parallel
    "fsdp4": ((1, 4, 1), "fsdp", False, 96, True),
    "fsdp4-tied": ((1, 4, 1), "fsdp", True, 96, True),
    "dp2xfsdp4": ((2, 4, 1), "fsdp", False, 96, True),
    "dp2xfsdp4-tied": ((2, 4, 1), "fsdp", True, 96, True),
    "fsdp2xsp2": ((1, 2, 2), "fsdp", False, 96, True),
    "dp2xfsdp2xsp2-tied": ((2, 2, 2), "fsdp", True, 96, True),
    "indivisible-vocabulary": ((2, 4, 1), "fsdp", False, 98, False),
    "zero2": ((2, 4, 1), "zero2", False, 96, False),
    "ddp-tied": ((2, 4, 1), "ddp", True, 96, False),
    "off-mesh": (None, "fsdp", False, 96, False),
}


@pytest.mark.parametrize("case", MESH_CASES)
def test_chunked_loss_under_a_mesh(case):
    """Loss, `num_tokens`, `accuracy` and both gradients equal the dense
    loss off-mesh; the path taken is the one the mesh and the mode
    call for: a shard_map under `vocab_parallel`, or the jaxpr the
    function had before it knew of meshes."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from oryx_tpu.parallel import sharding

    dims, mode, tied, V, parallel = MESH_CASES[case]
    n = 4
    hidden, w, labels = _mesh_case(V, n if V % n == 0 else 1)
    head = w.T if tied else w

    def dense(h, m):
        return loss_lib.causal_lm_loss(h @ (m.T if tied else m), labels)

    def chunked(h, m):
        return loss_lib.chunked_causal_lm_loss(
            h, m, labels, chunk=8, transpose=tied, sharding_mode=mode
        )

    def parent(h, m):
        return _parent_chunked_loss(h, m, labels, chunk=8, transpose=tied)

    grad = lambda f: jax.value_and_grad(f, argnums=(0, 1), has_aux=True)
    (ld, md), gd = grad(dense)(hidden, head)
    assert 0 < int(md["num_tokens"]) and 0 < float(md["accuracy"]) < 1

    mesh = None
    if dims is not None:
        if jax.device_count() < 8:
            pytest.skip("needs the 8-device CPU mesh (conftest)")
        dp, fsdp, sp = dims
        mesh = Mesh(
            np.asarray(jax.devices()[: dp * fsdp * sp]).reshape(
                dp, fsdp, 1, sp),
            ("dp", "fsdp", "tp", "sp"),
        )
    with sharding.mesh_scope(mesh):
        h, m = hidden, head
        if mesh is not None:
            h = jax.device_put(h, NamedSharding(mesh, P(("dp", "fsdp"))))
            m = jax.device_put(m, NamedSharding(mesh, sharding.param_specs(
                {"llm": {("embed" if tied else "lm_head"): {
                    ("weight" if tied else "kernel"): m}}}, mode,
            )["llm"]["embed" if tied else "lm_head"][
                "weight" if tied else "kernel"]))
        step = jax.jit(grad(chunked))
        (lc, mc), gc = step(h, m)
        traced = str(jax.make_jaxpr(grad(chunked))(hidden, head))
        before = str(jax.make_jaxpr(grad(parent))(hidden, head))
        scoped = "vocab_parallel" in step.lower(h, m).as_text(
            debug_info=True)
        assert loss_lib.vocab_parallel_axes(mode, V)[1] == (
            4 if parallel else 1)
    np.testing.assert_allclose(float(lc), float(ld), rtol=1e-6)
    assert int(mc["num_tokens"]) == int(md["num_tokens"])
    np.testing.assert_allclose(
        float(mc["accuracy"]), float(md["accuracy"]), rtol=1e-6)
    for a, b in zip(gd, gc):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6)
    assert scoped == parallel
    if parallel:
        assert "shard_map" in traced and "all_to_all" in traced
    else:
        assert traced == before


_COLLECTIVE = re.compile(
    r"= (\(?[^=]*?\)?) (all-gather|all-to-all|all-reduce|reduce-scatter|"
    r"collective-permute)(?:-start)?\(.*op_name=\"([^\"]*)\"")


def _loss_collectives(hlo: str, widths: set[int]):
    """[(op, in the chunk loop?)] of the compiled step's collectives
    under the `loss` scope whose result has a dimension in `widths`."""
    out = []
    for shape, op, op_name in _COLLECTIVE.findall(hlo):
        under = re.search(r"[/(]loss[/)](.*)", op_name)
        dims = {int(d) for group in re.findall(r"\[([\d,]+)\]", shape)
                for d in group.split(",")}
        if under and dims & widths:
            out.append((op, "while/body" in under.group(1)))
    return out


def test_fsdp_step_gathers_no_vocabulary_matrix_in_the_chunk_loop(tmp_path):
    """The finding of PR 58's scope table, pinned: the compiled fsdp=4
    train step (test_trainer_modes' recipe, dp=2 x fsdp=4) holds no
    all-gather with a vocabulary-wide result inside the loss's `while`
    body, and what the loss moves at the width of the vocabulary (or of
    a device's share of it) a microbatch does not grow with the number
    of chunks: two all-to-alls (the matrix re-laid once, its gradient
    laid back once) at 4 chunks and at 8.

    It fails on the parent (commit 92c4499), which gathers the whole
    [64, 512] matrix in the loop body, forward and again in the
    backward's recompute, and on this dp=2 mesh sums a full-width
    partial result over dp there twice more: 2 all-gathers and 2
    all-reduces a chunk, 16 vocabulary-wide collectives a microbatch
    at 4 chunks and 32 at 8, where this tree has 2 at either."""
    import dataclasses

    from test_trainer_modes import _batch, _cfg

    from oryx_tpu.parallel import sharding
    from oryx_tpu.train.trainer import Trainer

    if jax.device_count() < 8:
        pytest.skip("needs the 8-device CPU mesh (conftest)")
    cfg = _cfg(tmp_path, "hlo")
    V, n = cfg.llm.vocab_size, cfg.mesh.fsdp
    trainer = Trainer(cfg, sharding_mode="fsdp")
    calls = {}
    try:
        with sharding.mesh_scope(trainer.mesh):
            batch = trainer._device_batch(_batch(cfg))
            T = batch["labels"].shape[-1]
            for loss_chunk in (4, 2):  # cfg is a static argument
                assert T % loss_chunk == 0 and T > loss_chunk
                hlo = trainer._step.lower(
                    trainer.state, batch, tx=trainer.tx,
                    cfg=dataclasses.replace(cfg, train=dataclasses.replace(
                        cfg.train, loss_chunk=loss_chunk)),
                    sharding_mode="fsdp", numerics=False,
                ).compile().as_text()
                found = _loss_collectives(hlo, {V, V // n})
                assert found, "no collective under the loss scope"
                assert not [
                    op for op, looped in _loss_collectives(hlo, {V})
                    if looped and op == "all-gather"
                ]
                chunks = T // loss_chunk
                calls[chunks] = sum(
                    chunks if looped else 1 for _, looped in found)
    finally:
        trainer.close()
    assert calls == {4: 2, 8: 2}, calls
