"""LoRA adapter training (reference train.py `lora_enable` parity):
zero-init delta, frozen base under tune='lora', merge-for-serving
equivalence, config round-trip."""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from oryx_tpu import config as cfg_lib
from oryx_tpu.models import oryx, qwen2
from oryx_tpu.train.optimizer import trainable_mask

LORA = cfg_lib.LoraConfig(enable=True, r=4, alpha=8.0)


def _cfg():
    cfg = cfg_lib.oryx_tiny()
    return dataclasses.replace(
        cfg,
        train=dataclasses.replace(
            cfg.train, tune="lora", lora=LORA,
            # Visible updates from step 2 on (warmup LR is ~0 at step 1).
            learning_rate=1e-2, lr_schedule="constant", warmup_ratio=0.0,
        ),
    )


def test_lora_init_is_identity():
    """B = 0 at init: adapted decoder logits == base logits exactly."""
    cfg = _cfg()
    base = qwen2.init_params(cfg.llm, jax.random.key(0))
    ids = jnp.asarray(
        np.random.default_rng(0).integers(0, cfg.llm.vocab_size, (2, 9))
    )
    ref, _ = qwen2.forward(base, cfg.llm, input_ids=ids)
    adapted = qwen2.add_lora_params(
        base, cfg.llm, cfg.train.lora, jax.random.key(1)
    )
    got, _ = qwen2.forward(adapted, cfg.llm, input_ids=ids)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))


def test_lora_mask_selects_adapters_and_projector():
    cfg = _cfg()
    params = oryx.enable_lora(
        oryx.init_params(cfg, jax.random.key(0)), cfg, jax.random.key(1)
    )
    mask = trainable_mask(params, "lora")
    flat = jax.tree_util.tree_flatten_with_path(mask)[0]
    for path, m in flat:
        names = tuple(p.key for p in path if hasattr(p, "key"))
        expect = names[-1] in ("lora_a", "lora_b") or names[0] == "compressor"
        assert m == expect, names


def test_lora_train_step_only_moves_adapters():
    """One SFT step with tune='lora': lora_b leaves grow off zero; base
    kernels and embeddings stay bit-identical."""
    from oryx_tpu.train import step as step_lib
    from oryx_tpu.train.optimizer import make_optimizer

    cfg = _cfg()
    params = oryx.enable_lora(
        oryx.init_params(cfg, jax.random.key(0)), cfg, jax.random.key(1)
    )
    tx = make_optimizer(cfg.train, params)
    state = step_lib.TrainState(
        step=jnp.zeros((), jnp.int32), params=params,
        opt_state=tx.init(params),
    )
    batch = _mm_batch(cfg, 1)
    old = jax.tree.map(np.asarray, params)
    # Three steps: warmup LR is 0 at step 1; B==0 keeps A's gradient
    # exactly zero until B moves (standard LoRA dynamics).
    for _ in range(3):
        state, metrics = step_lib.train_step(state, batch, cfg, tx)
    assert np.isfinite(float(metrics["loss"]))
    new = jax.tree.map(np.asarray, state.params)

    q = "q_proj"
    np.testing.assert_array_equal(
        new["llm"]["layers"][q]["kernel"], old["llm"]["layers"][q]["kernel"]
    )
    np.testing.assert_array_equal(
        new["llm"]["embed"]["weight"], old["llm"]["embed"]["weight"]
    )
    np.testing.assert_array_equal(
        new["vit"]["patch_embed"]["kernel"],
        old["vit"]["patch_embed"]["kernel"],
    )
    assert np.any(new["llm"]["layers"][q]["lora_a"]
                  != old["llm"]["layers"][q]["lora_a"])
    assert np.any(new["llm"]["layers"][q]["lora_b"] != 0)
    assert np.any(
        new["compressor"]["projector"]["fc1"]["kernel"]
        != old["compressor"]["projector"]["fc1"]["kernel"]
    )


def test_lora_merge_matches_adapted_forward():
    cfg = _cfg()
    base = qwen2.init_params(cfg.llm, jax.random.key(0))
    adapted = qwen2.add_lora_params(
        base, cfg.llm, cfg.train.lora, jax.random.key(1)
    )
    # Give B real values so the delta is nonzero.
    adapted["layers"]["q_proj"]["lora_b"] = (
        jax.random.normal(
            jax.random.key(2), adapted["layers"]["q_proj"]["lora_b"].shape
        ) * 0.05
    )
    ids = jnp.asarray(
        np.random.default_rng(1).integers(0, cfg.llm.vocab_size, (1, 7))
    )
    want, _ = qwen2.forward(adapted, cfg.llm, input_ids=ids)
    merged = qwen2.merge_lora_params(adapted)
    assert "lora_a" not in merged["layers"]["q_proj"]
    got, _ = qwen2.forward(merged, cfg.llm, input_ids=ids)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), atol=1e-5
    )


def test_lora_config_round_trip():
    cfg = _cfg()
    back = cfg_lib.OryxConfig.from_json(cfg.to_json())
    assert back == cfg
    assert isinstance(back.train.lora, cfg_lib.LoraConfig)
    assert back.train.lora.scaling == pytest.approx(8.0 / 4)


def test_lora_export_merge_round_trip(tmp_path):
    """export_lora_dir (PEFT layout) → merge_lora_dir on the base params
    == merge_lora_params on the adapted params."""
    from oryx_tpu.models import import_hf

    cfg = _cfg()
    base = qwen2.init_params(cfg.llm, jax.random.key(0))
    adapted = qwen2.add_lora_params(
        base, cfg.llm, cfg.train.lora, jax.random.key(1)
    )
    adapted["layers"]["v_proj"]["lora_b"] = (
        jax.random.normal(
            jax.random.key(3), adapted["layers"]["v_proj"]["lora_b"].shape
        ) * 0.05
    )
    d = str(tmp_path / "adapter")
    import_hf.export_lora_dir(adapted, cfg.train.lora, d)
    merged_via_dir = import_hf.merge_lora_dir(base, d, cfg.llm)
    merged_in_tree = qwen2.merge_lora_params(adapted)
    np.testing.assert_allclose(
        np.asarray(merged_via_dir["layers"]["v_proj"]["kernel"]),
        np.asarray(merged_in_tree["layers"]["v_proj"]["kernel"]),
        atol=1e-5,
    )


# ---------------------------------------------------------------------------
# The step differentiates the trainable leaves only (train/step.py).
# ---------------------------------------------------------------------------

TUNES = ("lora", "projector_only", "no_vision", "full")


def _whole_tree_step_fn(state, batch, cfg, tx):
    """The reference: differentiate EVERY leaf of `state.params` and let
    the optimizer's mask throw the frozen leaves' gradients away — the
    step as it was before it split the tree (train_step_fn's body of that
    commit, numerics probes left out). Returns the whole gradient tree
    beside the metrics."""
    import optax

    from oryx_tpu.train import step as step_lib

    grad_fn = jax.value_and_grad(
        lambda p, c, m: step_lib.microbatch_loss(p, c, m, "fsdp", False),
        has_aux=True,
    )
    accum = jax.tree.leaves(batch)[0].shape[0]
    if accum == 1:
        with jax.named_scope("forward_backward"):
            (loss_sum, metrics), grads = grad_fn(
                state.params, cfg, jax.tree.map(lambda x: x[0], batch)
            )
            grads = jax.tree.map(lambda g: g.astype(jnp.float32), grads)
        ntok = metrics["num_tokens"]
    else:
        def one_micro(carry, mb):
            grads_acc, loss_acc, ntok_acc = carry
            (loss, metrics), grads = grad_fn(state.params, cfg, mb)
            grads_acc = jax.tree.map(
                lambda a, g: a + g.astype(jnp.float32), grads_acc, grads
            )
            return (
                grads_acc, loss_acc + loss, ntok_acc + metrics["num_tokens"]
            ), metrics

        with jax.named_scope("forward_backward_accum"):
            zeros = jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), state.params
            )
            (grads, loss_sum, ntok), _ = jax.lax.scan(
                one_micro,
                (zeros, jnp.zeros((), jnp.float32),
                 jnp.zeros((), jnp.int32)),
                batch,
            )
            grads = jax.tree.map(lambda g: g / accum, grads)
    with jax.named_scope("optimizer_update"):
        updates, opt_state = tx.update(grads, state.opt_state, state.params)
        params = optax.apply_updates(state.params, updates)
        gnorm = optax.global_norm(grads)
    metrics = {"loss": loss_sum / accum, "grad_norm": gnorm,
               "num_tokens": ntok}
    return (
        step_lib.TrainState(
            step=state.step + 1, params=params, opt_state=opt_state
        ),
        metrics,
    ), grads


def _mm_batch(cfg, accum, seed=0):
    """[accum, ...] microbatches of one image + text row each."""
    from oryx_tpu.constants import IGNORE_INDEX, IMAGE_TOKEN_INDEX
    from oryx_tpu.models import splice
    from oryx_tpu.ops import packing

    rng = np.random.default_rng(seed)
    p = cfg.vision.patch_size
    micro = []
    for _ in range(accum):
        imgs = [rng.standard_normal((2 * p, 2 * p, 3)).astype(np.float32)]
        packed = packing.pack_images(
            imgs, patch_size=p, base_grid=cfg.vision.base_grid,
            side_factors=1, buckets=(64,),
        )
        row = np.concatenate(
            [[5, IMAGE_TOKEN_INDEX], rng.integers(3, 500, 8)]
        )
        lab = np.full(row.shape, IGNORE_INDEX, np.int64)
        lab[-8:] = row[-8:]
        mm = splice.build_mm_batch(
            [row], splice.query_slots(packed), labels=[lab], buckets=(32,)
        )
        micro.append({
            "patches": packed.patches, "segment_ids": packed.segment_ids,
            "pos_coords": packed.pos_coords,
            "region_ids": packed.region_ids,
            "q_region_ids": packed.q_region_ids,
            "token_ids": mm.token_ids, "visual_idx": mm.visual_idx,
            "is_visual": mm.is_visual, "attn_mask": mm.attn_mask,
            "positions": mm.positions, "labels": mm.labels,
        })
    return {
        k: jnp.stack([jnp.asarray(m[k]) for m in micro]) for k in micro[0]
    }


def _tune_setup(tune):
    """(cfg, tx, state) on oryx_tiny float32; under LoRA every lora_b is
    seeded off zero so lora_a has a gradient from the first step."""
    from oryx_tpu.train import step as step_lib
    from oryx_tpu.train.optimizer import make_optimizer

    cfg = _cfg()
    cfg = dataclasses.replace(
        cfg, train=dataclasses.replace(
            cfg.train, tune=tune,
            lora=LORA if tune == "lora" else cfg_lib.LoraConfig(),
        ),
    )
    params = oryx.init_params(cfg, jax.random.key(0))
    if tune == "lora":
        params = oryx.enable_lora(params, cfg, jax.random.key(1))
        layers = params["llm"]["layers"]
        for i, t in enumerate(sorted(cfg.train.lora.targets)):
            b = layers[t]["lora_b"]
            layers[t]["lora_b"] = 0.05 * jax.random.normal(
                jax.random.key(10 + i), b.shape, b.dtype
            )
    tx = make_optimizer(cfg.train, params)
    state = step_lib.TrainState(
        step=jnp.zeros((), jnp.int32), params=params,
        opt_state=tx.init(params),
    )
    return cfg, tx, state


def _jitted(fn, cfg, tx):
    return jax.jit(lambda s, b: fn(s, b, cfg, tx))


def _assert_trees_equal(got, want):
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for (path, g), w in zip(
        jax.tree_util.tree_flatten_with_path(got)[0], jax.tree.leaves(want)
    ):
        np.testing.assert_array_equal(
            np.asarray(g), np.asarray(w), err_msg=jax.tree_util.keystr(path)
        )


@pytest.mark.parametrize("accum", [1, 2])
@pytest.mark.parametrize("tune", TUNES)
def test_step_matches_whole_tree_reference(tune, accum):
    """Two optimizer steps (the first at the warm-up's lr 0, the second
    moves the weights): trainable leaves, optimizer state and loss equal
    the whole-tree reference's bit for bit; frozen leaves are untouched;
    grad_norm is the norm over the trainable leaves' gradients.

    Run op by op (disable_jit): the same primitive on the same operands
    gives the same bits, where two compiled programs fuse their
    reductions differently (1e-6 relative in a bias gradient)."""
    import optax

    from oryx_tpu.train import step as step_lib

    cfg, tx, state = _tune_setup(tune)
    batch = _mm_batch(cfg, accum)
    start = jax.tree.map(np.asarray, state.params)
    s_new = s_ref = state
    with jax.disable_jit():
        for _ in range(2):
            s_new, m_new = step_lib.train_step_fn(s_new, batch, cfg, tx)
            (s_ref, m_ref), g_ref = _whole_tree_step_fn(
                s_ref, batch, cfg, tx
            )
            np.testing.assert_array_equal(
                np.asarray(m_new["loss"]), np.asarray(m_ref["loss"])
            )
    _assert_trees_equal(s_new.params, s_ref.params)
    _assert_trees_equal(s_new.opt_state, s_ref.opt_state)
    assert int(s_new.step) == int(s_ref.step) == 2

    mask = trainable_mask(state.params, tune)
    moved = 0
    for m, (path, p), p0 in zip(
        jax.tree.leaves(mask),
        jax.tree_util.tree_flatten_with_path(s_new.params)[0],
        jax.tree.leaves(start),
    ):
        if not m:
            np.testing.assert_array_equal(
                np.asarray(p), p0, err_msg=jax.tree_util.keystr(path)
            )
        else:
            moved += bool(np.any(np.asarray(p) != p0))
    assert moved
    want = optax.global_norm(
        [g for g, m in zip(jax.tree.leaves(g_ref), jax.tree.leaves(mask))
         if m]
    )
    np.testing.assert_allclose(
        float(m_new["grad_norm"]), float(want), rtol=1e-6
    )
    if tune != "full":
        assert float(m_new["grad_norm"]) < float(m_ref["grad_norm"])


def _eqns(jaxpr):
    """Every equation of a jaxpr and of the jaxprs inside it."""
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _eqns(sub)


def _scan_output_shapes(closed):
    return {
        tuple(v.aval.shape)
        for eqn in _eqns(closed.jaxpr) if eqn.primitive.name == "scan"
        for v in eqn.outvars
    }


def test_lora_step_traces_no_frozen_weight_gradient():
    """Structure of the tune='lora' step: no scan in it (the layer scans'
    backward, the microbatch scan) carries or stacks anything of a frozen
    kernel's shape, every frozen leaf leaves the step as the variable it
    came in as, and the compiled step costs at most three quarters of the
    whole-tree reference's FLOPs."""
    from oryx_tpu.train import step as step_lib

    cfg, tx, state = _tune_setup("lora")
    mask = jax.tree.leaves(trainable_mask(state.params, "lora"))
    leaves = jax.tree.leaves(state.params)
    train_shapes = {p.shape for p, m in zip(leaves, mask) if m}
    frozen = {p.shape for p, m in zip(leaves, mask) if not m and p.ndim >= 2}
    # a stacked [L, in, out] kernel leaves a backward scan as [L, in, out]
    # and is accumulated inside it as [in, out]
    frozen |= {s[1:] for s in frozen if len(s) == 3}
    frozen -= train_shapes
    assert (cfg.llm.num_layers, cfg.llm.hidden_size,
            cfg.llm.intermediate_size) in frozen
    for accum in (1, 2):
        batch = _mm_batch(cfg, accum)
        new = jax.make_jaxpr(
            lambda s, b: step_lib.train_step_fn(s, b, cfg, tx)
        )(state, batch)
        ref = jax.make_jaxpr(
            lambda s, b: _whole_tree_step_fn(s, b, cfg, tx)[0]
        )(state, batch)
        assert _scan_output_shapes(ref) & frozen, "the check sees nothing"
        assert not _scan_output_shapes(new) & frozen
        # outputs: step, params..., opt_state..., metrics; invars: step,
        # params..., opt_state..., batch — the params line up
        ins, outs = new.jaxpr.invars, new.jaxpr.outvars
        for i, m in enumerate(mask):
            assert (outs[1 + i] is ins[1 + i]) == (not m)
    batch = _mm_batch(cfg, 1)
    flops = [
        _jitted(fn, cfg, tx).lower(state, batch).compile()
        .cost_analysis()["flops"]
        for fn in (step_lib.train_step_fn, _whole_tree_step_fn)
    ]
    assert flops[0] <= 0.75 * flops[1], flops


@pytest.mark.parametrize("accum", [1, 2])
def test_full_tune_step_is_the_whole_tree_program(accum):
    """tune='full' freezes nothing, so the step traces to the whole-tree
    reference's jaxpr: the full-tune program is untouched."""
    from oryx_tpu.train import step as step_lib

    cfg, tx, state = _tune_setup("full")
    batch = _mm_batch(cfg, accum)
    new = jax.make_jaxpr(
        lambda s, b: step_lib.train_step_fn(s, b, cfg, tx)
    )(state, batch)
    ref = jax.make_jaxpr(
        lambda s, b: _whole_tree_step_fn(s, b, cfg, tx)[0]
    )(state, batch)
    assert str(new) == str(ref)


def test_whole_tree_checkpoint_resumes_into_the_step(tmp_path):
    """A checkpoint written from a state the whole-tree reference made
    restores into today's TrainState (same trees, same layout) and the
    step goes on from it as the reference does."""
    from oryx_tpu.train import step as step_lib
    from oryx_tpu.utils.checkpoint import CheckpointManager

    cfg, tx, state = _tune_setup("lora")
    batch = _mm_batch(cfg, 1)
    with jax.disable_jit():
        (s_ref, _), _ = _whole_tree_step_fn(state, batch, cfg, tx)
    ckpt = CheckpointManager(str(tmp_path / "ckpt"))
    try:
        ckpt.save(1, s_ref, force=True)
        ckpt.wait()
        restored = ckpt.restore(state)
    finally:
        ckpt.close()
    assert int(restored.step) == 1
    _assert_trees_equal(restored.params, s_ref.params)
    _assert_trees_equal(restored.opt_state, s_ref.opt_state)
    with jax.disable_jit():  # bit for bit: see the parity test
        s_new, m_new = step_lib.train_step_fn(restored, batch, cfg, tx)
        (s_ref2, m_ref), _ = _whole_tree_step_fn(s_ref, batch, cfg, tx)
    np.testing.assert_array_equal(
        np.asarray(m_new["loss"]), np.asarray(m_ref["loss"])
    )
    _assert_trees_equal(s_new.params, s_ref2.params)
    _assert_trees_equal(s_new.opt_state, s_ref2.opt_state)
