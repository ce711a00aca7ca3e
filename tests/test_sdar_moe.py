"""SDAR-MoE at `sdar_tiny` on the CPU: the expert layer, the block mask
and the block step against the plain reference
(benchmark/reference/sdar_moe_ref.py), the checkpoint name map, and
the promise that a dense configuration's `forward` did not change."""

import dataclasses
import hashlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark.reference import sdar_moe_ref as ref
from oryx_tpu import config as cfg_lib
from oryx_tpu.models import generate, import_hf, oryx, qwen2

F32 = jnp.float32


@pytest.fixture(scope="module")
def tiny():
    cfg = cfg_lib.sdar_tiny().llm
    params = qwen2.init_params(cfg, jax.random.key(0))
    # Norm weights away from 1, so a missing norm weight would show.
    for name, seed in (("q_norm", 1), ("k_norm", 2)):
        w = params["layers"][name]["weight"]
        params["layers"][name]["weight"] = 1 + 0.1 * jax.random.normal(
            jax.random.key(seed), w.shape)
    return cfg, params


def _ref_moe(cfg, x, lp):
    y, ids = ref.moe_layer(x.astype(F32), lp, cfg)
    return np.asarray(y), np.asarray(ids)


def _layer0(params):
    return jax.tree.map(lambda a: a[0], params["layers"])


def _program_moe(cfg, params, x, router=None):
    flat = jax.tree.map(lambda a: a.reshape((-1,) + a.shape[2:]),
                        params["layers"]["experts"])
    router = params["layers"]["router"]["kernel"][0] if router is None \
        else router
    return qwen2._moe(cfg, x, router, flat, jnp.asarray(0, jnp.int32))


@pytest.mark.parametrize("case", ["random", "tied", "starved"])
def test_expert_layer_matches_the_reference_layer(tiny, case):
    """Router in float32, top-K with ties to the lower expert id,
    renormalised weights, dropless grouped products: against a loop
    over all experts. `tied`: every router logit of a row equal, and
    rows whose 2nd and 3rd are equal. `starved`: an expert that no row
    chooses takes no row and breaks nothing."""
    cfg, params = tiny
    x = jax.random.normal(jax.random.key(4), (24, cfg.hidden_size), F32)
    lp = dict(_layer0(params))
    router = lp["router"]["kernel"]
    if case == "tied":
        x = x.at[:6].set(0.0)  # every logit 0: experts 0 and 1 win
        router = router.at[:, 3].set(router[:, 2])  # 2 and 3 always tie
    if case == "starved":
        router = router.at[:, 5].set(-1e3 * jnp.abs(router[:, 5]))
        x = jnp.abs(x)
    lp["router"] = {"kernel": router}
    want, want_ids = _ref_moe(cfg, x, lp)
    got, routing = _program_moe(cfg, params, x, router)
    np.testing.assert_array_equal(
        np.sort(np.asarray(routing["ids"]), -1), np.sort(want_ids, -1))
    np.testing.assert_allclose(np.asarray(got), want, atol=2e-6)
    counts = np.asarray(routing["counts"])
    assert counts.sum() == 24 * cfg.num_experts_per_tok
    if case == "tied":
        assert set(np.asarray(routing["ids"])[0]) == {0, 1}
    if case == "starved":
        assert counts[5] == 0


@pytest.mark.parametrize("rows", [40, 128])
def test_grouped_matmul_under_pallas_equals_the_grouped_product(rows):
    """`_grouped_dot` under impl="pallas" at widths its tiles divide is
    the grouped matmul jax ships (interpreted here), elsewhere
    `jax.lax.ragged_dot`: the same product, with empty groups, groups
    that cross a row tile, and a row count that is no multiple of it."""
    K, N, G = 128, 256, 6
    k1, k2 = jax.random.split(jax.random.key(9))
    x = jax.random.normal(k1, (rows, K), F32)
    w = jax.random.normal(k2, (G, K, N), F32) * 0.1
    groups = jnp.asarray([0, rows - 25, 0, 20, 5, 0], jnp.int32)
    want = jax.lax.ragged_dot(x, w, groups)
    got = qwen2._grouped_dot(x, w, groups, "pallas")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    assert got.shape == want.shape
    # Widths no tile divides stay with the grouped product, bit for bit.
    narrow = qwen2._grouped_dot(x[:, :64], w[:, :64], groups, "pallas")
    assert jnp.array_equal(
        narrow, jax.lax.ragged_dot(x[:, :64], w[:, :64], groups))


def test_shares_of_the_expert_layer_add_up_to_the_whole(tiny):
    """A layer's result is the sum over experts of what each gives: the
    parts computed with every other expert's down kernel zeroed add up
    to the uncut layer (what an expert-parallel share would compute)."""
    cfg, params = tiny
    x = jax.random.normal(jax.random.key(5), (16, cfg.hidden_size), F32)
    whole, _ = _program_moe(cfg, params, x)
    total = jnp.zeros_like(whole)
    for half in (range(0, 4), range(4, 8)):
        keep = jnp.zeros((cfg.num_experts,)).at[jnp.asarray(list(half))].set(1)
        cut = jax.tree.map(lambda a: a, params)
        cut["layers"] = dict(params["layers"])
        ex = dict(params["layers"]["experts"])
        ex["down"] = ex["down"] * keep[None, :, None, None]
        cut["layers"]["experts"] = ex
        total = total + _program_moe(cfg, cut, x)[0]
    np.testing.assert_allclose(np.asarray(total), np.asarray(whole), atol=2e-6)


@pytest.mark.parametrize("T", [8, 23])
def test_forward_under_the_block_mask_matches_the_reference(tiny, T):
    cfg, params = tiny
    ids = np.random.default_rng(T).integers(0, 500, T)
    got, _, routing = qwen2.forward(
        params, cfg, input_ids=jnp.asarray(ids)[None], return_routing=True)
    want, chosen = ref.logits(params, cfg, ids, return_experts=True)
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want), atol=2e-6)
    np.testing.assert_array_equal(
        np.sort(np.asarray(routing["ids"]), -1), np.sort(chosen, -1))
    assert routing["counts"].shape == (cfg.num_layers, cfg.num_experts)


def test_block_mask_is_full_inside_a_block_and_causal_across(tiny):
    """Changing a token changes the logits of every position of its own
    block and of later blocks, and of no earlier block."""
    cfg, params = tiny
    ids = np.random.default_rng(0).integers(0, 500, 16)
    base = np.asarray(qwen2.forward(
        params, cfg, input_ids=jnp.asarray(ids)[None])[0][0])
    ids2 = ids.copy()
    ids2[6] = (ids2[6] + 1) % 500  # block 1 = positions 4..7
    moved = np.abs(np.asarray(qwen2.forward(
        params, cfg, input_ids=jnp.asarray(ids2)[None])[0][0]) - base
    ).max(-1) > 1e-7
    assert not moved[:4].any() and moved[4:].all()


RULES = [("low_confidence_static", 1), ("low_confidence_static", 2),
         ("low_confidence_static", 4), ("low_confidence_dynamic", 4)]


def _generate_through_the_programs(cfg, params, ids, new, remasking, steps,
                                   threshold):
    B, ps, n = cfg.block_length, 16, len(ids)
    head = n - n % B
    kv = qwen2.init_paged_kv_cache(cfg, 8, ps, dtype=F32)
    bt = jnp.arange(8, dtype=jnp.int32)[None]
    one = (jnp.zeros((1,)), jnp.ones((1,)), jnp.zeros((1,), jnp.int32))
    keys = jax.random.split(jax.random.key(0), 1)
    if head:
        emb = params["embed"]["weight"][
            jnp.asarray(np.pad(ids[:head], (0, 32 - head)))][None]
        kv, _, keys = generate.paged_prefill(
            params, cfg, emb, jnp.asarray([head], jnp.int32), bt, kv,
            jnp.asarray([0], jnp.int32), keys, *one)
    blk = np.zeros((1, B), np.int32)
    blk[0, :n - head] = ids[head:]
    known = np.asarray([n - head], np.int32)
    length = np.asarray([head], np.int32)
    out, forwards = [], []
    # Every block's tokens go on, as they lie on the device, to the next
    # dispatch, whose first forward commits them; the first has none.
    toks, pending = jnp.zeros((1, B), jnp.int32), jnp.zeros((1,), bool)
    while len(out) < new:
        kv, toks, n_new, length, _, keys, counts = generate.paged_block_step(
            params, cfg, kv, bt, jnp.asarray(blk), jnp.asarray(known),
            jnp.asarray(length), jnp.zeros((1,), bool), keys, *one,
            toks, pending,
            steps=steps, remasking=remasking, threshold=threshold, eos=-1)
        pending = jnp.ones((1,), bool)
        assert int(n_new[0]) == B - known[0]
        out += [int(t) for t in np.asarray(toks)[0][known[0]:]]
        forwards.append(int(counts["stats"][0]))
        blk[:], known[:] = 0, 0
    return out[:new], forwards


@pytest.mark.parametrize("tail", [0, 1, 2, 3])
@pytest.mark.parametrize("remasking,steps", RULES)
def test_prefill_and_block_step_match_the_reference_loop(
        tiny, remasking, steps, tail):
    """`paged_prefill` + `paged_block_step` (paged pool, packed lanes,
    the on-device denoising loop, each block's commit riding the next
    block's first forward) generate the
    tokens of the reference's cache-less loop, for every unmasking rule
    and every prompt tail."""
    cfg, params = tiny
    ids = np.random.default_rng(10 * steps + tail).integers(0, 500, 20 + tail)
    threshold = 1.0 / 400  # near 1/vocab: the dynamic rule takes several
    want, _ = ref.generate(params, cfg, ids, 10, steps=steps,
                           remasking=remasking, threshold=threshold)
    got, forwards = _generate_through_the_programs(
        cfg, params, ids, 10, remasking, steps, threshold)
    assert got == want
    if remasking == "low_confidence_static":
        # T forwards a block; a tail of 3 leaves one position, which
        # one denoising forward fills whatever T is.
        first = min(steps, cfg.block_length - tail)
        assert forwards == [first] + [steps] * (len(forwards) - 1)


def test_block_step_skips_finished_slots_and_counts(tiny):
    """A finished slot's lanes ride along: its tokens are not new, its
    length stays, its pages are not written; the counts add up."""
    cfg, params = tiny
    B = cfg.block_length
    kv = qwen2.init_paged_kv_cache(cfg, 4, 16, dtype=F32)
    bt = jnp.asarray([[0, 1], [2, 3]], jnp.int32)
    before = np.asarray(kv["k"][:, 2:4])
    out = generate.paged_block_step(
        params, cfg, kv, bt, jnp.zeros((2, B), jnp.int32),
        jnp.zeros((2,), jnp.int32), jnp.zeros((2,), jnp.int32),
        jnp.asarray([False, True]), jax.random.split(jax.random.key(0), 2),
        jnp.zeros((2,)), jnp.ones((2,)), jnp.zeros((2,), jnp.int32),
        steps=2, remasking="low_confidence_static", threshold=0.9, eos=-1)
    kv, _, n_new, lengths, finished, _, counts = out
    assert list(np.asarray(n_new)) == [B, 0]
    assert list(np.asarray(lengths)) == [B, 0]
    assert list(np.asarray(finished)) == [False, True]
    np.testing.assert_array_equal(np.asarray(kv["k"][:, 2:4]), before)
    stats = dict(zip(generate.BLOCK_STATS, (int(x) for x in counts["stats"])))
    # Two forwards, no commit-only one; without a pending block none
    # carries commit lanes.
    assert stats["forwards"] == 2 and stats["unmasked"] == B
    assert list(np.asarray(counts["slot_forwards"])) == [2, 0]
    routed, rows_max, hit = (stats[k] for k in generate.BLOCK_STATS[2:])
    pairs = 2 * cfg.num_layers * 2 * B * cfg.num_experts_per_tok
    assert routed == pairs == int(np.asarray(counts["expert_rows"]).sum())
    assert pairs / cfg.num_experts <= rows_max <= pairs
    assert 0 < hit <= 2 * cfg.num_layers * cfg.num_experts


def test_sampled_rows_go_through_the_sampler_and_are_reproducible(tiny):
    cfg, params = tiny
    B = cfg.block_length

    def run(seed):
        kv = qwen2.init_paged_kv_cache(cfg, 2, 16, dtype=F32)
        return np.asarray(generate.paged_block_step(
            params, cfg, kv, jnp.asarray([[0, 1]], jnp.int32),
            jnp.zeros((1, B), jnp.int32), jnp.zeros((1,), jnp.int32),
            jnp.zeros((1,), jnp.int32), jnp.zeros((1,), bool),
            jax.random.split(jax.random.key(seed), 1),
            jnp.ones((1,)), jnp.full((1,), 0.95), jnp.zeros((1,), jnp.int32),
            steps=4, remasking="low_confidence_static", threshold=0.9,
            eos=-1)[1])

    a, b, c = run(1), run(1), run(2)
    np.testing.assert_array_equal(a, b)
    assert (a != c).any()


@pytest.mark.parametrize("m,steps,want", [
    (4, 2, [2, 2]), (4, 3, [2, 1, 1]), (4, 4, [1, 1, 1, 1]), (2, 2, [1, 1]),
    (1, 2, [1, 0]), (4, 1, [4]),
])
def test_static_schedule_is_an_even_ceil_spread(m, steps, want):
    """The device's rule and the reference's agree step by step."""
    masked = jnp.arange(4)[None] >= 4 - m
    conf = jnp.asarray([[0.4, 0.1, 0.3, 0.2]])
    took = []
    for t in range(steps):
        fix = generate.block_unmask(
            masked, conf, jnp.asarray(t, jnp.int32), steps=steps,
            remasking="low_confidence_static", threshold=0.9)
        took.append(int(fix.sum()))
        assert took[-1] == ref.unmask_count(int(masked.sum()), t, steps) \
            or not masked.any()
        masked = masked & ~fix
    assert took == want and not masked.any()


def test_dynamic_rule_takes_every_confident_position_and_the_best(tiny):
    masked = jnp.asarray([[True, True, True, False]])
    conf = jnp.asarray([[0.95, 0.2, 0.92, 0.99]])
    fix = generate.block_unmask(
        masked, conf, jnp.asarray(0, jnp.int32), steps=4,
        remasking="low_confidence_dynamic", threshold=0.9)
    assert list(np.asarray(fix)[0]) == [True, False, True, False]
    fix = generate.block_unmask(
        masked, conf * 0.1, jnp.asarray(0, jnp.int32), steps=4,
        remasking="low_confidence_dynamic", threshold=0.9)
    assert list(np.asarray(fix)[0]) == [True, False, False, False]


# The parent commit's jaxprs of `forward` for oryx_tiny (sha256 of the
# text, first 16 hex digits; scratch script against `git archive` of
# 1c9d1bf, this installation's jax 0.9.0, matmul precision "highest" as
# tests/conftest.py sets it). A later PR that changes the dense decoder
# on purpose replaces them and says so.
PARENT_JAXPRS = {
    "no_cache": "1343f37fa01d9e99", "dense_cache": "30ccef85b0983705",
    "paged": "d8a0e09b0f5c4510", "packed": "7685342ea1f0b255",
}


# `sdar_tiny`'s own, taken the same way on c638234 (PR 31, before the
# expert layer learned held ranges, zero-compute experts, a selection
# bias and a scaling factor): at SDAR's values of those the generalised
# `_moe` is the parent's, operation for operation. ("packed" asks for
# the routing too.)
SDAR_PARENT_JAXPRS = {
    "no_cache": "fd78b44923debc73", "dense_cache": "f91c0814b3bd52fa",
    "paged": "64ccb85b35d8a8ce", "packed": "fe0c17db9b7b8274",
}


@pytest.mark.parametrize("model, branch", [
    (m, b) for m in ("oryx_tiny", "sdar_tiny") for b in sorted(PARENT_JAXPRS)
])
def test_dense_forward_jaxpr_is_the_parents(model, branch):
    """Expert layer, q/k norm, block mask, routing outputs, latent
    attention and the expert layer's share are chosen by the config
    alone: with the new arguments at their defaults a dense
    configuration, and SDAR's expert decoder, trace to the parent's
    jaxpr, character for character, on every cache branch."""
    assert jax.config.jax_default_matmul_precision == "highest"
    cfg = getattr(cfg_lib, model)().llm
    golden = PARENT_JAXPRS if model == "oryx_tiny" else SDAR_PARENT_JAXPRS
    routing = {"return_routing": True} if model == "sdar_tiny" else {}
    p = jax.eval_shape(lambda: qwen2.init_params(cfg, jax.random.key(0)))
    ids = jax.ShapeDtypeStruct((2, 8), jnp.int32)
    pos = lambda: jnp.zeros((2, 8), jnp.int32) + jnp.arange(8)  # noqa: E731
    mask = lambda: jnp.ones((2, 32), jnp.int32)  # noqa: E731
    paged = jax.eval_shape(
        lambda: qwen2.init_paged_kv_cache(cfg, 8, 16, jnp.float32))
    bt = jax.ShapeDtypeStruct((2, 2), jnp.int32)
    if branch == "no_cache":
        jaxpr = jax.make_jaxpr(
            lambda p, i: qwen2.forward(p, cfg, input_ids=i))(p, ids)
    elif branch == "dense_cache":
        kv = jax.eval_shape(
            lambda: qwen2.init_kv_cache(cfg, 2, 32, jnp.float32))
        jaxpr = jax.make_jaxpr(lambda p, i, kv: qwen2.forward(
            p, cfg, input_ids=i, kv_cache=kv, positions=pos(),
            kv_mask=mask()))(p, ids, kv)
    elif branch == "paged":
        jaxpr = jax.make_jaxpr(lambda p, i, kv, bt: qwen2.forward(
            p, cfg, input_ids=i, kv_cache=kv, block_tables=bt,
            positions=pos(), kv_mask=mask()))(p, ids, paged, bt)
    else:
        seg = jax.ShapeDtypeStruct((1, 8), jnp.int32)
        jaxpr = jax.make_jaxpr(lambda p, i, kv, bt, s: qwen2.forward(
            p, cfg, input_ids=i, kv_cache=kv, block_tables=bt,
            positions=s, q_segments=s, **routing))(p, seg, paged, bt, seg)
    digest = hashlib.sha256(str(jaxpr).encode()).hexdigest()[:16]
    assert digest == golden[branch]


def test_sdar_moe_checkpoint_names_round_trip(tiny):
    """`model.layers.N.mlp.experts.M.{gate,up,down}_proj`, `mlp.gate`,
    `self_attn.{q,k}_norm` <-> the stacked layout, on a synthetic state
    dict; the router comes back float32 whatever the dtype asked for."""
    cfg, params = tiny
    sd = import_hf.export_qwen2(params, cfg)
    assert sd["model.layers.1.mlp.experts.7.down_proj.weight"].shape == (
        cfg.hidden_size, cfg.moe_intermediate_size)
    assert sd["model.layers.0.mlp.gate.weight"].shape == (
        cfg.num_experts, cfg.hidden_size)
    assert sd["model.layers.0.self_attn.q_norm.weight"].shape == (
        cfg.head_dim,)
    assert not any("mlp.gate_proj" in k for k in sd)
    back = import_hf.import_qwen2(sd, cfg)
    jax.tree.map(np.testing.assert_array_equal, params, back)
    half = import_hf.import_qwen2(sd, cfg, dtype=jnp.bfloat16)
    assert half["layers"]["router"]["kernel"].dtype == jnp.float32
    assert half["layers"]["experts"]["up"].dtype == jnp.bfloat16
    got = qwen2.forward(back, cfg, input_ids=jnp.arange(8)[None])[0]
    want = qwen2.forward(params, cfg, input_ids=jnp.arange(8)[None])[0]
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_presets_and_a_config_without_a_vision_tower():
    big = cfg_lib.sdar_30b_a3b()
    assert big.vision is None
    assert (big.llm.num_experts, big.llm.num_experts_per_tok,
            big.llm.moe_intermediate_size) == (128, 8, 768)
    assert big.llm.qk_norm and big.llm.block_length == 4
    assert cfg_lib.OryxConfig.from_json(big.to_json()) == big
    tiny = cfg_lib.sdar_tiny()
    params = oryx.init_params(tiny, jax.random.key(0))
    assert set(params) == {"llm"}
    assert "gate_proj" not in params["llm"]["layers"]
    assert params["llm"]["layers"]["router"]["kernel"].dtype == jnp.float32
    assert cfg_lib.OryxConfig.from_json(
        cfg_lib.oryx_tiny().to_json()).vision == cfg_lib.tiny_vision()


@pytest.mark.parametrize("bad", [
    dict(block_length=3), dict(num_experts=4, num_experts_per_tok=5,
                               moe_intermediate_size=8),
    dict(num_experts=4, num_experts_per_tok=2),
])
def test_config_refuses_what_the_layers_cannot_run(bad):
    with pytest.raises(ValueError):
        dataclasses.replace(cfg_lib.tiny_llm(), **bad)


def test_generation_config_refuses_an_unknown_rule():
    with pytest.raises(ValueError, match="remasking"):
        cfg_lib.GenerationConfig(remasking="random")
