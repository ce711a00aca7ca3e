"""Mistral-Small-4 at `mistral4_tiny` on the CPU: the single latent
block (one latent attention, then a shared expert beside the routed
experts), YaRN positions, the query's scale by position and the
bucketed prefill table against the plain reference
(benchmark/reference/mistral4_ref.py); the split engine end to end with
its new counters; what the config refuses; and that the programs of the
models the benchmark already has trace to the jaxprs they had."""

import dataclasses
import hashlib
import math

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark.reference import mistral4_ref as ref
from oryx_tpu import config as cfg_lib
from oryx_tpu.models import generate, oryx, qwen2
from oryx_tpu.ops import paged_kv, rope
from oryx_tpu.serve import scheduler as scheduler_lib
from oryx_tpu.serve.pipeline import OryxInference
from oryx_tpu.serve.scheduler import ContinuousScheduler
from oryx_tpu.utils.metrics import ServingMetrics

F32 = jnp.float32
TOL = 2e-6  # float32 on both sides: summation order only
PS = 16


@pytest.fixture(scope="module")
def tiny():
    cfg = cfg_lib.mistral4_tiny().llm
    params = qwen2.init_params(cfg, jax.random.key(0))
    # Norm weights away from 1, so a missing one would show.
    for j, name in enumerate(("q_a_norm", "kv_a_norm", "input_norm",
                              "post_attn_norm")):
        w = params["layers"][name]["weight"]
        params["layers"][name]["weight"] = (
            1 + 0.1 * jax.random.normal(jax.random.key(j), w.shape))
    return cfg, params


def _greedy(n):
    return (jnp.zeros((n,)), jnp.ones((n,)), jnp.zeros((n,), jnp.int32))


def test_forward_without_a_cache_matches_the_reference(tiny):
    """100 positions at an original length of 16: the query's scale
    takes 7 values and every pair of YaRN's ramp is live."""
    cfg, params = tiny
    ids = np.random.default_rng(0).integers(3, cfg.vocab_size, 100).astype(
        np.int32)
    got, _, routing = qwen2.forward(
        params, cfg, input_ids=jnp.asarray(ids)[None], return_routing=True)
    want, chosen = ref.logits(params, cfg, ids, return_experts=True)
    np.testing.assert_allclose(got[0], want, atol=TOL)
    assert np.array_equal(np.sort(routing["ids"], -1), np.sort(chosen, -1))
    # Filled up to a multiple of 64 with further tokens, which no row
    # before them sees: the same logits and experts at the 100.
    padded, same = ref.logits(params, cfg, ids, return_experts=True,
                              pad_to=64, rows=[0, 57, 99])
    np.testing.assert_allclose(padded, want[jnp.asarray([0, 57, 99])],
                               atol=TOL)
    assert same.shape == chosen.shape and np.array_equal(same, chosen)
    forced = ref.logits(params, cfg, ids, forced_experts=chosen, pad_to=64)
    np.testing.assert_allclose(forced, want, atol=TOL)


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_chunked_prefill_across_two_table_widths_then_decode_matches(
        tiny, impl, monkeypatch):
    """A 150-token prompt in chunks of 32 through `paged_prefill`, each
    chunk handed the table cut to the width the scheduler would cut it
    to (64, 128 and 256 positions here), then two decode chunks through
    the absorbed walk: every row's logits are the reference's full
    forward's."""
    cfg, params = tiny
    monkeypatch.setattr(scheduler_lib, "PREFILL_TABLE_MIN", 64)
    maxp, chunk, n = 256 // PS, 32, 150
    widths = scheduler_lib.prefill_table_buckets(maxp, PS)
    assert widths == (4, 8, 16)
    ids = np.random.default_rng(1).integers(3, cfg.vocab_size, n).astype(
        np.int32)
    kv = qwen2.init_paged_kv_cache(cfg, 2 * maxp, PS, F32)
    assert kv[paged_kv.LATENT].shape == (cfg.num_layers, 2 * maxp, PS, 128)
    bt = jnp.arange(2 * maxp, dtype=jnp.int32).reshape(2, maxp)[::-1]
    emb = generate.pad_embeds_for_chunks(
        params["embed"]["weight"][jnp.asarray(ids)][None], chunk)
    keys = jax.random.split(jax.random.key(0), 1)
    used = set()
    for off in range(0, n, chunk):
        end = min(off + chunk, n)
        table = next(w for w in widths if w * PS >= off + chunk)
        used.add(table)
        kv, tok, keys, r = generate.paged_prefill(
            params, cfg,
            generate.slice_embeds(emb, jnp.asarray(off, jnp.int32),
                                  width=chunk),
            jnp.asarray([end], jnp.int32), bt[:1, :table], kv,
            jnp.asarray([off], jnp.int32), keys, *_greedy(1),
            attn_impl=impl, return_routing=True)
    assert used == {4, 8, 16}
    rows = [np.asarray(r["logits"])[0]]
    state = (jnp.asarray([int(tok[0]), 0]), jnp.asarray([n, 0], jnp.int32),
             jnp.asarray([False, True]), jnp.zeros((2, 0), jnp.int32),
             jax.random.split(jax.random.key(1), 2))
    fed = []
    for _ in range(2):
        out = generate.paged_decode_chunk(
            params, cfg, kv, bt, *state, *_greedy(2), chunk=4, eos=-1,
            attn_impl=impl, return_routing=True)
        kv, state = out[0], out[1:6]
        fed += [int(t) for t in np.asarray(out[6])[0]]
        rows += list(np.asarray(out[-2])[0])
    given = np.concatenate([ids, np.asarray(fed, np.int32)])
    want = ref.logits(params, cfg, given, rows=list(range(n - 1, n + 8)))
    np.testing.assert_allclose(np.stack(rows), want, atol=TOL)


def test_yarn_frequencies_of_the_published_parameters_by_hand():
    """theta 10000, factor 128, 8192 original positions, beta 32 and 1
    over the 64 rope columns: the ramp runs from pair floor(12.88) = 12
    to pair ceil(24.92) = 25."""
    llm = cfg_lib.mistral_small_4().llm
    assert rope.yarn_correction_range(64, 10000.0, 8192, 32, 1) == (12, 25)
    assert 64 * math.log(8192 / (32 * 2 * math.pi)) / (
        2 * math.log(10000)) == pytest.approx(12.88, abs=0.01)
    f = np.asarray(rope.yarn_frequencies(
        64, 10000.0, factor=128.0, original=8192, beta_fast=32, beta_slow=1))
    plain = 10000.0 ** (-np.arange(32) / 32.0)
    np.testing.assert_allclose(f[:13], plain[:13], rtol=1e-6)  # kept
    np.testing.assert_allclose(f[25:], plain[25:] / 128, rtol=1e-6)
    # Pair 18, 6/13 of the way up the ramp: 7/13 its own, 6/13 divided.
    assert f[18] == pytest.approx(
        plain[18] * (7 / 13 + 6 / 13 / 128), rel=1e-6)
    assert f[13] == pytest.approx(
        plain[13] * (12 / 13 + 1 / 13 / 128), rel=1e-6)
    assert np.all(np.diff(f) < 0)
    # The reference's own blend, written apart from the program's.
    np.testing.assert_allclose(ref.yarn_inv_freq(llm), f, rtol=1e-6)
    # Without scaling `rope_cos_sin` is what it was.
    pos = jnp.arange(40)[None]
    cos, sin = rope.rope_cos_sin(pos, 64, 10000.0)
    np.testing.assert_allclose(
        cos[0, :, :32], np.cos(np.arange(40)[:, None] * plain), atol=1e-5)
    assert llm.rope_cos_sin_scale == 1.0
    assert llm.softmax_scale == pytest.approx(
        128 ** -0.5 * (0.1 * math.log(128) + 1) ** 2)
    assert 0.1 * math.log(128) + 1 == pytest.approx(1.4852, abs=1e-4)


def test_query_scale_steps_at_multiples_of_the_original_length():
    llm = cfg_lib.mistral_small_4().llm
    pos = np.asarray([0, 8191, 8192, 16383, 16384, 24576, 32767])
    got = np.asarray(llm.query_position_scale(jnp.asarray(pos)))
    want = [1, 1, 1 + 0.1 * math.log(2), 1 + 0.1 * math.log(2),
            1 + 0.1 * math.log(3), 1 + 0.1 * math.log(4),
            1 + 0.1 * math.log(4)]
    np.testing.assert_allclose(got, want, rtol=1e-6)
    tiny = cfg_lib.mistral4_tiny().llm
    steps = np.asarray(tiny.query_position_scale(jnp.arange(100)))
    assert len(set(np.round(steps, 6))) == 7  # floor(99 / 16) + 1
    assert cfg_lib.longcat_tiny().llm.query_position_scale(pos) is None


@pytest.mark.parametrize("changed", [
    {},  # the published values: m = 1.4852, cos / sin x 1
    {"rope_mscale": 0.7, "rope_mscale_all_dim": 1.3},  # m != mscale
    {"rope_mscale_all_dim": 0.0},  # no m * m at all
    {"rope_scaling_factor": 40.0, "llama4_scaling_beta": 0.25,
     "rope_original_max_position": 4096},
])
def test_the_programs_conventions_equal_the_references_own(changed):
    """The reference works the three `assumed` conventions out from the
    raw fields (mistral4_ref.softmax_scale / cos_sin_scale /
    query_scale); LLMConfig has its own arithmetic. At values where
    `log` for `log1p`, the floor by another length, `m` for `m * m` or
    the ratio upside down would each read differently, the two agree,
    and at the published values they are the numbers worked by hand."""
    llm = dataclasses.replace(cfg_lib.mistral_small_4().llm, **changed)
    assert llm.softmax_scale == pytest.approx(ref.softmax_scale(llm), rel=1e-12)
    assert llm.rope_cos_sin_scale == pytest.approx(
        ref.cos_sin_scale(llm), rel=1e-12)
    pos = jnp.asarray([0, 4095, 4096, 8191, 8192, 12288, 16384, 32767])
    np.testing.assert_allclose(
        llm.query_position_scale(pos), ref.query_scale(llm, pos), rtol=1e-7)
    if not changed:
        assert ref.softmax_scale(llm) == pytest.approx(
            1.4852 ** 2 / math.sqrt(128), rel=1e-4)
        assert ref.cos_sin_scale(llm) == 1.0
        np.testing.assert_allclose(
            ref.query_scale(llm, pos),
            [1, 1, 1, 1, 1.0693147, 1.0693147, 1.1098612, 1.1386294],
            rtol=1e-6)
    elif "rope_mscale" in changed:
        assert ref.cos_sin_scale(llm) == pytest.approx(
            (0.07 * math.log(128) + 1) / (0.13 * math.log(128) + 1))
        assert ref.softmax_scale(llm) == pytest.approx(
            (0.13 * math.log(128) + 1) ** 2 / math.sqrt(128))


def test_four_shares_routed_parts_and_one_shared_expert_are_the_layer():
    """The share test at the published proportions: 128 routed experts,
    4 a token, the four shares of 32 (experts 0-31, 32-63, 64-95,
    96-127). Each share's program computes its routed part AND the
    shared expert; the uncut reference's layer is the four routed parts
    plus the shared expert counted once."""
    cfg = dataclasses.replace(
        cfg_lib.mistral4_tiny().llm, num_experts=128, num_experts_per_tok=4,
        experts_held=None, num_layers=1)
    params = qwen2.init_params(cfg, jax.random.key(4))
    lp = jax.tree.map(lambda a: a[0], params["layers"])
    x = jax.random.normal(jax.random.key(5), (24, cfg.hidden_size), F32)
    want, ids = ref.moe_layer(x, lp, cfg)
    shared_part = ref.swiglu(x, lp["shared"])
    total = shared_part
    for first in (0, 32, 64, 96):
        share = dataclasses.replace(cfg, experts_held=(first, 32))
        kernels = jax.tree.map(lambda a: a[0, first:first + 32],
                               params["layers"]["experts"])
        y, routing = qwen2._moe(
            share, x, lp["router"]["kernel"], kernels,
            jnp.asarray(0, jnp.int32), shared=lp["shared"])
        assert np.array_equal(routing["ids"], ids)
        assert int(routing["counts"].sum()) == int(
            np.sum((np.asarray(ids) >= first) & (np.asarray(ids) < first + 32)))
        total = total + (y - shared_part)
        # The reference's share is the program's.
        part, _ = ref.moe_layer(x, {**lp, "experts": kernels}, share)
        np.testing.assert_allclose(y, part, atol=TOL)
    np.testing.assert_allclose(total, want, atol=TOL)
    assert float(jnp.max(jnp.abs(shared_part))) > 0
    # Renormalised: a token's four weights sum to 1.
    w, _ = qwen2.moe_route(cfg, x, lp["router"]["kernel"])
    np.testing.assert_allclose(np.sum(w, -1), 1.0, atol=1e-6)


@pytest.mark.parametrize("bad, match", [
    ({"n_shared_experts": 1, "shortcut_double_layer": True,
      "intermediate_size": 128}, "n_shared_experts"),
    ({"kv_lora_rank": 0}, "latent attention only"),
    ({"num_experts": 0, "experts_held": None}, "expert decoder"),
    ({"rope_original_max_position": 0}, "rope_original_max_position"),
    ({"block_length": 4}, "expert decoder"),
    ({"n_shared_experts": -1}, "n_shared_experts"),
])
def test_config_refuses_what_the_layers_cannot_run(bad, match):
    with pytest.raises(ValueError, match=match):
        dataclasses.replace(cfg_lib.mistral4_tiny().llm, **bad)


def test_a_shared_expert_or_rope_scaling_off_the_latent_path_is_refused():
    with pytest.raises(ValueError, match="n_shared_experts"):
        dataclasses.replace(cfg_lib.sdar_tiny().llm, n_shared_experts=1)
    with pytest.raises(ValueError, match="latent attention only"):
        dataclasses.replace(
            cfg_lib.tiny_llm(), rope_scaling_factor=4.0,
            rope_original_max_position=64)


def test_presets_state_the_published_geometry_and_the_share():
    full = cfg_lib.mistral_small_4().llm
    share = cfg_lib.mistral_small_4_ep4().llm
    assert (full.num_layers, full.hidden_size, full.num_heads) == (36, 4096, 32)
    assert (full.num_experts, full.num_experts_per_tok,
            full.n_shared_experts) == (128, 4, 1)
    assert (full.kv_lora_rank, full.q_lora_rank, full.qk_nope_head_dim,
            full.qk_rope_head_dim, full.v_head_dim) == (256, 1024, 64, 64, 128)
    assert full.held == (0, 128) and share.held == (0, 32)
    assert (share.vocab_size, full.vocab_size) == (32768, 131072)
    assert share.latent_dim == 320 and share.latent_page_dim == 384
    assert share.cache_layers == 36 and not share.shortcut_double_layer
    assert cfg_lib.longcat_flash_chat_ep32().llm.cache_layers == 56
    assert cfg_lib.mistral_small_4().vision is None
    n = sum(a.size for a in jax.tree.leaves(jax.eval_shape(
        lambda: qwen2.init_params(
            dataclasses.replace(share, num_layers=6), jax.random.key(0)))))
    # ISSUE 33's arithmetic: 6 x (28.05 M + 25.17 M + 0.52 M + 32 x
    # 25.17 M) + 268.4 M of vocabulary, plus the norms.
    assert n == 5_422_771_712
    assert 6 * (28_049_408 + 25_165_824 + 524_288 + 32 * 25_165_824) \
        + 2 * 32768 * 4096 == n - 6 * 9472 - 4096
    rt = cfg_lib.OryxConfig.from_json(cfg_lib.mistral_small_4_ep4().to_json())
    assert rt.llm == share


# --- the split engine, end to end -----------------------------------------


class IdTokenizer:
    def encode(self, text, add_special_tokens=False):
        return [min(ord(c), 500) for c in text]

    def decode(self, ids, skip_special_tokens=True):
        return "".join(f"<{int(i)}>" for i in ids)


def _ids(reply):
    return [int(x) for x in reply.strip("<>").split("><")] if reply else []


@pytest.fixture(scope="module")
def pipe():
    cfg = cfg_lib.mistral4_tiny()
    return OryxInference(
        IdTokenizer(), oryx.init_params(cfg, jax.random.key(0)), cfg,
        template="plain")


def _want(pipe, question, cap):
    """The reference's greedy continuation and each step's top-two margin."""
    ids, *_ = pipe._prepare_request({"question": question})
    seq, margins = [int(t) for t in ids], []
    for _ in range(cap):
        row = np.asarray(ref.logits(
            pipe.params["llm"], pipe.cfg.llm, np.asarray(seq, np.int32),
            rows=[len(seq) - 1]))[0]
        top = np.sort(row)[-2:]
        margins.append(float(top[1] - top[0]))
        seq.append(int(row.argmax()))
    return seq[len(ids):], margins, len(ids)


def test_engine_serves_a_document_and_questions_with_the_new_counters(
        pipe, monkeypatch):
    """A document and two questions about it through the continuous
    split engine at table widths of 64 / 128 / 256 positions: the
    replies are the reference's, the second question finds the document
    in the prefix cache and holds only its uncached suffix's embeds, and
    the prefill counters say what the attention needed of what it was
    handed."""
    monkeypatch.setattr(scheduler_lib, "PREFILL_TABLE_MIN", 64)
    metrics = ServingMetrics()
    sched = ContinuousScheduler(
        pipe, num_slots=2, page_size=PS, max_ctx=256, prefill_chunk=32,
        autostart=False, metrics=metrics)
    assert sched.table_buckets == (4, 8, 16)
    doc = "the contract says: " + "clause; " * 16  # 147 tokens
    reqs = [(doc + "\nwho pays?", 6), (doc + "\nwho pays?\n<1><2>\nwhen?", 5)]
    sched.start()
    results = [sched.submit({"question": q}, cap, None).result(timeout=600)
               for q, cap in reqs]
    sched.close()
    for (q, cap), (reply, reason, usage) in zip(reqs, results):
        want, margins, n = _want(pipe, q, cap)
        assert reason == "length" and usage == (n, cap)
        for g, w, m in zip(_ids(reply), want, margins):
            if m <= 1e-4:
                break
            assert g == w
    n0, n1 = (usage[0] for _, _, usage in results)
    hit = metrics.get("prefix_cache_hit_tokens_total")
    assert hit >= 9 * PS  # the document's whole pages
    tokens = metrics.get("prefill_tokens_total")
    assert tokens == n0 + n1 - hit
    # Token p attends positions 0..p.
    pairs = n0 * (n0 + 1) // 2 + sum(range(int(hit) + 1, n1 + 1))
    assert metrics.get("prefill_attn_pairs_total") == pairs
    table = metrics.get("prefill_table_positions_total")
    # Chunks of 32 rows against tables of 64, 128 and 256 positions.
    assert table % (32 * 64) == 0 and pairs < table < 7 * 32 * 256
    assert metrics.get("prefill_live_positions_total") >= n0 + n1
    L, K = pipe.cfg.llm.num_layers, pipe.cfg.llm.num_experts_per_tok
    assert metrics.get("moe_shared_rows_total") == (
        tokens * L + metrics.get("moe_pairs_total") / K)
    slots = metrics.get("moe_held_expert_slots_total")
    assert 0 < metrics.get("moe_held_experts_hit_total") <= slots
    assert metrics.get("decode_kv_tokens_total") > 0
    assert metrics.get("moe_zero_pairs_total") == 0
    # The prefill chunks' own routing: every real row's picks, those the
    # reference routes to a held expert, a layer-forward a chunk.
    llm = pipe.cfg.llm
    first, count = llm.held
    assert metrics.get("moe_prefill_pairs_total") == tokens * L * K
    held = 0
    for (q, _), start in zip(reqs, (0, int(hit))):
        ids, *_ = pipe._prepare_request({"question": q})
        _, chosen = ref.logits(pipe.params["llm"], llm,
                               np.asarray(ids, np.int32), return_experts=True)
        chosen = np.asarray(chosen)[:, start:]
        held += int(np.sum((chosen >= first) & (chosen < first + count)))
    assert 0 < held < tokens * L * K
    assert metrics.get("moe_prefill_held_rows_total") == held
    chunks = -(-n0 // 32) + -(-(n1 - int(hit)) // 32)
    slots = metrics.get("moe_prefill_held_expert_slots_total")
    assert slots == chunks * L * count
    assert 0 < metrics.get("moe_prefill_held_experts_hit_total") <= slots


def test_the_prefill_span_carries_the_table_width(pipe, monkeypatch):
    monkeypatch.setattr(scheduler_lib, "PREFILL_TABLE_MIN", 64)
    sched = ContinuousScheduler(
        pipe, num_slots=2, page_size=PS, max_ctx=256, prefill_chunk=32,
        autostart=False)
    sched.start()
    h = sched.submit({"question": "x" * 100}, 2, None)
    h.result(timeout=600)
    sched.close()
    spans = [s for s in h.trace.to_dict()["spans"] if s["name"] == "prefill"]
    assert [s["args"]["table_positions"] for s in spans] == [64, 64, 128, 128]


@pytest.mark.parametrize("max_ctx, page, want", [
    (4096, 64, (64,)), (8192, 64, (128,)), (6144, 64, (96,)),
    (1024, 16, (64,)), (32768, 64, (128, 256, 512)),
    (12288, 64, (128, 192)), (16384, 16, (512, 1024)),
])
def test_a_context_of_8192_or_less_keeps_one_prefill_table_width(
        max_ctx, page, want):
    """chat (4,096), visual-batch, blockgen (1,024) and tool-sessions
    (6,144) compile the one `paged_prefill` they compiled before."""
    assert scheduler_lib.prefill_table_buckets(max_ctx // page, page) == want


def test_an_engine_under_8192_dispatches_the_whole_table(monkeypatch):
    cfg = cfg_lib.longcat_tiny()
    pipe = OryxInference(
        IdTokenizer(), oryx.init_params(cfg, jax.random.key(0)), cfg,
        template="plain")
    seen = []
    real = generate.paged_prefill

    def spy(params, llm, emb, lengths, tables, *a, **kw):
        seen.append(tables.shape)
        return real(params, llm, emb, lengths, tables, *a, **kw)

    monkeypatch.setattr(generate, "paged_prefill", spy)
    sched = ContinuousScheduler(
        pipe, num_slots=2, page_size=PS, max_ctx=256, prefill_chunk=32,
        autostart=False)
    assert sched.table_buckets == (16,)
    sched.start()
    sched.submit({"question": "y" * 70}, 2, None).result(timeout=600)
    sched.close()
    assert seen == [(1, 16)] * 3


# --- the models the benchmark already has trace as they did ----------------

# sha256 of the jaxpr's text, first 16 hex digits, of `qwen2.forward`
# for `longcat_tiny`, taken on the parent commit 3462bda (scratch script
# against `git archive`, this installation's jax 0.9.0, matmul precision
# "highest" as tests/conftest.py sets it). The dense models' and SDAR's
# are in tests/test_sdar_moe.py. A later PR that changes the double
# layer on purpose replaces them and says so.
LONGCAT_PARENT_JAXPRS = {
    "no_cache": "06ba8da1e5967907", "paged_prefill": "92794709a91ce4bb",
    "paged_decode": "1b18465298f884ea",
}


@pytest.mark.parametrize("branch", sorted(LONGCAT_PARENT_JAXPRS))
def test_longcat_forward_jaxpr_is_the_parents(branch):
    """The single block, the shared expert, YaRN, the query's scale and
    the softmax scale are chosen by the config alone: LongCat's double
    layer traces to the parent's jaxpr, character for character."""
    assert jax.config.jax_default_matmul_precision == "highest"
    cfg = cfg_lib.longcat_tiny().llm
    p = jax.eval_shape(lambda: qwen2.init_params(cfg, jax.random.key(0)))
    paged = jax.eval_shape(
        lambda: qwen2.init_paged_kv_cache(cfg, 8, 16, jnp.float32))
    bt = jax.ShapeDtypeStruct((2, 2), jnp.int32)
    mask = lambda: jnp.ones((2, 32), jnp.int32)  # noqa: E731
    if branch == "no_cache":
        jaxpr = jax.make_jaxpr(lambda p, i: qwen2.forward(
            p, cfg, input_ids=i, return_routing=True))(
                p, jax.ShapeDtypeStruct((2, 8), jnp.int32))
    elif branch == "paged_prefill":
        jaxpr = jax.make_jaxpr(lambda p, i, kv, bt: qwen2.forward(
            p, cfg, input_ids=i, kv_cache=kv, block_tables=bt,
            positions=jnp.zeros((2, 8), jnp.int32) + jnp.arange(8),
            kv_mask=mask()))(
                p, jax.ShapeDtypeStruct((2, 8), jnp.int32), paged, bt)
    else:
        jaxpr = jax.make_jaxpr(lambda p, i, kv, bt: qwen2.forward(
            p, cfg, input_ids=i, kv_cache=kv, block_tables=bt,
            positions=jnp.full((2, 1), 5, jnp.int32), kv_mask=mask(),
            kv_lengths=jnp.full((2,), 6, jnp.int32),
            write_mask=jnp.ones((2,), bool)))(
                p, jax.ShapeDtypeStruct((2, 1), jnp.int32), paged, bt)
    digest = hashlib.sha256(str(jaxpr).encode()).hexdigest()[:16]
    assert digest == LONGCAT_PARENT_JAXPRS[branch]


def test_longcat_seeded_weights_are_the_parents():
    """`_init_latent_params` draws the double layer's weights from the
    same keys in the same order as before the single block was added."""
    cfg = cfg_lib.longcat_tiny().llm
    p = qwen2.init_params(cfg, jax.random.key(0))
    assert set(p["layers"]) == {"sub0", "sub1", "router", "experts"}
    assert float(p["layers"]["experts"]["down"][0, 0, 0, 0]) == pytest.approx(
        -0.019135121256113052, rel=1e-6)
    assert float(p["lm_head"]["kernel"][0, 0]) == pytest.approx(
        0.013579378835856915, rel=1e-6)
