"""LFM2-24B-A2B at `lfm2_tiny` on the CPU, float32: the layer table
(the published 40-entry list and the benchmark's ten-layer cut), the
gated short convolution's chunk and step forms, the `conv` plane beside
the paged pool with no `ssm` plane, the page-edge snapshots
(`conv_edge`) and the hand-over at a prefix hit, the sigmoid router with
its selection bias and `sum + 1e-6`, all against the plain reference
(benchmark/reference/lfm2_ref.py); and what is still refused, by name."""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark.reference import lfm2_ref as ref
from oryx_tpu import config as cfg_lib
from oryx_tpu.models import generate, qwen2, short_conv
from oryx_tpu.ops import paged_kv

F32 = jnp.float32
TOL = 1e-5  # float32 on both sides: summation order only
PS = 16
REFUSAL = "is not built for a recurrent state beside the paged pool"


def keys_of(llm) -> dict:
    """The source's keys for what the tiny config runs."""
    return {
        "hidden_size": llm.hidden_size, "num_attention_heads": llm.num_heads,
        "num_hidden_layers": llm.num_layers,
        "layer_types": list(llm.layer_types),
        "num_dense_layers": llm.dense_layers,
        "conv_L_cache": llm.conv_L_cache,
        "num_key_value_heads": llm.num_kv_heads,
        "rope_theta": llm.rope_theta, "norm_eps": llm.rms_norm_eps,
        "num_experts_per_tok": llm.num_experts_per_tok,
        "norm_topk_prob": llm.norm_topk_prob,
        "routed_scaling_factor": llm.routed_scaling_factor,
        "use_expert_bias": llm.router_bias,
    }


def sizes_of(llm) -> dict:
    return ref.sizes_from_keys(keys_of(llm))


def scaled(params):
    """Kernels times 4 and norm weights away from 1: at 0.02 every
    layer adds little and a missing norm would not show."""
    def leaf(path, a):
        name = jax.tree_util.keystr(path)
        if "norm" in name and "weight" in name:
            return 1 + 0.1 * jax.random.normal(
                jax.random.key(len(name)), a.shape)
        if "kernel" in name and "conv" not in name and "router" not in name:
            return a * 4
        if "router" in name and "kernel" in name:
            return a * 20  # probabilities away from 1/2
        return a
    return jax.tree_util.tree_map_with_path(leaf, params)


def tiny_cfg(depth: int):
    return dataclasses.replace(cfg_lib.lfm2_tiny().llm, num_layers=depth)


@pytest.fixture(scope="module", params=[10, 40], ids=["cut10", "list40"])
def tiny(request):
    cfg = tiny_cfg(request.param)
    return cfg, scaled(qwen2.init_params(cfg, jax.random.key(0)))


@pytest.fixture(scope="module")
def cut():
    cfg = tiny_cfg(10)
    return cfg, scaled(qwen2.init_params(cfg, jax.random.key(0)))


def _greedy(n):
    return (jnp.zeros((n,)), jnp.ones((n,)), jnp.zeros((n,), jnp.int32))


def _pool(cfg, slots, pages_a_slot=8):
    kv = qwen2.init_paged_kv_cache(
        cfg, slots * pages_a_slot, PS, dtype=F32, num_slots=slots)
    bt = jnp.arange(slots * pages_a_slot, dtype=jnp.int32).reshape(
        slots, pages_a_slot)
    return kv, bt


def _prefill(params, cfg, kv, bt_rows, ids, slots, chunk, start=0):
    """Rows of ids (unequal lengths allowed) through `paged_prefill` in
    right-padded chunks from `start`. Returns (kv, logits [B, V] of
    each row's last token)."""
    B = len(ids)
    n = np.asarray([len(r) for r in ids])
    T = int(n.max())
    toks = np.zeros((B, T), np.int32)
    for b, r in enumerate(ids):
        toks[b, :len(r)] = r
    emb = generate.pad_embeds_for_chunks(
        params["embed"]["weight"][jnp.asarray(toks)], chunk)
    last = [None] * B
    for off in range(start, T, chunk):
        rows = [b for b in range(B) if n[b] > off]
        sel = np.asarray(rows)
        kv, tok, _, routing = generate.paged_prefill(
            params, cfg, emb[sel, off:off + chunk],
            jnp.asarray(np.minimum(off + chunk, n[sel]), jnp.int32),
            bt_rows[sel], kv, jnp.full((len(rows),), off, jnp.int32),
            jax.random.split(jax.random.key(0), len(rows)),
            *_greedy(len(rows)),
            slots=jnp.asarray(np.asarray(slots)[sel], jnp.int32),
            return_routing=True)
        for i, b in enumerate(rows):
            if n[b] <= off + chunk:
                last[b] = np.asarray(routing["logits"][i])
    return kv, last


def test_the_plan_scans_whole_periods_and_unrolls_the_ragged_ends():
    llm = cfg_lib.lfm2_tiny().llm
    lead, period, reps, tail = llm.layer_plan()
    assert lead == (("conv", "dense"),) * 2 and reps == 9
    assert [k for k, _ in period] == ["attn", "conv", "conv", "conv"]
    assert tail == (("attn", "moe"), ("conv", "moe"))
    cut = tiny_cfg(10)
    assert cut.layer_plan()[2:] == (2, ()) and cut.num_attn_layers == 2
    assert cut.state_kind == "conv" and llm.num_state_layers == 30
    # Jamba's period and offset are one case of the same table.
    jam = cfg_lib.jamba_tiny().llm
    assert jam.layer_plan() == (
        (), (("mamba", "own"),) * 2 + (("attn", "own"), ("mamba", "own")),
        2, ())
    big = cfg_lib.jamba2_3b().llm
    assert big.layer_plan()[2] == 2 and big.state_kind == "mamba"
    assert [i for i, k in enumerate(big.layer_kinds) if k == "attn"] == [7, 21]


def test_the_published_parameter_count():
    """The whole model's 23.84 B and the ten-layer cut's 5,267,090,176
    (ISSUE 56's arithmetic), from shapes alone."""
    def count(llm):
        shapes = jax.eval_shape(
            lambda k: qwen2.init_params(llm, k, jnp.bfloat16),
            jax.random.key(0))
        return sum(x.size for x in jax.tree.leaves(shapes))

    llm = cfg_lib.lfm2_24b_a2b().llm
    assert count(llm) == (2 * 89_139_200 + 10 * 614_600_896
                          + 28 * 620_898_368 + 134_219_776)
    cut = dataclasses.replace(llm, num_layers=10)
    assert count(cut) == 5_267_090_176
    assert cut.state_bytes_per_slot(2) == 65_536


def test_forward_without_a_cache_matches_the_reference(tiny):
    cfg, params = tiny
    ids = jax.random.randint(jax.random.key(1), (2, 37), 3, cfg.vocab_size)
    got, cache = qwen2.forward(params, cfg, input_ids=ids)
    assert cache is None
    for b in range(2):
        want = ref.logits(params, sizes_of(cfg), ids[b])
        assert float(jnp.max(jnp.abs(got[b] - want))) < TOL


def test_the_pool_has_a_conv_plane_an_edge_plane_and_no_ssm_plane(cut):
    cfg, _ = cut
    kv, _ = _pool(cfg, 3)
    Lc = cfg.num_state_layers
    assert set(kv) == {"k", "v", "conv", "conv_edge"}
    assert kv["conv"].shape == (Lc, 3, 2 * cfg.hidden_size)
    assert kv["conv_edge"].shape == (Lc, 24, 2 * cfg.hidden_size)
    assert kv["k"].shape[0] == cfg.num_attn_layers
    assert set(paged_kv.paged_planes(kv)) == {"k", "v", "conv_edge"}
    # A page mover moves the snapshot with the page and no slot's rows.
    kv = dict(kv, conv_edge=kv["conv_edge"].at[:, 5].set(3.0),
              conv=kv["conv"].at[:, 1].set(2.0))
    kv = paged_kv.copy_pages(kv, jnp.asarray(5), jnp.asarray(9))
    assert float(kv["conv_edge"][0, 9, 0]) == 3.0
    assert float(jnp.sum(kv["conv"])) == 2.0 * Lc * 2 * cfg.hidden_size


def test_single_steps_equal_the_chunk_and_a_dead_lane_keeps_its_rows(cut):
    cfg, params = cut
    lp = jax.tree_util.tree_map(
        lambda a: a[1], params["layers"]["conv"]["mixer"])
    d = cfg.hidden_size
    u = jax.random.normal(jax.random.key(6), (2, 12, d))
    zero = jnp.zeros((2, 2, d))
    want, st_w, _ = short_conv.mixer_prefill(
        cfg, lp, u, zero, jnp.ones((2, 12), bool))
    want_ref = ref.short_conv(u[0], lp, sizes_of(cfg))
    assert float(jnp.max(jnp.abs(want[0] - want_ref))) < TOL
    st, outs = zero, []
    for t in range(12):
        o, st = short_conv.mixer_step(
            cfg, lp, u[:, t:t + 1], st, jnp.asarray([True, True]))
        outs.append(o)
    assert float(jnp.max(jnp.abs(jnp.concatenate(outs, 1) - want))) < TOL
    assert float(jnp.max(jnp.abs(st - st_w))) < TOL
    _, st2 = short_conv.mixer_step(
        cfg, lp, u[:, :1], st, jnp.asarray([True, False]))
    assert float(jnp.max(jnp.abs(st2[1] - st[1]))) == 0.0
    assert float(jnp.max(jnp.abs(st2[0] - st[0]))) > 0.0
    # A right-padded chunk leaves the window of its last REAL token,
    # and one shorter than the window keeps the old window's tail.
    valid = jnp.broadcast_to(jnp.arange(12)[None] < 5, (2, 12))
    _, st_p, _ = short_conv.mixer_prefill(
        cfg, lp, u.at[:, 5:].set(7.0), zero, valid)
    _, st_5, _ = short_conv.mixer_prefill(
        cfg, lp, u[:, :5], zero, jnp.ones((2, 5), bool))
    assert float(jnp.max(jnp.abs(st_p - st_5))) == 0.0
    _, st_1, _ = short_conv.mixer_prefill(
        cfg, lp, u[:, :1], st, jnp.ones((2, 1), bool))
    assert float(jnp.max(jnp.abs(st_1[:, 0] - st[:, 1]))) == 0.0


@pytest.mark.parametrize("chunk", [8, 24, 64])
def test_chunked_prefill_of_unequal_lanes_then_decode_equal_the_reference(
        tiny, chunk):
    """Two prompts of 37 and 21 tokens in chunks whose edges lie off
    the 16-token page edges (8 under a page, 24 across them, 64 in one
    shot), right-padded, at slots 2 and 0 of 3; then 10 decode steps in
    chunks of 5, slot 1 riding as finished: every logit row is the
    reference's full forward over prompt and stream, the state at each
    slot is the same whatever the chunk, and a page's snapshot is the
    state after its last token."""
    cfg, params = tiny
    rng = np.random.default_rng(2)
    ids = [rng.integers(3, cfg.vocab_size, (n,)) for n in (37, 21)]
    kv, bt = _pool(cfg, 3)
    slots = [2, 0]
    kv, last = _prefill(params, cfg, kv, bt[np.asarray(slots)], ids, slots,
                        chunk)
    sz = sizes_of(cfg)
    for r, row in zip(ids, last):
        want = np.asarray(ref.logits(params, sz, r, rows=[len(r) - 1]))[0]
        assert np.max(np.abs(row - want)) < TOL
    one, bt1 = _pool(cfg, 3)
    one, _ = _prefill(params, cfg, one, bt1[np.asarray(slots)], ids, slots, 64)
    assert float(jnp.max(jnp.abs(kv["conv"] - one["conv"]))) < TOL
    assert not np.any(np.asarray(kv["conv"][:, 1]))
    # Pages 0 and 1 of slot 2's prompt (tokens 0-15, 16-31) and page 0
    # of slot 0's are full: their snapshots are the same whatever the
    # chunk; the pages the prompts end in hold none.
    full = [int(bt[2, 0]), int(bt[2, 1]), int(bt[0, 0])]
    edge, edge1 = np.asarray(kv["conv_edge"]), np.asarray(one["conv_edge"])
    assert np.max(np.abs(edge[:, full] - edge1[:, full])) < TOL
    assert np.all(np.abs(edge[:, full]).sum(-1) > 0)
    assert not np.any(edge[:, [int(bt[2, 2]), int(bt[0, 1])]])
    # The snapshot of slot 2's page 1 is the state after token 31.
    kv32, _ = _pool(cfg, 3)
    kv32, _ = _prefill(params, cfg, kv32, bt[2:3], [ids[0][:32]], [2], 64)
    assert np.max(np.abs(
        edge[:, full[1]] - np.asarray(kv32["conv"][:, 2]))) < TOL

    S = 3
    tok0 = [int(r.argmax()) for r in last]
    state = (jnp.zeros((S,), jnp.int32).at[2].set(tok0[0]).at[0].set(tok0[1]),
             jnp.asarray([21, 0, 37], jnp.int32),
             jnp.asarray([False, True, False]),
             jnp.zeros((S, 0), jnp.int32),
             jax.random.split(jax.random.key(1), S))
    rows, streams = [], {0: [tok0[1]], 2: [tok0[0]]}
    for _ in range(2):
        out = generate.paged_decode_chunk(
            params, cfg, kv, bt, *state, *_greedy(S), chunk=5,
            eos=cfg.vocab_size, return_routing=True)
        kv, state = out[0], out[1:6]
        rows.append(np.asarray(out[-2]))  # [S, chunk, V]
        for s in streams:
            streams[s] += [int(t) for t in np.asarray(out[6][s])][1:]
            streams[s].append(int(state[0][s]))
    got = np.concatenate(rows, axis=1)
    for s, r in ((2, ids[0]), (0, ids[1])):
        full_ids = np.concatenate([r, np.asarray(streams[s][:-1], np.int32)])
        want = np.asarray(ref.logits(params, sz, full_ids))
        n = len(r)
        assert np.max(np.abs(got[s] - want[n:n + 10])) < TOL
        assert streams[s] == [int(t) for t in want[n - 1:n + 10].argmax(-1)]
    assert not np.any(np.asarray(kv["conv"][:, 1]))
    # Slot 2 decoded positions 37..46: token 47 is the last of page 2,
    # which it has not fed. Slot 0 fed 21..30; 31 not yet. One more
    # chunk of 5 feeds both edges (47 only if it gets there: 47..51).
    before = np.asarray(kv["conv_edge"])
    out = generate.paged_decode_chunk(
        params, cfg, kv, bt, *state, *_greedy(S), chunk=5,
        eos=cfg.vocab_size)
    after = np.asarray(out[0]["conv_edge"])
    p0, p2 = int(bt[0, 1]), int(bt[2, 2])
    assert not np.any(before[:, [p0, p2]])
    assert np.all(np.abs(after[:, [p0, p2]]).sum(-1) > 0)
    rest = [p for p in range(after.shape[1]) if p not in (p0, p2)]
    assert np.array_equal(after[:, rest], before[:, rest])


def test_the_page_walk_reads_two_heads_a_row_as_the_gather_does(cut):
    """The pool keeps `kv_pack` key/value heads side by side in a row
    of lanes ([La, P, page, Hk / 2, 2 * D] here and at the published
    head of 64). Under `attn_impl="pallas"` (interpret mode on the CPU)
    the decode step's page walk reads such rows with the query in its
    own head's lanes; the "xla" step gathers and splits them: the same
    logits, and the reference's."""
    cfg, params = cut
    assert cfg.kv_pack == 2
    assert cfg_lib.lfm2_24b_a2b().llm.kv_pack == 2
    assert cfg_lib.jamba2_3b().llm.kv_pack == 1
    ids = np.random.default_rng(8).integers(3, cfg.vocab_size, (2, 21))
    rows = {}
    for impl in ("xla", "pallas"):
        kv, bt = _pool(cfg, 2)
        assert kv["k"].shape[-2:] == (1, 32)
        kv, last = _prefill(params, cfg, kv, bt, list(ids), [0, 1], 24)
        state = (jnp.asarray([int(r.argmax()) for r in last], jnp.int32),
                 jnp.asarray([21, 21], jnp.int32), jnp.zeros((2,), bool),
                 jnp.zeros((2, 0), jnp.int32),
                 jax.random.split(jax.random.key(1), 2))
        out = generate.paged_decode_chunk(
            params, cfg, kv, bt, *state, *_greedy(2), chunk=3,
            eos=cfg.vocab_size, return_routing=True, attn_impl=impl)
        rows[impl] = np.asarray(out[-2])
    assert np.max(np.abs(rows["pallas"] - rows["xla"])) < TOL


def _cold_and_hit(cfg, params, ids, cached: int, pages, *, chunk=24):
    """The last-token logits of `ids` prefilled cold at slot 0, and
    prefilled from `cached` tokens on at slot 1 behind the block table
    `pages` with the snapshot of the last cached page handed over."""
    kv, bt = _pool(cfg, 2)
    kv, cold = _prefill(params, cfg, kv, bt[:1], [ids], [0], chunk)
    row = np.asarray(bt[1]).copy()
    row[:cached // PS] = pages[:cached // PS]
    kv = paged_kv.handover_state(
        kv, jnp.asarray(int(row[cached // PS - 1])), jnp.asarray(1))
    kv, hit = _prefill(params, cfg, kv, jnp.asarray(row)[None], [ids], [1],
                       chunk, start=cached)
    return kv, bt, cold[0], hit[0]


@pytest.mark.parametrize("cached", [16, 32])
def test_a_prompt_served_after_a_hit_on_prefilled_pages_equals_the_cold_one(
        tiny, cached):
    """Slot 1 shares slot 0's first pages (written by slot 0's prefill)
    and starts from the last shared page's snapshot: its logits are the
    cold prompt's and the reference's, and the shared pages, their
    snapshots and slot 0's rows are as they were."""
    cfg, params = tiny
    ids = np.random.default_rng(3).integers(3, cfg.vocab_size, (45,))
    kv0, bt = _pool(cfg, 2)
    kv0, _ = _prefill(params, cfg, kv0, bt[:1], [ids], [0], 24)
    kv, bt, cold, hit = _cold_and_hit(
        cfg, params, ids, cached, np.asarray(bt[0]))
    want = np.asarray(ref.logits(params, sizes_of(cfg), ids, rows=[44]))[0]
    assert np.max(np.abs(cold - want)) < TOL
    assert np.max(np.abs(hit - want)) < TOL
    shared = np.asarray(bt[0, :cached // PS])
    for n in ("k", "v", "conv_edge"):
        assert np.array_equal(np.asarray(kv[n][:, shared]),
                              np.asarray(kv0[n][:, shared]))
    assert np.array_equal(np.asarray(kv["conv"][:, 0]),
                          np.asarray(kv0["conv"][:, 0]))
    # Both lanes end in the same state.
    assert float(jnp.max(jnp.abs(kv["conv"][:, 0] - kv["conv"][:, 1]))) < TOL


def test_a_hit_on_pages_filled_during_decode_equals_the_cold_prompt(cut):
    """A 20-token prompt at slot 0 decodes 30 tokens: pages 1 and 2
    (tokens 16-47) are filled by decode steps, whose last tokens leave
    their snapshots. A second request re-sends prompt and reply and 9
    tokens more, shares those three pages and starts from page 2's
    snapshot: its logits are the cold ones and the reference's."""
    cfg, params = cut
    rng = np.random.default_rng(4)
    ids = rng.integers(3, cfg.vocab_size, (20,))
    kv, bt = _pool(cfg, 2)
    kv, last = _prefill(params, cfg, kv, bt[:1], [ids], [0], 24)
    S = 2
    state = (jnp.asarray([int(last[0].argmax()), 0], jnp.int32),
             jnp.asarray([20, 0], jnp.int32), jnp.asarray([False, True]),
             jnp.zeros((S, 0), jnp.int32),
             jax.random.split(jax.random.key(1), S))
    stream = [int(state[0][0])]
    for _ in range(5):
        out = generate.paged_decode_chunk(
            params, cfg, kv, bt, *state, *_greedy(S), chunk=6,
            eos=cfg.vocab_size)
        kv, state = out[0], out[1:6]
        stream += [int(t) for t in np.asarray(out[6][0])][1:]
        stream.append(int(state[0][0]))
    history = np.concatenate([ids, np.asarray(stream[:30], np.int32)])
    assert int(state[1][0]) == 50  # 48 tokens and more have K/V
    more = np.concatenate([history, rng.integers(3, cfg.vocab_size, (9,))])
    row = np.asarray(bt[1]).copy()
    row[:3] = np.asarray(bt[0, :3])
    kv = paged_kv.handover_state(kv, jnp.asarray(int(row[2])), jnp.asarray(1))
    kv, hit = _prefill(params, cfg, kv, jnp.asarray(row)[None], [more], [1],
                       24, start=48)
    want = np.asarray(ref.logits(
        params, sizes_of(cfg), more, rows=[len(more) - 1]))[0]
    assert np.max(np.abs(hit[0] - want)) < TOL


def test_the_snapshot_of_the_page_before_is_not_the_state(cut):
    """The control of the comparison, at tiny widths: handing over the
    page BEFORE the right one, or zeros, moves the logits."""
    cfg, params = cut
    ids = np.random.default_rng(5).integers(3, cfg.vocab_size, (45,))
    kv0, bt = _pool(cfg, 2)
    kv0, cold = _prefill(params, cfg, kv0, bt[:1], [ids], [0], 24)
    for wrong in (int(bt[0, 0]), int(bt[1, 7])):  # page before; a zero row
        row = np.asarray(bt[1]).copy()
        row[:2] = np.asarray(bt[0, :2])
        kv = paged_kv.handover_state(
            jax.tree_util.tree_map(jnp.copy, kv0), jnp.asarray(wrong),
            jnp.asarray(1))
        _, hit = _prefill(params, cfg, kv, jnp.asarray(row)[None], [ids],
                          [1], 24, start=32)
        assert np.max(np.abs(hit[0] - cold[0])) > 100 * TOL


def test_the_selection_bias_changes_which_experts_run_not_their_weights(cut):
    cfg, params = cut
    x = jax.random.normal(jax.random.key(7), (64, cfg.hidden_size))
    router = jax.tree_util.tree_map(lambda a: a[0], params["layers"]["router"])
    w, idx = qwen2.moe_route(cfg, x, router["kernel"], router["bias"] * 8)
    w0, idx0 = qwen2.moe_route(cfg, x, router["kernel"], None)
    assert np.any(np.sort(np.asarray(idx), -1) != np.sort(np.asarray(idx0), -1))
    s = jax.nn.sigmoid(qwen2.router_logits(x, router["kernel"]))
    picked = jnp.take_along_axis(s, idx, axis=-1)
    want = picked / (jnp.sum(picked, -1, keepdims=True) + 1e-6)
    assert float(jnp.max(jnp.abs(w - want))) < 1e-7
    # ... and the published normaliser is the configuration's: without
    # norm_topk_eps the weights sum to 1 exactly as they did.
    bare = dataclasses.replace(cfg, norm_topk_eps=0.0)
    wb, _ = qwen2.moe_route(bare, x, router["kernel"], None)
    assert float(jnp.max(jnp.abs(jnp.sum(wb, -1) - 1))) < 1e-6
    assert float(jnp.max(jnp.sum(w0, -1))) < 1.0
    # The seeded bias is no no-op: the selection differs at some tokens.
    _, idx_b = qwen2.moe_route(cfg, x, router["kernel"], router["bias"])
    big = cfg_lib.lfm2_24b_a2b().llm
    assert big.router_bias and big.norm_topk_eps == 1e-6
    assert idx_b.shape == (64, cfg.num_experts_per_tok)


@pytest.mark.parametrize("bad", [
    {"block_length": 4, "mask_token_id": 511}, {"attention_bias": True},
    {"moe_activation": "relu"}, {"zero_experts": 2},
])
def test_the_config_refuses_what_is_not_built_for_a_state(bad):
    with pytest.raises(ValueError, match=REFUSAL):
        dataclasses.replace(cfg_lib.lfm2_tiny().llm, **bad)


def test_a_mamba_hybrid_with_experts_and_qk_norm_simply_runs():
    """What `LLMConfig` refused until PR 56 and the layer table now
    runs: Jamba's period and offset with an expert FFN, a leading dense
    layer and q/k norm. The paged chunks give the cache-less forward's
    logits."""
    cfg = dataclasses.replace(
        cfg_lib.jamba_tiny().llm, qk_norm=True, num_experts=4,
        num_experts_per_tok=2, moe_intermediate_size=32, dense_layers=1)
    assert cfg.ffn_kinds == ("dense",) + ("moe",) * 7
    params = scaled(qwen2.init_params(cfg, jax.random.key(0)))
    ids = np.random.default_rng(9).integers(3, cfg.vocab_size, (29,))
    want, _ = qwen2.forward(params, cfg, input_ids=jnp.asarray(ids)[None])
    kv, bt = _pool(cfg, 2)
    assert set(kv) == {"k", "v", "conv", "ssm"}
    kv, last = _prefill(params, cfg, kv, bt[1:], [ids], [1], 8)
    assert np.max(np.abs(last[0] - np.asarray(want[0, -1]))) < TOL


def test_a_short_or_unknown_layer_list_is_refused():
    llm = cfg_lib.lfm2_tiny().llm
    with pytest.raises(ValueError, match="layer_types"):
        dataclasses.replace(llm, num_layers=41)
    with pytest.raises(ValueError, match="layer_types"):
        dataclasses.replace(llm, layer_types=("conv", "mamba") * 20)
    with pytest.raises(ValueError, match="layer_types"):
        dataclasses.replace(llm, attn_layer_period=4)
    cfg = cfg_lib.lfm2_tiny()
    with pytest.raises(ValueError, match=REFUSAL):
        dataclasses.replace(cfg, mesh=cfg_lib.MeshConfig(tp=2))
    with pytest.raises(ValueError, match=REFUSAL):
        dataclasses.replace(cfg, attn_impl="ring")


def test_the_step_programs_refuse_by_name(cut):
    cfg, params = cut
    kv, bt = _pool(cfg, 2)
    ids = jnp.zeros((1, 4), jnp.int32)
    with pytest.raises(ValueError, match=REFUSAL):  # the ragged step's rows
        qwen2.forward(params, cfg, input_ids=ids, kv_cache=kv,
                      block_tables=bt, q_segments=jnp.zeros((1, 4), jnp.int32),
                      positions=jnp.zeros((1, 4), jnp.int32))
    with pytest.raises(ValueError, match=REFUSAL):  # packed training
        qwen2.forward(params, cfg, input_ids=ids,
                      segment_ids=jnp.ones((1, 4), jnp.int32))
    with pytest.raises(ValueError, match=REFUSAL):  # a dense cache
        qwen2.forward(params, cfg, input_ids=ids,
                      kv_cache=qwen2.init_kv_cache(cfg, 1, 8))
    with pytest.raises(ValueError, match=REFUSAL):  # no slot indices
        generate.paged_prefill(
            params, cfg, jnp.zeros((1, 4, cfg.hidden_size)),
            jnp.asarray([4]), bt[:1], kv, jnp.asarray([0]),
            jax.random.split(jax.random.key(0), 1), *_greedy(1))
    with pytest.raises(ValueError, match=REFUSAL):
        qwen2.init_paged_kv_cache(cfg, 8, PS, kv_dtype="int8", num_slots=2)
