"""NVIDIA-Nemotron-3-Super at `nemotron3_tiny` on the CPU: the layer
table of one sublayer a layer, the Mamba-2 mixer's chunked matmul form
against the token-by-token scan (chunks, carried state, ragged padding,
single steps), the per-slot planes beside the paged pool, the latent
non-gated experts with a held share and a shared expert, all against the
plain reference (benchmark/reference/nemotron_h_ref.py); the Pallas step
kernel against its `xla` twin; what is refused; the published geometry
and the parameter count."""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark import costs_nemotron
from benchmark.reference import nemotron_h_ref as ref
from oryx_tpu import config as cfg_lib
from oryx_tpu.models import generate, mamba2, qwen2
from oryx_tpu.ops import paged_kv
from oryx_tpu.ops.pallas import ssd_step

F32 = jnp.float32
TOL = 5e-6  # float32 on both sides: summation order only
PS = 16
REFUSAL = "is not built for a recurrent state beside the paged pool"


def keys_of(llm) -> dict:
    """The source's keys of a program config (what the benchmark's
    configuration file holds at the published widths)."""
    return {
        "hybrid_override_pattern": llm.hybrid_override_pattern,
        "num_hidden_layers": llm.num_layers, "hidden_size": llm.hidden_size,
        "vocab_size": llm.vocab_size,
        "num_attention_heads": llm.num_heads,
        "num_key_value_heads": llm.num_kv_heads, "head_dim": llm.head_dim,
        "mamba_num_heads": llm.mamba_num_heads,
        "mamba_head_dim": llm.mamba_head_dim,
        "n_groups": llm.mamba_n_groups, "ssm_state_size": llm.mamba_d_state,
        "conv_kernel": llm.mamba_d_conv, "chunk_size": llm.mamba_chunk_size,
        "layer_norm_epsilon": llm.rms_norm_eps,
        "n_routed_experts": llm.num_experts,
        "num_experts_per_tok": llm.num_experts_per_tok,
        "moe_intermediate_size": llm.moe_intermediate_size,
        "moe_latent_size": llm.moe_latent_size,
        "moe_shared_expert_intermediate_size":
            llm.moe_shared_expert_intermediate_size,
        "routed_scaling_factor": llm.routed_scaling_factor,
        "norm_topk_prob": llm.norm_topk_prob,
        "experts_first": llm.held[0], "experts_held": llm.held[1],
    }


def sizes_of(llm) -> dict:
    return ref.sizes_from_keys(keys_of(llm))


def _scaled(params):
    """Kernels times 4 and norm weights away from 1: at 0.02 every
    layer adds little and a missing norm would not show."""
    def leaf(path, a):
        name = jax.tree_util.keystr(path)
        if "norm" in name and "weight" in name:
            return jnp.asarray(1 + 0.1 * np.random.default_rng(
                len(name)).standard_normal(a.shape), a.dtype)
        if ("kernel" in name and "conv" not in name) or "experts" in name:
            return a * 4
        return a
    return jax.tree_util.tree_map_with_path(leaf, params)


def _init(cfg, seed):
    """Seeded weights in ONE jitted call (a leaf at a time compiles a
    program a leaf: 10 s a worker)."""
    return jax.jit(lambda k: qwen2.init_params(cfg, k))(jax.random.key(seed))


@pytest.fixture(scope="module")
def tiny():
    cfg = cfg_lib.nemotron3_tiny().llm
    return cfg, _scaled(_init(cfg, 0))


def _greedy(n):
    return (jnp.zeros((n,)), jnp.ones((n,)), jnp.zeros((n,), jnp.int32))


def _pool(cfg, slots, pages_a_slot=8):
    kv = qwen2.init_paged_kv_cache(
        cfg, slots * pages_a_slot, PS, dtype=F32, num_slots=slots)
    bt = jnp.arange(slots * pages_a_slot, dtype=jnp.int32).reshape(
        slots, pages_a_slot)
    return kv, bt


def _prefill(params, cfg, kv, bt, ids, slot, chunk):
    """ids through `paged_prefill` in right-padded chunks at `slot`.
    Returns (kv, first token, its logits)."""
    n = len(ids)
    emb = generate.pad_embeds_for_chunks(
        params["embed"]["weight"][jnp.asarray(ids)][None], chunk)
    for off in range(0, n, chunk):
        out = generate.paged_prefill(
            params, cfg, emb[:, off:off + chunk],
            jnp.asarray([min(off + chunk, n)], jnp.int32),
            bt[slot:slot + 1], kv, jnp.asarray([off], jnp.int32),
            jax.random.split(jax.random.key(0), 1), *_greedy(1),
            slots=jnp.asarray([slot], jnp.int32), return_logits=True)
        kv, tok, logits = out[0], out[1], out[-1]
    return kv, int(tok[0]), np.asarray(logits[0])


def _mixer(params, i=1):
    return jax.tree_util.tree_map(
        lambda a: a[i], params["layers"]["mamba2"]["mixer"])


def _mixer_sizes(cfg):
    return {"m_heads": cfg.mamba_num_heads, "m_head": cfg.mamba_head_dim,
            "groups": cfg.mamba_n_groups, "state": cfg.mamba_d_state,
            "eps": cfg.rms_norm_eps}


def _plane(S):
    """The reference's S [nh, P, N] as the pool keeps it, [N, nh P]."""
    nh, P, N = S.shape
    return jnp.transpose(S, (2, 0, 1)).reshape(N, nh * P)


def test_the_layer_table_is_one_sublayer_a_layer():
    llm = cfg_lib.nemotron3_tiny().llm
    assert llm.hybrid_override_pattern[:11] == "MEMEMEM*EME"
    assert llm.layer_kinds == (
        "mamba2", "none", "mamba2", "none", "mamba2", "none", "mamba2",
        "attn", "none", "mamba2", "none")
    assert [k for k in llm.ffn_kinds if k != "none"] == ["moe"] * 5
    assert all((k == "none") != (f == "none")
               for k, f in zip(llm.layer_kinds, llm.ffn_kinds))
    lead, period, reps, tail = llm.layer_plan()
    assert (lead, reps) == ((), 3)
    assert period == (("mamba2", "none"), ("none", "moe"))
    assert [k for k, _ in tail] == ["mamba2", "attn", "none", "mamba2", "none"]
    assert (llm.state_kind, llm.num_state_layers, llm.cache_layers,
            llm.moe_layers) == ("mamba2", 5, 1, 5)


def test_forward_without_a_cache_matches_the_reference(tiny):
    cfg, params = tiny
    ids = jax.random.randint(jax.random.key(1), (2, 37), 3, cfg.vocab_size)
    got, cache, routing = qwen2.forward(
        params, cfg, input_ids=ids, return_routing=True)
    assert cache is None
    for b in range(2):
        want, chose = ref.logits(
            params, sizes_of(cfg), ids[b], return_chosen=True)
        assert float(jnp.max(jnp.abs(got[b] - want))) < TOL
        mine = np.asarray(routing["ids"]).reshape(5, 2, 37, -1)[:, b]
        assert np.array_equal(np.sort(mine, -1), np.sort(np.stack(chose), -1))


@pytest.mark.parametrize("T", [5, 8, 19, 32])
def test_the_chunk_form_equals_the_scan_from_a_carried_state(tiny, T):
    """Lengths that are and are not multiples of the chunk of 8, from a
    state some earlier chunk left: the outputs and the state after are
    the token-by-token scan's."""
    cfg, params = tiny
    lp, sz = _mixer(params), _mixer_sizes(cfg)
    nh, P, N = cfg.mamba_num_heads, cfg.mamba_head_dim, cfg.mamba_d_state
    u = jax.random.normal(jax.random.key(T), (T, cfg.hidden_size))
    conv0 = 0.3 * jax.random.normal(
        jax.random.key(4), (cfg.mamba_d_conv - 1, cfg.mamba2_conv_dim))
    S0 = jax.random.normal(jax.random.key(5), (nh, P, N))
    with jax.default_matmul_precision("highest"):
        want, (conv_w, S_w) = ref.mamba2(
            u, lp, sz, state=(conv0, S0), return_state=True)
    got, (conv1, S1) = mamba2.mixer_prefill(
        cfg, lp, u[None], (conv0[None], _plane(S0)[None]),
        jnp.ones((1, T), bool))
    assert float(jnp.max(jnp.abs(got[0] - want))) < TOL
    assert float(jnp.max(jnp.abs(conv1[0] - conv_w))) < TOL
    assert float(jnp.max(jnp.abs(S1[0] - _plane(S_w)))) < TOL


def test_ragged_valid_lengths_leave_each_rows_own_state(tiny):
    """Two rows of 13 and 20 real tokens in one right-padded call of 24
    against the shorter alone: the same outputs at the real positions, the
    same state; and the window a chunk shorter than it leaves is the
    old window's tail."""
    cfg, params = tiny
    lp = _mixer(params, 2)
    shapes = mamba2.state_shapes(cfg, 2)
    u = jax.random.normal(jax.random.key(3), (2, 24, cfg.hidden_size))
    state = (0.3 * jax.random.normal(jax.random.key(4), shapes[0]),
             jax.random.normal(jax.random.key(5), shapes[1]))
    n = jnp.asarray([13, 20])
    valid = jnp.arange(24)[None] < n[:, None]
    padded = jnp.where(valid[..., None], u, 7.0)
    out_p, st_p = mamba2.mixer_prefill(cfg, lp, padded, state, valid)
    b, m = 0, 13  # the shorter row alone, unpadded
    out_1, st_1 = mamba2.mixer_prefill(
        cfg, lp, u[b:b + 1, :m],
        tuple(a[b:b + 1] for a in state), jnp.ones((1, m), bool))
    assert float(jnp.max(jnp.abs(out_p[b, :m] - out_1[0]))) < TOL
    for a, w in zip(st_p, st_1):
        assert float(jnp.max(jnp.abs(a[b] - w[0]))) < TOL
    _, (win, _) = mamba2.mixer_prefill(
        cfg, lp, u[:, :1], state, jnp.ones((2, 1), bool))
    assert float(jnp.max(jnp.abs(win[:, :2] - state[0][:, 1:]))) == 0.0


def test_single_steps_equal_the_chunk_and_a_dead_lane_keeps_its_state(tiny):
    cfg, params = tiny
    lp = _mixer(params, 0)
    u = jax.random.normal(jax.random.key(6), (2, 12, cfg.hidden_size))
    zero = tuple(jnp.zeros(s) for s in mamba2.state_shapes(cfg, 2))
    want, st_w = mamba2.mixer_prefill(
        cfg, lp, u, zero, jnp.ones((2, 12), bool))
    st, outs = zero, []
    live = jnp.asarray([True, True])
    for t in range(12):
        o, st = mamba2.mixer_step(cfg, lp, u[:, t:t + 1], st, live)
        outs.append(o)
    assert float(jnp.max(jnp.abs(jnp.concatenate(outs, 1) - want))) < TOL
    for a, b in zip(st, st_w):
        assert float(jnp.max(jnp.abs(a - b))) < TOL
    _, st2 = mamba2.mixer_step(
        cfg, lp, u[:, :1], st, jnp.asarray([True, False]))
    assert float(jnp.max(jnp.abs(st2[1][1] - st[1][1]))) == 0.0
    assert float(jnp.max(jnp.abs(st2[0][1] - st[0][1]))) == 0.0
    assert float(jnp.max(jnp.abs(st2[1][0] - st[1][0]))) > 0.0


@pytest.mark.parametrize("chunk", [8, 16, 64])
def test_prefill_in_chunks_gives_one_state_and_one_logit_row(tiny, chunk):
    cfg, params = tiny
    ids = np.asarray(jax.random.randint(
        jax.random.key(2), (37,), 3, cfg.vocab_size))
    kv, bt = _pool(cfg, 3)
    kv, tok, logits = _prefill(params, cfg, kv, bt, ids, 1, chunk)
    want = np.asarray(ref.logits(params, sizes_of(cfg), ids, rows=[36]))[0]
    assert np.max(np.abs(logits - want)) < TOL
    assert tok == int(want.argmax())
    one, bt1 = _pool(cfg, 3)
    one, _, _ = _prefill(params, cfg, one, bt1, ids, 1, 64)
    for plane in paged_kv.SLOT_PLANES:
        assert float(jnp.max(jnp.abs(kv[plane] - one[plane]))) < TOL
        # the other slots' rows were never touched
        assert not np.any(np.asarray(kv[plane][:, [0, 2]]))


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_prefill_and_decode_through_the_slot_equal_the_reference(tiny, impl):
    """A 29-token prompt in two chunks of 16 at slot 1 of 3, then 12
    decode steps in chunks of 4 with the other lanes riding as
    finished: the logits of every step are the reference's full
    forward over prompt and stream, position by position."""
    cfg, params = tiny
    ids = np.asarray(jax.random.randint(
        jax.random.key(7), (29,), 3, cfg.vocab_size))
    kv, bt = _pool(cfg, 3)
    kv, tok, _ = _prefill(params, cfg, kv, bt, ids, 1, 16)
    S = 3
    state = (jnp.zeros((S,), jnp.int32).at[1].set(tok),
             jnp.zeros((S,), jnp.int32).at[1].set(29),
             jnp.ones((S,), bool).at[1].set(False),
             jnp.zeros((S, 0), jnp.int32),
             jax.random.split(jax.random.key(1), S))
    rows, stream = [], [tok]
    for _ in range(3):
        out = generate.paged_decode_chunk(
            params, cfg, kv, bt, *state, *_greedy(S), chunk=4,
            eos=cfg.vocab_size, return_logits=True, attn_impl=impl)
        kv, state = out[0], out[1:6]
        rows.append(np.asarray(out[-1][1]))
        stream += [int(t) for t in np.asarray(out[6][1])][1:]
        stream.append(int(state[0][1]))
    full = np.concatenate([ids, np.asarray(stream[:-1], np.int32)])
    want = np.asarray(ref.logits(params, sizes_of(cfg), full))
    got = np.concatenate(rows)
    assert np.max(np.abs(got - want[29:29 + 12])) < 4 * TOL
    assert stream == [int(t) for t in want[28:28 + 13].argmax(-1)]
    # the lanes that rode as finished hold no state
    for plane in paged_kv.SLOT_PLANES:
        assert not np.any(np.asarray(kv[plane][:, [0, 2]]))


@pytest.mark.parametrize("shape", [(4, 2, 128, 128), (3, 4, 256, 128)])
def test_the_step_kernel_in_interpret_mode_equals_its_xla_twin(shape):
    """[B, G, channels a group, N]: the live lanes' rows of layer 1 of 3
    advance as the twin's, a dead lane's rows and the other layers'
    stay bit for bit, a dead lane's y is zeros."""
    B, G, dg, N = shape
    d = G * dg
    assert ssd_step.fits(B, d, N, G)
    ks = jax.random.split(jax.random.key(8), 5)
    a = jnp.exp(-jax.nn.softplus(jax.random.normal(ks[0], (B, d))))
    dtx = jax.random.normal(ks[1], (B, d))
    bc = jax.random.normal(ks[2], (B, 2 * G * N))
    plane = jax.random.normal(ks[3], (3, B, N, d))
    live = jnp.ones((B,), jnp.int32).at[1].set(0)
    want_y, want_S = ssd_step.ssd_step_xla(a, dtx, bc, plane[1], G)
    got_y, got = ssd_step.ssd_step(a, dtx, bc, live, plane, jnp.int32(1), G)
    on = np.asarray(live, bool)
    assert float(jnp.max(jnp.abs(got_y[on] - want_y[on]))) < 2e-5
    assert float(jnp.max(jnp.abs(got[1][on] - want_S[on]))) < 2e-5
    assert not np.any(np.asarray(got_y[1]))
    assert np.array_equal(np.asarray(got[1, 1]), np.asarray(plane[1, 1]))
    assert np.array_equal(np.asarray(got)[[0, 2]], np.asarray(plane)[[0, 2]])


def test_the_step_kernel_is_taken_only_where_its_tiles_fit():
    assert ssd_step.fits(96, 8192, 128, 8)  # the published widths
    assert not ssd_step.fits(96, 8192, 16, 8)
    assert not mamba2.step_fits(cfg_lib.nemotron3_tiny().llm, 4)
    full = dataclasses.replace(
        cfg_lib.nemotron3_super_ep4().llm, num_layers=11)
    assert mamba2.step_fits(full, 96)


def _share_cfg(cfg, first, count):
    return dataclasses.replace(cfg, experts_held=(first, count))


def test_the_four_shares_add_up_to_the_uncut_layer(tiny):
    """Each quarter of the experts computed as its chip would (the
    router whole, the held experts' weighted sum through `W_up`), plus
    the shared expert counted ONCE, is the uncut reference's whole
    layer: `W_up` is linear, so the shares add behind it."""
    cfg, _ = tiny
    whole = dataclasses.replace(cfg, experts_held=None)
    params = _scaled(_init(whole, 3))
    layers, m = params["layers"], 2  # the third expert layer
    ffn = {k: jax.tree_util.tree_map(lambda a: a[m], layers[k])
           for k in ref.FFN_STACKS}
    x = jax.random.normal(jax.random.key(9), (33, cfg.hidden_size))
    with jax.default_matmul_precision("highest"):
        want, _ = ref.expert_layer(x, ffn, sizes_of(whole))
        shared = ref.relu2_mlp(x, ffn["shared"]["up_proj"]["kernel"],
                               ffn["shared"]["down_proj"]["kernel"])
    E = cfg.num_experts
    total = jnp.zeros_like(want)
    for first in range(0, E, E // 4):
        share = _share_cfg(cfg, first, E // 4)
        held = jax.tree_util.tree_map(
            lambda a: a[:, first:first + E // 4].reshape(
                (-1,) + a.shape[2:]), layers["experts"])
        y, routing = qwen2._moe(
            share, x, ffn["router"]["kernel"], held, jnp.int32(m),
            router_bias=ffn["router"]["bias"], latent=ffn["latent"])
        total = total + y
        assert int(routing["counts"].sum()) == int(jnp.sum(
            (routing["ids"] >= first) & (routing["ids"] < first + E // 4)))
    assert float(jnp.max(jnp.abs(total + shared - want))) < 4 * TOL
    assert float(jnp.max(jnp.abs(shared))) > 100 * TOL


def test_presets_state_the_published_geometry():
    llm = cfg_lib.nemotron3_super().llm
    pat = llm.hybrid_override_pattern
    assert (len(pat), pat.count("M"), pat.count("E"), pat.count("*")) == (
        88, 40, 40, 8)
    assert (llm.hidden_size, llm.mamba_d_inner, llm.mamba2_conv_dim,
            llm.mamba_d_state, llm.mamba_n_groups) == (
        4096, 8192, 10240, 128, 8)
    assert (llm.num_heads, llm.num_kv_heads, llm.head_dim) == (32, 2, 128)
    assert not llm.tie_word_embeddings and not llm.use_rope
    cut = dataclasses.replace(
        cfg_lib.nemotron3_super_ep4().llm, num_layers=11)
    assert cut.held == (0, 128) and cut.vocab_size == 32768
    assert (cut.num_state_layers, cut.cache_layers, cut.moe_layers) == (
        5, 1, 5)
    assert cut.state_bytes_per_slot(2) == 5 * 4_255_744
    assert cut.kv_pack == 1


@pytest.mark.parametrize("preset,layers,count", [
    ("nemotron3_super_ep4", 11, 4_648_163_712),
    ("nemotron3_super", 88, 120_668_707_840),
])
def test_the_parameter_count_is_the_costs_files(preset, layers, count):
    llm = dataclasses.replace(
        getattr(cfg_lib, preset)().llm, num_layers=layers)
    shapes = jax.eval_shape(
        lambda: qwen2.init_params(llm, jax.random.key(0), jnp.bfloat16))
    got = sum(a.size for a in jax.tree_util.tree_leaves(shapes))
    assert got == costs_nemotron.total_params(keys_of(llm)) == count


@pytest.mark.parametrize("bad", [
    {"attention_bias": True},
    {"zero_experts": 2},
    {"block_length": 4},
])
def test_the_config_refuses_what_is_not_built_for_a_state(bad):
    with pytest.raises(ValueError, match=REFUSAL):
        dataclasses.replace(cfg_lib.nemotron3_tiny().llm, **bad)


@pytest.mark.parametrize("bad", [
    {"hybrid_override_pattern": "MEMXMEM*EME"},
    {"hybrid_override_pattern": "MEME"},
    {"mamba_n_groups": 3},
    {"num_experts": 0, "num_experts_per_tok": 0, "router_bias": False,
     "experts_held": None, "n_shared_experts": 0},
])
def test_a_broken_pattern_is_refused(bad):
    with pytest.raises(ValueError, match="hybrid_override_pattern"):
        dataclasses.replace(cfg_lib.nemotron3_tiny().llm, **bad)


def test_latent_relu2_and_mamba2_keys_need_a_pattern():
    with pytest.raises(ValueError, match="hybrid_override_pattern"):
        dataclasses.replace(cfg_lib.tiny_llm(), mamba_num_heads=4)
    with pytest.raises(ValueError, match="hybrid_override_pattern"):
        dataclasses.replace(
            cfg_lib.lfm2_tiny().llm, moe_activation="relu2")


def test_the_step_programs_refuse_by_name(tiny):
    cfg, params = tiny
    kv, bt = _pool(cfg, 2)
    emb = jnp.zeros((1, 8, cfg.hidden_size))
    with pytest.raises(ValueError, match=REFUSAL):
        generate.paged_prefill(
            params, cfg, emb, jnp.asarray([8], jnp.int32), bt[:1], kv,
            jnp.asarray([0], jnp.int32),
            jax.random.split(jax.random.key(0), 1), *_greedy(1))
    with pytest.raises(ValueError, match="kv_dtype"):
        qwen2.init_paged_kv_cache(cfg, 8, PS, kv_dtype="int8", num_slots=2)
