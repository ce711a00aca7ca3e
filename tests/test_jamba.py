"""AI21-Jamba2-3B at `jamba_tiny` on the CPU: the period scan of Mamba
and attention layers, the mixer's carried state (chunks, right padding,
single steps), the per-slot planes beside the paged pool and the split
engine over them (slot reuse, eviction and replay), all against the
plain reference (benchmark/reference/jamba_ref.py); the Pallas scan
against its `xla` twin; what is refused, in the one refusal's words;
and that the step programs of the models the benchmark already has
trace to the jaxprs they had."""

import dataclasses
import hashlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark import costs_ssm
from benchmark.reference import jamba_ref as ref
from oryx_tpu import config as cfg_lib
from oryx_tpu.models import generate, mamba, oryx, qwen2
from oryx_tpu.ops import paged_kv
from oryx_tpu.ops.pallas import selective_scan as ss
from oryx_tpu.serve.pipeline import OryxInference
from oryx_tpu.serve.scheduler import ContinuousScheduler
from oryx_tpu.utils.metrics import ServingMetrics

F32 = jnp.float32
TOL = 5e-6  # float32 on both sides: summation order only
PS = 16
REFUSAL = "is not built for a recurrent state beside the paged pool"


def sizes_of(llm) -> dict:
    return ref.sizes_from_keys({
        "hidden_size": llm.hidden_size, "num_attention_heads": llm.num_heads,
        "num_hidden_layers": llm.num_layers,
        "attn_layer_period": llm.attn_layer_period,
        "attn_layer_offset": llm.attn_layer_offset,
        "mamba_expand": llm.mamba_expand, "mamba_d_state": llm.mamba_d_state,
        "mamba_d_conv": llm.mamba_d_conv, "mamba_dt_rank": llm.mamba_dt_rank,
        "num_key_value_heads": llm.num_kv_heads, "head_dim": llm.head_dim,
        "rms_norm_eps": llm.rms_norm_eps,
    })


def _scaled(params):
    """Kernels times 4 and norm weights away from 1: at 0.02 every
    layer adds little and a missing norm would not show."""
    def leaf(path, a):
        name = jax.tree_util.keystr(path)
        if "norm" in name and "weight" in name:
            return 1 + 0.1 * jax.random.normal(
                jax.random.key(len(name)), a.shape)
        if "kernel" in name and "conv" not in name:
            return a * 4
        return a
    return jax.tree_util.tree_map_with_path(leaf, params)


@pytest.fixture(scope="module")
def tiny():
    cfg = cfg_lib.jamba_tiny().llm
    return cfg, _scaled(qwen2.init_params(cfg, jax.random.key(0)))


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
        kv, tok, _, logits = generate.paged_prefill(
            params, cfg, emb[:, off:off + chunk],
            jnp.asarray([min(off + chunk, n)], jnp.int32),
            bt[slot:slot + 1], kv, jnp.asarray([off], jnp.int32),
            jax.random.split(jax.random.key(0), 1), *_greedy(1),
            slots=jnp.asarray([slot], jnp.int32), return_logits=True)
    return kv, int(tok[0]), np.asarray(logits[0])


def test_forward_without_a_cache_matches_the_reference(tiny):
    cfg, params = tiny
    ids = jax.random.randint(jax.random.key(1), (2, 37), 3, cfg.vocab_size)
    got, cache = qwen2.forward(params, cfg, input_ids=ids)
    assert cache is None
    for b in range(2):
        want = ref.logits(params, sizes_of(cfg), ids[b])
        assert float(jnp.max(jnp.abs(got[b] - want))) < TOL


@pytest.mark.parametrize("chunk", [8, 16, 64])
def test_prefill_in_chunks_gives_one_state_and_one_logit_row(tiny, chunk):
    """Chunks of 8 and 16 (the last right-padded: 37 is no multiple)
    and one shot leave the same state at the slot and the same logits,
    and they are the reference's."""
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


def test_a_right_padded_chunk_equals_the_unpadded_one(tiny):
    """The mixer on 5 real tokens and 3 of padding against the 5 alone:
    the same outputs at the real positions, the same state; and the
    window a chunk shorter than it leaves is the old window's tail."""
    cfg, params = tiny
    lp = jax.tree_util.tree_map(
        lambda a: a[1], params["layers"]["mamba"]["mixer"])
    d, N, K = cfg.mamba_d_inner, cfg.mamba_d_state, cfg.mamba_d_conv
    u = jax.random.normal(jax.random.key(3), (2, 8, cfg.hidden_size))
    state = (jax.random.normal(jax.random.key(4), (2, K - 1, d)),
             jax.random.normal(jax.random.key(5), (2, N, d)))
    valid = jnp.broadcast_to(jnp.arange(8)[None] < 5, (2, 8))
    padded = u.at[:, 5:].set(7.0)
    out_p, st_p = mamba.mixer_prefill(cfg, lp, padded, state, valid)
    out_5, st_5 = mamba.mixer_prefill(
        cfg, lp, u[:, :5], state, jnp.ones((2, 5), bool))
    assert float(jnp.max(jnp.abs(out_p[:, :5] - out_5))) < TOL
    for a, b in zip(st_p, st_5):
        assert float(jnp.max(jnp.abs(a - b))) == 0.0
    _, (win, _) = mamba.mixer_prefill(
        cfg, lp, u[:, :1], state, jnp.ones((2, 1), bool))
    assert float(jnp.max(jnp.abs(win[:, :2] - state[0][:, 1:]))) == 0.0


def test_single_steps_equal_the_chunk_and_a_dead_lane_keeps_its_state(tiny):
    cfg, params = tiny
    lp = jax.tree_util.tree_map(
        lambda a: a[0], params["layers"]["mamba"]["mixer"])
    d, N, K = cfg.mamba_d_inner, cfg.mamba_d_state, cfg.mamba_d_conv
    u = jax.random.normal(jax.random.key(6), (2, 12, cfg.hidden_size))
    zero = (jnp.zeros((2, K - 1, d)), jnp.zeros((2, N, d)))
    want, st_w = mamba.mixer_prefill(
        cfg, lp, u, zero, jnp.ones((2, 12), bool))
    st, outs = zero, []
    live = jnp.asarray([True, True])
    for t in range(12):
        o, st = mamba.mixer_step(cfg, lp, u[:, t:t + 1], st, live)
        outs.append(o)
    assert float(jnp.max(jnp.abs(jnp.concatenate(outs, 1) - want))) < TOL
    for a, b in zip(st, st_w):
        assert float(jnp.max(jnp.abs(a - b))) < TOL
    _, st2 = mamba.mixer_step(
        cfg, lp, u[:, :1], st, jnp.asarray([True, False]))
    assert float(jnp.max(jnp.abs(st2[1][1] - st[1][1]))) == 0.0
    assert float(jnp.max(jnp.abs(st2[0][1] - st[0][1]))) == 0.0
    assert float(jnp.max(jnp.abs(st2[1][0] - st[1][0]))) > 0.0


def test_prefill_and_decode_through_the_slot_equal_the_reference(tiny):
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
            eos=cfg.vocab_size, return_logits=True)
        kv, state = out[0], out[1:6]
        rows.append(np.asarray(out[-1][1]))
        stream += [int(t) for t in np.asarray(out[6][1])][1:]
        stream.append(int(state[0][1]))
    full = np.concatenate([ids, np.asarray(stream[:-1], np.int32)])
    want = np.asarray(ref.logits(params, sizes_of(cfg), full))
    got = np.concatenate(rows)
    assert np.max(np.abs(got - want[29:29 + 12])) < TOL
    assert stream == [int(t) for t in want[28:28 + 13].argmax(-1)]
    # the lanes that rode as finished hold no state
    for plane in paged_kv.SLOT_PLANES:
        assert not np.any(np.asarray(kv[plane][:, [0, 2]]))


@pytest.mark.parametrize("shape", [(1, 16, 128, 8), (2, 32, 256, 16)])
def test_the_pallas_scan_in_interpret_mode_equals_its_xla_twin(shape):
    B, T, d, N = shape
    ks = jax.random.split(jax.random.key(8), 7)
    x, z = (jax.random.normal(k, (B, T, d)) for k in ks[:2])
    dt = jax.nn.softplus(jax.random.normal(ks[2], (B, T, d)) - 2)
    dt = dt.at[:, T - 3:].set(0.0)  # padding
    Bm, Cm = (jax.random.normal(k, (B, T, N)) for k in ks[3:5])
    A = -jnp.exp(jax.random.normal(ks[5], (N, d)) * 0.5)
    D = jnp.ones((d,))
    h0 = jax.random.normal(ks[6], (B, N, d))
    want = ss.selective_scan(x, dt, z, Bm, Cm, A, D, h0, impl="xla")
    got = ss.selective_scan(x, dt, z, Bm, Cm, A, D, h0, impl="pallas")
    for a, b in zip(got, want):
        assert float(jnp.max(jnp.abs(a - b))) < 2e-5
    # dt = 0 over the last three tokens: the state stopped moving there
    short = ss.selective_scan(
        x[:, :T - 3], dt[:, :T - 3], z[:, :T - 3], Bm[:, :T - 3],
        Cm[:, :T - 3], A, D, h0, impl="xla")
    assert float(jnp.max(jnp.abs(short[1] - want[1]))) < 1e-6


def test_the_scan_falls_back_where_its_tiles_do_not_fit():
    """A single token, or channels that are no multiple of 128: the
    `xla` twin whatever impl asks."""
    args = (jnp.ones((1, 1, 64)),) * 3 + (jnp.ones((1, 1, 8)),) * 2 + (
        -jnp.ones((8, 64)), jnp.ones((64,)), jnp.zeros((1, 8, 64)))
    a = ss.selective_scan(*args, impl="pallas")
    b = ss.selective_scan(*args, impl="xla")
    assert float(jnp.max(jnp.abs(a[0] - b[0]))) == 0.0


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
    cfg = cfg_lib.jamba_tiny()
    params = oryx.init_params(cfg, jax.random.key(0))
    params["llm"] = _scaled(params["llm"])
    return OryxInference(IdTokenizer(), params, cfg, template="plain")


def _want(pipe, question, cap):
    ids, *_ = pipe._prepare_request({"question": question})
    seq = [int(t) for t in ids]
    sz = sizes_of(pipe.cfg.llm)
    for _ in range(cap):
        row = np.asarray(ref.logits(
            pipe.params["llm"], sz, np.asarray(seq, np.int32),
            rows=[len(seq) - 1]))[0]
        seq.append(int(row.argmax()))
    return seq[len(ids):], len(ids)


QUESTIONS = [("hello there, how are you doing today my friend?", 9),
             ("abc" * 20, 7), ("zzz tell me a story", 12), ("q" * 33, 5)]


def _engine(pipe, metrics=None, **kw):
    return ContinuousScheduler(pipe, **{
        "num_slots": 2, "page_size": PS, "max_ctx": 256, "prefill_chunk": 16,
        "autostart": False, "metrics": metrics, **kw})


def test_engine_serves_four_requests_on_two_slots_with_the_counters(pipe):
    """Four requests over two slots through the continuous split engine
    (each slot is reused, a request prefills in one while the other
    decodes): every reply is the reference's greedy continuation, the
    prefix cache is off, and the ssm_* counters say what ran."""
    metrics = ServingMetrics()
    sched = _engine(pipe, metrics)
    assert sched.prefix_cache is None and sched.recurrent
    sched.start()
    handles = [sched.submit({"question": q}, cap, None)
               for q, cap in QUESTIONS]
    results = [h.result(timeout=600) for h in handles]
    sched.close()
    prompt = 0
    for (q, cap), (reply, reason, usage) in zip(QUESTIONS, results):
        want, n = _want(pipe, q, cap)
        prompt += n
        assert reason == "length" and usage == (n, cap)
        assert _ids(reply) == want
    assert metrics.get("ssm_prefill_tokens_total") == prompt
    assert metrics.get("prefill_tokens_total") == prompt
    assert metrics.get("ssm_state_resets_total") == len(QUESTIONS)
    steps = metrics.get("ssm_decode_lane_steps_total")
    out = sum(cap for _, cap in QUESTIONS)
    assert out - len(QUESTIONS) <= steps <= 8 * (out // 8 + len(QUESTIONS))
    assert metrics.get("decode_kv_tokens_total") > steps
    llm = pipe.cfg.llm
    assert metrics.get("ssm_state_bytes") == 2 * llm.state_bytes_per_slot(4)
    assert llm.state_bytes_per_slot(4) == costs_ssm.state_bytes_per_lane({
        "hidden_size": 64, "attn_layer_period": 4, "num_hidden_layers": 8,
        "mamba_expand": 2, "mamba_d_state": 8, "mamba_d_conv": 4,
        "mamba_dt_rank": 8, "intermediate_size": 128, "vocab_size": 512,
        "num_attention_heads": 4, "num_key_value_heads": 1}, 4)


@pytest.mark.parametrize("q,cap", QUESTIONS[:3])
def test_engine_streams_are_the_same_under_both_impls(
        pipe, q, cap, monkeypatch):
    """`attn_impl="pallas"` (every kernel in interpret mode here: the
    decode step of a Mamba layer is `_ssm_conv` and `_ssm_step` on the
    planes whole, in place) serves what "xla" serves, which is the
    reference's greedy continuation: two requests share the two slots,
    so that the case's stream decodes beside another lane that prefills,
    finishes and leaves its slot dead."""
    traced = []
    step = mamba.mixer_step_inplace
    monkeypatch.setattr(
        mamba, "mixer_step_inplace",
        lambda *a: traced.append(serving) or step(*a))
    jax.clear_caches()  # the decode program traces anew, through the spy
    replies = {}
    for serving in ("xla", "pallas"):
        served = OryxInference(
            IdTokenizer(), pipe.params,
            dataclasses.replace(pipe.cfg, attn_impl=serving),
            template="plain")
        sched = _engine(served)
        assert sched.pipe.cfg.attn_impl == serving
        sched.start()
        handles = [sched.submit({"question": text}, n, None)
                   for text, n in ((q, cap), ("w" * 21, 3))]
        replies[serving] = [h.result(timeout=600)[0] for h in handles]
        sched.close()
    assert traced and set(traced) == {"pallas"}  # "xla": mixer_step
    assert replies["pallas"] == replies["xla"]
    assert _ids(replies["pallas"][0]) == _want(pipe, q, cap)[0]


def test_a_reused_slot_serves_what_a_fresh_engine_serves(pipe):
    """One slot, two requests in turn: the second finds the first's
    state at its slot and must start from zeros."""
    replies = []
    for first in (True, False):
        sched = ContinuousScheduler(
            pipe, num_slots=1, page_size=PS, max_ctx=256, prefill_chunk=16,
            autostart=False)
        sched.start()
        if first:
            sched.submit({"question": "x" * 40}, 6, None).result(timeout=600)
        replies.append(sched.submit(
            {"question": "now something else"}, 8, None).result(timeout=600))
        sched.close()
    assert replies[0][0] == replies[1][0]
    assert _ids(replies[0][0]) == _want(pipe, "now something else", 8)[0]


def test_eviction_and_replay_reproduce_the_stream(pipe):
    """A request evicted mid-decode re-prefills from token 0 (its state
    is rebuilt from zeros at whatever slot it lands in) and streams the
    same tokens, each once."""
    sched = _engine(pipe)
    q, cap = "tell me about state space models please", 20
    want, _ = _want(pipe, q, cap)
    evicted = []
    step = sched._step_chunk

    def evict_once():
        step()
        if not evicted and sched.slots[0] is not None \
                and sched.slots[0].activated:
            evicted.append(sched.slots[0].processed)
            sched._evict(0)

    sched._step_chunk = evict_once
    sched.start()
    reply, reason, usage = sched.submit(
        {"question": q}, cap, None).result(timeout=600)
    sched.close()
    assert evicted and reason == "length"
    assert _ids(reply) == want


# --- what is refused, in the one refusal's words ---------------------------


@pytest.mark.parametrize("kw", [
    {"ragged": True}, {"ragged": True, "speculate": 2},
    {"kv_dtype": "int8"},
    {"host_cache_bytes": 1 << 20}, {"audit_sample_every": 4},
])
def test_the_engine_refuses_what_is_not_built_for_a_state(pipe, kw):
    with pytest.raises(ValueError, match=REFUSAL):
        _engine(pipe, **kw)


@pytest.mark.parametrize("option", [
    {"numerics_every": 1}, {"prefill_chunk": None},
])
def test_the_engine_serves_what_it_does_not_refuse(
        pipe, option, serves_like_the_default):
    """The probe reads the decode chunk's logits, whatever made them;
    an unchunked prefill is one `paged_prefill` from position 0, which
    zeroes the slot's state as a first chunk does."""
    serves_like_the_default(
        lambda **kw: _engine(pipe, **kw), option, QUESTIONS[0][0], 8)


@pytest.mark.parametrize("bad", [
    {"block_length": 4, "mask_token_id": 511},
    {"attention_bias": True},
    # (an expert layer and q/k norm beside a state layer run since PR 56:
    # tests/test_lfm2.py; these two kinds of expert layer do not.)
    {"num_experts": 4, "num_experts_per_tok": 2, "moe_intermediate_size": 8,
     "zero_experts": 2},
    {"num_experts": 4, "num_experts_per_tok": 2, "moe_intermediate_size": 8,
     "moe_activation": "relu"},
])
def test_the_config_refuses_what_is_not_built_for_a_state(bad):
    with pytest.raises(ValueError, match=REFUSAL):
        dataclasses.replace(cfg_lib.jamba_tiny().llm, **bad)


def test_a_mesh_ring_attention_and_a_broken_period_are_refused():
    cfg = cfg_lib.jamba_tiny()
    with pytest.raises(ValueError, match=REFUSAL):
        dataclasses.replace(cfg, mesh=cfg_lib.MeshConfig(tp=2))
    with pytest.raises(ValueError, match=REFUSAL):
        dataclasses.replace(cfg, attn_impl="ring")
    with pytest.raises(ValueError, match="whole number of periods"):
        dataclasses.replace(cfg.llm, num_layers=6)
    with pytest.raises(ValueError, match="use_rope=False"):
        dataclasses.replace(cfg_lib.tiny_llm(), use_rope=False)


def test_the_step_programs_refuse_by_name(tiny):
    cfg, params = tiny
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


def test_page_movers_leave_the_slot_planes_alone(tiny):
    cfg, _ = tiny
    kv, _ = _pool(cfg, 2)
    kv = {k: v + 1 for k, v in kv.items()}
    blob = paged_kv.fetch_page(kv, 3)
    assert set(blob) == {"k", "v"}
    out = paged_kv.copy_pages(kv, jnp.asarray(3), jnp.asarray(5))
    out = paged_kv.upload_page(out, jnp.asarray(6), blob)
    assert set(out) == {"k", "v", "conv", "ssm"}
    assert out["ssm"].shape == (cfg.num_state_layers, 2, 8, 128)
    assert out["conv"].shape == (cfg.num_state_layers, 2, 3 * 128)


def test_presets_state_the_published_geometry():
    llm = cfg_lib.jamba2_3b().llm
    assert (llm.num_layers, llm.num_attn_layers, llm.num_state_layers) == (
        28, 2, 26)
    assert [i for i in range(28)
            if i % llm.attn_layer_period == llm.attn_layer_offset] == [7, 21]
    assert (llm.hidden_size, llm.mamba_d_inner, llm.mamba_d_state,
            llm.mamba_dt_rank, llm.mamba_d_conv) == (2560, 5120, 16, 160, 4)
    assert (llm.num_heads, llm.num_kv_heads, llm.head_dim) == (20, 1, 128)
    assert llm.tie_word_embeddings and not llm.use_rope
    assert llm.cache_layers == 2
    assert llm.state_bytes_per_slot(2) == 9_318_400
    shapes = jax.eval_shape(
        lambda: qwen2.init_params(llm, jax.random.key(0), jnp.bfloat16))
    count = sum(a.size for a in jax.tree_util.tree_leaves(shapes))
    assert count == 3_029_337_472


# --- the models the benchmark already has trace as they did ----------------

# sha256 of the jaxpr's text, first 16 hex digits, of the two step
# programs for the tiny preset of each model the benchmark already has,
# taken on the parent commit 16a4ae6 (scratch script against `git
# archive`, this installation's jax 0.9.0, matmul precision "highest" as
# tests/conftest.py sets it). `qwen2.forward`'s own are in
# tests/test_sdar_moe.py and tests/test_mistral4.py.
PARENT_PROGRAMS = {
    "tiny_llm.paged_prefill": "a55a5d79ad1babec",
    "tiny_llm.paged_decode_chunk": "730c65cec79f9d60",
    "sdar_tiny.paged_prefill": "fe23ae797577b94c",
    "longcat_tiny.paged_prefill": "8279ff06fd04389f",
    "longcat_tiny.paged_decode_chunk": "2f9e585701192f3b",
    "mistral4_tiny.paged_prefill": "f6085cc6167ae8e3",
    "mistral4_tiny.paged_decode_chunk": "3e8e77f3519e7242",
}


@pytest.mark.parametrize("case", sorted(PARENT_PROGRAMS))
def test_the_step_programs_of_the_other_models_trace_as_the_parents(case):
    """The slot indices, the logits twin and the fourth branch of
    `forward` are chosen by the config and by arguments the other
    models never pass: their `paged_prefill` and `paged_decode_chunk`
    trace to the parent's jaxprs, character for character."""
    assert jax.config.jax_default_matmul_precision == "highest"
    name, program = case.split(".")
    c = getattr(cfg_lib, name)()
    cfg = c if name == "tiny_llm" else c.llm
    S = 2
    p = jax.eval_shape(lambda: qwen2.init_params(cfg, jax.random.key(0)))
    kv = jax.eval_shape(
        lambda: qwen2.init_paged_kv_cache(cfg, 8, 16, jnp.float32))
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)  # noqa: E731
    f32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32)  # noqa: E731
    keys = jax.eval_shape(lambda: jax.random.split(jax.random.key(0), S))
    sampling = (keys, f32(S), f32(S), i32(S))
    if program == "paged_prefill":
        jaxpr = jax.make_jaxpr(
            lambda p, e, n, bt, kv, st, *s: generate.paged_prefill(
                p, cfg, e, n, bt, kv, st, *s))(
            p, f32(S, 8, cfg.hidden_size), i32(S), i32(S, 2), kv, i32(S),
            *sampling)
    else:
        jaxpr = jax.make_jaxpr(
            lambda p, kv, bt, tok, n, fin, rec, *s:
            generate.paged_decode_chunk(
                p, cfg, kv, bt, tok, n, fin, rec, *s, chunk=2, eos=1))(
            p, kv, i32(S, 2), i32(S), i32(S),
            jax.ShapeDtypeStruct((S,), jnp.bool_), i32(S, 0), *sampling)
    digest = hashlib.sha256(str(jaxpr).encode()).hexdigest()[:16]
    assert digest == PARENT_PROGRAMS[case]
