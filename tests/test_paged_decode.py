"""Paged KV cache + chunked decode: allocator behavior, ragged decode
attention (XLA reference and Pallas twin), and greedy bit-parity between
the dense `_decode_while` path and the paged chunked path — with and
without prefix KV reuse."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from oryx_tpu import config as cfg_lib
from oryx_tpu.models import generate as gen_lib
from oryx_tpu.models import qwen2
from oryx_tpu.ops import attention as att_lib
from oryx_tpu.ops import paged_kv


# ---------------------------------------------------------------------------
# Allocator
# ---------------------------------------------------------------------------


def test_allocator_alloc_free_recycle():
    a = paged_kv.PageAllocator(4, 8)
    assert a.num_free == 4 and a.sentinel == 4
    p1 = a.alloc(2)
    p2 = a.alloc(2)
    assert sorted(p1 + p2) == [0, 1, 2, 3]
    assert a.num_free == 0
    with pytest.raises(paged_kv.OutOfPagesError):
        a.alloc(1)
    a.free(p1)
    # LIFO recycling: freshly freed pages come back first.
    assert a.alloc(2) == p1
    a.free(p1)
    a.free(p2)
    assert a.num_free == 4
    with pytest.raises(ValueError):
        a.free(p2)  # double free
    assert a.pages_for(0) == 0
    assert a.pages_for(1) == 1
    assert a.pages_for(8) == 1
    assert a.pages_for(9) == 2


def test_allocator_all_or_nothing():
    a = paged_kv.PageAllocator(3, 4)
    a.alloc(2)
    with pytest.raises(paged_kv.OutOfPagesError):
        a.alloc(2)
    assert a.num_free == 1  # the failed alloc leaked nothing


# ---------------------------------------------------------------------------
# Page I/O + ragged attention vs the dense reference
# ---------------------------------------------------------------------------


def _ragged_fixture(seed=0, B=3, Hq=4, Hk=2, D=16, ps=8, maxp=4, P=16):
    """Pages + block tables + an equivalent dense [B, K, Hk, D] view."""
    rng = np.random.default_rng(seed)
    lengths = np.array([5, 17, maxp * ps], np.int32)[:B]
    alloc = paged_kv.PageAllocator(P, ps)
    bt = np.full((B, maxp), alloc.sentinel, np.int32)
    k_pool = rng.standard_normal((P, ps, Hk, D)).astype(np.float32)
    v_pool = rng.standard_normal((P, ps, Hk, D)).astype(np.float32)
    K = maxp * ps
    k_dense = np.zeros((B, K, Hk, D), np.float32)
    v_dense = np.zeros((B, K, Hk, D), np.float32)
    for b in range(B):
        pages = alloc.alloc(alloc.pages_for(int(lengths[b])))
        bt[b, : len(pages)] = pages
        for s in range(int(lengths[b])):
            k_dense[b, s] = k_pool[pages[s // ps], s % ps]
            v_dense[b, s] = v_pool[pages[s // ps], s % ps]
    q = rng.standard_normal((B, 1, Hq, D)).astype(np.float32)
    return q, k_pool, v_pool, bt, lengths, k_dense, v_dense


def test_ragged_decode_attention_matches_dense():
    q, kp, vp, bt, lengths, kd, vd = _ragged_fixture()
    K = kd.shape[1]
    kv_mask = (np.arange(K)[None] < lengths[:, None]).astype(np.int32)
    ref = att_lib.attention(
        jnp.asarray(q), jnp.asarray(kd), jnp.asarray(vd), causal=True,
        q_positions=jnp.asarray(lengths - 1)[:, None],
        kv_mask=jnp.asarray(kv_mask),
    )
    got = paged_kv.ragged_decode_attention(
        jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp),
        jnp.asarray(bt), jnp.asarray(lengths),
    )
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))


def test_pallas_paged_decode_matches_reference():
    from oryx_tpu.ops.pallas import paged_attention as ppa

    q, kp, vp, bt, lengths, _, _ = _ragged_fixture(seed=3)
    ref = paged_kv.ragged_decode_attention(
        jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp),
        jnp.asarray(bt), jnp.asarray(lengths),
    )
    got = ppa.ragged_decode_attention(
        jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp),
        jnp.asarray(bt), jnp.asarray(lengths),
    )
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(ref), atol=2e-6, rtol=2e-6
    )


def test_write_pages_masks_and_sentinels():
    rng = np.random.default_rng(1)
    P, ps, Hk, D = 4, 4, 2, 8
    alloc = paged_kv.PageAllocator(P, ps)
    bt = np.full((2, 2), alloc.sentinel, np.int32)
    bt[0, :2] = alloc.alloc(2)
    bt[1, :1] = alloc.alloc(1)  # row 1 holds ONE page: slots >= 4 drop
    pool = jnp.zeros((P, ps, Hk, D), jnp.float32)
    new = jnp.asarray(rng.standard_normal((2, 3, Hk, D)), jnp.float32)
    out = paged_kv.write_pages(
        pool, new, jnp.asarray(bt), jnp.asarray([2, 3], jnp.int32)
    )
    g = paged_kv.gather_pages(out, jnp.asarray(bt))
    # Row 0: slots 2..4 all covered.
    np.testing.assert_array_equal(np.asarray(g)[0, 2:5], np.asarray(new)[0])
    # Row 1: slot 3 lands, slots 4..5 routed through the sentinel drop.
    np.testing.assert_array_equal(np.asarray(g)[1, 3], np.asarray(new)[1, 0])
    untouched = [p for p in range(P) if p not in list(bt[0]) + list(bt[1])]
    for p in untouched:
        np.testing.assert_array_equal(np.asarray(out)[p], 0.0)
    # write_mask False rows drop everything.
    out2 = paged_kv.write_pages(
        out, new * 7, jnp.asarray(bt), jnp.asarray([2, 3], jnp.int32),
        write_mask=jnp.asarray([False, False]),
    )
    np.testing.assert_array_equal(np.asarray(out2), np.asarray(out))


# ---------------------------------------------------------------------------
# Greedy parity: dense while-loop decode vs paged chunked decode
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def tiny_llm():
    cfg = cfg_lib.tiny_llm(vocab_size=128)
    params = qwen2.init_params(cfg, jax.random.key(0))
    return cfg, params


def _embed(params, ids):
    return params["embed"]["weight"][jnp.asarray(ids)]


def test_paged_greedy_parity_mixed_lengths(tiny_llm):
    cfg, params = tiny_llm
    gcfg = cfg_lib.GenerationConfig(temperature=0.0, eos_token_id=7)
    rng = np.random.default_rng(0)
    B, Tb, max_new, cache_len = 3, 16, 12, 32
    lengths = np.array([5, 11, 16], np.int32)
    ids = rng.integers(1, 128, size=(B, Tb)).astype(np.int32)
    toks, num, fin = gen_lib.generate(
        params, cfg, gcfg, inputs_embeds=_embed(params, ids),
        lengths=jnp.asarray(lengths), max_new_tokens=max_new,
        cache_len=cache_len,
    )
    # kv_capacity == the dense cache_len: identical fp32 reductions,
    # masked kv columns contribute exact zeros either way → BIT parity.
    ptoks, pnum, pfin = gen_lib.generate_paged(
        params, cfg, gcfg, inputs_embeds=_embed(params, ids),
        lengths=lengths, max_new_tokens=max_new, page_size=8, chunk=4,
        kv_capacity=cache_len,
    )
    np.testing.assert_array_equal(np.asarray(toks), np.asarray(ptoks))
    np.testing.assert_array_equal(np.asarray(num), np.asarray(pnum))
    np.testing.assert_array_equal(np.asarray(fin), np.asarray(pfin))


def test_paged_greedy_parity_with_stop_sequences(tiny_llm):
    """Stop-sequence rows must freeze identically on both paths: run
    dense once, turn its second emitted token into a stop sequence, and
    demand bit-equal tokens AND finish accounting."""
    cfg, params = tiny_llm
    gcfg = cfg_lib.GenerationConfig(temperature=0.0, eos_token_id=7)
    rng = np.random.default_rng(2)
    B, Tb, max_new, cache_len = 2, 16, 12, 32
    lengths = np.array([9, 14], np.int32)
    ids = rng.integers(1, 128, size=(B, Tb)).astype(np.int32)
    toks, _, _ = gen_lib.generate(
        params, cfg, gcfg, inputs_embeds=_embed(params, ids),
        lengths=jnp.asarray(lengths), max_new_tokens=max_new,
        cache_len=cache_len,
    )
    stop = np.full((1, 4), -1, np.int32)
    stop[0, -1] = int(np.asarray(toks)[0, 1])  # fires early on row 0
    stop = jnp.asarray(stop)
    args = dict(
        inputs_embeds=_embed(params, ids), max_new_tokens=max_new,
        stop_sequences=stop,
    )
    toks, num, fin = gen_lib.generate(
        params, cfg, gcfg, lengths=jnp.asarray(lengths),
        cache_len=cache_len, **args,
    )
    ptoks, pnum, pfin = gen_lib.generate_paged(
        params, cfg, gcfg, lengths=lengths, page_size=8, chunk=4,
        kv_capacity=cache_len, **args,
    )
    np.testing.assert_array_equal(np.asarray(toks), np.asarray(ptoks))
    np.testing.assert_array_equal(np.asarray(num), np.asarray(pnum))
    np.testing.assert_array_equal(np.asarray(fin), np.asarray(pfin))
    assert bool(np.asarray(fin)[0])  # the stop actually fired


def test_paged_greedy_parity_prefix_reuse(tiny_llm):
    """Two-turn conversation: turn 2 prefills only the suffix against
    the turn-1 KV (dense kv_cache/start vs paged state/start) — token
    ids must stay bit-identical."""
    cfg, params = tiny_llm
    gcfg = cfg_lib.GenerationConfig(temperature=0.0, eos_token_id=7)
    rng = np.random.default_rng(1)
    max_new, cache_len = 8, 64
    ids1 = rng.integers(1, 128, size=(1, 16)).astype(np.int32)
    L1 = 9
    t1, n1, _, cache = gen_lib.generate(
        params, cfg, gcfg, inputs_embeds=_embed(params, ids1),
        lengths=jnp.asarray([L1], np.int32), max_new_tokens=max_new,
        cache_len=cache_len, return_cache=True,
    )
    pt1, pn1, _, state = gen_lib.generate_paged(
        params, cfg, gcfg, inputs_embeds=_embed(params, ids1),
        lengths=np.asarray([L1]), max_new_tokens=max_new, page_size=8,
        chunk=4, kv_capacity=cache_len, num_pages=8, return_state=True,
    )
    np.testing.assert_array_equal(np.asarray(t1), np.asarray(pt1))
    # Turn 2: keep prompt + generated KV, append a 6-token suffix.
    common = L1 + int(np.asarray(n1)[0])
    suf = rng.integers(1, 128, size=(1, 8)).astype(np.int32)
    L2 = common + 6
    t2, n2, f2 = gen_lib.generate(
        params, cfg, gcfg, inputs_embeds=_embed(params, suf),
        lengths=jnp.asarray([L2], np.int32), max_new_tokens=max_new,
        cache_len=cache_len, kv_cache=cache,
        start=jnp.asarray(common, jnp.int32),
    )
    pt2, pn2, pf2 = gen_lib.generate_paged(
        params, cfg, gcfg, inputs_embeds=_embed(params, suf),
        lengths=np.asarray([L2]), max_new_tokens=max_new, page_size=8,
        chunk=4, kv_capacity=cache_len, state=state,
        start=np.asarray([common]),
    )
    np.testing.assert_array_equal(np.asarray(t2), np.asarray(pt2))
    np.testing.assert_array_equal(np.asarray(n2), np.asarray(pn2))
    np.testing.assert_array_equal(np.asarray(f2), np.asarray(pf2))


def test_generate_paged_ragged_pool_sizing(tiny_llm):
    """The default pool is the exact ragged need — a short row costs its
    own pages, not the batch max (the perf claim behind the change)."""
    cfg, params = tiny_llm
    gcfg = cfg_lib.GenerationConfig(temperature=0.0, eos_token_id=7)
    rng = np.random.default_rng(4)
    ids = rng.integers(1, 128, size=(2, 32)).astype(np.int32)
    lengths = np.array([4, 32], np.int32)
    _, _, _, state = gen_lib.generate_paged(
        params, cfg, gcfg, inputs_embeds=_embed(params, ids),
        lengths=lengths, max_new_tokens=8, page_size=8, chunk=8,
        kv_capacity=64, return_state=True,
    )
    # ceil((4+8)/8)=2 + ceil((32+8)/8)=5 pages, vs 2*8 for dense capacity.
    assert state.allocator.num_pages == 7
    assert state.allocator.num_free == 0


def test_paged_decode_pallas_matches_xla(tiny_llm):
    """The chunked decode with attn_impl=pallas (in-place page reads via
    the Pallas kernel, interpret mode on CPU) emits the same greedy
    tokens as the gather-based XLA reference path."""
    cfg, params = tiny_llm
    gcfg = cfg_lib.GenerationConfig(temperature=0.0, eos_token_id=7)
    rng = np.random.default_rng(5)
    ids = rng.integers(1, 128, size=(2, 16)).astype(np.int32)
    lengths = np.array([7, 13], np.int32)
    common = dict(
        inputs_embeds=_embed(params, ids), lengths=lengths,
        max_new_tokens=6, page_size=8, chunk=2, kv_capacity=32,
    )
    xt, xn, xf = gen_lib.generate_paged(
        params, cfg, gcfg, attn_impl="xla", **common
    )
    pt, pn, pf = gen_lib.generate_paged(
        params, cfg, gcfg, attn_impl="pallas", **common
    )
    np.testing.assert_array_equal(np.asarray(xt), np.asarray(pt))
    np.testing.assert_array_equal(np.asarray(xn), np.asarray(pn))


# ---------------------------------------------------------------------------
# The pool is the layer scan's carry: layer-offset tables on one flat pool
# ---------------------------------------------------------------------------

_P, _PS = 6, 4  # pages a layer (the sentinel is _P), page size
# Three sequences: 0 live on pages 3, 5; 1 finished (write_mask False) on
# pages 1, 2; 2 live with ONE page, so its slots >= 4 route through the
# sentinel. Page 0 belongs to nobody: a sentinel entry offset like a real
# one (6 + l*6) would land on page 0 of layer l + 1.
_TABLES = np.array([[3, 5], [1, 2], [4, _P]], np.int32)


def _carried_pool_case(mode):
    """(forward kwargs, the (page, offset) slots a layer may write, the
    rows whose every visible slot is allocated: only their logits mean
    anything) for one `forward` per paged entry shape."""
    slot = np.arange(2 * _PS, dtype=np.int32)[None]
    if mode == "packed":
        seg = np.array([[0, 0, 1, 2, 2]], np.int32)
        pos = np.array([[4, 5, 6, 3, 4]], np.int32)
        kw = dict(
            input_ids=np.array([[9, 8, 7, 6, 5]], np.int32), positions=pos,
            q_segments=seg,
            write_mask=np.array([[True, True, False, True, True]]),
        )
        # seq 2's token at slot 4 routes through the sentinel and sees it.
        return _on_device(kw), [(5, 0), (5, 1), (4, 3)], np.s_[0, :4]
    if mode == "prefill":
        start = np.array([2, 4, 2], np.int32)
        lengths = np.array([6, 8, 4], np.int32)  # seq 2: 2 real + 2 pad rows
        kw = dict(
            input_ids=np.arange(1, 13, dtype=np.int32).reshape(3, 4),
            positions=start[:, None] + np.arange(4, dtype=np.int32)[None],
            write_slots=start,
            kv_mask=(slot < lengths[:, None]).astype(np.int32),
            write_mask=np.array([True, False, True]), kv_lengths=lengths,
        )
        written = [(3, 2), (3, 3), (5, 0), (5, 1), (4, 2), (4, 3)]
        return _on_device(kw), written, np.s_[:2]
    cur = np.array([5, 6, 4], np.int32)  # seq 2 writes slot 4: dropped
    kw = dict(
        input_ids=np.array([[9], [8], [7]], np.int32), positions=cur[:, None],
        write_slots=cur, kv_mask=(slot <= cur[:, None]).astype(np.int32),
        write_mask=np.array([True, False, True]), kv_lengths=cur + 1,
        attn_impl="pallas" if mode == "decode-pallas" else "xla",
    )
    return _on_device(kw), [(5, 1)], np.s_[:2]


def _on_device(kw):
    kw = {
        k: v if isinstance(v, str) else jnp.asarray(v) for k, v in kw.items()
    }
    return dict(kw, block_tables=jnp.asarray(_TABLES))


def _noise_pool(cfg, pool, seed=0):
    """A [L, _P, _PS, Hk, D] pool with no zero byte anywhere, so a write
    that strays shows wherever it lands."""
    rng = np.random.default_rng(seed)
    shape = (cfg.num_layers, _P, _PS, cfg.num_kv_heads, cfg.head_dim)

    def plane():
        if pool == "int8":
            return paged_kv.QuantPages(
                jnp.asarray(rng.integers(1, 128, shape), jnp.int8),
                jnp.asarray(rng.uniform(0.01, 0.02, shape[:3]), jnp.float32),
            )
        return jnp.asarray(rng.standard_normal(shape), jnp.float32)

    return {"k": plane(), "v": plane()}


def _forward_layer_by_layer(params, cfg, kv, *, input_ids, positions,
                            attn_impl="xla", **kw):
    """`qwen2.forward`'s paged route as it was before the pool became the
    scan's carry: the pool is the scan's xs, so `_block` sees `pool[l]`,
    a [P, ps, Hk, D] slice of its own, through the UNSHIFTED tables, and
    the written slices are restacked as ys. (A Python loop over the layers
    is the same arithmetic but not the same fusions: on the CPU it is one
    ulp off EVERY scanned forward, this one and the old one alike.)"""
    from oryx_tpu.ops import norms, rope

    def attn_fn(q, k, v, slot_positions=False, **kw):
        return att_lib.attention(q, k, v, causal=True, **kw)

    kw = {"write_slots": None, "kv_mask": None, **kw}
    cos, sin = rope.rope_cos_sin(positions, cfg.head_dim, cfg.rope_theta)

    def body(h, xs):
        lp, ck, cv = xs
        h, ck, cv = qwen2._block(
            cfg, h, lp, cos, sin, positions=positions, cache_k=ck,
            cache_v=cv, attn_fn=attn_fn, attn_impl=attn_impl, **kw,
        )
        return h, {"k": ck, "v": cv}

    h, new = jax.lax.scan(
        body, params["embed"]["weight"][input_ids],
        (params["layers"], kv["k"], kv["v"]),
    )
    h = norms.rms_norm(h, params["final_norm"]["weight"], cfg.rms_norm_eps)
    return h @ params["lm_head"]["kernel"], new


def _bytes(kv):
    return [np.asarray(a) for a in jax.tree_util.tree_leaves(kv)]


_MODES = ["decode", "decode-pallas", "prefill", "packed"]


@pytest.mark.parametrize("pool", ["bf16", "int8"])
@pytest.mark.parametrize("mode", _MODES)
def test_carried_pool_writes_only_the_rows_layer_slots(tiny_llm, mode, pool):
    """A write masked by `write_mask`, or routed through the sentinel, at
    layer l drops: after one `forward` every byte of the pool is as it
    was, save the live rows' own slots in each layer's own pages."""
    cfg, params = tiny_llm
    kw, written, _ = _carried_pool_case(mode)
    kv = _noise_pool(cfg, pool)
    before = _bytes(kv)
    _, new = qwen2.forward(params, cfg, kv_cache=kv, **kw)
    for was, now in zip(before, _bytes(new)):
        assert was.shape == now.shape and was.dtype == now.dtype
        may = np.zeros(was.shape[:3], bool)
        for page, offset in written:
            may[:, page, offset] = True
        changed = (now != was).reshape(*may.shape, -1).any(-1)
        np.testing.assert_array_equal(changed, may)


@pytest.mark.parametrize("pool", ["bf16", "int8"])
@pytest.mark.parametrize("mode", _MODES)
def test_carried_pool_matches_layer_by_layer_reference(tiny_llm, mode, pool):
    """Same logits and the same pool, byte for byte, as the per-layer
    slices the carried pool replaced."""
    cfg, params = tiny_llm
    kw, _, meaningful = _carried_pool_case(mode)
    want_logits, want = _forward_layer_by_layer(
        params, cfg, _noise_pool(cfg, pool), **kw
    )
    got_logits, got = qwen2.forward(
        params, cfg, kv_cache=_noise_pool(cfg, pool), **kw
    )
    np.testing.assert_array_equal(
        np.asarray(got_logits)[meaningful], np.asarray(want_logits)[meaningful]
    )
    for a, b in zip(_bytes(got), _bytes(want)):
        np.testing.assert_array_equal(a, b)


# ---------------------------------------------------------------------------
# Samplers
# ---------------------------------------------------------------------------


def test_sample_token_top_k_clamps_to_vocab():
    """Regression: top_k >= vocab_size used to index out of range in
    jnp.sort(logits)[:, -top_k]; it must behave as 'keep everything'."""
    rng = np.random.default_rng(0)
    logits = jnp.asarray(rng.standard_normal((2, 8)), jnp.float32)
    key = jax.random.key(0)
    huge = gen_lib.sample_token(
        logits, key, temperature=0.7, top_p=1.0, top_k=50
    )
    # Same key on purpose: the test asserts the three top_k settings
    # draw IDENTICAL tokens, which only holds under identical RNG.
    nofilter = gen_lib.sample_token(  # oryxlint: disable=key-linearity
        logits, key, temperature=0.7, top_p=1.0, top_k=0
    )
    exact = gen_lib.sample_token(  # oryxlint: disable=key-linearity
        logits, key, temperature=0.7, top_p=1.0, top_k=8
    )
    np.testing.assert_array_equal(np.asarray(huge), np.asarray(nofilter))
    np.testing.assert_array_equal(np.asarray(huge), np.asarray(exact))


def test_sample_token_rows_per_row_behavior():
    rng = np.random.default_rng(0)
    V = 16
    logits = jnp.asarray(rng.standard_normal((3, V)), jnp.float32)
    keys = jax.random.split(jax.random.key(1), 3)
    # Row 0 greedy, row 1 heavily top-k-1 (=> argmax too), row 2 free.
    out = gen_lib.sample_token_rows(
        logits, keys,
        temperature=jnp.asarray([0.0, 1.0, 1.0]),
        top_p=jnp.asarray([1.0, 1.0, 1.0]),
        top_k=jnp.asarray([0, 1, 0]),
    )
    assert int(out[0]) == int(jnp.argmax(logits[0]))
    assert int(out[1]) == int(jnp.argmax(logits[1]))
    assert 0 <= int(out[2]) < V
    # A row's draw is independent of its neighbors: same row alone gives
    # the same token (continuous-batching invariant).
    solo = gen_lib.sample_token_rows(
        logits[2:], keys[2:],
        temperature=jnp.asarray([1.0]),
        top_p=jnp.asarray([1.0]),
        top_k=jnp.asarray([0]),
    )
    assert int(solo[0]) == int(out[2])
    # top_k above V clamps rather than erroring: same keys on purpose —
    # the assertion is that clamped and unfiltered draw IDENTICALLY.
    clamped = gen_lib.sample_token_rows(  # oryxlint: disable=key-linearity
        logits, keys,
        temperature=jnp.asarray([1.0, 1.0, 1.0]),
        top_p=jnp.asarray([1.0, 1.0, 1.0]),
        top_k=jnp.asarray([V + 50, V + 50, V + 50]),
    )
    unfiltered = gen_lib.sample_token_rows(  # oryxlint: disable=key-linearity
        logits, keys,
        temperature=jnp.asarray([1.0, 1.0, 1.0]),
        top_p=jnp.asarray([1.0, 1.0, 1.0]),
        top_k=jnp.asarray([0, 0, 0]),
    )
    np.testing.assert_array_equal(np.asarray(clamped),
                                  np.asarray(unfiltered))


# ---------------------------------------------------------------------------
# The sampler does only what its rows ask for: an all-greedy call takes
# a conditional's argmax branch, any other call sorts once
# ---------------------------------------------------------------------------


def _truncate_two_sorts(logits, *, temperature, top_p, top_k):
    """`truncate_logits_rows` as it stood before the single sort, kept
    verbatim: the reference the one-sort code is held to, bit for bit."""
    V = logits.shape[-1]
    is_greedy = temperature <= 0.0
    t = jnp.where(is_greedy, 1.0, temperature)[:, None]
    l = logits / t
    tk = jnp.clip(top_k.astype(jnp.int32), 0, V)
    srt = jnp.sort(l, axis=-1)  # ascending
    kth = jnp.take_along_axis(
        srt, jnp.clip(V - tk, 0, V - 1)[:, None], axis=-1
    )
    l = jnp.where((tk > 0)[:, None] & (l < kth), -jnp.inf, l)
    srt_d = jnp.sort(l, axis=-1)[:, ::-1]
    probs = jax.nn.softmax(srt_d, axis=-1)
    cum = jnp.cumsum(probs, axis=-1)
    # Smallest prefix with cumulative prob >= top_p (keeps the top token).
    cutoff_idx = jnp.sum(cum < top_p[:, None], axis=-1)
    cutoff = jnp.take_along_axis(srt_d, cutoff_idx[:, None], axis=-1)
    l = jnp.where((top_p < 1.0)[:, None] & (l < cutoff), -jnp.inf, l)
    return l, is_greedy


def _sample_two_sorts(logits, keys, *, temperature, top_p, top_k):
    """`sample_token_rows` as it stood before its conditional, kept
    verbatim: every call sorts twice and draws its noise."""
    V = logits.shape[-1]
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    l, is_greedy = _truncate_two_sorts(
        logits, temperature=temperature, top_p=top_p, top_k=top_k
    )
    u = jax.vmap(lambda k: jax.random.uniform(k, (V,)))(keys)
    g = -jnp.log(-jnp.log(jnp.maximum(u, jnp.finfo(jnp.float32).tiny)))
    sampled = jnp.argmax(l + g, axis=-1).astype(jnp.int32)
    return jnp.where(is_greedy, greedy, sampled)


_SV = 64  # the sampler cases' vocabulary


def _sampler_rows(k):
    """[8, _SV] logits: free rows, rows on a grid of 0.5 (ties
    everywhere), and a row whose sorted places k-2..k+2 hold one value
    (a tie AT the k-th place, when k lies inside the row)."""
    rng = np.random.default_rng(7)
    x = rng.standard_normal((8, _SV)).astype(np.float32) * 3.0
    x[2:5] = np.round(x[2:5] * 2.0) / 2.0
    kk = int(np.clip(k, 3, _SV - 3))
    order = np.argsort(-x[5])
    x[5, order[kk - 3:kk + 2]] = x[5, order[kk - 1]]
    return jnp.asarray(x)


def _bits(a):
    return np.asarray(a).view(np.int32)


@pytest.mark.parametrize("top_p", [0.1, 0.8, 1.0])
@pytest.mark.parametrize("top_k", [0, 1, 20, _SV, _SV + 5])
@pytest.mark.parametrize("temperature", [0.0, 0.7, 1.3])
def test_one_sort_sampler_matches_the_two_sort_code_bit_for_bit(
    temperature, top_k, top_p
):
    """Truncated logits and sampled ids equal the two-sort code's for
    equal keys: a call of eight rows under one setting, and the same
    rows with greedy and otherwise-set neighbours mixed in."""
    logits = _sampler_rows(top_k)
    S = logits.shape[0]
    uniform = (
        jnp.full((S,), temperature), jnp.full((S,), top_p),
        jnp.full((S,), top_k, jnp.int32),
    )
    mixed = (
        uniform[0].at[::3].set(0.0).at[1].set(0.9),
        uniform[1].at[1].set(0.5), uniform[2].at[1].set(3),
    )
    for t, p, k in (uniform, mixed):
        kw = dict(temperature=t, top_p=p, top_k=k)
        got, greedy = gen_lib.truncate_logits_rows(logits, **kw)
        want, want_greedy = _truncate_two_sorts(logits, **kw)
        np.testing.assert_array_equal(_bits(got), _bits(want))
        np.testing.assert_array_equal(
            np.asarray(greedy), np.asarray(want_greedy))
        # Equal ids need equal noise: both sides split the same seed.
        ids = gen_lib.sample_token_rows(
            logits, jax.random.split(jax.random.key(11), S), **kw)
        want_ids = _sample_two_sorts(
            logits, jax.random.split(jax.random.key(11), S), **kw)
        np.testing.assert_array_equal(np.asarray(ids), np.asarray(want_ids))


@pytest.mark.parametrize("neighbour", ["greedy", "sampled"])
def test_a_rows_draw_does_not_depend_on_the_branch_its_call_takes(
    tiny_llm, neighbour
):
    """A row's tokens and its advanced key are the same whether the
    dispatch took the argmax branch or the sorting one: a greedy row
    beside a greedy or a sampled neighbour, a sampled row likewise."""
    cfg, params = tiny_llm
    ps, S = 8, 2
    mine = 0.0 if neighbour == "sampled" else 0.8

    def run(other):
        kv = qwen2.init_paged_kv_cache(cfg, 4, ps, dtype=jnp.float32)
        out = gen_lib.paged_decode_chunk(
            params, cfg, kv, jnp.asarray([[0, 1], [2, 3]], jnp.int32),
            jnp.asarray([5, 9], jnp.int32), jnp.zeros((S,), jnp.int32),
            jnp.zeros((S,), bool), jnp.zeros((S, 0), jnp.int32),
            jax.random.split(jax.random.key(3), S),
            jnp.asarray([mine, other]), jnp.asarray([0.9, 0.9]),
            jnp.asarray([0, 12], jnp.int32), None, chunk=4, eos=-1,
        )
        return np.asarray(out[6])[:, 0], jax.random.key_data(out[5])[0]

    # `mine` beside a greedy neighbour, then beside a sampled one: with
    # mine == 0 the first call is all greedy and the second is not.
    toks_a, key_a = run(0.0)
    toks_b, key_b = run(1.1)
    np.testing.assert_array_equal(toks_a, toks_b)
    np.testing.assert_array_equal(np.asarray(key_a), np.asarray(key_b))


def _sorts_in(jaxpr, inside=False):
    """[(is inside a conditional's branch)] for every sort in a jaxpr,
    through every nested program (scan bodies, calls, branches)."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "sort":
            found.append(inside)
        below = inside or eqn.primitive.name == "cond"
        for value in eqn.params.values():
            for sub in value if isinstance(value, (tuple, list)) else (value,):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    found += _sorts_in(sub, below)
    return found


@pytest.mark.parametrize("program", ["paged_decode_chunk", "paged_prefill"])
def test_step_programs_sort_once_and_only_inside_the_conditional(
    tiny_llm, program
):
    """One sampler call a program, one sort in it, and that sort in a
    branch of a conditional that survives lowering (the two-sort code
    had two, outside any conditional)."""
    cfg, params = tiny_llm
    S = 3 if program == "paged_decode_chunk" else 1
    kv = qwen2.init_paged_kv_cache(cfg, 8, 8, dtype=jnp.float32)
    tables = jnp.zeros((S, 2), jnp.int32)
    sampling = (
        jax.random.split(jax.random.key(0), S), jnp.zeros((S,)),
        jnp.ones((S,)), jnp.zeros((S,), jnp.int32),
    )
    if program == "paged_decode_chunk":
        traced = gen_lib.paged_decode_chunk.trace(
            params, cfg, kv, tables, jnp.zeros((S,), jnp.int32),
            jnp.zeros((S,), jnp.int32), jnp.zeros((S,), bool),
            jnp.zeros((S, 0), jnp.int32), *sampling, None, chunk=4, eos=-1,
        )
    else:
        traced = gen_lib.paged_prefill.trace(
            params, cfg, jnp.zeros((S, 16, cfg.hidden_size)),
            jnp.asarray([9], jnp.int32), tables, kv,
            jnp.zeros((S,), jnp.int32), *sampling,
        )
    assert _sorts_in(traced.jaxpr.jaxpr) == [True]
    assert "stablehlo.case" in traced.lower().as_text()


@pytest.mark.parametrize("temperature", [0.0, 0.9])
def test_block_step_tokens_equal_the_two_sort_samplers(
    monkeypatch, temperature
):
    """`paged_block_step` through the shared sampler gives the tokens
    and keys it gave with its own conditional around the two-sort
    sampler, for a greedy and for a sampled dispatch (a finished slot
    with temperature 0 rides along in both)."""
    cfg = cfg_lib.sdar_tiny().llm
    params = qwen2.init_params(cfg, jax.random.key(0))
    S, B = 3, cfg.block_length

    def run(step):
        kv = qwen2.init_paged_kv_cache(cfg, 6, 16, dtype=jnp.float32)
        out = step(
            params, cfg, kv, jnp.arange(6, dtype=jnp.int32).reshape(S, 2),
            jnp.asarray(np.arange(S * B).reshape(S, B) % 90, jnp.int32),
            jnp.asarray([0, 2, 0], jnp.int32), jnp.zeros((S,), jnp.int32),
            jnp.asarray([False, False, True]),
            jax.random.split(jax.random.key(5), S),
            jnp.asarray([temperature, temperature, 0.0]),
            jnp.asarray([0.9, 1.0, 1.0]), jnp.asarray([0, 7, 0], jnp.int32),
            steps=2, remasking="low_confidence_static", threshold=0.9,
            eos=-1,
        )
        return np.asarray(out[1]), np.asarray(jax.random.key_data(out[5]))

    toks, keys = run(gen_lib.paged_block_step)
    # The same program traced anew over the two-sort sampler.
    monkeypatch.setattr(gen_lib, "sample_token_rows", _sample_two_sorts)
    old_toks, old_keys = run(jax.jit(
        gen_lib.paged_block_step.__wrapped__,
        static_argnames=("cfg", "steps", "remasking", "threshold", "eos",
                         "attn_impl", "compute_dtype"),
    ))
    np.testing.assert_array_equal(toks, old_toks)
    np.testing.assert_array_equal(keys, old_keys)
