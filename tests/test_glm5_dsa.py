"""GLM-5 at `glm5_tiny` on the CPU: learned sparse attention (an indexer,
the exact top k, the selected rows of the latent pool) through the
two-array pool, prefill then decode, against the plain reference
(benchmark/reference/glm5_dsa_ref.py); sigmoid routing, the leading
dense layer, the share test; the split engine with the prefix cache and
an eviction past the top k; the ops one by one; what the config
refuses."""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark.reference import glm5_dsa_ref as ref
from oryx_tpu import config as cfg_lib
from oryx_tpu.models import generate, oryx, qwen2
from oryx_tpu.ops import paged_kv
from oryx_tpu.ops.pallas import masked_attention
from oryx_tpu.ops.pallas import paged_attention as ppa
from oryx_tpu.serve.pipeline import OryxInference
from oryx_tpu.serve.scheduler import ContinuousScheduler
from oryx_tpu.utils.metrics import ServingMetrics

F32 = jnp.float32
TOL = 5e-6  # float32 on both sides: summation order only
PS = 8
TOPK = 16


@pytest.fixture(scope="module")
def tiny():
    cfg = cfg_lib.glm5_tiny().llm
    assert cfg.index_topk == TOPK
    params = qwen2.init_params(cfg, jax.random.key(0))
    # Weights away from their init scale, so that a key's score and a
    # router's choice move with the token; norms away from 1, so a
    # missing one would show.
    params = jax.tree.map(lambda a: a * 4 if a.ndim > 2 else a, params)
    for j, name in enumerate(("q_a_norm", "kv_a_norm", "input_norm",
                              "post_attn_norm")):
        for stack in ("layers", "dense_layers"):
            w = params[stack][name]["weight"]
            params[stack][name]["weight"] = (
                1 + 0.1 * jax.random.normal(jax.random.key(j), w.shape))
    return cfg, params


def _greedy(n):
    return (jnp.zeros((n,)), jnp.ones((n,)), jnp.zeros((n,), jnp.int32))


def _ids(seed, n, vocab=512):
    return np.random.default_rng(seed).integers(3, vocab, n).astype(np.int32)


def _prefill(cfg, params, kv, bt, ids, *, chunk, impl, start=0):
    """`ids[start:]` of lane 0 through `paged_prefill` in chunks; (kv,
    first token, the last chunk's routing)."""
    n = len(ids)
    emb = generate.pad_embeds_for_chunks(
        params["embed"]["weight"][jnp.asarray(ids[start:])][None], chunk)
    keys = jax.random.split(jax.random.key(0), 1)
    for off in range(start, n, chunk):
        kv, tok, keys, r = generate.paged_prefill(
            params, cfg,
            generate.slice_embeds(emb, jnp.asarray(off - start, jnp.int32),
                                  width=chunk),
            jnp.asarray([min(off + chunk, n)], jnp.int32), bt[:1], kv,
            jnp.asarray([off], jnp.int32), keys, *_greedy(1),
            attn_impl=impl, return_routing=True)
    return kv, tok, r


def _decode(cfg, params, kv, bt, tok, lengths, live, *, chunks, chunk, impl):
    """Decode chunks over every lane of `bt`; (kv, per lane the tokens
    fed, per lane the logits rows, the last chunk's selections)."""
    S = bt.shape[0]
    state = (jnp.asarray(tok, jnp.int32), jnp.asarray(lengths, jnp.int32),
             ~jnp.asarray(live), jnp.zeros((S, 0), jnp.int32),
             jax.random.split(jax.random.key(1), S))
    fed, rows = [[] for _ in range(S)], [[] for _ in range(S)]
    for _ in range(chunks):
        out = generate.paged_decode_chunk(
            params, cfg, kv, bt, *state, *_greedy(S), chunk=chunk, eos=-1,
            attn_impl=impl, return_routing=True)
        kv, state = out[0], out[1:6]
        for s in range(S):
            fed[s] += [int(t) for t in np.asarray(out[6])[s]]
            rows[s] += list(np.asarray(out[-3])[s])
    return kv, fed, rows, np.asarray(out[-1])


def _pool(cfg, lanes, maxp):
    kv = qwen2.init_paged_kv_cache(cfg, lanes * maxp, PS, F32)
    bt = jnp.arange(lanes * maxp, dtype=jnp.int32).reshape(lanes, maxp)[::-1]
    return kv, bt


def test_forward_without_a_cache_matches_the_reference(tiny):
    cfg, params = tiny
    ids = _ids(0, 90)
    got, _, routing = qwen2.forward(
        params, cfg, input_ids=jnp.asarray(ids)[None], return_routing=True)
    want, chosen, selected = ref.logits(
        params, cfg, ids, return_experts=True, return_selection=True)
    np.testing.assert_allclose(got[0], want, atol=TOL)
    assert routing["ids"].shape[0] == cfg.num_layers - cfg.dense_layers == 3
    assert np.array_equal(np.sort(routing["ids"], -1), np.sort(chosen, -1))
    # Every query past the top k keeps exactly k keys, every layer.
    for packed in selected:
        kept = np.unpackbits(np.asarray(packed), axis=-1)[:, :90].sum(-1)
        assert np.array_equal(kept, np.minimum(np.arange(90) + 1, TOPK))
    # Padded, forced to its own choices: the same logits.
    again = ref.logits(params, cfg, ids, forced_experts=chosen,
                       forced_selection=selected, pad_to=64, rows=[0, 40, 89])
    np.testing.assert_allclose(again, want[jnp.asarray([0, 40, 89])],
                               atol=TOL)


# (a) never reaches the top k; (b) passes it during decode; (c) passes
# it inside a prefill chunk and goes on for several times the top k.
@pytest.mark.parametrize("impl", ["xla", "pallas"])
@pytest.mark.parametrize("case, n, chunk, steps", [
    ("a_under", 5, 8, 8), ("b_decode_passes", 12, 8, 12),
    ("c_chunk_passes", 75, 24, 8),
])
def test_prefill_then_decode_matches_the_reference(
        tiny, impl, case, n, chunk, steps):
    cfg, params = tiny
    ids = _ids(hash(case) % 1000, n)
    kv, bt = _pool(cfg, 1, 16)
    assert kv[paged_kv.LATENT].shape == (4, 16, PS, 128)
    assert kv[paged_kv.INDEX_K].shape == (4, 16, PS, 24)
    kv, tok, r = _prefill(cfg, params, kv, bt, ids, chunk=chunk, impl=impl)
    rows = [np.asarray(r["logits"])[0]]
    kv, fed, dec, sel = _decode(
        cfg, params, kv, bt, [int(tok[0])], [n], [True], chunks=steps // 4,
        chunk=4, impl=impl)
    given = np.concatenate([ids, np.asarray(fed[0], np.int32)])
    want, selected = ref.logits(
        params, cfg, given, rows=list(range(n - 1, n + steps)),
        return_selection=True)
    np.testing.assert_allclose(np.stack(rows + dec[0]), want, atol=TOL)
    # The last decode step's selection is the reference's, every layer.
    t = n + steps - 1
    for l in range(cfg.num_layers):
        bits = np.unpackbits(np.asarray(selected[l])[t])[: t + 1]
        assert np.array_equal(np.flatnonzero(bits),
                              sel[-1, l, 0][: min(t + 1, TOPK)])
    # The prefill's own record of what it attended (the twin's output).
    packed = np.asarray(r["selected"])  # [L, 1, chunk, K / 8]
    assert packed.shape[:3] == (4, 1, chunk) and packed.shape[3] >= 16
    last = (n - 1) % chunk
    for l in range(cfg.num_layers):
        assert np.array_equal(
            np.unpackbits(packed[l, 0, last])[:n],
            np.unpackbits(np.asarray(selected[l])[n - 1])[:n])


# (f) two lanes either side of the top k in one decode chunk.
@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_two_lanes_either_side_of_the_top_k_in_one_chunk(tiny, impl):
    cfg, params = tiny
    short, long_ = _ids(7, 6), _ids(8, 41)
    kv, bt = _pool(cfg, 3, 8)
    toks = []
    for s, ids in ((0, short), (2, long_)):
        kv, tok, _ = _prefill(cfg, params, kv, bt[s:s + 1], ids, chunk=16,
                              impl=impl)
        toks.append(int(tok[0]))
    kv, fed, rows, _ = _decode(
        cfg, params, kv, bt, [toks[0], 0, toks[1]], [6, 0, 41],
        [True, False, True], chunks=1, chunk=4, impl=impl)
    for s, ids in ((0, short), (2, long_)):
        n = len(ids)
        given = np.concatenate([ids, np.asarray(fed[s], np.int32)])
        want = ref.logits(params, cfg, given, rows=list(range(n, n + 4)))
        np.testing.assert_allclose(np.stack(rows[s]), want, atol=TOL)


# (d) a prefix hit past the top k: shared pages (and a copied last page)
# hand over the index keys with the latents. To the bit where the hit
# ends on a chunk boundary, so that every row is computed at the place
# in its chunk the cold request computes it at (XLA:CPU's matmul sums a
# row of another place in another order: one ulp, whatever the model).
@pytest.mark.parametrize("impl", ["xla", "pallas"])
@pytest.mark.parametrize("shared", [64, 76])
def test_a_prefix_hit_past_the_top_k_is_the_cold_request(tiny, shared, impl):
    cfg, params = tiny
    ids = _ids(3, 100)
    kv, bt = _pool(cfg, 1, 16)
    kv, tok, r = _prefill(cfg, params, kv, bt, ids, chunk=32, impl=impl)
    _, _, cold, _ = _decode(cfg, params, kv, bt, [int(tok[0])], [100], [True],
                            chunks=1, chunk=4, impl=impl)
    cold = np.stack([np.asarray(r["logits"])[0]] + cold[0])
    # Another request left the first `shared` positions behind; this one
    # shares its whole pages and copies the page it will write into.
    kv, _ = _pool(cfg, 2, 16)
    other = jnp.arange(16, 32, dtype=jnp.int32)[None]
    kv, _, _ = _prefill(cfg, params, kv, other, ids[:shared], chunk=32,
                        impl=impl)
    whole = shared // PS
    mine = np.arange(16, dtype=np.int32)
    mine[:whole] = np.asarray(other)[0, :whole]
    if shared % PS:
        kv = paged_kv.copy_pages(kv, other[0, whole], jnp.asarray(mine[whole]))
    mine = jnp.asarray(mine)[None]
    kv, tok, r = _prefill(cfg, params, kv, mine, ids, chunk=32, impl=impl,
                          start=shared)
    stale = jax.tree.map(jnp.copy, kv)
    _, _, hit, _ = _decode(cfg, params, kv, mine, [int(tok[0])], [100],
                           [True], chunks=1, chunk=4, impl=impl)
    hit = np.stack([np.asarray(r["logits"])[0]] + hit[0])
    if shared % 32 == 0:
        assert np.array_equal(hit, cold)
    np.testing.assert_allclose(hit, cold, atol=5e-7)
    # ... and a page of index keys left stale would not be.
    stale[paged_kv.INDEX_K] = stale[paged_kv.INDEX_K].at[
        :, other[0, 2]].set(0.0)
    _, _, off, _ = _decode(cfg, params, stale, mine, [int(tok[0])], [100],
                           [True], chunks=1, chunk=4, impl=impl)
    assert float(np.max(np.abs(np.stack(off[0]) - cold[1:]))) > 1e-3


def test_a_lane_under_the_top_k_reads_what_the_dense_walk_reads(tiny):
    """`_sparse_decode` under k keys is `latent_decode_attention` over
    the lane's pages, and over k it reads k rows whatever the length."""
    cfg, _ = tiny
    rng = np.random.default_rng(0)
    P, maxp, Dp, R = 12, 4, 128, cfg.kv_lora_rank
    pool = jnp.asarray(rng.normal(size=(P, PS, Dp)), F32)
    ipool = jnp.asarray(rng.normal(size=(P, PS, 24)), F32)
    bt = jnp.asarray(rng.permutation(P)[:8].reshape(2, maxp), jnp.int32)
    qf = jnp.asarray(rng.normal(size=(2, 4, Dp)), F32)
    qi = jnp.asarray(rng.normal(size=(2, 3, 24)), F32)
    wi = jnp.asarray(rng.normal(size=(2, 3)), F32)
    lengths = jnp.asarray([11, 30], jnp.int32)
    dense = paged_kv.latent_decode_attention(
        qf, pool, bt, lengths, scale=cfg.softmax_scale, value_dim=R)
    got, idx = qwen2._sparse_decode(
        cfg, qf, qi, wi, pool, ipool, bt, lengths,
        paged_kv.latent_decode_attention, "xla")
    np.testing.assert_allclose(got[0], dense[0], atol=1e-6)
    assert float(jnp.max(jnp.abs(got[1] - dense[1]))) > 1e-3
    assert idx.shape == (2, TOPK)
    assert np.array_equal(idx[0, :11], np.arange(11))
    assert np.all(np.diff(np.asarray(idx[1])) > 0) and int(idx[1, -1]) < 30


def _sorted_top_k(x, k):
    """The formula `topk_indices` replaced and is held to."""
    _, idx = jax.lax.top_k(x, min(k, x.shape[-1]))
    return jnp.sort(idx.astype(jnp.int32), axis=-1)


@pytest.mark.parametrize("ties", [False, True])
def test_topk_mask_is_lax_top_ks_set(ties):
    rng = np.random.default_rng(1)
    x = rng.normal(size=(5, 3, 70)).astype(np.float32)
    if ties:  # few distinct values, zeros of both signs, -inf tails
        x = np.round(x * 2) / 2
        x[0, 0, ::3] = -0.0
        x[1, 1, 20:] = -np.inf
        x[2, 2, 5:] = -np.inf
    for k in (1, 16, 69, 70, 200):
        got = np.asarray(paged_kv.topk_mask(jnp.asarray(x), k))
        _, idx = jax.lax.top_k(jnp.asarray(x), min(k, 70))
        want = np.zeros_like(got)
        np.put_along_axis(want, np.asarray(idx), True, axis=-1)
        assert np.array_equal(got, want), k
    row = jnp.asarray(x[:, 0])
    assert np.array_equal(np.asarray(paged_kv.topk_indices(row, 16)),
                          np.asarray(_sorted_top_k(row, 16)))


def _topk_case(name):
    rng = np.random.default_rng(3)
    if name == "random":
        return rng.normal(size=(5, 1000)).astype(np.float32), 64
    if name == "eight levels":  # heavy ties across pages and groups
        return np.round(rng.normal(size=(5, 1400)) * 2).clip(-4, 3).astype(
            np.float32), 64
    if name == "all equal":
        return np.full((3, 1100), 0.25, np.float32), 48
    if name.startswith("head of "):  # n finite scores, the rest -inf
        x = rng.normal(size=(3, 200)).astype(np.float32)
        x[:, {"0": 0, "k - 1": 15, "k": 16, "k + 1": 17}[name[8:]]:] = -np.inf
        return x, 16
    if name == "K <= k":
        return rng.normal(size=(3, 16)).astype(np.float32), 16
    if name == "K under k":
        return rng.normal(size=(3, 11)).astype(np.float32), 16
    if name == "the served width":  # 1,024 pages of 64, the cell's k
        x = rng.normal(size=(2, 65536)).astype(np.float32)
        x[1] = np.round(x[1] * 4) / 4
        x[1, 40000:] = -np.inf
        return x, 2048
    if name == "no page divides the width":
        return rng.normal(size=(4, 1531)).astype(np.float32), 100
    if name == "negative zero beside zero":
        x = -np.abs(rng.normal(size=(4, 700))).astype(np.float32)
        zeros = rng.random((4, 700)) < 0.2
        x[zeros] = np.where(rng.random(int(zeros.sum())) < 0.5, 0.0, -0.0)
        return x, 64  # fewer places than zeros: the cut falls among them
    raise KeyError(name)


@pytest.mark.parametrize("name", [
    "random", "eight levels", "all equal", "head of 0", "head of k - 1",
    "head of k", "head of k + 1", "K <= k", "K under k", "the served width",
    "no page divides the width", "negative zero beside zero"])
def test_topk_indices_is_lax_top_k_then_sorted(name):
    """The same set in the same order as `jax.lax.top_k` followed by a
    sort of its indices, under `jit` as the decode program runs it."""
    x, k = _topk_case(name)
    got = jax.jit(paged_kv.topk_indices, static_argnums=1)(jnp.asarray(x), k)
    want = _sorted_top_k(jnp.asarray(x), k)
    assert got.dtype == jnp.int32 and got.shape == want.shape
    assert np.array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_sparse_decode_is_the_sorted_top_ks_to_the_bit(
        tiny, impl, monkeypatch):
    """`_sparse_decode` with the counted selection returns the indices
    and the attention output it returned with `jax.lax.top_k` and a
    sort, bit for bit: lanes under, at and over the top k, tied scores
    (index keys repeated) and a lane of length 0."""
    cfg, _ = tiny
    rng = np.random.default_rng(5)
    P, maxp, S = 40, 8, 4
    Dp, Di, R = 128, cfg.index_head_dim, cfg.kv_lora_rank
    decode = (ppa if impl == "pallas" else paged_kv).latent_decode_attention
    pool = jnp.asarray(rng.normal(size=(P, PS, Dp)), F32)
    keys = rng.normal(size=(P, PS, Di)).astype(np.float32)
    keys[:, ::2] = keys[:, 1::2]  # pairs of equal index keys: tied scores
    bt = jnp.asarray(rng.permutation(P)[:S * maxp].reshape(S, maxp), jnp.int32)
    qf = jnp.asarray(rng.normal(size=(S, cfg.num_heads, Dp)), F32)
    qi = jnp.asarray(rng.normal(size=(S, cfg.index_heads, Di)), F32)
    wi = jnp.asarray(rng.normal(size=(S, cfg.index_heads)), F32)
    lengths = jnp.asarray([11, TOPK, 0, maxp * PS - 3], jnp.int32)

    def run():
        return qwen2._sparse_decode(
            cfg, qf, qi, wi, pool, jnp.asarray(keys), bt, lengths, decode,
            impl)

    got, idx = run()
    monkeypatch.setattr(paged_kv, "topk_indices", _sorted_top_k)
    want, want_idx = run()
    assert idx.shape == (S, TOPK) and idx.dtype == want_idx.dtype
    assert np.array_equal(np.asarray(idx), np.asarray(want_idx))
    assert np.array_equal(np.asarray(got), np.asarray(want))
    assert np.isfinite(np.asarray(got)).all()


@pytest.mark.parametrize("maxp", [4, 70])
def test_the_index_score_kernel_is_its_xla_twin(maxp):
    rng = np.random.default_rng(2)
    P, Hi, Di = 80, 3, 128
    pages = jnp.asarray(rng.normal(size=(P, PS, Di)), F32)
    pages = pages.at[5].set(jnp.nan)  # a page no lane owns
    owned = np.array([p for p in rng.permutation(P) if p != 5])
    bt = jnp.asarray(owned[:3 * maxp].reshape(3, maxp) if 3 * maxp < P
                     else np.resize(owned, (3, maxp)), jnp.int32)
    q = jnp.asarray(rng.normal(size=(3, Hi, Di)), F32)
    w = jnp.asarray(rng.normal(size=(3, Hi)), F32)
    lengths = jnp.asarray([maxp * PS, 0, maxp * PS // 2 + 3], jnp.int32)
    want = paged_kv.index_scores(q, w, pages, bt, lengths)
    got = ppa.index_scores(q, w, pages, bt, lengths, interpret=True)
    assert got.shape == want.shape == (3, maxp * PS)
    assert np.array_equal(np.isneginf(got), np.isneginf(want))
    assert np.all(np.isneginf(np.asarray(got)[1]))
    seen = np.isfinite(np.asarray(want))
    np.testing.assert_allclose(np.asarray(got)[seen], np.asarray(want)[seen],
                               atol=2e-5)


def _tile_mask(name, rng, B, T, Kt):
    if name == "full":
        return np.ones((B, T, Kt), bool)
    if name == "causal inside the tile":  # the chunk's own tile
        return np.broadcast_to(
            np.arange(T)[:, None] + (Kt - T) >= np.arange(Kt)[None], (B, T, Kt))
    seen = rng.random((B, T, Kt)) < 0.06
    if name == "empty rows and key blocks":
        seen[:, 3] = seen[:, T - 8:] = False  # rows with nothing selected
        seen[:, :, 128:384] = False  # whole blocks of keys nobody selected
        seen[1] = False  # a lane past its length
    return seen


def _fresh_carry(B, Hq, T, dv):
    """The state `_sparse_prefill`'s loop starts from."""
    return (jnp.full((B, Hq, T), jnp.finfo(F32).min, F32),
            jnp.zeros((B, Hq, T), F32), jnp.zeros((B, T, Hq, dv), F32))


@pytest.mark.parametrize("T", [24, 512])
@pytest.mark.parametrize("mask", [
    "full", "causal inside the tile", "six percent",
    "empty rows and key blocks"])
def test_the_masked_attention_kernel_is_its_xla_twin(mask, T):
    """`_dsa_attend` in interpret mode. From the loop's first state it
    returns `_masked_attend`'s tile as it is: the row maxima, the row
    sums and the unnormalised output, and a row with nothing selected
    comes back as the twin's (m = finfo.min, l = 0, o = 0). From the
    state that tile left, a second tile is `_attend_tile`'s merge, and a
    row with nothing in it keeps its state. 512 queries are two blocks
    of the kernel's 256."""
    rng = np.random.default_rng(4)
    B, Hq, d, dv, Kt = 2, 3, 24, 20, 512
    normal = lambda *shape: jnp.asarray(rng.normal(size=shape), F32)  # noqa: E731
    q = normal(B, T, Hq, d)
    k, v = normal(B, Kt, Hq, d), normal(B, Kt, Hq, dv)
    seen = jnp.asarray(_tile_mask(mask, rng, B, T, Kt))
    o, m, l = qwen2._masked_attend(q, k, v, seen, 0.3)
    first = masked_attention.masked_attend(
        _fresh_carry(B, Hq, T, dv), q, k, v, seen, 0.3, interpret=True)
    for g, w, tol in zip(first, (m, l, o), (0, 2e-5, 2e-5)):
        assert g.shape == w.shape and g.dtype == w.dtype == F32
        np.testing.assert_allclose(g, w, atol=tol, rtol=1e-6)
    empty = ~np.asarray(seen).any(-1)  # [B, T]
    assert empty.any() == (mask == "empty rows and key blocks")
    gm, gl, go = (np.asarray(x) for x in first)
    assert np.all(go[empty] == 0) and np.all(np.moveaxis(gl, 1, 2)[empty] == 0)
    assert np.all(np.moveaxis(gm, 1, 2)[empty] == np.finfo(np.float32).min)
    # A second tile, other keys under the same mask, merged into the first.
    k2, v2 = normal(B, Kt, Hq, d) * 1.5, normal(B, Kt, Hq, dv)
    want = qwen2._attend_tile(first, q, k2, v2, seen, 0.3)
    got = masked_attention.masked_attend(
        first, q, k2, v2, seen, 0.3, interpret=True)
    for g, w, tol in zip(got, want, (0, 5e-5, 5e-5)):
        np.testing.assert_allclose(g, w, atol=tol, rtol=1e-6)
    for g, f in zip(got, first):  # nothing selected: the state as it was
        g, f = (np.asarray(x) if x.shape[1] == T
                else np.moveaxis(np.asarray(x), 1, 2) for x in (g, f))
        assert np.array_equal(g[empty], f[empty])


def test_the_masked_attention_kernel_refuses_what_it_cannot_tile():
    x = jnp.zeros((1, 300, 2, 128), jnp.bfloat16)
    kv = jnp.zeros((1, 256, 2, 128), jnp.bfloat16)
    with pytest.raises(ValueError, match="_dsa_attend: cannot tile 300 q"):
        masked_attention.masked_attend(
            _fresh_carry(1, 2, 300, 128), x, kv, kv,
            jnp.ones((1, 300, 256), bool), 1.0, interpret=True)
    with pytest.raises(ValueError, match="_dsa_attend: cannot tile .* 200 k"):
        masked_attention.masked_attend(
            _fresh_carry(1, 2, 64, 128), x[:, :64], kv[:, :200], kv[:, :200],
            jnp.ones((1, 64, 200), bool), 1.0, interpret=False)


# A chunk that ends under the top k (the selection is everything), one
# that passes it, and one against a cached prefix of several key tiles.
@pytest.mark.parametrize("case, start, T", [
    ("under the top k", 0, 8), ("across the top k", 8, 24),
    ("over a prefix of three tiles", 2 * 1024 + 40, 32)])
def test_sparse_prefill_under_pallas_is_the_xla_loop(tiny, case, start, T):
    cfg, _ = tiny
    rng = np.random.default_rng(6)
    Hq, R = cfg.num_heads, cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    Hi, Di = cfg.index_heads, cfg.index_head_dim
    maxp = -(-(start + T) // PS) + 3
    P = maxp + 5
    normal = lambda *shape: jnp.asarray(rng.normal(size=shape), F32)  # noqa: E731
    pool, ipool = normal(P, PS, 128), normal(P, PS, Di)
    tables = jnp.asarray(rng.permutation(P)[:maxp][None], jnp.int32)
    args = (cfg, normal(1, T, Hq, dn), normal(1, T, Hq, dr),
            normal(Hq, dn, R), normal(Hq, R, dv), normal(1, T, Hi, Di),
            normal(1, T, Hi), pool, ipool, tables)
    positions = jnp.arange(start, start + T, dtype=jnp.int32)[None]
    kv_mask = jnp.ones((1, maxp * PS), bool)
    want, seen = qwen2._sparse_prefill(
        *args, positions=positions, kv_mask=kv_mask, attn_impl="xla")
    got, seen_p = qwen2._sparse_prefill(
        *args, positions=positions, kv_mask=kv_mask, attn_impl="pallas")
    assert np.array_equal(np.asarray(seen), np.asarray(seen_p))
    kept = np.asarray(seen).sum(-1)[0]
    assert np.array_equal(kept, np.minimum(np.arange(start, start + T) + 1,
                                           TOPK))
    assert seen.shape[-1] == -(-(maxp * PS) // 1024) * 1024
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=1e-5)


# (g) the router's scoring and its bias, four cases told apart.
@pytest.mark.parametrize("scoring", ["softmax", "sigmoid"])
@pytest.mark.parametrize("bias", [False, True])
def test_moe_select_scores_by_the_configs_function(scoring, bias):
    cfg = dataclasses.replace(
        cfg_lib.glm5_tiny().llm, router_scoring=scoring, router_bias=bias)
    r = jax.random.normal(jax.random.key(0), (40, 8), F32) * 2
    b = jax.random.normal(jax.random.key(1), (8,), F32) * 0.5
    w, idx = qwen2.moe_select(cfg, r, b if bias else None)
    p = np.asarray(jax.nn.sigmoid(r) if scoring == "sigmoid"
                   else jax.nn.softmax(r, -1), np.float64)
    pick = np.argsort(-(p + (np.asarray(b) if bias else 0)), -1,
                      kind="stable")[:, :2]
    assert np.array_equal(np.sort(idx, -1), np.sort(pick, -1))
    want = np.take_along_axis(p, np.asarray(idx), -1)
    want = 2.5 * want / want.sum(-1, keepdims=True)
    np.testing.assert_allclose(w, want, rtol=1e-5)
    # Told apart: the other three cases give other weights or experts.
    for other_s in ("softmax", "sigmoid"):
        for other_b in (False, True):
            if (other_s, other_b) == (scoring, bias):
                continue
            o = dataclasses.replace(cfg, router_scoring=other_s,
                                    router_bias=other_b)
            w2, idx2 = qwen2.moe_select(o, r, b if other_b else None)
            assert (not np.array_equal(idx, idx2)
                    or float(np.max(np.abs(w - w2))) > 1e-3)
    # The reference's own route, from the raw keys.
    if scoring == "sigmoid":
        x = jax.random.normal(jax.random.key(2), (40, 6), F32)
        kern = jax.random.normal(jax.random.key(3), (6, 8), F32)
        router = {"kernel": kern, **({"bias": b} if bias else {})}
        rw, rids = ref.route(x, router, cfg)
        w3, idx3 = qwen2.moe_route(cfg, x, kern, b if bias else None)
        assert np.array_equal(rids, idx3)
        np.testing.assert_allclose(rw, w3, rtol=1e-5)


# (h) the leading dense layer against an expert layer in its place.
def test_the_leading_dense_layer_is_run_and_told_apart(tiny):
    cfg, params = tiny
    ids = _ids(5, 30)
    got, _ = qwen2.forward(params, cfg, input_ids=jnp.asarray(ids)[None])
    np.testing.assert_allclose(got[0], ref.logits(params, cfg, ids), atol=TOL)
    # A model whose first layer is an expert layer is another model.
    moe_first = dataclasses.replace(cfg, dense_layers=0, num_layers=3)
    other, _ = qwen2.forward(
        {k: v for k, v in params.items() if k != "dense_layers"}, moe_first,
        input_ids=jnp.asarray(ids)[None])
    assert float(jnp.max(jnp.abs(other - got))) > 1e-2
    # ... and the dense FFN's own weights are in the result.
    cut = jax.tree.map(lambda a: a, params)
    cut["dense_layers"] = dict(
        params["dense_layers"],
        down_proj={"kernel": 0 * params["dense_layers"]["down_proj"]["kernel"]})
    assert float(jnp.max(jnp.abs(
        ref.logits(cut, cfg, ids) - got[0]))) > 1e-3
    assert params["dense_layers"]["gate_proj"]["kernel"].shape == (1, 64, 96)
    assert "router" not in params["dense_layers"]


# (i) the share test of the guide's section 4.
def test_sixteen_shares_routed_parts_and_one_shared_expert_are_the_layer():
    """32 routed experts, 4 a token, 16 shares of 2: each share's
    program computes its routed part AND the shared expert; the uncut
    reference's layer is the sixteen routed parts plus the shared expert
    counted once."""
    cfg = dataclasses.replace(
        cfg_lib.glm5_tiny().llm, num_experts=32, num_experts_per_tok=4,
        experts_held=None, num_layers=2)
    params = qwen2.init_params(cfg, jax.random.key(4))
    lp = jax.tree.map(lambda a: a[0] * 4 if a.ndim > 2 else a[0],
                      params["layers"])
    x = jax.random.normal(jax.random.key(5), (24, cfg.hidden_size), F32)
    want, ids = ref.moe_layer(x, lp, cfg)
    shared_part = ref.swiglu(x, lp["shared"])
    total = shared_part
    for first in range(0, 32, 2):
        share = dataclasses.replace(cfg, experts_held=(first, 2))
        kernels = jax.tree.map(lambda a: a[first:first + 2], lp["experts"])
        y, routing = qwen2._moe(
            share, x, lp["router"]["kernel"], kernels,
            jnp.asarray(0, jnp.int32), router_bias=lp["router"]["bias"],
            shared=lp["shared"])
        assert np.array_equal(routing["ids"], ids)
        total = total + (y - shared_part)
        part, _ = ref.moe_layer(x, {**lp, "experts": kernels}, share)
        np.testing.assert_allclose(y, part, atol=TOL)
    np.testing.assert_allclose(total, want, atol=4 * TOL)
    assert float(jnp.max(jnp.abs(shared_part))) > 0
    w, _ = qwen2.moe_route(cfg, x, lp["router"]["kernel"],
                           lp["router"]["bias"])
    np.testing.assert_allclose(np.sum(w, -1), 2.5, atol=1e-5)


@pytest.mark.parametrize("bad, match", [
    ({"kv_lora_rank": 0, "n_shared_experts": 0, "dense_layers": 0},
     "learned sparse attention"),
    ({"shortcut_double_layer": True, "n_shared_experts": 0,
      "dense_layers": 0}, "learned sparse attention"),
    ({"rope_scaling_factor": 8.0, "rope_original_max_position": 16},
     "learned sparse attention"),
    ({"rope_interleaved": False}, "learned sparse attention"),
    ({"index_heads": 0}, "index_heads"),
    ({"index_head_dim": 4}, "index_head_dim"),
    ({"router_scoring": "tanh"}, "router_scoring"),
    ({"dense_layers": 4}, "dense_layers"),
    ({"index_topk": 0}, "index_topk"),
])
def test_config_refuses_what_is_not_built(bad, match):
    with pytest.raises(ValueError, match=match):
        dataclasses.replace(cfg_lib.glm5_tiny().llm, **bad)


def test_presets_state_the_published_geometry_and_the_share():
    whole, share = cfg_lib.glm5().llm, cfg_lib.glm5_ep16().llm
    assert (whole.hidden_size, whole.num_heads, whole.num_layers) == (
        6144, 64, 78)
    assert (whole.qk_nope_head_dim, whole.qk_rope_head_dim,
            whole.v_head_dim) == (192, 64, 256)
    assert (whole.q_lora_rank, whole.kv_lora_rank) == (2048, 512)
    assert (whole.index_heads, whole.index_head_dim, whole.index_topk) == (
        32, 128, 2048)
    assert (whole.num_experts, whole.num_experts_per_tok,
            whole.moe_intermediate_size, whole.intermediate_size) == (
        256, 8, 2048, 12288)
    assert whole.dense_layers == 3 and whole.router_scoring == "sigmoid"
    assert whole.softmax_scale == ref.softmax_scale(whole) == 1 / 16
    assert whole.latent_page_dim == 640
    assert share.held == (0, 16) and share.vocab_size == 19360
    assert share.dense_layers == 1
    # The cell's depth: one dense and four expert layers.
    cell = dataclasses.replace(share, num_layers=5)
    shapes = jax.eval_shape(
        lambda: qwen2.init_params(cell, jax.random.key(0), jnp.bfloat16))
    nbytes = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(shapes))
    assert nbytes == 7_831_850_496
    pool = jax.eval_shape(lambda: qwen2.init_paged_kv_cache(
        cell, 12 * 1024, 64, jnp.bfloat16))
    assert sum(a.size * 2 for a in jax.tree.leaves(pool)) == 6_039_797_760


# --- the split engine, end to end -----------------------------------------


class IdTokenizer:
    def encode(self, text, add_special_tokens=False):
        return [min(ord(c), 500) for c in text]

    def decode(self, ids, skip_special_tokens=True):
        return "".join(f"<{int(i)}>" for i in ids)


def _reply_ids(reply):
    return [int(x) for x in reply.strip("<>").split("><")] if reply else []


@pytest.fixture(scope="module")
def pipe():
    cfg = cfg_lib.glm5_tiny()
    params = oryx.init_params(cfg, jax.random.key(0))
    params["llm"] = jax.tree.map(
        lambda a: a * 4 if a.ndim > 2 else a, params["llm"])
    return OryxInference(IdTokenizer(), params, cfg, template="plain")


def _want(pipe, question, cap):
    """The reference's greedy continuation and each step's top-two margin."""
    ids, *_ = pipe._prepare_request({"question": question})
    seq, margins = [int(t) for t in ids], []
    for _ in range(cap):
        row = np.asarray(ref.logits(
            pipe.params["llm"], pipe.cfg.llm, np.asarray(seq, np.int32),
            rows=[len(seq) - 1], pad_to=32))[0]
        top = np.sort(row)[-2:]
        margins.append(float(top[1] - top[0]))
        seq.append(int(row.argmax()))
    return seq[len(ids):], margins, len(ids)


def _same_until_a_near_tie(reply, want, margins):
    for g, w, m in zip(_reply_ids(reply), want, margins):
        if m <= 1e-4:
            break
        assert g == w


def test_engine_serves_a_context_and_turns_through_the_prefix_cache(pipe):
    """A context and two turns over it: the replies are the reference's,
    the second turn finds the context past the top k in the prefix cache
    (index keys with the latents), and the new counters say what the
    indexer scored and what attention read."""
    metrics = ServingMetrics()
    sched = ContinuousScheduler(
        pipe, num_slots=2, page_size=PS, max_ctx=256, prefill_chunk=32,
        autostart=False, metrics=metrics)
    doc = "the log says: " + "line; " * 14  # 98 tokens
    reqs = [(doc + "\nwhat failed?", 6),
            (doc + "\nwhat failed?\n<1><2>\nwhen?", 9)]
    sched.start()
    results = [sched.submit({"question": q}, cap, None).result(timeout=600)
               for q, cap in reqs]
    sched.close()
    for (q, cap), (reply, reason, usage) in zip(reqs, results):
        want, margins, n = _want(pipe, q, cap)
        assert reason == "length" and usage == (n, cap)
        _same_until_a_near_tie(reply, want, margins)
    n0, n1 = (usage[0] for _, _, usage in results)
    hit = int(metrics.get("prefix_cache_hit_tokens_total"))
    assert hit >= 12 * PS > TOPK
    seen = np.concatenate([np.arange(n0), np.arange(hit, n1)]) + 1
    assert metrics.get("prefill_attn_pairs_total") == seen.sum()
    assert metrics.get("prefill_index_pairs_total") == seen[seen > TOPK].sum()
    assert metrics.get("prefill_selected_pairs_total") == np.minimum(
        seen, TOPK).sum()
    # One key tile of 1,024 a chunk at these lengths, whichever side of
    # the top k the chunk ends on.
    assert metrics.get("prefill_masked_tiles_total") == (
        -(-n0 // 32) - (-(n1 - hit) // 32))
    # Decode rows at lengths n + 1 .. n + cap - 1 (the last token is
    # sampled, never fed).
    steps = np.concatenate([np.arange(n0 + 1, n0 + 6),
                            np.arange(n1 + 1, n1 + 9)])
    kv = metrics.get("decode_kv_tokens_total")
    sel = metrics.get("decode_selected_tokens_total")
    assert kv >= steps.sum() and sel >= TOPK * len(steps)
    assert sel / kv < 0.2  # 16 rows of a hundred and more
    L, K = pipe.cfg.llm.moe_layers, pipe.cfg.llm.num_experts_per_tok
    assert metrics.get("moe_prefill_pairs_total") == (
        metrics.get("prefill_tokens_total") * L * K)
    assert 0 < metrics.get("moe_held_experts_hit_total") <= metrics.get(
        "moe_held_expert_slots_total")


# (e) evicted and replayed past the top k.
def test_eviction_and_replay_past_the_top_k_reproduce_the_stream(pipe):
    sched = ContinuousScheduler(
        pipe, num_slots=2, page_size=PS, max_ctx=256, prefill_chunk=32,
        autostart=False)
    q, cap = "a question that is longer than the top k keys", 14
    want, margins, n = _want(pipe, q, cap)
    assert n > TOPK
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
    assert evicted and reason == "length" and usage == (n, cap)
    _same_until_a_near_tie(reply, want, margins)


@pytest.mark.parametrize("kw", [
    {"ragged": True}, {"ragged": True, "speculate": 2}, {"kv_dtype": "int8"},
])
def test_the_engine_refuses_what_a_latent_pool_refuses(pipe, kw):
    with pytest.raises(ValueError, match="latent attention"):
        ContinuousScheduler(pipe, num_slots=2, page_size=PS, max_ctx=128,
                            prefill_chunk=32, autostart=False, **kw)
