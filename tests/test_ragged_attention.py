"""Unified ragged paged attention: ONE dispatch for mixed prefill +
decode (ROADMAP item 1, arXiv 2604.15464).

Three layers of proof, every one against the split path it replaces:

  * op level — the packed reference (`ops.paged_kv.ragged_paged_attention`)
    is BIT-identical per row to the split decode reference and to the
    per-sequence prefill attention call; the Pallas kernel
    (`ops.pallas.paged_attention.ragged_paged_attention`) matches the
    reference to fp tolerance across its grid-table tile variants.
  * driver level — `generate_paged(ragged=True)` emits bit-identical
    token ids to the split chunked-decode driver.
  * engine level — `ContinuousScheduler(ragged=True)` replies are
    byte-identical to the split scheduler AND the solo pipeline across
    mixed query lengths, page-boundary prompts, sub-page prompts,
    prefix-cache partial-page COW hits, eviction replay, and a tp=2
    mesh — while `oryx_serving_dispatches_total` shows kind="ragged"
    ONLY (the one-dispatch-per-step claim), and a recompile watchdog
    shows ZERO compiles across varying live-slot mixes after warmup.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from oryx_tpu import config as cfg_lib
from oryx_tpu.models import generate as gen_lib
from oryx_tpu.models import oryx, qwen2
from oryx_tpu.ops import attention as att_lib
from oryx_tpu.ops import paged_kv
from oryx_tpu.ops.pallas import paged_attention as ppa
from oryx_tpu.serve.pipeline import OryxInference
from oryx_tpu.serve.scheduler import ContinuousScheduler
from oryx_tpu.utils.metrics import ServingMetrics


class FakeTokenizer:
    def encode(self, text, add_special_tokens=False):
        return [min(ord(c), 500) for c in text]

    def decode(self, ids, skip_special_tokens=True):
        return "".join(chr(i) for i in ids if 0 < i < 500)


# ---------------------------------------------------------------------------
# Op level: packed reference vs the split references, bit for bit
# ---------------------------------------------------------------------------


def _pool(seed=0, S=3, Hk=2, D=16, ps=8, maxp=4, P=16,
          lengths=(5, 17, 32)):
    rng = np.random.default_rng(seed)
    alloc = paged_kv.PageAllocator(P, ps)
    bt = np.full((S, maxp), alloc.sentinel, np.int32)
    for b, L in enumerate(lengths):
        pages = alloc.alloc(alloc.pages_for(int(L)))
        bt[b, : len(pages)] = pages
    kp = rng.standard_normal((P, ps, Hk, D)).astype(np.float32)
    vp = rng.standard_normal((P, ps, Hk, D)).astype(np.float32)
    return bt, kp, vp, np.asarray(lengths, np.int32)


def test_packed_reference_matches_decode_rows():
    """A packed row at position len-1 IS a decode step: bit-equal to
    the split decode reference for every sequence at once."""
    bt, kp, vp, lengths = _pool()
    rng = np.random.default_rng(1)
    S, Hq, D = 3, 4, 16
    q = rng.standard_normal((S, Hq, D)).astype(np.float32)
    dec = paged_kv.ragged_decode_attention(
        jnp.asarray(q[:, None]), jnp.asarray(kp), jnp.asarray(vp),
        jnp.asarray(bt), jnp.asarray(lengths),
    )
    got = paged_kv.ragged_paged_attention(
        jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp),
        jnp.asarray(bt), jnp.arange(S, dtype=jnp.int32),
        jnp.asarray(lengths - 1),
    )
    np.testing.assert_array_equal(np.asarray(got), np.asarray(dec)[:, 0])


def test_packed_reference_matches_prefill_rows():
    """Packed rows at consecutive positions of ONE sequence are that
    sequence's chunked-prefill attention, row for row, bit for bit —
    mixed q lengths in one buffer change nothing per row."""
    bt, kp, vp, lengths = _pool()
    rng = np.random.default_rng(2)
    Hq, D, ps, maxp = 4, 16, 8, 4
    K = maxp * ps
    T = 6  # suffix tokens of sequence 1 at positions 9..14
    start = 9
    q = rng.standard_normal((T, Hq, D)).astype(np.float32)
    # Split path: the [1, T] chunk attention paged_prefill runs.
    kd = paged_kv.gather_pages(jnp.asarray(kp), jnp.asarray(bt[1:2]))
    vd = paged_kv.gather_pages(jnp.asarray(vp), jnp.asarray(bt[1:2]))
    kv_mask = (
        np.arange(K)[None] < min(int(lengths[1]), start + T)
    ).astype(np.int32)
    ref = att_lib.attention(
        jnp.asarray(q[None]), kd, vd, causal=True,
        q_positions=jnp.asarray(
            start + np.arange(T, dtype=np.int32)
        )[None],
        kv_mask=jnp.asarray(kv_mask),
    )
    # Packed path: the same tokens as ragged rows, with decode rows of
    # OTHER sequences interleaved around them.
    seg = np.array([0, 2] + [1] * T, np.int32)
    pos = np.concatenate(
        [[4, 31], start + np.arange(T)]
    ).astype(np.int32)
    qpack = np.concatenate(
        [rng.standard_normal((2, Hq, D)).astype(np.float32), q]
    )
    got = paged_kv.ragged_paged_attention(
        jnp.asarray(qpack), jnp.asarray(kp), jnp.asarray(vp),
        jnp.asarray(bt), jnp.asarray(seg), jnp.asarray(pos),
    )
    np.testing.assert_array_equal(np.asarray(got)[2:], np.asarray(ref)[0])


def test_write_pages_packed_matches_write_pages():
    """The packed writer lands a contiguous chunk exactly where the
    per-sequence writer does; masked rows and sentinel routes drop."""
    bt, kp, _, _ = _pool()
    rng = np.random.default_rng(3)
    Hk, D, ps = 2, 16, 8
    new = rng.standard_normal((1, 5, Hk, D)).astype(np.float32)
    w_seq = paged_kv.write_pages(
        jnp.asarray(kp), jnp.asarray(new), jnp.asarray(bt[1:2]),
        jnp.asarray([10], np.int32),
    )
    w_pack = paged_kv.write_pages_packed(
        jnp.asarray(kp), jnp.asarray(new[0]), jnp.asarray(bt),
        jnp.full((5,), 1, jnp.int32),
        jnp.asarray(10 + np.arange(5), np.int32),
    )
    np.testing.assert_array_equal(np.asarray(w_seq), np.asarray(w_pack))
    # write_mask False and sentinel-routed rows leave the pool alone.
    w_none = paged_kv.write_pages_packed(
        jnp.asarray(kp), jnp.asarray(new[0]), jnp.asarray(bt),
        jnp.full((5,), 1, jnp.int32),
        jnp.asarray(10 + np.arange(5), np.int32),
        write_mask=jnp.zeros((5,), bool),
    )
    np.testing.assert_array_equal(np.asarray(w_none), kp)
    w_sent = paged_kv.write_pages_packed(
        jnp.asarray(kp), jnp.asarray(new[0]), jnp.asarray(bt),
        jnp.full((5,), 0, jnp.int32),  # slot 0 holds 1 page (5 slots)
        jnp.asarray(100 + np.arange(5), np.int32),  # beyond its table
    )
    # Slot 0's table past its page is all sentinel -> dropped.
    np.testing.assert_array_equal(np.asarray(w_sent), kp)


@pytest.mark.parametrize(
    "Hk,hb", [(4, 4), (1, 1), (16, 8), (16, 16), (4, None)]
)
def test_pallas_ragged_matches_reference_across_tiles(Hk, hb):
    """The Pallas kernel (interpret mode on CPU) matches the packed
    reference across the kv-head tiles the TPU lowering accepts,
    page-boundary positions and position 0."""
    bt, kp, vp, _ = _pool(seed=4, Hk=Hk, lengths=(8, 17, 32))
    rng = np.random.default_rng(5)
    Hq, D = 2 * Hk, 16
    seg = np.array([0, 1, 2, 1, 1, 0], np.int32)
    pos = np.array([7, 16, 31, 8, 3, 0], np.int32)  # 7,8: page edges
    q = rng.standard_normal((len(seg), Hq, D)).astype(np.float32)
    ref = paged_kv.ragged_paged_attention(
        jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp),
        jnp.asarray(bt), jnp.asarray(seg), jnp.asarray(pos),
    )
    got = ppa.ragged_paged_attention(
        jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp),
        jnp.asarray(bt), jnp.asarray(seg), jnp.asarray(pos),
        heads_per_block=hb,
    )
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(ref), atol=2e-6, rtol=2e-6,
    )


def test_pallas_decode_is_a_length_one_ragged_lane():
    """`ragged_decode_attention` runs the packed kernel (row b = segment
    b at position len-1): equal to the XLA decode reference, zeros for
    an empty row."""
    bt, kp, vp, _ = _pool(seed=6, Hk=2, lengths=(8, 17, 32))
    rng = np.random.default_rng(7)
    q = rng.standard_normal((3, 1, 4, 16)).astype(np.float32)
    lens = jnp.asarray([8, 9, 0], jnp.int32)
    ref = paged_kv.ragged_decode_attention(
        jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp),
        jnp.asarray(bt), lens,
    )
    got = ppa.ragged_decode_attention(
        jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp),
        jnp.asarray(bt), lens,
    )
    np.testing.assert_allclose(
        np.asarray(got)[:2], np.asarray(ref)[:2], atol=2e-6, rtol=2e-6
    )
    assert not np.asarray(got)[2].any()


def test_heads_per_block_offers_only_what_the_lowering_takes(monkeypatch):
    """Only the whole kv-head axis or a multiple of 8 dividing it is a
    legal tile; the default picks one, and an illegal explicit value or
    $ORYX_RPA_HEADS_PER_BLOCK pin raises naming it (never a clamp)."""
    assert ppa.legal_heads_per_block(4) == (4,)
    assert ppa.legal_heads_per_block(16) == (8, 16)
    assert ppa.legal_heads_per_block(3) == (3,)
    monkeypatch.delenv("ORYX_RPA_HEADS_PER_BLOCK", raising=False)
    assert ppa.ragged_heads_per_block(128, 64, 4) == 4
    assert ppa.ragged_heads_per_block(128, 256, 16) == 8  # VMEM budget
    assert ppa.ragged_heads_per_block(64, 16, 16) == 16
    with pytest.raises(ValueError, match="heads_per_block=2"):
        ppa.check_heads_per_block(2, 4)
    monkeypatch.setenv("ORYX_RPA_HEADS_PER_BLOCK", "8")
    assert ppa.ragged_heads_per_block(128, 64, 16) == 8
    monkeypatch.setenv("ORYX_RPA_HEADS_PER_BLOCK", "2")
    with pytest.raises(ValueError, match=r"\$ORYX_RPA_HEADS_PER_BLOCK=2"):
        ppa.ragged_heads_per_block(128, 64, 4)


# ---------------------------------------------------------------------------
# The page walk's edges, at the serving geometry (page 64, 4 kv heads,
# D 128), where a step handles `ragged_pages_per_block` pages
# ---------------------------------------------------------------------------

W_PS, W_HK, W_D, W_MAXP, W_S, W_LAYER_PAGES = 64, 4, 128, 20, 16, 128
W_NPB = ppa.ragged_pages_per_block(W_D, W_PS, W_HK, W_MAXP)
W_BLOCK = W_NPB * W_PS  # tokens one step of the walk handles
W_FULL = W_MAXP * W_PS  # the whole table; not a whole number of blocks


def _decode_rows(*lengths):
    """Decode rows: row i is segment i at position lengths[i] - 1."""
    return [(i, n - 1) for i, n in enumerate(lengths)]


def _block_rows(lengths, B=4):
    """Block-diffusion rows as `qwen2.forward` masks them: slot s's B
    lanes sit at lengths[s]..+B-1 and see up to the end of the block
    their own position lies in."""
    return [
        (s, p - p % B + B - 1)
        for s, n in enumerate(lengths) for p in range(n, n + B)
    ]


# name -> (packed rows [(segment, position)], layer of the flat pool)
WALK_CASES = {
    **{
        f"length_{n}": (_decode_rows(n, 700, 0, 300), 0)
        for n in (0, 1, W_PS - 1, W_PS, W_PS + 1, W_BLOCK - 1, W_BLOCK,
                  W_BLOCK + 1, W_FULL)
    },
    "shuffled_tables_sentinel_tails": (
        _decode_rows(*(37 + 53 * i for i in range(W_S))), 0,
    ),
    "layer_offset_tables": (_decode_rows(5, 640, 1000, W_FULL, 129), 1),
    # One segment's consecutive positions, over a page edge and over
    # the edge of a step's block.
    "prefill_suffix": (
        [(2, p) for p in range(W_PS - 3, W_PS + 3)]
        + [(5, p) for p in range(W_BLOCK - 4, W_BLOCK + 4)], 0,
    ),
    "verify_lanes_1_plus_3": (
        [(s, n + j) for s, n in ((0, 61), (3, 510), (7, 1021))
         for j in range(4)], 0,
    ),
    # Four slots: tails 3, 1 and 0 (a slot's lanes then lie in two
    # blocks), one over a page edge, one over a step's edge.
    "block_rows": (
        _block_rows([W_PS * 3 - 1, W_BLOCK - 3, 252, 2 * W_BLOCK - 2]), 0,
    ),
}


def _walk_pool(pool: str, rows):
    """A flat two-layer pool with shuffled tables: every segment owns
    just the pages its longest row reads, the rest of its table is the
    sentinel."""
    rng = np.random.default_rng(11)
    P = 2 * W_LAYER_PAGES
    need = np.zeros(W_S, np.int64)
    for seg, pos in rows:
        need[seg] = max(need[seg], pos // W_PS + 1)
    assert need.sum() <= W_LAYER_PAGES
    bt = np.full((W_S, W_MAXP), P + 3, np.int32)
    order = rng.permutation(W_LAYER_PAGES)
    at = 0
    for seg, n in enumerate(need):
        bt[seg, :n] = order[at:at + n]
        at += n
    shape = (P, W_PS, W_HK, W_D)
    if pool == "bf16":
        kp = jnp.asarray(rng.standard_normal(shape), jnp.bfloat16)
        vp = jnp.asarray(rng.standard_normal(shape), jnp.bfloat16)
        return bt, kp, vp

    def quant():
        return paged_kv.QuantPages(
            jnp.asarray(rng.integers(-127, 128, shape), jnp.int8),
            jnp.asarray(rng.uniform(0.002, 0.03, shape[:2]), jnp.float32),
            jnp.bfloat16,
        )

    return bt, quant(), quant()


@pytest.mark.parametrize("pool", ["bf16", "int8"])
@pytest.mark.parametrize("Hq", [28, 32], ids=["G7", "G8"])
@pytest.mark.parametrize("case", sorted(WALK_CASES))
def test_pallas_page_walk_edges(case, Hq, pool):
    """The kernel's walk (interpret mode) against the packed reference
    at every edge of it: nothing to read, a page's and a step's last,
    first and next token, the whole table, tables that are shuffled,
    end in sentinels and point into a flat pool's second layer,
    consecutive positions of one segment, block rows; 7 and 8 q heads a
    kv head; bf16 and int8 pools. Rows past the case's own are rows of
    length 0 and give zeros."""
    rows, layer = WALK_CASES[case]
    bt, kp, vp = _walk_pool(pool, rows)
    bt = np.where(bt < W_LAYER_PAGES, bt + layer * W_LAYER_PAGES, bt)
    seg = np.zeros(W_S, np.int32)
    pos = np.full(W_S, -1, np.int32)
    seg[: len(rows)], pos[: len(rows)] = np.asarray(rows, np.int32).T
    q = jnp.asarray(
        np.random.default_rng(12).standard_normal((W_S, Hq, W_D)),
        jnp.bfloat16,
    )
    args = (q, kp, vp, jnp.asarray(bt), jnp.asarray(seg), jnp.asarray(pos))
    ref = np.asarray(paged_kv.ragged_paged_attention(*args), np.float32)
    got = np.asarray(ppa.ragged_paged_attention(*args), np.float32)
    live = pos >= 0
    assert live.any() and np.isfinite(got).all()
    np.testing.assert_allclose(got[live], ref[live], atol=1e-2, rtol=2e-2)
    assert not got[~live].any()


@pytest.mark.parametrize("pool", ["bf16", "int8"])
@pytest.mark.parametrize("Hq", [28, 32], ids=["G7", "G8"])
def test_pallas_finished_decode_lanes_beside_live_ones(Hq, pool):
    """`paged_decode_chunk` hands a finished or empty lane kv_lengths 0:
    it takes no step of the walk and returns zeros, and the live lanes
    beside it (before, between and after) read what they read alone."""
    lens = np.zeros(W_S, np.int32)
    lens[[1, 2, 6, 15]] = [W_BLOCK + 1, 800, 1, W_FULL]
    bt, kp, vp = _walk_pool(pool, _decode_rows(*lens))
    q = jnp.asarray(
        np.random.default_rng(13).standard_normal((W_S, 1, Hq, W_D)),
        jnp.bfloat16,
    )
    args = (q, kp, vp, jnp.asarray(bt), jnp.asarray(lens))
    ref = np.asarray(paged_kv.ragged_decode_attention(*args), np.float32)
    got = np.asarray(ppa.ragged_decode_attention(*args), np.float32)
    live = lens > 0
    np.testing.assert_allclose(got[live], ref[live], atol=1e-2, rtol=2e-2)
    assert not got[~live].any()


# ---------------------------------------------------------------------------
# Driver level: generate_paged(ragged=True)
# ---------------------------------------------------------------------------


def test_generate_paged_ragged_bit_parity():
    """The packed one-buffer decode program emits bit-identical token
    ids to the split [B, 1]-batch chunked decode — greedy AND seeded
    sampling (per-row keys make the draw layout-independent)."""
    tiny = cfg_lib.oryx_tiny()
    cfg, gcfg = tiny.llm, tiny.generation
    params = qwen2.init_params(cfg, jax.random.key(0))
    rng = np.random.default_rng(0)
    lengths = np.array([5, 12, 9], np.int32)
    emb = rng.standard_normal(
        (3, int(lengths.max()), cfg.hidden_size)
    ).astype(np.float32) * 0.1
    for b, L in enumerate(lengths):
        emb[b, L:] = 0.0
    import dataclasses

    for gc in (gcfg, dataclasses.replace(gcfg, temperature=0.8, top_p=0.9)):
        kw = dict(
            inputs_embeds=jnp.asarray(emb), lengths=jnp.asarray(lengths),
            max_new_tokens=7, page_size=8, chunk=4, kv_capacity=64,
            key=jax.random.key(7),
        )
        t1, n1, f1 = gen_lib.generate_paged(params, cfg, gc, **kw)
        t2, n2, f2 = gen_lib.generate_paged(
            params, cfg, gc, ragged=True, **kw
        )
        np.testing.assert_array_equal(np.asarray(t1), np.asarray(t2))
        np.testing.assert_array_equal(np.asarray(n1), np.asarray(n2))
        np.testing.assert_array_equal(np.asarray(f1), np.asarray(f2))


# ---------------------------------------------------------------------------
# Engine level: ragged scheduler == split scheduler == solo pipeline
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def pipe():
    cfg = cfg_lib.oryx_tiny()
    params = oryx.init_params(cfg, jax.random.key(0))
    return OryxInference(FakeTokenizer(), params, cfg)


def _run(pipe, reqs, *, ragged, **kw):
    metrics = ServingMetrics()
    defaults = dict(
        num_slots=2, page_size=16, chunk=4, max_ctx=512,
        prefill_chunk=8,
    )
    defaults.update(kw)
    sched = ContinuousScheduler(
        pipe, metrics=metrics, autostart=False, ragged=ragged,
        **defaults,
    )
    handles = [sched.submit({"question": q}, cap) for q, cap in reqs]
    sched.start()
    results = [h.result(timeout=600) for h in handles]
    sched.close()
    return results, metrics


def _dispatches(metrics, kind):
    fam = metrics.registry.counter("dispatches_total", ("kind",))
    return fam.labels(kind=kind).value


def test_scheduler_ragged_parity_one_dispatch_mixed_lengths(pipe):
    """The headline: mixed prompt lengths (one prompt shorter than a
    page, one spanning pages) through the FUSED engine — replies
    byte-identical to the split engine and the solo pipeline, with
    kind=\"ragged\" the ONLY dispatch kind the engine paid."""
    reqs = [
        ("hi", 5),  # prompt + template shorter than several pages
        ("what is going on with all of this, tell me now please", 8),
        ("tell me more", 6),
    ]
    split, _ = _run(pipe, reqs, ragged=False)
    ragg, rm = _run(pipe, reqs, ragged=True)
    for (q, cap), a, b in zip(reqs, split, ragg):
        assert a == b, q
        assert b[0] == pipe.chat(q, max_new_tokens=cap), q
    assert _dispatches(rm, "ragged") > 0
    assert _dispatches(rm, "prefill") == 0
    assert _dispatches(rm, "decode") == 0
    # The fused path fed the dispatch-occupancy histogram.
    assert "oryx_serving_dispatch_rows" in rm.render()


def test_scheduler_ragged_page_boundary_prompt(pipe):
    """A prompt whose token count is an exact page multiple (the
    boundary the block-table walk and the splice clamp both care
    about) stays byte-identical through the fused path."""
    ps = 16
    q = "hello"
    n = len(pipe._prepare_request({"question": q})[0])
    q = q + " " + "a" * ((-n - 1) % ps)  # pad ids to a page multiple
    n2 = len(pipe._prepare_request({"question": q})[0])
    assert n2 % ps == 0, (n2, ps)
    split, _ = _run(pipe, [(q, 6)], ragged=False, page_size=ps)
    ragg, _ = _run(pipe, [(q, 6)], ragged=True, page_size=ps)
    assert split[0] == ragg[0]
    assert ragg[0][0] == pipe.chat(q, max_new_tokens=6)


def test_scheduler_ragged_prefix_cache_partial_page_cow(pipe):
    """Look-alike prompts: the second splices the first's cached
    prefix with a partial-page COW (the shared prefix is not
    page-aligned) — fused-path replies stay byte-identical to the
    solo pipeline and the cache genuinely hit."""
    reqs = [
        ("hello there", 5),
        ("hello there friend", 5),
        ("hello there again, why?", 4),
    ]
    ragg, rm = _run(pipe, reqs, ragged=True)
    for (q, cap), r in zip(reqs, ragg):
        assert r[0] == pipe.chat(q, max_new_tokens=cap), q
    assert rm.get("prefix_cache_hit_tokens_total") > 0


def test_scheduler_ragged_eviction_replay(pipe):
    """Page pressure evicts the younger slot mid-decode; its
    deterministic replay re-admits THROUGH THE FUSED PATH and both
    replies stay byte-identical to the solo pipeline."""
    import math

    q1, q2 = "hello there", "tell me more"
    chunk, ps = 4, 16
    ids1 = len(pipe._prepare_request({"question": q1})[0])
    ids2 = len(pipe._prepare_request({"question": q2})[0])
    admit1 = math.ceil((ids1 + chunk) / ps)
    admit2 = math.ceil((ids2 + chunk) / ps)
    cap = (admit1 * ps - ids1) + ps  # forces one extra page per row
    ragg, rm = _run(
        pipe, [(q1, cap), (q2, cap)], ragged=True, page_size=ps,
        chunk=chunk, num_pages=admit1 + admit2 + 1, prefix_cache=False,
    )
    assert rm.get("evicted") >= 1
    for q, (reply, _, usage) in zip((q1, q2), ragg):
        assert reply == pipe.chat(q, max_new_tokens=cap), q
        assert usage[1] == cap


def test_scheduler_ragged_tp2_mesh_parity():
    """The fused dispatch under a tp=2 mesh (KV pool heads-sharded by
    _place_kv, params tp-sharded): byte-identical to the unsharded
    solo pipeline — the packed buffer changes nothing about WHERE
    heads compute."""
    if jax.device_count() < 2:
        pytest.skip("needs multiple (CPU) devices")
    from oryx_tpu.config import MeshConfig
    from oryx_tpu.parallel.mesh import build_mesh

    mesh = build_mesh(MeshConfig(tp=2), devices=jax.devices()[:2])
    cfg = cfg_lib.oryx_tiny()
    params = oryx.init_params(cfg, jax.random.key(0))
    ref_pipe = OryxInference(FakeTokenizer(), params, cfg)
    tp_pipe = OryxInference(
        FakeTokenizer(), params, cfg, mesh=mesh, sharding_mode="tp"
    )
    reqs = [("hello there", 5), ("hello there friend", 5)]
    ragg, rm = _run(tp_pipe, reqs, ragged=True)
    for (q, cap), r in zip(reqs, ragg):
        assert r[0] == ref_pipe.chat(q, max_new_tokens=cap), q
    assert _dispatches(rm, "ragged") > 0


def test_scheduler_ragged_zero_recompiles_across_mixes(pipe):
    """The static-dispatch-shape claim, runtime-proven: after a warmup
    workload compiles the two shape classes (prefill lanes present /
    absent), a DIFFERENT live-slot mix — other lengths, other
    concurrency, staggered finishes — compiles NOTHING."""
    from oryx_tpu.analysis.sanitizers import recompile_watchdog

    metrics = ServingMetrics()
    sched = ContinuousScheduler(
        pipe, num_slots=3, page_size=16, chunk=4, max_ctx=512,
        metrics=metrics, autostart=False, prefill_chunk=8,
        ragged=True, prefix_cache=False,
    )
    warm = [
        sched.submit({"question": "warm up the two shape classes"}, 6),
        sched.submit({"question": "warm the second slot too"}, 3),
    ]
    sched.start()
    for h in warm:
        h.result(timeout=600)
    with recompile_watchdog(budget=1, action="record") as stats:
        hs = [
            sched.submit({"question": q}, cap)
            for q, cap in [
                ("a totally different mix of lengths now", 7),
                ("short", 2),
                ("and a third request to stagger the finishes", 5),
                ("plus one more that queues behind them all", 4),
            ]
        ]
        for h in hs:
            h.result(timeout=600)
    sched.close()
    assert not stats.counts, (
        f"varying live-slot mixes recompiled: {stats.counts}"
    )


def test_dispatch_metrics_split_mode(pipe):
    """The split engine's dispatch accounting: both legacy kinds tick
    (the A/B denominator scripts/bench_paged_attention.py divides by)
    and the occupancy histogram renders."""
    reqs = [("hello there", 4), ("tell me more", 4)]
    _, sm = _run(pipe, reqs, ragged=False)
    assert _dispatches(sm, "prefill") > 0
    assert _dispatches(sm, "decode") > 0
    assert _dispatches(sm, "ragged") == 0
    text = sm.render()
    assert "oryx_serving_dispatches_total" in text
    assert "oryx_serving_dispatch_rows_bucket" in text
