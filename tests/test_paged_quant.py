"""int8 paged KV pool (ops/paged_kv.QuantPages): quantize-on-write /
dequantize-in-the-page-walk numerics, byte-determinism invariants
(chunk-grouping independence, COW, fetch/upload round trip), kernel
parity vs the XLA reference, and the engine-level contracts the
serving tier leans on (ragged==split within the int8 config,
cold-vs-cached byte parity, ~2x resident tokens per byte)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from oryx_tpu import config as cfg_lib
from oryx_tpu.models import generate, oryx, qwen2
from oryx_tpu.ops import paged_kv
from oryx_tpu.ops.pallas import paged_attention as ppa
from oryx_tpu.serve.pipeline import OryxInference
from oryx_tpu.serve.scheduler import ContinuousScheduler
from oryx_tpu.utils import quant


class FakeTokenizer:
    def encode(self, text, add_special_tokens=False):
        return [min(ord(c), 500) for c in text]

    def decode(self, ids, skip_special_tokens=True):
        return "".join(chr(i) for i in ids if 0 < i < 500)


@pytest.fixture(scope="module")
def pipe():
    cfg = cfg_lib.oryx_tiny()
    params = oryx.init_params(cfg, jax.random.key(0))
    return OryxInference(FakeTokenizer(), params, cfg)


def _quant_pool(P=8, ps=4, Hk=2, D=8):
    return paged_kv.QuantPages(
        jnp.zeros((P, ps, Hk, D), jnp.int8),
        jnp.zeros((P, ps), jnp.float32),
    )


# ---------------------------------------------------------------------------
# Op layer: write/gather numerics + byte determinism
# ---------------------------------------------------------------------------


def test_write_gather_roundtrip_error_within_envelope():
    qp = _quant_pool()
    bt = jnp.asarray([[0, 1, 2, 3]], jnp.int32)
    new = jax.random.normal(jax.random.key(0), (1, 10, 2, 8))
    pool = paged_kv.write_pages(qp, new, bt, jnp.asarray([0], jnp.int32))
    got = paged_kv.gather_pages(pool, bt)[0, :10]
    err = np.abs(np.asarray(got) - np.asarray(new[0]))
    # Per-row symmetric int8: error <= scale/2 per element.
    scale = np.asarray(pool.scale).reshape(-1)[:10]
    assert (err <= scale[:, None, None] / 2 + 1e-7).all()
    # Statistical envelope matches the shared round-trip helper.
    stats = quant.roundtrip_error_stats(new[0], axis=-1)
    assert err.max() <= 10 * max(stats["max_abs_err"], 1e-6)


def test_quantization_is_chunk_grouping_independent():
    """Per-row scales make the stored bytes a pure function of each
    token's value: writing the same 10 tokens in one shot vs 2+8 vs
    5+5 lands IDENTICAL codes and scales — the invariant that keeps
    cold-vs-cached, eviction-replay and spill/reload byte-exact on
    the quantized path."""
    bt = jnp.asarray([[0, 1, 2, 3]], jnp.int32)
    new = jax.random.normal(jax.random.key(1), (1, 10, 2, 8))

    def write_split(*spans):
        pool = _quant_pool()
        off = 0
        for n in spans:
            pool = paged_kv.write_pages(
                pool, new[:, off:off + n], bt,
                jnp.asarray([off], jnp.int32),
            )
            off += n
        return pool

    one = write_split(10)
    for spans in ((2, 8), (5, 5), (1, 1, 8)):
        other = write_split(*spans)
        assert jnp.array_equal(one.q, other.q)
        assert jnp.array_equal(one.scale, other.scale)


def test_packed_writer_matches_per_sequence_writer_bytes():
    bt = jnp.asarray([[0, 1, 2, 3]], jnp.int32)
    new = jax.random.normal(jax.random.key(2), (1, 6, 2, 8))
    seq = paged_kv.write_pages(
        _quant_pool(), new, bt, jnp.asarray([0], jnp.int32)
    )
    packed = paged_kv.write_pages_packed(
        _quant_pool(), new[0], bt,
        jnp.zeros((6,), jnp.int32),
        jnp.arange(6, dtype=jnp.int32),
    )
    assert jnp.array_equal(seq.q, packed.q)
    assert jnp.array_equal(seq.scale, packed.scale)


def test_masked_rows_drop_codes_and_scales_together():
    bt = jnp.asarray([[0, 1, 2, 3]], jnp.int32)
    new = jax.random.normal(jax.random.key(3), (1, 4, 2, 8)) + 5.0
    pool = paged_kv.write_pages(
        _quant_pool(), new, bt, jnp.asarray([0], jnp.int32),
        write_mask=jnp.asarray([False]),
    )
    assert not np.asarray(pool.q).any()
    assert not np.asarray(pool.scale).any()


def _layered_quant_pool(L=2, P=8, ps=4, Hk=2, D=8, seed=4):
    """A populated POOL-level pytree: [L, P, ...] leaves, the layout
    copy_pages/fetch_page/upload_page contract on (the per-plane
    tests above exercise the in-dispatch [P, ...] layer view)."""
    k1, k2 = jax.random.split(jax.random.key(seed))

    def mk(key):
        kq, ks = jax.random.split(key)
        return paged_kv.QuantPages(
            jax.random.randint(kq, (L, P, ps, Hk, D), -127, 128).astype(
                jnp.int8
            ),
            jax.random.uniform(ks, (L, P, ps), jnp.float32),
        )

    return {"k": mk(k1), "v": mk(k2)}


def test_cow_copies_codes_and_scales_verbatim():
    pool = _layered_quant_pool()
    out = paged_kv.copy_pages(
        pool, jnp.asarray(1, jnp.int32), jnp.asarray(6, jnp.int32)
    )
    assert jnp.array_equal(out["k"].q[:, 6], out["k"].q[:, 1])
    assert jnp.array_equal(out["k"].scale[:, 6], out["k"].scale[:, 1])
    assert jnp.array_equal(out["v"].q[:, 6], out["v"].q[:, 1])


def test_fetch_upload_page_bitwise_roundtrip():
    pool = _layered_quant_pool()
    blob = paged_kv.fetch_page(pool, 1)
    nbytes = paged_kv.host_blob_bytes(blob)
    assert nbytes > 0
    ref_q = np.asarray(pool["k"].q[:, 1]).copy()
    ref_s = np.asarray(pool["k"].scale[:, 1]).copy()
    out = paged_kv.upload_page(pool, jnp.asarray(5, jnp.int32), blob)
    assert np.array_equal(np.asarray(out["k"].q[:, 5]), ref_q)
    assert np.array_equal(np.asarray(out["k"].scale[:, 5]), ref_s)


def test_kv_pool_dtype_names():
    cfg = cfg_lib.oryx_tiny().llm
    dense = qwen2.init_paged_kv_cache(cfg, 4, 8, dtype=jnp.float32)
    assert paged_kv.kv_pool_dtype(dense) == "float32"
    q8 = qwen2.init_paged_kv_cache(
        cfg, 4, 8, dtype=jnp.float32, kv_dtype="int8"
    )
    assert paged_kv.kv_pool_dtype(q8) == "int8"
    assert q8["k"].shape == dense["k"].shape
    assert q8["k"].storage_dtype == jnp.int8
    f8 = qwen2.init_paged_kv_cache(
        cfg, 4, 8, dtype=jnp.float32, kv_dtype="fp8_e4m3"
    )
    assert paged_kv.kv_pool_dtype(f8) == "fp8_e4m3"
    with pytest.raises(ValueError, match="unknown KV storage dtype"):
        qwen2.init_paged_kv_cache(cfg, 4, 8, kv_dtype="int4")


# ---------------------------------------------------------------------------
# Kernel parity: Pallas in-walk dequant vs the XLA gather-dequant ref
# ---------------------------------------------------------------------------


def _written_quant_pool(P=16, ps=8, Hk=2, D=16, tokens=40, seed=0):
    pool = paged_kv.QuantPages(
        jnp.zeros((P, ps, Hk, D), jnp.int8),
        jnp.zeros((P, ps), jnp.float32),
    )
    maxp = -(-tokens // ps)
    bt = jnp.arange(maxp, dtype=jnp.int32)[None]
    new = jax.random.normal(jax.random.key(seed), (1, tokens, Hk, D))
    pool = paged_kv.write_pages(
        pool, new, bt, jnp.asarray([0], jnp.int32)
    )
    return pool, bt


def test_ragged_kernel_matches_reference_on_quant_pool():
    pool, bt = _written_quant_pool()
    S = 1
    bt_s = jnp.tile(bt, (S, 1))
    q = jax.random.normal(jax.random.key(9), (6, 4, 16))
    seg = jnp.zeros((6,), jnp.int32)
    pos = jnp.asarray([3, 10, 17, 25, 33, 39], jnp.int32)
    ref = paged_kv.ragged_paged_attention(q, pool, pool, bt_s, seg, pos)
    for hb in (2, None):  # Hk=2: the whole axis is the one legal tile
        ker = ppa.ragged_paged_attention(
            q, pool, pool, bt_s, seg, pos,
            heads_per_block=hb, interpret=True,
        )
        np.testing.assert_allclose(
            np.asarray(ker), np.asarray(ref), rtol=2e-6, atol=2e-6
        )


def test_decode_kernel_matches_reference_on_quant_pool():
    pool, bt = _written_quant_pool()
    q = jax.random.normal(jax.random.key(10), (1, 4, 16))
    for n in (1, 7, 40):
        kl = jnp.asarray([n], jnp.int32)
        ref = paged_kv.ragged_decode_attention(q, pool, pool, bt, kl)
        ker = ppa.ragged_decode_attention(
            q, pool, pool, bt, kl, interpret=True
        )
        np.testing.assert_allclose(
            np.asarray(ker), np.asarray(ref), rtol=2e-6, atol=2e-6
        )


def test_mixed_quant_dense_pool_rejected():
    pool, bt = _written_quant_pool()
    dense = jnp.zeros(pool.shape, jnp.float32)
    q = jax.random.normal(jax.random.key(11), (2, 4, 16))
    with pytest.raises(ValueError, match="both planes"):
        ppa.ragged_paged_attention(
            q, pool, dense, bt, jnp.zeros((2,), jnp.int32),
            jnp.asarray([1, 2], jnp.int32), interpret=True,
        )


# ---------------------------------------------------------------------------
# Driver + engine layer
# ---------------------------------------------------------------------------


def _gen(pipe, kv_dtype, ragged=False, prefill_chunk=None, seed=1):
    cfg = pipe.cfg
    H = cfg.llm.hidden_size
    emb = (
        jax.random.normal(jax.random.key(seed), (2, 12, H)) * 0.05
    ).astype(jnp.float32)
    out = generate.generate_paged(
        pipe.params["llm"], cfg.llm, cfg.generation,
        inputs_embeds=emb,
        lengths=jnp.asarray([12, 7], jnp.int32),
        max_new_tokens=8, page_size=8, chunk=4,
        compute_dtype=jnp.float32, kv_dtype=kv_dtype,
        ragged=ragged, prefill_chunk=prefill_chunk,
    )
    return np.asarray(out[0] if isinstance(out, tuple) else out)


def test_generate_paged_int8_ragged_equals_split(pipe):
    split = _gen(pipe, "int8")
    ragged = _gen(pipe, "int8", ragged=True)
    assert np.array_equal(split, ragged)


def test_generate_paged_int8_chunked_prefill_parity(pipe):
    one = _gen(pipe, "int8")
    chunked = _gen(pipe, "int8", prefill_chunk=4)
    assert np.array_equal(one, chunked)


def _boot(pipe, **kw):
    return ContinuousScheduler(
        pipe, num_slots=2, page_size=8, chunk=4, max_ctx=256,
        prefill_chunk=16, **kw,
    )


def _ask(sched, text, n=8):
    h = sched.submit({"question": text}, n, {"temperature": 0.0})
    return h.result(timeout=180)


def test_engine_int8_cold_vs_cached_byte_parity(pipe):
    sched = _boot(pipe, kv_dtype="int8")
    try:
        prompt = "cached prefix parity check " * 3
        cold = _ask(sched, prompt)
        warm = _ask(sched, prompt)
        assert cold[0] == warm[0]
        # One of the two requests spliced (suffix-only prefill).
        cached = [
            ev.get("cached_tokens", 0)
            for ev in sched.request_log.snapshot(4)
            if ev.get("status") == "ok"
        ]
        assert max(cached) > 0
        sched._check_pool_invariant()
    finally:
        sched.close()


def test_engine_int8_pool_info_gauge(pipe):
    sched = _boot(pipe, kv_dtype="int8")
    try:
        text = sched.metrics.render()
        assert 'oryx_pool_kv_dtype{kv_dtype="int8"} 1' in text
    finally:
        sched.close()


def test_int8_pool_bytes_half_of_bf16():
    """The capacity claim at its root: per-token KV bytes. int8 codes
    + per-row fp32 scales cost (Hk*D + 4) bytes vs 2*Hk*D for bf16 —
    ~2x resident tokens per HBM byte at real head geometry (the tiny
    test geometry is below 2x only because of the fixed scale)."""
    cfg = cfg_lib.oryx_tiny().llm

    def pool_bytes(kv_dtype):
        pool = qwen2.init_paged_kv_cache(
            cfg, 8, 16, dtype=jnp.bfloat16, kv_dtype=kv_dtype
        )
        return sum(
            leaf.size * leaf.dtype.itemsize
            for leaf in jax.tree_util.tree_leaves(pool)
        )

    dense = pool_bytes(None)
    q8 = pool_bytes("int8")
    row = cfg.num_kv_heads * cfg.head_dim
    expect = (row + 4) / (2 * row)
    assert q8 / dense == pytest.approx(expect, rel=1e-6)
    # At serving geometry (8 kv heads x 128 dims) that ratio is ~0.502.
    assert (1024 + 4) / 2048 < 0.51
