"""Mosaic compiles of every kernel `attn_impl="pallas"` can select, at
Oryx-7B geometry (and the paged kernel's walk at SDAR's block geometry
too), for a DESCRIBED TPU v5e (no chip attached).

The TPU's compiler is installed with jax; `topologies.get_topology_desc`
lets it compile for a chip that is described and not attached, so a
block shape, a dtype or a VMEM footprint the chip would refuse is
refused here, in tier-1, at no chip time. The kernels pick interpret
mode from the backend, which is the CPU under test — so the tests steer
them (`interpret=False` where the wrapper takes it, the patched
`flash_attention._use_interpret` otherwise) and assert that the compiled
text holds a `tpu_custom_call`: what was lowered is the Mosaic kernel,
not the interpreter's emulation.

Compile-only: nothing runs, so this says nothing about results or
times. Numeric parity on the chip is chip_smoke.py's kernels phase
(scripts/tpu_validate.py). Keep these tests in THIS one file and the
topology inside the fixture: only one process may load the TPU's
library, and under xdist only the worker that is handed this file does.
"""

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

# Oryx-7B attention geometry (config.LLMConfig / VisionConfig defaults).
HQ, HK, D = 28, 4, 128
VIT_H, VIT_D = 16, 72
BF16 = jnp.bfloat16


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described device is written to the persistent
    # cache but cannot be read back without a chip (the next run warns
    # and compiles again): keep the cache off around these compiles.
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    # conftest pins fp32 matmuls for the CPU parity tests; the chip
    # runs the default precision, and Mosaic refuses fp32-precision
    # matmuls of bf16 operands.
    precision = jax.config.jax_default_matmul_precision
    jax.config.update("jax_default_matmul_precision", None)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_default_matmul_precision", precision)
    jax.config.update("jax_enable_compilation_cache", True)
    compilation_cache.reset_cache()


@pytest.fixture
def mosaic(monkeypatch):
    """Steer the kernels under test to the real lowering."""
    from oryx_tpu.ops.pallas import flash_attention

    monkeypatch.setattr(flash_attention, "_use_interpret", lambda: False)


def _compiled_text(fn, one_chip, *shapes):
    args = [
        jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
        for shape, dtype in shapes
    ]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    return text


def _flash_fwd(q, k, v):
    from oryx_tpu.ops.pallas.flash_attention import flash_attention

    return flash_attention(q, k, v, causal=True)


def _flash_grads(q, k, v):
    return jax.grad(
        lambda *a: jnp.sum(_flash_fwd(*a).astype(jnp.float32) ** 2),
        argnums=(0, 1, 2),
    )(q, k, v)


@pytest.mark.parametrize("fn", [_flash_fwd, _flash_grads],
                         ids=["fwd", "fwd_bwd"])
def test_flash_causal_gqa_compiles_for_v5e(one_chip, mosaic, fn):
    T = 2048
    _compiled_text(
        fn, one_chip, ((1, T, HQ, D), BF16), ((1, T, HK, D), BF16),
        ((1, T, HK, D), BF16),
    )


def test_segment_attention_vit_d72_compiles_for_v5e(one_chip, mosaic):
    from oryx_tpu.ops.pallas.segment_attention import segment_attention

    T = 4096
    qkv = ((1, T, VIT_H, VIT_D), BF16)
    seg = ((1, T), jnp.int32)
    _compiled_text(
        lambda q, k, v, s: segment_attention(q, k, v, s, s),
        one_chip, qkv, qkv, qkv, seg,
    )


def test_flash_kv_cache_decode_compiles_for_v5e(one_chip, mosaic):
    from oryx_tpu.ops.pallas.flash_attention import flash_attention

    B, Tq, S = 4, 8, 4096

    def decode(q, k, v, qpos, kv_mask):
        return flash_attention(
            q, k, v, causal=True, q_positions=qpos, kv_positions=None,
            kv_mask=kv_mask,
        )

    _compiled_text(
        decode, one_chip, ((B, Tq, HQ, D), BF16), ((B, S, HK, D), BF16),
        ((B, S, HK, D), BF16), ((B, Tq), jnp.int32), ((B, S), jnp.int32),
    )


def _pool_shapes(pool, pages, page_size):
    codes = ((pages, page_size, HK, D), jnp.int8 if pool == "int8" else BF16)
    return codes, ((pages, page_size), jnp.float32)


def _as_pool(pool, codes, scale):
    from oryx_tpu.ops import paged_kv

    if pool == "int8":
        return paged_kv.QuantPages(codes, scale, BF16)
    return codes


@pytest.mark.parametrize("page_size", [16, 64])
@pytest.mark.parametrize("pool", ["bf16", "int8"])
def test_ragged_paged_compiles_for_v5e(one_chip, pool, page_size):
    """The packed ragged kernel at serving shapes: a [S, maxp] block
    table at a real max_ctx / page_size rides SMEM as scalar prefetch."""
    from oryx_tpu.ops.pallas import paged_attention as ppa

    S, max_ctx, R = 8, 4096, 40
    maxp = max_ctx // page_size

    def ragged(q, codes, scale, bt, seg, pos):
        kv = _as_pool(pool, codes, scale)
        return ppa.ragged_paged_attention(
            q, kv, kv, bt, seg, pos, interpret=False
        )

    _compiled_text(
        ragged, one_chip, ((R, HQ, D), BF16),
        *_pool_shapes(pool, S * maxp, page_size),
        ((S, maxp), jnp.int32), ((R,), jnp.int32), ((R,), jnp.int32),
    )


@pytest.mark.parametrize("pool", ["bf16", "int8"])
def test_paged_decode_compiles_for_v5e(one_chip, pool):
    from oryx_tpu.ops.pallas import paged_attention as ppa

    S, page_size, maxp = 8, 64, 64

    def decode(q, codes, scale, bt, lens):
        kv = _as_pool(pool, codes, scale)
        return ppa.ragged_decode_attention(
            q, kv, kv, bt, lens, interpret=False
        )

    _compiled_text(
        decode, one_chip, ((S, 1, HQ, D), BF16),
        *_pool_shapes(pool, S * maxp, page_size),
        ((S, maxp), jnp.int32), ((S,), jnp.int32),
    )


# The page walk at the two serving geometries (benchmark/configs: 16
# slots x 4096 tokens at depth 16; 32 slots x 4 lanes x 1024 tokens at
# depth 7), each over the flat [layers * pages] pool its program holds.
WALK_GEOMETRIES = {
    "oryx_decode": dict(rows=16, Hq=28, slots=16, maxp=64, layers=16),
    "sdar_block": dict(rows=128, Hq=32, slots=32, maxp=16, layers=7),
}


@pytest.mark.parametrize("pool", ["bf16", "int8"])
@pytest.mark.parametrize("geometry", sorted(WALK_GEOMETRIES))
def test_page_walk_compiles_at_serving_geometries(one_chip, geometry, pool):
    """What interpret mode cannot show of the walk: the packed
    [P, ps * Hk, D] view copied a page at a time into the VMEM blocks,
    a quantized block's scale row beside it, the [Hq, npb * ps * Hk]
    logit tile at 7 and at 8 q heads a kv head, all inside VMEM."""
    from oryx_tpu.ops.pallas import paged_attention as ppa

    g = WALK_GEOMETRIES[geometry]
    page_size = 64
    assert ppa.ragged_pages_per_block(D, page_size, HK, g["maxp"]) == 8
    rows = ((g["rows"],), jnp.int32)

    def walk(q, codes, scale, bt, seg, pos):
        kv = _as_pool(pool, codes, scale)
        return ppa.ragged_paged_attention(
            q, kv, kv, bt, seg, pos, interpret=False
        )

    _compiled_text(
        walk, one_chip, ((g["rows"], g["Hq"], D), BF16),
        *_pool_shapes(pool, g["layers"] * g["slots"] * g["maxp"], page_size),
        ((g["slots"], g["maxp"]), jnp.int32), rows, rows,
    )


def _lower_serve_program(program, pool, one_chip):
    """(lowered `paged_decode_chunk` (chunk 8) or `paged_prefill`
    (1 x 256), the pool's bytes, one layer's bf16 K plane's bytes) over
    abstract arguments: the Oryx-7B decoder's widths at depth 4, 16
    slots x 4096 tokens, page 64, the Pallas kernels. The vocabulary is
    cut to 1024: it is the sampler's width and no pool op's, and at
    152,064 the TPU compiler spends 10 of a case's 12-13 s on the
    sampler's sort, beside the suite's other workers."""
    from oryx_tpu.config import LLMConfig
    from oryx_tpu.models import generate, qwen2

    def on_chip(make):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
            jax.eval_shape(make),
        )

    cfg = LLMConfig(num_layers=4, vocab_size=1024)
    pages, page_size = 16 * 4096 // 64, 64
    S = 16 if program == "paged_decode_chunk" else 1
    rows = lambda dtype, *tail: jax.ShapeDtypeStruct(  # noqa: E731
        (S, *tail), dtype, sharding=one_chip
    )
    params = on_chip(
        lambda: qwen2.init_params(cfg, jax.random.key(0), dtype=BF16)
    )
    kv = on_chip(lambda: qwen2.init_paged_kv_cache(
        cfg, pages, page_size, dtype=BF16,
        kv_dtype=None if pool == "bf16" else pool,
    ))
    tables = rows(jnp.int32, 4096 // page_size)
    sampling = (
        on_chip(lambda: jax.random.split(jax.random.key(0), S)),
        rows(jnp.float32), rows(jnp.float32), rows(jnp.int32),
    )
    common = dict(attn_impl="pallas", compute_dtype=BF16)
    if program == "paged_decode_chunk":
        lowered = generate.paged_decode_chunk.lower(
            params, cfg, kv, tables, rows(jnp.int32), rows(jnp.int32),
            rows(jnp.bool_), rows(jnp.int32, 0), *sampling,
            chunk=8, eos=0, **common,
        )
    else:
        lowered = generate.paged_prefill.lower(
            params, cfg, rows(BF16, 256, cfg.hidden_size), rows(jnp.int32),
            tables, kv, rows(jnp.int32), *sampling, **common,
        )
    pool_bytes = sum(
        a.size * a.dtype.itemsize for a in jax.tree_util.tree_leaves(kv)
    )
    return lowered, pool_bytes, pages * page_size * HK * D * 2


@pytest.mark.parametrize("pool", ["bf16", "int8"])
@pytest.mark.parametrize("program", ["paged_decode_chunk", "paged_prefill"])
def test_serve_program_keeps_the_kv_pool_in_place(
    one_chip, mosaic, program, pool
):
    """The two programs every serve cell runs, whole: the donated pool
    is aliased to the output and NO temporary of a layer's K plane or
    more exists. `qwen2.forward` carries the pool through its layer
    scan; with the pool as the scan's xs/ys the same compile shows a
    slice and an update-slice of it in every layer, a copy of all of it
    in every decode step, and 0.69 / 0.81 GB of temporaries against a
    0.54 GB pool. `_use_interpret` MUST be patched: unpatched, the text
    holds the interpreter's `while` over the kernel's grid with its own
    dynamic slices of the pool, which the chip never runs and this
    bound does not describe. (At the full vocabulary the same compiles
    give 25 and 80 MB of temporaries, 78 MB of the latter being
    `paged_prefill`'s [1, 256, vocab] logits: not pool traffic.)"""
    lowered, pool_bytes, layer_k_plane = _lower_serve_program(
        program, pool, one_chip
    )
    compiled = lowered.compile()
    assert "tpu_custom_call" in compiled.as_text()
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes == pool_bytes
    assert memory.temp_size_in_bytes < layer_k_plane


def test_block_step_passes_the_expert_kernels_whole(one_chip, mosaic):
    """`paged_block_step` of the SDAR-30B-A3B widths at depth 2 (32
    slots x 4 lanes and the 4 commit lanes a slot of its first forward,
    128 experts top-8 at width 768; vocabulary 1024 as above): the grouped expert products are Mosaic kernels (the grouped
    matmul jax ships, which `_grouped_dot` picks under attn_impl
    "pallas" at these widths; no `ragged-dot` of XLA's is left and no
    masked dense product), the donated pool is aliased, and no
    temporary as large as ONE expert tensor of a layer exists. With the layer's `[E, in, out]` kernels as the layer scan's
    xs the same compile puts a copy of each (403 MB, three a layer) in
    front of every kernel call and holds 0.452 GB of temporaries; with
    the stack passed whole, flat over layers (`qwen2._moe`), 0.048 GB."""
    import dataclasses

    from oryx_tpu import config as cfg_lib
    from oryx_tpu.models import generate, qwen2

    def on_chip(make):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
            jax.eval_shape(make),
        )

    cfg = dataclasses.replace(
        cfg_lib.sdar_30b_a3b().llm, num_layers=2, vocab_size=1024,
        mask_token_id=1023,
    )
    S, page_size, ctx = 32, 64, 1024
    rows = lambda dtype, *tail: jax.ShapeDtypeStruct(  # noqa: E731
        (S, *tail), dtype, sharding=one_chip
    )
    params = on_chip(
        lambda: qwen2.init_params(cfg, jax.random.key(0), dtype=BF16)
    )
    kv = on_chip(lambda: qwen2.init_paged_kv_cache(
        cfg, S * ctx // page_size, page_size, dtype=BF16))
    compiled = generate.paged_block_step.lower(
        params, cfg, kv, rows(jnp.int32, ctx // page_size),
        rows(jnp.int32, cfg.block_length), rows(jnp.int32), rows(jnp.int32),
        rows(jnp.bool_),
        on_chip(lambda: jax.random.split(jax.random.key(0), S)),
        rows(jnp.float32), rows(jnp.float32), rows(jnp.int32),
        rows(jnp.int32, cfg.block_length), rows(jnp.bool_),  # the pending block
        steps=2, remasking="low_confidence_static", threshold=0.9,
        eos=0, attn_impl="pallas", compute_dtype=BF16,
    ).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "ragged-dot" not in text
    memory = compiled.memory_analysis()
    pool_bytes = sum(
        a.size * a.dtype.itemsize for a in jax.tree_util.tree_leaves(kv)
    )
    assert memory.alias_size_in_bytes == pool_bytes
    one_expert_tensor = (
        cfg.num_experts * cfg.hidden_size * cfg.moe_intermediate_size * 2
    )
    assert memory.temp_size_in_bytes < one_expert_tensor // 4


def test_latent_page_walk_compiles_at_the_cell_geometry(one_chip):
    """`_latent_paged` at the LongCat-Flash cell's decode geometry: 64
    rows of 64 heads against the one shared 640-wide key a token (576
    values and the pad to whole lane tiles), page 64, 96 pages a row,
    the 8 cache layers of 4 model layers in one flat pool: blocks of 8
    pages (512 x 640 bf16, two in flight) and the [64, 512] logit tile
    inside VMEM, the value read as the block's first 512 columns."""
    from oryx_tpu.ops.pallas import paged_attention as ppa

    rows, heads, Dp, ps, maxp = 64, 64, 640, 64, 96
    text = _compiled_text(
        lambda q, pages, bt, lens: ppa.latent_decode_attention(
            q, pages, bt, lens, scale=192 ** -0.5, value_dim=512,
            interpret=False),
        one_chip, ((rows, heads, Dp), BF16),
        ((8 * rows * maxp, ps, Dp), BF16), ((rows, maxp), jnp.int32),
        ((rows,), jnp.int32),
    )
    assert "gather" not in text


@pytest.mark.parametrize("program", ["paged_decode_chunk", "paged_prefill"])
def test_latent_serve_program_keeps_the_pool_in_place(
    one_chip, mosaic, program
):
    """The two programs of the latent-attention model at the published
    LongCat-Flash widths, depth 1 (two cache layers), 64 slots x 6144,
    vocabulary 1024 as above: the donated latent pool is aliased to the
    output, the decode step holds no gather of it (the absorbed product
    walks the pages in place) and its temporaries stay under one cache
    layer's plane; the prefill chunk's stay under the two (it holds the
    up-projected keys and values of one row's 6144-token prefix, 0.3
    GB, by design: the expanded path); the grouped expert products are
    the Mosaic grouped matmul over the held experts' kernels as they
    lie."""
    import dataclasses

    from oryx_tpu import config as cfg_lib
    from oryx_tpu.models import generate, qwen2

    def on_chip(make):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
            jax.eval_shape(make),
        )

    cfg = dataclasses.replace(
        cfg_lib.longcat_flash_chat_ep32().llm, num_layers=1, vocab_size=1024)
    slots, page_size, ctx = 64, 64, 6144
    S = slots if program == "paged_decode_chunk" else 1
    rows = lambda dtype, *tail: jax.ShapeDtypeStruct(  # noqa: E731
        (S, *tail), dtype, sharding=one_chip
    )
    params = on_chip(
        lambda: qwen2.init_params(cfg, jax.random.key(0), dtype=BF16))
    kv = on_chip(lambda: qwen2.init_paged_kv_cache(
        cfg, slots * ctx // page_size, page_size, dtype=BF16))
    tables = rows(jnp.int32, ctx // page_size)
    sampling = (
        on_chip(lambda: jax.random.split(jax.random.key(0), S)),
        rows(jnp.float32), rows(jnp.float32), rows(jnp.int32),
    )
    common = dict(attn_impl="pallas", compute_dtype=BF16)
    if program == "paged_decode_chunk":
        lowered = generate.paged_decode_chunk.lower(
            params, cfg, kv, tables, rows(jnp.int32), rows(jnp.int32),
            rows(jnp.bool_), rows(jnp.int32, 0), *sampling,
            chunk=8, eos=2, **common,
        )
    else:
        lowered = generate.paged_prefill.lower(
            params, cfg, rows(BF16, 1024, cfg.hidden_size), rows(jnp.int32),
            tables, kv, rows(jnp.int32), *sampling, **common,
        )
    compiled = lowered.compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "ragged-dot" not in text
    memory = compiled.memory_analysis()
    pool_bytes = sum(
        a.size * a.dtype.itemsize for a in jax.tree_util.tree_leaves(kv)
    )
    assert memory.alias_size_in_bytes == pool_bytes
    planes = 1 if program == "paged_decode_chunk" else 2
    assert memory.temp_size_in_bytes < planes * pool_bytes // 2
    if program == "paged_decode_chunk":
        # No [S, max_len] copy of a cache layer: nothing of a row's 6144
        # x 640 stream, for all 64 rows, is ever built.
        assert f"bf16[{slots},{ctx},640]" not in text
        assert f"bf16[{slots},{ctx // page_size},{page_size},640]" not in text


def test_mistral_small_4_cell_fits_the_chip_by_the_half_gigabyte_rule(
    one_chip, mosaic
):
    """The two programs of `mistral-small-4.doc-qa` at the cell's full
    size (depth 6, 32 of 128 experts, 32,768 rows of vocabulary, 16
    slots x 32,768 positions, page 64, chunk 1024), compiled for the
    described v5e. ISSUE 33's rule for the depth: arguments + the decode
    program's temporaries + the WIDEST prefill table's temporaries must
    leave 0.5 GB of the chip's 15.75 GB, else depth 5. They leave 1.33
    GB (13.268 + 0.055 + 1.096). And the reason for the table's widths:
    a chunk against 8,192 positions holds a fifth of the temporaries of
    one against all 32,768 (0.20 against 1.10 GB: the expanded keys and
    values of every position of the table, whatever the prompt's
    length). This file, not tests/benchmark/test_bench_rehearsal_docqa.py,
    because only one process may load the TPU's compiler."""
    import dataclasses

    from oryx_tpu import config as cfg_lib
    from oryx_tpu.models import generate, qwen2
    from oryx_tpu.serve import scheduler

    def on_chip(make):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
            jax.eval_shape(make),
        )

    cfg = dataclasses.replace(cfg_lib.mistral_small_4_ep4().llm, num_layers=6)
    slots, page_size, ctx = 16, 64, 32768
    widths = scheduler.prefill_table_buckets(ctx // page_size, page_size)
    assert widths == (128, 256, 512)
    params = on_chip(
        lambda: qwen2.init_params(cfg, jax.random.key(0), dtype=BF16))
    kv = on_chip(lambda: qwen2.init_paged_kv_cache(
        cfg, slots * ctx // page_size, page_size, dtype=BF16))
    nbytes = lambda tree: sum(  # noqa: E731
        a.size * a.dtype.itemsize for a in jax.tree_util.tree_leaves(tree))
    pool_bytes = nbytes(kv)
    assert pool_bytes == 6 * slots * ctx * 384 * 2  # 2.416 GB
    assert 10.84e9 < nbytes(params) < 10.86e9

    def rows(S, dtype, *tail):
        return jax.ShapeDtypeStruct((S, *tail), dtype, sharding=one_chip)

    def sampling(S):
        return (on_chip(lambda: jax.random.split(jax.random.key(0), S)),
                rows(S, jnp.float32), rows(S, jnp.float32),
                rows(S, jnp.int32))

    common = dict(attn_impl="pallas", compute_dtype=BF16)
    S = slots
    decode = generate.paged_decode_chunk.lower(
        params, cfg, kv, rows(S, jnp.int32, ctx // page_size),
        rows(S, jnp.int32), rows(S, jnp.int32), rows(S, jnp.bool_),
        rows(S, jnp.int32, 0), *sampling(S), chunk=8, eos=32768, **common,
    ).compile()
    text = decode.as_text()
    assert "tpu_custom_call" in text and "ragged-dot" not in text
    # The absorbed walk: no [S, max_len] copy of a cache layer.
    assert f"bf16[{slots},{ctx},384]" not in text
    temps = {}
    for width in (widths[0], widths[-1]):
        prefill = generate.paged_prefill.lower(
            params, cfg, rows(1, BF16, 1024, cfg.hidden_size),
            rows(1, jnp.int32), rows(1, jnp.int32, width), kv,
            rows(1, jnp.int32), *sampling(1), held_stats=True, **common,
        ).compile()  # as the engine dispatches it for a share of experts
        memory = prefill.memory_analysis()
        assert memory.alias_size_in_bytes == pool_bytes
        temps[width] = memory.temp_size_in_bytes
    memory = decode.memory_analysis()
    assert memory.alias_size_in_bytes == pool_bytes
    assert memory.temp_size_in_bytes < 0.1e9
    assert temps[widths[0]] < temps[widths[-1]] / 4
    total = (nbytes(params) + pool_bytes + memory.temp_size_in_bytes
             + temps[widths[-1]])
    assert total <= 15.75e9 - 0.5e9, total


def test_glm5_cell_fits_the_chip_and_expands_one_tile_of_keys(
    one_chip, mosaic
):
    """The two programs of `glm-5.long-sessions` at the cell's full size
    (1 dense + 4 expert layers, 16 of 256 experts, 19,360 rows of
    vocabulary, 12 slots x 65,536 positions, page 64, chunk 1024),
    compiled for the described v5e. ISSUE 49's rule for the slots:
    arguments + the decode program's temporaries + the WIDEST prefill
    table's temporaries must leave 0.5 GB of the 16.91 GB (15.75 GiB) a
    program may use, else 8 slots. They leave 1.77 GB (13.872 + 0.434 +
    0.833). And point 4's outcome: the widest bucket's temporaries are a
    fifth of the 4.3 GB that per-head keys and values of the whole table
    would take, and what grows with the table is the index scores and
    the mask (0.37 GB at 8,192 positions, 0.83 at 65,536); the decode
    program builds neither a lane's whole latent stream nor its index
    keys' (a page walk and a gather of 2,048 rows)."""
    import dataclasses

    from oryx_tpu import config as cfg_lib
    from oryx_tpu.models import generate, qwen2
    from oryx_tpu.serve import scheduler

    def on_chip(make):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
            jax.eval_shape(make),
        )

    cfg = dataclasses.replace(cfg_lib.glm5_ep16().llm, num_layers=5)
    slots, page_size, ctx = 12, 64, 65536
    widths = scheduler.prefill_table_buckets(ctx // page_size, page_size)
    assert widths == (128, 256, 512, 1024)
    params = on_chip(
        lambda: qwen2.init_params(cfg, jax.random.key(0), dtype=BF16))
    kv = on_chip(lambda: qwen2.init_paged_kv_cache(
        cfg, slots * ctx // page_size, page_size, dtype=BF16))
    nbytes = lambda tree: sum(  # noqa: E731
        a.size * a.dtype.itemsize for a in jax.tree_util.tree_leaves(tree))
    pool_bytes = nbytes(kv)
    assert pool_bytes == 5 * slots * ctx * (640 + 128) * 2  # 6.040 GB
    assert nbytes(params) == 7_831_850_496

    def rows(S, dtype, *tail):
        return jax.ShapeDtypeStruct((S, *tail), dtype, sharding=one_chip)

    def sampling(S):
        return (on_chip(lambda: jax.random.split(jax.random.key(0), S)),
                rows(S, jnp.float32), rows(S, jnp.float32),
                rows(S, jnp.int32))

    common = dict(attn_impl="pallas", compute_dtype=BF16)
    S = slots
    decode = generate.paged_decode_chunk.lower(
        params, cfg, kv, rows(S, jnp.int32, ctx // page_size),
        rows(S, jnp.int32), rows(S, jnp.int32), rows(S, jnp.bool_),
        rows(S, jnp.int32, 0), *sampling(S), chunk=8, eos=19360, **common,
    ).compile()
    text = decode.as_text()
    assert text.count("tpu_custom_call") >= 3 and "ragged-dot" not in text
    # Index scores by a page walk, latent rows by a gather of the top k.
    assert f"bf16[{slots},{ctx},640]" not in text
    assert f"bf16[{slots},{ctx},128]" not in text
    assert f"bf16[{slots},2048,640]" in text
    # ... whose indices come from counts: no sort as wide as the table.
    assert not [line for line in text.splitlines()
                if " sort(" in line and f"[{slots},{ctx}]" in line]
    temps = {}
    for width in (widths[0], widths[-1]):
        prefill = generate.paged_prefill.lower(
            params, cfg, rows(1, BF16, 1024, cfg.hidden_size),
            rows(1, jnp.int32), rows(1, jnp.int32, width), kv,
            rows(1, jnp.int32), *sampling(1), held_stats=True, **common,
        ).compile()  # as the engine dispatches it for a share of experts
        memory = prefill.memory_analysis()
        assert memory.alias_size_in_bytes == pool_bytes
        temps[width] = memory.temp_size_in_bytes
        # Per-head keys and values of ONE tile of 1,024 positions.
        ptext = prefill.as_text()
        assert "bf16[1,1024,64,256]" in ptext
        assert f"bf16[1,{width * page_size},64,256]" not in ptext
        # ... whose attention scores stay in the kernel's VMEM (PR 57):
        # no float32 [heads, queries, keys] block in the module.
        assert "_dsa_attend" in ptext
        assert "f32[64,1024,1024]" not in ptext
        assert "f32[1,64,1024,1024]" not in ptext
    memory = decode.memory_analysis()
    assert memory.alias_size_in_bytes == pool_bytes
    assert memory.temp_size_in_bytes < 0.5e9
    assert temps[widths[-1]] < 4.3e9 / 4
    # What grows with the table: the float32 scores of a chunk and their
    # order keys (2 x 4 B a pair) and the mask, not keys and values.
    # (11.0 B a pair since PR 57: the narrowest bucket's peak was one
    # tile's 268 MB of attention scores and 67 MB of its output, which
    # hid 118 MB of these; it is 0.186 GB now, the widest's 0.832 as
    # it was.)
    grown = temps[widths[-1]] - temps[widths[0]]
    assert grown < 1024 * (65536 - 8192) * 12
    assert temps[widths[0]] < 0.2e9
    total = (nbytes(params) + pool_bytes + memory.temp_size_in_bytes
             + temps[widths[-1]])
    assert total <= 16.91e9 - 0.5e9, total


@pytest.mark.parametrize("T, Kt", [(1024, 1024), (512, 1024), (16, 128)])
def test_masked_attention_compiles_at_the_cells_tile(one_chip, T, Kt):
    """`_dsa_attend` at `glm-5.long-sessions`' shapes (a chunk of 1,024
    queries against one tile of 1,024 keys, 64 heads of 256), at half a
    chunk, and at the smallest tile it takes: the [queries, keys] bias
    in scratch, a head's keys and values whole, the running state
    aliased in and out, its row statistics turned from lane-major rows
    to columns and back in the kernel."""
    from oryx_tpu.ops.pallas import masked_attention

    Hq, d, dv = 64, 256, 256
    text = _compiled_text(
        lambda m, l, acc, q, k, v, seen: masked_attention.masked_attend(
            (m, l, acc), q, k, v, seen, d ** -0.5, interpret=False),
        one_chip, ((1, Hq, T), jnp.float32), ((1, Hq, T), jnp.float32),
        ((1, T, Hq, dv), jnp.float32), ((1, T, Hq, d), BF16),
        ((1, Kt, Hq, d), BF16), ((1, Kt, Hq, dv), BF16),
        ((1, T, Kt), jnp.bool_),
    )
    assert "_dsa_attend" in text
    assert f"f32[1,{Hq},{T},{Kt}]" not in text


def test_illegal_heads_per_block_pin_raises_with_its_name(
    one_chip, monkeypatch
):
    """A kv-head tile the lowering would refuse is an error naming the
    pin — not a gcd clamp, not a compile failure deep in Mosaic."""
    from oryx_tpu.ops.pallas import paged_attention as ppa

    monkeypatch.setenv("ORYX_RPA_HEADS_PER_BLOCK", "2")

    def ragged(q, kv, bt, seg, pos):
        return ppa.ragged_paged_attention(
            q, kv, kv, bt, seg, pos, interpret=False
        )

    with pytest.raises(ValueError, match=r"\$ORYX_RPA_HEADS_PER_BLOCK=2"):
        _compiled_text(
            ragged, one_chip, ((16, HQ, D), BF16),
            ((64, 64, HK, D), BF16), ((4, 16), jnp.int32),
            ((16,), jnp.int32), ((16,), jnp.int32),
        )


# --- AI21-Jamba2-3B (PR 40): ONE key/value head, the selective scan,
# and the two step programs over a pool with per-slot state planes ---


def test_page_walk_compiles_at_one_kv_head_with_twenty_query_heads(one_chip):
    """`_ragged_paged` at Jamba2-3B's attention geometry: 20 query
    heads over ONE key/value head (`legal_heads_per_block(1) = (1,)`),
    64 decode rows, 64 pages of 64 a row, the two attention layers'
    flat pool. Never compiled for the chip before this model."""
    from oryx_tpu.ops.pallas import paged_attention as ppa

    rows, Hq, page_size, maxp, layers = 64, 20, 64, 64, 2
    r = ((rows,), jnp.int32)
    kv = ((layers * rows * maxp, page_size, 1, D), BF16)
    _compiled_text(
        lambda q, k, v, bt, seg, pos: ppa.ragged_paged_attention(
            q, k, v, bt, seg, pos, interpret=False),
        one_chip, ((rows, Hq, D), BF16), kv, kv,
        ((rows, maxp), jnp.int32), r, r,
    )
    _compiled_text(
        lambda q, k, v, bt, lens: ppa.ragged_decode_attention(
            q, k, v, bt, lens, interpret=False),
        one_chip, ((rows, 1, Hq, D), BF16), kv, kv,
        ((rows, maxp), jnp.int32), r,
    )


def test_selective_scan_compiles_at_the_cells_chunk(one_chip, mosaic):
    """`_selective_scan` over one 512-token prefill chunk of 5120
    channels with a [16, 5120] state: the [16, 512] state tile stays in
    registers across the chunk, x / dt / z / y are [512, 512] blocks and
    the lane-broadcast B and C slabs [512, 16, 128], all inside the 64
    MiB the kernel asks of VMEM."""
    from oryx_tpu.ops.pallas import selective_scan as ss

    T, d, N = 512, 5120, 16
    f32 = jnp.float32
    seq, sel = ((1, T, d), f32), ((1, T, N), f32)
    _compiled_text(
        lambda x, dt, z, B, C, A, D_, h0: ss.selective_scan(
            x, dt, z, B, C, A, D_, h0, impl="pallas"),
        one_chip, seq, seq, seq, sel, sel, ((N, d), f32), ((d,), f32),
        ((1, N, d), f32),
    )


@pytest.mark.parametrize("slots", [64, 3])
def test_ssm_step_kernels_compile_at_the_cells_shapes_in_place(
    one_chip, mosaic, slots
):
    """`mamba.mixer_step_inplace` (`_ssm_conv` and `_ssm_step` around
    `x_proj`, between `in_proj` and `out_proj`) for one Mamba layer of
    `jamba2-3b.reasoning`'s decode step (d 5120, N 16, K 4, R 160,
    the 26 layers' planes and stacked mixers WHOLE, the layer's number
    an argument), over the engine's 64 lanes (blocks of 16) and the
    comparison's twin pool of 3 (one block): Mosaic takes both, the two
    donated planes are aliased to the outputs whole, and the only
    temporaries are the step's invariants (`mamba.step_invariants`: 24
    float32 rows a layer) and the [B, d] activations, far under ONE
    layer's state rows: the alias held, nothing copies a plane or a
    layer of it."""
    from oryx_tpu import config as cfg_lib
    from oryx_tpu.models import mamba

    cfg = cfg_lib.jamba2_3b().llm
    Lm, d, N, K = 26, cfg.mamba_d_inner, cfg.mamba_d_state, cfg.mamba_d_conv
    on_chip = lambda shape, dtype: jax.ShapeDtypeStruct(  # noqa: E731
        shape, dtype, sharding=one_chip)
    mp = jax.tree_util.tree_map(
        lambda a: on_chip(a.shape, a.dtype),
        jax.eval_shape(lambda: mamba.init_mixer_params(
            cfg, jax.random.key(0), Lm, BF16)))
    assert mamba.step_fits(cfg, slots)

    def step(u, mp, live, conv_pl, ssm_pl, li):
        lp = jax.tree_util.tree_map(lambda a: a[li], mp)
        return mamba.mixer_step_inplace(
            cfg, lp, mamba.step_invariants(mp, live, u.dtype), li, u,
            (conv_pl, ssm_pl))

    planes = (on_chip((Lm, slots, (K - 1) * d), BF16),
              on_chip((Lm, slots, N, d), jnp.float32))
    compiled = jax.jit(step, donate_argnums=(3, 4)).lower(
        on_chip((slots, 1, cfg.hidden_size), BF16), mp,
        on_chip((slots,), jnp.bool_), *planes,
        on_chip((), jnp.int32)).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 2
    assert "_ssm_conv" in text and "_ssm_step" in text
    memory = compiled.memory_analysis()
    # (== at 64 slots; 3 rows of the conv plane pad to a sublane tile)
    assert memory.alias_size_in_bytes >= sum(
        p.size * p.dtype.itemsize for p in planes)
    invariants = Lm * (8 + N) * d * 4
    assert memory.temp_size_in_bytes < invariants + 16 * slots * d


@pytest.mark.parametrize("program", ["paged_decode_chunk", "paged_prefill"])
def test_jamba_serve_programs_keep_pages_and_state_in_place(
    one_chip, mosaic, program, capsys
):
    """The two programs of `jamba2-3b.reasoning` at the cell's FULL size
    (28 layers, the whole vocabulary, 64 slots x 4,096, page 64, chunk
    512 / 8), compiled for the described v5e: the donated pool (paged
    K/V of the two attention layers AND the per-slot conv and state
    planes of the 26 Mamba layers) is aliased to the output whole, and
    the temporaries stay under five layers' state rows for all slots
    (21 MB each, of the 26 layers' 545 MB: nothing copies a [26, 64,
    ...] plane, which the period scan and its inner scans CARRY; the
    decode step also keeps its kernels' invariants, 24 float32 rows a
    Mamba layer = 12.8 MB, made once a step: PR 42). The decode step of
    a Mamba layer is the kernels `_ssm_conv` and `_ssm_step`, the
    prefill's is `_selective_scan`. The numbers printed here
    are the configuration file's `memory` block and PERF.md section 4's
    (arguments + temporaries under half the chip)."""
    from oryx_tpu import config as cfg_lib
    from oryx_tpu.models import generate, qwen2

    def on_chip(make):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
            jax.eval_shape(make),
        )

    cfg = cfg_lib.jamba2_3b().llm
    slots, page_size, ctx = 64, 64, 4096
    S = slots if program == "paged_decode_chunk" else 1
    rows = lambda dtype, *tail: jax.ShapeDtypeStruct(  # noqa: E731
        (S, *tail), dtype, sharding=one_chip
    )
    params = on_chip(
        lambda: qwen2.init_params(cfg, jax.random.key(0), dtype=BF16))
    kv = on_chip(lambda: qwen2.init_paged_kv_cache(
        cfg, slots * ctx // page_size, page_size, dtype=BF16,
        num_slots=slots))
    tables = rows(jnp.int32, ctx // page_size)
    sampling = (
        on_chip(lambda: jax.random.split(jax.random.key(0), S)),
        rows(jnp.float32), rows(jnp.float32), rows(jnp.int32),
    )
    common = dict(attn_impl="pallas", compute_dtype=BF16)
    if program == "paged_decode_chunk":
        lowered = generate.paged_decode_chunk.lower(
            params, cfg, kv, tables, rows(jnp.int32), rows(jnp.int32),
            rows(jnp.bool_), rows(jnp.int32, 0), *sampling,
            chunk=8, eos=65536, **common,
        )
    else:
        lowered = generate.paged_prefill.lower(
            params, cfg, rows(BF16, 512, cfg.hidden_size), rows(jnp.int32),
            tables, kv, rows(jnp.int32), *sampling,
            slots=rows(jnp.int32), **common,
        )
    compiled = lowered.compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert ("_selective_scan" in text) == (program == "paged_prefill")
    for kernel in ("_ssm_conv", "_ssm_step"):
        assert (kernel in text) == (program == "paged_decode_chunk")
    memory = compiled.memory_analysis()
    nbytes = lambda t: sum(  # noqa: E731
        a.size * a.dtype.itemsize for a in jax.tree_util.tree_leaves(t))
    pool_bytes, weight_bytes = nbytes(kv), nbytes(params)
    with capsys.disabled():
        print(f"\n{program}: weights {weight_bytes} B, pool {pool_bytes} B, "
              f"arguments {memory.argument_size_in_bytes} B, temporaries "
              f"{memory.temp_size_in_bytes} B")
    assert weight_bytes == 6_063_467_264
    assert pool_bytes == 64 * 9_318_400 + 64 * ctx * 1024
    assert memory.alias_size_in_bytes == pool_bytes
    one_layers_state = slots * 16 * 5120 * 4
    invariants = 26 * 24 * 5120 * 4 * (program == "paged_decode_chunk")
    assert memory.temp_size_in_bytes < 5 * one_layers_state + invariants
    total = memory.argument_size_in_bytes + memory.temp_size_in_bytes
    assert 0.25 * 16e9 < total < 0.5 * 16e9


@pytest.mark.parametrize("program", ["paged_decode_chunk", "paged_prefill"])
def test_smallthinker_serve_programs_keep_both_planes_in_place(
    one_chip, mosaic, program, capsys
):
    """The two programs of `smallthinker-21b-a3b.mixed-queue` at the
    cell's FULL size (8 layers = two periods of global, window, window,
    window; 64 experts of 768; the whole vocabulary of 151,936; 32 slots
    x 16,384, page 64, chunk 1,024 / 8), compiled for the described
    v5e: the donated pool is TWO paged planes, the global layers' [2,
    8192, 64, 4, 128] behind a table of 256 pages a slot and the window
    layers' [6, 2592, 64, 4, 128] behind a table of 81, and both are
    aliased to the output whole; the temporaries stay far under one
    plane (nothing copies a plane: the period scan CARRIES all four
    arrays). The attention kernels are `_ragged_paged` (decode, with a
    fourth scalar-prefetched array on window layers) and `_mha_forward`
    (prefill, the lower bound in its mask), the experts `gmm`. The
    numbers printed here are the configuration file's `memory` block:
    the pool is 2.15 + 2.04 GB where one table for every layer would
    be 8.59 GB."""
    import dataclasses

    from oryx_tpu import config as cfg_lib
    from oryx_tpu.models import generate, qwen2
    from oryx_tpu.ops import paged_kv

    def on_chip(make):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
            jax.eval_shape(make),
        )

    cfg = dataclasses.replace(cfg_lib.smallthinker_21b().llm, num_layers=8)
    slots, page_size, ctx, chunk = 32, 64, 16384, 1024
    wide = paged_kv.window_table_pages(cfg.sliding_window, chunk, page_size)
    assert wide == 81
    S = slots if program == "paged_decode_chunk" else 1
    rows = lambda dtype, *tail: jax.ShapeDtypeStruct(  # noqa: E731
        (S, *tail), dtype, sharding=one_chip
    )
    params = on_chip(
        lambda: qwen2.init_params(cfg, jax.random.key(0), dtype=BF16))
    kv = on_chip(lambda: qwen2.init_paged_kv_cache(
        cfg, (slots * ctx // page_size, slots * wide), page_size, dtype=BF16))
    tables = rows(jnp.int32, ctx // page_size)
    window = dict(window_tables=rows(jnp.int32, wide),
                  window_base=rows(jnp.int32))
    sampling = (
        on_chip(lambda: jax.random.split(jax.random.key(0), S)),
        rows(jnp.float32), rows(jnp.float32), rows(jnp.int32),
    )
    common = dict(attn_impl="pallas", compute_dtype=BF16, **window)
    if program == "paged_decode_chunk":
        lowered = generate.paged_decode_chunk.lower(
            params, cfg, kv, tables, rows(jnp.int32), rows(jnp.int32),
            rows(jnp.bool_), rows(jnp.int32, 0), *sampling,
            chunk=8, eos=151936, **common,
        )
    else:
        lowered = generate.paged_prefill.lower(
            params, cfg, rows(BF16, chunk, cfg.hidden_size), rows(jnp.int32),
            tables, kv, rows(jnp.int32), *sampling, held_stats=True, **common,
        )
    compiled = lowered.compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "gmm" in text
    kernel = {"paged_decode_chunk": "_ragged_paged",
              "paged_prefill": "_mha_forward"}[program]
    assert kernel in text
    memory = compiled.memory_analysis()
    nbytes = lambda t: sum(  # noqa: E731
        a.size * a.dtype.itemsize for a in jax.tree_util.tree_leaves(t))
    pool_bytes, weight_bytes = nbytes(kv), nbytes(params)
    with capsys.disabled():
        print(f"\n{program}: weights {weight_bytes} B, pool {pool_bytes} B, "
              f"arguments {memory.argument_size_in_bytes} B, temporaries "
              f"{memory.temp_size_in_bytes} B")
    # 3,966,937,600 parameters, the routers' 8 x 2560 x 64 in float32.
    assert weight_bytes == 2 * 3_966_937_600 + 2 * 8 * 2560 * 64
    global_bytes = slots * ctx * 4096  # 2 layers x 2,048 B a token
    window_bytes = slots * wide * page_size * 12288  # 6 layers
    assert pool_bytes == global_bytes + window_bytes
    assert pool_bytes < 0.5 * slots * ctx * 16384  # one table for all: 8.59 GB
    assert memory.alias_size_in_bytes == pool_bytes
    # (the window plane's K alone is 1.02 GB; a body that wrote and read
    # one carried plane three times kept such a copy, 1.375 GB.)
    assert memory.temp_size_in_bytes < 0.45e9
    total = memory.argument_size_in_bytes + memory.temp_size_in_bytes
    assert 0.25 * 16e9 < total < 0.8 * 16e9


@pytest.mark.parametrize(
    "program", ["paged_decode_chunk", "paged_prefill", "handover_state"])
def test_lfm2_serve_programs_keep_pages_state_and_snapshots_in_place(
    one_chip, mosaic, program, capsys
):
    """The programs of `lfm2-24b-a2b.agent-sessions` at the cell's FULL
    size (the ten-layer cut, 64 experts of 1,536, the whole vocabulary of
    65,536; 96 slots x 8,192, the engine's default pool of 12,288 pages of
    64, chunk 512 / 8), compiled for the described v5e: the donated pool (paged K/V of the
    two attention layers, two heads of 64 a row of 128 lanes; the
    per-slot conv rows of the eight conv layers; their page-edge
    snapshots, one row a page) is aliased to the output whole, and the
    temporaries stay under a tenth of the snapshot plane (nothing copies
    a plane: a decode step that wrote every layer's snapshots in ONE
    scatter behind the layers kept a copy of it, 0.6 GB). The attention
    kernels are `_ragged_paged` (decode: the head of 64 is no tile of
    the page walk's copies, the packed row is) and `_mha_forward`
    (prefill), the experts `gmm` (a kernel of 2,048 x 1,536 in tiles of
    1,024 x 768). The numbers printed here are the configuration file's
    `memory` block: arguments and both programs' temporaries leave over
    half a gigabyte of the chip's 15.75 GB usable (ISSUE 56)."""
    import dataclasses
    import json
    import os

    from oryx_tpu import config as cfg_lib
    from oryx_tpu.models import generate, qwen2
    from oryx_tpu.ops import paged_kv

    def on_chip(make):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
            jax.eval_shape(make),
        )

    conf = json.load(open(os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "benchmark", "configs", "lfm2-24b-a2b-serve.json")))
    lay, mem = conf["layout"], conf["memory"]
    cfg = dataclasses.replace(
        cfg_lib.lfm2_24b_a2b().llm, num_layers=lay["num_layers"])
    slots, page_size, ctx = lay["num_slots"], lay["page_size"], lay["max_ctx"]
    assert (slots, page_size, ctx) == (96, 64, 8192)
    pages = slots * ctx // page_size  # the engine's default pool
    assert pages == mem["num_pages"] == 12288 and "num_pages" not in lay
    S = slots if program == "paged_decode_chunk" else 1
    rows = lambda dtype, *tail: jax.ShapeDtypeStruct(  # noqa: E731
        (S, *tail), dtype, sharding=one_chip
    )
    params = on_chip(
        lambda: qwen2.init_params(cfg, jax.random.key(0), dtype=BF16))
    kv = on_chip(lambda: qwen2.init_paged_kv_cache(
        cfg, pages, page_size, dtype=BF16, num_slots=slots))
    assert kv["k"].shape == (2, 12288, 64, 4, 128)  # two heads a row
    assert kv["conv"].shape == (8, 96, 4096)
    assert kv[paged_kv.CONV_EDGE].shape == (8, 12288, 4096)
    tables = rows(jnp.int32, ctx // page_size)
    sampling = (
        on_chip(lambda: jax.random.split(jax.random.key(0), S)),
        rows(jnp.float32), rows(jnp.float32), rows(jnp.int32),
    )
    common = dict(attn_impl="pallas", compute_dtype=BF16)
    if program == "paged_decode_chunk":
        lowered = generate.paged_decode_chunk.lower(
            params, cfg, kv, tables, rows(jnp.int32), rows(jnp.int32),
            rows(jnp.bool_), rows(jnp.int32, 0), *sampling,
            chunk=lay["decode_chunk"], eos=65536, **common,
        )
    elif program == "paged_prefill":
        lowered = generate.paged_prefill.lower(
            params, cfg, rows(BF16, lay["prefill_chunk"], cfg.hidden_size),
            rows(jnp.int32), tables, kv, rows(jnp.int32), *sampling,
            slots=rows(jnp.int32), held_stats=True, **common,
        )
    else:
        scalar = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
        lowered = paged_kv.handover_state.lower(kv, scalar, scalar)
    compiled = lowered.compile()
    text = compiled.as_text()
    kernels = {"paged_decode_chunk": ("_ragged_paged", "gmm"),
               "paged_prefill": ("_mha_forward", "gmm"),
               "handover_state": ()}[program]
    for kernel in kernels:
        assert kernel in text
    assert "ragged-dot" not in text  # every grouped product is the kernel
    memory = compiled.memory_analysis()
    nbytes = lambda t: sum(  # noqa: E731
        a.size * a.dtype.itemsize for a in jax.tree_util.tree_leaves(t))
    pool_bytes, weight_bytes = nbytes(kv), nbytes(params)
    with capsys.disabled():
        print(f"\n{program}: weights {weight_bytes} B, pool {pool_bytes} B, "
              f"arguments {memory.argument_size_in_bytes} B, temporaries "
              f"{memory.temp_size_in_bytes} B")
    assert weight_bytes == mem["weights_bytes"] == 10_536_278_528
    assert pool_bytes == mem["pool_bytes"] == (
        12288 * (64 * 4096 + 65_536) + 96 * 65_536)
    assert memory.alias_size_in_bytes == pool_bytes
    edge_plane = 12288 * 65_536
    assert memory.temp_size_in_bytes < 0.1 * edge_plane
    if program != "handover_state":
        key = program.split("_")[1]  # decode / prefill
        assert memory.argument_size_in_bytes == mem[f"arguments_{key}_bytes"]
        assert abs(memory.temp_size_in_bytes
                   - mem[f"temporaries_{key}_bytes"]) < 8e6
        total = (memory.argument_size_in_bytes
                 + mem["temporaries_decode_bytes"]
                 + mem["temporaries_prefill_bytes"])
        assert 0.25 * 16e9 < total < 15.75e9 - 0.5e9


def test_ssd_step_kernel_compiles_at_the_cells_shapes_in_place(
    one_chip, mosaic
):
    """`_ssd_step` and the two row copies of a prefill at the published
    widths (96 slots, 5 mixers, a state of 128 x 8,192 float32 a lane a
    layer), compiled for the described v5e with the plane aliased."""
    from oryx_tpu.ops.pallas import ssd_step

    B, G, N, d = 96, 8, 128, 8192
    assert ssd_step.fits(B, d, N, G)
    sd = lambda shape, dt=jnp.float32: jax.ShapeDtypeStruct(  # noqa: E731
        shape, dt, sharding=one_chip)
    plane = sd((5, B, N, d))
    text = jax.jit(
        lambda a, dtx, bc, live, pl, li: ssd_step.ssd_step(
            a, dtx, bc, live, pl, li, G), donate_argnums=(4,),
    ).lower(sd((B, d)), sd((B, d)), sd((B, 2 * G * N)),
            sd((B,), jnp.int32), plane, sd((), jnp.int32)).compile().as_text()
    assert "_ssd_step" in text and "tpu_custom_call" in text
    compiled = jax.jit(
        lambda pl, li, slots, rows: ssd_step.write_rows(
            pl, li, slots, rows + ssd_step.read_rows(pl, li, slots)),
        donate_argnums=(0,),
    ).lower(plane, sd((), jnp.int32), sd((1,), jnp.int32),
            sd((1, N, d))).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 4 * N * d * 4


@pytest.mark.parametrize("program", ["paged_decode_chunk", "paged_prefill"])
def test_nemotron_serve_programs_keep_pages_and_state_in_place(
    one_chip, mosaic, program, capsys
):
    """The programs of `nemotron-3-super.agent-reasoning` at the cell's
    FULL size (the eleven-layer cut, 128 of 512 latent experts, 32,768
    vocabulary rows; 96 slots x 8,448, the engine's default pool of
    12,672 pages of 64, chunk 1,024 / 8), compiled for the described
    v5e: the donated pool (paged K/V of the one attention layer, the
    per-slot conv rows and the float32 `ssm` plane of the five mixers,
    2.0 GB) is aliased to the output whole and NOTHING copies the plane
    (as a gather and a scatter on it, the chunked prefill had XLA lay
    all of it out anew around every chunk: 2.2 GB of temporaries; the
    row copies of `ssd_step` keep them at 0.44). The kernels are
    `_ssd_step` (decode), `_ragged_paged` / `_mha_forward` and `gmm`
    (1,024 x 2,688 in tiles of 1,024 x 896). THE RULE ISSUE 60 agreed
    on: 96 slots where arguments + the larger program's temporaries
    leave 1.0 GB of the 16.91 GB a program may use, else 64. The
    numbers printed here are the configuration file's `memory` block."""
    import dataclasses
    import json
    import os

    from oryx_tpu import config as cfg_lib
    from oryx_tpu.models import generate, qwen2

    def on_chip(make):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
            jax.eval_shape(make),
        )

    conf = json.load(open(os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "benchmark", "configs", "nemotron-3-super-ep4-serve.json")))
    lay, mem = conf["layout"], conf["memory"]
    cfg = dataclasses.replace(
        cfg_lib.nemotron3_super_ep4().llm, num_layers=lay["num_layers"])
    slots, page_size, ctx = lay["num_slots"], lay["page_size"], lay["max_ctx"]
    assert (slots, page_size, ctx) == (96, 64, 8448)
    pages = slots * ctx // page_size  # the engine's default pool
    assert pages == mem["num_pages"] == 12672 and "num_pages" not in lay
    S = slots if program == "paged_decode_chunk" else 1
    rows = lambda dtype, *tail: jax.ShapeDtypeStruct(  # noqa: E731
        (S, *tail), dtype, sharding=one_chip
    )
    params = on_chip(
        lambda: qwen2.init_params(cfg, jax.random.key(0), dtype=BF16))
    kv = on_chip(lambda: qwen2.init_paged_kv_cache(
        cfg, pages, page_size, dtype=BF16, num_slots=slots))
    assert kv["k"].shape == (1, 12672, 64, 2, 128)
    assert kv["conv"].shape == (5, 96, 30720)
    assert kv["ssm"].shape == (5, 96, 128, 8192)
    assert kv["ssm"].dtype == jnp.float32
    tables = rows(jnp.int32, ctx // page_size)
    sampling = (
        on_chip(lambda: jax.random.split(jax.random.key(0), S)),
        rows(jnp.float32), rows(jnp.float32), rows(jnp.int32),
    )
    common = dict(attn_impl="pallas", compute_dtype=BF16)
    if program == "paged_decode_chunk":
        lowered = generate.paged_decode_chunk.lower(
            params, cfg, kv, tables, rows(jnp.int32), rows(jnp.int32),
            rows(jnp.bool_), rows(jnp.int32, 0), *sampling,
            chunk=lay["decode_chunk"], eos=32768, **common,
        )
    else:
        lowered = generate.paged_prefill.lower(
            params, cfg, rows(BF16, lay["prefill_chunk"], cfg.hidden_size),
            rows(jnp.int32), tables, kv, rows(jnp.int32), *sampling,
            slots=rows(jnp.int32), held_stats=True, **common,
        )
    compiled = lowered.compile()
    text = compiled.as_text()
    kernels = {"paged_decode_chunk": ("_ssd_step", "_ragged_paged", "gmm"),
               "paged_prefill": ("_ssd_read_rows", "_ssd_write_rows",
                                 "_mha_forward", "gmm")}[program]
    for kernel in kernels:
        assert kernel in text
    assert "ragged-dot" not in text  # every grouped product is the kernel
    memory = compiled.memory_analysis()
    nbytes = lambda t: sum(  # noqa: E731
        a.size * a.dtype.itemsize for a in jax.tree_util.tree_leaves(t))
    pool_bytes, weight_bytes = nbytes(kv), nbytes(params)
    with capsys.disabled():
        print(f"\n{program}: weights {weight_bytes} B, pool {pool_bytes} B, "
              f"arguments {memory.argument_size_in_bytes} B, temporaries "
              f"{memory.temp_size_in_bytes} B")
    assert weight_bytes == mem["weights_bytes"] == 9_317_307_904
    assert pool_bytes == mem["pool_bytes"] == 2_873_229_312
    assert memory.alias_size_in_bytes == pool_bytes
    # nothing copies the state plane (2.0 GB)
    assert memory.temp_size_in_bytes < 0.3 * nbytes(kv["ssm"])
    key = program.split("_")[1]  # decode / prefill
    assert memory.argument_size_in_bytes == mem[f"arguments_{key}_bytes"]
    assert abs(memory.temp_size_in_bytes
               - mem[f"temporaries_{key}_bytes"]) < 16e6
    total = mem["arguments_decode_bytes"] + max(
        mem["temporaries_decode_bytes"], mem["temporaries_prefill_bytes"])
    assert 0.6 * 16.91e9 < total < 16.91e9 - 1.0e9
