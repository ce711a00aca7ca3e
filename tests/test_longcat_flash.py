"""LongCat-Flash at `longcat_tiny` on the CPU: the latent (MLA) paged
cache, the absorbed decode, the shortcut-connected double layer and the
expert layer's share against the plain reference
(benchmark/reference/longcat_flash_ref.py); the split engine end to end
with the prefix cache; every mode that refuses a latent pool."""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark.reference import longcat_flash_ref as ref
from oryx_tpu import config as cfg_lib
from oryx_tpu.models import generate, oryx, qwen2
from oryx_tpu.ops import paged_kv
from oryx_tpu.ops.pallas import paged_attention as ppa
from oryx_tpu.parallel import sharding
from oryx_tpu.serve import api_server
from oryx_tpu.serve.pipeline import OryxInference
from oryx_tpu.serve.scheduler import ContinuousScheduler
from oryx_tpu.utils.metrics import ServingMetrics

F32 = jnp.float32
TOL = 2e-6  # float32 on both sides: summation order only
PS, MAXP = 16, 4


@pytest.fixture(scope="module")
def tiny():
    cfg = cfg_lib.longcat_tiny().llm
    params = qwen2.init_params(cfg, jax.random.key(0))
    # Norm weights away from 1, so a missing one would show.
    for i, sub in enumerate(("sub0", "sub1")):
        for j, name in enumerate(("q_a_norm", "kv_a_norm", "input_norm")):
            w = params["layers"][sub][name]["weight"]
            params["layers"][sub][name]["weight"] = (
                1 + 0.1 * jax.random.normal(jax.random.key(3 * i + j), w.shape))
    return cfg, params


@pytest.fixture(scope="module")
def prompt(tiny):
    cfg, _ = tiny
    return np.random.default_rng(0).integers(
        3, cfg.vocab_size, 40).astype(np.int32)


def _greedy(n):
    return (jnp.zeros((n,)), jnp.ones((n,)), jnp.zeros((n,), jnp.int32))


def _prefill(cfg, params, ids, head, impl, chunk=16):
    """`head` tokens of `ids` through paged_prefill in chunks, into slot
    0 of a two-slot pool whose pages lie in reverse order."""
    kv = qwen2.init_paged_kv_cache(cfg, 2 * MAXP, PS, F32)
    bt = jnp.arange(2 * MAXP, dtype=jnp.int32).reshape(2, MAXP)[::-1]
    emb = generate.pad_embeds_for_chunks(
        params["embed"]["weight"][jnp.asarray(ids[:head])][None], chunk)
    keys = jax.random.split(jax.random.key(0), 1)
    for off in range(0, head, chunk):
        kv, _, keys, routing = generate.paged_prefill(
            params, cfg, emb[:, off:off + chunk],
            jnp.asarray([min(head, off + chunk)]), bt[:1], kv,
            jnp.asarray([off]), keys, *_greedy(1), attn_impl=impl,
            return_routing=True)
    return kv, bt, routing["logits"], routing


def test_forward_without_a_cache_matches_the_reference(tiny, prompt):
    cfg, params = tiny
    want, chosen = ref.logits(params, cfg, prompt, return_experts=True)
    got, _, routing = qwen2.forward(
        params, cfg, input_ids=jnp.asarray(prompt)[None], return_routing=True)
    np.testing.assert_allclose(got[0], want, atol=TOL)
    assert np.array_equal(routing["ids"], chosen)
    assert np.any(np.asarray(chosen) >= cfg.num_experts)  # zero-compute hit
    first, count = cfg.held
    assert np.any((np.asarray(chosen) >= first)
                  & (np.asarray(chosen) < first + count))


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_prefill_then_decode_through_the_latent_pool_matches_the_reference(
        tiny, prompt, impl):
    """Chunked prefill (the second chunk meets a cached latent prefix,
    the expanded path), then the timed decode chunk (the absorbed path
    over the pages in place, an empty lane beside the live one): logits
    of every step against the reference's full forward."""
    cfg, params = tiny
    head = 29
    kv, bt, last, _ = _prefill(cfg, params, prompt, head, impl)
    want = ref.logits(params, cfg, prompt)
    np.testing.assert_allclose(last[0], want[head - 1], atol=TOL)
    out = generate.paged_decode_chunk(
        params, cfg, kv, bt, jnp.asarray([prompt[head], 0]),
        jnp.asarray([head, 0]), jnp.asarray([False, True]),
        jnp.zeros((2, 0), jnp.int32), jax.random.split(jax.random.key(1), 2),
        *_greedy(2), chunk=4, eos=-1, attn_impl=impl, return_routing=True)
    toks, stats, logits = out[6], out[8], out[9]
    seq = np.concatenate([prompt[:head], np.asarray(toks)[0]])
    want = ref.logits(params, cfg, seq)
    np.testing.assert_allclose(logits[0], want[head:head + 4], atol=TOL)
    st = dict(zip(generate.SHARE_STATS, np.asarray(stats)))
    L, K = cfg.num_layers, cfg.num_experts_per_tok
    assert st["layer_forwards"] == 4 * L and st["pairs"] == 4 * L * K
    assert 0 < st["zero_pairs"] < st["pairs"]
    assert st["held_hit"] <= st["held_rows"] <= st["pairs"] - st["zero_pairs"]
    assert st["kv_tokens"] == sum(range(head + 1, head + 5))


def test_absorbed_decode_equals_the_expanded_form(tiny, prompt):
    """One step on the same pool two ways: with kv_lengths the absorbed
    product over the paged latents, without it the up-projected keys
    and values of the gathered prefix."""
    cfg, params = tiny
    head = 21
    kv, bt, _, _ = _prefill(cfg, params, prompt, head, "xla")
    common = dict(
        input_ids=jnp.asarray(prompt[head:head + 1])[None],
        positions=jnp.asarray([[head]]), kv_cache=kv, block_tables=bt[:1],
        write_slots=jnp.asarray([head]),
        kv_mask=(jnp.arange(PS * MAXP) <= head)[None].astype(jnp.int32))
    absorbed, _ = qwen2.forward(
        params, cfg, kv_lengths=jnp.asarray([head + 1]), **common)
    expanded, _ = qwen2.forward(params, cfg, **common)
    np.testing.assert_allclose(absorbed, expanded, atol=TOL)


def test_latent_kernel_equals_its_xla_twin_on_ragged_rows():
    """The page walk in interpret mode: rows of length 0, one page, a
    partial last page and every page, over a shuffled table."""
    P, Dp, Dv, Hq, B = 14, 128, 96, 4, 4
    pages = jax.random.normal(jax.random.key(0), (P, PS, Dp), F32)
    q = jax.random.normal(jax.random.key(1), (B, Hq, Dp), F32)
    bt = np.full((B, 3), P, np.int32)
    perm = np.random.default_rng(0).permutation(P)
    for b, n in enumerate((0, 1, 2, 3)):
        bt[b, :n] = perm[3 * b:3 * b + n]
    lens = jnp.asarray([0, 16, 27, 48])
    kw = dict(scale=0.3, value_dim=Dv)
    want = paged_kv.latent_decode_attention(q, pages, jnp.asarray(bt), lens,
                                            **kw)
    got = ppa.latent_decode_attention(q, pages, jnp.asarray(bt), lens, **kw)
    np.testing.assert_allclose(got, want, atol=TOL)
    assert not np.any(np.asarray(got[0])) and np.any(np.asarray(got[1]))


def test_held_parts_of_all_shares_and_the_zero_part_once_are_the_layer(tiny):
    """The share test: the uncut layer's MoE(x) (every routed expert
    held, by the reference) is the sum over the E / count shares of
    what the program's expert layer computes for its held experts, plus
    the zero-compute part counted once."""
    cfg, _ = tiny
    E, count = cfg.num_experts, cfg.held[1]
    whole = dataclasses.replace(cfg, experts_held=None)
    params = qwen2.init_params(whole, jax.random.key(4))
    lp = jax.tree.map(lambda a: a[0], params["layers"])
    x = jax.random.normal(jax.random.key(5), (16, cfg.hidden_size), F32)
    want, ids = ref.moe_layer(x, lp, whole)
    zero_part, _ = ref.moe_layer(x, lp, whole, held=(0, 0))
    total = zero_part
    for first in range(0, E, count):
        share = dataclasses.replace(cfg, experts_held=(first, count))
        kernels = jax.tree.map(lambda a: a[0, first:first + count],
                               params["layers"]["experts"])
        y, routing = qwen2._moe(
            share, x, lp["router"]["kernel"], kernels,
            jnp.asarray(0, jnp.int32), router_bias=lp["router"]["bias"])
        assert np.array_equal(routing["ids"], ids)
        total = total + (y - zero_part)
    np.testing.assert_allclose(total, want, atol=TOL)
    assert float(jnp.max(jnp.abs(zero_part))) > 0


def test_router_selects_by_the_bias_and_weighs_by_the_probability(tiny):
    cfg, params = tiny
    x = jax.random.normal(jax.random.key(7), (32, cfg.hidden_size), F32)
    kernel = params["layers"]["router"]["kernel"][0]
    bias = jnp.zeros((kernel.shape[1],)).at[5].set(1.0)  # always chosen
    w, idx = qwen2.moe_route(cfg, x, kernel, bias)
    p = jax.nn.softmax(x @ kernel, axis=-1)
    assert np.all(np.asarray(idx)[:, 0] == 5)
    np.testing.assert_allclose(
        w, cfg.routed_scaling_factor * jnp.take_along_axis(p, idx, -1),
        rtol=1e-6)
    assert float(jnp.max(jnp.sum(w, -1))) < cfg.routed_scaling_factor


def test_page_copy_fetch_and_upload_walk_the_latent_plane(tiny):
    cfg, _ = tiny
    kv = qwen2.init_paged_kv_cache(cfg, 6, PS, F32)
    plane = kv[paged_kv.LATENT]
    assert plane.shape == (2 * cfg.num_layers, 6, PS, cfg.latent_page_dim)
    assert cfg.latent_dim == 40 and cfg.latent_page_dim == 128
    assert paged_kv.pool_plane(kv).shape[2] == PS
    assert paged_kv.kv_pool_dtype(kv) == "float32"
    kv = {paged_kv.LATENT: jax.random.normal(jax.random.key(0), plane.shape)}
    want = np.asarray(kv[paged_kv.LATENT][:, 2])
    blob = paged_kv.fetch_page(kv, 2)
    kv = paged_kv.copy_pages(kv, jnp.asarray(2), jnp.asarray(4))
    kv = paged_kv.upload_page(kv, jnp.asarray(5), blob)
    for page in (2, 4, 5):
        assert np.array_equal(kv[paged_kv.LATENT][:, page], want)


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
    cfg = cfg_lib.longcat_tiny()
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


def test_engine_replies_are_the_references_and_histories_are_spliced(pipe):
    """More requests than slots through the continuous split engine; a
    re-sent history is spliced from the prefix cache's latent pages and
    still answers as the reference does."""
    metrics = ServingMetrics()
    sched = ContinuousScheduler(
        pipe, num_slots=2, page_size=PS, max_ctx=256, prefill_chunk=32,
        autostart=False, metrics=metrics)
    first = "a tool said: " + "x, y and z; " * 4
    reqs = [(first, 6), ("another session", 5), (first + "<1><2> and then?", 6)]
    handles = [sched.submit({"question": q}, cap, None) for q, cap in reqs[:2]]
    sched.start()
    results = [h.result(timeout=600) for h in handles]
    results.append(sched.submit(
        {"question": reqs[2][0]}, reqs[2][1], None).result(timeout=600))
    sched.close()
    for (q, cap), (reply, reason, usage) in zip(reqs, results):
        want, margins, n = _want(pipe, q, cap)
        got = _ids(reply)
        assert reason == "length" and usage == (n, cap)
        for g, w, m in zip(got, want, margins):
            if m <= 1e-4:
                break
            assert g == w
    assert metrics.get("prefix_cache_hit_tokens_total") >= 3 * PS
    pairs = metrics.get("moe_pairs_total")
    assert 0 < metrics.get("moe_zero_pairs_total") < pairs
    slots = metrics.get("moe_held_expert_slots_total")
    assert 0 < metrics.get("moe_held_experts_hit_total") <= slots
    assert metrics.get("moe_expert_rows_max_total") >= metrics.get(
        "moe_expert_rows_mean_total") > 0
    assert metrics.get("decode_kv_tokens_total") > 0


def test_build_server_serves_the_latent_model_over_http(pipe):
    import json
    import threading
    import urllib.request

    srv = api_server.build_server(
        pipe, port=0, engine="continuous", num_slots=2, page_size=PS,
        max_ctx=256, prefill_chunk=32, max_tokens_limit=256)
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    try:
        body = {"messages": [{"role": "user", "content": "over http"}],
                "max_tokens": 5, "temperature": 0.0}
        req = urllib.request.Request(
            f"http://127.0.0.1:{srv.server_address[1]}/v1/chat/completions",
            data=json.dumps(body).encode(),
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=300) as r:
            out = json.load(r)
    finally:
        if srv.supervisor is not None:
            srv.supervisor.stop()
        srv.scheduler.close()
        srv.shutdown()
        srv.server_close()
    assert out["usage"]["completion_tokens"] == 5
    assert len(_ids(out["choices"][0]["message"]["content"])) == 5


# --- what is refused --------------------------------------------------------


@pytest.mark.parametrize("kw, names", [
    ({"ragged": True}, "ragged=True"),
    ({"ragged": True, "speculate": 2}, "ragged=True"),
    ({"kv_dtype": "int8"}, "kv_dtype='int8'"),
])
def test_scheduler_refuses_the_modes_a_latent_pool_is_not_built_for(
        pipe, kw, names):
    with pytest.raises(ValueError, match="latent attention") as e:
        ContinuousScheduler(pipe, num_slots=2, page_size=PS, max_ctx=256,
                            prefill_chunk=32, autostart=False, **kw)
    assert names in str(e.value)


@pytest.mark.parametrize("option", [
    {"host_cache_bytes": 1 << 24}, {"audit_sample_every": 1},
    {"numerics_every": 1}, {"prefill_chunk": None},
])
def test_the_engine_serves_what_it_does_not_refuse(
        pipe, option, serves_like_the_default):
    """A latent pool's page axis is where a K/V pool's is, so the host
    tier moves its pages; the auditor's one-token step and an unchunked
    prefill read it through `qwen2.forward` as the two programs do."""
    def build(**kw):
        return ContinuousScheduler(pipe, **{
            "num_slots": 2, "page_size": PS, "max_ctx": 256,
            "prefill_chunk": 32, "autostart": False, **kw})

    serves_like_the_default(
        build, option, "a tool said: " + "x, y and z; " * 4, 6)


def test_sharded_engine_refuses_a_latent_model(pipe):
    class Meshed:
        mesh = object()

        def __getattr__(self, name):
            return getattr(pipe, name)

    with pytest.raises(ValueError, match="tensor-parallel engine"):
        ContinuousScheduler(Meshed(), num_slots=2, page_size=PS, max_ctx=256,
                            prefill_chunk=32, autostart=False)


def test_a_quantized_or_head_sharded_latent_pool_is_refused(tiny):
    cfg, _ = tiny
    with pytest.raises(ValueError, match="kv_dtype='int8'"):
        qwen2.init_paged_kv_cache(cfg, 4, PS, F32, kv_dtype="int8")
    kv = qwen2.init_paged_kv_cache(cfg, 4, PS, F32)
    with pytest.raises(ValueError, match="latent pool"):
        paged_kv.QuantPages(
            kv[paged_kv.LATENT].astype(jnp.int8), jnp.zeros((4, 4, PS)))
    mesh = jax.make_mesh((2,), ("tp",))
    assert sharding.paged_kv_spec(mesh) is not None
    with pytest.raises(ValueError, match="sharded over KV heads"):
        sharding.paged_kv_spec(mesh, kv)
    with pytest.raises(ValueError, match="sharded over KV heads"):
        sharding.shard_paged_kv(kv, mesh)


def test_forward_refuses_a_packed_step_and_a_per_head_cache(tiny):
    cfg, params = tiny
    kv = qwen2.init_paged_kv_cache(cfg, 4, PS, F32)
    seg = jnp.zeros((1, 4), jnp.int32)
    with pytest.raises(ValueError, match="packed ragged step"):
        qwen2.forward(params, cfg, input_ids=seg, positions=seg, kv_cache=kv,
                      block_tables=jnp.zeros((2, 2), jnp.int32),
                      q_segments=seg)
    with pytest.raises(ValueError, match="per-head cache"):
        qwen2.forward(params, cfg, input_ids=seg,
                      kv_cache=qwen2.init_kv_cache(cfg, 1, 8, F32))


@pytest.mark.parametrize("bad", [
    {"kv_lora_rank": 32, "q_lora_rank": 0},
    {"num_experts": 0, "experts_held": None, "router_bias": False,
     "zero_experts": 0},
    {"block_length": 4},
    {"experts_held": (6, 4)},
    {"num_experts": 0, "zero_experts": 4, "kv_lora_rank": 0},
])
def test_config_refuses_what_the_layers_cannot_run(bad):
    with pytest.raises(ValueError):
        dataclasses.replace(cfg_lib.longcat_tiny().llm, **bad)


def test_presets_state_the_published_geometry_and_the_share():
    full = cfg_lib.longcat_flash_chat().llm
    share = cfg_lib.longcat_flash_chat_ep32().llm
    assert (full.num_layers, full.hidden_size, full.num_heads) == (28, 6144, 64)
    assert (full.num_experts, full.zero_experts, full.num_experts_per_tok) \
        == (512, 256, 12)
    assert full.held == (0, 512) and share.held == (0, 16)
    assert (share.vocab_size, full.vocab_size) == (16384, 131072)
    assert share.latent_dim == 576 and share.latent_page_dim == 640
    assert cfg_lib.longcat_flash_chat().vision is None
    n = sum(a.size for a in jax.tree.leaves(jax.eval_shape(
        lambda: qwen2.init_params(
            dataclasses.replace(share, num_layers=4), jax.random.key(0)))))
    # ISSUE 31's arithmetic: 4 x (638.9 M + 16 x 37.75 M) + 201.3 M,
    # plus norms and the router's bias.
    assert abs(n - 5_173.2e6) < 2e6
    rt = cfg_lib.OryxConfig.from_json(cfg_lib.longcat_flash_chat_ep32().to_json())
    assert rt.llm.experts_held == (0, 16)
