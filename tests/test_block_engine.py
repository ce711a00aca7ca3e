"""Block mode of the continuous engine (a block-diffusion model behind
`build_server(engine="continuous")`), at `sdar_tiny` on the CPU: tokens
against the reference's cache-less loop, cuts inside a block, the prefix
cache, streaming by block, the counters, and every construction that
block mode refuses."""

import dataclasses
import json
import threading
import urllib.error
import urllib.request

import numpy as np
import pytest

import jax

from benchmark.reference import sdar_moe_ref as ref
from oryx_tpu import config as cfg_lib
from oryx_tpu.models import oryx
from oryx_tpu.serve import api_server
from oryx_tpu.serve.pipeline import OryxInference
from oryx_tpu.serve.scheduler import ContinuousScheduler
from oryx_tpu.utils.metrics import ServingMetrics

# Float32 on both sides: the engine's logits and the reference's differ
# by summation order only, far below any margin worth the name.
MARGIN_TOL = 1e-4


class IdTokenizer:
    """One id per character in, `<id>` per token out."""

    def encode(self, text, add_special_tokens=False):
        return [min(ord(c), 500) for c in text]

    def decode(self, ids, skip_special_tokens=True):
        return "".join(f"<{int(i)}>" for i in ids)


def _ids(reply):
    return [int(x) for x in reply.strip("<>").split("><")] if reply else []


def _cfg(steps=2, remasking="low_confidence_static", **gen):
    cfg = cfg_lib.sdar_tiny()
    return dataclasses.replace(cfg, generation=dataclasses.replace(
        cfg.generation, denoising_steps=steps, remasking=remasking, **gen))


@pytest.fixture(scope="module")
def pipe():
    cfg = _cfg()
    return OryxInference(
        IdTokenizer(), oryx.init_params(cfg, jax.random.key(0)), cfg)


def _sched(pipe, **kw):
    kw = {"num_slots": 2, "page_size": 16, "max_ctx": 256,
          "prefill_chunk": 32, "autostart": False, **kw}
    return ContinuousScheduler(pipe, **kw)


def _run_all(sched, reqs):
    handles = [sched.submit({"question": q}, cap, s) for q, cap, s in reqs]
    sched.start()
    results = [h.result(timeout=600) for h in handles]
    sched.close()
    return handles, results


def _want(pipe, question, cap, **kw):
    ids, *_ = pipe._prepare_request({"question": question})
    gen = pipe.cfg.generation
    return ref.generate(
        pipe.params["llm"], pipe.cfg.llm, ids, cap,
        steps=gen.denoising_steps, remasking=gen.remasking,
        threshold=gen.confidence_threshold, **kw), len(ids)


def _assert_tokens(got, want, margins):
    """Equal wherever the reference's top-two margin exceeds the
    tolerance; after a position that may differ, contexts differ."""
    assert len(got) == len(want)
    for g, w, m in zip(got, want, margins):
        if m <= MARGIN_TOL:
            return
        assert g == w


def test_scheduler_generates_the_reference_loops_tokens(pipe):
    """More requests than slots, prompts with every tail length, a
    repeated prompt (spliced from the prefix cache): every reply is the
    reference's, and the counters keep their meaning."""
    metrics = ServingMetrics()
    sched = _sched(pipe, metrics=metrics)
    reqs = [("hello there", 7, None), ("what now? " * 6, 12, None),
            ("tell me more!", 5, None), ("hello there", 9, None),
            ("and a tail..", 6, None)]
    handles, results = _run_all(sched, reqs)
    tails = set()
    for (q, cap, _), (reply, reason, usage) in zip(reqs, results):
        (want, margins), n = _want(pipe, q, cap)
        tails.add(n % 4)
        _assert_tokens(_ids(reply), want, margins)
        assert reason == "length" and usage == (n, cap)
    assert len(tails) >= 3
    assert metrics.get("completed") == 5
    assert metrics.get("prefix_cache_hit_tokens_total") > 0
    blocks = metrics.get("diffusion_blocks_total")
    assert blocks >= sum(-(-cap // 4) for _, cap, _ in reqs)
    # T = 2 forwards a dispatch (a block's commit rides the next
    # block's first); slot-forwards of live slots with work against
    # slot-forwards dispatched.
    useful = metrics.get("decode_steps_useful")
    total = metrics.get("decode_steps_total")
    assert 0 < useful <= blocks * 2 and useful <= total
    assert total % 2 == 0  # every forward runs both slots
    assert metrics.get("diffusion_tokens_unmasked_total") <= blocks * 4
    routed = metrics.get("moe_rows_routed_total")
    assert routed > 0
    assert metrics.get("moe_expert_rows_mean_total") == pytest.approx(
        routed / pipe.cfg.llm.num_experts)
    assert metrics.get("moe_expert_rows_max_total") >= metrics.get(
        "moe_expert_rows_mean_total")
    assert metrics.get("moe_experts_hit_total") > 0
    text = metrics.registry.render()
    assert 'diffusion_forwards_total{kind="commit"} 0' in text
    how = metrics.registry.counter("diffusion_commits_total", ("how",))
    assert how.labels(how="fused").value + how.labels(
        how="dropped").value == blocks
    assert 'engine_phase_seconds_total{phase="denoise"}' in text
    assert 'phase="decode"' not in text
    assert 'dispatches_total{kind="block"}' in text


def test_whole_page_prefix_hit_gives_the_cold_prefills_tokens(pipe):
    """A page cached by one request (its K/V written under the block
    mask, by prefill and by the commit lanes of later blocks) serves a later request
    with the same prefix: same tokens as that request served cold."""
    q, longer = "the same opening words, ", "the same opening words, and more"
    cold = _sched(pipe, prefix_cache=False)
    _, (cold_a, cold_b) = _run_all(cold, [(q, 8, None), (longer, 8, None)])
    metrics = ServingMetrics()
    warm = _sched(pipe, num_slots=1, metrics=metrics)
    handles, (warm_a, warm_b) = _run_all(
        warm, [(q, 8, None), (longer, 8, None)])
    assert warm_a[0] == cold_a[0] and warm_b[0] == cold_b[0]
    assert metrics.get("prefix_cache_hit_tokens_total") >= 16
    assert metrics.get("prefix_cache_hit_tokens_total") % 4 == 0


def test_fully_cached_prompt_activates_without_a_prefill(pipe):
    """The same prompt again, a whole number of pages long: every block
    of it is spliced, no prefill dispatch runs, the reply is the same."""
    metrics = ServingMetrics()
    sched = _sched(pipe, num_slots=1, metrics=metrics)
    ids, *_ = pipe._prepare_request({"question": "x"})
    q = "x" * (1 + (-len(ids)) % 16)
    ids, *_ = pipe._prepare_request({"question": q})
    assert len(ids) % 16 == 0
    handles = [sched.submit({"question": q}, 6, None) for _ in range(2)]
    sched.start()
    first, second = (h.result(timeout=600) for h in handles)
    sched.close()
    assert first[0] == second[0]
    assert metrics.get("prefix_cache_hit_tokens_total") == len(ids)
    assert metrics.get("prefill_tokens_total") == len(ids)  # the first only


@pytest.mark.parametrize("cap", [1, 2, 3, 5])
def test_max_tokens_cuts_inside_a_block(pipe, cap):
    (want, margins), n = _want(pipe, "cut me short", cap)
    _, ((reply, reason, usage),) = _run_all(
        _sched(pipe), [("cut me short", cap, None)])
    _assert_tokens(_ids(reply), want, margins)
    assert reason == "length" and usage == (n, cap)


@pytest.mark.parametrize("where", [0, 1, 2, 5])
def test_eos_cuts_inside_a_block(where):
    """The token the reference generates at `where` is made the EOS: the
    reply stops before it, finish reason `stop`, mid-block or not."""
    base = _cfg()
    params = oryx.init_params(base, jax.random.key(0))
    probe = OryxInference(IdTokenizer(), params, base)
    (free, _), n = _want(probe, "stop inside", 8)
    eos = free[where]
    first = free.index(eos)
    cfg = _cfg(eos_token_id=eos)
    pipe = OryxInference(IdTokenizer(), params, cfg)
    _, ((reply, reason, usage),) = _run_all(
        _sched(pipe), [("stop inside", 8, None)])
    assert _ids(reply) == free[:first]
    assert reason == "stop" and usage == (n, first + 1)
    want, _ = ref.generate(
        params["llm"], cfg.llm, probe._prepare_request(
            {"question": "stop inside"})[0], 8, steps=2,
        remasking="low_confidence_static", eos=eos)
    assert want == free[:first]


def test_dynamic_rule_through_the_engine():
    cfg = _cfg(steps=0, remasking="low_confidence_dynamic",
               confidence_threshold=1.0 / 400)
    pipe = OryxInference(
        IdTokenizer(), oryx.init_params(cfg, jax.random.key(1)), cfg)
    metrics = ServingMetrics()
    _, ((reply, _, _),) = _run_all(
        _sched(pipe, metrics=metrics), [("dynamic rule", 8, None)])
    (want, margins), _ = _want(pipe, "dynamic rule", 8)
    _assert_tokens(_ids(reply), want, margins)


def test_eviction_replays_the_same_tokens(pipe):
    """Page pressure evicts the younger slot; its replay regenerates the
    blocks it had emitted and goes on: replies equal the solo ones."""
    metrics = ServingMetrics()
    sched = _sched(pipe, num_pages=20, max_ctx=256, metrics=metrics)
    reqs = [("a" * 40, 60, None), ("b" * 40, 60, None)]
    _, results = _run_all(sched, reqs)
    for (q, cap, _), (reply, _, _) in zip(reqs, results):
        _, ((solo, _, _),) = _run_all(_sched(pipe), [(q, cap, None)])
        assert reply == solo
    assert metrics.get("evicted") >= 1


REFUSALS = [
    (dict(ragged=True), "ragged"),
    (dict(ragged=True, speculate=2), "ragged"),
    (dict(prefill_chunk=None), "chunked prefill"),
    (dict(kv_dtype="int8"), "quantized"),
    (dict(audit_sample_every=4), "auditor"),
    (dict(numerics_every=4), "numerics"),
    (dict(page_size=18, max_ctx=252), "page_size=18"),
    (dict(prefill_chunk=30), "prefill_chunk=30"),
]


@pytest.mark.parametrize("kw,match", REFUSALS)
def test_block_mode_refuses_what_is_not_built(pipe, kw, match):
    with pytest.raises(ValueError, match=match):
        _sched(pipe, **kw)


@pytest.mark.parametrize("option", [{"host_cache_bytes": 1 << 24}])
def test_the_engine_serves_what_it_does_not_refuse(
        pipe, option, serves_like_the_default):
    """Block mode keeps the K/V pool and the prefix cache of the split
    engine, so the host tier moves its pages as it moves theirs. The
    weights are four times the init's: at 0.02 a reply is one token
    repeated whatever the pages hold."""
    loud = OryxInference(IdTokenizer(), jax.tree.map(
        lambda x: x * 4 if x.ndim >= 2 else x, pipe.params), pipe.cfg,
        template="plain")
    serves_like_the_default(
        lambda **kw: _sched(loud, **kw), option,
        "the same opening words, and more of them", 8)


def test_block_mode_refuses_a_mesh_and_too_many_steps(pipe):
    class Meshed:
        cfg, params, mesh = pipe.cfg, pipe.params, object()

    with pytest.raises(ValueError, match="tensor-parallel"):
        _sched(Meshed())
    many = OryxInference(IdTokenizer(), pipe.params, _cfg(steps=8))
    with pytest.raises(ValueError, match="denoising_steps=8"):
        _sched(many)


def test_speculate_without_ragged_keeps_its_own_error(pipe):
    """The scheduler's older check still speaks first."""
    with pytest.raises(ValueError, match="speculate requires ragged"):
        _sched(pipe, speculate=2)


def test_the_pipes_own_loops_are_refused(pipe):
    for call in (lambda: pipe.chat("hi", max_new_tokens=2),
                 lambda: next(pipe.chat_stream("hi", max_new_tokens=2)),
                 lambda: pipe.score_options("q", ["a", "b"])):
        with pytest.raises(NotImplementedError, match="continuous engine"):
            call()


@pytest.fixture(scope="module")
def server(pipe):
    srv = api_server.build_server(
        pipe, port=0, engine="continuous", num_slots=2, page_size=16,
        max_ctx=256, prefill_chunk=32, max_tokens_limit=256,
    )
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    yield f"http://127.0.0.1:{srv.server_address[1]}"
    if srv.supervisor is not None:
        srv.supervisor.stop()
    srv.scheduler.close()
    srv.shutdown()
    srv.server_close()


def _post(url, body):
    req = urllib.request.Request(
        url + "/v1/chat/completions", data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json"},
    )
    return urllib.request.urlopen(req, timeout=300)


def test_http_reply_is_the_reference_loops(server, pipe):
    body = {"messages": [{"role": "user", "content": "over http"}],
            "max_tokens": 10, "temperature": 0.0}
    with _post(server, body) as r:
        out = json.load(r)
    (want, margins), n = _want(pipe, "over http", 10)
    _assert_tokens(_ids(out["choices"][0]["message"]["content"]), want,
                   margins)
    assert out["usage"]["prompt_tokens"] == n
    assert out["usage"]["completion_tokens"] == 10


def test_http_stream_sends_one_chunk_a_block(server, pipe):
    body = {"messages": [{"role": "user", "content": "stream it"}],
            "max_tokens": 12, "temperature": 0.0, "stream": True}
    deltas = []
    with _post(server, body) as r:
        for line in r:
            line = line.decode().strip()
            if not line.startswith("data: ") or line == "data: [DONE]":
                continue
            for c in json.loads(line[6:]).get("choices", []):
                if c.get("delta", {}).get("content"):
                    deltas.append(c["delta"]["content"])
    (want, margins), n = _want(pipe, "stream it", 12)
    _assert_tokens(_ids("".join(deltas)), want, margins)
    # A block's tokens arrive together, in position order: the prompt's
    # tail shortens the first chunk, every other one is a whole block.
    sizes = [len(_ids(d)) for d in deltas]
    first = 4 - n % 4
    assert sizes[0] == first and set(sizes[1:-1]) <= {4}
    assert sum(sizes) == 12


def test_media_on_a_text_only_model_is_a_400(server):
    body = {"messages": [{"role": "user", "content": [
        {"type": "text", "text": "what is this?"},
        {"type": "image_url", "image_url": {
            "url": "data:image/png;base64,"
            "iVBORw0KGgoAAAANSUhEUgAAAAEAAAABCAIAAACQd1PeAAAADElEQVR4nGP4z8AA"
            "AAMBAQDJ/pLvAAAAAElFTkSuQmCC"}},
    ]}], "max_tokens": 4}
    with pytest.raises(urllib.error.HTTPError) as e:
        _post(server, body)
    assert e.value.code == 400
    assert "text-only" in json.load(e.value)["error"]["message"]


def test_metrics_endpoint_has_the_block_families(server):
    with urllib.request.urlopen(server + "/metrics", timeout=30) as r:
        text = r.read().decode()
    for name in ("oryx_serving_diffusion_blocks_total",
                 "oryx_serving_diffusion_forwards_total",
                 "oryx_serving_diffusion_tokens_unmasked_total",
                 "oryx_serving_moe_rows_routed_total",
                 "oryx_serving_moe_expert_rows_max_total",
                 "oryx_serving_moe_expert_rows_mean_total",
                 "oryx_serving_moe_experts_hit_total",
                 'oryx_serving_diffusion_forwards_total{kind="commit"} 0',
                 'oryx_serving_diffusion_commits_total{how="fused"}',
                 'oryx_serving_diffusion_commits_total{how="dropped"}'):
        assert name in text
    assert 'phase="denoise"' in text


def test_sampled_request_is_reproducible_by_seed(pipe):
    sampling = {"temperature": 0.8, "top_p": 0.9, "seed": 7}
    other = {"temperature": 0.8, "top_p": 0.9, "seed": 8}
    _, results = _run_all(_sched(pipe), [
        ("sample me", 8, sampling), ("sample me", 8, dict(sampling)),
        ("sample me", 8, other)])
    assert results[0][0] == results[1][0]
    assert results[0][0] != results[2][0]


def test_a_block_in_flight_starves_neither_its_harvest_nor_the_emit(pipe):
    """With block n+1 enqueued behind it the harvest of block n is a
    wait for a device that has work when it returns: none of the
    seconds of `harvest`, `copy_out` and `emit` that pass while a block
    is in flight are billed as starved; the harvest that drains the
    engine is followed by starved ones."""
    metrics = ServingMetrics()
    sched = _sched(pipe, metrics=metrics)
    seen = {"phase": [], "starved": []}
    for kind, billed in (("phase", sched._phase_seconds),
                         ("starved", sched._starved_seconds)):
        for name in ("harvest", "copy_out", "emit"):
            def spy(s, _real=billed[name], _to=seen[kind], _name=name):
                _to.append((_name, sched._inflight is not None))
                _real(s)
            billed[name] = spy
    _run_all(sched, [("hello there", 24, None), ("what now?", 16, None)])
    assert metrics.get("block_dispatches_ahead_total") >= 4
    flying = {n for n, inflight in seen["phase"] if inflight}
    assert flying == {"harvest", "copy_out", "emit"}
    assert not [n for n, inflight in seen["starved"] if inflight]
    # the drain's harvest returns to a drained device
    drained = {n for n, inflight in seen["starved"] if not inflight}
    assert {"copy_out", "emit"} <= drained and "harvest" not in drained
    fam = metrics.registry.existing("engine_starved_seconds_total")
    assert fam.labels(phase="harvest").value == 0
    assert fam.labels(phase="copy_out").value > 0


def test_a_dense_model_keeps_its_phases_and_families():
    cfg = cfg_lib.oryx_tiny()
    pipe = OryxInference(
        IdTokenizer(), oryx.init_params(cfg, jax.random.key(0)), cfg)
    metrics = ServingMetrics()
    sched = ContinuousScheduler(
        pipe, num_slots=2, page_size=16, chunk=4, max_ctx=256,
        metrics=metrics, autostart=False)
    sched.close()
    text = metrics.registry.render()
    assert 'phase="decode"' in text and 'phase="first_token"' in text
    assert "diffusion_" not in text and "moe_" not in text
    assert np.shape(sched.blk) == (2, 1)
