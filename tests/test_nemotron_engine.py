"""The continuous split engine over `nemotron3_tiny` (Mamba-2 state a
slot beside the paged K/V of one attention layer, latent experts with a
held share): mixed-length requests over reused slots stream what a solo
run streams, which is the plain reference's greedy continuation
(benchmark/reference/nemotron_h_ref.py); the counters say what ran; what
the engine is not built for beside a state is refused by name."""

import dataclasses

import numpy as np
import pytest

import jax

from benchmark.reference import nemotron_h_ref as ref
from oryx_tpu import config as cfg_lib
from oryx_tpu.models import oryx
from oryx_tpu.serve.pipeline import OryxInference
from oryx_tpu.serve.scheduler import ContinuousScheduler
from oryx_tpu.utils.metrics import ServingMetrics
from tests.test_nemotron_h import REFUSAL, _scaled, sizes_of

PS = 16


class IdTokenizer:
    def encode(self, text, add_special_tokens=False):
        return [min(ord(c), 500) for c in text]

    def decode(self, ids, skip_special_tokens=True):
        return "".join(f"<{int(i)}>" for i in ids)


def _ids(reply):
    return [int(x) for x in reply.strip("<>").split("><")] if reply else []


@pytest.fixture(scope="module")
def pipe():
    cfg = cfg_lib.nemotron3_tiny()
    params = jax.jit(lambda k: oryx.init_params(cfg, k))(jax.random.key(0))
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
             ("abc" * 20, 7), ("zzz tell me a story", 9), ("q" * 33, 5)]


def _engine(pipe, metrics=None, **kw):
    return ContinuousScheduler(pipe, **{
        "num_slots": 2, "page_size": PS, "max_ctx": 256, "prefill_chunk": 16,
        "autostart": False, "metrics": metrics, **kw})


def test_engine_serves_four_requests_on_two_slots_with_the_counters(pipe):
    """Four requests of mixed lengths over two slots (each slot is
    reused, a request prefills in one while the other decodes): every
    reply is the reference's greedy continuation, which is what a solo
    run streams; the prefix cache is off, and the ssm_*, ssd_* and moe_*
    counters say what ran."""
    metrics = ServingMetrics()
    sched = _engine(pipe, metrics)
    assert sched.prefix_cache is None and sched.recurrent
    assert sched.share_stats and sched.prefill_held_stats
    sched.start()
    handles = [sched.submit({"question": q}, cap, None)
               for q, cap in QUESTIONS]
    results = [h.result(timeout=600) for h in handles]
    sched.close()
    prompt = chunks = 0
    llm = pipe.cfg.llm
    for (q, cap), (reply, reason, usage) in zip(QUESTIONS, results):
        want, n = _want(pipe, q, cap)
        prompt += n
        # dispatches of 16 tokens, each in mixer chunks of 8
        chunks += sum(-(-min(16, n - off) // llm.mamba_chunk_size)
                      for off in range(0, n, 16))
        assert reason == "length" and usage == (n, cap)
        assert _ids(reply) == want
    assert metrics.get("ssm_prefill_tokens_total") == prompt
    assert metrics.get("ssd_prefill_chunks_total") == chunks
    assert metrics.get("ssm_state_resets_total") == len(QUESTIONS)
    steps = metrics.get("ssm_decode_lane_steps_total")
    out = sum(cap for _, cap in QUESTIONS)
    assert out - len(QUESTIONS) <= steps <= 8 * (out // 8 + len(QUESTIONS))
    assert metrics.get("decode_kv_tokens_total") > steps
    assert metrics.get("ssm_state_bytes") == 2 * llm.state_bytes_per_slot(4)
    # Every live lane-step routes K pairs an expert layer (the step's
    # own count of its live lanes, within a step a request of the
    # host's); half of the eight experts are held here.
    K, Lm = llm.num_experts_per_tok, llm.moe_layers
    pairs = metrics.get("moe_pairs_total")
    assert pairs % (K * Lm) == 0
    assert abs(pairs / (K * Lm) - steps) <= len(QUESTIONS)
    assert 0 < metrics.get("moe_held_experts_hit_total") <= (
        metrics.get("moe_held_expert_slots_total"))
    assert metrics.get("moe_prefill_pairs_total") == prompt * K * Lm
    assert metrics.get("moe_shared_rows_total") > 0


@pytest.mark.parametrize("q,cap", QUESTIONS[:1])
def test_engine_streams_are_the_same_under_both_impls(pipe, q, cap):
    """`attn_impl="pallas"` (the paged attention kernel in interpret
    mode; the tiny widths fit neither `_ssd_step` nor `gmm`, which keep
    their twins) serves what "xla" serves, beside another lane that
    prefills, finishes and leaves its slot dead."""
    replies = {}
    for serving in ("xla", "pallas"):
        served = OryxInference(
            IdTokenizer(), pipe.params,
            dataclasses.replace(pipe.cfg, attn_impl=serving),
            template="plain")
        sched = _engine(served)
        sched.start()
        handles = [sched.submit({"question": text}, n, None)
                   for text, n in ((q, cap), ("w" * 21, 3))]
        replies[serving] = [h.result(timeout=600)[0] for h in handles]
        sched.close()
    assert replies["pallas"] == replies["xla"]
    assert _ids(replies["pallas"][0]) == _want(pipe, q, cap)[0]


@pytest.mark.parametrize("kw", [
    {"ragged": True}, {"ragged": True, "speculate": 2},
    {"kv_dtype": "int8"},
    {"host_cache_bytes": 1 << 20}, {"audit_sample_every": 4},
])
def test_the_engine_refuses_what_is_not_built_for_a_state(pipe, kw):
    with pytest.raises(ValueError, match=REFUSAL):
        _engine(pipe, **kw)


def test_the_prefix_cache_is_constructed_off_with_the_reason_logged(
        pipe, caplog):
    with caplog.at_level("INFO"):
        sched = _engine(pipe, prefix_cache=True)
    assert sched.prefix_cache is None
    assert any("prefix cache off" in r.getMessage() and
               "recurrent state" in r.getMessage() for r in caplog.records)


@pytest.mark.parametrize("option", [
    {"numerics_every": 1}, {"prefill_chunk": None},
])
def test_the_engine_serves_what_it_does_not_refuse(
        pipe, option, serves_like_the_default):
    serves_like_the_default(
        lambda **kw: _engine(pipe, **kw), option, QUESTIONS[0][0], 8)
