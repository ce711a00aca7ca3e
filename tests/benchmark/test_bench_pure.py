"""The benchmark's yardstick, checked without any device: traffic as a
pure function of the seed, percentile ranking with failures, TTFT from
the due instant, the trace reduction on a recorded file, the FLOP and
byte functions against hand-worked cases."""

import http.server
import json
import math
import os
import random
import threading
import time

import pytest

from benchmark import costs, loadgen, metric_files, stats, trace, traffic
from benchmark.runners import serve

HERE = os.path.dirname(os.path.abspath(__file__))
CHAT = {
    "arrivals": {"process": "poisson", "rate": 5.0}, "system_tokens": 32,
    "turns": [1, 3, 2],
    "user_tokens": {"kind": "lognormal", "median": 96, "sigma": 0.8,
                    "min": 16, "max": 768},
    "max_tokens": {"kind": "lognormal", "median": 96, "sigma": 0.6,
                   "min": 16, "max": 384},
    "max_session_tokens": 3584,
}


def _lens(sessions):
    return sorted(
        (len(b["messages"][-1]["content"]), b["max_tokens"])
        for s in sessions for b in s
    )


@pytest.mark.parametrize("seed", [0, 7, 2**31 + 11])
def test_traffic_is_a_pure_function_of_the_seed(seed):
    a = traffic.build_sessions(CHAT, seed, 60)
    b = traffic.build_sessions(CHAT, seed, 60)
    assert json.dumps(a) == json.dumps(b)
    oa = traffic.arrival_offsets(CHAT["arrivals"], 60, random.Random(seed))
    ob = traffic.arrival_offsets(CHAT["arrivals"], 60, random.Random(seed))
    assert oa == ob and oa == sorted(oa)


def test_every_seed_gets_the_same_lengths_and_gaps_in_one_order():
    a = traffic.build_sessions(CHAT, 1, 60)
    b = traffic.build_sessions(CHAT, 2, 60)
    assert json.dumps(a) != json.dumps(b)  # other words
    # ... of the same lengths, request for request: one fixed shuffle
    assert _lens(a) == _lens(b)
    base = traffic.arrival_offsets(CHAT["arrivals"], 60, random.Random(0))
    assert base[-1] == pytest.approx(60 / 5.0)  # the stated rate, exactly
    # a closed loop's seed rotates each client's list
    flat = [x for s in a for x in s]
    assert traffic.rotated(flat, 61) == flat[1:] + flat[:1]


@pytest.mark.parametrize("dist,lo,hi,med", [
    ({"kind": "lognormal", "median": 96, "sigma": 0.8, "min": 16,
      "max": 768}, 16, 768, 96),
    ({"kind": "uniform", "min": 24, "max": 64}, 24, 64, 44),
    ({"kind": "const", "value": 224}, 224, 224, 224),
])
def test_quantile_values_respect_the_stated_distribution(dist, lo, hi, med):
    v = traffic.quantile_values(dist, 101)
    assert min(v) >= lo and max(v) <= hi
    assert abs(sorted(v)[50] - med) <= 1


def test_later_turns_resend_the_history_under_one_system_prompt():
    sessions = traffic.build_sessions(CHAT, 3, 30)
    multi = next(s for s in sessions if len(s) >= 2)
    first, second = multi[0]["messages"], multi[1]["messages"]
    assert first[0]["role"] == "system"
    assert second[: len(first)] == first  # history is a prefix
    systems = {s[0]["messages"][0]["content"] for s in sessions}
    assert len(systems) == 1 and len(systems.pop()) == 32


def test_media_sessions_ask_several_questions_of_one_medium():
    p = {
        "media": [{"kind": "image", "questions": 3, "block": 14,
                   "side": {"kind": "uniform", "min": 42, "max": 56}},
                  {"kind": "video", "video": True, "frames": 4,
                   "questions": 2, "block": 14,
                   "side": {"kind": "const", "value": 28}}],
        "user_tokens": {"kind": "uniform", "min": 8, "max": 16},
        "max_tokens": {"kind": "uniform", "min": 4, "max": 8},
    }
    s = traffic.build_sessions(p, 5, 10)
    img, vid = s[0], s[1]
    assert len(img) == 3 and len(vid) == 2
    urls = [[c["image_url"]["url"] for c in b["messages"][0]["content"]
             if c["type"] == "image_url"] for b in img]
    assert urls[0] == urls[1] == urls[2] and len(urls[0]) == 1
    assert vid[0]["video"] is True
    assert sum(c["type"] == "image_url"
               for c in vid[0]["messages"][0]["content"]) == 4
    assert traffic.build_sessions(p, 5, 10) == s


@pytest.mark.parametrize("values,failed,q,want", [
    ([1, 2, 3, 4, 5, 6, 7, 8, 9, 10], 0, 90, 9),
    ([1, 2, 3, 4, 5, 6, 7, 8, 9, 10], 0, 50, 5),
    ([1, 2, 3, 4, 5, 6, 7, 8, 9], 1, 90, 9),      # the failure ranks last
    ([1, 2, 3, 4, 5, 6, 7, 8], 2, 90, math.inf),  # ... and reaches p90
    ([], 0, 90, None),
])
def test_percentile_ranks_failed_requests_as_infinite(values, failed, q, want):
    got = stats.percentile(values, q, failed=failed)
    if want is None:
        assert math.isnan(got)
    else:
        assert got == want


@pytest.mark.parametrize("values,q,failed,want", [
    ([7.0] * 20, 90, 0, 7.0),                  # weights sum to one
    (list(range(1, 101)), 50, 0, 50.5),        # symmetric: the middle
    ([3.0], 90, 0, 3.0),
    ([1.0, 2.0, 3.0], 90, 1, math.inf),        # a failure is +inf
    ([], 90, 2, math.inf),
])
def test_harrell_davis_quantile_by_hand(values, q, failed, want):
    assert stats.quantile_hd(values, q, failed=failed) == pytest.approx(want)


def test_harrell_davis_does_not_hang_on_one_order_statistic():
    # 60 times to first token quantised in engine iterations of 0.3 s;
    # one request slips by one iteration at the p90 rank: the single
    # order statistic jumps a whole quantum, the estimator a fraction.
    base = sorted([0.3 * (1 + i // 6) for i in range(60)])
    slip = list(base)
    slip[53] += 0.3
    rank = stats.percentile(slip, 90) - stats.percentile(base, 90)
    hd = stats.quantile_hd(slip, 90) - stats.quantile_hd(base, 90)
    assert rank == pytest.approx(0.3)
    assert 0 < hd < 0.1 * 0.3 * 1.7
    # and it is still a p90: between p75 and the maximum, above p50
    assert (stats.percentile(base, 75) < stats.quantile_hd(base, 90)
            <= max(base))
    assert stats.quantile_hd(base, 50) < stats.quantile_hd(base, 90)


def test_iqr_share_is_the_contracts_spread():
    vals = [10.0, 11.0, 12.0, 13.0, 14.0, 15.0]
    import statistics

    q1, _, q3 = statistics.quantiles(vals, n=4)
    assert stats.iqr_share(vals) == pytest.approx((q3 - q1) / 12.5)


class _Stalling(http.server.BaseHTTPRequestHandler):
    """Answers every request 0.05 s after it arrives, ONE AT A TIME: a
    server that stalls makes later requests wait in its accept queue."""

    lock = threading.Lock()

    def do_POST(self):
        n = int(self.headers["Content-Length"])
        want = json.loads(self.rfile.read(n))["max_tokens"]
        with self.lock:
            time.sleep(0.05)
        self.send_response(200)
        self.send_header("Content-Type", "text/event-stream")
        self.end_headers()
        for i in range(want):
            ev = {"choices": [{"delta": {"content": f"<{i}>"},
                               "finish_reason": None}]}
            self.wfile.write(b"data: " + json.dumps(ev).encode() + b"\n\n")
        end = {"choices": [{"delta": {}, "finish_reason": "length"}],
               "usage": {"completion_tokens": want, "prompt_tokens": 3}}
        self.wfile.write(b"data: " + json.dumps(end).encode() + b"\n\n")
        self.wfile.write(b"data: [DONE]\n\n")

    def log_message(self, *a):
        pass


@pytest.fixture()
def stalling_server():
    srv = http.server.ThreadingHTTPServer(("127.0.0.1", 0), _Stalling)
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    yield srv.server_address[1]
    srv.shutdown()
    srv.server_close()


def test_ttft_counts_from_the_due_instant_not_from_the_send(stalling_server):
    # 8 requests all due at once into a server that answers one per
    # 50 ms: the last one's first token is >= 0.4 s after it was DUE,
    # though each answer took 50 ms once the server got to it.
    items = loadgen.encode_bodies([
        {"messages": [], "max_tokens": 3, "stream": True} for _ in range(8)
    ])
    res = loadgen.run_open_loop(
        "127.0.0.1", stalling_server, items, [0.0] * 8, 2.5, workers=8)
    recs = res["records"]
    assert len(recs) == 8 and all(r["ok"] for r in recs)
    ttft = sorted(r["t_first"] - r["t_ref"] for r in recs)
    assert ttft[-1] >= 0.35 and ttft[0] < 0.3
    assert all(r["tokens"] == 3 and r["tokens_after_first"] == 2
               for r in recs)
    # lateness: the generator sent on time (its own threads were free)
    assert max(r["t_sent"] - r["t_ref"] for r in recs) < 0.25


def test_a_cut_or_short_answer_counts_as_failed(stalling_server):
    body = {"messages": [], "max_tokens": 3, "stream": True}
    payload = json.dumps(body).encode()
    ok = loadgen.send_stream("127.0.0.1", stalling_server, payload,
                             time.monotonic(), 5.0, 3)
    short = loadgen.send_stream("127.0.0.1", stalling_server, payload,
                                time.monotonic(), 5.0, 5)  # wanted 5, got 3
    refused = loadgen.send_stream("127.0.0.1", 1, payload,
                                  time.monotonic(), 1.0, 3)
    assert ok["ok"] and not short["ok"] and not refused["ok"]
    assert refused["error"]


def test_closed_loop_sends_the_next_request_after_the_reply(stalling_server):
    items = loadgen.encode_bodies([
        {"messages": [], "max_tokens": 2, "stream": True} for _ in range(50)
    ])
    res = loadgen.run_closed_loop(
        "127.0.0.1", stalling_server, [items], 0.5)
    recs = sorted(res["records"], key=lambda r: r["t_sent"])
    assert 3 <= len(recs) <= 11  # one client, one 50 ms answer at a time
    for a, b in zip(recs, recs[1:]):
        assert b["t_sent"] >= a["t_done"]


def test_closed_loop_clients_start_a_fixed_gap_apart(stalling_server):
    items = loadgen.encode_bodies([
        {"messages": [], "max_tokens": 2, "stream": True} for _ in range(50)
    ])
    res = loadgen.run_closed_loop(
        "127.0.0.1", stalling_server, [items[:1]] * 3, 0.6, start_gap_s=0.15)
    first = sorted(r["t_sent"] - res["t0"] for r in res["records"])
    assert len(first) == 3
    assert [round(t / 0.15) for t in first] == [0, 1, 2]


def _rec(t_ref, t_first=None, t_done=None, tokens=0):
    return {"t_ref": t_ref, "t_sent": t_ref, "t_first": t_first,
            "t_last": t_done or t_first, "t_done": t_done,
            "ok": t_done is not None, "tokens": tokens,
            "tokens_after_first": max(0, tokens - 1), "prompt_tokens": 5}


@pytest.mark.parametrize("limit", [None, 20.0])
def test_a_request_still_waiting_at_the_end_counts_with_its_wait(limit):
    # Ten answered requests with a first token after 1 s, and one sent
    # 12 s before the window's end that has nothing yet: it is in the
    # tail with the 12 s it has waited, open loop or closed.
    recs = [_rec(float(i), i + 1.0, i + 2.0, 9) for i in range(10)]
    recs.append(_rec(38.0))
    res = {"t0": 0.0, "t_end": 50.0, "records": recs}
    red = serve.reduce_requests(res, first_token_limit_s=limit)
    assert red["failed"] == 0 and red["ttft_n"] == 11
    assert max(red["ttft_ms"]) == pytest.approx(12000.0)
    assert stats.percentile(red["ttft_ms"], 95) == pytest.approx(12000.0)
    assert red["serve_tok_s"] == pytest.approx(90 / 50.0)
    assert red["attempted"] == 10 and red["waiting_at_end"] == 1


def test_a_wait_over_the_mixes_limit_is_a_failure_and_tops_both_tails():
    recs = [_rec(float(i), i + 1.0, i + 2.0, 9) for i in range(10)]
    recs.append(_rec(25.0))  # 25 s without a first token
    res = {"t0": 0.0, "t_end": 50.0, "records": recs}
    red = serve.reduce_requests(res, first_token_limit_s=20.0)
    assert red["failed"] == 1 and red["overdue"] == 1
    assert red["attempted"] == 11
    assert math.isinf(red["ttft_p90_ms"]) and math.isinf(red["tpot_p90_ms"])
    # a mix that states no limit (an offline batch) ranks it by its wait
    red = serve.reduce_requests(res, first_token_limit_s=None)
    assert red["failed"] == 0 and max(red["ttft_ms"]) == pytest.approx(25e3)


def test_only_poisson_arrivals_exist():
    with pytest.raises(ValueError, match="arrival process"):
        traffic.arrival_offsets({"process": "gamma", "rate": 1.0}, 4,
                                random.Random(0))


def test_a_metric_named_for_a_group_of_cells_reads_the_base_file():
    base = metric_files.load("sched.decode_util")
    group = metric_files.load("sched.decode_util.batch")
    run = {"counters": {"decode_steps_useful": 9.0,
                        "decode_steps_total": 100.0}}
    assert base.read(run) == group.read(run) == pytest.approx(9.0)
    assert base.LAYER == group.LAYER == "scheduler"
    # nothing to read is None (left out of the line), never a made-up 0
    idle = {"counters": {"decode_steps_useful": 0.0,
                         "decode_steps_total": 0.0}}
    assert base.read(idle) is None
    hit = metric_files.load("cache.prefix_hit_share")
    assert hit.read({"counters": {"prefix_cache_hit_tokens_total": 0.0,
                                  "prefill_tokens_total": 50.0}}) == 0.0


# ---- the trace reduction on the recorded file ---------------------------


@pytest.fixture(scope="module")
def reduced():
    planes = trace.parse_xspace(os.path.join(HERE, "data", "small.xplane.pb"))
    return trace.reduce_planes(planes, window_s=1000e-6)


def test_trace_busy_time_is_a_union_not_a_sum(reduced):
    # ops cover [0,100) [200,500) [700,800) us = 500 us of a 1000 us
    # window; summed durations would be 800 us (while.1 holds the
    # kernel and a fusion).
    assert reduced["chips"] == 1
    assert reduced["busy_s"] == pytest.approx(500e-6)
    assert 1 - reduced["busy_s"] / reduced["window_s"] == pytest.approx(0.5)


@pytest.mark.parametrize("table,patterns,seconds,count", [
    ("ops", ("_ragged_paged",), 70e-6, 2),
    ("ops", ("_mha_forward", "_mha_backward"), 250e-6, 1),
    ("ops", ("while",), 0.0, 1),      # all of it is its children's
    ("ops", ("fusion.1",), 180e-6, 3),
    ("modules", ("paged_decode_chunk", "paged_ragged_step"), 200e-6, 2),
    ("modules", ("paged_prefill",), 300e-6, 1),
    ("modules", ("mm_embeds",), 0.0, 0),
])
def test_trace_time_by_name(reduced, table, patterns, seconds, count):
    sec, n = trace.match_seconds(reduced[table], patterns)
    assert sec == pytest.approx(seconds) and n == count


def test_trace_idle_gaps_are_labelled_by_the_innermost_host_span(reduced):
    gaps = dict(reduced["idle_gaps"])
    # [100,200) us lies inside `harvest`; [500,700) only in engine_loop
    assert gaps["harvest"] == pytest.approx(100e-6)
    assert gaps["engine_loop"] == pytest.approx(200e-6)
    b = trace.breakdown(reduced)
    assert b["device_ops"][0] == ["_mha_forward.7", pytest.approx(250e-6)]
    assert sum(v for _, v in b["device_ops"]) == pytest.approx(
        reduced["busy_s"])  # self times add up to the busy time
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10


def test_a_truncated_trace_is_an_error_not_a_number(tmp_path):
    src = os.path.join(HERE, "data", "small.xplane.pb")
    cut = tmp_path / "cut.xplane.pb"
    cut.write_bytes(open(src, "rb").read()[:-7])
    with pytest.raises(ValueError):
        trace.parse_xspace(str(cut))


# ---- operations and bytes, by hand, at Oryx-7B's geometry ---------------

ORYX = {"hidden_size": 3584, "intermediate_size": 18944, "head_dim": 128,
        "num_attention_heads": 28, "num_key_value_heads": 4,
        "vocab_size": 152064, "num_hidden_layers": 1}


def test_matmul_params_of_one_oryx_7b_layer():
    # q 3584x3584, k and v 3584x512 each, o 3584x3584, three 3584x18944
    layer = 2 * 3584 * 3584 + 2 * 3584 * 512 + 3 * 3584 * 18944
    assert layer == 233_046_016
    assert costs.llm_matmul_params(ORYX) == layer + 3584 * 152064


@pytest.mark.parametrize("backward,factor", [(False, 1.0), (True, 3.5)])
def test_causal_attention_flops_at_hq28_d128(backward, factor):
    # T 2048: QK^T and PV are 2*T*T*D each per head, halved by the mask
    fwd = 2 * (2 * 2048 * 2048 * 128) * 28 / 2
    assert fwd == 30_064_771_072
    got = costs.attention_flops_causal([2048], hq=28, d=128,
                                       backward=backward)
    assert got == fwd * factor
    two = costs.attention_flops_causal([2048, 2048], hq=28, d=128, layers=6)
    assert two == 2 * 6 * fwd


@pytest.mark.parametrize("lens,pages", [([64], 1), ([65], 2),
                                        ([2048, 1, 700], 32 + 1 + 11)])
def test_paged_kv_bytes_at_hk4_d128(lens, pages):
    # a page: 64 tokens x 4 kv heads x 128 x 2 B = 65,536 B, K and V
    got = costs.paged_kv_bytes(lens, hk=4, d=128, page_size=64, layers=16)
    assert got == 2 * 16 * pages * 65_536


def test_lora_steps_count_no_weight_gradients_of_the_frozen_base():
    full = costs.train_step_model_flops(ORYX, 8192, [2048] * 4)
    lora = costs.train_step_model_flops({**ORYX, "tune": "lora"}, 8192,
                                        [2048] * 4)
    n = costs.llm_matmul_params(ORYX)
    assert full - lora == 2 * n * 8192
    attn = costs.attention_flops_causal([2048] * 4, hq=28, d=128,
                                        backward=True)
    assert lora == 4 * n * 8192 + attn


def test_vit_flops_by_hand():
    v = {"hidden_size": 1152, "intermediate_size": 4304, "num_layers": 27,
         "num_heads": 16, "head_dim": 72}
    per_layer = 4 * 1152 * 1152 + 2 * 1152 * 4304
    # two images, 1089 and 1936 patches: each attends within itself
    mm = 2 * 27 * per_layer * (1089 + 1936)
    attn = 27 * 2 * 2 * (1089**2 + 1936**2) * 72 * 16
    assert costs.attention_flops_full(
        [1089, 1936], h=16, d=72, layers=27) == attn
    assert costs.vit_flops(v, [1089, 1936], backward=False) == mm + attn
    assert costs.vit_flops(v, [1089, 1936], backward=True) == (
        3 * mm + 3.5 * attn)
    assert costs.vit_flops(v, [], backward=False) == 0
