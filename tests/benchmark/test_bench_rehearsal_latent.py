"""The latent-attention cell of the benchmark, off the chip: its
rehearsal through the harness in a temporary copy, the readers it
brings against a hand-made run, `costs_mla` against bytes counted by
hand, its tokenizer and its tagged sessions, the comparison that
decides `correct` against programs that must fail it, and the
manifest's entries."""

import json
import os
import shutil

import pytest

from benchmark import costs_mla, metric_files
from test_bench_rehearsal_train import LINE_KEYS, ROOT, last_line, run_cell

CELL = "longcat-flash.tool-sessions"
CONFIG = "longcat-flash-ep32-serve"
NEW = ("cache.prefix_hit_share.batch", "kernel.latent_paged_bw",
       "step.decode_weight_bw.batch", "moe.held_hit_share",
       "moe.zero_pair_share")
SHARED = ("sched.decode_util.batch", "sched.ttft_p90_ms.batch",
          "sched.tpot_p90_ms", "step.decode_ms.batch",
          "step.prefill_ms_ktok.batch", "sched.host_ms_per_dispatch.batch",
          "sched.queue_wait_ms.batch", "sched.admission_ms.batch",
          "idle.named_share.batch", "moe.expert_imbalance")
CONF = json.load(open(os.path.join(
    ROOT, "benchmark", "configs", CONFIG + ".json")))


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    root = tmp_path_factory.mktemp("latent") / "checkout"
    shutil.copytree(os.path.join(ROOT, "benchmark"), root / "benchmark",
                    ignore=shutil.ignore_patterns("out", "__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    return str(root)


def test_rehearsal_reports_the_cell_end_to_end(checkout):
    line = last_line(run_cell(checkout, CELL))
    assert LINE_KEYS <= set(line) and line["correct"] is True, line
    assert set(line["metrics"]) == {"serve_tok_s", "setup_s"}
    assert line["metrics"]["serve_tok_s"]["value"] > 0
    assert line["attempted"] >= 3 and line["failed"] == 0
    assert line["rehearsal"] is True and line["device"]["platform"] == "cpu"


def test_rehearsal_traced_line_has_the_counter_metrics(checkout):
    p = run_cell(checkout, CELL, "--trace", "1")
    line = last_line(p)
    assert line["correct"] is True, line["problems"]
    m = line["metrics"]
    # Re-sent histories are found in the prefix cache's latent pages.
    assert 10.0 < m["cache.prefix_hit_share.batch"]["value"] < 100.0
    # 4 of the tiny router's 12 outputs are zero-compute, 4 held.
    assert 15.0 < m["moe.zero_pair_share"]["value"] < 60.0
    assert 0.0 < m["moe.held_hit_share"]["value"] <= 100.0
    assert m["moe.expert_imbalance"]["value"] >= 1.0
    assert 0 < m["sched.decode_util.batch"]["value"] <= 100
    # No device plane on the CPU: the trace readers find nothing.
    assert "kernel.latent_paged_bw" not in m
    assert "step.decode_weight_bw.batch" not in m
    info = json.loads(p.stdout.strip().splitlines()[-2])["info"]
    check = next(e for e in info["setup"]["events"]
                 if e["event"] == "logit_check")
    # Three slots x (the first token's logits + 2 chunks of 4 steps).
    assert check["ok"] and check["positions"] == 3 * 9
    assert check["routing_agree"] == 1.0 and all(check["passed"].values())
    assert set(check["passed"]) == {"forced", "routing", "experts", "timed"}
    assert info["compiles_in_window"] == 0
    assert info["setup"]["histories_sent"] >= 1


def test_a_program_without_the_preset_leaves_at_once(checkout):
    """What the parent commit does with this cell: the child names the
    missing preset and exits before it touches a device."""
    conf = os.path.join(checkout, "benchmark", "configs", CONFIG + ".json")
    saved = open(conf).read()
    try:
        c = json.loads(saved)
        c["rehearse"]["layout"]["preset"] = "no_such_preset"
        open(conf, "w").write(json.dumps(c))
        p = run_cell(checkout, CELL)
    finally:
        open(conf, "w").write(saved)
    assert p.returncode != 0
    log = open(os.path.join(checkout, "benchmark", "out", CELL,
                            "serve_child.log")).read()
    assert "no preset 'no_such_preset'" in log


# A slice of 10 dispatches of 8 steps: 80 steps x 4 layers x 16 held
# expert slots, 10 of 16 hit; 64 lanes of 3,000 tokens a step.
STEPS = 80.0
SLICE = {"moe_held_experts_hit_total": STEPS * 4 * 10,
         "moe_held_expert_slots_total": STEPS * 4 * 16,
         "decode_kv_tokens_total": STEPS * 64 * 3000}
COUNTERS = {"moe_pairs_total": 64 * 12 * 4 * 800.0,
            "moe_zero_pairs_total": 64 * 4 * 4 * 800.0,
            "moe_held_experts_hit_total": 10.0 * 3200,
            "moe_held_expert_slots_total": 16.0 * 3200,
            "moe_expert_rows_max_total": 3.0 * 3200,
            "moe_expert_rows_mean_total": 1.0 * 3200,
            "prefix_cache_hit_tokens_total": 300.0,
            "prefill_tokens_total": 100.0}
RUN = {
    "counters": COUNTERS, "config": CONF,
    "device": {"kind": "TPU v5 lite"},
    "trace": {"modules": {"jit_paged_decode_chunk": [1.6, 10.0],
                          "jit_paged_prefill": [0.5, 8.0]},
              "ops": {"_latent_paged.3": [0.2, 320.0],
                      "_latent_paged.4": [0.2, 320.0],
                      "gmm.26": [0.06, 63.0]},
              "slice_counters": SLICE},
}
LATENT_BYTES = STEPS * 64 * 3000 * 8 * 576 * 2
WEIGHT_BYTES = STEPS * (
    4 * 2 * (90_585_088 + 226_492_416) * 2 + 4 * 6145 * 768 * 4
    + (6144 + 6144 * 16384) * 2) + STEPS * 4 * 10 * 37_748_736 * 2


@pytest.mark.parametrize("name, run, want", [
    ("kernel.latent_paged_bw", RUN, 100 * LATENT_BYTES / 0.4 / 819e9),
    ("kernel.latent_paged_bw",
     dict(RUN, trace=dict(RUN["trace"], ops={"gmm.26": [0.06, 63.0]})), None),
    ("kernel.latent_paged_bw",
     dict(RUN, trace=dict(RUN["trace"], slice_counters={})), None),
    ("step.decode_weight_bw.batch", RUN, 100 * WEIGHT_BYTES / 1.6 / 819e9),
    ("step.decode_weight_bw.batch",
     dict(RUN, trace=dict(RUN["trace"], slice_counters={})), None),
    ("moe.held_hit_share", RUN, 62.5),
    ("moe.held_hit_share", dict(RUN, counters={}), None),
    ("moe.zero_pair_share", RUN, 100 / 3),
    ("moe.zero_pair_share", dict(RUN, counters={}), None),
    ("moe.expert_imbalance", RUN, 3.0),
    ("cache.prefix_hit_share.batch", RUN, 75.0),
])
def test_new_readers_on_a_hand_made_run(name, run, want):
    got = metric_files.load(name).read(run)
    if want is None:
        assert got is None
    else:
        assert got == pytest.approx(want, rel=1e-9)
    assert got is None or got <= 100.0 or name == "moe.expert_imbalance"


def test_costs_mla_against_hand_counted_bytes_and_flops():
    # ISSUE 31's arithmetic, plus the norms: 90.57 M a sublayer.
    assert costs_mla.attention_params(CONF) == (
        6144 * 1536 + 1536 * 12288 + 6144 * 576 + 512 * 16384 + 8192 * 6144
        + 2 * 6144 + 1536 + 512)
    assert costs_mla.dense_ffn_params(CONF) == 3 * 6144 * 12288
    assert costs_mla.expert_params(CONF) == 37_748_736
    assert costs_mla.router_outputs(CONF) == 768
    assert costs_mla.latent_decode_bytes(CONF, kv_tokens=1) == 8 * 1152
    assert costs_mla.latent_decode_flops(CONF, kv_tokens=1) == (
        8 * 64 * 2 * (576 + 512))
    # A step that hits no expert reads 8.4 GB; one that hits every held
    # expert of every layer 4.8 GB more.
    fixed = costs_mla.decode_weight_bytes(CONF, steps=1, held_hit=0)
    assert 5.2e9 < fixed < 5.4e9
    full = costs_mla.decode_weight_bytes(CONF, steps=1, held_hit=64)
    assert full - fixed == 64 * 37_748_736 * 2


def test_manifest_entries_for_the_cell():
    m = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    cell = {w["name"]: w for w in m["workloads"]}[CELL]
    assert cell["chips"] == 1 and cell["config"] == CONFIG
    assert cell["traffic"] == "tool-sessions"
    conf = {c["name"]: c for c in m["configs"]}[CONFIG]
    assert conf["source"] == CONF["source"]
    assert conf["reduced"] == ["num_layers", "n_routed_experts", "vocab_size"]
    assert set(conf["reduced"]) == set(CONF["reduced"])
    by_name = {e["name"]: e for e in m["per_layer"]}
    for name in NEW:
        assert CELL in by_name[name]["workloads"]
        assert by_name[name]["moves"] == "serve_tok_s"
        assert metric_files.load(name).LAYER == by_name[name]["layer"]
    for name in SHARED:
        assert CELL in by_name[name]["workloads"]
        assert by_name[name]["moves"] == "serve_tok_s"
    e2e = {e["name"]: e for e in m["end_to_end"]}
    assert CELL in e2e["serve_tok_s"]["workloads"]
    assert "workloads" not in e2e["setup_s"]
    wl = json.load(open(os.path.join(
        ROOT, "benchmark", "workloads", CELL + ".json")))
    assert wl["config"] == CONFIG and wl["runner"] == "serve_latent"
    t = wl["traffic"]
    # 48: ISSUE 31's pre-agreed fallback from 64 (the file says why).
    assert (t["clients"], t["start_gap_s"], t["turns"]) == (48, 0.05,
                                                            [4, 8, 12])
    assert "ran out of device memory" in t["clients_note"]
    assert t["user_tokens"] == {"kind": "lognormal", "median": 320,
                                "sigma": 0.9, "min": 32, "max": 2048}
    assert t["max_tokens"] == {"kind": "uniform", "min": 192, "max": 384}
    # 64 under max_ctx: the template's newline a message and a decode chunk.
    assert t["max_session_tokens"] == 6080 == CONF["layout"]["max_ctx"] - 64
    assert t["system_tokens"] == 0 and "first_token_limit_s" not in t
    assert t["clients"] == CONF["layout"]["num_slots"]


def test_configuration_file_keeps_every_published_width():
    """The catalog's numbers under the same keys; depth, the vocabulary
    rows held here and (by what is held, not by the router's width) the
    experts are the chip's share."""
    published = {
        "attention_bias": False, "vocab_size": 131072, "hidden_size": 6144,
        "ffn_hidden_size": 12288, "expert_ffn_hidden_size": 2048,
        "num_layers": 28, "num_attention_heads": 64, "kv_lora_rank": 512,
        "q_lora_rank": 1536, "qk_rope_head_dim": 64, "v_head_dim": 128,
        "qk_nope_head_dim": 128, "mla_scale_q_lora": True,
        "mla_scale_kv_lora": True, "routed_scaling_factor": 6,
        "n_routed_experts": 512, "max_position_embeddings": 131072,
        "rms_norm_eps": 1e-05, "rope_theta": 10000000,
        "attention_method": "MLA", "zero_expert_num": 256,
        "zero_expert_type": "identity", "moe_topk": 12,
    }
    differ = {k for k, v in published.items() if CONF.get(k, "absent") != v}
    assert differ == {"num_layers", "vocab_size"}
    assert (CONF["num_layers"], CONF["vocab_size"]) == (4, 16384)
    assert (CONF["source_num_layers"], CONF["source_vocab_size"]) == (
        28, 131072)
    assert CONF["experts_held"] == 16 and CONF["chips_sharing_a_layer"] == 32
    for key in ("mla_scale_q_lora", "mla_scale_kv_lora", "norm_topk_prob",
                "router_bias", "rope_interleaved", "eos_token_id"):
        assert key in CONF["assumed"]
    assert "32 chips" in CONF["stands_for"]


def test_child_refuses_a_geometry_the_program_would_not_run():
    from benchmark.runners import serve_latent_child as child

    cfg = child.build_config(CONF)
    assert (cfg.llm.num_layers, cfg.llm.held, cfg.llm.vocab_size) == (
        4, (0, 16), 16384)
    assert cfg.vision is None and cfg.attn_impl == "pallas"
    for key, bad in (("kv_lora_rank", 256), ("expert_ffn_hidden_size", 1024),
                     ("zero_expert_num", 0), ("moe_topk", 8),
                     ("experts_held", 8), ("hidden_size", 4096),
                     ("routed_scaling_factor", 1), ("ffn_hidden_size", 8192)):
        with pytest.raises(SystemExit, match=key):
            child.build_config(dict(CONF, **{key: bad}))


def test_the_childs_tokenizer_keeps_a_prefix_and_tells_sessions_apart():
    """One id per character; ids spread over 3..hi-1; an id depends on
    the first 16 characters and on the text up to its character, so a
    re-sent history encodes to the same ids; two sessions that differ
    only in their tag differ from the first id on."""
    import random

    from benchmark import traffic
    from benchmark.runners import serve_latent_child as child

    tok = child.PrefixTokenizer(16384)
    text = traffic.text_of(random.Random(7), 400)
    a, b = "A" * 16 + text, "B" * 16 + text
    ids = tok.encode(a)
    assert len(ids) == len(a) and min(ids) >= 3 and max(ids) < 16384
    assert len(set(ids)) > 350  # spread, where the text has 27 code points
    longer = tok.encode(a + "\nand a reply\nand the next turn")
    assert longer[:len(ids)] == ids
    other = tok.encode(b)
    assert sum(x == y for x, y in zip(ids, other)) <= 2
    assert tok.decode([5, 17]) == "<5><17>"


def test_tagged_sessions_keep_the_lengths_and_open_apart():
    from benchmark import traffic
    from benchmark.runners import serve_latent

    wl = json.load(open(os.path.join(
        ROOT, "benchmark", "workloads", CELL + ".json")))["traffic"]
    plain = traffic.build_sessions(wl, 11, 60)
    tagged = serve_latent.tagged_sessions(wl, 11, 60)
    assert tagged == serve_latent.tagged_sessions(wl, 11, 60)
    heads = set()
    assert [len(s) for s in plain] == [len(s) for s in tagged]
    for before, after in zip(plain, tagged):
        first = after[0]["messages"][0]["content"]
        heads.add(first[:16])
        for b, a in zip(before, after):
            assert [len(m["content"]) for m in b["messages"]] == [
                len(m["content"]) for m in a["messages"]]
            assert a["messages"][0]["content"] == first
            assert a["messages"][0]["content"][16:] == \
                b["messages"][0]["content"][16:]
            total = sum(len(m["content"]) for m in a["messages"])
            assert total + a["max_tokens"] + len(a["messages"]) + 8 <= 6144
            assert 192 <= a["max_tokens"] <= 384
        # A later turn re-sends the earlier ones.
        assert after[-1]["messages"][:len(after[0]["messages"])] == \
            after[0]["messages"]
    assert len(heads) == len(tagged)
    assert {len(s) for s in tagged} <= set(range(1, 13))
    assert serve_latent.tagged_sessions(wl, 12, 60) != tagged


def test_every_seed_opens_the_window_on_the_same_turns_with_histories_cached():
    """Client i of 48 starts i/48 of the way through the list the
    harness deals it, at every seed: the window serves the same lengths
    whatever the seed (which makes the words), at every depth of a
    session; a client that opens on a later turn sends the turn before
    it in set-up, so what its first request re-sends is in the prefix
    cache up to that turn's user text."""
    from benchmark import traffic
    from benchmark.runners import serve_latent

    wl = json.load(open(os.path.join(
        ROOT, "benchmark", "workloads", CELL + ".json")))["traffic"]
    assert wl["warm_previous_turn"] is True and wl["clients"] == 48
    shapes = []
    for seed in (5, 2**31 + 11):
        window, before = serve_latent.client_lists(wl, seed, 50)
        per_client = [[] for _ in range(48)]
        for i, s in enumerate(serve_latent.tagged_sessions(wl, seed, 1200)):
            per_client[i % 48].extend(s)
        assert window == [traffic.rotated(c, i * len(c) // 48)
                          for i, c in enumerate(per_client)]
        later = [w[0] for w in window if len(w[0]["messages"]) > 1]
        assert len(before) == len(later) > 24
        for first, prev in zip(later, before):
            assert prev["max_tokens"] == 8
            # The turn before: the same messages less the last reply
            # and the new user turn.
            assert prev["messages"] == first["messages"][:-2]
        # The window serves turns at every depth, up to the 6k limit.
        sent = [b for w in window for b in w[:4]]
        depth = [sum(len(m["content"]) + 1 for m in b["messages"])
                 for b in sent]
        assert max(depth) > 5000 and all(
            d + b["max_tokens"] + 8 <= 6144 for d, b in zip(depth, sent))
        assert {(len(w[0]["messages"]) + 1) // 2 for w in window} >= set(
            range(1, 8))
        assert max(len(b["messages"]) for b in sent) >= 15
        shapes.append([
            ([len(m["content"]) for m in b["messages"]], b["max_tokens"])
            for w in window for b in w])
    assert shapes[0] == shapes[1]
    # Without the key nothing is sent ahead of the window.
    assert serve_latent.client_lists(
        dict(wl, warm_previous_turn=False), 5, 50)[1] == []


@pytest.fixture(scope="module")
def readings():
    import jax

    from oryx_tpu import config as cfg_lib
    from oryx_tpu.models import oryx

    from benchmark.tools import controls_longcat

    cfg = cfg_lib.longcat_tiny()
    params = oryx.init_params(cfg, jax.random.key(3))
    return controls_longcat.run_all(
        params, cfg, 2147483659, page_size=16, prefill_chunk=32,
        decode_chunk=4, prompt_tokens=(70, 33, 9))


def test_the_comparison_passes_the_program_as_it_is(readings):
    r = readings["as served"]
    assert r["ok"] and r["routing_agree"] == 1.0
    assert r["forced_rms_rel"] < 1e-5 and r["positions"] == 27
    assert r["timed_token_agree"] == 1.0


@pytest.mark.parametrize("control, clause", [
    ("int8 activations in the grouped products", "experts"),
    ("the zero-compute term left out", "forced"),
    ("sqrt(hidden / kv_lora_rank) left out", "forced"),
    ("a decode that walks another slot's pages", "forced"),
    ("a dispatched program that is not the compared one", "timed"),
])
def test_the_comparison_fails_a_wrong_program(readings, control, clause):
    r = readings[control]
    assert not r["ok"] and not r["passed"][clause]


def test_a_lower_precision_of_the_latent_reads_above_the_program(readings):
    """In float32 at the tiny size fp8 latents read far above the
    program as it is; whether they read over the LIMIT is a matter of
    the published widths in bf16, on the chip (PERF.md section 6). The
    grouped products' precision shows in the expert layer alone and
    not in the logits (one pair in a few is live)."""
    served = readings["as served"]
    assert readings["the latent stored in fp8"]["forced_rms_rel"] > \
        1000 * served["forced_rms_rel"]
    int8 = readings["int8 activations in the grouped products"]
    assert int8["passed"]["forced"] and not int8["passed"]["experts"]
    assert served["expert_rms_rel"] < 1e-5
