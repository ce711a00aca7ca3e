"""The long-document cell of the benchmark (Mistral-Small-4), off the
chip: its rehearsal through the harness in a temporary copy, the readers
it brings against a hand-made run, `costs_mla_single` against bytes and
operations counted by hand, its session builder, the comparison that
decides `correct` against programs that must read above the program as
it is, and the manifest's entries (looked up by name: a later PR appends
after them). The chipless compile that holds the configuration's 0.5 GB
rule is in tests/test_pallas_topology_compile.py, the one file that may
load the TPU's compiler."""

import json
import os
import shutil

import pytest

from benchmark import costs_mla_single, metric_files
from test_bench_rehearsal_train import LINE_KEYS, ROOT, last_line, run_cell

CELL = "mistral-small-4.doc-qa"
CONFIG = "mistral-small-4-ep4-serve"
NEW = ("kernel.latent_paged_bw.single", "step.decode_weight_bw.single",
       "step.prefill_flops", "kernel.moe_gmm_bw.held",
       "step.prefill_live_share")
SHARED = ("sched.decode_util.batch", "sched.ttft_p90_ms.batch",
          "sched.tpot_p90_ms", "step.decode_ms.batch",
          "step.prefill_ms_ktok.batch", "sched.host_ms_per_dispatch.batch",
          "sched.queue_wait_ms.batch", "sched.admission_ms.batch",
          "idle.named_share.batch", "moe.expert_imbalance",
          "moe.held_hit_share", "cache.prefix_hit_share.batch")
NOT_THIS_MODELS = ("kernel.latent_paged_bw", "step.decode_weight_bw.batch",
                   "moe.zero_pair_share")
CONF = json.load(open(os.path.join(
    ROOT, "benchmark", "configs", CONFIG + ".json")))
WL = json.load(open(os.path.join(
    ROOT, "benchmark", "workloads", CELL + ".json")))


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    root = tmp_path_factory.mktemp("docqa") / "checkout"
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
    # Documents re-sent with every question come from the prefix cache.
    assert 30.0 < m["cache.prefix_hit_share.batch"]["value"] < 100.0
    assert 0.0 < m["moe.held_hit_share"]["value"] <= 100.0
    assert m["moe.expert_imbalance"]["value"] >= 1.0
    assert 0 < m["sched.decode_util.batch"]["value"] <= 100
    # The rehearsal's context is one table width: a chunk of 32 against
    # 512 positions needs a few per cent of what it is handed.
    assert 0.0 < m["step.prefill_live_share"]["value"] < 100.0
    # No device plane on the CPU: the trace readers find nothing, and
    # the readers of LongCat's costs are not this cell's.
    for name in NEW[:4] + NOT_THIS_MODELS:
        assert name not in m
    info = json.loads(p.stdout.strip().splitlines()[-2])["info"]
    # `correct` came after the window, from what it served: no event of
    # set-up is the comparison, and its seconds are not in `setup_s`.
    assert all(e["event"] != "logit_check" for e in info["setup"]["events"])
    check = info["setup"]["check_after_window"]
    kinds = [w["kind"] for w in check["sample"]]
    assert "cached" in kinds and len(kinds) == check["slots"] >= 2
    assert check["finished_in_window"] >= line["attempted"] - 4
    # A slot: the first token's logits + 2 chunks of 4 steps; every
    # served token against the free reference.
    assert check["ok"] and check["positions"] == check["slots"] * 9
    assert check["served_tokens"] == sum(
        w["served_tokens"] for w in check["sample"]) >= 20
    assert check["served_ref_agree"] == check["served_twin_agree"] == 1.0
    assert check["served_ref_agree_swapped"] < 0.5
    assert check["routing_agree"] == 1.0 and all(check["passed"].values())
    assert set(check["passed"]) == {"forced", "routing", "experts", "served"}
    assert info["compiles_in_window"] == 0
    assert info["setup"]["histories_sent"] >= 1


class _Handle:
    def __init__(self, reply, finish="length", done=True, error=None):
        import threading

        self.done = threading.Event()
        if done:
            self.done.set()
        self.reply, self.finish_reason, self.error = reply, finish, error
        self.cancelled = False


class _Engine:
    def submit(self, request, max_new, sampling=None, **kw):
        return request["handle"]


class _Pipe:
    def _prepare_request(self, request):
        return list(range(request["prompt"])), [], [], []


def _request(prompt, out, *, history=False, **handle):
    reply = "".join(f"<{7 + i}>" for i in range(out))
    return ({"prompt": prompt, "history": ["turn"] if history else [],
             "handle": _Handle(reply, **handle)}, out)


def test_only_what_the_window_finished_is_sampled_shortest_of_each_kind():
    """`Served` keeps what is submitted between `arm` and `disarm`;
    `sample_served` takes the shortest finished request of each kind
    while the positions fit, and leaves out what was cut, failed,
    stopped early or is too short for the twin."""
    from benchmark.runners.serve_docqa_child import Served, sample_served

    engine = _Engine()
    lines = iter(["arm\n", "trace_start\n", "disarm\n", "stop\n"])
    served = Served(engine, lines)
    commands = iter(served)
    engine.submit(*_request(300, 20))  # warm-up: before `arm`
    assert next(commands) == "arm\n" and served.armed
    window = [
        _request(900, 20), _request(700, 20),  # cold, over `long`
        _request(650, 20, done=False),  # still in flight at the end
        _request(640, 20, error="boom"), _request(630, 20, finish="stop"),
        _request(620, 9),  # too short for the twin's 16 steps
        _request(800, 30, history=True), _request(750, 25, history=True),
        _request(200, 20, history=True), _request(150, 20, history=True),
        _request(100, 20), _request(90, 20),
    ]
    for r in window:
        engine.submit(*r)
    assert next(commands) == "trace_start\n" and served.armed
    assert next(commands) == "disarm\n"
    assert not served.armed and served.window_closed
    engine.submit(*_request(610, 20))  # after the window
    assert len(served.items) == len(window)
    pick = lambda **kw: sample_served(  # noqa: E731
        served, _Pipe(), long_prompt=512, min_tokens=17, **kw)
    prompts, streams, what = pick(max_positions=10_000)
    assert [(w["kind"], w["prompt_tokens"], w["served_tokens"])
            for w in what] == [("cold_long", 700, 20), ("cached_long", 750, 25),
                               ("cached", 150, 20), ("cold", 90, 20)]
    assert [len(x) for x in prompts] == [700, 750, 150, 90]
    assert streams[1] == list(range(7, 32))
    # A budget that the long follow-up does not fit: the rest still go.
    assert [w["kind"] for w in pick(max_positions=1_000)[2]] == [
        "cold_long", "cached", "cold"]


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


# A slice of 10 decode dispatches of 8 steps: 80 steps x 6 layers x 32
# held expert slots, 5 of 32 hit; 5 lanes of 16,000 tokens a step. And
# 20 prefill chunks of 1024 at a live prefix of 10,240 on average, 30 %
# of their picks on a held expert, every held expert hit a chunk's layer.
STEPS = 80.0
PAIRS = 20 * 1024 * 9_728.5
SLICE = {"moe_held_experts_hit_total": STEPS * 6 * 5,
         "moe_held_expert_slots_total": STEPS * 6 * 32,
         "decode_kv_tokens_total": STEPS * 5 * 16000,
         "prefill_tokens_total": 20 * 1024.0,
         "prefill_attn_pairs_total": PAIRS,
         "prefill_live_positions_total": 20 * 10_240.0,
         "moe_prefill_pairs_total": 20 * 1024 * 6 * 4.0,
         "moe_prefill_held_rows_total": 20 * 1024 * 6 * 4 * 0.3,
         "moe_prefill_held_experts_hit_total": 20 * 6 * 32.0,
         "moe_prefill_held_expert_slots_total": 20 * 6 * 32.0}
COUNTERS = {"moe_held_experts_hit_total": 5.0 * 4800,
            "moe_held_expert_slots_total": 32.0 * 4800,
            "moe_expert_rows_max_total": 2.0 * 4800,
            "moe_expert_rows_mean_total": 0.5 * 4800,
            "prefix_cache_hit_tokens_total": 300.0,
            "prefill_tokens_total": 100.0,
            "prefill_attn_pairs_total": 3.0e9,
            "prefill_table_positions_total": 8.0e9}
RUN = {
    "counters": COUNTERS, "config": CONF,
    "device": {"kind": "TPU v5 lite"},
    "trace": {"modules": {"jit_paged_decode_chunk": [0.4, 10.0],
                          "jit_paged_prefill": [0.9, 12.0],
                          "jit_paged_prefill.1": [0.3, 8.0]},
              "ops": {"_latent_paged.3": [0.04, 480.0],
                      "gmm.26": [0.6, 63.0]},
              "slice_counters": SLICE},
}
ATTN = 4096 * 1024 + 1024 * 4096 + 4096 * 320 + 256 * 32 * 192 + 4096 * 4096
EXPERT = 3 * 4096 * 2048
LATENT_BYTES = STEPS * 5 * 16000 * 6 * 320 * 2
WEIGHT_BYTES = STEPS * (
    6 * (ATTN + 2 * 4096 + 1024 + 256 + EXPERT) * 2 + 6 * 4096 * 128 * 4
    + (4096 + 4096 * 32768) * 2) + STEPS * 6 * 5 * EXPERT * 2
PER_TOKEN = (4096 * 1024 + 1024 * 4096 + 4096 * 320 + 4096 * 4096
             + 4096 * 128 + EXPERT)
PREFILL_FLOPS = {
    held: 2 * 6 * (20 * 1024 * (PER_TOKEN + 4 * held * EXPERT)
                   + PAIRS * 32 * 256 + 20 * 10_240 * 256 * 32 * 192)
    for held in (0.3, 0.25)}
GMM_BYTES = (STEPS * 6 * 5 + 20 * 6 * 32) * EXPERT * 2


def _without(key):
    sc = {k: v for k, v in SLICE.items() if k != key}
    return dict(RUN, trace=dict(RUN["trace"], slice_counters=sc))


@pytest.mark.parametrize("name, run, want", [
    ("kernel.latent_paged_bw.single", RUN,
     100 * LATENT_BYTES / 0.04 / 819e9),
    ("kernel.latent_paged_bw.single",
     dict(RUN, trace=dict(RUN["trace"], ops={"gmm.26": [0.6, 63.0]})), None),
    ("kernel.latent_paged_bw.single", _without("decode_kv_tokens_total"),
     None),
    ("kernel.latent_paged_bw.single", dict(RUN, trace={}), None),
    ("step.decode_weight_bw.single", RUN, 100 * WEIGHT_BYTES / 0.4 / 819e9),
    ("step.decode_weight_bw.single",
     _without("moe_held_expert_slots_total"), None),
    ("step.decode_weight_bw.single", dict(RUN, trace={}), None),
    ("step.prefill_flops", RUN, 100 * PREFILL_FLOPS[0.3] / 1.2 / 197e12),
    # A program without the counters: uniform routing's 32 / 128.
    ("step.prefill_flops", _without("moe_prefill_pairs_total"),
     100 * PREFILL_FLOPS[0.25] / 1.2 / 197e12),
    ("kernel.moe_gmm_bw.held", RUN, 100 * GMM_BYTES / 0.6 / 819e9),
    ("kernel.moe_gmm_bw.held",
     dict(RUN, trace=dict(RUN["trace"], ops={"_latent_paged.3": [0.04, 480.0]})),
     None),  # XLA's ragged-dot: no such kernel
    ("kernel.moe_gmm_bw.held",
     _without("moe_prefill_held_experts_hit_total"), None),
    ("kernel.moe_gmm_bw.held", dict(RUN, trace={}), None),
    # A program from before PR 33 has no such counters: nothing to read.
    ("step.prefill_flops", _without("prefill_attn_pairs_total"), None),
    ("step.prefill_flops", _without("prefill_live_positions_total"), None),
    ("step.prefill_flops", dict(RUN, trace={}), None),
    ("step.prefill_live_share", RUN, 37.5),
    ("step.prefill_live_share", dict(RUN, counters={}), None),
    ("step.prefill_live_share",
     dict(RUN, counters={"prefill_attn_pairs_total": 5.0}), None),
    ("moe.held_hit_share", RUN, 100 * 5 / 32),
    ("moe.expert_imbalance", RUN, 4.0),
    ("cache.prefix_hit_share.batch", RUN, 75.0),
])
def test_new_readers_on_a_hand_made_run(name, run, want):
    got = metric_files.load(name).read(run)
    if want is None:
        assert got is None
    else:
        assert got == pytest.approx(want, rel=1e-9)
    assert got is None or 0 < got <= 100.0 or name == "moe.expert_imbalance"


def test_every_new_metric_has_a_reader_file_of_its_own():
    """`metric_files.load` falls back to the name without its last part,
    which for `kernel.latent_paged_bw.single` would be LongCat's cost
    file."""
    for name in NEW:
        assert os.path.exists(os.path.join(metric_files.DIR, name + ".py"))
        mod = metric_files.load(name)
        assert "costs_mla " not in open(mod.__spec__.origin).read()


def test_costs_mla_single_against_hand_counted_bytes_and_flops():
    c = costs_mla_single
    assert c.attention_params(CONF) == ATTN + 2 * 4096 + 1024 + 256
    assert c.expert_params(CONF) == EXPERT == 25_165_824
    assert c.shared_params(CONF) == EXPERT
    assert c.cache_layers(CONF) == 6 and c.latent_row_values(CONF) == 320
    assert c.latent_decode_bytes(CONF, kv_tokens=1) == 6 * 640
    assert c.latent_decode_flops(CONF, kv_tokens=1) == (
        6 * 32 * 2 * (320 + 256))
    # A step that hits no expert reads 0.91 GB (0.64 of layers, 0.27 of
    # the head's slice); every held expert of every layer 9.66 GB more.
    fixed = c.decode_weight_bytes(CONF, steps=1, held_hit=0)
    assert 0.90e9 < fixed < 0.93e9
    full = c.decode_weight_bytes(CONF, steps=1, held_hit=6 * 32)
    assert full - fixed == 6 * 32 * EXPERT * 2
    # One token against nothing: its matmuls, one pair, one position.
    one = c.prefill_flops(CONF, tokens=1, attn_pairs=1, live_positions=1,
                          held_share=0.25)
    assert one == 2 * 6 * (PER_TOKEN + 4 * 0.25 * EXPERT + 32 * 256
                           + 256 * 32 * 192)
    assert c.held_expert_bytes(CONF, held_hit=3) == 3 * EXPERT * 2
    # LongCat's cost file would raise on this file's keys or count two
    # cache layers a model layer.
    from benchmark import costs_mla
    with pytest.raises(KeyError):
        costs_mla.cache_layers(CONF)


def test_manifest_entries_for_the_cell():
    m = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    cell = next(w for w in m["workloads"] if w["name"] == CELL)
    assert cell["chips"] == 1 and cell["config"] == CONFIG
    assert cell["traffic"] == "doc-qa" and len(cell["why"]) <= 200
    conf = next(c for c in m["configs"] if c["name"] == CONFIG)
    assert conf["source"] == CONF["source"] and len(conf["why"]) <= 200
    assert conf["file"] == "benchmark/configs/" + CONFIG + ".json"
    assert conf["reduced"] == ["num_hidden_layers", "n_routed_experts",
                               "vocab_size"]
    assert set(conf["reduced"]) == set(CONF["reduced"])
    by_name = {e["name"]: e for e in m["per_layer"]}
    for name in NEW:
        e = by_name[name]
        assert e["workloads"] == [CELL] and e["moves"] == "serve_tok_s"
        assert (e["unit"], e["better"]) == ("%", "higher")
        assert metric_files.load(name).LAYER == e["layer"]
    for name in SHARED:
        assert CELL in by_name[name]["workloads"]
        assert by_name[name]["moves"] == "serve_tok_s"
    for name in NOT_THIS_MODELS:
        assert CELL not in by_name[name]["workloads"]
    e2e = {e["name"]: e for e in m["end_to_end"]}
    assert CELL in e2e["serve_tok_s"]["workloads"]
    assert "workloads" not in e2e["setup_s"]
    assert WL["config"] == CONFIG and WL["runner"] == "serve_docqa"
    t = WL["traffic"]
    assert (t["clients"], t["start_gap_s"], t["questions"]) == (
        16, 0.05, [3, 4, 5])
    assert t["document_tokens"] == {
        "kind": "lognormal", "median": 14000, "sigma": 0.45, "min": 8192,
        "max": 30000}
    assert t["question_tokens"] == {
        "kind": "lognormal", "median": 96, "sigma": 0.7, "min": 32,
        "max": 256}
    assert t["max_tokens"] == {"kind": "uniform", "min": 96, "max": 224}
    assert "first_token_limit_s" not in t and t["warm_previous_turn"] is True
    lay = CONF["layout"]
    assert t["clients"] == lay["num_slots"] == 16
    assert (lay["max_ctx"], lay["page_size"], lay["prefill_chunk"],
            lay["decode_chunk"], lay["prefix_cache"], lay["kv_dtype"]) == (
        32768, 64, 1024, 8, True, "bf16")
    assert t["max_session_tokens"] < lay["max_ctx"]


def test_configuration_file_keeps_every_published_number():
    """The catalog's `config` under the same keys; depth and the
    vocabulary rows held here are the chip's share, and the experts are
    cut by what is held, not by the router's width."""
    published = {
        "attention_bias": False, "first_k_dense_replace": 0, "head_dim": 128,
        "hidden_act": "silu", "hidden_size": 4096, "intermediate_size": 12288,
        "kv_lora_rank": 256, "max_position_embeddings": 1048576,
        "mlp_bias": False, "model_type": "mistral4",
        "moe_intermediate_size": 2048, "n_group": 1, "n_routed_experts": 128,
        "n_shared_experts": 1, "norm_topk_prob": True,
        "num_attention_heads": 32, "num_experts_per_tok": 4,
        "num_hidden_layers": 36, "num_key_value_heads": 32,
        "q_lora_rank": 1024, "qk_head_dim": 128, "qk_nope_head_dim": 64,
        "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06, "rope_interleave": True,
        "rope_parameters": {
            "beta_fast": 32, "beta_slow": 1, "factor": 128,
            "llama_4_scaling_beta": 0.1, "mscale": 1, "mscale_all_dim": 1,
            "original_max_position_embeddings": 8192, "rope_theta": 10000,
            "rope_type": "yarn", "type": "yarn"},
        "routed_scaling_factor": 1, "sliding_window": None,
        "tie_word_embeddings": False, "topk_group": 1, "v_head_dim": 128,
        "vocab_size": 131072,
    }
    differ = {k for k, v in published.items() if CONF.get(k, "absent") != v}
    assert differ == {"num_hidden_layers", "vocab_size"}
    assert (CONF["num_hidden_layers"], CONF["vocab_size"]) == (6, 32768)
    assert (CONF["source_num_hidden_layers"], CONF["source_vocab_size"]) == (
        36, 131072)
    assert CONF["experts_held"] == 32 and CONF["chips_sharing_a_layer"] == 4
    for key in ("router_scoring", "softmax_scale", "llama_4_scaling_beta",
                "eos_token_id", "max_position_embeddings"):
        assert key in CONF["assumed"]
    assert "intermediate_size" in CONF["unused"]
    assert "4 chips" in CONF["stands_for"]
    assert "No vision tower" in CONF["stands_for"]


def test_child_refuses_a_geometry_the_program_would_not_run():
    from benchmark.runners import serve_docqa_child as child

    cfg = child.build_config(CONF)
    assert (cfg.llm.num_layers, cfg.llm.held, cfg.llm.vocab_size) == (
        6, (0, 32), 32768)
    assert cfg.vision is None and cfg.attn_impl == "pallas"
    assert cfg.llm.n_shared_experts == 1 and cfg.llm.yarn
    for key, bad in (("kv_lora_rank", 512), ("moe_intermediate_size", 1024),
                     ("n_shared_experts", 0), ("num_experts_per_tok", 8),
                     ("experts_held", 16), ("hidden_size", 6144),
                     ("norm_topk_prob", False), ("q_lora_rank", 1536)):
        with pytest.raises(SystemExit, match=key):
            child.build_config(dict(CONF, **{key: bad}))
    for key, bad in (("factor", 32), ("llama_4_scaling_beta", 0.0),
                     ("original_max_position_embeddings", 4096)):
        rope = dict(CONF["rope_parameters"], **{key: bad})
        with pytest.raises(SystemExit, match=key):
            child.build_config(dict(CONF, rope_parameters=rope))


def test_every_seed_sends_the_same_lengths_from_the_same_places():
    """16 clients, six sessions each; client i starts i/16 of the way
    through its list at every seed; about three in four open on a later
    question and send the request before it in set-up, so their
    document is cached when the window opens."""
    from benchmark import traffic
    from benchmark.runners import serve_docqa

    t = WL["traffic"]
    shapes, firsts = [], []
    for seed in (5, 2**31 + 11):
        sessions = serve_docqa.doc_sessions(t, seed)
        assert len(sessions) == 96
        assert sorted({len(s) for s in sessions}) == [3, 4, 5]
        window, before = serve_docqa.client_lists(t, seed)
        per_client = [[] for _ in range(16)]
        for i, s in enumerate(sessions):
            per_client[i % 16].extend(s)
        assert window == [traffic.rotated(c, i * len(c) // 16)
                          for i, c in enumerate(per_client)]
        later = [w[0] for w in window if len(w[0]["messages"]) > 1]
        assert len(before) == len(later) and 10 <= len(later) <= 14
        for first, prev in zip(later, before):
            assert prev["max_tokens"] == 8
            # The request before: the same messages less the last reply
            # and the new question, so the document's pages are cached.
            assert prev["messages"] == first["messages"][:-2]
        for s in sessions:
            doc = s[0]["messages"][0]["content"]
            assert all(b["messages"][0]["content"] == doc for b in s)
            n = doc.index("\n")
            assert 8192 <= n <= 30000 and "\n" not in doc[:n]
            last = s[-1]
            total = sum(len(m["content"]) + 1 for m in last["messages"])
            assert total + last["max_tokens"] + 8 <= 32768
            for b in s:
                assert 96 <= b["max_tokens"] <= 224
                assert 32 <= len(b["messages"][-1]["content"].split("\n")[-1]
                                 ) <= 256
        docs = sorted(s[0]["messages"][0]["content"].index("\n")
                      for s in sessions)
        assert 13000 < docs[len(docs) // 2] < 15000
        # A client's list holds three times what 50 s serve and more.
        assert min(len(w) for w in window) >= 23
        shapes.append([
            ([len(m["content"]) for m in b["messages"]], b["max_tokens"])
            for w in window for b in w])
        firsts.append(sessions[0][0]["messages"][0]["content"][:32])
    assert shapes[0] == shapes[1] and firsts[0] != firsts[1]
    tags = {s[0]["messages"][0]["content"][:16]
            for s in serve_docqa.doc_sessions(t, 5)}
    assert len(tags) == 96
    # About three quarters of what the window's first asks send is cached.
    sent = cached = 0
    for w in window:
        for b in w[:5]:
            n = sum(len(m["content"]) + 1 for m in b["messages"])
            sent += n
            if len(b["messages"]) > 1:
                cached += sum(len(m["content"]) + 1
                              for m in b["messages"][:-2])
    assert 0.65 < cached / sent < 0.85
    assert serve_docqa.client_lists(
        dict(t, warm_previous_turn=False), 5)[1] == []


def test_warm_up_reaches_every_table_width():
    """One prompt inside each embed bucket up to the longest session:
    the longest is prefilled through tables of 8k, 16k and 32k
    positions, so every `paged_prefill` the window runs is compiled in
    set-up."""
    from oryx_tpu.ops import packing
    from oryx_tpu.serve import scheduler

    from benchmark.runners import serve_docqa

    bodies = serve_docqa.warmup_bodies(
        WL["traffic"], packing.DEFAULT_BUCKETS, 7)
    lens = [len(b["messages"][0]["content"]) for b in bodies]
    assert max(lens) > 16384 + 1024 and max(lens) + 8 < 32768
    widths = scheduler.prefill_table_buckets(32768 // 64, 64)
    assert widths == (128, 256, 512)
    reached = {next(w for w in widths if w * 64 >= off + 1024)
               for off in range(0, max(lens), 1024)}
    assert reached == set(widths)


@pytest.fixture(scope="module")
def readings():
    import jax

    from oryx_tpu import config as cfg_lib
    from oryx_tpu.models import oryx

    from benchmark.tools import controls_mistral4

    cfg = cfg_lib.mistral4_tiny()
    params = oryx.init_params(cfg, jax.random.key(3))
    return controls_mistral4.run_all(
        params, cfg, 2147483659, page_size=16, prefill_chunk=32,
        decode_chunk=4, max_ctx=512, prompt_tokens=(70, 33, 9))


def test_the_comparison_passes_the_program_as_it_is(readings):
    r = readings["as served"]
    assert r["ok"] and r["routing_agree"] == 1.0
    assert r["forced_rms_rel"] < 1e-5 and r["positions"] == 27
    assert r["served_twin_agree"] == r["served_ref_agree"] == 1.0
    assert r["served_tokens"] == 27 and r["expert_rms_rel"] < 1e-5


@pytest.mark.parametrize("control, clause", [
    ("the shared expert left out", "forced"),
    ("the shared expert left out", "experts"),
    ("a decode that walks another slot's pages", "forced"),
    ("a dispatched program that is not the compared one", "served"),
])
def test_the_comparison_fails_a_wrong_program(readings, control, clause):
    r = readings[control]
    assert not r["ok"] and not r["passed"][clause]


@pytest.mark.parametrize("control", [
    "YaRN replaced by plain RoPE", "m * m left out of the softmax scale",
    "the query's scale by position left out", "the latent stored in fp8",
    "int8 activations in the grouped products",
])
def test_a_one_line_fault_reads_far_above_the_program(readings, control):
    """In float32 at the tiny size each of these reads a thousand times
    the program as it is or more; whether it reads over the LIMIT is a
    matter of the published widths in bf16, on the chip (PERF.md
    section 6, PR 33)."""
    served = readings["as served"]
    r = readings[control]
    worse = max(r["forced_rms_rel"] / served["forced_rms_rel"],
                r["expert_rms_rel"] / served["expert_rms_rel"])
    assert worse > 1000
