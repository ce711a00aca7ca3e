"""The mixed-queue cell of the benchmark, off the chip: its rehearsal
through the harness in a temporary copy (correct, decided after the
window on what it served, a long request past the tiny window among
the sample), the five readers it brings against a hand-made run and
over a run without their counter, `costs_smallthinker` against counts
by hand, the manifest's entries looked up BY NAME, the configuration
file against the catalog's keys, the traffic's one fixed order, and
the controls failing by the clauses they must."""

import json
import os
import shutil

import pytest

from benchmark import costs_smallthinker as costs
from benchmark import metric_files
from test_bench_rehearsal_train import (
    LINE_KEYS, ROOT, info_line, last_line, run_cell,
)

CELL = "smallthinker-21b-a3b.mixed-queue"
CONFIG = "smallthinker-21b-a3b-serve"
NEW = ("kernel.ragged_paged_bw.window", "kernel.flash_window_flops",
       "kernel.moe_gmm_bw.whole", "step.decode_mixed_bw",
       "attn.window_read_share")
SHARED = ("sched.decode_util.batch", "sched.ttft_p90_ms.batch",
          "sched.tpot_p90_ms", "step.decode_ms.batch",
          "step.prefill_ms_ktok.batch", "sched.host_ms_per_dispatch.batch",
          "sched.queue_wait_ms.batch", "sched.admission_ms.batch",
          "idle.named_share.batch", "idle.unexplained_share.batch",
          "sched.starved_share.batch", "sched.copy_out_ms.batch",
          "sched.stall_s.batch", "moe.expert_imbalance")
CONF = json.load(open(os.path.join(
    ROOT, "benchmark", "configs", CONFIG + ".json")))
WL = json.load(open(os.path.join(
    ROOT, "benchmark", "workloads", CELL + ".json")))


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    root = tmp_path_factory.mktemp("mixedq") / "checkout"
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


def test_rehearsal_traced_line_and_the_check_after_the_window(checkout):
    p = run_cell(checkout, CELL, "--trace", "1")
    line = last_line(p)
    assert line["correct"] is True, line["problems"]
    m = line["metrics"]
    assert 0 < m["sched.decode_util.batch"]["value"] <= 100
    assert m["moe.expert_imbalance"]["value"] >= 1
    # Lanes passed the tiny window: a window layer read less.
    assert 0 < m["attn.window_read_share"]["value"] < 100
    # No device plane on the CPU: the trace readers find nothing, and
    # say so by leaving their metric out.
    for name in NEW[:4] + ("step.decode_ms.batch",):
        assert name not in m
    info = info_line(p)
    assert info["compiles_in_window"] == 0
    check = info["setup"]["check_after_window"]
    assert check["ok"] and all(check["passed"].values())
    kinds = [w["kind"] for w in check["sample"]]
    assert kinds[:2] == ["long_doc", "short"] and set(kinds) <= {
        "long_doc", "short", "crossing"}
    assert {"long_doc", "short", "served"} <= set(check["passed"])
    long_ = check["sample"][0]
    # ... a prompt more than two tiny windows long
    assert long_["prompt_tokens"] >= 64 > 32
    assert check["served_ref_agree"] == 1.0 == check["served_twin_agree"]
    assert check["served_ref_agree_swapped"] < 0.3
    assert all(r["rms_rel"] < 1e-5 for r in check["by_kind"].values())
    # the comparison's seconds are no part of set-up
    assert not any(e["event"] == "logit_check"
                   for e in info["setup"]["events"])
    ready = [e for e in info["setup"]["events"] if e["event"] == "ready"][0]
    assert ready["window_table_pages"] == 7  # (32 + 16 + 6) / 8


def test_another_cells_traced_line_is_unharmed_by_the_new_readers(checkout):
    """The new readers are asked only in their own cell, and where they
    are asked of a run without their op or counter (the parent's
    program) they return None."""
    line = last_line(run_cell(checkout, "oryx-7b.chat", "--trace", "1"))
    assert line["correct"] is True, line["problems"]
    assert not set(NEW) & set(line["metrics"])
    run = {"config": CONF, "device": {"kind": "TPU v5 lite"},
           "counters": {"decode_kv_tokens_total": 1e6}, "trace": {
        "modules": {"jit_paged_decode_chunk": [1.0, 10.0],
                    "jit_paged_prefill": [0.5, 8.0]},
        "ops": {"_ragged_paged.8": [0.1, 99.0], "_mha_forward.3": [0.1, 9.0],
                "gmm.2": [0.2, 30.0]},
        "slice_counters": {"decode_kv_tokens_total": 1e6,
                           "prefill_attn_pairs_total": 1e6}}}
    for name in NEW:
        assert metric_files.load(name).read(run) is None
        assert metric_files.load(name).read(
            dict(run, trace={}, counters={})) is None


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


# A slice of 30 decode dispatches of 8 steps with 30 lanes live, 7 of
# them at 9,500 positions and 23 at 1,500, and 6 prefill chunks of
# 1,024 tokens at offset 4,096.
STEPS = 240.0
KV = STEPS * (7 * 9500 + 23 * 1500)
WKV = STEPS * (7 * 4096 + 23 * 1500)
HIT_D, HIT_P = STEPS * 8 * 60.0, 6 * 8 * 64.0
PAIRS = 6 * (1024 * (4096 + 4096 + 1024 + 1) // 2)
WPAIRS = 6 * 1024 * 4096
SLICE = {"decode_kv_tokens_total": KV, "decode_window_kv_tokens_total": WKV,
         "moe_experts_hit_total": HIT_D + HIT_P,
         "moe_prefill_held_experts_hit_total": HIT_P,
         "prefill_attn_pairs_total": PAIRS,
         "prefill_window_attn_pairs_total": WPAIRS}
RUN = {"config": CONF, "device": {"kind": "TPU v5 lite"},
       "counters": SLICE, "trace": {
    "modules": {"jit_paged_decode_chunk(1)": [30 * 0.1, 30.0]},
    "ops": {"_ragged_paged.8": [0.4, 1920.0], "_mha_forward.3": [0.05, 48.0],
            "gmm.2": [1.8, 6000.0]},
    "slice_counters": SLICE}}
EXPERT = 3 * 2560 * 768 * 2
ONCE = 8 * (20_971_520 * 2 + 5120 * 2 + 163_840 * 4) + (
    2560 * 151_936 + 2560) * 2
WANT = {
    "kernel.ragged_paged_bw.window":
        100 * (4096 * KV + 12288 * WKV) / 0.4 / 819e9,
    "kernel.flash_window_flops":
        100 * 4 * 128 * 28 * (2 * PAIRS + 6 * WPAIRS) / 0.05 / 197e12,
    "kernel.moe_gmm_bw.whole": 100 * (HIT_D + HIT_P) * EXPERT / 1.8 / 819e9,
    "step.decode_mixed_bw": 100 * (
        STEPS * ONCE + HIT_D * EXPERT + 4096 * KV + 12288 * WKV
    ) / 3.0 / 819e9,
    "attn.window_read_share": 100 * WKV / KV,
}


@pytest.mark.parametrize("name", NEW)
def test_new_readers_on_a_hand_made_run(name):
    got = metric_files.load(name).read(RUN)
    assert got == pytest.approx(WANT[name], rel=1e-9)
    assert 0 < got < 100


def test_costs_against_hand_counts():
    s = costs.sizes(CONF)
    assert (s["global_layers"], s["window_layers"], s["W"]) == (2, 6, 4096)
    assert costs.layer_params(CONF) == {
        "attention": 20_971_520, "router": 163_840, "norms": 5_120,
        "experts": 377_487_360}
    assert costs.total_params(CONF) == 3_966_937_600
    assert costs.kv_bytes_per_token(CONF) == {"global": 4096, "window": 12288}
    assert costs.expert_bytes(CONF) == EXPERT
    assert costs.step_weight_bytes(CONF) == ONCE
    mem = CONF["memory"]
    assert mem["weights_bytes"] == 2 * 3_966_937_600 + 2 * 8 * 163_840
    lay = CONF["layout"]
    assert mem["global_plane_bytes"] == lay["num_slots"] * lay[
        "max_ctx"] * 4096 == mem["global_pages"] * 64 * 4096
    assert mem["window_plane_bytes"] == mem["window_pages"] * 64 * 12288
    assert mem["window_pages"] == lay["num_slots"] * mem["window_table_pages"]
    assert mem["pool_bytes"] == mem["global_plane_bytes"] + mem[
        "window_plane_bytes"] < mem["one_table_for_every_layer_bytes"] / 2
    assert mem["arguments_decode_bytes"] > 0.25 * 16e9


def test_manifest_entries_for_the_cell_by_name():
    m = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    cell = {w["name"]: w for w in m["workloads"]}[CELL]
    assert cell["chips"] == 1 and cell["config"] == CONFIG
    assert cell["traffic"] == "mixed-queue"
    assert sum(w["chips"] == 4 for w in m["workloads"]) == 1
    conf = {c["name"]: c for c in m["configs"]}[CONFIG]
    assert conf["source"] == CONF["source"]
    assert conf["reduced"] == ["num_hidden_layers"] == CONF["reduced"]
    assert conf["file"] == f"benchmark/configs/{CONFIG}.json"
    # the manifest's own limit on a line of text
    assert 0 < len(cell["why"]) <= 200 and 0 < len(conf["why"]) <= 200
    by_name = {e["name"]: e for e in m["per_layer"]}
    for name in NEW:
        assert by_name[name]["workloads"] == [CELL]
        assert by_name[name]["moves"] == "serve_tok_s"
        assert by_name[name]["unit"] == "%"
        assert metric_files.load(name).LAYER == by_name[name]["layer"]
    assert by_name["attn.window_read_share"]["better"] == "lower"
    for name in SHARED:
        assert CELL in by_name[name]["workloads"]
        assert by_name[name]["moves"] == "serve_tok_s"
    # their readers reckon every layer a whole-context layer, or read a
    # key this source does not have: not this cell's
    for name in ("kernel.ragged_paged_bw", "kernel.moe_gmm_bw",
                 "moe.step_weight_bw", "cache.prefix_hit_share"):
        assert CELL not in by_name[name].get("workloads", [CELL][:0])
    e2e = {e["name"]: e for e in m["end_to_end"]}
    assert CELL in e2e["serve_tok_s"]["workloads"]
    assert WL["config"] == CONFIG and WL["runner"] == "serve_mixedq"
    t = WL["traffic"]
    assert (t["clients"], t["start_gap_s"], t["long_every"],
            t["max_requests_per_client_s"]) == (32, 0.05, 5, 0.25)
    assert t["clients"] == CONF["layout"]["num_slots"]
    assert t["system_tokens"] == 128 and "first_token_limit_s" not in t
    assert t["user_tokens"] == {"kind": "lognormal", "median": 256,
                                "sigma": 0.8, "min": 32, "max": 1024}
    assert t["document_tokens"] == {"kind": "lognormal", "median": 8192,
                                    "sigma": 0.45, "min": 3072, "max": 14336}
    assert t["question_tokens"] == {"kind": "uniform", "min": 32, "max": 128}
    assert t["max_tokens"] == {"kind": "lognormal", "median": 768,
                               "sigma": 0.5, "min": 256, "max": 1536}
    assert 128 + 14336 + 1 + 128 + 1536 < t["max_session_tokens"] < CONF[
        "layout"]["max_ctx"]
    assert t["check_sample_kinds"] == ["long_doc", "short"]


def test_configuration_file_keeps_every_published_key():
    rows = [json.loads(line) for line in open(
        "/opt/skills/guides/model-configs/architectures.jsonl")] if (
        os.path.exists("/opt/skills/guides/model-configs/architectures.jsonl")
    ) else []
    row = [r for r in rows if r["name"] == "SmallThinker-21BA3B-Instruct"]
    published = row[0]["config"] if row else {
        "head_dim": 128, "hidden_size": 2560, "max_position_embeddings": 16384,
        "moe_ffn_hidden_size": 768, "moe_num_active_primary_experts": 6,
        "moe_num_primary_experts": 64, "num_attention_heads": 28,
        "num_hidden_layers": 52, "num_key_value_heads": 4,
        "rms_norm_eps": 1e-06, "rope_theta": 1500000,
        "sliding_window_size": 4096, "vocab_size": 151936}
    differ = {k for k, v in published.items() if CONF.get(k, "absent") != v}
    assert differ == {"num_hidden_layers"} == set(CONF["reduced"])
    assert CONF["num_hidden_layers"] == 8
    for key in ("router_input", "attention_bias", "rope_pairs", "window",
                "eos_token_id", "max_position_embeddings"):
        assert key in CONF["assumed"]
    lay = CONF["layout"]
    assert (lay["preset"], lay["num_layers"], lay["num_slots"],
            lay["max_ctx"], lay["prefill_chunk"], lay["decode_chunk"],
            lay["page_size"]) == ("smallthinker_21b", 8, 32, 16384, 1024, 8,
                                  64)
    assert lay["prefix_cache"] is False and "window plane" in lay[
        "prefix_cache_note"]


def test_child_builds_the_config_and_refuses_another_geometry():
    from benchmark.reference import smallthinker_ref
    from benchmark.runners import serve_mixedq_child as child

    cfg = child.build_config(CONF)
    assert (cfg.llm.num_layers, cfg.llm.vocab_size) == (8, 151936)
    assert cfg.vision is None and cfg.attn_impl == "pallas"
    layout = [1, 0, 1, 1] * 13
    for key, bad in (("sliding_window_size", 2048), ("hidden_size", 2048),
                     ("moe_num_primary_experts", 32),
                     ("moe_num_active_primary_experts", 8),
                     ("moe_ffn_hidden_size", 1024), ("rope_theta", 1e6),
                     ("sliding_window_layout", layout),
                     ("rope_layout", layout), ("tie_word_embeddings", True)):
        with pytest.raises(SystemExit, match=key):
            child.build_config(dict(CONF, **{key: bad}))
    sz = child.ref_sizes(CONF, cfg)
    assert sz == smallthinker_ref.sizes_from_keys(CONF)
    assert sz["windowed"] == sz["roped"] == (False, True, True, True) * 2
    assert (sz["window"], sz["experts"], sz["top_k"]) == (4096, 64, 6)


def test_the_mix_is_one_fixed_order_at_every_seed():
    from benchmark.runners import serve_mixedq as runner

    t = WL["traffic"]
    size = lambda b: (sum(len(x["content"]) for x in b["messages"]),  # noqa: E731
                      b["max_tokens"])
    a = runner.client_lists(t, 1, 50.0)
    b = runner.client_lists(t, 2**31 + 9, 50.0)
    assert len(a) == 32 and sum(len(c) for c in a) == 400
    assert [[size(x) for x in c] for c in a] == [
        [size(x) for x in c] for c in b]
    assert a[0][0]["messages"][-1]["content"] != b[0][0]["messages"][-1][
        "content"]
    prompts = [size(x)[0] for c in a for x in c]
    assert sum(n > 3000 for n in prompts) == 80  # one in five
    assert max(n + o for c in a for n, o in map(size, c)) <= t[
        "max_session_tokens"]
    # every client's list holds both kinds, and documents of 8,192 and
    # more are among the FIRST requests (they finish inside a window)
    assert all(any(size(x)[0] > 3000 for x in c) for c in a)
    assert sum(size(c[0])[0] >= 8192 for c in a) >= 3


def test_the_sample_takes_one_request_of_each_kind():
    from benchmark import correctness_smallthinker as check

    assert check.kind_of(8192, 8500, 4096, 8192) == "long_doc"
    assert check.kind_of(300, 4096, 4096, 8192) == "short"
    assert check.kind_of(4000, 4161, 4096, 8192) == "crossing"
    assert check.kind_of(4000, 4160, 4096, 8192) is None
    assert check.kind_of(5000, 6000, 4096, 8192) is None


def test_the_controls_fail_by_the_clauses_they_must():
    """On the CPU at the tiny preset: the program as served passes;
    the two faults that only a stream past the window can show fail
    the `long_doc` clause and pass `short` (a base that did not move
    fails `long_doc` too, and writes wherever its rows land; a window a
    page short is read, not judged); the experts' activation and
    the router's input fail. (Positions on the wrong layer kind and
    fp8 weights read far above the program but under the chip's limits
    at these widths and this init: the chip's readings are PERF.md's.)"""
    import jax

    from benchmark import program, run
    from benchmark.runners import serve_mixedq_child as child
    from benchmark.tools import controls_smallthinker as tool

    conf = run.resolve(CONF, True)
    cfg = child.build_config(conf)
    params = program.seeded_params(cfg, 5, "float32")
    lay, about = conf["layout"], conf["logit_check"]
    readings = tool.run_all(
        params, cfg, 5, sizes=child.ref_sizes(conf, cfg),
        page_size=lay["page_size"], prefill_chunk=lay["prefill_chunk"],
        decode_chunk=lay["decode_chunk"], max_ctx=lay["max_ctx"],
        head=about["head"], tail=about["tail"],
        long_prompt=about["sample"]["long_prompt"],
        prompt_tokens=about["prompt_tokens"],
        decode_chunks=about["decode_chunks"])
    jax.clear_caches()
    served = readings["as served"]
    assert served["ok"] and served["kinds"] == ["long_doc", "short",
                                                "crossing"]
    must = tool.WINDOW_ONLY + (tool.BASE_STAYS,
                               "silu for relu in the experts",
                               "the router fed the post-attention state")
    assert not [w for w in tool.wrong(readings) if w.split(" (")[0] in must]
    for name in tool.WINDOW_ONLY:
        p = readings[name]["passed"]
        assert not p["long_doc"] and p["short"], (name, p)
    assert not readings[tool.BASE_STAYS]["passed"]["long_doc"]
    as_served = served["by_kind"]["long_doc"]["rms_rel"]
    for name, r in readings.items():
        if name != "as served":
            assert r["by_kind"]["long_doc"]["rms_rel"] > 100 * as_served or \
                name == "the weights rounded to fp8 (e4m3)"
