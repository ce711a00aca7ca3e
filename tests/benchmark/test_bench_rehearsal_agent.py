"""The agent-session cell of the benchmark, off the chip: its rehearsal
through the harness in a temporary copy (correct, decided after the
window on what it served, a handed-over state among it), the four
readers it brings against a hand-made run and on another cell's line,
`costs_lfm2` against bytes counted by hand, the manifest's entries
looked up BY NAME, the configuration file against the catalog's keys,
the sample, and the controls that must fail the comparison."""

import json
import os
import shutil

import pytest

from benchmark import costs_lfm2, metric_files
from test_bench_rehearsal_train import (
    LINE_KEYS, ROOT, info_line, last_line, run_cell,
)

CELL = "lfm2-24b-a2b.agent-sessions"
CONFIG = "lfm2-24b-a2b-serve"
NEW = ("step.decode_hybrid_bw", "kernel.moe_gmm_bw.hybrid",
       "kernel.ragged_paged_bw.hybrid", "cache.state_handover_share")
SHARED = ("sched.decode_util.batch", "sched.ttft_p90_ms.batch",
          "sched.tpot_p90_ms", "step.decode_ms.batch",
          "step.prefill_ms_ktok.batch", "sched.host_ms_per_dispatch.batch",
          "sched.queue_wait_ms.batch", "sched.admission_ms.batch",
          "idle.named_share.batch", "idle.unexplained_share.batch",
          "sched.starved_share.batch", "sched.copy_out_ms.batch",
          "sched.stall_s.batch", "cache.prefix_hit_share.batch",
          "moe.expert_imbalance")
CONF = json.load(open(os.path.join(
    ROOT, "benchmark", "configs", CONFIG + ".json")))
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    root = tmp_path_factory.mktemp("agent") / "checkout"
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
    assert 0 < m["cache.prefix_hit_share.batch"]["value"] <= 100
    # Every stream of the window but the first few began from a
    # snapshot: every session opens behind the shared system prompt.
    assert 50 < m["cache.state_handover_share"]["value"] <= 100
    assert m["moe.expert_imbalance"]["value"] >= 1
    # No device plane on the CPU: the trace readers find nothing, and
    # say so by leaving their metric out.
    for name in NEW[:3] + ("step.decode_ms.batch",):
        assert name not in m
    info = info_line(p)
    assert info["compiles_in_window"] == 0
    check = info["setup"]["check_after_window"]
    assert check["ok"] and all(check["passed"].values())
    assert set(check["passed"]) == {
        "head", "tail", "handover", "router", "routing", "experts",
        "served"}
    assert check["routing_agree"] > 0.99
    assert [w["kind"] for w in check["sample"]] == [
        "deep_turn", "long_reply", "first_turn"]
    deep, _, first = check["sample"]
    assert deep["cached_tokens"] >= 64 and deep["cached_tokens"] % 16 == 0
    assert 0 < first["cached_tokens"] <= 48
    # a handed-over state is among what was compared, at every stream
    assert all(c > 0 for c in check["cached_tokens"])
    assert check["handover_rms_rel"] < 1e-5 and check["head_rms_rel"] < 1e-5
    assert check["served_ref_agree"] == 1.0 == check["served_twin_agree"]
    assert check["router_error"] < 1e-6
    # the comparison's seconds are no part of set-up
    assert not any(e["event"] == "logit_check"
                   for e in info["setup"]["events"])
    assert {"histories", "comparison"} <= set(info["phases"])


def test_another_cells_traced_line_is_unharmed_by_the_new_readers(checkout):
    """The new readers are asked only in their own cell, and where they
    are asked of a run without their op or counter they return None."""
    line = last_line(run_cell(checkout, "oryx-7b.chat", "--trace", "1"))
    assert line["correct"] is True, line["problems"]
    assert not set(NEW) & set(line["metrics"])
    other = json.load(open(os.path.join(
        ROOT, "benchmark", "configs", "smallthinker-21b-a3b-serve.json")))
    run = {"config": other, "device": {"kind": "TPU v5 lite"},
           "counters": {"prefill_tokens_total": 5.0}, "trace": {
        "modules": {"jit_paged_decode_chunk": [1.0, 10.0]},
        "ops": {"_ragged_paged.8": [0.1, 99.0], "gmm.3": [0.2, 50.0]},
        "slice_counters": {"decode_kv_tokens_total": 1e6,
                           "moe_experts_hit_total": 1e4}}}
    for name in NEW:
        assert metric_files.load(name).read(run) is None
        assert metric_files.load(name).read(dict(run, trace={})) is None


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


# A slice of 30 decode dispatches of 8 steps with 90 lanes live at
# ~4,500 cached tokens, 3,000 experts hit a step of 8 x 64, and 25
# prefill chunks that hit 60 experts a layer.
STEPS, LANES, CHUNKS = 240.0, 90.0, 25.0
DECODE_HIT, PREFILL_HIT = STEPS * 8 * 58, CHUNKS * 8 * 60
SLICE = {"conv_decode_lane_steps_total": STEPS * LANES,
         "decode_kv_tokens_total": STEPS * LANES * 4500,
         "moe_experts_hit_total": DECODE_HIT + PREFILL_HIT,
         "moe_prefill_held_experts_hit_total": PREFILL_HIT}
RUN = {
    "config": CONF, "device": {"kind": "TPU v5 lite"},
    "counters": {"conv_state_handovers_total": 95.0,
                 "conv_state_resets_total": 5.0},
    "trace": {"modules": {"jit_paged_decode_chunk": [4.0, 30.0],
                          "jit_paged_prefill": [0.5, CHUNKS]},
              "ops": {"gmm.3": [1.5, 100.0], "gmm.4": [1.5, 100.0],
                      "_ragged_paged.8": [0.9, 480.0],
                      "fusion.9": [0.3, 100.0]},
              "slice_counters": SLICE},
}
EXPERT = 3 * 2048 * 1536 * 2
STEP_WEIGHTS = (
    (8 * 16_783_360 + 2 * 10_485_888 + 2 * 72_351_744 + 10 * 4096
     + 134_217_728 + 2048) * 2 + 8 * (2048 * 64 + 64) * 4)
DECODE_BYTES = (STEPS * STEP_WEIGHTS + DECODE_HIT * EXPERT
                + STEPS * LANES * 4500 * 4096 + 2 * STEPS * LANES * 65536)


@pytest.mark.parametrize("name, run, want", [
    ("step.decode_hybrid_bw", RUN, 100 * DECODE_BYTES / 4.0 / 819e9),
    ("step.decode_hybrid_bw",
     dict(RUN, trace=dict(RUN["trace"], slice_counters={})), None),
    ("step.decode_hybrid_bw",
     dict(RUN, trace=dict(RUN["trace"], modules={})), None),
    ("kernel.moe_gmm_bw.hybrid", RUN,
     100 * (DECODE_HIT + PREFILL_HIT) * EXPERT / 3.0 / 819e9),
    ("kernel.moe_gmm_bw.hybrid",
     dict(RUN, trace=dict(RUN["trace"], ops={"fusion.9": [0.3, 1.0]})), None),
    ("kernel.ragged_paged_bw.hybrid", RUN,
     100 * STEPS * LANES * 4500 * 4096 / 0.9 / 819e9),
    ("kernel.ragged_paged_bw.hybrid",
     dict(RUN, trace=dict(RUN["trace"], slice_counters={})), None),
    ("cache.state_handover_share", RUN, 95.0),
    ("cache.state_handover_share", dict(RUN, counters={}), None),
    ("cache.state_handover_share", dict(RUN, counters={
        "conv_state_handovers_total": 0.0, "conv_state_resets_total": 0.0}),
     None),
])
def test_new_readers_on_a_hand_made_run(name, run, want):
    got = metric_files.load(name).read(run)
    if want is None:
        assert got is None
    else:
        assert got == pytest.approx(want, rel=1e-9)
        assert 0 < got <= 100.0


def test_costs_lfm2_against_hand_counts():
    # ISSUE 56's arithmetic.
    per = costs_lfm2.layer_params(CONF)
    assert per["conv"] == 16_783_360 == 12_582_912 + 6_144 + 4_194_304
    assert per["attention"] == 10_485_888
    assert per["dense_ffn"] == 72_351_744
    assert per["router"] + per["experts"] == 604_110_912
    assert costs_lfm2.total_params(CONF) == 5_267_090_176
    full = dict(CONF, num_hidden_layers=40, layer_types=(
        ["conv", "conv"] + 9 * ["full_attention", "conv", "conv", "conv"]
        + ["full_attention", "conv"]))
    assert costs_lfm2.total_params(full) == (
        2 * 89_139_200 + 10 * 614_600_896 + 28 * 620_898_368 + 134_219_776)
    assert costs_lfm2.expert_bytes(CONF) == EXPERT
    assert costs_lfm2.kv_bytes_per_token(CONF) == 4096
    assert costs_lfm2.state_bytes_per_lane(CONF) == 65_536
    assert costs_lfm2.step_weight_bytes(CONF) == STEP_WEIGHTS
    mem, lay = CONF["memory"], CONF["layout"]
    assert mem["weights_bytes"] == 5_267_090_176 * 2 + 8 * (
        2048 * 64 + 64) * 2  # routers and biases float32
    assert mem["state_bytes_per_slot"] == 65_536
    assert mem["page_bytes"] == 64 * 4096 + 65_536 == 327_680
    # the engine's default pool: every slot at the ceiling at once
    assert "num_pages" not in lay and mem["num_pages"] == (
        lay["num_slots"] * lay["max_ctx"] // lay["page_size"]) == 12288
    assert mem["pool_bytes"] == (
        mem["num_pages"] * 327_680 + lay["num_slots"] * 65_536)
    # every expert a step: the experts are eleven twelfths of the bytes
    one = costs_lfm2.decode_bytes(
        CONF, steps=1, experts_hit=8 * 64, kv_tokens=0, lane_steps=0)
    assert one == STEP_WEIGHTS + 8 * 64 * EXPERT
    assert abs(one - mem["weights_bytes"]) < 1e6
    # the whole of the arguments and temporaries: under the chip's
    # usable memory less ISSUE 56's half gigabyte, over a quarter
    total = mem["arguments_decode_bytes"] + mem[
        "temporaries_decode_bytes"] + mem["temporaries_prefill_bytes"]
    assert 0.25 * 16e9 < total < 15.75e9 - 0.5e9


def test_manifest_entries_for_the_cell_by_name():
    m = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    cells = {w["name"]: w for w in m["workloads"]}
    cell = cells[CELL]
    assert cell["chips"] == 1 and cell["config"] == CONFIG
    assert cell["traffic"] == "agent-sessions"
    assert m["workloads"][-1] is cell and len(cell["why"]) <= 200
    assert sum(w["chips"] == 4 for w in m["workloads"]) == 1
    conf = {c["name"]: c for c in m["configs"]}[CONFIG]
    assert conf["source"] == CONF["source"]
    assert conf["reduced"] == ["num_hidden_layers", "layer_types"] == CONF[
        "reduced"]
    assert conf["file"] == f"benchmark/configs/{CONFIG}.json"
    by_name = {e["name"]: e for e in m["per_layer"]}
    assert [e["name"] for e in m["per_layer"][-4:]] == list(NEW)
    for name in NEW:
        assert by_name[name]["workloads"] == [CELL]
        assert by_name[name]["moves"] == "serve_tok_s"
        assert by_name[name]["unit"] == "%"
        assert metric_files.load(name).LAYER == by_name[name]["layer"]
    for name in SHARED:
        assert by_name[name]["workloads"][-1] == CELL
        assert by_name[name]["moves"] == "serve_tok_s"
    # their readers read other configurations' keys or reckon every
    # layer a K/V layer: not this cell's
    for name in ("kernel.ragged_paged_bw", "kernel.moe_gmm_bw.whole",
                 "step.decode_state_bw", "step.decode_mixed_bw"):
        assert CELL not in by_name[name]["workloads"]
    e2e = {e["name"]: e for e in m["end_to_end"]}
    assert e2e["serve_tok_s"]["workloads"][-1] == CELL
    assert "workloads" not in e2e["setup_s"]
    wl = json.load(open(os.path.join(
        ROOT, "benchmark", "workloads", CELL + ".json")))
    assert wl["config"] == CONFIG and wl["runner"] == "serve_agent"
    t = wl["traffic"]
    assert (t["clients"], t["start_gap_s"], t["turns"],
            t["max_requests_per_client_s"]) == (96, 0.05, [6, 10, 14], 1.0)
    assert t["clients"] == CONF["layout"]["num_slots"]
    assert t["system_tokens"] == 2048 and "first_token_limit_s" not in t
    assert "session_tag_chars" not in t  # ONE system prompt for all
    assert t["user_tokens"] == {"kind": "lognormal", "median": 256,
                                "sigma": 0.9, "min": 32, "max": 1536}
    assert t["max_tokens"] == {"kind": "uniform", "min": 64, "max": 256}
    assert t["max_session_tokens"] == 8128 < CONF["layout"]["max_ctx"]
    assert t["warm_previous_turn"] is True
    assert wl["trace_seconds"] == 3.0


def test_configuration_file_keeps_every_published_key():
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog beside the guides here")
    row = next(json.loads(ln) for ln in open(CATALOG)
               if '"LFM2-24B-A2B"' in ln)
    assert CONF["source"] == row["source_url"]
    cut = {"num_hidden_layers": 10,
           "layer_types": row["config"]["layer_types"][:10]}
    assert {k for k, v in row["config"].items()
            if CONF.get(k, "absent") != cut.get(k, v)} == set()
    assert CONF["layer_types"] == [
        "conv", "conv", "full_attention", "conv", "conv", "conv",
        "full_attention", "conv", "conv", "conv"]
    for key in ("conv_form", "head_dim", "qk_norm", "router", "expert_bias",
                "tie_word_embeddings", "eos_token_id",
                "max_position_embeddings", "hidden_act"):
        assert key in CONF["assumed"]
    lay = CONF["layout"]
    assert (lay["preset"], lay["num_layers"], lay["num_slots"],
            lay["page_size"], lay["max_ctx"], lay["prefill_chunk"],
            lay["decode_chunk"], lay["dtype"], lay["attn_impl"]) == (
        "lfm2_24b_a2b", 10, 96, 64, 8192, 512, 8, "bfloat16", "pallas")
    assert lay["prefix_cache"] is True
    assert "first of four pipeline stages" in CONF["stands_for"].lower()


def test_child_builds_the_config_and_refuses_another_geometry():
    from benchmark.reference import lfm2_ref
    from benchmark.runners import serve_agent_holder as child

    cfg = child.build_config(CONF)
    llm = cfg.llm
    assert (llm.num_layers, llm.vocab_size, llm.head_dim) == (10, 65536, 64)
    assert cfg.vision is None and cfg.attn_impl == "pallas"
    assert llm.layer_kinds == (
        "conv", "conv", "attn", "conv", "conv", "conv", "attn", "conv",
        "conv", "conv")
    assert llm.ffn_kinds == ("dense",) * 2 + ("moe",) * 8
    for key, bad in (("num_dense_layers", 1), ("conv_L_cache", 4),
                     ("num_experts", 32), ("hidden_size", 1024),
                     ("num_key_value_heads", 4), ("norm_eps", 1e-6),
                     ("use_expert_bias", False),
                     ("layer_types", ["conv"] * 10), ("conv_bias", True)):
        with pytest.raises(SystemExit, match=key):
            child.build_config(dict(CONF, **{key: bad}))
    sz = child.ref_sizes(CONF, cfg)
    assert sz == lfm2_ref.sizes_from_keys({**CONF, "rope_theta": 1000000})
    assert (sz["head_dim"], sz["dense"], sz["taps"], sz["top_k"]) == (
        64, 2, 3, 4)
    assert sz["kinds"].count("full_attention") == 2 and len(sz["kinds"]) == 10


def test_the_sample_takes_one_request_of_each_kind():
    from benchmark.runners import serve_agent_holder as child

    class Handle:
        error, cancelled, finish_reason = None, False, "length"

        def __init__(self, n, hit, done=True):
            self.reply = "".join(f"<{i}>" for i in range(n))
            self.done = type("E", (), {"is_set": lambda s: done})()
            self.debug = {"cost": {"cached_tokens": hit}}

    class Pipe:
        def _prepare_request(self, request):
            return (list(range(request["n"])),)

    served = type("S", (), {})()
    turn = lambda n, k: {"n": n, "history": [("q", "a")] * k}  # noqa: E731
    served.items = [
        (turn(300, 3), 20, Handle(20, 256)), (turn(500, 5), 12, Handle(12, 448)),
        (turn(90, 0), 30, Handle(30, 64)), (turn(70, 0), 10, Handle(10, 64)),
        (turn(80, 0), 10, Handle(10, 0)), (turn(200, 2), 40, Handle(40, 128)),
        (turn(600, 6), 50, Handle(50, 512, done=False)),
        (turn(60, 0), 10, Handle(9, 64)), (turn(50, 0), 4, Handle(4, 32)),
    ]
    prompts, cached, streams, what = child.sample_served(
        served, Pipe(), deep_hit=200, max_positions=2000, min_tokens=5)
    assert [(w["kind"], w["prompt_tokens"], w["cached_tokens"],
             w["served_tokens"]) for w in what] == [
        ("deep_turn", 500, 448, 12), ("long_reply", 200, 128, 40),
        ("first_turn", 70, 64, 10)]
    assert cached == [448, 128, 64] and [len(p) for p in prompts] == [
        500, 200, 70]
    assert [len(s) for s in streams] == [12, 40, 10]
    *_, what = child.sample_served(
        served, Pipe(), deep_hit=200, max_positions=600, min_tokens=5)
    # the deepest turn that FITS, then the longest reply that still does
    assert [(w["kind"], w["prompt_tokens"]) for w in what] == [
        ("deep_turn", 500), ("long_reply", 70)]


# --- the comparison against programs that must fail it (CPU, tiny) ---------

WRONG_STATE = ("a zero state at a hit", "the snapshot taken one token early")
FAILS_ON_THE_CPU = {
    "the bias in the weights": "experts",
    "q/k norm left out": "head",
    "a bfloat16 router": "router",
    "the first expert layer's kernels rounded to fp8 (e4m3)": "experts",
    "one selection in five replaced by the next best": "routing",
}
ALSO_RUN = ("the selection without its bias",)


def test_the_controls_fail_the_comparison_and_the_program_passes():
    """tools/controls_lfm2.run_all at `lfm2_tiny` (the ten-layer cut) in
    float32 with the kernels scaled (tests/test_lfm2.py `scaled`): the
    structural faults fail by the chip's own limits here too, but for a
    wrong state at a hit, which at a width of 64 reads 4-8 % in the
    rows behind the hit (47-49 % at the published 2,048) where the
    program reads 3e-7; the precision controls (fp8 weights of a float32 model, a bias of 0.2
    in weights that sum to 1 at this size, a second decode program that
    agrees at this size) only run. One test, so that one worker traces
    the seven programs once."""
    import dataclasses

    import jax

    from oryx_tpu import config as cfg_lib
    from oryx_tpu.models import oryx

    from benchmark.tools import controls_lfm2
    from test_lfm2 import scaled, sizes_of

    cfg = cfg_lib.lfm2_tiny()
    cfg = dataclasses.replace(
        cfg, llm=dataclasses.replace(cfg.llm, num_layers=10))
    params = oryx.init_params(cfg, jax.random.key(0))
    params["llm"] = scaled(params["llm"])
    readings = controls_lfm2.run_all(
        params, cfg, 7, sizes=sizes_of(cfg.llm), page_size=16,
        prefill_chunk=32, decode_chunk=4, max_ctx=512, head=4, tail=6,
        prompt_tokens=(90, 40, 150), cached_tokens=(48, 0, 112),
        decode_chunks=3,
        only=",".join(WRONG_STATE + tuple(FAILS_ON_THE_CPU) + ALSO_RUN))
    r = readings["as served"]
    assert r["ok"] and all(r["passed"].values())
    assert r["head_rms_rel"] < 1e-5 and r["handover_rms_rel"] < 1e-5
    assert r["served_ref_agree"] == 1.0 and r["router_error"] < 1e-6
    assert r["cached_tokens"] == [48, 0, 112]
    assert len(readings) == 9  # of fourteen: each traces every program anew
    for control, clause in FAILS_ON_THE_CPU.items():
        r = readings[control]
        assert not r["ok"] and not r["passed"][clause], (control, r)
    # a wrong state moves the rows behind the hit and little else
    for control in WRONG_STATE:
        r = readings[control]
        assert r["handover_rms_rel"] > 2e-2 > 1e3 * readings["as served"][
            "handover_rms_rel"], (control, r)
        assert r["handover_rms_rel"] > 2 * r["tail_rms_rel"]
    # a bias of 0.05 moves few choices where `scaled` has spread the
    # scores: fewer agree, and the logits are handed the choice
    r = readings["the selection without its bias"]
    assert r["routing_agree"] < readings["as served"]["routing_agree"] == 1.0
    assert r["head_rms_rel"] < 1e-5
    # a wrong expert at one token in five: four sets in five agree, the
    # logits are handed the choice, and only `routing` refuses it
    r = readings["one selection in five replaced by the next best"]
    assert 0.7 < r["routing_agree"] < 0.9 and r["head_rms_rel"] < 1e-5
    assert [c for c, ok in r["passed"].items() if not ok] == ["routing"]
    # the experts' own kernels in fp8: the `experts` clause alone
    r = readings["the first expert layer's kernels rounded to fp8 (e4m3)"]
    assert [c for c, ok in r["passed"].items() if not ok] == ["experts"]
