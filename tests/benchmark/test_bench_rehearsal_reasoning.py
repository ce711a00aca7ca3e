"""The long-reasoning cell of the benchmark, off the chip: its
rehearsal through the harness in a temporary copy (correct, decided
after the window on what it served), the three readers it brings
against a hand-made run and on another cell's line, `costs_ssm`
against bytes counted by hand, the manifest's entries looked up BY
NAME, the configuration file against the catalog's keys, and the
traced slice's window on the device's own clock."""

import json
import os
import shutil

import pytest

from benchmark import costs_ssm, metric_files, trace
from test_bench_rehearsal_train import (
    LINE_KEYS, ROOT, info_line, last_line, run_cell,
)

CELL = "jamba2-3b.reasoning"
CONFIG = "jamba2-3b-serve"
NEW = ("kernel.ssm_scan_bw", "step.decode_state_bw", "ssm.prefill_share")
SHARED = ("sched.decode_util.batch", "sched.ttft_p90_ms.batch",
          "sched.tpot_p90_ms", "step.decode_ms.batch",
          "step.prefill_ms_ktok.batch", "sched.host_ms_per_dispatch.batch",
          "sched.queue_wait_ms.batch", "sched.admission_ms.batch",
          "idle.named_share.batch", "idle.unexplained_share.batch",
          "sched.starved_share.batch", "sched.copy_out_ms.batch",
          "sched.stall_s.batch")
CONF = json.load(open(os.path.join(
    ROOT, "benchmark", "configs", CONFIG + ".json")))


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    root = tmp_path_factory.mktemp("reasoning") / "checkout"
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
    assert m["sched.tpot_p90_ms"]["value"] > 0
    # No device plane on the CPU: the trace readers find nothing, and
    # say so by leaving their metric out.
    for name in NEW + ("step.decode_ms.batch", "step.prefill_ms_ktok.batch"):
        assert name not in m
    info = info_line(p)
    assert info["compiles_in_window"] == 0
    check = info["setup"]["check_after_window"]
    assert check["ok"] and all(check["passed"].values())
    assert set(check["passed"]) == {"head", "tail", "state", "served"}
    assert check["state_bf16_share"] < 0.01
    assert [w["kind"] for w in check["sample"]] == [
        "long_answer", "two_chunk", "one_chunk"]
    long_, two, one = check["sample"]
    assert long_["served_tokens"] >= 24 and two["prompt_tokens"] > 32 \
        and one["prompt_tokens"] <= 32
    assert check["served_ref_agree"] == 1.0 == check["served_twin_agree"]
    assert check["served_ref_agree_swapped"] < 0.2
    assert check["head_rms_rel"] < 1e-5 and check["tail_rms_rel"] < 1e-5
    # the comparison's seconds are no part of set-up
    assert not any(e["event"] == "logit_check"
                   for e in info["setup"]["events"])


def test_another_cells_traced_line_is_unharmed_by_the_new_readers(checkout):
    """The new readers are asked only in their own cell, and where they
    are asked of a run without their op or counter they return None."""
    line = last_line(run_cell(checkout, "oryx-7b.chat", "--trace", "1"))
    assert line["correct"] is True, line["problems"]
    assert not set(NEW) & set(line["metrics"])
    run = {"config": CONF, "device": {"kind": "TPU v5 lite"}, "trace": {
        "modules": {"jit_paged_decode_chunk": [1.0, 10.0],
                    "jit_paged_prefill": [0.5, 8.0]},
        "ops": {"_ragged_paged.8": [0.1, 99.0]},
        "slice_counters": {"decode_kv_tokens_total": 1e6}}}
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


# A slice of 30 decode dispatches of 8 steps with 60 lanes live at
# ~1,200 cached tokens, and 20 prefill chunks of 300 real tokens.
STEPS, LANES, CHUNKS = 240.0, 60.0, 20.0
SLICE = {"ssm_decode_lane_steps_total": STEPS * LANES,
         "decode_kv_tokens_total": STEPS * LANES * 1200,
         "ssm_prefill_tokens_total": CHUNKS * 300}
RUN = {
    "config": CONF, "device": {"kind": "TPU v5 lite"},
    "trace": {"modules": {"jit_paged_decode_chunk": [2.4, 30.0],
                          "jit_paged_prefill": [0.5, CHUNKS]},
              "ops": {"_selective_scan.10": [0.1, 7 * 2 * CHUNKS],
                      "_selective_scan.11": [0.1, 6 * 2 * CHUNKS],
                      "fusion.9": [0.3, 100.0]},
              "slice_counters": SLICE},
}
WEIGHTS = 3_029_337_472 * 2 + 26 * (16 * 5120 + 2 * 5120) * 2
STATE = 26 * 5120 * (16 * 4 + 3 * 2)
DECODE_BYTES = (STEPS * WEIGHTS + 2 * STEPS * LANES * STATE
                + STEPS * LANES * 1200 * 1024)
SCAN_BYTES = 26 * (CHUNKS * 300 * (5120 * (3 * 2 + 4) + 2 * 16 * 4)
                   + CHUNKS * 2 * 16 * 5120 * 4)


@pytest.mark.parametrize("name, run, want", [
    ("step.decode_state_bw", RUN, 100 * DECODE_BYTES / 2.4 / 819e9),
    ("step.decode_state_bw",
     dict(RUN, trace=dict(RUN["trace"], slice_counters={})), None),
    ("step.decode_state_bw",
     dict(RUN, trace=dict(RUN["trace"], modules={})), None),
    ("kernel.ssm_scan_bw", RUN, 100 * SCAN_BYTES / 0.2 / 819e9),
    ("kernel.ssm_scan_bw",
     dict(RUN, trace=dict(RUN["trace"], ops={"fusion.9": [0.3, 1.0]})), None),
    ("kernel.ssm_scan_bw",
     dict(RUN, trace=dict(RUN["trace"], slice_counters={})), None),
    ("ssm.prefill_share", RUN, 100 * 0.2 / 0.5),
    ("ssm.prefill_share",
     dict(RUN, trace=dict(RUN["trace"], ops={})), None),
    ("ssm.prefill_share",
     dict(RUN, trace=dict(RUN["trace"], modules={})), None),
])
def test_new_readers_on_a_hand_made_run(name, run, want):
    got = metric_files.load(name).read(run)
    if want is None:
        assert got is None
    else:
        assert got == pytest.approx(want, rel=1e-9)
        assert 0 < got <= 100.0


def test_costs_ssm_against_hand_counts():
    # ISSUE 40's arithmetic.
    assert costs_ssm.mixer_params(CONF) == 41_241_792 == (
        2560 * 10240 + 4 * 5120 + 5120 + 5120 * 192 + 192 + 160 * 5120
        + 5120 + 5120 * 16 + 5120 + 5120 * 2560)
    assert costs_ssm.layer_params(CONF) == {
        "mamba": 104_161_472, "attn": 76_682_240}
    assert costs_ssm.total_params(CONF) == 3_029_337_472
    assert costs_ssm.decode_weight_bytes(CONF) == WEIGHTS == 6_063_467_264
    assert costs_ssm.decode_weight_bytes(CONF) == CONF["memory"][
        "weights_bytes"]
    assert costs_ssm.state_bytes_per_lane(CONF) == STATE == 9_318_400
    assert costs_ssm.kv_bytes_per_token(CONF) == 1024
    lay, mem = CONF["layout"], CONF["memory"]
    assert mem["state_bytes"] == lay["num_slots"] * STATE
    assert mem["kv_bytes"] == lay["num_slots"] * lay["max_ctx"] * 1024
    # 64 live lanes: the state moved a step is a fifth of the weights.
    one = costs_ssm.decode_bytes(CONF, steps=1, lane_steps=64, kv_tokens=0)
    assert one - WEIGHTS == 2 * 64 * STATE == 1_192_755_200
    assert costs_ssm.scan_bytes(CONF, tokens=300, chunks=1) == SCAN_BYTES / 20
    # the whole of the arguments: over a quarter of the chip
    assert mem["arguments_decode_bytes"] > 0.25 * 16e9


def test_manifest_entries_for_the_cell_by_name():
    m = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    cells = {w["name"]: w for w in m["workloads"]}
    cell = cells[CELL]
    assert cell["chips"] == 1 and cell["config"] == CONFIG
    assert cell["traffic"] == "reasoning"
    assert sum(w["chips"] == 4 for w in m["workloads"]) == 1
    conf = {c["name"]: c for c in m["configs"]}[CONFIG]
    assert conf["source"] == CONF["source"] and conf["reduced"] == []
    assert conf["file"] == f"benchmark/configs/{CONFIG}.json"
    assert CONF["reduced"] == []
    by_name = {e["name"]: e for e in m["per_layer"]}
    for name in NEW:
        assert by_name[name]["workloads"] == [CELL]
        assert by_name[name]["moves"] == "serve_tok_s"
        assert by_name[name]["unit"] == "%"
        assert metric_files.load(name).LAYER == by_name[name]["layer"]
    for name in SHARED:
        assert CELL in by_name[name]["workloads"]
        assert by_name[name]["moves"] == "serve_tok_s"
    # its reader reckons every layer a KV layer: not this cell's
    assert CELL not in by_name["kernel.ragged_paged_bw"]["workloads"]
    e2e = {e["name"]: e for e in m["end_to_end"]}
    assert CELL in e2e["serve_tok_s"]["workloads"]
    assert "workloads" not in e2e["setup_s"]
    wl = json.load(open(os.path.join(
        ROOT, "benchmark", "workloads", CELL + ".json")))
    assert wl["config"] == CONFIG and wl["runner"] == "serve_reasoning"
    t = wl["traffic"]
    assert (t["clients"], t["start_gap_s"], t["turns"],
            t["max_requests_per_client_s"]) == (64, 0.05, [1], 0.25)
    assert t["clients"] == CONF["layout"]["num_slots"]
    assert t["system_tokens"] == 128 and "first_token_limit_s" not in t
    assert t["user_tokens"] == {"kind": "lognormal", "median": 192,
                                "sigma": 0.7, "min": 48, "max": 768}
    assert t["max_tokens"] == {"kind": "lognormal", "median": 1024,
                               "sigma": 0.5, "min": 384, "max": 3072}
    assert 128 + 768 + 3072 < t["max_session_tokens"] < CONF["layout"][
        "max_ctx"]


def test_configuration_file_keeps_every_published_key():
    published = {
        "attn_layer_offset": 7, "attn_layer_period": 14,
        "expert_layer_offset": 1, "expert_layer_period": 2,
        "hidden_act": "silu", "hidden_size": 2560, "intermediate_size": 8192,
        "mamba_conv_bias": True, "mamba_d_conv": 4, "mamba_d_state": 16,
        "mamba_dt_rank": 160, "mamba_expand": 2, "mamba_proj_bias": False,
        "max_position_embeddings": 262144, "model_type": "jamba",
        "num_attention_heads": 20, "num_experts": 1,
        "num_experts_per_tok": 1, "num_hidden_layers": 28,
        "num_key_value_heads": 1, "num_logits_to_keep": 1,
        "rms_norm_eps": 1e-06, "sliding_window": None,
        "tie_word_embeddings": True, "use_mamba_kernels": True,
        "vocab_size": 65536,
    }
    assert {k for k, v in published.items()
            if CONF.get(k, "absent") != v} == set()
    for key in ("layer_order", "inner_norms", "mixer_init",
                "state_precision", "eos_token_id", "positions"):
        assert key in CONF["assumed"]
    lay = CONF["layout"]
    assert (lay["preset"], lay["num_layers"], lay["num_slots"],
            lay["max_ctx"], lay["prefill_chunk"], lay["decode_chunk"]) == (
        "jamba2_3b", 28, 64, 4096, 512, 8)
    assert lay["prefix_cache"] is False and "no recurrent state" in lay[
        "prefix_cache_note"].lower()


def test_child_builds_the_config_and_refuses_another_geometry():
    from benchmark.reference import jamba_ref
    from benchmark.runners import serve_reasoning_child as child

    cfg = child.build_config(CONF)
    assert (cfg.llm.num_layers, cfg.llm.vocab_size) == (28, 65536)
    assert cfg.vision is None and cfg.attn_impl == "pallas"
    for key, bad in (("attn_layer_offset", 3), ("mamba_dt_rank", 128),
                     ("mamba_d_state", 8), ("hidden_size", 2048),
                     ("num_key_value_heads", 4),
                     ("tie_word_embeddings", False)):
        with pytest.raises(SystemExit, match=key):
            child.build_config(dict(CONF, **{key: bad}))
    sz = child.ref_sizes(CONF, cfg)
    assert sz == jamba_ref.sizes_from_keys(CONF)
    assert (sz["d_inner"], sz["head_dim"], sz["period"], sz["offset"]) == (
        5120, 128, 14, 7)
    assert jamba_ref.layer_kinds(sz).count("attn") == 2
    assert [i for i, k in enumerate(jamba_ref.layer_kinds(sz))
            if k == "attn"] == [7, 21]


def test_the_traced_window_is_the_device_planes_own_extent():
    """`busy_s` sums device events; the window is first start to last
    end of the same events, so busy can never read over it."""
    ops = trace.Line("XLA Ops", [trace.Event("a", 400, 0),
                                 trace.Event("b", 300, 700)], 5)
    mods = trace.Line("XLA Modules", [trace.Event("jit_x(1)", 1100, 0)], 5)
    planes = [trace.Plane("/device:TPU:0", [ops, mods]),
              trace.Plane("/host:CPU", [trace.Line("t", [
                  trace.Event("span", 10**9, 0)], 1)])]
    assert trace.device_extent_s(planes) == pytest.approx(1100e-12)
    assert trace.device_extent_s(planes[1:]) == 0.0
    red = trace.reduce_planes(
        planes, window_s=trace.device_extent_s(planes))
    assert red["busy_s"] == pytest.approx(700e-12) and (
        red["busy_s"] <= red["window_s"])


def test_the_sample_takes_one_request_of_each_kind():
    from benchmark.runners import serve_reasoning_child as child

    class Handle:
        error, cancelled, finish_reason = None, False, "length"

        def __init__(self, n, done=True):
            self.reply = "".join(f"<{i}>" for i in range(n))
            self.done = type("E", (), {"is_set": lambda s: done})()

    class Pipe:
        def _prepare_request(self, request):
            return (list(range(request["n"])),)

    served = type("S", (), {})()
    served.items = [
        ({"n": 40}, 30, Handle(30)), ({"n": 20}, 8, Handle(8)),
        ({"n": 10}, 8, Handle(8)), ({"n": 12}, 40, Handle(40)),
        ({"n": 30}, 26, Handle(26)),
        ({"n": 9}, 50, Handle(50, done=False)), ({"n": 8}, 8, Handle(7)),
    ]
    prompts, streams, what = child.sample_served(
        served, Pipe(), long_answer=24, prefill_chunk=16, max_positions=500)
    assert [(w["kind"], w["prompt_tokens"], w["served_tokens"])
            for w in what] == [("long_answer", 12, 40), ("two_chunk", 20, 8),
                               ("one_chunk", 10, 8)]
    assert [len(p) for p in prompts] == [12, 20, 10]
    _, _, what = child.sample_served(
        served, Pipe(), long_answer=24, prefill_chunk=16, max_positions=60)
    # the longest answer that FITS, then nothing else does
    assert [(w["kind"], w["served_tokens"]) for w in what] == [
        ("long_answer", 40)]


# --- the comparison against programs that must fail it (CPU, tiny) ---------

FAILS_ON_THE_CPU = {
    "the state kept in bfloat16": "state",
    "a padded chunk that moves the state": "head",
    "the conv window one token late": "head",
    "rotary positions switched on": "head",
    "dt's norm left out": "head",
    "a finished lane's state advanced": "head",
}


def _control_readings():
    """tools/controls_jamba.run_all at `jamba_tiny` in float32 with the
    kernels scaled by 4 (at 0.02 a layer adds too little for a fault to
    show): the structural faults fail by the chip's own limits here
    too; the precision controls (fp8 weights of a float32 model, a
    second decode program that agrees at this size) only run."""
    import jax

    from oryx_tpu import config as cfg_lib
    from oryx_tpu.models import oryx

    from benchmark.tools import controls_jamba
    from test_jamba import _scaled, sizes_of

    cfg = cfg_lib.jamba_tiny()
    params = oryx.init_params(cfg, jax.random.key(0))
    params["llm"] = _scaled(params["llm"])
    return controls_jamba.run_all(
        params, cfg, 7, sizes=sizes_of(cfg.llm), page_size=16,
        prefill_chunk=32, decode_chunk=4, max_ctx=512, head=4, tail=6,
        prompt_tokens=(40, 70, 9), decode_chunks=5)


def test_the_controls_fail_the_comparison_and_the_program_passes():
    """One test, so that one worker traces the ten programs once."""
    readings = _control_readings()
    r = readings["as served"]
    assert r["ok"] and all(r["passed"].values())
    assert r["head_rms_rel"] < 1e-5 and r["tail_rms_rel"] < 1e-5
    assert r["served_ref_agree"] == 1.0 and r["state_bf16_share"] < 0.01
    assert r["served_ref_agree_swapped"] < 0.2
    assert len(readings) == 10
    for control, clause in FAILS_ON_THE_CPU.items():
        r = readings[control]
        assert not r["ok"] and not r["passed"][clause], (control, r)
    # 5.6 % against a limit of 5.4 at this size (8.6 % on the chip): too
    # near to pin as a failure here, far above the program's 2e-7.
    r = readings["a reused slot not zeroed"]
    assert r["head_rms_rel"] > 0.02 and r["tail_rms_rel"] > 0.02
