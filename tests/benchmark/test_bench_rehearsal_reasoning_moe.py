"""The agent-reasoning cell of the benchmark, off the chip: its
rehearsal through the harness in a temporary copy (correct, decided
after the window on what it served), the four readers it brings against
a hand-made run and on another cell's line, `costs_nemotron` against
bytes counted by hand, the manifest's entries looked up BY NAME and never
by position (the next appended cell must not break this file), the
configuration file against the catalog's keys, the holder's rules, the
sample, and the controls that must fail the comparison."""

import json
import os
import shutil

import pytest

from benchmark import costs_nemotron, metric_files
from test_bench_rehearsal_train import (
    LINE_KEYS, ROOT, info_line, last_line, run_cell,
)

CELL = "nemotron-3-super.agent-reasoning"
CONFIG = "nemotron-3-super-ep4-serve"
NEW = ("kernel.ssd_step_bw", "kernel.moe_gmm_bw.latent",
       "step.decode_latent_moe_bw", "ssd.prefill_flops")
SHARED = ("sched.decode_util.batch", "sched.ttft_p90_ms.batch",
          "sched.tpot_p90_ms", "step.decode_ms.batch",
          "step.prefill_ms_ktok.batch", "sched.host_ms_per_dispatch.batch",
          "sched.queue_wait_ms.batch", "sched.admission_ms.batch",
          "idle.named_share.batch", "idle.unexplained_share.batch",
          "sched.starved_share.batch", "sched.copy_out_ms.batch",
          "sched.stall_s.batch", "moe.expert_imbalance",
          "moe.held_hit_share", "scope.attn_share.batch",
          "scope.moe_share.batch", "scope.mixer_share.batch",
          "scope.head_share.batch", "scope.prefill_attn_share.batch",
          "scope.unscoped_share.batch")
CONF = json.load(open(os.path.join(
    ROOT, "benchmark", "configs", CONFIG + ".json")))
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    root = tmp_path_factory.mktemp("reasoning_moe") / "checkout"
    shutil.copytree(os.path.join(ROOT, "benchmark"), root / "benchmark",
                    ignore=shutil.ignore_patterns("out", "__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    return str(root)


@pytest.fixture(scope="module")
def traced(checkout):
    """ONE traced rehearsal of the cell, shared by the tests below (a
    run is a minute of this file's time)."""
    return run_cell(checkout, CELL, "--trace", "1")


def test_rehearsal_reports_the_cell_end_to_end(traced):
    line = last_line(traced)
    assert LINE_KEYS <= set(line) and line["correct"] is True, line
    assert line["attempted"] >= 3 and line["failed"] == 0
    assert line["rehearsal"] is True and line["device"]["platform"] == "cpu"
    m = line["metrics"]
    assert 0 < m["sched.decode_util.batch"]["value"] <= 100
    assert m["moe.expert_imbalance"]["value"] >= 1
    assert 0 < m["moe.held_hit_share"]["value"] <= 100
    # No device plane on the CPU: the four new readers and the scope
    # readers find nothing to read, and say so by leaving their metric
    # out; what feeds them from the program's counters is in the run
    # (the next test).
    for name in NEW + ("step.decode_ms.batch", "scope.mixer_share.batch"):
        assert name not in m


def test_the_check_after_the_window_and_what_feeds_the_new_readers(
        checkout, traced):
    info = info_line(traced)
    assert info["compiles_in_window"] == 0
    assert info["requests"]["serve_tok_s"] > 0
    check = info["setup"]["check_after_window"]
    assert check["ok"] and all(check["passed"].values())
    assert set(check["passed"]) == {
        "head", "tail", "state", "router", "routing", "experts", "served"}
    assert [w["kind"] for w in check["sample"]] == [
        "long_answer", "multi_chunk", "one_chunk"]
    assert check["head_rms_rel"] < 1e-5 and check["tail_rms_rel"] < 1e-5
    assert check["routing_agree"] > 0.99 and check["state_bf16_share"] < 0.01
    assert check["served_ref_agree"] == 1.0 == check["served_twin_agree"]
    assert check["router_error"] < 1e-6
    # the comparison's seconds are no part of set-up
    assert not any(e["event"] == "logit_check"
                   for e in info["setup"]["events"])
    assert "comparison" in info["phases"]
    # The counters the four readers divide by are in the run's record
    # of the window.
    out = os.path.join(checkout, "benchmark", "out", CELL)
    name = next(f for f in sorted(os.listdir(out))
                if f.startswith("run.seed") and f.endswith(".trace1.json"))
    counters = json.load(open(os.path.join(out, name)))["counters"]
    for c in ("ssm_decode_lane_steps_total", "ssm_prefill_tokens_total",
              "ssd_prefill_chunks_total", "moe_pairs_total",
              "moe_experts_hit_total", "moe_prefill_held_experts_hit_total",
              "decode_kv_tokens_total"):
        assert counters.get(c, 0) > 0, c


def test_another_configurations_run_is_unharmed_by_the_new_readers():
    """Asked of a run without their configuration's keys, their op or
    their counter, the new readers return None."""
    other = json.load(open(os.path.join(
        ROOT, "benchmark", "configs", "jamba2-3b-serve.json")))
    run = {"config": other, "device": {"kind": "TPU v5 lite"}, "cell": CELL,
           "counters": {"prefill_tokens_total": 5.0}, "trace": {
        "modules": {"jit_paged_decode_chunk": [1.0, 10.0]},
        "ops": {"_ssd_step.8": [0.1, 99.0], "gmm.3": [0.2, 50.0]},
        "slice_counters": {"decode_kv_tokens_total": 1e6,
                           "ssm_decode_lane_steps_total": 1e4,
                           "ssd_prefill_chunks_total": 1e2,
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
# ~3,000 cached tokens, ~120 held experts hit a layer-step, and 20
# prefill chunks of 1,024 tokens that hit all 128 a layer.
STEPS, LANES, CHUNKS = 240.0, 90.0, 20.0
DECODE_HIT, PREFILL_HIT = STEPS * 5 * 120, CHUNKS * 5 * 128
SLICE = {"ssm_decode_lane_steps_total": STEPS * LANES,
         "decode_kv_tokens_total": STEPS * LANES * 3000,
         "moe_experts_hit_total": DECODE_HIT + PREFILL_HIT,
         "moe_prefill_held_experts_hit_total": PREFILL_HIT,
         "ssd_prefill_chunks_total": CHUNKS * 8}
RUN = {
    "config": CONF, "device": {"kind": "TPU v5 lite"}, "counters": {},
    "trace": {"modules": {"jit_paged_decode_chunk": [5.0, 30.0],
                          "jit_paged_prefill": [0.8, CHUNKS]},
              "ops": {"gmm.3": [1.2, 100.0], "gmm.4": [1.2, 100.0],
                      "_ssd_step.8": [1.3, 1200.0],
                      "fusion.9": [0.3, 100.0]},
              "slice_counters": SLICE},
}
EXPERT = 2 * 1024 * 2688 * 2
SSD_STEP = 2 * 4 * 128 * 8192
ROUTER = 4096 * 512 + 512
STEP_WEIGHTS = (
    (5 * 109_640_064 + 35_655_680 + 5 * (54_530_560 - ROUTER)
     + 4096 * 32768 + 4096) * 2 + 5 * ROUTER * 4)
DECODE_BYTES = (STEPS * STEP_WEIGHTS + DECODE_HIT * EXPERT
                + STEPS * LANES * 3000 * 1024
                + STEPS * LANES * 5 * (SSD_STEP + 2 * 61_440))
CHUNK_FLOPS = 2 * (8 * 128 * 128 * 128
                   + 128 * (128 * 128 * 64 + 2 * 128 * 64 * 128))


@pytest.mark.parametrize("name, run, want", [
    ("kernel.ssd_step_bw", RUN,
     100 * STEPS * LANES * 5 * SSD_STEP / 1.3 / 819e9),
    ("kernel.ssd_step_bw",
     dict(RUN, trace=dict(RUN["trace"], slice_counters={})), None),
    ("kernel.ssd_step_bw",
     dict(RUN, trace=dict(RUN["trace"], ops={"fusion.9": [0.3, 1.0]})), None),
    ("kernel.moe_gmm_bw.latent", RUN,
     100 * (DECODE_HIT + PREFILL_HIT) * EXPERT / 2.4 / 819e9),
    ("kernel.moe_gmm_bw.latent",
     dict(RUN, trace=dict(RUN["trace"], ops={"fusion.9": [0.3, 1.0]})), None),
    ("step.decode_latent_moe_bw", RUN, 100 * DECODE_BYTES / 5.0 / 819e9),
    ("step.decode_latent_moe_bw",
     dict(RUN, trace=dict(RUN["trace"], slice_counters={})), None),
    ("step.decode_latent_moe_bw",
     dict(RUN, trace=dict(RUN["trace"], modules={})), None),
])
def test_new_readers_on_a_hand_made_run(name, run, want):
    got = metric_files.load(name).read(run)
    if want is None:
        assert got is None
    else:
        assert got == pytest.approx(want, rel=1e-9)
        assert 0 < got <= 100.0


def test_the_prefill_scan_reader_reads_its_scope(monkeypatch):
    from benchmark import scope_table

    reader = metric_files.load("ssd.prefill_flops")
    table = {"paged_prefill": {"mixer/ssd_chunk": [0.05, 900],
                               "mixer/mamba2": [0.2, 400],
                               "moe/moe_routed": [0.3, 50]},
             "paged_decode_chunk": {"mixer/ssd_step": [1.0, 10]}}
    monkeypatch.setattr(scope_table, "table", lambda run: table)
    got = reader.read(dict(RUN, cell=CELL))
    assert got == pytest.approx(
        100 * CHUNKS * 8 * 5 * CHUNK_FLOPS / 0.05 / 197e12, rel=1e-9)
    assert 0 < got <= 100.0
    monkeypatch.setattr(scope_table, "table", lambda run: {})
    assert reader.read(dict(RUN, cell=CELL)) is None
    monkeypatch.setattr(scope_table, "table", lambda run: table)
    assert reader.read(dict(
        RUN, cell=CELL, trace=dict(RUN["trace"], slice_counters={}))) is None


def test_costs_nemotron_against_hand_counts():
    # ISSUE 60's arithmetic.
    per = costs_nemotron.layer_params(CONF)
    assert per["mixer"] == 109_640_064 == (
        76_021_760 + 51_200 + 384 + 8_192 + 33_554_432 + 4_096)
    assert per["attention"] == 35_655_680
    assert per["expert_layer"] == 54_530_560 == (
        2_097_664 + 8_388_608 + 44_040_192 + 4_096)
    assert per["expert"] == 5_505_024
    assert costs_nemotron.total_params(CONF) == 4_648_163_712
    full = dict(CONF, **{k: CONF["published"][k] for k in (
        "num_hidden_layers", "hybrid_override_pattern", "vocab_size")},
        experts_held=512)
    assert costs_nemotron.total_params(full) == CONF["published"][
        "parameters"] == 120_668_707_840
    assert costs_nemotron.expert_bytes(CONF) == EXPERT
    assert costs_nemotron.kv_bytes_per_token(CONF) == 1024
    assert costs_nemotron.ssd_step_bytes(CONF) == SSD_STEP == 2 * 4_194_304
    assert costs_nemotron.conv_bytes_per_lane(CONF) == 61_440
    assert costs_nemotron.ssd_chunk_flops(CONF) == CHUNK_FLOPS
    assert costs_nemotron.step_weight_bytes(CONF) == STEP_WEIGHTS
    mem, lay = CONF["memory"], CONF["layout"]
    # routers with their bias float32, A_log / D / dt_bias float32
    assert mem["weights_bytes"] == 4_648_163_712 * 2 + 5 * (
        ROUTER + 3 * 128) * 2
    assert mem["state_bytes_per_slot"] == 5 * (4_194_304 + 61_440)
    assert mem["state_bytes"] == lay["num_slots"] * mem["state_bytes_per_slot"]
    assert mem["num_pages"] == (
        lay["num_slots"] * lay["max_ctx"] // lay["page_size"]) == 12672
    assert "num_pages" not in lay
    assert mem["kv_bytes"] == mem["num_pages"] * lay["page_size"] * 1024
    assert mem["pool_bytes"] == mem["state_bytes"] + mem["kv_bytes"]
    # a full step: the state is about a third of the bytes, the experts
    # about half (ISSUE 60: 31 % and 53 %)
    one = costs_nemotron.decode_bytes(
        CONF, steps=1, experts_hit=5 * 126, kv_tokens=96 * 3000,
        lane_steps=96)
    state = 96 * 5 * SSD_STEP
    assert 0.28 < state / one < 0.34 and 0.50 < 5 * 126 * EXPERT / one < 0.56
    # the pre-agreed rule: 96 slots where arguments and the larger
    # program's temporaries leave 1.0 GB of the 16.91 a program may use
    total = mem["arguments_decode_bytes"] + max(
        mem["temporaries_decode_bytes"], mem["temporaries_prefill_bytes"])
    assert 0.6 * 16.91e9 < total < 16.91e9 - 1.0e9 and lay["num_slots"] == 96


def test_manifest_entries_for_the_cell_by_name():
    """By NAME: nothing here asks where in a list an entry lies."""
    m = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    cells = {w["name"]: w for w in m["workloads"]}
    cell = cells[CELL]
    assert cell["chips"] == 1 and cell["config"] == CONFIG
    assert cell["traffic"] == "agent-reasoning" and len(cell["why"]) <= 200
    conf = {c["name"]: c for c in m["configs"]}[CONFIG]
    assert conf["source"] == CONF["source"] and len(conf["why"]) <= 200
    assert conf["reduced"] == CONF["reduced"] == [
        "num_hidden_layers", "hybrid_override_pattern", "n_routed_experts",
        "vocab_size"]
    assert conf["file"] == f"benchmark/configs/{CONFIG}.json"
    by_name = {e["name"]: e for e in m["per_layer"]}
    for name in NEW:
        assert by_name[name]["workloads"] == [CELL]
        assert by_name[name]["moves"] == "serve_tok_s"
        assert by_name[name]["unit"] == "%"
        assert by_name[name]["source"] == "device_trace"
        assert metric_files.load(name).LAYER == by_name[name]["layer"]
    for name in SHARED:
        assert CELL in by_name[name]["workloads"]
        assert by_name[name]["moves"] == "serve_tok_s"
    # their readers reckon another model's bytes: not this cell's
    for name in ("step.decode_state_bw", "step.decode_hybrid_bw",
                 "kernel.ssm_scan_bw", "kernel.moe_gmm_bw.hybrid",
                 "kernel.ragged_paged_bw.hybrid", "kernel.ragged_paged_bw"):
        assert CELL not in by_name[name]["workloads"]
    e2e = {e["name"]: e for e in m["end_to_end"]}
    assert CELL in e2e["serve_tok_s"]["workloads"]
    assert "workloads" not in e2e["setup_s"]
    wl = json.load(open(os.path.join(
        ROOT, "benchmark", "workloads", CELL + ".json")))
    assert wl["config"] == CONFIG and wl["runner"] == "serve_reasoning_moe"
    t = wl["traffic"]
    assert (t["clients"], t["start_gap_s"], t["turns"],
            t["max_requests_per_client_s"]) == (96, 0.05, [1], 0.1)
    assert t["clients"] == CONF["layout"]["num_slots"]
    assert t["system_tokens"] == 128 and "first_token_limit_s" not in t
    assert t["user_tokens"] == {"kind": "lognormal", "median": 768,
                                "sigma": 0.9, "min": 128, "max": 4096}
    assert t["max_tokens"] == {"kind": "lognormal", "median": 2048,
                               "sigma": 0.5, "min": 768, "max": 4096}
    assert t["max_session_tokens"] == 8400 < CONF["layout"]["max_ctx"]
    assert t["check_sample_kinds"] == [
        "long_answer", "multi_chunk", "one_chunk"]
    assert wl["trace_seconds"] == 3.0


def test_configuration_file_keeps_every_published_key():
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog beside the guides here")
    row = next(json.loads(ln) for ln in open(CATALOG)
               if '"NVIDIA-Nemotron-3-Super-120B-A12B-BF16"' in ln)
    assert CONF["source"] == row["source_url"]
    cut = {"num_hidden_layers": 11, "vocab_size": 32768,
           "hybrid_override_pattern":
               row["config"]["hybrid_override_pattern"][:11]}
    assert {k for k, v in row["config"].items()
            if CONF.get(k, "absent") != cut.get(k, v)} == set()
    assert set(cut) < set(CONF["reduced"])
    assert CONF["hybrid_override_pattern"] == "MEMEMEM*EME"
    pub = CONF["published"]
    assert pub["hybrid_override_pattern"] == row["config"][
        "hybrid_override_pattern"]
    assert (pub["num_hidden_layers"], pub["n_routed_experts"],
            pub["vocab_size"]) == (88, 512, 131072)
    assert (CONF["experts_held"], CONF["chips_sharing_a_layer"],
            CONF["pipeline_stages"]) == (128, 4, 8)
    for key in ("no_position_term", "router_input", "shared_expert", "dt",
                "mixer_init", "norm"):
        assert key in CONF["assumed"]
    assert "multi_token_prediction" in CONF["not_run"]
    lay = CONF["layout"]
    assert (lay["preset"], lay["num_layers"], lay["num_slots"],
            lay["page_size"], lay["max_ctx"], lay["prefill_chunk"],
            lay["decode_chunk"], lay["dtype"], lay["attn_impl"]) == (
        "nemotron3_super_ep4", 11, 96, 64, 8448, 1024, 8, "bfloat16",
        "pallas")
    assert lay["prefix_cache"] is False and "prefix_cache_note" in lay


def test_holder_builds_the_config_and_refuses_another_geometry():
    from benchmark.reference import nemotron_h_ref
    from benchmark.runners import serve_reasoning_moe_holder as child

    cfg = child.build_config(CONF)
    llm = cfg.llm
    assert (llm.num_layers, llm.vocab_size, llm.held) == (
        11, 32768, (0, 128))
    assert cfg.vision is None and cfg.attn_impl == "pallas"
    assert llm.layer_kinds.count("mamba2") == 5 and llm.moe_layers == 5
    for key, bad in (("mamba_num_heads", 64), ("n_groups", 4),
                     ("ssm_state_size", 64), ("moe_latent_size", 2048),
                     ("n_routed_experts", 256), ("experts_held", 512),
                     ("hidden_size", 2048), ("num_key_value_heads", 8),
                     ("routed_scaling_factor", 1),
                     ("hybrid_override_pattern", "MEMEMEM*EMM"),
                     ("mlp_hidden_act", "silu"), ("chunk_size", 256)):
        with pytest.raises(SystemExit, match=key):
            child.build_config(dict(CONF, **{key: bad}))
    sz = child.ref_sizes(CONF, cfg)
    assert sz == nemotron_h_ref.sizes_from_keys(CONF)
    assert (sz["pattern"], sz["held"], sz["top_k"], sz["scale"]) == (
        "MEMEMEM*EME", (0, 128), 22, 5.0)


def test_the_runner_and_its_holder_keep_the_process_rules():
    """`test_bench_no_process_left*`'s rules for the ninth process that
    holds a chip: started through the one Popen, tied to its parent
    before jax, no command loop of its own."""
    from benchmark.runners import serve, serve_reasoning_moe as runner

    assert issubclass(runner.Child, serve.Child)
    assert runner.Child.script == "serve_reasoning_moe_holder.py"
    assert set(vars(runner.Child)) <= {
        "script", "__module__", "__doc__", "__qualname__",
        "__firstlineno__", "__static_attributes__"}
    src = open(runner.__file__).read()
    assert "Popen" not in src and "import jax" not in src
    src = open(os.path.join(os.path.dirname(runner.__file__),
                            runner.Child.script)).read()
    main = src[src.index("def main("):]
    tie = main.index("lifeline.tie_to_parent(args.parent_pid)")
    assert tie < main.index("import jax")
    assert tie < main.index("from benchmark import program")
    assert "import jax" not in src[:src.index("def main(")]
    assert "lifeline.serve_until_stopped(srv" in src
    assert "return lifeline.ORPHANED" in src
    assert "sys.stdin:" not in src and "def serve_commands" not in src
    assert "subprocess" not in src


def test_the_sample_takes_one_request_of_each_kind():
    from benchmark.runners import serve_reasoning_moe_holder as child

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
        ({"n": 30}, 20, Handle(20)), ({"n": 50}, 40, Handle(40)),
        ({"n": 20}, 60, Handle(60, done=False)), ({"n": 45}, 12, Handle(12)),
        ({"n": 10}, 9, Handle(9)), ({"n": 12}, 9, Handle(8)),
        ({"n": 70}, 35, Handle(35)),
    ]
    prompts, streams, what = child.sample_served(
        served, Pipe(), long_answer=30, prefill_chunk=32, max_positions=500)
    assert [(w["kind"], w["prompt_tokens"], w["served_tokens"])
            for w in what] == [
        ("long_answer", 50, 40), ("multi_chunk", 45, 12),
        ("one_chunk", 10, 9)]
    assert [len(p) for p in prompts] == [50, 45, 10]
    assert [len(s) for s in streams] == [40, 12, 9]
    *_, what = child.sample_served(
        served, Pipe(), long_answer=30, prefill_chunk=32, max_positions=110)
    # the longest answer that FITS, then what still does
    assert [(w["kind"], w["prompt_tokens"]) for w in what] == [
        ("long_answer", 50), ("one_chunk", 10)]


# --- the comparison against programs that must fail it (CPU, tiny) ---------

FAILS_ON_THE_CPU = {
    "a bfloat16 state": "state",
    "the scale 5 left out": "experts",
}


def test_the_controls_fail_the_comparison_and_the_program_passes():
    """tools/controls_nemotron.run_all at `nemotron3_tiny` in float32
    with the kernels scaled (tests/test_nemotron_h.py `_scaled`), two
    of the seven controls (each traces every program anew; the chip's
    run of all seven is in PERF.md section 6): each fails by the clause
    named for it, by the chip's own limits. One test, so that one worker
    traces the programs once."""
    import jax

    from oryx_tpu import config as cfg_lib
    from oryx_tpu.models import oryx

    from benchmark.tools import controls_nemotron
    from test_nemotron_h import _scaled, sizes_of

    cfg = cfg_lib.nemotron3_tiny()
    params = jax.jit(lambda k: oryx.init_params(cfg, k))(jax.random.key(0))
    params["llm"] = _scaled(params["llm"])
    assert set(FAILS_ON_THE_CPU) < set(
        controls_nemotron.controls(params, cfg)) and len(
        controls_nemotron.controls(params, cfg)) == 7
    readings = controls_nemotron.run_all(
        params, cfg, 7, sizes=sizes_of(cfg.llm), page_size=16,
        prefill_chunk=32, decode_chunk=4, max_ctx=512, head=4, tail=6,
        prompt_tokens=(40, 70, 9), decode_chunks=3,
        only=",".join(FAILS_ON_THE_CPU))
    r = readings["as served"]
    assert r["ok"] and all(r["passed"].values())
    assert r["head_rms_rel"] < 1e-5 and r["expert_rms_rel"] < 1e-5
    assert r["served_ref_agree"] == 1.0 and r["router_error"] < 1e-6
    assert len(readings) == 3
    for control, clause in FAILS_ON_THE_CPU.items():
        r = readings[control]
        assert not r["ok"] and not r["passed"][clause], (control, r)
    # a state rounded to bfloat16 hides in the logits: `state` alone
    r = readings["a bfloat16 state"]
    assert [c for c, ok in r["passed"].items() if not ok] == ["state"]
