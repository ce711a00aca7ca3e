"""The long-context session cell of the benchmark (GLM-5), off the chip:
its rehearsal through the harness in a temporary copy, traced and
untraced, the six readers it brings against a hand-made run, `costs_dsa`
against bytes and operations counted by hand, its session builder, the
comparison that decides `correct` against controls that must fail it,
and the manifest's entries (looked up by name: a later PR appends after
them). The chipless compile that holds the configuration's memory rule
is in tests/test_pallas_topology_compile.py, the one file that may load
the TPU's compiler."""

import json
import os
import shutil

import pytest

from benchmark import costs_dsa, metric_files
from benchmark.runners import serve_longctx
from test_bench_rehearsal_train import LINE_KEYS, ROOT, last_line, run_cell

CELL = "glm-5.long-sessions"
CONFIG = "glm-5-ep16-serve"
NEW = ("kernel.index_score_bw", "kernel.sparse_latent_bw",
       "attn.select_share", "attn.selected_share", "step.decode_sparse_bw",
       "step.prefill_flops.dsa")
SHARED = ("sched.decode_util.batch", "sched.ttft_p90_ms.batch",
          "sched.tpot_p90_ms", "step.decode_ms.batch",
          "step.prefill_ms_ktok.batch", "sched.host_ms_per_dispatch.batch",
          "sched.queue_wait_ms.batch", "sched.admission_ms.batch",
          "idle.named_share.batch", "idle.unexplained_share.batch",
          "sched.starved_share.batch", "sched.copy_out_ms.batch",
          "sched.stall_s.batch", "moe.expert_imbalance",
          "moe.held_hit_share", "cache.prefix_hit_share.batch")
# Their cost files count every live latent, no indexer, no dense layer.
NOT_THIS_MODELS = ("kernel.latent_paged_bw", "kernel.latent_paged_bw.single",
                   "step.decode_weight_bw.batch",
                   "step.decode_weight_bw.single", "step.prefill_flops",
                   "kernel.moe_gmm_bw.held")
CONF = json.load(open(os.path.join(
    ROOT, "benchmark", "configs", CONFIG + ".json")))
WL = json.load(open(os.path.join(
    ROOT, "benchmark", "workloads", CELL + ".json")))


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    root = tmp_path_factory.mktemp("longctx") / "checkout"
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
    # Contexts re-sent with every turn come from the prefix cache.
    assert 30.0 < m["cache.prefix_hit_share.batch"]["value"] < 100.0
    assert 0.0 < m["moe.held_hit_share"]["value"] <= 100.0
    assert m["moe.expert_imbalance"]["value"] >= 1.0
    # 16 rows of contexts of 100-300 and their turns.
    assert 2.0 < m["attn.selected_share"]["value"] < 20.0
    # No device plane on the CPU: the trace readers find nothing, and
    # the dense latent cells' readers are not this cell's.
    for name in NOT_THIS_MODELS + tuple(
            n for n in NEW if n != "attn.selected_share"):
        assert name not in m
    info = json.loads(p.stdout.strip().splitlines()[-2])["info"]
    assert all(e["event"] != "logit_check" for e in info["setup"]["events"])
    check = info["setup"]["check_after_window"]
    kinds = [w["kind"] for w in check["sample"]]
    assert "cached_long" in kinds and len(kinds) == check["slots"] >= 2
    # A slot: the first token's logits + 2 chunks of 4 steps.
    assert check["ok"] and check["positions"] == check["slots"] * 9
    assert set(check["passed"]) == {"forced", "selection", "free", "experts",
                                    "served"}
    assert check["select_gap"] == 0.0 and check["select_agree"] == 1.0
    assert check["select_miscounted_rows"] == 0
    assert check["served_twin_agree"] == check["routing_agree"] == 1.0
    assert info["compiles_in_window"] == 0


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


# A slice of 10 decode dispatches of 8 steps: 80 steps x 4 expert layers
# x 16 held slots, 5 of 16 hit; 12 lanes of 36,000 tokens a step, 2,048
# rows of each selected. And 20 prefill chunks of 1024 at a prefix of
# 20,000: every query scores ~20.5k pairs and reads 2,048; 7 % of picks
# on a held expert.
STEPS = 80.0
KEYS = STEPS * 12 * 36000
ROWS = STEPS * 12 * 2048
TOKENS = 20 * 1024.0
INDEX_PAIRS = TOKENS * 20_512.0
SELECTED_PAIRS = TOKENS * 2048.0
SLICE = {"moe_held_experts_hit_total": STEPS * 4 * 5,
         "moe_held_expert_slots_total": STEPS * 4 * 16,
         "decode_kv_tokens_total": KEYS,
         "decode_selected_tokens_total": ROWS,
         "prefill_tokens_total": TOKENS,
         "prefill_index_pairs_total": INDEX_PAIRS,
         "prefill_selected_pairs_total": SELECTED_PAIRS,
         "moe_prefill_pairs_total": TOKENS * 4 * 8,
         "moe_prefill_held_rows_total": TOKENS * 4 * 8 * 0.07}
RUN = {
    "counters": {"decode_kv_tokens_total": 4.0e8,
                 "decode_selected_tokens_total": 2.4e7},
    "config": CONF, "device": {"kind": "TPU v5 lite"},
    "trace": {"modules": {"jit_paged_decode_chunk": [1.2, 10.0],
                          "jit_paged_prefill": [2.0, 12.0],
                          "jit_paged_prefill.1": [1.0, 8.0]},
              "ops": {"_dsa_index.3": [0.06, 400.0],
                      "sort.81": [0.3, 400.0], "sort.82": [0.02, 400.0],
                      "gather_fusion.4": [0.03, 400.0],
                      "_latent_paged.3": [0.05, 400.0],
                      "fusion.12": [0.5, 400.0]},
              "slice_counters": SLICE},
}
ATTN = (6144 * 2048 + 2048 * 64 * 256 + 6144 * 576 + 512 * 64 * 448
        + 64 * 256 * 6144)
INDEXER = 2048 * 32 * 128 + 6144 * 128 + 6144 * 32
EXPERT = 3 * 6144 * 2048
DENSE = 3 * 6144 * 12288
STEP_BYTES = (
    (5 * (ATTN + 2 * 6144 + 2048 + 512 + INDEXER + 256) + DENSE + 4 * EXPERT
     + 6144 + 6144 * 19360) * 2 + 4 * 6145 * 256 * 4)
DECODE_BYTES = (STEPS * STEP_BYTES + STEPS * 4 * 5 * EXPERT * 2
                + KEYS * 5 * 128 * 2 + ROWS * 5 * 576 * 2)
PER_TOKEN_ATTN = (6144 * 2048 + 2048 * 64 * 256 + 6144 * 576
                  + 64 * 256 * 6144 + 2048 * 4096 + 6144 * 128 + 6144 * 32)
PREFILL_FLOPS = 2 * (
    TOKENS * (5 * PER_TOKEN_ATTN + DENSE
              + 4 * (6144 * 256 + EXPERT + 8 * 0.07 * EXPERT))
    + 5 * (INDEX_PAIRS * 32 * 128 + SELECTED_PAIRS * 64 * 512))


def _without(*keys):
    sc = {k: v for k, v in SLICE.items() if k not in keys}
    return dict(RUN, trace=dict(RUN["trace"], slice_counters=sc))


@pytest.mark.parametrize("name, run, want", [
    ("kernel.index_score_bw", RUN,
     100 * KEYS * 5 * 128 * 2 / 0.06 / 819e9),
    ("kernel.index_score_bw", _without("decode_kv_tokens_total"), None),
    ("kernel.sparse_latent_bw", RUN,
     100 * ROWS * 5 * 576 * 2 / (0.05 + 0.03) / 819e9),
    ("kernel.sparse_latent_bw", _without("decode_selected_tokens_total"),
     None),
    ("attn.select_share", RUN,
     100 * (0.06 + 0.3 + 0.02 + 0.03 + 0.05) / 1.2),
    ("attn.select_share", dict(RUN, trace=dict(RUN["trace"], ops={
        "_latent_paged.3": [0.05, 400.0]})), None),
    ("attn.selected_share", RUN, 100 * 2.4e7 / 4.0e8),
    ("attn.selected_share", dict(RUN, counters={
        "decode_kv_tokens_total": 4.0e8}), None),
    ("step.decode_sparse_bw", RUN, 100 * DECODE_BYTES / 1.2 / 819e9),
    ("step.decode_sparse_bw", _without("decode_selected_tokens_total"), None),
    ("step.prefill_flops.dsa", RUN, 100 * PREFILL_FLOPS / 3.0 / 197e12),
    ("step.prefill_flops.dsa", _without("prefill_selected_pairs_total"),
     None),
    ("step.prefill_flops.dsa", dict(RUN, trace={}), None),
])
def test_new_readers_on_a_hand_made_run(name, run, want):
    """A program without the spans or counters (the parent commit) gives
    nothing and does not raise; with them the share is the hand count."""
    got = metric_files.load(name).read(run)
    if want is None:
        assert got is None
    else:
        assert got == pytest.approx(want, rel=1e-9) and 0 < got < 100


def test_every_new_metric_has_a_reader_file_of_its_own():
    for name in NEW:
        path = os.path.join(ROOT, "benchmark", "layer_metrics", name + ".py")
        assert os.path.exists(path), name


def test_costs_dsa_against_hand_counted_bytes_and_flops():
    c = CONF
    assert costs_dsa.layers(c) == (1, 4)
    assert costs_dsa.attention_params(c) + costs_dsa.indexer_params(c) == (
        174_406_400)  # the configuration file's arithmetic, a layer
    assert costs_dsa.index_key_bytes(c, kv_tokens=1000) == 1000 * 5 * 256
    assert costs_dsa.selected_row_bytes(c, selected_tokens=1000) == (
        1000 * 5 * 1152)
    assert costs_dsa.step_weight_bytes(c) == STEP_BYTES
    # 2.76 GB a step before any held expert is hit (ISSUE 49's 4.3 GB
    # counts ~5 of 16 held experts a layer with it: 1.5 GB).
    assert 2.7e9 < STEP_BYTES < 2.8e9
    assert STEP_BYTES + 4 * 5 * EXPERT * 2 == pytest.approx(4.27e9, rel=1e-2)
    assert costs_dsa.decode_steps(c, held_slots=STEPS * 4 * 16) == STEPS
    assert costs_dsa.decode_bytes(
        c, steps=STEPS, held_hit=STEPS * 4 * 5, kv_tokens=KEYS,
        selected_tokens=ROWS) == pytest.approx(DECODE_BYTES, rel=1e-12)
    assert costs_dsa.prefill_flops(
        c, tokens=TOKENS, index_pairs=INDEX_PAIRS,
        selected_pairs=SELECTED_PAIRS, held_share=0.07
    ) == pytest.approx(PREFILL_FLOPS, rel=1e-12)
    # ISSUE 49's reckoning: 12 lanes at 36k read 0.55 GB of index keys
    # and 0.14 GB of selected rows a step where dense attention reads
    # 2.49 GB of latents.
    assert costs_dsa.index_key_bytes(c, kv_tokens=12 * 36000) == (
        pytest.approx(0.553e9, rel=1e-2))
    assert costs_dsa.selected_row_bytes(c, selected_tokens=12 * 2048) == (
        pytest.approx(0.1416e9, rel=1e-2))
    assert 12 * 36000 * 5 * 1152 == pytest.approx(2.49e9, rel=1e-2)


def test_manifest_entries_for_the_cell():
    m = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    cell = next(w for w in m["workloads"] if w["name"] == CELL)
    assert cell["chips"] == 1 and cell["config"] == CONFIG
    assert cell["traffic"] == "long-sessions" and len(cell["why"]) <= 200
    conf = next(c for c in m["configs"] if c["name"] == CONFIG)
    assert conf["source"] == CONF["source"] and len(conf["why"]) <= 200
    assert conf["file"] == "benchmark/configs/" + CONFIG + ".json"
    assert conf["reduced"] == ["num_hidden_layers", "first_k_dense_replace",
                               "n_routed_experts", "vocab_size"]
    assert set(conf["reduced"]) == set(CONF["reduced"])
    by_name = {e["name"]: e for e in m["per_layer"]}
    for name in NEW:
        e = by_name[name]
        assert e["workloads"] == [CELL] and e["moves"] == "serve_tok_s"
        assert e["unit"] == "%"
        assert metric_files.load(name).LAYER == e["layer"]
    for name in SHARED:
        assert CELL in by_name[name]["workloads"]
        assert by_name[name]["moves"] == "serve_tok_s"
    for name in NOT_THIS_MODELS:
        assert CELL not in by_name[name]["workloads"]
    e2e = {e["name"]: e for e in m["end_to_end"]}
    assert CELL in e2e["serve_tok_s"]["workloads"]
    assert "workloads" not in e2e["setup_s"]
    assert WL["config"] == CONFIG and WL["runner"] == "serve_longctx"
    t = WL["traffic"]
    assert (t["clients"], t["start_gap_s"], t["turns"]) == (
        12, 0.05, [8, 10, 12])
    assert t["context_tokens"] == {
        "kind": "lognormal", "median": 24000, "sigma": 0.5, "min": 12288,
        "max": 49152}
    assert t["user_tokens"] == {
        "kind": "lognormal", "median": 384, "sigma": 0.8, "min": 64,
        "max": 2048}
    assert t["max_tokens"] == {"kind": "uniform", "min": 384, "max": 1024}
    assert "first_token_limit_s" not in t and t["warm_previous_turn"] is True
    assert t["max_session_tokens"] == 65400
    lay = CONF["layout"]
    assert t["clients"] == lay["num_slots"] == 12
    assert (lay["max_ctx"], lay["page_size"], lay["prefill_chunk"],
            lay["decode_chunk"], lay["prefix_cache"], lay["kv_dtype"],
            lay["ragged"], lay["attn_impl"]) == (
        65536, 64, 1024, 8, True, "bf16", False, "pallas")
    assert t["max_session_tokens"] < lay["max_ctx"]
    assert WL["trace_seconds"] == 3.0


def test_configuration_file_keeps_every_published_number():
    """The catalog's `config` under the same keys; depth, the leading
    dense layers and the vocabulary rows held here are the chip's share,
    and the experts are held, not cut."""
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog on this machine")
    row = next(r for r in map(json.loads, open(catalog))
               if r["name"] == "GLM-5")
    assert CONF["source"] == row["source_url"]
    cut = {"num_hidden_layers": 5, "first_k_dense_replace": 1,
           "vocab_size": 19360}
    for key, value in row["config"].items():
        assert CONF[key] == cut.get(key, value), key
    assert set(CONF["reduced"]) == set(cut) | {"n_routed_experts"}
    assert CONF["experts_held"] == 16 and CONF["chips_sharing_a_layer"] == 16
    for key in ("assumed", "not_run", "stands_for", "unused"):
        assert CONF[key]
    assert set(CONF["not_run"]) == {"multi_token_prediction",
                                    "indexer_hadamard_and_fp8"}
    for width in ("hidden_size", "kv_lora_rank", "q_lora_rank",
                  "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
                  "index_head_dim", "index_n_heads", "index_topk",
                  "moe_intermediate_size", "intermediate_size",
                  "num_experts_per_tok"):
        assert width not in CONF["reduced"]


def test_child_refuses_a_geometry_the_program_would_not_run():
    from benchmark.runners import serve_longctx_child as child

    conf = {k: v for k, v in CONF.items() if k != "rehearse"}
    cfg = child.build_config(conf)
    assert cfg.llm.num_layers == 5 and cfg.llm.dense_layers == 1
    assert cfg.llm.held == (0, 16) and cfg.llm.index_topk == 2048
    for key, bad in (("index_topk", 1024), ("index_n_heads", 16),
                     ("scoring_func", "softmax"), ("kv_lora_rank", 256),
                     ("first_k_dense_replace", 3), ("experts_held", 32),
                     ("hidden_size", 4096)):
        with pytest.raises(SystemExit, match=key):
            child.build_config(dict(conf, **{key: bad}))
    with pytest.raises(SystemExit, match="rope_type"):
        child.build_config(dict(conf, rope_parameters={
            "rope_theta": 1000000, "rope_type": "yarn"}))
    with pytest.raises(SystemExit, match="no preset"):
        child.build_config(dict(conf, layout=dict(
            conf["layout"], preset="glm5_ep99")))


def _prompt_tokens(body):
    return sum(len(m["content"]) + 1 for m in body["messages"])


def test_no_id_the_model_emits_is_the_templates_stop():
    """The program ends a lane when the ids it emitted match the chat
    template's stop string as the cell's tokenizer encodes it.
    PrefixTokenizer gives that string an id the seeded model emits (one
    greedy token in `vocab_size`: a request in twenty ended early, at
    another place at every seed); the tokenizer the cell's child builds
    gives it one past the head's rows and the preset's EOS id, and every
    other text PrefixTokenizer's ids."""
    import numpy as np

    from benchmark.runners import serve_latent_child, serve_longctx_child
    from oryx_tpu.conversation import conv_templates
    from oryx_tpu.models import generate

    hi = CONF["vocab_size"]
    stop = conv_templates["plain"].stop_str
    plain = serve_latent_child.PrefixTokenizer(hi)
    tok = serve_longctx_child.NoStopPrefixTokenizer(hi)
    assert 3 <= plain.encode(stop)[0] < hi  # what went wrong
    rows = np.asarray(generate.make_stop_sequences([stop], tok))
    assert rows.shape == np.asarray(
        generate.make_stop_sequences([stop], plain)).shape  # same programs
    ids = rows[rows >= 0]
    assert ids.size == len(stop) and (ids > hi).all()
    text = "0123456789abcdef a context\nand a question\n"
    assert tok.encode(text) == plain.encode(text)
    assert max(tok.encode(text)) < hi


def test_a_request_that_stops_early_makes_the_run_incorrect(checkout):
    """`serve_longctx.run` holds the window to the same work at every
    seed: a record with `finish` "stop" is a problem on the line. The
    rehearsal in the temporary copy, with one finished record of the
    window altered where the load generator hands them over."""
    import subprocess
    import sys

    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=ROOT)
    code = (
        "import sys; sys.argv = ['run.py', '--workload', %r, '--seed', '7', "
        "'--rehearse', '1']\n"
        "from benchmark import loadgen, run\n"
        "real = loadgen.run_closed_loop\n"
        "def stopped(*a, **kw):\n"
        "    res = real(*a, **kw)\n"
        "    if not kw.get('until_done'):\n"
        "        done = [r for r in res['records'] if r.get('t_done')]\n"
        "        done[0]['finish'] = 'stop'\n"
        "    return res\n"
        "loadgen.run_closed_loop = stopped\n"
        "sys.exit(run.main())\n" % CELL)
    p = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=env, timeout=240, cwd=checkout)
    line = last_line(p)
    assert line["correct"] is False
    assert any("ended before their max_tokens" in x
               for x in line["problems"]), line["problems"]


def test_sessions_end_before_the_limit_and_open_the_window_on_both_kinds():
    """Every seed sends the same lengths from the same places; a session
    ends before 65,400 positions; the window opens with a cold context
    over 16,384 tokens at the head of one client's list and a later turn
    over one at the head of another's (the sample kinds the cell
    requires), and a later turn under 16,384 too."""
    p = WL["traffic"]
    shapes = []
    for seed in (3, 2**31 + 11):
        window, before = serve_longctx.client_lists(p, seed)
        shapes.append([[(_prompt_tokens(b), b["max_tokens"],
                         len(b["messages"])) for b in c] for c in window])
        assert len(window) == 12 and 8 <= len(before) <= 12
        for b in before:
            assert b["max_tokens"] == 8 and len(b["messages"]) > 1
    assert shapes[0] == shapes[1]
    heads = [c[0] for c in shapes[0]]
    assert any(n > 16384 and turns == 1 for n, _, turns in heads)
    assert any(n > 16384 and turns > 1 for n, _, turns in heads)
    assert any(n <= 16384 and turns > 1 for n, _, turns in heads)
    sessions = serve_longctx.long_sessions(p, 3)
    assert len(sessions) == 36
    turns = [len(s) for s in sessions]
    assert min(turns) >= 1 and max(turns) == 12
    # ... and with less room a long context's session ends early.
    short = serve_longctx.long_sessions(dict(p, max_session_tokens=52000), 3)
    assert min(len(s) for s in short) < 8 and max(len(s) for s in short) == 12
    for s in short:
        assert _prompt_tokens(s[-1]) + s[-1]["max_tokens"] <= 52000
    for s in sessions:
        last = s[-1]
        assert _prompt_tokens(last) + last["max_tokens"] <= 65400
        first = _prompt_tokens(s[0])
        assert 12288 + 64 <= first <= 49152 + 2048 + 2
        # Every request re-sends the context and the history.
        for a, b in zip(s, s[1:]):
            assert b["messages"][: len(a["messages"])] == a["messages"]
    # A list is at least three times what a client is served in 50 s
    # (PERF.md section 4: the pace found).
    assert min(len(c) for c in shapes[0]) >= 20
    # About nine requests in ten are later turns.
    later = sum(t > 1 for c in shapes[0] for _, _, t in c)
    assert 0.85 < later / sum(len(c) for c in shapes[0]) < 0.95


@pytest.fixture(scope="module")
def readings():
    import jax

    from oryx_tpu import config as cfg_lib
    from oryx_tpu.models import oryx

    from benchmark.tools import controls_glm5

    cfg = cfg_lib.glm5_tiny()
    params = oryx.init_params(cfg, jax.random.key(3))
    params["llm"] = jax.tree.map(
        lambda a: a * 4 if a.ndim > 2 else a, params["llm"])
    controls_glm5.STALE = (32, 40)
    return controls_glm5.run_all(
        params, cfg, 2147483659, page_size=8, prefill_chunk=32,
        decode_chunk=4, max_ctx=512, prompt_tokens=(90, 12))


def test_the_comparison_passes_the_program_as_it_is(readings):
    r = readings["as served"]
    assert r["ok"] and r["routing_agree"] == 1.0
    assert r["forced_rms_rel"] < 1e-5 and r["free_rms_rel"] < 1e-5
    assert r["positions"] == 18 and r["select_gap"] == 0.0
    assert r["served_twin_agree"] == 1.0 and r["expert_rms_rel"] < 1e-5


@pytest.mark.parametrize("control, clause", [
    ("no selection (dense attention)", "selection"),
    ("the top 1,024 (half the keys)", "selection"),
    ("the most RECENT keys", "selection"),
    ("relu left out of the index scores", "selection"),
    ("the heads' weights left out", "selection"),
    ("no RoPE on the index keys", "selection"),
    ("a page of index keys stale after a prefix hit", "selection"),
    ("softmax for sigmoid", "experts"),
    ("the bias in the weights", "experts"),
    ("the scaling factor left out", "experts"),
    ("the dense layer's FFN left out", "forced"),
])
def test_the_comparison_fails_a_wrong_program(readings, control, clause):
    r = readings[control]
    assert r["clause"] == clause
    assert not r["ok"] and not r["passed"][clause], r


@pytest.mark.parametrize("control, reading", [
    ("index keys in fp8", "select_gap"),
    ("fp8 (e4m3) weights outside the held experts", "forced_rms_rel"),
])
def test_a_step_down_in_precision_reads_far_above_the_program(
        readings, control, reading):
    """In float32 at the tiny size each of these reads a thousand times
    the program as it is or more; whether it reads over the LIMIT is a
    matter of the published widths in bf16, on the chip (PERF.md
    section 6, PR 49)."""
    served, r = readings["as served"], readings[control]
    assert r[reading] > 1000 * max(served[reading], 1e-9)
