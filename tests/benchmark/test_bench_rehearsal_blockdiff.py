"""The block-diffusion cell of the benchmark, off the chip: its
rehearsal through the harness in a temporary copy, the six readers it
brings against a hand-made run, `costs_moe` against bytes counted by
hand, its tokenizer, the comparison that decides `correct` against
seven programs that must fail it, and the manifest's entries."""

import dataclasses
import json
import os
import shutil

import pytest

from benchmark import costs_moe, metric_files
from test_bench_rehearsal_train import LINE_KEYS, ROOT, last_line, run_cell

CELL = "sdar-30b-a3b.blockgen"
NEW = ("diff.tokens_per_forward", "diff.commit_share",
       "step.block_forward_ms", "moe.expert_imbalance", "moe.step_weight_bw",
       "kernel.moe_gmm_bw")
SHARED = ("sched.decode_util.batch", "sched.ttft_p90_ms.batch",
          "sched.tpot_p90_ms", "step.prefill_ms_ktok.batch",
          "sched.host_ms_per_dispatch.batch", "sched.queue_wait_ms.batch",
          "sched.admission_ms.batch", "idle.named_share.batch")


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    root = tmp_path_factory.mktemp("blockdiff") / "checkout"
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
    # B = 4, T = 2: a block is two denoising forwards, its commit rides
    # the next block's first (PR 39) and no forward only commits; a
    # prompt's tail can leave a first block fewer positions than forwards.
    assert 1.0 < m["diff.tokens_per_forward"]["value"] <= 4 / 2 + 1e-9
    assert 0.0 <= m["diff.commit_share"]["value"] < 1.0
    assert m["moe.expert_imbalance"]["value"] >= 1.0
    assert 0 < m["sched.decode_util.batch"]["value"] <= 100
    # No device plane on the CPU: the trace readers find nothing.
    assert "step.block_forward_ms" not in m and "moe.step_weight_bw" not in m
    info = json.loads(p.stdout.strip().splitlines()[-2])["info"]
    check = next(e for e in info["setup"]["events"]
                 if e["event"] == "logit_check")
    # Four slots (prompt tails 2, 3, 1, 0) x 3 blocks: 6 + 5 + 6 + 6
    # denoising forwards with a mask left, B rows each.
    assert check["ok"] and check["positions"] == 23 * 4
    assert check["slots"] == 4 and all(check["passed"].values())
    assert check["routing_agree"] == 1.0
    assert info["compiles_in_window"] == 0


def test_a_program_without_the_preset_leaves_at_once(checkout, tmp_path):
    """What the parent commit does with this cell: the child names the
    missing preset and exits before it touches a device."""
    conf = os.path.join(checkout, "benchmark", "configs",
                        "sdar-30b-a3b-serve.json")
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


CONF = json.load(open(os.path.join(
    ROOT, "benchmark", "configs", "sdar-30b-a3b-serve.json")))
FWD = 'diffusion_forwards_total{kind="%s"}'
COUNTERS = {
    "decode_steps_total": 96.0 * 32, "decode_steps_useful": 90.0 * 30,
    "diffusion_tokens_unmasked_total": 3600.0,
    "diffusion_forwards_total": 96.0, FWD % "denoise": 64.0,
    FWD % "commit": 32.0,
    "moe_expert_rows_max_total": 17.0 * 672, "moe_expert_rows_mean_total":
    8.0 * 672, "moe_experts_hit_total": 670.0 * 128,
}
SLICE = {"diffusion_forwards_total": 9.0, FWD % "denoise": 6.0,
         FWD % "commit": 3.0, "moe_experts_hit_total": 9.0 * 7 * 128}
RUN = {
    "counters": COUNTERS, "config": CONF,
    "device": {"kind": "TPU v5 lite"},
    "trace": {"modules": {"jit_paged_block_step": [0.18, 3.0],
                          "jit_paged_prefill": [0.05, 2.0]},
              "ops": {"gmm.26": [0.06, 63.0], "gmm.29": [0.05, 63.0],
                      "_ragged_paged.26": [0.06, 63.0]},
              "slice_counters": SLICE},
}


def _hand_bytes(forwards_with_head, forwards_without, hit, rows=None):
    """Weight bytes by hand, from the published widths (`rows` lanes a
    forward look their embeddings up: the cell's slots x 4)."""
    rows = rows or CONF["layout"]["num_slots"] * 4
    attn = 2048 * 4096 + 2 * 2048 * 512 + 4096 * 2048 + 2 * 2048 + 2 * 128
    fixed = 7 * (attn * 2 + 2048 * 128 * 4) + rows * 2048 * 2 + 2048 * 2
    head = 2048 * 151936 * 2
    expert = 3 * 2048 * 768 * 2
    n = forwards_with_head + forwards_without
    return n * fixed + forwards_with_head * head + hit * expert


@pytest.mark.parametrize("name,run,want", [
    ("diff.tokens_per_forward", RUN, 3600.0 / 2700.0),
    ("diff.tokens_per_forward", {"counters": {"decode_steps_useful": 5.0}},
     None),  # a program before PR 26: no such counter
    ("diff.tokens_per_forward", {"counters": {}}, None),
    ("diff.commit_share", RUN, 100.0 / 3),
    ("diff.commit_share", {"counters": {}}, None),
    ("step.block_forward_ms", RUN, 20.0),
    ("step.block_forward_ms", dict(RUN, trace={
        "modules": {}, "slice_counters": SLICE}), None),
    ("step.block_forward_ms", dict(RUN, trace={
        "modules": RUN["trace"]["modules"], "slice_counters": {}}), None),
    ("moe.expert_imbalance", RUN, 17.0 / 8.0),
    ("moe.expert_imbalance", {"counters": {}}, None),
    ("moe.step_weight_bw", RUN,
     100.0 * _hand_bytes(6, 3, 9 * 7 * 128) / 0.18 / 819e9),
    # since PR 39 no forward only commits: the series is there and 0,
    # a count like any other (every forward has a head)
    ("moe.step_weight_bw", dict(RUN, trace={
        "modules": RUN["trace"]["modules"],
        "slice_counters": dict(SLICE, **{FWD % "denoise": 9.0,
                                         FWD % "commit": 0.0})}),
     100.0 * _hand_bytes(9, 0, 9 * 7 * 128) / 0.18 / 819e9),
    # a program without the series (before PR 26): nothing to bill
    ("moe.step_weight_bw", dict(RUN, trace={
        "modules": RUN["trace"]["modules"],
        "slice_counters": {k: v for k, v in SLICE.items()
                           if k != FWD % "commit"}}), None),
    ("moe.step_weight_bw", dict(RUN, trace={
        "modules": {}, "slice_counters": SLICE}), None),
    ("moe.step_weight_bw", dict(RUN, trace={
        "modules": RUN["trace"]["modules"], "slice_counters": {}}), None),
    ("kernel.moe_gmm_bw", RUN,
     100.0 * 9 * 7 * 128 * 3 * 2048 * 768 * 2 / 0.11 / 819e9),
    ("kernel.moe_gmm_bw", dict(RUN, trace={  # XLA's ragged-dot: no kernel
        "ops": {"ragged-dot-none.1": [0.2, 63.0]},
        "slice_counters": SLICE}), None),
    ("kernel.moe_gmm_bw", dict(RUN, trace={
        "ops": RUN["trace"]["ops"], "slice_counters": {}}), None),
])
def test_new_readers_on_a_recorded_run(name, run, want):
    got = metric_files.load(name).read(run)
    if want is None:
        assert got is None
    else:
        assert got == pytest.approx(want, rel=1e-9)
        if name == "moe.step_weight_bw":
            assert 40 < got < 100  # 9 forwards in 0.18 s: 20 ms each
        if name == "kernel.moe_gmm_bw":
            assert 80 < got < 100  # 7.6 GB of kernels in 110 ms


def test_costs_moe_against_hand_counted_bytes_and_flops():
    every = 7 * 128
    assert costs_moe.forward_weight_bytes(
        CONF, experts_hit=every, rows=128) == _hand_bytes(1, 0, every, 128)
    assert costs_moe.forward_weight_bytes(
        CONF, experts_hit=every, rows=128, head=False
    ) == _hand_bytes(0, 1, every, 128)
    # ISSUE 26's arithmetic: 8.72 GB of layers + 0.62 GB of head, 11.4 ms
    total = costs_moe.forward_weight_bytes(CONF, experts_hit=every, rows=128)
    assert total / 1e9 == pytest.approx(9.35, abs=0.01)
    assert total / 819e9 * 1e3 == pytest.approx(11.4, abs=0.05)
    experts = every * costs_moe.expert_params(CONF) * 2
    assert experts / (total - 2048 * 151936 * 2) == pytest.approx(0.97, abs=0.01)
    # 0.18 TFLOP: experts 68, attention projections 34, head 80 GFLOP
    assert costs_moe.forward_flops(CONF, rows=128) / 1e12 == pytest.approx(
        0.18, abs=0.005)
    per_row = 2 * 7 * 8 * costs_moe.expert_params(CONF)
    assert 128 * per_row / 1e9 == pytest.approx(67.6, abs=0.2)
    # fewer experts hit, fewer bytes, one expert's kernels at a time
    one = costs_moe.forward_weight_bytes(CONF, experts_hit=every - 1, rows=128)
    assert total - one == 3 * 2048 * 768 * 2
    assert costs_moe.expert_bytes_read(CONF, experts_hit=every) == experts


def test_manifest_entries_for_the_cell():
    m = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    cell, = [w for w in m["workloads"] if w["name"] == CELL]
    assert cell["chips"] == 1 and cell["config"] == "sdar-30b-a3b-serve"
    conf, = [c for c in m["configs"] if c["name"] == cell["config"]]
    assert conf["reduced"] == ["num_hidden_layers"]
    assert conf["source"] == CONF["source"]
    by_name = {e["name"]: e for e in m["per_layer"]}
    for name in NEW:
        assert CELL in by_name[name]["workloads"]
        assert by_name[name]["moves"] == "serve_tok_s"
        assert os.path.exists(os.path.join(
            ROOT, "benchmark", "layer_metrics", name + ".py"))
    for name in SHARED:
        assert CELL in by_name[name]["workloads"]
    e2e = {e["name"]: e for e in m["end_to_end"]}
    assert CELL in e2e["serve_tok_s"]["workloads"]


def test_configuration_file_keeps_every_published_width():
    """The catalog's numbers under the same keys; only depth is cut."""
    published = {
        "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128,
        "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
        "max_position_embeddings": 32768, "max_window_layers": 48,
        "mlp_only_layers": [], "model_type": "sdar_moe",
        "moe_intermediate_size": 768, "norm_topk_prob": True,
        "num_attention_heads": 32, "num_experts": 128,
        "num_experts_per_tok": 8, "num_hidden_layers": 48,
        "num_key_value_heads": 4, "rms_norm_eps": 1e-06, "rope_scaling": None,
        "rope_theta": 1000000, "sliding_window": None,
        "tie_word_embeddings": False, "use_sliding_window": False,
        "vocab_size": 151936,
    }
    differ = {k for k, v in published.items() if CONF.get(k, "absent") != v}
    assert differ == {"num_hidden_layers"}
    assert CONF["num_hidden_layers"] == 7
    assert CONF["source_num_hidden_layers"] == 48
    for key in ("qk_norm", "no_shift", "block_length", "mask_token_id",
                "denoising_steps", "remasking"):
        assert key in CONF["assumed"]


def test_child_refuses_expert_widths_the_program_would_not_run():
    from benchmark.runners import serve_blockdiff_child as child

    cfg = child.build_config(CONF)
    assert (cfg.llm.num_layers, cfg.llm.num_experts) == (7, 128)
    assert cfg.generation.denoising_steps == 2
    assert cfg.generation.remasking == "low_confidence_static"
    for key, bad in (("moe_intermediate_size", 512), ("num_experts", 64),
                     ("num_experts_per_tok", 4), ("hidden_size", 4096)):
        with pytest.raises(SystemExit, match=key):
            child.build_config(dict(CONF, **{key: bad}))
    lay = dict(CONF["layout"], block_length=8)
    with pytest.raises(SystemExit, match="block_length"):
        child.build_config(dict(CONF, layout=lay))


def test_the_childs_tokenizer_spreads_ids_from_the_first_on():
    """One id per character, as the traffic's lengths count on; ids
    spread over the vocabulary below the mask id where the characters
    are 27 code points; a function of the text alone; two texts differ
    from their first id on, even where they open alike; `<id>` per
    token out, as IdTokenizer."""
    import random

    from benchmark import program, traffic
    from benchmark.runners import serve_blockdiff_child as child

    tok = child.SpreadTokenizer(151669)
    rng = random.Random(7)
    text = traffic.text_of(rng, 300)
    ids = tok.encode(text)
    assert len(ids) == 300 and all(3 <= i < 151669 for i in ids)
    assert len(set(ids)) > 290 > 30 > len(set(program.IdTokenizer().encode(text)))
    assert tok.encode(text) == ids and tok.encode("") == []
    other = tok.encode(text[:299] + "?")
    assert sum(a == b for a, b in zip(ids, other)) <= 1
    many = {i for _ in range(32) for i in tok.encode(traffic.text_of(rng, 300))}
    assert len(many) > 9000  # prompts share next to no id
    assert tok.decode([5, 77]) == program.IdTokenizer().decode([5, 77])


# --------------------------------------------------------------------------
# the comparison, against programs that must fail it
# --------------------------------------------------------------------------


@pytest.fixture(scope="module")
def tiny_model():
    import jax

    from oryx_tpu import config as cfg_lib
    from oryx_tpu.models import oryx

    cfg = cfg_lib.sdar_tiny()
    cfg = dataclasses.replace(cfg, generation=dataclasses.replace(
        cfg.generation, denoising_steps=2,
        remasking="low_confidence_static"))
    params = oryx.init_params(cfg, jax.random.key(3))["llm"]
    # At the published widths the seeded 0.02 init makes an expert's
    # output several times the residual stream it is added to (2048 and
    # 768 terms a sum); at hidden 64 / width 32 it is 7 % of it and a
    # fault in the expert layer hides under any tolerance. Ten times the
    # expert and router kernels restores the proportion.
    params = dict(params, layers=dict(params["layers"]))
    for name in ("experts", "router"):
        params["layers"][name] = jax.tree.map(
            lambda a: a * 10.0, params["layers"][name])
    return cfg, params


def _check(cfg, params, **kw):
    from benchmark import correctness_sdar

    return correctness_sdar.block_logit_check(
        params, cfg, 5, page_size=16, prefill_chunk=32, prompt_tokens=62,
        blocks=3, **kw)


def _eager():
    """The comparison's programs without their jit, so that a patch of
    what they call is what runs (a jitted program that was compiled
    before the patch would not see it)."""
    from oryx_tpu.models import generate

    return (generate.paged_prefill.__wrapped__,
            generate.paged_block_forward.__wrapped__,
            generate.paged_block_step.__wrapped__)


def test_the_comparison_passes_the_program_as_it_is(tiny_model):
    cfg, params = tiny_model
    out = _check(cfg, params)
    assert out["ok"] and out["routing_agree"] == 1.0
    assert all(out["passed"].values())
    assert out["logit_max_abs_diff"] < 1e-4 < out["forced_max_tol"]
    assert out["forced_logit_rms_diff"] < 1e-6 < out["forced_tol"]
    assert out["forced_logit_max_abs_diff"] < 1e-4
    # Slots of 62, 35, 17, 8 prompt tokens: 23 forwards with a mask
    # left, whose sequences hold 852 rows in all, in 2 layers.
    assert out["slots"] == 4 and out["positions"] == 23 * 4
    assert out["routing_sets"] == 2 * 852
    assert out["step_tokens_agree"] == out["step_tokens"] == 4 * 12 - 6
    assert out["step_tokens_agree_min_slot"] == 1.0
    eager = _check(cfg, params, programs=_eager())
    assert eager["ok"] and eager["logit_max_abs_diff"] < 1e-4


def test_the_forced_run_reads_bf16_under_int8(tiny_model):
    """The limits are set at the published widths, on the chip (PERF.md
    section 6). This model is 64 wide with its experts scaled up, so
    bf16 moves its forced logits by 2-3 % of the largest and flips move
    the free ones by more: what holds at any size is the ORDER of the
    forced readings, bf16 here and int8 (7 % and more) in the
    `int8_product` case below, and that rounding alone leaves routing
    agreement and the timed program's tokens where they were."""
    cfg, params = tiny_model
    out = _check(cfg, params, program=(
        params, dataclasses.replace(cfg, dtype="bfloat16")))
    assert 1e-4 < out["forced_logit_max_abs_diff"] < 0.045 * out["ref_absmax"]
    assert out["forced_logit_max_abs_diff"] <= out["logit_max_abs_diff"]
    assert out["passed"]["routing"] and out["passed"]["step_tokens"], out


def _llm(cfg, **kw):
    return dataclasses.replace(cfg, llm=dataclasses.replace(cfg.llm, **kw))


@pytest.mark.parametrize("mutation, clause", [
    ("dropped_expert", "forced"), ("top_7", "routing"),
    ("unnormalised", "forced"), ("no_qk_norm", "forced"),
    ("causal_in_block", "forced"), ("int8_product", "forced"),
    ("step_reads_other_pages", "step_tokens"),
])
def test_the_comparison_fails_a_wrong_program(tiny_model, mutation, clause,
                                              monkeypatch):
    """Each is a program that computes something else than the
    equations, small enough to hide: one expert's result dropped, one
    expert too few a token (top-1 of the tiny model's 2: its top-7 of
    8), router weights not renormalised, q/k norm left out, a causal
    mask inside a block, the expert products in int8, and a timed
    program that walks the slots' pages in another order than the
    forward-by-forward pass. The reference gets the true weights and
    configuration every time; `clause` is the limit that has to catch
    the case (others may as well)."""
    import jax
    import jax.numpy as jnp

    from oryx_tpu.models import qwen2

    cfg, params = tiny_model
    program, programs = (params, cfg), None
    if mutation == "dropped_expert":
        bad = dict(params, layers=dict(params["layers"]))
        ex = dict(params["layers"]["experts"])
        ex["down"] = ex["down"].at[:, 3].set(0.0)
        bad["layers"]["experts"] = ex
        program = (bad, cfg)
    elif mutation == "top_7":
        program = (params, _llm(cfg, num_experts_per_tok=1))
    elif mutation == "unnormalised":
        program = (params, _llm(cfg, norm_topk_prob=False))
    elif mutation == "no_qk_norm":
        program = (params, _llm(cfg, qk_norm=False))
    elif mutation == "causal_in_block":
        real = qwen2.forward

        def causal(p, c, **kw):
            return real(p, dataclasses.replace(c, block_length=0), **kw)

        monkeypatch.setattr(qwen2, "forward", causal)
        programs = _eager()
    elif mutation == "step_reads_other_pages":
        from oryx_tpu.models import generate

        def rolled(p, c, kv, bt, *a, **kw):
            return generate.paged_block_step(
                p, c, kv, jnp.roll(bt, 1, axis=0), *a, **kw)

        programs = (generate.paged_prefill, generate.paged_block_forward,
                    rolled)
    else:
        real_dot = jax.lax.ragged_dot

        def q8(x):
            scale = jnp.max(jnp.abs(x)) / 127.0
            return jnp.round(x / scale).astype(jnp.int8), scale

        def int8_dot(lhs, rhs, group_sizes, **kw):
            (a, sa), (b, sb) = q8(lhs), q8(rhs)
            out = real_dot(a.astype(jnp.float32), b.astype(jnp.float32),
                           group_sizes, **kw)
            return (out * sa * sb).astype(lhs.dtype)

        monkeypatch.setattr(jax.lax, "ragged_dot", int8_dot)
        programs = _eager()
    out = _check(cfg, params, program=program, programs=programs)
    assert not out["ok"] and not out["passed"][clause], out
    if mutation == "int8_product":
        assert out["forced_logit_max_abs_diff"] > 0.07 * out["ref_absmax"]
