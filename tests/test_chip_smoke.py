"""CPU rehearsal of chip_smoke.py: its phase functions at oryx_tiny with
the kernels in interpret mode, and the script's refusal to report `ok`
off the chip."""

import dataclasses
import json
import os
import sys

import pytest

import jax
import jax.numpy as jnp

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402


@pytest.fixture(scope="module")
def size():
    return dataclasses.replace(chip_smoke.tiny_size(), kernel_seq=64)


@pytest.fixture(scope="module")
def params(size):
    from oryx_tpu.models import oryx

    return oryx.init_params(size.cfg, jax.random.key(0), dtype=jnp.bfloat16)


def _phase_lines(capsys):
    return [
        json.loads(line) for line in capsys.readouterr().out.splitlines()
        if line.startswith("{")
    ]


def test_refuses_to_report_ok_off_the_chip(capsys):
    assert chip_smoke.main([]) != 0
    last = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert last["ok"] is False
    assert last["device"] == {"platform": "cpu", "kind": "cpu", "count": 8}


def test_kernels_phase_rehearsal(size, capsys):
    chip_smoke.phase_kernels(size)
    lines = _phase_lines(capsys)
    cases = {r["case"] for r in lines if "case" in r}
    assert {
        "flash_causal_gqa_fwd", "flash_causal_gqa_bwd_dk",
        "segment_vit_d72_fwd", "paged_decode_bf16", "paged_ragged_int8",
    } <= cases
    assert lines[-1]["phase"] == "kernels" and lines[-1]["failed"] == []


@pytest.mark.parametrize("ragged", [False, True], ids=["split", "ragged"])
def test_serve_phase_rehearsal(size, params, capsys, ragged):
    first = chip_smoke.phase_serve(
        size, params, 0, ragged=ragged, on_chip=False
    )
    assert [r["name"] for r in first] == ["short", "long", "stream", "image"]
    assert all(len(r["ids"]) == size.max_tokens for r in first)
    report = _phase_lines(capsys)[-1]
    assert report["repeat_pass_compiles"] == 0 and report["repeat_ids_equal"]
    assert report["long_prompt_chunks"] >= 2
    want = "paged_ragged_step" if ragged else "paged_decode_chunk"
    assert want in report["step_program_kernels"]


def test_logit_rows_agree_across_attention_impls(size, params, capsys):
    prompt = chip_smoke.IdTokenizer().encode("hello there")
    rows = [
        chip_smoke.first_logit_row(
            params, size.cfg, prompt, attn_impl=impl,
            page_size=size.page_size, max_ctx=4 * size.page_size,
        )
        for impl in ("pallas", "xla")
    ]
    chip_smoke.compare_logit_rows("pallas_vs_xla", *rows)
    assert _phase_lines(capsys)[-1]["ok"] is True
    bad = (rows[0][0] + 1.0, *rows[0][1:])
    with pytest.raises(SystemExit):
        chip_smoke.compare_logit_rows("shifted", bad, rows[1])


def test_train_phase_rehearsal(size, params, capsys):
    losses = chip_smoke.phase_train(size, params, 0, on_chip=False)
    assert len(losses) == size.train_steps
    report = _phase_lines(capsys)[-1]
    assert report["adapters_moved"] and report["base_unchanged"]
    assert not os.path.exists(os.path.join(REPO, ".smoke_tmp"))


def test_mosaic_kernels_reads_names_from_compiled_text():
    text = (
        '%x = bf16[1] custom-call(%a), custom_call_target="tpu_custom_call"'
        ', metadata={op_name="jit(step)/jit(_ragged_paged)/pallas_call"}\n'
        '%y = bf16[1] custom-call(%a), custom_call_target="other"\n'
    )
    assert chip_smoke.mosaic_kernels(text) == {"_ragged_paged"}
    assert chip_smoke.mosaic_kernels("no kernels here") == set()
