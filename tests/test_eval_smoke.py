"""The committed smoke benchmark (assets/smoke_eval) through the REAL
CLI path: scripts/make_smoke_eval.py builds a model dir with an on-disk
HF tokenizer, then eval.harness.main loads the pipeline from disk, runs
batched decode over the committed media, scores, and writes the result
JSON (SURVEY.md §3.5)."""

import json
import os

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ASSETS = os.path.join(REPO, "assets", "smoke_eval")


def test_committed_task_schema():
    task = os.path.join(ASSETS, "task.jsonl")
    with open(task) as f:
        records = [json.loads(l) for l in f if l.strip()]
    assert len(records) == 8
    kinds = {r["meta"]["kind"] for r in records}
    assert kinds == {"image", "video"}
    for r in records:
        assert r["answer"] in "ABCD"
        assert len(r["options"]) == 4
        media = r.get("image") or r.get("video")
        assert os.path.exists(os.path.join(ASSETS, media)), media


@pytest.mark.slow
def test_smoke_eval_cli_end_to_end(tmp_path, capsys):
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "make_smoke_eval", os.path.join(REPO, "scripts", "make_smoke_eval.py")
    )
    make_smoke_eval = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(make_smoke_eval)

    model_dir = make_smoke_eval.build_model_dir(str(tmp_path))
    from oryx_tpu.eval import harness

    out = tmp_path / "result.json"
    harness.main([
        "--model-path", model_dir,
        "--task", os.path.join(ASSETS, "task.jsonl"),
        "--media-root", ASSETS,
        "--num-frames", "4",
        "--max-new-tokens", "4",
        "--by", "kind",
        "--output", str(out),
    ])
    printed = capsys.readouterr().out
    summary = json.loads(printed.strip().splitlines()[-1])
    assert summary["n"] == 8
    assert set(summary["by_kind"]) == {"image", "video"}
    result = json.loads(out.read_text())
    assert result["num_total"] == 8
    assert len(result["records"]) == 8
    ids = {r["id"] for r in result["records"]}
    assert ids == {f"smoke-{i}" for i in range(8)}
