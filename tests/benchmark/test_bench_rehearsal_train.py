"""The harness end to end in rehearsal (CPU, oryx_tiny): the last-line
contract for a train cell, through a new cell, a new configuration and
new per-layer metrics added as NEW FILES plus entries to a temporary
copy of the benchmark — no file that was there is edited."""

import json
import os
import shutil
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
LINE_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def run_cell(root, cell, *extra, seed=2**31 + 5):
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=ROOT)
    p = subprocess.run(
        [sys.executable, os.path.join(root, "benchmark", "run.py"),
         "--workload", cell, "--seed", str(seed), "--rehearse", "1", *extra],
        capture_output=True, text=True, env=env, timeout=240,
    )
    return p


def last_line(p):
    assert p.returncode == 0, p.stderr[-2000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def info_line(p):
    return json.loads(p.stdout.strip().splitlines()[-2])["info"]


@pytest.fixture()
def copy_with_new_files(tmp_path):
    """BENCHMARK.json + benchmark/ copied; then ONLY new files and new
    manifest entries: a configuration, a cell, a per-layer metric."""
    root = tmp_path / "checkout"
    shutil.copytree(os.path.join(ROOT, "benchmark"), root / "benchmark",
                    ignore=shutil.ignore_patterns("out", "__pycache__"))
    before = {p: p.read_bytes() for p in (root / "benchmark").rglob("*")
              if p.is_file()}
    b = root / "benchmark"
    conf = json.loads((b / "configs" / "oryx-7b-lora.json").read_text())
    conf["name"] = "dummy-lora"
    (b / "configs" / "dummy-lora.json").write_text(json.dumps(conf))
    wl = json.loads(
        (b / "workloads" / "oryx-7b-lora.sft-mixed.json").read_text())
    wl.update(name="dummy-lora.tiny-rows", config="dummy-lora")
    wl["rehearse"]["batch"]["rows"] = 3
    wl["rehearse"]["seconds"] = 2
    (b / "workloads" / "dummy-lora.tiny-rows.json").write_text(json.dumps(wl))
    (b / "layer_metrics" / "dummy.first_loss.py").write_text(
        'LAYER = "trainer"\n\n\ndef read(run):\n'
        '    return run["train"]["first_loss"]\n'
    )
    (b / "layer_metrics" / "dummy.nothing.py").write_text(
        'LAYER = "trainer"\n\n\ndef read(run):\n    return None\n'
    )
    (b / "layer_metrics" / "dummy.broken.py").write_text(
        'LAYER = "trainer"\n\n\ndef read(run):\n'
        '    return run["no such key"]\n'
    )
    m = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    m["configs"].append({
        "name": "dummy-lora", "source": conf["source"],
        "file": "benchmark/configs/dummy-lora.json",
        "reduced": ["num_hidden_layers"], "why": "test"})
    m["workloads"].append({
        "name": "dummy-lora.tiny-rows", "config": "dummy-lora",
        "traffic": "tiny-rows", "chips": 1, "why": "test"})
    for e in m["end_to_end"]:
        if e["name"] == "train_tok_s":
            e["workloads"].append("dummy-lora.tiny-rows")
    for name in ("dummy.first_loss", "dummy.nothing", "dummy.broken"):
        m["per_layer"].append({
            "name": name, "unit": "nat", "better": "lower",
            "source": "program_counter", "layer": "trainer",
            "moves": "train_tok_s", "workloads": ["dummy-lora.tiny-rows"]})
    (root / "BENCHMARK.json").write_text(json.dumps(m))
    after = {p: p.read_bytes() for p in before}
    assert after == before  # nothing that was there changed
    return str(root)


def test_a_new_cell_config_and_metric_are_new_files_only(copy_with_new_files):
    root = copy_with_new_files
    e2e_p = run_cell(root, "dummy-lora.tiny-rows")
    e2e = last_line(e2e_p)
    assert LINE_KEYS <= set(e2e) and e2e["correct"] is True, e2e
    assert set(e2e["metrics"]) == {"train_tok_s", "setup_s"}
    assert e2e["metrics"]["train_tok_s"]["unit"] == "tokens/s/chip"
    assert e2e["attempted"] >= 2 and e2e["failed"] == 0
    traced = last_line(run_cell(root, "dummy-lora.tiny-rows", "--trace", "1"))
    # the new metric is read; the reader that finds nothing is left out;
    # metrics of other cells (serve) are not reported here
    assert traced["metrics"]["dummy.first_loss"]["value"] > 0
    assert "dummy.nothing" not in traced["metrics"]
    # ... and a reader that raises makes the run incorrect, by name
    assert traced["correct"] is False and "dummy.broken" not in traced["metrics"]
    assert any(p.startswith("dummy.broken: KeyError")
               for p in traced["problems"])
    assert not any(k.startswith(("sched.", "step.")) for k in traced["metrics"])
    # same seed, same first loss: weights, data and order all come from it
    assert (traced["metrics"]["dummy.first_loss"]["value"]
            == info_line(e2e_p)["train"]["first_loss"])
