"""The harness end to end in rehearsal (CPU, oryx_tiny): the last-line
contract for a serve cell, and the refusal to measure off a TPU."""

import json
import os
import subprocess
import sys


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


def test_serve_cell_prints_the_contracts_last_line():
    line = last_line(run_cell(ROOT, "oryx-7b.chat"))
    assert LINE_KEYS <= set(line)
    assert line["correct"] is True, line
    assert line["rehearsal"] is True and line["device"]["platform"] == "cpu"
    assert line["failed"] == 0 and line["attempted"] > 5
    names = set(line["metrics"])
    assert names == {"tpot_p90_ms", "setup_s"}  # PERF.md section 2: why these
    for m in line["metrics"].values():
        assert m["value"] > 0 and m["unit"]


def test_closed_loop_cell_reads_its_layers_in_a_traced_run():
    line = last_line(run_cell(ROOT, "oryx-7b.visual-batch", "--trace", "1"))
    assert LINE_KEYS <= set(line)
    assert line["correct"] is True, line  # a reader that raises is a fault
    assert line["failed"] == 0 and line["attempted"] > 5
    names = set(line["metrics"])
    # the group's names read the shared readers; device metrics have
    # nothing to read off a chip and are left out
    assert {"sched.decode_util.batch", "sched.ttft_p90_ms.batch",
            "sched.tpot_p90_ms"} <= names
    assert "vision.encode_ms" not in names and "setup_s" not in names
    # no device plane off a chip: the window is the host's stamp, which
    # the line carries beside it in any case
    assert line["device"]["window_s"] == line["device"]["host_window_s"] > 0


def test_off_a_tpu_a_measurement_run_prints_no_metric_line():
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=ROOT)
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", "oryx-7b-lora.sft-mixed", "--seed", "1",
         "--seconds", "1"],
        capture_output=True, text=True, env=env, timeout=120,
    )
    assert p.returncode != 0
    assert '"metrics"' not in p.stdout
    assert "needs a TPU" in p.stderr


def test_unknown_device_kind_is_an_error_not_a_default():
    sys.path.insert(0, ROOT)
    from benchmark import program

    peaks = program.load_peaks()
    assert peaks["TPU v5 lite"]["bf16_flops_per_s"] == 197e12
    assert peaks["TPU v5 lite"]["hbm_bytes_per_s"] == 819e9
    assert "TPU v4" not in peaks and "cpu" not in peaks
