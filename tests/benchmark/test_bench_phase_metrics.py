"""The per-layer metrics that read the program's phases and spans (PR
24): each reader against a hand-made `run` (its value; None where there
is nothing to read; a raise where a family that has always existed is
gone), the manifest's entries, and the serve and train rehearsals."""

import json
import os

import pytest

from benchmark import metric_files
from test_bench_rehearsal_serve import ROOT, last_line, run_cell

PHASE = 'engine_phase_seconds_total{phase="%s"}'
COUNTERS = {
    "engine_phase_seconds_total": 50.0, "dispatches_total": 200.0,
    "admitted": 40.0,
    PHASE % "idle": 1.0, PHASE % "harvest": 40.0, PHASE % "first_token": 7.0,
    PHASE % "housekeeping": 0.2, PHASE % "admit": 0.3,
    PHASE % "prompt_prep": 0.6, PHASE % "embed": 0.1,
    PHASE % "prefill": 0.2, PHASE % "decode": 0.4,  # emit: never entered
    "request_queue_seconds_sum": 3.0, "request_queue_seconds_count": 60.0,
    "request_prefill_seconds_sum": 12.0, "request_prefill_seconds_count": 60.0,
}
TRAIN = {"step_s": [1.13, 1.12, 1.14], "program_step_s": [1.11, 1.12, 1.10],
         "data_s": [0.0004, 0.0002, 0.0003]}
TRACE = {
    "busy_s": 2.0, "window_s": 2.1,
    "ops": {"all-gather.7": [0.5, 8], "all-gather-start.1": [0.1, 2],
            "reduce-scatter.2": [0.2, 4], "fusion.all-gather": [0.4, 1],
            "fusion.1": [0.8, 9]},
    "idle_gaps": [["oryx.engine.host", 0.06], ["unattributed", 0.02],
                  ["np.asarray_jax.Array_", 0.02]],
}
NO_DEVICE = {"busy_s": 0.0, "window_s": 3.0, "ops": {}, "idle_gaps": []}


def without(d, *keys):
    return {k: v for k, v in d.items() if k not in keys}


@pytest.mark.parametrize("name,run,want", [
    # host phases 1.8 s over 200 dispatches; the waits are left out and
    # a phase that was never entered counts as 0
    ("sched.host_ms_per_dispatch", {"counters": COUNTERS}, 9.0),
    ("sched.host_ms_per_dispatch.batch", {"counters": COUNTERS}, 9.0),
    ("sched.host_ms_per_dispatch",
     {"counters": dict(COUNTERS, dispatches_total=0.0)}, None),
    # a program from before the phases: nothing to read, no fault
    ("sched.host_ms_per_dispatch", {"counters": {"dispatches_total": 9.0}},
     None),
    ("sched.host_ms_per_dispatch",
     {"counters": without(COUNTERS, "dispatches_total")}, KeyError),
    ("sched.queue_wait_ms", {"counters": COUNTERS}, 50.0),
    ("sched.queue_wait_ms",
     {"counters": dict(COUNTERS, request_queue_seconds_count=0.0)}, None),
    ("sched.queue_wait_ms.batch",
     {"counters": without(COUNTERS, "request_queue_seconds_sum")}, KeyError),
    ("sched.admission_ms", {"counters": COUNTERS}, 200.0),
    ("sched.admission_ms.batch",
     {"counters": dict(COUNTERS, request_prefill_seconds_count=0.0)}, None),
    ("sched.admission_ms",
     {"counters": without(COUNTERS, "request_prefill_seconds_count")},
     KeyError),
    ("vision.prep_ms", {"counters": COUNTERS}, 17.5),  # prompt_prep + embed
    ("vision.prep_ms", {"counters": dict(COUNTERS, admitted=0.0)}, None),
    ("vision.prep_ms", {"counters": {"admitted": 4.0}}, None),
    ("vision.prep_ms", {"counters": without(COUNTERS, "admitted")}, KeyError),
    ("train.host_ms_per_step", {"train": TRAIN}, 20.0),
    ("train.host_ms_per_step",
     {"train": dict(TRAIN, step_s=[], program_step_s=[])}, None),
    ("train.host_ms_per_step", {"train": without(TRAIN, "step_s")}, KeyError),
    ("train.data_wait_ms", {"train": TRAIN}, 0.3),
    ("train.data_wait_ms", {"train": dict(TRAIN, data_s=[])}, None),
    ("train.data_wait_ms", {"train": {}}, KeyError),
    # by the START of the name: a fusion named after one is compute
    ("comm.collective_share", {"trace": TRACE}, 40.0),
    ("comm.collective_share", {"trace": NO_DEVICE}, None),
    ("comm.collective_share", {"trace": {}}, None),
    ("comm.collective_share", {"trace": without(TRACE, "ops")}, KeyError),
    ("idle.named_share", {"trace": TRACE}, 80.0),
    ("idle.named_share.batch", {"trace": TRACE}, 80.0),
    ("idle.named_share.train", {"trace": NO_DEVICE}, None),
    ("idle.named_share", {"trace": {}}, None),
    ("idle.named_share", {}, KeyError),
])
def test_a_reader_against_a_hand_made_run(name, run, want):
    reader = metric_files.load(name)
    if isinstance(want, type):
        with pytest.raises(want):
            reader.read(run)
    elif want is None:
        assert reader.read(run) is None
    else:
        assert reader.read(run) == pytest.approx(want)


NEW = (
    "sched.host_ms_per_dispatch", "sched.host_ms_per_dispatch.batch",
    "sched.queue_wait_ms", "sched.queue_wait_ms.batch",
    "sched.admission_ms", "sched.admission_ms.batch", "vision.prep_ms",
    "train.host_ms_per_step", "train.data_wait_ms", "comm.collective_share",
    "idle.named_share", "idle.named_share.batch", "idle.named_share.train",
)


def test_the_new_entries_resolve_and_move_a_metric_of_each_of_their_cells():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        m = json.load(f)
    entries = {e["name"]: e for e in m["per_layer"]}
    e2e = {e["name"]: e for e in m["end_to_end"]}
    cells = {w["name"] for w in m["workloads"]}
    assert set(NEW) <= set(entries)
    for name in NEW:
        e = entries[name]
        reader = metric_files.load(name)
        assert callable(reader.read) and reader.LAYER == e["layer"]
        assert e["workloads"] and set(e["workloads"]) <= cells
        # `moves` is an end-to-end metric of every cell that reports it
        assert set(e["workloads"]) <= set(e2e[e["moves"]]["workloads"])


def traced(cell):
    line = last_line(run_cell(ROOT, cell, "--trace", "1"))
    assert line["correct"] is True, line  # a reader that raises is a fault
    return {k: v["value"] for k, v in line["metrics"].items()}


def program_has_phases():
    """False over a program before PR 24 (the driver lays these files
    over the parent's checkout too): no phase counter to read there."""
    with open(os.path.join(ROOT, "oryx_tpu", "serve", "scheduler.py")) as f:
        return "engine_phase_seconds_total" in f.read()


def test_the_serve_rehearsal_prints_the_counter_and_span_metrics():
    got = traced("oryx-7b.chat")
    assert ("sched.host_ms_per_dispatch" in got) == program_has_phases()
    for name in ("sched.host_ms_per_dispatch", "sched.queue_wait_ms",
                 "sched.admission_ms")[not program_has_phases():]:
        assert got[name] > 0, got
    # queue head -> first token is no longer than the client's wait
    assert got["sched.admission_ms"] < got["sched.ttft_p90_ms"] * 2
    # no device plane on the CPU: nothing to name, nothing made up
    assert "idle.named_share" not in got and "vision.prep_ms" not in got


def test_the_train_rehearsal_prints_the_span_metrics():
    got = traced("oryx-7b-lora.sft-mixed")
    assert got["train.host_ms_per_step"] > 0, got
    assert got["train.data_wait_ms"] >= 0
    assert got["train.host_ms_per_step"] < got["train.step_ms"]
    assert "idle.named_share.train" not in got
    assert "comm.collective_share" not in got  # the fsdp4 cell's alone
