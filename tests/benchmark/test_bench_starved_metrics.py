"""The per-layer metrics that read the engine's own account of the
device's idle time (PR 35): each reader against a hand-made `run` (its
value; None over a program that lacks the counters, which is how the
driver runs these files over the parent), the manifest's entries, and
the chat rehearsal's traced line."""

import json
import os

import pytest

from benchmark import metric_files
from test_bench_rehearsal_serve import ROOT, last_line, run_cell

PHASE = 'engine_phase_seconds_total{phase="%s"}'
STARVED = 'engine_starved_seconds_total{phase="%s"}'
STALL = 'engine_stall_seconds_total{phase="%s"}'
# A window of 50 s: 5 s request-less, 3 s starved, one stall of 3.1 s.
COUNTERS = {
    "engine_phase_seconds_total": 50.0, "dispatches_total": 400.0,
    PHASE % "idle": 5.0, PHASE % "harvest": 38.0, PHASE % "copy_out": 1.6,
    PHASE % "emit": 2.4, PHASE % "decode": 3.0,
    "engine_starved_seconds_total": 3.0,
    STARVED % "copy_out": 1.6, STARVED % "emit": 1.0, STARVED % "decode": 0.4,
    "engine_stall_seconds_total": 3.1,
    STALL % "harvest": 3.1, STALL % "emit": 0.0,
}
QUIET = dict(COUNTERS, **{"engine_stall_seconds_total": 0.0,
                          STALL % "harvest": 0.0})
# A program before PR 35: phases, and none of the new families.
PARENT = {
    k: v for k, v in COUNTERS.items()
    if "starved" not in k and "stall" not in k and "copy_out" not in k
}
# A slice of 3 s: the trace finds the device idle 16 % of it, the
# engine counts 0.30 s request-less + 0.15 s starved of 3.0 s = 15 %.
SLICE = {
    "engine_phase_seconds_total": 3.0, PHASE % "idle": 0.30,
    "engine_starved_seconds_total": 0.15,
}
TRACE = {"busy_s": 2.53, "window_s": 3.012, "slice_counters": SLICE}


@pytest.mark.parametrize("name,run,want", [
    ("sched.starved_share", {"counters": COUNTERS}, 6.0),
    ("sched.starved_share.batch", {"counters": COUNTERS}, 6.0),
    ("sched.starved_share", {"counters": PARENT}, None),
    ("sched.starved_share",
     {"counters": dict(COUNTERS, engine_phase_seconds_total=0.0)}, None),
    ("sched.copy_out_ms", {"counters": COUNTERS}, 4.0),
    ("sched.copy_out_ms.batch", {"counters": COUNTERS}, 4.0),
    ("sched.copy_out_ms", {"counters": PARENT}, None),
    ("sched.copy_out_ms",
     {"counters": dict(COUNTERS, dispatches_total=0.0)}, None),
    ("sched.copy_out_ms",
     {"counters": {k: v for k, v in COUNTERS.items()
                   if k != "dispatches_total"}}, KeyError),
    # an injected delay's seconds under the phase they fell in are read
    ("sched.stall_s", {"counters": COUNTERS}, 3.1),
    ("sched.stall_s.batch", {"counters": COUNTERS}, 3.1),
    ("sched.stall_s", {"counters": QUIET}, 0.0),  # 0.0 means 0
    ("sched.stall_s", {"counters": PARENT}, None),
    ("sched.norequest_share", {"counters": COUNTERS}, 10.0),
    ("sched.norequest_share", {"counters": PARENT}, None),
    ("sched.norequest_share",
     {"counters": {k: v for k, v in COUNTERS.items()
                   if k != PHASE % "idle"}}, 0.0),
    ("idle.unexplained_share", {"trace": TRACE},
     100.0 * abs((1 - 2.53 / 3.012) - 0.15)),
    ("idle.unexplained_share.batch", {"trace": TRACE},
     100.0 * abs((1 - 2.53 / 3.012) - 0.15)),
    # the engine counting MORE idle than the trace shows is a fault of
    # the account as well
    ("idle.unexplained_share",
     {"trace": dict(TRACE, busy_s=2.9)},
     100.0 * abs((1 - 2.9 / 3.012) - 0.15)),
    ("idle.unexplained_share",
     {"trace": dict(TRACE, busy_s=0.0)}, None),  # no device plane
    ("idle.unexplained_share",
     {"trace": dict(TRACE, slice_counters={
         "engine_phase_seconds_total": 3.0, PHASE % "idle": 0.3})}, None),
    ("idle.unexplained_share", {"trace": {}}, None),
    ("idle.unexplained_share", {"trace": {"busy_s": 1.0, "window_s": 3.0}},
     None),
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


CHAT = ["oryx-7b.chat"]
CLOSED = ["oryx-7b.visual-batch", "sdar-30b-a3b.blockgen",
          "longcat-flash.tool-sessions", "mistral-small-4.doc-qa"]
NEW = {
    "sched.starved_share": CHAT, "sched.starved_share.batch": CLOSED,
    "sched.copy_out_ms": CHAT, "sched.copy_out_ms.batch": CLOSED,
    "sched.stall_s": CHAT, "sched.stall_s.batch": CLOSED,
    "sched.norequest_share": CHAT,
    "idle.unexplained_share": CHAT, "idle.unexplained_share.batch": CLOSED,
}


@pytest.fixture(scope="module")
def manifest():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("name", list(NEW))
def test_the_entry_is_present_resolves_and_moves_a_metric_of_its_cells(
    manifest, name
):
    # PRESENT, wherever later PRs append theirs
    (e,) = [e for e in manifest["per_layer"] if e["name"] == name]
    reader = metric_files.load(name)
    assert callable(reader.read) and reader.LAYER == e["layer"]
    cells = {w["name"] for w in manifest["workloads"]}
    assert set(NEW[name]) <= set(e["workloads"]) <= cells
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    assert set(e["workloads"]) <= set(e2e[e["moves"]]["workloads"])
    assert e["better"] == "lower"
    assert e["source"] == (
        "device_trace" if name.startswith("idle.") else "program_counter")


def program_counts_starved_seconds():
    """False over a program before PR 35 (the driver lays these files
    over the parent's checkout too): none of the families there."""
    with open(os.path.join(ROOT, "oryx_tpu", "serve", "scheduler.py")) as f:
        return "engine_starved_seconds_total" in f.read()


def test_the_chat_rehearsal_prints_the_counter_metrics_and_no_device_one():
    line = last_line(run_cell(ROOT, "oryx-7b.chat", "--trace", "1"))
    assert line["correct"] is True, line  # a reader that raises is a fault
    got = {k: v["value"] for k, v in line["metrics"].items()}
    mine = {"sched.starved_share", "sched.copy_out_ms", "sched.stall_s",
            "sched.norequest_share"}
    if not program_counts_starved_seconds():
        assert not mine & set(got) and "idle.unexplained_share" not in got
        return
    assert mine <= set(got)
    assert "idle.unexplained_share" not in got  # no device plane on the CPU
    assert not any(k.endswith(".batch") for k in got)  # the other group's
    assert 0 < got["sched.starved_share"] < 100
    assert 0 <= got["sched.norequest_share"] < 100
    assert got["sched.starved_share"] + got["sched.norequest_share"] < 100
    assert got["sched.copy_out_ms"] > 0
    assert got["sched.stall_s"] >= 0
