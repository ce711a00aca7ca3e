"""The device instrument's one clock (benchmark/trace.py `reduce_dir`):
the traced slice's `window_s` comes off the same timeline as the device
ops it is divided into, so `busy_s <= window_s` whatever the host's
stamp around start_trace / stop_trace said. Captures are written in the
profiler's wire format by the fixture's own writer."""

import os
import sys

import pytest

from benchmark import trace

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(HERE, "data"))
import make_small_xplane as pb  # noqa: E402

US, T0 = pb.US, pb.T0
NAMES = {1: "jit_paged_block_step(9)", 2: "gmm.26", 3: "fusion.1"}


def device(n, busy, *, at=0, t0=T0):
    """Device plane `n`: one program from `at` us that runs ops back to
    back but for a 5 us gap in the middle, `busy` us of ops in all."""
    half = busy // 2
    ops = pb.line("XLA Ops", t0, [
        pb.event(2, at * US, half * US),
        pb.event(3, (at + half + 5) * US, (busy - half) * US)])
    mods = pb.line("XLA Modules", t0, [pb.event(1, at * US, (busy + 5) * US)])
    return pb.plane(f"/device:TPU:{n}", [mods, ops], NAMES)


HOST = pb.plane("/host:CPU", [pb.line("engine", T0, [
    pb.event(1, 0, 4000 * US)])], {1: "engine_loop"})


def capture(tmp_path, planes):
    d = tmp_path / "plugins" / "profile" / "run"
    d.mkdir(parents=True)
    (d / "host.xplane.pb").write_bytes(
        b"".join(pb.field(1, 2, p) for p in planes))
    return str(tmp_path)


# name, device planes, the host's stamp (s), busy_s, window_s
CASES = [
    # the capture is longer than the stamp: 3000 us of ops, 5 us idle,
    # the host stamped 2995 us (blockgen: idle under the clocks' gap)
    ("ops_run_past_the_stamp", [device(0, 3000)], 2995e-6, 3000e-6, 3005e-6),
    # the stamp is the longer one: the extent still is the window
    ("stamp_runs_past_the_ops", [device(0, 3000)], 3400e-6, 3000e-6, 3005e-6),
    # a line of its own epoch: the extent is over every line's own clock
    ("lines_of_two_epochs",
     [device(0, 1000), device(1, 1000, t0=T0 + 2_000_000)],
     1000e-6, 1000e-6, 3005e-6),
    # four chips: busy is the mean of four unions, each inside the ONE
    # extent (chip 3 starts 100 us late and ends last)
    ("four_planes", [device(0, 3000), device(1, 2000), device(2, 1000),
                     device(3, 2000, at=1100)], 2990e-6, 2000e-6, 3105e-6),
]


@pytest.mark.parametrize("name,devs,stamp,busy,window",
                         CASES, ids=[c[0] for c in CASES])
def test_window_and_busy_share_the_device_planes_clock(
        tmp_path, name, devs, stamp, busy, window):
    red = trace.reduce_dir(capture(tmp_path, devs + [HOST]), window_s=stamp)
    assert red["chips"] == len(devs)
    assert red["busy_s"] == pytest.approx(busy)
    assert red["window_s"] == pytest.approx(window)
    assert 0 < red["busy_s"] <= red["window_s"]
    assert red["host_window_s"] == stamp  # kept beside it, as given


def test_the_old_rule_read_busy_over_the_stamped_window(tmp_path):
    """What refused PR 35, 41 and 44: the same capture against the
    host's stamp, which `reduce_planes` still takes as given."""
    d = capture(tmp_path, [device(0, 3000), HOST])
    planes = trace.parse_xspace(trace.find_xplane_files(d)[-1])
    old = trace.reduce_planes(planes, window_s=2995e-6)
    assert old["busy_s"] > old["window_s"]
    new = trace.reduce_dir(d, window_s=2995e-6)
    assert new["busy_s"] == old["busy_s"] <= new["window_s"]
    assert new["ops"] == old["ops"] and new["modules"] == old["modules"]


@pytest.mark.parametrize("planes", [[HOST], []],
                         ids=["host_plane_only", "no_plane"])
def test_a_capture_without_a_device_event_keeps_the_hosts_stamp(
        tmp_path, planes):
    """The CPU rehearsal: nothing to take an extent from."""
    red = trace.reduce_dir(capture(tmp_path, planes), window_s=3.0125)
    assert red["chips"] == 0 and red["busy_s"] == 0.0
    assert red["window_s"] == red["host_window_s"] == 3.0125


def test_a_directory_without_a_capture_reduces_to_nothing(tmp_path):
    assert trace.reduce_dir(str(tmp_path), window_s=3.0) == {}


def test_edge_idle_is_outside_the_extent(tmp_path):
    """The bias, stated: idle time before the first device event and
    after the last is not in `window_s`; the gap between ops is."""
    red = trace.reduce_dir(
        capture(tmp_path, [device(0, 1000, at=500), HOST]), window_s=4000e-6)
    assert red["window_s"] == pytest.approx(1005e-6)
    assert 1 - red["busy_s"] / red["window_s"] == pytest.approx(5 / 1005)
    assert 1 - red["busy_s"] / red["host_window_s"] == pytest.approx(0.75)


def test_every_runner_reduces_through_the_one_function():
    """`device_extent_s` is defined once and nothing but `reduce_dir`
    hands `reduce_planes` a window (a later runner is another caller of
    `reduce_dir`, so the callers are held as a subset)."""
    root = os.path.dirname(os.path.dirname(HERE))
    defs, callers = [], []
    for dirpath, _, files in os.walk(os.path.join(root, "benchmark")):
        if os.sep + "out" in dirpath:
            continue
        for f in files:
            if not f.endswith(".py"):
                continue
            src = open(os.path.join(dirpath, f)).read()
            if "def device_extent_s(" in src:
                defs.append(f)
            if "reduce_dir(" in src and f != "trace.py":
                callers.append(f)
            assert "reduce_planes(" not in src or f == "trace.py", f
    assert defs == ["trace.py"]
    # Every serve child's command loop is runners/lifeline.py's (PR 55).
    assert {"lifeline.py", "train.py"} <= set(callers)
