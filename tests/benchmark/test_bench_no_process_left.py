"""No benchmark child outlives its run (PR 55).

A serve cell is two processes: `benchmark/run.py` (traffic, clocks) and
the child that holds the chip. A child left behind holds the chip for
nobody and the next run on that machine meets it, so the driver refuses
the PR whole. Held here, off the chip, in rehearsal:

  - `run.py` ended from outside by SIGTERM and by SIGKILL at three
    phases (before `ready`, inside the window, after `disarm` while the
    comparison runs) leaves within 5 s no process that it started, none
    in its session, and nothing listening on the child's port; a normal
    run ends the same way;
  - a child fed `arm`, `disarm` and end-of-file exits non-zero without
    the comparison; fed `stop`, it makes it;
  - `lifeline.tie_to_parent` ends a child whose parent is killed, and one
    that was handed a pid that is not its parent's;
  - `serve.Child.kill` takes the child's whole group, and a comparison
    that does not come in time is a problem of the run, not a hang.

Every case has its own time limit (`wait_until`, `wait(timeout=)`), and
ends whatever it started in a `finally`.
"""

import contextlib
import io
import json
import os
import shutil
import signal
import subprocess
import sys
import textwrap
import time

import psutil
import pytest

from benchmark import run as bench_run
from benchmark.runners import lifeline, serve
from test_bench_rehearsal_train import ROOT

# Cells of three runners: the comparison after the window on what it
# served (serve_longctx, serve_docqa) and in set-up (serve).
CELLS = ("glm-5.long-sessions", "mistral-small-4.doc-qa", "oryx-7b.chat")
# A phase begins at the line the runner writes into the child's log
# when the phase before it is done (serve.Phases.mark).
PHASES = {"before_ready": None, "in_window": "phase arm ",
          "after_disarm": "phase disarm "}
REACH_S = 200.0  # to get a rehearsal to a phase, beside five other workers
GONE_S = 5.0


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    root = tmp_path_factory.mktemp("noleft") / "checkout"
    shutil.copytree(os.path.join(ROOT, "benchmark"), root / "benchmark",
                    ignore=shutil.ignore_patterns("out", "__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    return str(root)


def cpu_env(tmp_path):
    """The CPU, and a compile cache of the case's own: jax writes an
    entry in place (`lru_cache.put`: `write_bytes`), so a child killed
    in the middle of one would leave the suite's shared cache an entry
    cut short, and whoever reads it next segfaults."""
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=ROOT,
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "jax_cache"))
    return env


def wait_until(cond, limit_s, what):
    end = time.monotonic() + limit_s
    while time.monotonic() < end:
        got = cond()
        if got:
            return got
        time.sleep(0.05)
    raise AssertionError(f"not within {limit_s:.0f} s: {what}")


def living(procs):
    """Those of the psutil processes that still run (a zombie does not).
    Processes, not pids: the suite spawns enough to wrap the pids, and a
    recycled pid is another test's process."""
    out = []
    for p in procs:
        try:
            if p.is_running() and p.status() != psutil.STATUS_ZOMBIE:
                out.append(p)
        except psutil.NoSuchProcess:
            pass
    return out


def started_by(pid):
    try:
        return psutil.Process(pid).children(recursive=True)
    except psutil.NoSuchProcess:
        return []


def in_session(sid):
    out = []
    for p in psutil.process_iter():
        try:
            if os.getsid(p.pid) == sid:
                out.append(p)
        except OSError:
            pass
    return living(out)


def listening(pids=None, ports=None):
    """TCP ports in LISTEN: those of the processes `pids`, or those of
    `ports` that are still there."""
    if pids is not None:
        found = set()
        for p in pids:
            try:
                found |= {c.laddr.port for c in p.net_connections("tcp")
                          if c.status == psutil.CONN_LISTEN}
            except (psutil.NoSuchProcess, psutil.AccessDenied):
                pass
        return found
    return {c.laddr.port for c in psutil.net_connections("tcp")
            if c.status == psutil.CONN_LISTEN and c.laddr.port in ports}


def end_all(run, kids):
    """Whatever the case did: nothing of it survives the case."""
    if run.poll() is None:  # not reaped, so its pid and group are its own
        with contextlib.suppress(OSError):
            os.killpg(run.pid, signal.SIGKILL)
        run.kill()
    for p in living(kids):
        with contextlib.suppress(psutil.NoSuchProcess):
            p.kill()
    run.wait(timeout=30)


def start_run(checkout, cell, out, tmp_path):
    return subprocess.Popen(
        [sys.executable, os.path.join(checkout, "benchmark", "run.py"),
         "--workload", cell, "--seed", str(2**31 + 7), "--rehearse", "1"],
        stdout=out, stderr=subprocess.STDOUT, env=cpu_env(tmp_path),
        start_new_session=True)


@pytest.mark.parametrize("sig", [signal.SIGTERM, signal.SIGKILL],
                         ids=["sigterm", "sigkill"])
@pytest.mark.parametrize("phase", list(PHASES))
@pytest.mark.parametrize("cell", CELLS)
def test_a_run_ended_from_outside_leaves_no_process(
        checkout, tmp_path, cell, phase, sig):
    log = os.path.join(checkout, "benchmark", "out", cell, "serve_child.log")
    if os.path.exists(log):
        os.remove(log)
    kids = []
    with open(tmp_path / "run.out", "w") as out:
        run = start_run(checkout, cell, out, tmp_path)
        try:
            wait_until(lambda: started_by(run.pid), 60.0,
                       "the runner starts its child")
            mark = PHASES[phase]
            if mark is None:
                time.sleep(1.0)  # the child is importing, or compiling
            else:
                wait_until(
                    lambda: os.path.exists(log) and mark in open(log).read(),
                    REACH_S, f"{cell} reaches {phase}")
            assert run.poll() is None, "the run ended before it was ended"
            kids = started_by(run.pid)
            assert living(kids)
            ports = listening(pids=kids)
            assert ports or mark is None, "a served child listens"
            assert "phase ready " not in open(log).read() or mark is not None
            sid = os.getsid(run.pid)
            assert sid == run.pid and all(os.getsid(k.pid) != sid for k in kids)

            os.kill(run.pid, sig)
            wait_until(
                lambda: not living(kids) and not in_session(sid)
                and not listening(ports=ports),
                GONE_S, f"after {sig.name} {phase}: children "
                f"{living(kids)}, session {in_session(sid)}, ports "
                f"{listening(ports=ports)} still there")
            rc = run.wait(timeout=GONE_S)
            assert rc in (-sig, 128 + sig), rc
        finally:
            end_all(run, kids)
    assert '"metrics"' not in open(tmp_path / "run.out").read()


@pytest.mark.parametrize("cell", CELLS)
def test_a_normal_run_ends_with_its_group_empty(checkout, tmp_path, cell):
    kids = []
    with open(tmp_path / "run.out", "w") as out:
        run = start_run(checkout, cell, out, tmp_path)
        try:
            kids = wait_until(lambda: started_by(run.pid), 60.0,
                              "the runner starts its child")
            ports = wait_until(
                lambda: listening(pids=kids) or run.poll() is not None,
                REACH_S, "the child listens")
            rc = run.wait(timeout=240)
            assert not living(kids) and not in_session(run.pid)
            assert not isinstance(ports, set) or not listening(ports=ports)
        finally:
            end_all(run, kids)
    text = open(tmp_path / "run.out").read()
    assert rc == 0, text[-2000:]
    lines = [ln for ln in text.splitlines() if ln.startswith("{")]
    line, info = json.loads(lines[-1]), json.loads(lines[-2])["info"]
    # (`correct` is the rehearsal tests' to hold: a fast machine serves a
    # rehearsal's short client lists dry, which is a problem of its own.)
    assert {"correct", "metrics", "device"} <= set(line)
    assert not any("comparison" in p for p in line["problems"])
    # The phases of the run, on the info line alone.
    want = {"device", "ready", "warmup", "arm", "window", "disarm", "stop"}
    want |= {"comparison_in_ready"} if cell == "oryx-7b.chat" else {
        "histories", "comparison"}
    assert set(info["phases"]) == want
    assert all(v >= 0 for v in info["phases"].values())
    clocked = sum(v for k, v in info["phases"].items()
                  if k != "comparison_in_ready")
    assert clocked <= info["wall_s"] < clocked + 5.0
    assert "phases" not in line and "wall_s" not in line


# ---- end-of-file is not `stop` ---------------------------------------


def child_events(proc, until, limit_s):
    """The child's JSON lines up to the event `until` (or its end)."""
    events, end = [], time.monotonic() + limit_s
    for line in proc.stdout:
        assert time.monotonic() < end, f"no {until!r} in {limit_s:.0f} s"
        try:
            obj = json.loads(line)
        except ValueError:
            continue
        if isinstance(obj, dict) and "event" in obj:
            events.append(obj)
            if obj["event"] == until:
                break
    return events


@pytest.mark.parametrize("last", ["eof", "stop"])
def test_a_child_compares_on_stop_and_never_on_end_of_file(tmp_path, last):
    conf = bench_run.resolve(bench_run.load_json(
        ROOT, "benchmark", "configs", "glm-5-ep16-serve.json"), True)
    with open(tmp_path / "child.log", "w") as log:
        proc = subprocess.Popen(
            [sys.executable, os.path.join(ROOT, "benchmark", "runners",
                                          "serve_longctx_child.py"),
             "--config", json.dumps(conf), "--seed", "5", "--rehearse", "1",
             "--parent-pid", str(os.getpid())],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE, stderr=log,
            text=True, env=cpu_env(tmp_path), start_new_session=True)
        try:
            port = child_events(proc, "ready", REACH_S)[-1]["port"]
            for cmd, event in (("arm", "armed"), ("disarm", "disarmed")):
                proc.stdin.write(cmd + "\n")
                proc.stdin.flush()
                assert child_events(proc, event, 60.0)[-1]["event"] == event
            if last == "stop":
                proc.stdin.write("stop\n")
                proc.stdin.flush()
            else:
                proc.stdin.close()
            rest = [e["event"] for e in child_events(proc, "stopped", 120.0)]
            rc = proc.wait(timeout=60)
            assert not listening(ports={port})
        finally:
            end_all(proc, [])
    if last == "stop":
        # No request was served, so there is nothing to sample: the
        # comparison is made and says so.
        assert rest == ["logit_check", "stopped"] and rc == 0
    else:
        assert rest == [] and rc == lifeline.ORPHANED
        assert "no comparison" in open(tmp_path / "child.log").read()


@pytest.mark.parametrize("lines,stopped", [
    ("arm\ndisarm\n", False), ("arm\ndisarm\nstop\narm\n", True),
    ("", False), ("stop\n", True)])
def test_the_command_loop_says_how_it_was_left(monkeypatch, capsys, lines,
                                               stopped):
    monkeypatch.setattr(sys, "stdin", io.StringIO(lines))
    assert lifeline.serve_commands(None, "") is stopped
    said = [json.loads(ln)["event"] for ln in capsys.readouterr().out.split(
        "\n") if ln]
    assert said == [{"arm": "armed", "disarm": "disarmed"}[c]
                    for c in lines.split("stop")[0].split()]


def test_every_child_is_tied_and_none_has_a_loop_of_its_own():
    runners = os.path.join(ROOT, "benchmark", "runners")
    children = sorted(f for f in os.listdir(runners)
                      if f.endswith("_child.py"))
    assert len(children) == 7
    for f in children:
        src = open(os.path.join(runners, f)).read()
        main = src[src.index("def main("):]
        tie = main.index("lifeline.tie_to_parent(args.parent_pid)")
        assert tie < main.index("import jax"), f
        assert tie < main.index("from benchmark import program"), f
        assert "import jax" not in src[:src.index("def main(")], f
        assert "lifeline.serve_until_stopped(srv" in src, f
        assert "return lifeline.ORPHANED" in src, f
        assert "sys.stdin:" not in src and "def serve_commands" not in src, f
    popens = sum(open(os.path.join(runners, f)).read().count(
        "subprocess.Popen") for f in os.listdir(runners) if f.endswith(".py"))
    assert popens == 1


# ---- the lifeline and the reaping, on processes that cost nothing ----

LEAF = textwrap.dedent("""
    import sys, time
    sys.path.insert(0, {root!r})
    from benchmark.runners import lifeline
    lifeline.tie_to_parent(int(sys.argv[1]))
    print("tied", flush=True)
    time.sleep(120)
""")
MIDDLE = textwrap.dedent("""
    import os, subprocess, sys, time
    leaf = subprocess.Popen([sys.executable, sys.argv[1], str(os.getpid())],
                            stdout=subprocess.PIPE, text=True,
                            start_new_session=True)
    leaf.stdout.readline()
    print(leaf.pid, flush=True)
    time.sleep(120)
""")


def test_a_tied_child_dies_with_its_parent(tmp_path):
    (tmp_path / "leaf.py").write_text(LEAF.format(root=ROOT))
    (tmp_path / "middle.py").write_text(MIDDLE)
    mid = subprocess.Popen(
        [sys.executable, str(tmp_path / "middle.py"),
         str(tmp_path / "leaf.py")], stdout=subprocess.PIPE, text=True)
    leaf = None
    try:
        leaf = psutil.Process(int(mid.stdout.readline()))
        assert living([leaf])
        mid.kill()
        wait_until(lambda: not living([leaf]), 2.0, "the leaf follows")
    finally:
        end_all(mid, [leaf] if leaf else [])


def test_a_child_handed_another_pid_than_its_parents_leaves_at_once(tmp_path):
    (tmp_path / "leaf.py").write_text(LEAF.format(root=ROOT))
    p = subprocess.run([sys.executable, str(tmp_path / "leaf.py"), "1"],
                       capture_output=True, text=True, timeout=30)
    assert p.returncode == lifeline.ORPHANED and "tied" not in p.stdout


FAKE_CHILD = textwrap.dedent("""
    import json, subprocess, sys, time
    grand = subprocess.Popen([sys.executable, "-c",
                              "import time; time.sleep(120)"])
    print(json.dumps({"event": "up", "grandchild": grand.pid}), flush=True)
    for line in sys.stdin:
        pass  # deaf to `stop`: no comparison ever comes
    time.sleep(120)
""")


@pytest.fixture
def fake_child(tmp_path):
    (tmp_path / "fake_child.py").write_text(FAKE_CHILD)

    class Fake(serve.Child):
        script = str(tmp_path / "fake_child.py")  # absolute: joins to itself

    child = Fake({}, 0, 1, True, "", str(tmp_path / "fake.log"))
    try:
        yield child
    finally:
        child.kill()
    assert child not in serve.LIVE


def test_kill_takes_the_childs_whole_group(fake_child):
    up = fake_child.wait_for("up", 30.0)
    both = [psutil.Process(fake_child.proc.pid),
            psutil.Process(up["grandchild"])]
    assert living(both) == both and fake_child in serve.LIVE
    assert os.getsid(both[0].pid) == both[0].pid != os.getsid(0)
    serve.kill_live()  # what run.py's signal handlers and atexit call
    assert not living(both) and fake_child not in serve.LIVE


def test_a_comparison_that_does_not_come_is_a_problem_not_a_hang(fake_child):
    up = fake_child.wait_for("up", 30.0)
    both = [psutil.Process(fake_child.proc.pid),
            psutil.Process(up["grandchild"])]
    t0 = time.monotonic()
    check = fake_child.check_after_window(timeout=1.0)
    assert time.monotonic() - t0 < 10.0
    assert check["ok"] is False and "within 1 s" in check["problem"]
    assert serve.check_problems(check, ("cold_long",)) == [check["problem"]]
    assert not living(both)
