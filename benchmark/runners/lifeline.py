"""What every serve child (`*_child.py`) shares with the parent that
started it: its life, and the one loop of commands.

  - `tie_to_parent(pid)`, first thing in a child's `main`, before jax is
    imported: the child dies with the parent that passed its pid. A
    child whose parent is gone holds the chip for nobody, and the next
    run on that machine meets it.
  - `serve_commands(srv, trace_dir)`: the one-line commands on stdin
    (`arm`, `trace_start`, `trace_stop`, `disarm`, `stop`), each answered
    with one JSON line on stdout. It says HOW it left: True on `stop`,
    False on end-of-file. End-of-file is not `stop`: the parent is gone
    or has let go, so the child closes its server (`serve_until_stopped`)
    and leaves with `ORPHANED`, and never starts the comparison that a
    `stop` after `disarm` asks for.

Imports jax only inside `serve_commands`, which runs after the child
has configured it.
"""

from __future__ import annotations

import contextlib
import ctypes
import json
import os
import signal
import sys
import threading
import time

ORPHANED = 3  # a child's exit code when its parent went without `stop`
_PR_SET_PDEATHSIG = 1
_POLL_S = 0.5


def add_parent_pid(ap) -> None:
    ap.add_argument("--parent-pid", type=int, default=0,
                    help="the pid to die with (0: tied to nobody, for a "
                    "child started by hand)")


def _end() -> None:
    """Leave NOW, with whatever this process started: the parent gave
    the child a session of its own, so its group is its own too."""
    if os.getpgrp() == os.getpid():
        with contextlib.suppress(OSError):
            os.killpg(os.getpid(), signal.SIGKILL)
    os._exit(ORPHANED)


def tie_to_parent(parent_pid: int) -> None:
    """Die when process `parent_pid`, which started this one, dies:
    the kernel sends SIGKILL at its death (Linux `prctl`; the thread
    that started the child is the parent's main one, which lives as
    long as it), and a daemon thread that finds another parent twice a
    second ends the process where the kernel's signal was not to be had
    or the parent was gone before it was asked for."""
    if not parent_pid:
        return
    if sys.platform == "linux":
        with contextlib.suppress(OSError, AttributeError):
            ctypes.CDLL(None, use_errno=True).prctl(
                _PR_SET_PDEATHSIG, signal.SIGKILL, 0, 0, 0)

    def watch():
        while os.getppid() == parent_pid:
            time.sleep(_POLL_S)
        _end()

    if os.getppid() != parent_pid:
        _end()
    threading.Thread(target=watch, name="lifeline", daemon=True).start()


def say(**kw) -> None:
    sys.stdout.write(json.dumps(kw) + "\n")
    sys.stdout.flush()


def serve_commands(srv, trace_dir: str) -> bool:
    """Obey the parent's one-line commands. True: left on `stop`.
    False: stdin ended first (nobody is there to read a comparison)."""
    import jax

    from oryx_tpu.analysis.sanitizers import recompile_watchdog

    from benchmark import program

    stack = contextlib.ExitStack()
    wd = None
    trace_t = {}
    for line in sys.stdin:
        cmd = line.strip()
        if cmd == "arm":
            wd = stack.enter_context(
                recompile_watchdog(budget=10**9, action="record"))
            say(event="armed")
        elif cmd == "trace_start":
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0  # host spans, no py stacks
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
            trace_t["start"] = time.monotonic()
            say(event="trace_started")
        elif cmd == "trace_stop":
            trace_t["stop"] = time.monotonic()
            jax.profiler.stop_trace()
            say(event="trace_stopped",
                seconds=trace_t["stop"] - trace_t["start"])
        elif cmd == "disarm":
            stack.close()
            out = {
                "event": "disarmed",
                "compiles": int(wd.total) if wd else None,
                "compile_counts": dict(wd.counts) if wd else {},
                "memory_peak_bytes": program.memory_peak_bytes(),
            }
            if trace_t:
                from benchmark import trace as trace_lib

                out["trace"] = trace_lib.reduce_dir(
                    trace_dir, window_s=trace_t["stop"] - trace_t["start"])
            say(**out)
        elif cmd == "stop":
            return True
    return False


def close_server(srv) -> None:
    if srv.supervisor is not None:
        srv.supervisor.stop()
    srv.scheduler.close()
    srv.shutdown()
    srv.server_close()


def serve_until_stopped(srv, trace_dir: str) -> bool:
    """`serve_commands`, then the server closed however the loop was
    left. False (end-of-file): the caller returns `ORPHANED` at once."""
    try:
        on_stop = serve_commands(srv, trace_dir)
    finally:
        close_server(srv)
    if not on_stop:
        print("serve child: stdin ended before `stop`: the parent is gone; "
              "server closed, no comparison", file=sys.stderr, flush=True)
    return on_stop
