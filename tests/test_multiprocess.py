"""REAL multi-process distributed training (SURVEY.md §2c: the NCCL/MPI
multi-host backend equivalent): two OS processes, each owning 4 CPU
devices, rendezvous via jax.distributed (Gloo) and run the unmodified
Trainer over the global dp=2 x fsdp=4 mesh. This is the closest
available analog to multi-host TPU on a single box — cross-process
collectives, single-controller batch semantics, per-process addressable
shards — and complements the in-process 8-device mesh tests which never
leave one runtime."""

import json
import os
import socket
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
WORKER = os.path.join(REPO, "tests", "mp_trainer_worker.py")
SERVE_WORKER = os.path.join(REPO, "tests", "mp_serve_worker.py")
RING_WORKER = os.path.join(REPO, "tests", "mp_ring_worker.py")


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _run_workers(worker: str, extra_args: list[str]) -> list[dict]:
    env = {
        **os.environ,
        "JAX_PLATFORMS": "cpu",
        # The collective timeout covers Gloo's key-value rendezvous
        # (default ~30s): under full-suite contention on this one-core
        # box the workers' first collectives can arrive minutes apart
        # (observed: 'GetKeyValue() timed out ... 29.99s' crashing one
        # worker while its peer still compiled).
        "XLA_FLAGS": "--xla_force_host_platform_device_count=4 "
                     "--xla_cpu_collective_timeout_seconds=600",
    }
    port = _free_port()
    procs = [
        subprocess.Popen(
            [sys.executable, worker, str(i), str(port), *extra_args],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
            text=True,
        )
        for i in range(2)
    ]
    results = []
    try:
        for p in procs:
            # Must exceed the workers' 600s rendezvous window
            # (mp_common.bootstrap) or a slow rendezvous times out HERE
            # first, killing the workers before they can report anything.
            out, err = p.communicate(timeout=900)
            # Generous stderr tail: a worker's jax traceback is long, and
            # this message is the ONLY diagnostic a CI failure preserves.
            assert p.returncode == 0, (out[-800:], err[-4000:])
            line = next(
                l for l in out.splitlines() if l.startswith('{"mp_result"')
            )
            rec = json.loads(line)
            # Keep only the harness's own report lines for assertions —
            # a failed assert must not dump two full worker stdouts of
            # XLA noise over the mismatched values.
            rec["_report_lines"] = [
                l for l in out.splitlines()
                if l.startswith("dryrun_multichip ok:")
            ]
            results.append(rec)
    finally:
        # A failed/crashed worker must not strand its peer in the Gloo
        # rendezvous (it would outlive the test run blocked on a dead
        # collective with an undrained pipe).
        for q in procs:
            if q.poll() is None:
                q.kill()
                q.communicate()
    assert {r["pid"] for r in results} == {0, 1}
    assert all(r["process_count"] == 2 for r in results)
    return results


@pytest.mark.slow
def test_two_process_trainer_fsdp(tmp_path):
    results = _run_workers(WORKER, [str(tmp_path)])

    for r in results:
        assert r["step"] == 2
        # Coordinated orbax save at step 2 restored by a fresh Trainer
        # in every process (multi-host pod-restart posture).
        assert r["resumed"] == 2
    # GSPMD must produce ONE global answer: both processes report the
    # same post-training loss to the printed precision.
    assert results[0]["loss"] == results[1]["loss"], results


@pytest.mark.slow
def test_two_process_tp_serving():
    """Tensor-parallel serving over the global tp=8 mesh across two
    processes — the reference's multi-GPU device_map analog at
    multi-host scale. Both processes run the same two-request batch and
    must report byte-identical reply lists."""
    results = _run_workers(SERVE_WORKER, [])
    assert results[0]["replies"] == results[1]["replies"], results
    assert len(results[0]["replies"]) == 2


@pytest.mark.slow
def test_two_process_ring_attention_sp8():
    """sp=8 over two processes: the decoder's ring attention ppermutes
    K/V blocks around a ring that crosses the process boundary — the
    single-box analog of ring attention over ICI/DCN on a pod. Runs the
    exact driver-facing dryrun program (__graft_entry__._dryrun_one_mesh)
    and requires the identical finite loss on both processes."""
    results = _run_workers(RING_WORKER, [])
    ok_lines = [r["_report_lines"][0] for r in results]
    assert " sp=8 " in ok_lines[0] and "attn=ring" in ok_lines[0], ok_lines
    assert ok_lines[0] == ok_lines[1], ok_lines
