"""Scheduler: p90 over requests of the time per token after the first
over the window of the traced run, ms (Harrell-Davis), in a cell that
is judged by its throughput: there each decode chunk waits for the
prefill chunks admitted between it and the last one."""
LAYER = "scheduler"


def read(run):
    return run["requests"]["tpot_p90_ms"]
