"""Trainer: median wall time of one step, ms — the program's own
dispatch + device_sync spans from its metrics log (the end-to-end rate
is taken on the benchmark's clock over the whole window instead)."""
LAYER = "trainer"
from benchmark import stats


def read(run):
    return 1e3 * stats.median(run["train"]["program_step_s"])
