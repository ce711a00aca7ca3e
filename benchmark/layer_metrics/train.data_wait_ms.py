"""Trainer: median of the trainer's `data` phase, ms: how long the step
loop waited for its next batch (the prefetch thread keeps it near zero
while collating is faster than a step).

Reads run["train"]["data_s"] (the metric records' data_s). None when no
step was logged."""
LAYER = "trainer"
from benchmark import stats


def read(run):
    data_s = run["train"]["data_s"]
    return 1e3 * stats.median(data_s) if data_s else None
