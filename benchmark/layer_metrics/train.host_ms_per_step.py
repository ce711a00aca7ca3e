"""Trainer: what the host does serially per step, ms: median step time
on the benchmark's clock (`step_s`, batch handed over to batch handed
over) - median of the trainer's own dispatch + sync phases
(`program_step_s`): data wait, h2d, the metric record and whatever else
sits between one step's sync and the next one's dispatch.

Reads run["train"]["step_s"] and ["program_step_s"]. None when no step
was timed."""
LAYER = "trainer"
from benchmark import stats


def read(run):
    t = run["train"]
    if not t["step_s"] or not t["program_step_s"]:
        return None
    return 1e3 * (stats.median(t["step_s"])
                  - stats.median(t["program_step_s"]))
