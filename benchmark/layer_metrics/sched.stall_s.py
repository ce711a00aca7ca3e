"""Scheduler: seconds of the window the engine thread spent in single
uninterrupted stretches of one phase longer than the program's
`STALL_SECONDS` (0.5; `idle` apart): `engine_stall_seconds_total`,
summed over its phases. 0.0 means 0: no stretch was that long, which is
every normal run. A run whose end-to-end metric reads low with seconds
here was held up once, and `engine_stall_seconds_total{phase=}` in its
`counters` record says in which phase.

Reads run["counters"]. None when the program has no stall counter (a
parent before PR 35)."""
LAYER = "scheduler"


def read(run):
    return run["counters"].get("engine_stall_seconds_total")
