"""Scheduler: engine-thread milliseconds a dispatch spent copying its
outputs to the host AFTER the first of them had arrived, so with the
device drained: `engine_phase_seconds_total{phase="copy_out"}` /
`dispatches_total`. The first read, which waits for the program, is
the `harvest` phase and is not in it. What one packed output, or
`copy_to_host_async` at the enqueue, would remove (in block mode the
copies run under the next block, so there it is the engine thread's
cost and not the device's wait).

Reads run["counters"]. None when the program has no `copy_out` phase (a
parent before PR 35) or nothing was dispatched; a KeyError when
`dispatches_total` is gone."""
LAYER = "scheduler"


def read(run):
    c = run["counters"]
    copy_s = c.get('engine_phase_seconds_total{phase="copy_out"}')
    if copy_s is None:
        return None
    n = c["dispatches_total"]
    return 1e3 * copy_s / n if n else None
