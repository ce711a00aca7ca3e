"""Scheduler: share of the window in which the device had NOTHING
QUEUED while the engine had a request to serve, %: the sum over the
phases of `engine_starved_seconds_total{phase=}` (the engine thread's
seconds from the return of a wait for the device to the end of the next
enqueue, i.e. under `oryx.engine.host`) / the sum over the phases of
`engine_phase_seconds_total{phase=}`, which is the window. What a
dispatch kept in flight, a shorter copy-out or a leaner loop can give
back, by phase in the run's `counters` record; the seconds of `idle`
(no request to serve) are not in it: `sched.norequest_share`.

Reads run["counters"] (the /metrics delta over the window; a family's
key is the sum over its labels). None when the program has no starved
counter (a parent before PR 35) or no phase second passed."""
LAYER = "scheduler"


def read(run):
    c = run["counters"]
    if "engine_starved_seconds_total" not in c:
        return None
    window = c["engine_phase_seconds_total"]
    if not window:
        return None
    return 100.0 * c["engine_starved_seconds_total"] / window
