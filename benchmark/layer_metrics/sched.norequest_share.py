"""Scheduler: share of the window in which the engine had no request to
serve, %: `engine_phase_seconds_total{phase="idle"}` / the sum over
the phases (the window). Device idle time that no change to the program
gives back: an open loop below the knee has it, a closed loop does not.
With `sched.starved_share` it is the engine's own account of the
device's idle share.

Reads run["counters"]. None when the program has no starved counter (a
parent before PR 35: its `idle` is counted alike, but the account this
is half of is not there) or no phase second passed."""
LAYER = "scheduler"


def read(run):
    c = run["counters"]
    if "engine_starved_seconds_total" not in c:
        return None
    window = c["engine_phase_seconds_total"]
    idle_s = c.get('engine_phase_seconds_total{phase="idle"}', 0.0)
    return 100.0 * idle_s / window if window else None
