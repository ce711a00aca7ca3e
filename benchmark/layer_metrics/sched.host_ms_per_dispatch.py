"""Scheduler: engine-thread seconds spent WORKING (not waiting) per
device dispatch over the window, ms: the sum over the host phases of
`engine_phase_seconds_total{phase=}` (exclusive seconds; the waits
idle, first_token and harvest are left out) / `dispatches_total`.
With the device's idle share it says how much of a cycle is the host's:
host ms x dispatches against idle seconds of the same slice.

Reads run["counters"] (the /metrics delta over the window). None when
the program has no phase counter (a parent before PR 24) or nothing was
dispatched; a KeyError (the run is then incorrect, by name) when
`dispatches_total` is gone."""
LAYER = "scheduler"

HOST_PHASES = ("housekeeping", "admit", "prompt_prep", "embed", "prefill",
               "decode", "emit")


def read(run):
    c = run["counters"]
    if "engine_phase_seconds_total" not in c:
        return None
    n = c["dispatches_total"]
    host_s = sum(
        c.get('engine_phase_seconds_total{phase="%s"}' % p, 0.0)
        for p in HOST_PHASES
    )
    return 1e3 * host_s / n if n else None
