"""Scheduler: device dispatches per generated token over the window
(dispatches_total / decode_steps_useful)."""
LAYER = "scheduler"


def read(run):
    c = run["counters"]
    useful = c["decode_steps_useful"]
    return c["dispatches_total"] / useful if useful else None
