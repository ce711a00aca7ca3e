"""Scheduler: share of decode-step lane slots that produced a token a
request wanted (decode_steps_useful / decode_steps_total), %."""
LAYER = "scheduler"


def read(run):
    c = run["counters"]
    total = c["decode_steps_total"]
    return 100.0 * c["decode_steps_useful"] / total if total else None
