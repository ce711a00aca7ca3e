"""Scheduler: mean time a request finished in the window spent in its
`queue_wait` spans (submit -> queue head, and again after an eviction),
ms: `request_queue_seconds_sum / _count`, window delta.

Reads run["counters"]. None when no request finished; a KeyError (the
run is then incorrect, by name) when the family is gone."""
LAYER = "scheduler"


def read(run):
    c = run["counters"]
    n = c["request_queue_seconds_count"]
    return 1e3 * c["request_queue_seconds_sum"] / n if n else None
