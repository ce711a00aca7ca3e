"""Scheduler: mean time a request finished in the window spent in its
`admission` spans, ms: queue head -> first token, i.e. prompt prep, the
wait for pages, every prefill chunk and whatever ran between them
(decode chunks, in the split engine). `request_prefill_seconds_sum /
_count`, window delta, as PR 24 redefined the family (before it the
family summed the `prefill` spans, which time an enqueue: a parent
before PR 24 reports that smaller number here).

Reads run["counters"]. None when no request finished; a KeyError (the
run is then incorrect, by name) when the family is gone."""
LAYER = "scheduler"


def read(run):
    c = run["counters"]
    n = c["request_prefill_seconds_count"]
    return 1e3 * c["request_prefill_seconds_sum"] / n if n else None
