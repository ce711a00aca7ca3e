"""Model step: device time of ONE decode step, ms, from the trace's XLA
Modules line: the time of a `paged_decode_chunk` dispatch divided by
the configuration's decode_chunk (it scans that many steps), or of a
`paged_ragged_step` dispatch as it is (one step)."""
LAYER = "model step"
from benchmark import trace


def read(run):
    mods = run["trace"]["modules"]
    chunk = run["config"]["layout"]["decode_chunk"]
    sec_c, n_c = trace.match_seconds(mods, ("paged_decode_chunk",))
    sec_r, n_r = trace.match_seconds(mods, ("paged_ragged_step",))
    steps = n_c * chunk + n_r
    return 1e3 * (sec_c + sec_r) / steps if steps else None
