"""Parallel: share of device busy time spent in collectives, %: self
seconds of the ops whose name starts with all-gather, all-reduce,
reduce-scatter, all-to-all or collective-permute / busy seconds, both
averaged over the chips. On a v5e trace collectives sit on the one
`XLA Ops` line with the compute, so none of this time is hidden behind
compute: all of it is exposed.

Reads run["trace"]["ops"] and ["busy_s"]. None where there is no device
plane (the CPU rehearsal)."""
LAYER = "parallel"

COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
               "collective-permute")


def read(run):
    t = run["trace"]
    if not t.get("busy_s"):
        return None
    sec = sum(s for name, (s, _) in t["ops"].items()
              if name.startswith(COLLECTIVES))
    return 100.0 * sec / t["busy_s"]
