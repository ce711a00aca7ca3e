"""Model step: device time of ONE forward of a block dispatch, ms: the
time of the `paged_block_step` program on the trace's XLA Modules line
/ the forwards (denoising + commit) it ran in the traced slice, from
`diffusion_forwards_total` scraped at the slice's ends. Its floor is
the weight bytes of a forward / the chip's HBM rate (11.4 ms for
SDAR-30B-A3B at depth 7 with every expert hit).

None where the trace has no such program or the counters no forwards."""
LAYER = "model step"
from benchmark import trace

PROGRAMS = ("paged_block_step",)


def read(run):
    sec, _ = trace.match_seconds(run["trace"]["modules"], PROGRAMS)
    n = run["trace"]["slice_counters"].get("diffusion_forwards_total")
    return 1e3 * sec / n if sec and n else None
