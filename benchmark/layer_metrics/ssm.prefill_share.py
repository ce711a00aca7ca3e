"""Model step: the selective scan's share of the prefill program's
device time, %: summed self time of `_selective_scan` / the time of
`paged_prefill` in the traced slice. What is left is the chunk's
matmuls, its attention layers and the head.

None where the trace has no such kernel (a program without state-space
layers) or no prefill dispatch."""
LAYER = "model step"
from benchmark import trace

KERNELS = ("_selective_scan",)
PROGRAMS = ("paged_prefill",)


def read(run):
    tr = run.get("trace") or {}
    scan, _ = trace.match_seconds(tr.get("ops", {}), KERNELS)
    sec, _ = trace.match_seconds(tr.get("modules", {}), PROGRAMS)
    return 100.0 * scan / sec if scan and sec else None
