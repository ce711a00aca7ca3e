"""Vision front end: device time of the ViT + compressor + splice
program (mm_embeds) per visual request, ms, from the trace."""
LAYER = "vision front end"
from benchmark import trace

PROGRAMS = ("mm_embeds",)


def read(run):
    sec, n = trace.match_seconds(run["trace"]["modules"], PROGRAMS)
    return 1e3 * sec / n if n else None
