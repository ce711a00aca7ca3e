"""Load generator: p90 of (actual send - due instant), ms, in an open
loop. A guard on reading the cell's tails: a generator that runs late
offers less load than the cell states."""
LAYER = "load generator"
from benchmark import stats


def read(run):
    return stats.percentile(run["lateness_ms"], 90)
