"""Order statistics the benchmark reports, kept with it."""

from __future__ import annotations

import math
import statistics


def percentile(values, q: float, *, failed: int = 0) -> float:
    """The q-th percentile (0 < q < 100) by nearest rank over `values`
    plus `failed` entries ranked as +inf: a request that failed, was
    refused or was cut short misses every limit, so it sits at the top
    of every tail. Returns inf when the rank falls among the failures,
    nan when there is nothing to rank."""
    n = len(values) + failed
    if n == 0:
        return math.nan
    rank = max(1, math.ceil(q / 100.0 * n))  # 1-based nearest rank
    if rank > len(values):
        return math.inf
    return sorted(values)[rank - 1]


def quantile_hd(values, q: float, *, failed: int = 0) -> float:
    """The q-th percentile by the Harrell-Davis estimator: a weighted
    mean of ALL the order statistics, the weights a Beta(q(n+1),
    (1-q)(n+1)) density's mass over each rank, so it peaks at rank q*n
    and falls off over the few ranks around it.

    Why not one order statistic: the engine answers in whole iterations
    (a decode chunk is 0.3 s), so time to first token is quantised, and
    the single value at rank 0.9 n of some 60 requests flips between
    two quanta 11 % apart from run to run of the same seed (PERF.md,
    PR 23). The estimator still reads the tail of all requests; it
    only refuses to hang on one of them.

    `failed` requests rank as +inf: any failure makes the estimate inf
    (and the run incorrect)."""
    from scipy.special import betainc

    n = len(values)
    if n == 0:
        return math.inf if failed else math.nan
    if failed:
        return math.inf
    xs = sorted(values)
    p = q / 100.0
    a, b = p * (n + 1), (1 - p) * (n + 1)
    cdf = [betainc(a, b, i / n) for i in range(n + 1)]
    return float(sum((cdf[i + 1] - cdf[i]) * xs[i] for i in range(n)))


def median(values) -> float:
    return statistics.median(values) if values else math.nan


def iqr_share(values) -> float:
    """The contract's spread: (Q3 - Q1) / median, quartiles as
    statistics.quantiles(values, n=4) gives them."""
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)
