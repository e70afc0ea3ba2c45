"""Metric arithmetic: percentiles, rates, spreads. No JAX."""
from __future__ import annotations

import math
import statistics
from typing import Iterable, List, Sequence, Tuple


def percentile(values: Sequence[float], q: float) -> float:
    """Nearest-rank percentile (q in 0..100) over ALL values: the smallest
    value with at least q% of the sample at or below it. No interpolation,
    so a tail is a request that happened."""
    if not values:
        raise ValueError("percentile of nothing")
    xs = sorted(values)
    k = max(1, math.ceil(q / 100.0 * len(xs)))
    return float(xs[k - 1])


def median(values: Sequence[float]) -> float:
    return float(statistics.median(values))


def rate(work: float, t_start: float, t_end: float) -> float:
    """All the work of the window over all the time of the window."""
    if t_end <= t_start:
        raise ValueError("window has no length")
    return work / (t_end - t_start)


def iqr_spread(values: Sequence[float]) -> float:
    """Distance between first and third quartile as a share of the median,
    with Python's `statistics.quantiles(values, n=4)` (the contract's)."""
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def merge_intervals(intervals: Iterable[Tuple[float, float]]
                    ) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def union_length(intervals: Iterable[Tuple[float, float]]) -> float:
    """Total length covered by (start, end) intervals."""
    return sum(e - s for s, e in merge_intervals(intervals))


def gaps(busy: Sequence[Tuple[float, float]], t0: float, t1: float
         ) -> List[Tuple[float, float]]:
    """Idle intervals of [t0, t1] given merged busy intervals."""
    out, cur = [], t0
    for s, e in busy:
        s, e = max(s, t0), min(e, t1)
        if e <= s:
            continue
        if s > cur:
            out.append((cur, s))
        cur = max(cur, e)
    if cur < t1:
        out.append((cur, t1))
    return out


def idle_share(busy_s: float, window_s: float) -> float:
    return 1.0 - busy_s / window_s
