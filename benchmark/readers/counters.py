"""Readers over the program's counters and histograms
(`optimize/metrics.py`), as deltas over the window."""
from __future__ import annotations

from benchmark import probes, stats


def ratio(reading, numerator: str, denominator: str):
    c = reading["probe"].counters
    num, den = c.get(numerator, 0.0), c.get(denominator, 0.0)
    if den <= 0 or num <= 0:
        return None
    return num / den


def histogram_median(reading, name: str):
    vals = probes.histogram_values(name, reading["probe"].t0_mono)
    if not vals:
        return None
    return stats.median(vals)
