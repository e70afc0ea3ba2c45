"""Readers over the program's own spans (`optimize/tracing.py`), which a
traced run collects from its ring. `reading["probe"].spans` holds
{name, ts, dur} in seconds on the host's perf_counter clock."""
from __future__ import annotations

from benchmark import stats


def _in_window(reading, name):
    w = reading["window"]
    return [s for s in reading["probe"].spans
            if s["name"] == name and w["t0"] <= s["ts"] < w["t1"]]


def share_of_window(reading, name: str):
    """Sum of the named spans over the window's whole time, in percent."""
    spans = _in_window(reading, name)
    if not spans:
        return None
    w = reading["window"]
    return 100.0 * sum(s["dur"] for s in spans) / (w["t1"] - w["t0"])


def median_ms(reading, name: str):
    spans = _in_window(reading, name)
    if not spans:
        return None
    return 1e3 * stats.median([s["dur"] for s in spans])
