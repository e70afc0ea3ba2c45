"""Reader over single phases of the per-request timelines that the
program's flight recorder embeds in each `/generate` reply of a traced
run (`readers/requests.py` reads the same timelines whole)."""
from __future__ import annotations

from benchmark import stats
from benchmark.readers.requests import _traced


def phase_median_ms(reading, phase: str):
    """Median, over the requests due in the window, of the time a request
    spent in `phase` (all of its segments of that name). None where no
    reply carries a timeline or none has the phase."""
    vals = []
    for r in _traced(reading):
        segs = [p["ms"] for p in r["trace"]["phases"] if p["phase"] == phase]
        if segs:
            vals.append(sum(segs))
    return stats.median(vals) if vals else None
