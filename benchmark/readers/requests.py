"""Readers over the per-request timelines that the program's flight
recorder embeds in each `/generate` reply of a traced run, beside the
client's own clock."""
from __future__ import annotations

from benchmark import stats


def _traced(reading):
    w = reading["window"]
    return [r for r in reading["records"]
            if r["ok"] and r.get("trace") and w["t0"] <= r["t_due"] < w["t1"]]


def overhead_ms(reading):
    """Median of the client's latency minus the time the gateway's own
    timeline accounts for (admission to the last phase): HTTP, JSON and
    the handler pool."""
    vals = []
    for r in _traced(reading):
        inside = sum(p["ms"] for p in r["trace"]["phases"])
        vals.append((r["t_done"] - r["t_send"]) * 1e3 - inside)
    return stats.median(vals) if vals else None


def first_token_ms(reading):
    """Median of the time from admission to the end of the first
    `prefill` phase: when the first token exists inside the server."""
    vals = []
    for r in _traced(reading):
        for p in r["trace"]["phases"]:
            if p["phase"] == "prefill":
                vals.append(p["start_ms"] + p["ms"])
                break
    return stats.median(vals) if vals else None
