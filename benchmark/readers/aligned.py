"""Reader that lays the program's own spans beside the device's module
runs on one clock, as `Probe` lays them: a span at perf_counter `ts` lies
at `ts - probe.session_t0` on the trace's clock, which is right only if
the trace's zero is the instant `session_t0` was read. The same reader
says how far that can be off: a module run cannot start before the span
that launched it began, nor end after the span that fetched its outputs
returned, and each run so bounds the true offset from one side."""
from __future__ import annotations

from bisect import bisect_right

from benchmark import stats


def _laid(probe, name):
    """(start, end) of the program's spans of that name on the trace's
    clock, by start."""
    t0 = probe.session_t0
    return sorted((s["ts"] - t0, s["ts"] - t0 + s["dur"])
                  for s in probe.spans if s["name"] == name)


def align(probe, module: str, launch: str, fetch: str):
    """For each run of a module whose name contains `module`, wholly in
    the traced window on the busiest device: (latency, slack) in seconds.
    `latency` is the run's start minus the start of the latest `launch`
    span that began before it; `slack` the end of the first `fetch` span
    that began after that launch, minus the run's end. If the trace's zero
    truly lies `d` seconds after `session_t0`, causality wants
    -latency <= d <= slack of every run. None where there is no trace, no
    such run or no such span."""
    tr = probe.reduced
    if tr is None or not tr.modules or not tr.window:
        return None
    launches, fetches = _laid(probe, launch), _laid(probe, fetch)
    if not launches:
        return None
    w0, w1 = tr.window
    runs = sorted((s, s + d) for name, s, d in tr.modules.get(tr.fullest(), [])
                  if module in name and s >= w0 and s + d <= w1)
    starts = [a for a, _ in launches]
    fetch_starts = [a for a, _ in fetches]
    out = []
    for a, b in runs:
        i = bisect_right(starts, a) - 1
        if i < 0:
            continue
        j = bisect_right(fetch_starts, starts[i])
        slack = fetches[j][1] - b if j < len(fetches) else None
        out.append((a - starts[i], slack))
    return out or None


def launch_to_device_ms(reading, module: str, launch: str, fetch: str):
    """Median milliseconds from the start of a `launch` span to the start
    of the module run it launched: the operands' way over the link and the
    dispatch. Prints the spread and the interval in which the offset
    between `session_t0` and the trace's zero must lie."""
    pairs = align(reading["probe"], module, launch, fetch)
    if pairs is None:
        return None
    lat = [p[0] for p in pairs]
    slack = [p[1] for p in pairs if p[1] is not None]
    lo = -min(lat)
    hi = min(slack) if slack else float("nan")
    print(f"info clock: launch->device over {len(lat)} runs "
          f"min/median/max {1e3 * min(lat):.3f}/"
          f"{1e3 * stats.median(lat):.3f}/{1e3 * max(lat):.3f} ms; the "
          f"trace's zero lies between {1e3 * lo:.3f} and {1e3 * hi:.3f} ms "
          f"after session_t0" + (
              ": EMPTY, spans and runs are mispaired" if lo > hi else
              "" if lo <= 0.0 <= hi else
              ": NOT at session_t0, the spans are laid off the trace's clock"),
          flush=True)
    return 1e3 * stats.median(lat)
