"""Reduction of a JAX profiler trace (`.xplane.pb`) to what the metrics
read: per device the operations with start and duration and the executed
modules.

Reads with `jax.profiler.ProfileData` and nothing else. A device plane is
one whose name starts with `/device:TPU:`; its `XLA Ops` line holds one
event per executed HLO operation and its `XLA Modules` line one per
executed program. Times are seconds from the start of the profiler's
session. The benchmark traces with the host's tracer off (see
`probes.py`), so the caller sets `window` and `host` (the program's spans
that idle gaps are laid to) from the host's own clock.
"""
from __future__ import annotations

import glob
import os
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

from benchmark import stats

OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
Event = Tuple[str, float, float]          # name, start_s, dur_s


def short_name(name: str) -> str:
    """The TPU's trace names an event by its whole HLO instruction; keep
    the instruction's name and, for a fusion, its kind."""
    head = name.split(" = ", 1)[0].strip()
    kind = name.rsplit("kind=", 1)[1].split(",", 1)[0] if "kind=" in name \
        else ""
    return f"{head} {kind}".strip()


@dataclass
class Trace:
    ops: Dict[int, List[Event]] = field(default_factory=dict)
    modules: Dict[int, List[Event]] = field(default_factory=dict)
    host: List[Event] = field(default_factory=list)
    window: Optional[Tuple[float, float]] = None   # on the trace's clock

    def devices(self) -> List[int]:
        return sorted(self.ops)

    def clipped(self, events: Sequence[Event]) -> List[Event]:
        """(name, start, END) of the part of each event inside the window."""
        t0, t1 = self.window
        out = []
        for name, s, d in events:
            a, b = max(s, t0), min(s + d, t1)
            if b > a:
                out.append((name, a, b))
        return out

    def clip(self, events: Sequence[Event]) -> List[Tuple[float, float]]:
        return [(a, b) for _, a, b in self.clipped(events)]

    def window_s(self) -> float:
        return self.window[1] - self.window[0]

    def busy_s(self, dev: int) -> float:
        return stats.union_length(self.clip(self.ops[dev]))

    def mean_busy_s(self) -> float:
        return sum(self.busy_s(d) for d in self.devices()) / len(self.ops)

    def fullest(self) -> int:
        return max(self.devices(), key=self.busy_s)

    def top_ops(self, n: int = 10) -> List[list]:
        """Device operations that took most time in the window, summed by
        name over the busiest device."""
        tot: Dict[str, float] = {}
        for name, a, b in self.clipped(self.ops[self.fullest()]):
            key = short_name(name)
            tot[key] = tot.get(key, 0.0) + (b - a)
        return [[k, v] for k, v in sorted(tot.items(),
                                          key=lambda kv: -kv[1])[:n]]

    def idle_gaps(self, n: int = 10) -> List[list]:
        """Idle time of the busiest device in the window, by the innermost
        span of `host` that covers each gap's midpoint (`(none)` where
        none does)."""
        dev = self.fullest()
        busy = stats.merge_intervals(self.clip(self.ops[dev]))
        spans = sorted(((s, s + d, name) for name, s, d in self.host),
                       key=lambda e: e[1] - e[0])
        tot: Dict[str, float] = {}
        for a, b in stats.gaps(busy, *self.window):
            mid = (a + b) / 2
            who = next((nm for s, e, nm in spans if s <= mid <= e), "(none)")
            tot[who] = tot.get(who, 0.0) + (b - a)
        return [[k, v] for k, v in sorted(tot.items(),
                                          key=lambda kv: -kv[1])[:n]]

    def module_busy(self, dev: int, needle: str) -> Tuple[float, int]:
        """Busy seconds of operations that ran inside executions of modules
        whose name contains `needle`, and the number of such executions
        that lie wholly in the window."""
        t0, t1 = self.window
        runs = [(s, s + d) for name, s, d in self.modules.get(dev, [])
                if needle in name and s >= t0 and s + d <= t1]
        if not runs:
            return 0.0, 0
        runs.sort()
        ops = sorted((s, s + d) for _, s, d in self.ops[dev])
        inside, i = [], 0
        for a, b in runs:
            while i < len(ops) and ops[i][1] <= a:
                i += 1
            j = i
            while j < len(ops) and ops[j][0] < b:
                inside.append((max(ops[j][0], a), min(ops[j][1], b)))
                j += 1
        return stats.union_length(inside), len(runs)


def find_xplane(trace_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def load(path: str) -> Trace:
    """`path` is an .xplane.pb or the directory given to start_trace."""
    from jax.profiler import ProfileData
    if os.path.isdir(path):
        path = find_xplane(path)
    tr = Trace()
    data = ProfileData.from_file(path)
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            dev = int(plane.name.rsplit(":", 1)[1].split()[0])
            for line in plane.lines:
                if line.name not in (OPS_LINE, MODULES_LINE):
                    continue
                evs = [(e.name, e.start_ns * 1e-9, e.duration_ns * 1e-9)
                       for e in line.events]
                (tr.ops if line.name == OPS_LINE else tr.modules)[dev] = evs
    return tr


def describe(path: str, n: int = 8) -> str:
    """Planes, lines and the first events of each: for a look by hand."""
    from jax.profiler import ProfileData
    if os.path.isdir(path):
        path = find_xplane(path)
    out = []
    for plane in ProfileData.from_file(path).planes:
        out.append(f"plane {plane.name!r}")
        for line in plane.lines:
            evs = list(line.events)
            out.append(f"  line {line.name!r}: {len(evs)} events")
            if evs:
                out.append(f"    from {min(e.start_ns for e in evs)} to "
                           f"{max(e.start_ns + e.duration_ns for e in evs)}")
            for e in evs[:n]:
                st = {k: v for k, v in list(e.stats)[:6]}
                out.append(f"    {e.name!r} start={e.start_ns} "
                           f"dur={e.duration_ns} stats={st}")
    return "\n".join(out)
