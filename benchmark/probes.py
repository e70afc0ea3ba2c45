"""What the benchmark takes from the program while a window runs: its
counters (deltas over the window), its histograms' observations, its
spans, the count of compilations, and - in a traced run - the profiler's
trace of the device over a part of the window.
"""
from __future__ import annotations

import os
import shutil
import threading
import time
from typing import Dict, List, Optional

from benchmark import trace_reduce


def counter_totals() -> Dict[str, float]:
    """Every counter family of the program's registry, summed over its
    labels, and `name{label=value}` for each labelled child."""
    from deeplearning4j_tpu.optimize.metrics import Counter, registry
    reg = registry()
    out: Dict[str, float] = {}
    with reg._lock:
        fams = list(reg._families.values())
    for fam in fams:
        if not isinstance(fam, Counter):
            continue
        out[fam.name] = float(fam.total())
        for labels, child in fam.items():
            if labels:
                key = ",".join(f"{k}={v}" for k, v in sorted(labels.items()))
                out[f"{fam.name}{{{key}}}"] = float(child.value())
    return out


def histogram_values(name: str, since_monotonic: float) -> List[float]:
    """Observations of a histogram family (all labels) stamped at or after
    `since_monotonic`, as far as its ring still holds them."""
    from deeplearning4j_tpu.optimize.metrics import Histogram, registry
    reg = registry()
    with reg._lock:
        fam = reg._families.get(name)
    if not isinstance(fam, Histogram):
        return []
    now = time.monotonic()
    out: List[float] = []
    for h in [fam] + [c for labels, c in fam.items() if labels]:
        out += h.window_values(now - since_monotonic, now=now)
    return out


class Probe:
    """Opened before a window, closed after it."""

    def __init__(self, trace: bool, out_dir: str, host_names,
                 trace_offset_s: float, trace_seconds: float):
        self.trace = trace
        self.out_dir = out_dir
        self.host_names = list(host_names)
        self.offset, self.length = trace_offset_s, trace_seconds
        self.counters0: Dict[str, float] = {}
        self.counters: Dict[str, float] = {}
        self.compilations = 0
        self.spans: List[dict] = []
        self.reduced: Optional[trace_reduce.Trace] = None
        self.traced_host: Optional[tuple] = None   # perf_counter interval
        self._thread: Optional[threading.Thread] = None
        self._error: Optional[BaseException] = None
        self.t0_mono = 0.0

    # -- lifecycle -----------------------------------------------------
    def open(self) -> None:
        from deeplearning4j_tpu.optimize import tracing
        if self.trace:
            tracing.enable(ring_size=1 << 18, annotate=True, fence_every=0)
            tracing.clear()
        self.counters0 = counter_totals()
        self.t0_mono = time.monotonic()
        if self.trace:
            self._thread = threading.Thread(target=self._profile,
                                            name="bench-profiler")
            self._thread.start()

    def _profile(self) -> None:
        import jax
        try:
            time.sleep(self.offset)
            shutil.rmtree(self.out_dir, ignore_errors=True)
            os.makedirs(self.out_dir, exist_ok=True)
            # The device's events only. With the host's tracer on, the TPU
            # runtime's own threads log a million futex events a second
            # and the fit loop ran at a seventh of its speed inside the
            # traced window; with Python's call tracer on, worse (my chip
            # runs, PR 24). The program's spans come from its own ring, on
            # perf_counter, and are laid on the trace's clock by the time
            # at which the session was started.
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 0
            self.session_t0 = time.perf_counter()
            jax.profiler.start_trace(self.out_dir, profiler_options=opts)
            try:
                a = time.perf_counter()
                time.sleep(self.length)
                self.traced_host = (a, time.perf_counter())
            finally:
                jax.profiler.stop_trace()
        except BaseException as e:  # reported by close()
            self._error = e

    def close(self) -> None:
        from deeplearning4j_tpu.optimize import tracing
        now = counter_totals()
        self.counters = {k: v - self.counters0.get(k, 0.0)
                         for k, v in now.items()}
        self.compilations = int(self.counters.get("xla_compilations_total",
                                                  0))
        if not self.trace:
            return
        self._thread.join()
        self.spans = [dict(name=e["name"], ts=e["ts"] * 1e-6,
                           dur=e["dur"] * 1e-6)
                      for e in tracing.export_trace_events()["traceEvents"]]
        tracing.disable()
        if self._error is not None:
            raise self._error
        tr = trace_reduce.load(self.out_dir)
        c0 = self.session_t0
        tr.window = (self.traced_host[0] - c0, self.traced_host[1] - c0)
        tr.host = [(sp["name"], sp["ts"] - c0, sp["dur"])
                   for sp in self.spans if sp["name"] in self.host_names]
        self.reduced = tr
        shutil.rmtree(self.out_dir, ignore_errors=True)

    def selected(self) -> Dict[str, float]:
        """The window's `*_selected_total` deltas: which implementation
        each dispatch rule chose (printed, never a metric)."""
        return {k: v for k, v in self.counters.items()
                if "_selected_total" in k and v}
