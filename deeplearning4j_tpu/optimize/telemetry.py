"""XLA compilation telemetry.

"One compile per epoch" is an invariant worth enforcing, not inferring:
a ragged final batch silently compiling a second train-step program
costs seconds of wall time per epoch and shows up nowhere. This module
counts backend compilations two ways:

* A process-global counter fed by a `jax.monitoring` duration listener
  on the backend-compile event — every XLA compilation in the process,
  whatever jitted function triggered it. With tracing on, each is also
  a retroactive `compile` span whose parent is the span that caused
  it. `CompilationTracker` snapshots
  it around a region (PerformanceListener reports the delta between
  reports).
* `jit_cache_size(fn)` — the per-function executable-cache size of one
  `jax.jit` callable (e.g. `net._train_step_fn`), the precise "how many
  distinct shapes did THIS step compile for" probe the regression tests
  pin.

The monitoring listener registers lazily on first use and never
unregisters (jax.monitoring only offers clear-all); it is a counter
bump per compilation — harmless at steady state, where the whole point
is that compilations stop happening.
"""
from __future__ import annotations

import logging
import threading
import time

from . import tracing

log = logging.getLogger(__name__)

_lock = threading.Lock()
_listening = False
_warned_no_monitoring = False

# The event jax records around every backend (XLA) compilation; stable
# across recent jax versions. Matching on the suffix keeps us robust to
# the '/jax/core' vs '/jax' prefix shuffle between releases.
_COMPILE_EVENT_SUFFIX = "backend_compile_duration"


def _counter():
    # The registry is the single source of truth for the count; this
    # module owns registration + the snapshot-delta ergonomics.
    from .metrics import registry
    return registry().counter(
        "xla_compilations_total",
        "Backend (XLA) compilations observed by the jax.monitoring "
        "listener")


def _on_event(event: str, duration: float, **_kw) -> None:
    if event.endswith(_COMPILE_EVENT_SUFFIX):
        _counter().inc()
        # jax calls the listener on the compiling thread as the
        # compilation ends, so the span lies inside whatever span that
        # thread has open (a `dispatch`, a `decode/launch`) and names
        # the step that recompiled by its parent.
        tracing.add_span("compile", time.perf_counter() - duration,
                         duration, cat="compile")


def _ensure_listener() -> bool:
    global _listening, _warned_no_monitoring
    if _listening:
        return True
    with _lock:
        if _listening:
            return True
        try:
            from jax import monitoring
            monitoring.register_event_duration_secs_listener(_on_event)
        except Exception as e:
            # One-shot and LOUD: without this, a zero compile count is
            # indistinguishable from "listener never attached".
            if not _warned_no_monitoring:
                _warned_no_monitoring = True
                log.warning(
                    "jax.monitoring unavailable (%s): XLA compilation "
                    "counters will read 0 — compile-count telemetry is "
                    "OFF, not quiet", e)
            return False
        _listening = True
    return True


def compilation_count() -> int:
    """Process-global backend compilations observed since the listener
    registered (monotonic; meaningful as deltas). Reads the registry's
    `xla_compilations_total` counter — one source of truth with the
    `/metrics` scrape."""
    _ensure_listener()
    return int(_counter().value())


class CompilationTracker:
    """Snapshot-delta view of the global compile counter.

        with CompilationTracker() as trk:
            net.fit(it, epochs=1)
        assert trk.count == 1

    Usable as a context manager or via explicit `.start()`."""

    def __init__(self):
        self.start_count = compilation_count()

    def start(self) -> "CompilationTracker":
        self.start_count = compilation_count()
        return self

    @property
    def count(self) -> int:
        return compilation_count() - self.start_count

    def __enter__(self) -> "CompilationTracker":
        return self.start()

    def __exit__(self, *exc) -> None:
        pass


def jit_cache_size(fn) -> int:
    """Number of compiled executables cached by one jax.jit callable —
    the per-shape compile count of THAT function. Returns -1 when the
    jax version exposes no cache probe."""
    probe = getattr(fn, "_cache_size", None)
    if probe is None:
        return -1
    try:
        return int(probe())
    except Exception:
        return -1


# ---------------------------------------------------------------------------
# Recompile-churn guard
#
# jit_cache_size says HOW MANY shapes a step compiled for; it cannot say
# the fit loop keeps feeding new ones. This guard records the distinct
# shape signatures each logical step has seen and goes loud — one
# warning plus a labeled counter — when a step crosses the threshold:
# the canonical symptom is a data pipeline emitting ragged batches
# (every epoch tail a fresh compile) or unbucketed variable-length
# sequences. `churn_offenders()` names them.
# ---------------------------------------------------------------------------
ENV_CHURN_THRESHOLD = "DL4JTPU_RECOMPILE_CHURN_THRESHOLD"
DEFAULT_CHURN_THRESHOLD = 5

_churn_lock = threading.Lock()
_step_signatures: dict = {}   # label -> set of signatures
_churn_warned: set = set()    # labels already warned (one-shot)


def churn_threshold() -> int:
    import os
    try:
        return int(os.environ.get(ENV_CHURN_THRESHOLD,
                                  DEFAULT_CHURN_THRESHOLD))
    except ValueError:
        return DEFAULT_CHURN_THRESHOLD


def shape_signature(*args) -> tuple:
    """Cheap hashable signature of a call's data arguments: per-arg
    (shape, dtype) with None passing through. Metadata only — never
    forces a device sync."""
    sig = []
    for a in args:
        if a is None:
            sig.append(None)
        else:
            sig.append((tuple(getattr(a, "shape", ())),
                        str(getattr(a, "dtype", ""))))
    return tuple(sig)


def note_step_signature(label: str, sig: tuple) -> int:
    """Record one call signature for a logical step; returns the number
    of distinct signatures seen. Crossing the threshold fires ONE loud
    warning per label and bumps `recompile_churn_total{fn=label}` for
    every new signature past it."""
    with _churn_lock:
        seen = _step_signatures.setdefault(label, set())
        if sig in seen:
            return len(seen)
        seen.add(sig)
        n = len(seen)
        over = n > churn_threshold()
        warn = over and label not in _churn_warned
        if warn:
            _churn_warned.add(label)
    if over:
        from .metrics import registry
        registry().counter(
            "recompile_churn_total",
            "Distinct call signatures past the churn threshold — each "
            "one was a recompile of an already-hot step"
            ).labels(fn=label).inc()
    if warn:
        log.warning(
            "RECOMPILE CHURN: %s has now been called with %d distinct "
            "shape signatures (threshold %d) — every new signature "
            "recompiles. Bucket or pad your batches "
            "(pad_to_bucket=True, docs/perf_compile_cache.md)",
            label, n, churn_threshold())
    return n


def churn_offenders(top: int = 5):
    """Worst logical steps by distinct-signature count, for bench/debug
    output: [(label, n_signatures), ...] sorted descending."""
    with _churn_lock:
        items = [(lbl, len(sigs)) for lbl, sigs in _step_signatures.items()]
    items.sort(key=lambda kv: (-kv[1], kv[0]))
    return items[:max(0, int(top))]


def reset_churn() -> None:
    """Forget recorded signatures and re-arm the one-shot warnings
    (test isolation)."""
    with _churn_lock:
        _step_signatures.clear()
        _churn_warned.clear()
