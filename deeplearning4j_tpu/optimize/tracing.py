"""Nestable span tracing over the training loop, with a Chrome
trace-event exporter.

The fit loop (`Trainer.fit`, nn/stepping.py) emits the span taxonomy
`fit / epoch / step / {etl, dispatch, device}`
(docs/observability.md). Spans are
`time.perf_counter` intervals recorded into a bounded ring buffer —
O(1) memory however long training runs — and export as Chrome
trace-event-format JSON (`ph:"X"` complete events; load in
chrome://tracing or Perfetto), also served live at `GET /trace` on the
UI server.

Three design points keep steady-state overhead negligible:

* Disabled (the default), `span()` returns a shared no-op context
  manager: one branch per call site, nothing recorded.
* jax dispatch is async, so a `dispatch` span measures host-side
  enqueue time only. The sampled FENCE (`fence(step, value)`, every
  `fence_every`-th step) calls `jax.block_until_ready` and records the
  wait as a `device` span — the dispatch-side vs device-compute split.
  block_until_ready adds no computation and no compilation, so the
  1-compile-per-epoch invariant and numerics are untouched.
* `annotate=True` additionally enters `jax.profiler.TraceAnnotation`
  (and `StepTraceAnnotation` for spans carrying a `step_num` arg) so
  spans line up with XLA activity in a real profiler capture.

Every span has an `id` (process-wide counter, from 1) and a `parent`:
the innermost span open on the same thread when it was made (0: none),
kept on a thread-local stack. Retroactive spans (`add_span`,
`add_spans`) take the stack's top as parent unless one is passed. Both
go out under `args` as `span_id` / `parent_id`, so a layer's self time
is its span less its children, not a guess from containment. `enable()`
reads `perf_counter` and `time_ns` back to back; the export carries the
pair as `"clock"`, which lays the ring on wall time.
"""
from __future__ import annotations

import itertools
import json
import os
import threading
import time
from collections import deque
from typing import Any, Dict, List, Optional

__all__ = ["enable", "disable", "is_enabled", "clear", "span", "begin",
           "annotate", "add_span", "add_spans", "fence",
           "export_trace_events", "dump", "DEFAULT_FENCE_EVERY"]

# Default fence sampling once tracing is enabled: 1 fenced step in 16
# bounds the pipelining loss to ~1/16 of one step's dispatch-ahead.
# With tracing disabled there is NO fencing at all.
DEFAULT_FENCE_EVERY = 16

_lock = threading.Lock()
_enabled = False
_annotate = False
_fence_every = 0
_ring: deque = deque(maxlen=4096)
_ids = itertools.count(1)       # next() is atomic under the GIL
_tls = threading.local()        # .open: the thread's open Spans, outer first
_clock = (0.0, 0)               # (perf_counter s, time_ns) read at enable()


def _open() -> List["Span"]:
    try:
        return _tls.open
    except AttributeError:
        _tls.open = []
        return _tls.open


def _top_id() -> int:
    stack = _open()
    return stack[-1].id if stack else 0


class _NullSpan:
    """Reusable no-op: the disabled-path return of span()."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def end(self):
        pass

    def cancel(self):
        pass


_NULL = _NullSpan()


class Span:
    """One live interval; use as a context manager or end it with
    end(). cancel() discards it (a `step` span opened before the
    iterator reported exhaustion)."""

    __slots__ = ("name", "args", "cat", "id", "parent", "_t0", "_ann",
                 "_done")

    def __init__(self, name: str, args: Dict[str, Any],
                 cat: Optional[str] = None):
        self.name = name
        self.args = args
        self.cat = cat
        self._ann = None
        self._done = False
        stack = _open()
        self.parent = stack[-1].id if stack else 0
        self.id = next(_ids)
        stack.append(self)
        if _annotate:
            self._ann = _make_annotation(name, args)
            if self._ann is not None:
                self._ann.__enter__()
        self._t0 = time.perf_counter()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.end()
        return False

    def _close(self) -> bool:
        """Leave the thread's stack, with whatever was opened inside and
        never ended (an exception past an explicit open). False if it
        was closed before."""
        if self._done:
            return False
        self._done = True
        stack = _open()
        try:
            del stack[stack.index(self):]
        except ValueError:      # ended on another thread than it began on
            pass
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
        return True

    def end(self):
        dur = time.perf_counter() - self._t0
        if self._close():
            _record(self.name, self._t0, dur, self.args, self.cat,
                    self.id, self.parent)

    def cancel(self):
        self._close()


def _make_annotation(name: str, args: Dict[str, Any]):
    try:
        from jax import profiler
        if "step_num" in args and hasattr(profiler, "StepTraceAnnotation"):
            return profiler.StepTraceAnnotation(
                name, step_num=int(args["step_num"]))
        return profiler.TraceAnnotation(name)
    except Exception:
        return None


def _record(name: str, t0: float, dur: float,
            args: Optional[Dict[str, Any]], cat: Optional[str],
            sid: int, parent: int) -> None:
    ev = {"name": name, "ts": t0 * 1e6, "dur": dur * 1e6,
          "tid": threading.get_ident(), "id": sid, "parent": parent}
    if cat:
        ev["cat"] = cat
    if args:
        ev["args"] = args
    _ring.append(ev)  # deque.append is atomic; maxlen bounds memory


# ---------------------------------------------------------------------------
# Public API
# ---------------------------------------------------------------------------
def enable(ring_size: int = 4096, annotate: bool = False,
           fence_every: int = DEFAULT_FENCE_EVERY) -> None:
    """Turn tracing on. `fence_every=0` disables the sampled device
    fence (dispatch-side timings only); `annotate=True` mirrors spans
    into jax.profiler annotations."""
    global _enabled, _annotate, _fence_every, _ring, _clock
    with _lock:
        _ring = deque(_ring, maxlen=int(ring_size))
        _annotate = bool(annotate)
        _fence_every = max(0, int(fence_every))
        _clock = (time.perf_counter(), time.time_ns())
        _enabled = True


def disable() -> None:
    global _enabled, _annotate, _fence_every
    with _lock:
        _enabled = False
        _annotate = False
        _fence_every = 0


def is_enabled() -> bool:
    return _enabled


def clear() -> None:
    _ring.clear()


def span(name: str, cat: Optional[str] = None, **args):
    """Context manager for one interval, or ended explicitly with
    `.end()` where it cannot nest lexically (the step span opened before
    the iterator is polled); no-op (shared singleton) when tracing is
    disabled. `cat` tags the Chrome-export category ("train" when
    omitted)."""
    if not _enabled:
        return _NULL
    return Span(name, args, cat)


begin = span


def annotate(**args) -> None:
    """Add `args` to the innermost span open on this thread: what is
    known only once the work is under way (a step's buckets, the bytes a
    gather made). Nothing when tracing is off or no span is open."""
    if not _enabled:
        return
    stack = _open()
    if stack:
        stack[-1].args.update(args)


def add_span(name: str, start: float, dur_s: float,
             cat: Optional[str] = None, parent: Optional[int] = None,
             **args) -> int:
    """Record a retroactive span from an already-measured interval
    (`start` in time.perf_counter seconds): the fit loop times ETL with
    perf_counter anyway, so the span costs nothing extra. `cat` tags the
    event category in the Chrome export ("train" when omitted) — the
    serving flight recorder uses "serve" so a serving incident and a
    training profile separate cleanly in one viewer. `parent` is a span
    id (default: the innermost span open on this thread). Returns the
    new span's id, for retroactive children of its own; 0 when tracing
    is off."""
    if not _enabled:
        return 0
    sid = next(_ids)
    _record(name, start, dur_s, args or None, cat, sid,
            _top_id() if parent is None else parent)
    return sid


def add_spans(spans, cat: Optional[str] = None,
              parent: Optional[int] = None, **args) -> None:
    """Bulk `add_span`: `spans` is [(name, start_s, dur_s)]. One enabled
    check and ONE shared args dict for the whole group — the flight
    recorder emits seven phase spans per served request, and per-span
    kwargs repacking is measurable at serving rates. The shared dict is
    stored by reference; callers must not mutate it afterwards."""
    if not _enabled:
        return
    shared = args or None
    if parent is None:
        parent = _top_id()
    for name, start, dur_s in spans:
        _record(name, start, dur_s, shared, cat, next(_ids), parent)


def fence(step: int, value) -> Optional[float]:
    """Sampled dispatch-queue drain: every `fence_every`-th step, block
    until `value` (typically the committed loss) is device-complete and
    record the wait as a `device` span. Returns the wait in ms when it
    ran, else None. No-op when tracing is off or fence_every == 0."""
    if not _enabled or _fence_every <= 0 or value is None:
        return None
    if step % _fence_every != 0:
        return None
    t0 = time.perf_counter()
    try:
        import jax
        jax.block_until_ready(value)
    except Exception:
        return None
    dur = time.perf_counter() - t0
    add_span("device", t0, dur, step=int(step))
    return dur * 1000.0


def export_trace_events() -> Dict[str, Any]:
    """Chrome trace-event-format dict: {"traceEvents": [...],
    "displayTimeUnit": "ms", "clock": {...}}. Events are ph:"X"
    completes; each carries `span_id` and `parent_id` (0: none) under
    `args`. `ts` is perf_counter microseconds, and `clock` one reading of
    perf_counter and of wall time taken together at `enable()`: an
    event's wall time is `unix_ns + ts * 1e3 - perf_counter_s * 1e9`."""
    pid = os.getpid()
    events = []
    for ev in list(_ring):
        args = dict(ev.get("args") or (), span_id=ev["id"],
                    parent_id=ev["parent"])
        events.append({"name": ev["name"], "ph": "X", "pid": pid,
                       "tid": ev["tid"], "ts": round(ev["ts"], 3),
                       "dur": round(ev["dur"], 3),
                       "cat": ev.get("cat", "train"), "args": args})
    events.sort(key=lambda e: e["ts"])
    return {"traceEvents": events, "displayTimeUnit": "ms",
            "clock": {"perf_counter_s": _clock[0], "unix_ns": _clock[1]}}


def dump(path: str) -> str:
    """Write the current ring as trace-event JSON; returns the path."""
    with open(path, "w") as f:
        json.dump(export_trace_events(), f)
    return path
