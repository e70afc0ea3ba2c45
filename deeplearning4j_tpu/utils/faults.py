"""Deterministic fault-injection registry (reference: the chaos hooks DL4J's
parameter-server tests relied on, rebuilt as a first-class module).

Production code calls :func:`fire` / :func:`check` at *named injection
points*; when nothing is armed both are near-free no-ops.  Tests (or an
operator, via environment variables) arm a point with a plan string:

    ``"fail:2"``      raise :class:`FaultInjected` on the 2nd call
    ``"fail:1,3"``    ... on the 1st and 3rd calls
    ``"fail:2-4"``    ... on calls 2 through 4
    ``"fail:2/5"``    ... on calls 2, 7, 12, ... (every 5th from the 2nd:
                      a deterministic 20% failure rate for chaos storms)
    ``"fail:*"``      ... on every call
    ``"kill:3"``      SIGKILL *this process* on the 3rd call (crash tests)
    ``"delay:2@50"``  sleep 50 ms on the 2nd call, then continue (latency
                      injection — same call selectors as fail:/kill:,
                      e.g. ``"delay:*@10"``, ``"delay:1/4@25"``)

Call numbers are 1-based and counted per point, so a plan is fully
deterministic: the same program order always hits the same faults.

Points used by the training stack (arbitrary names are allowed):

    checkpoint.write   inside the atomic checkpoint writer, before rename
    ps.push / ps.pull  each parameter-server transport attempt (per retry)
    etl.next           each base-iterator poll in the async producer
    step.nonfinite     per-step divergence flag (checked, never raised)

Points used by the cluster health plane (docs/robustness.md):

    heartbeat.send     each watchdog beat publish — ``fail:`` suppresses
                       the beat (a peer goes quiet), ``delay:SEL@MS``
                       injects side-channel latency
    step.stall         checked in ClusterHealthMonitor.notify_step; when
                       armed the step report is swallowed, so the process
                       keeps beating but looks frozen (the deterministic
                       stand-in for a wedged main thread)

Points used by the serving stack (docs/serving.md):

    serve.forward      each coalesced forward in ParallelInference (and
                       each SEQUENTIAL-mode forward)
    serve.decode       the checkpoint decode/stage step of a hot-swap,
                       before any live state is mutated
    serve.pack         packed-admission assembly/unpack of a segment-
                       masked row (fires twice per packed forward:
                       before the pack and before the unpack)
    serve.schedule     entry to the device-scheduler slot, before the
                       waiter is enqueued — armed errors surface as
                       typed request failures without ever parking a
                       thread on the scheduler condition
    swap.warm          each per-bucket warm forward inside the
                       pause-assign-warm swap window (fires the rollback
                       path when armed)
    serve.decode_step  each iteration-level decode step in DecodeEngine,
                       before the step forward dispatches — an armed
                       failure fails the riding requests typed
                       (DecodeStepError), frees their KV blocks, and
                       leaves decode batchmates generating

Points used by the replica federation plane (docs/serving.md
§"Replica federation"):

    route.dispatch     each front-end dispatch leg (the first attempt
                       AND the failover retry each count one call) —
                       ``fail:`` drops the leg before the HTTP post,
                       exercising the typed failover path without
                       killing a replica; ``delay:SEL@MS`` injects
                       route latency
    replica.beat       each replica-side beat publish — ``fail:``
                       suppresses the beat, so the replica goes dark
                       and is evicted past timeout_s while its gateway
                       keeps serving (the deterministic stand-in for a
                       beat-channel partition); env-armable in replica
                       subprocesses via DL4JTPU_FAULT_REPLICA_BEAT

Environment arming: ``DL4JTPU_FAULT_<POINT>`` with dots mapped to
underscores, e.g. ``DL4JTPU_FAULT_CHECKPOINT_WRITE="kill:3"`` — this is
how subprocess crash tests arm the child without touching its code.

Stdlib-only on purpose: everything in the package may import this.
"""
from __future__ import annotations

import os
import signal
import threading
import time
from contextlib import contextmanager
from typing import Dict, List, Optional, Set, Tuple


class FaultInjected(RuntimeError):
    """Raised at an armed injection point.

    Marked ``transient`` so retry helpers treat it like a flaky-transport
    error rather than a programming bug.
    """

    transient = True


class _Plan:
    __slots__ = ("action", "calls", "periodic", "always", "delay_ms",
                 "count", "fired")

    def __init__(self, action: str, calls: Set[int],
                 periodic: List[Tuple[int, int]], always: bool,
                 delay_ms: float = 0.0):
        self.action = action      # "fail" | "kill" | "delay"
        self.calls = calls        # 1-based call numbers covered
        self.periodic = periodic  # (start, every) pairs: start, start+every, ...
        self.always = always
        self.delay_ms = delay_ms  # sleep duration for "delay" plans
        self.count = 0            # calls seen at this point
        self.fired = 0            # calls that actually faulted

    def covers(self, n: int) -> bool:
        return (self.always or n in self.calls or
                any(n >= s and (n - s) % p == 0 for s, p in self.periodic))


def _parse(spec: str) -> _Plan:
    action, _, arg = spec.strip().partition(":")
    if action not in ("fail", "kill", "delay"):
        raise ValueError(f"unknown fault action {action!r} in spec {spec!r} "
                         "(expected 'fail:...', 'kill:...' or 'delay:...')")
    arg = arg.strip()
    delay_ms = 0.0
    if action == "delay":
        arg, at, ms = arg.partition("@")
        arg = arg.strip()
        try:
            delay_ms = float(ms)
        except ValueError:
            at = ""
        if not at or delay_ms < 0:
            raise ValueError(
                f"delay spec {spec!r} needs 'delay:SELECTOR@MS' with a "
                "non-negative millisecond count")
    if arg in ("", "*"):
        return _Plan(action, set(), [], always=True, delay_ms=delay_ms)
    calls: Set[int] = set()
    periodic: List[Tuple[int, int]] = []
    for part in arg.split(","):
        part = part.strip()
        lo, slash, every = part.partition("/")
        try:
            if slash:
                start, period = int(lo), int(every)
                if start < 1 or period < 1:
                    raise ValueError
                periodic.append((start, period))
                continue
            lo, dash, hi = part.partition("-")
            if dash:
                calls.update(range(int(lo), int(hi) + 1))
            else:
                calls.add(int(lo))
        except ValueError:
            raise ValueError(f"bad call selector {part!r} in fault spec {spec!r}")
    if not (calls or periodic) or (calls and min(calls) < 1):
        raise ValueError(f"fault spec {spec!r} must select 1-based call numbers")
    return _Plan(action, calls, periodic, always=False, delay_ms=delay_ms)


_lock = threading.Lock()
_plans: Dict[str, _Plan] = {}
_env_checked: Set[str] = set()          # points whose env var was consulted


def _env_var(point: str) -> str:
    return "DL4JTPU_FAULT_" + point.upper().replace(".", "_").replace("-", "_")


def inject(point: str, spec: str) -> None:
    """Arm `point` with a plan (replacing any existing plan and counters)."""
    plan = _parse(spec)
    with _lock:
        _plans[point] = plan
        _env_checked.add(point)         # explicit plan wins over env


def clear(point: Optional[str] = None) -> None:
    """Disarm one point (or all); cleared points do not re-arm from env."""
    with _lock:
        if point is None:
            _env_checked.update(_plans)
            _plans.clear()
        else:
            _plans.pop(point, None)
            _env_checked.add(point)


def reset() -> None:
    """Full reset, including env re-arming — test fixtures only."""
    with _lock:
        _plans.clear()
        _env_checked.clear()


def _advance(point: str) -> Optional[Tuple[str, float]]:
    with _lock:
        plan = _plans.get(point)
        if plan is None:
            if point in _env_checked:
                return None
            _env_checked.add(point)
            spec = os.environ.get(_env_var(point))
            if not spec:
                return None
            plan = _plans[point] = _parse(spec)
        plan.count += 1
        if plan.covers(plan.count):
            plan.fired += 1
            return plan.action, plan.delay_ms
        return None


def fire(point: str) -> None:
    """Injection hook for raising points.

    No-op unless an armed plan covers this call; then raises
    :class:`FaultInjected` (``fail``), SIGKILLs the process (``kill`` —
    deliberately unmaskable, for torn-write crash tests), or sleeps and
    returns (``delay`` — latency injection, never an error).
    """
    hit = _advance(point)
    if hit is None:
        return
    action, delay_ms = hit
    if action == "kill":
        os.kill(os.getpid(), signal.SIGKILL)
    if action == "delay":
        time.sleep(delay_ms / 1000.0)
        return
    raise FaultInjected(f"injected fault at {point!r} (call #{call_count(point)})")


def check(point: str) -> bool:
    """Non-raising variant for flag-style points (e.g. ``step.nonfinite``):
    returns True when the plan covers this call. A ``delay`` plan sleeps
    but returns False — it slows the caller without flipping the flag."""
    hit = _advance(point)
    if hit is None:
        return False
    action, delay_ms = hit
    if action == "delay":
        time.sleep(delay_ms / 1000.0)
        return False
    return True


def call_count(point: str) -> int:
    with _lock:
        plan = _plans.get(point)
        return plan.count if plan else 0


def fired_count(point: str) -> int:
    with _lock:
        plan = _plans.get(point)
        return plan.fired if plan else 0


@contextmanager
def injected(point: str, spec: str):
    """Scoped arming for tests: arms on entry, disarms on exit."""
    inject(point, spec)
    try:
        yield
    finally:
        clear(point)
