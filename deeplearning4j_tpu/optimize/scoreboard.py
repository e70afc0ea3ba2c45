"""Bench observability plane: the scoreboard can never go dark again.

Round 5 shipped the motivating corpse: ``BENCH_r05.json`` is
``"parsed": null`` ("bench subprocess exceeded 420s with no completed
repeat") — the perf program's own measurement plane hung and the round
lost its scoreboard line. The discipline MLPerf-style harnesses apply to
workload results (every run produces a schema-valid, provenance-stamped
artifact or a *typed* failure) applies here:

* **Child liveness** — ``bench.py --once`` children publish heartbeat
  lines ``{workload, repeat, step, phase, ts}`` on a side channel (a
  file named in ``DL4JTPU_BENCH_HB_FILE``): a background beat thread
  every ~2 s proves the interpreter still schedules threads (XLA
  compiles release the GIL, so a *long compile keeps beating*), and the
  measurement loops beat with their (repeat, step) position. The parent
  :class:`ChildWatchdog` distinguishes **alive-but-slow** (fresh beats
  past the deadline → extend within the hard cap) from **wedged** (beats
  stopped → kill + typed ``"failure": "wedged"`` row) from **timeout**
  (deadline passed with no evidence of life). Ages are computed entirely
  on the PARENT's monotonic clock, same policy as the cluster health
  plane — child clock skew cannot false-trip the watchdog.

* **Device probe** — :func:`probe_device` runs a tiny jitted op in a
  throwaway subprocess under its own timeout before any child is
  spawned, so a device that cannot be reached reports
  ``"device": "dead"`` in seconds instead of hanging the first child
  for the whole budget. The probe exits before the first child starts:
  a chip belongs to one process at a time.

* **Run ledger** — every bench invocation appends one schema-validated
  row (git sha, host, backend, status, timeout flag,
  per-repeat raw values) to the append-only ``BENCH_ledger.jsonl``;
  :func:`check_rows` is the regression sentinel (`bench.py check`) and
  :func:`render_report` the trajectory view (`bench.py report`).

Fault points (``utils/faults.py``): ``bench.child`` fires on every child
heartbeat when the side channel is armed — ``delay:`` wedges the child
mid-measurement; ``bench.probe`` fires inside the probe subprocess
before it touches the device — ``delay:`` wedges the probe into a
``"device": "dead"`` verdict.

Metric families (pre-registered at 0 by :func:`register_metrics` so a
snapshot distinguishes "never fired" from "absent"):
``bench_rows_total{status}``, ``bench_regressions_total``,
``bench_baseline_corrupt_total``.

Module import stays jax-free on purpose: the parent process and the
fake-clock tests exercise the watchdog/ledger machinery without paying
a backend initialization.
"""
from __future__ import annotations

import json
import os
import socket
import subprocess
import sys
import tempfile
import threading
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

from ..utils import faults
from .metrics import registry

__all__ = [
    "ALIVE", "WEDGED", "TIMEOUT", "STATUSES", "SCHEMA_VERSION",
    "ChildWatchdog", "ChildResult", "run_child", "probe_device",
    "start_child_heartbeat", "child_heartbeat", "read_heartbeats",
    "make_row", "validate_row", "append_row", "read_ledger",
    "ledger_path", "baseline_path", "baseline_key", "load_baseline",
    "save_baseline", "check_rows", "render_report", "register_metrics",
    "host_sentinel_ms",
]

SCHEMA_VERSION = 1

# Watchdog verdicts (also the typed-failure vocabulary in artifacts).
ALIVE = "alive"
WEDGED = "wedged"
TIMEOUT = "timeout"

# Terminal row statuses the ledger schema accepts.
STATUSES = ("ok", "wedged", "timeout", "failed", "dead_device")

_HB_ENV = "DL4JTPU_BENCH_HB_FILE"


def _repo_root() -> str:
    return os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))


# ---------------------------------------------------------------------------
# Metrics
# ---------------------------------------------------------------------------
def register_metrics() -> None:
    """Pre-register the bench plane's families (every status label at 0)
    so BENCH snapshots always carry them — including their absence of
    activity."""
    reg = registry()
    rows = reg.counter(
        "bench_rows_total",
        "Ledger rows appended by the bench scoreboard plane, by "
        "terminal status")
    for status in STATUSES:
        rows.touch(status=status)
    reg.counter("bench_regressions_total",
                "Regressions flagged by the bench.py check sentinel")
    reg.counter("bench_baseline_corrupt_total",
                "Corrupt/unreadable BENCH_baseline.json files tolerated "
                "(fell back to empty instead of crashing)")


# ---------------------------------------------------------------------------
# Child side: heartbeat emission
# ---------------------------------------------------------------------------
_hb_lock = threading.Lock()
_hb_pos: Dict[str, Any] = {"workload": "", "repeat": -1, "step": -1,
                           "phase": ""}
_hb_thread: Optional[threading.Thread] = None


def start_child_heartbeat(workload: str, interval_s: float = 2.0) -> bool:
    """Arm this process as a bench child: record the workload, start the
    background beat thread, and publish an immediate ``start`` beat.
    No-op (returns False) unless the parent armed the side channel via
    ``DL4JTPU_BENCH_HB_FILE``."""
    global _hb_thread
    if not os.environ.get(_HB_ENV):
        return False
    with _hb_lock:
        _hb_pos["workload"] = workload
    if _hb_thread is None or not _hb_thread.is_alive():
        _hb_thread = threading.Thread(
            target=_beat_loop, args=(interval_s,), daemon=True,
            name="bench-heartbeat")
        _hb_thread.start()
    child_heartbeat(phase="start")
    return True


def _beat_loop(interval_s: float) -> None:
    # Liveness semantics: XLA compiles release the GIL, so this thread
    # keeps beating through a minutes-long compile (alive-but-slow); a
    # process wedged hard enough to stop scheduling threads stops
    # beating and the parent's stall timeout converts that to a typed
    # failure.
    while True:
        time.sleep(interval_s)
        try:
            child_heartbeat()
        except faults.FaultInjected:
            return  # a fail: plan on bench.child silences the channel


def child_heartbeat(repeat: Optional[int] = None,
                    step: Optional[int] = None,
                    phase: Optional[str] = None) -> None:
    """Publish one heartbeat line on the side channel (no-op when the
    channel is unarmed). The ``bench.child`` fault point fires here —
    a ``delay:`` plan wedges the child between beats, which is exactly
    the failure mode the watchdog exists to catch."""
    path = os.environ.get(_HB_ENV)
    if not path:
        return
    faults.fire("bench.child")
    with _hb_lock:
        if repeat is not None:
            _hb_pos["repeat"] = int(repeat)
        if step is not None:
            _hb_pos["step"] = int(step)
        if phase is not None:
            _hb_pos["phase"] = phase
        beat = dict(_hb_pos)
    beat["ts"] = time.time()
    try:
        with open(path, "a") as f:
            f.write(json.dumps(beat) + "\n")
            f.flush()
            os.fsync(f.fileno())
    except OSError:
        pass  # a torn side channel must never fail the measurement


def read_heartbeats(path: str, offset: int = 0
                    ) -> Tuple[List[Dict[str, Any]], int]:
    """Incremental heartbeat reader: parse complete lines past `offset`
    (bytes), skip a torn tail (it is re-read on the next poll), and
    return (beats, new_offset)."""
    try:
        with open(path, "rb") as f:
            f.seek(offset)
            data = f.read()
    except OSError:
        return [], offset
    beats: List[Dict[str, Any]] = []
    consumed = 0
    for line in data.splitlines(keepends=True):
        if not line.endswith(b"\n"):
            break
        consumed += len(line)
        try:
            beat = json.loads(line.decode("utf-8", "replace"))
        except ValueError:
            continue
        if isinstance(beat, dict):
            beats.append(beat)
    return beats, offset + consumed


# ---------------------------------------------------------------------------
# Parent side: watchdog + child runner
# ---------------------------------------------------------------------------
class ChildWatchdog:
    """Pure liveness state machine over one bench child (injectable
    clock — the fake-clock tests drive it without subprocesses).

    Verdicts from :meth:`decide`:

    * ``alive``   — within deadline, or past it with fresh beats and
      inside the hard cap (`extended` latches True: alive-but-slow).
    * ``wedged``  — the child HAS beaten before, then went silent for
      longer than ``stall_timeout_s``: kill + typed failure.
    * ``timeout`` — deadline passed with no beats ever (nothing to
      distinguish slow from dead), or the hard cap is exhausted.

    All ages use the parent's clock; beat payload timestamps are carried
    for diagnostics only (cross-process clock skew cannot false-trip).
    """

    def __init__(self, deadline_s: float, stall_timeout_s: float,
                 hard_cap_s: Optional[float] = None, clock=time.monotonic):
        self._clock = clock
        self._start = clock()
        self._last_activity = self._start
        self.deadline_s = float(deadline_s)
        self.stall_timeout_s = float(stall_timeout_s)
        self.hard_cap_s = max(float(hard_cap_s or 0.0), self.deadline_s)
        self.heartbeats = 0
        self.last_beat: Optional[Dict[str, Any]] = None
        self.extended = False

    def observe(self, beat: Optional[Dict[str, Any]] = None) -> None:
        # single writer: only the parent's beat-reader thread calls this
        self.heartbeats += 1  # jaxlint: atomic
        self.last_beat = beat
        self._last_activity = self._clock()

    def elapsed(self) -> float:
        return self._clock() - self._start

    def decide(self) -> str:
        now = self._clock()
        elapsed = now - self._start
        stalled = now - self._last_activity > self.stall_timeout_s
        if self.heartbeats and stalled:
            return WEDGED
        if elapsed > self.deadline_s:
            if self.heartbeats and not stalled and elapsed <= self.hard_cap_s:
                self.extended = True
                return ALIVE
            return TIMEOUT
        return ALIVE


class ChildResult:
    """Outcome of one watched child: `status` is ``ok`` / ``failed``
    (nonzero exit) / ``wedged`` / ``timeout``."""

    __slots__ = ("status", "returncode", "stdout", "stderr", "beats",
                 "last_beat", "extended", "duration_s")

    def __init__(self, status, returncode, stdout, stderr, beats,
                 last_beat, extended, duration_s):
        self.status = status
        self.returncode = returncode
        self.stdout = stdout
        self.stderr = stderr
        self.beats = beats
        self.last_beat = last_beat
        self.extended = extended
        self.duration_s = duration_s


def _kill(proc: "subprocess.Popen") -> None:
    try:
        proc.terminate()
        try:
            proc.wait(timeout=5)
            return
        except subprocess.TimeoutExpired:
            pass
        proc.kill()
        proc.wait(timeout=5)
    except Exception:
        pass  # already gone / unkillable: the parent moves on regardless


def run_child(cmd: Sequence[str], *, deadline_s: float,
              stall_timeout_s: float, hard_cap_s: Optional[float] = None,
              env: Optional[Dict[str, str]] = None,
              cwd: Optional[str] = None, clock=time.monotonic,
              poll_s: float = 0.25) -> ChildResult:
    """Spawn one bench child with the heartbeat side channel armed and
    watch it to a terminal verdict. stdout/stderr go to temp files (a
    pipe could deadlock on a chatty child with no reader)."""
    fd, hb_path = tempfile.mkstemp(prefix="dl4jtpu_bench_hb_",
                                   suffix=".jsonl")
    os.close(fd)
    out_path, err_path = hb_path + ".out", hb_path + ".err"
    child_env = dict(os.environ if env is None else env)
    child_env[_HB_ENV] = hb_path
    wd = ChildWatchdog(deadline_s, stall_timeout_s, hard_cap_s,
                       clock=clock)
    verdict = "ok"
    try:
        with open(out_path, "wb") as out_f, open(err_path, "wb") as err_f:
            proc = subprocess.Popen(list(cmd), stdout=out_f,
                                    stderr=err_f, env=child_env, cwd=cwd)
            offset = 0
            while True:
                rc = proc.poll()
                beats, offset = read_heartbeats(hb_path, offset)
                for b in beats:
                    wd.observe(b)
                if rc is not None:
                    break
                v = wd.decide()
                if v != ALIVE:
                    verdict = v
                    _kill(proc)
                    rc = proc.returncode
                    break
                time.sleep(poll_s)
        with open(out_path, "r", errors="replace") as f:
            stdout = f.read()
        with open(err_path, "r", errors="replace") as f:
            stderr = f.read()
        if verdict == "ok" and rc != 0:
            verdict = "failed"
        return ChildResult(verdict, rc, stdout, stderr, wd.heartbeats,
                           wd.last_beat, wd.extended, wd.elapsed())
    finally:
        for p in (hb_path, out_path, err_path):
            try:
                os.unlink(p)
            except OSError:
                pass


# ---------------------------------------------------------------------------
# Device liveness probe
# ---------------------------------------------------------------------------
# The probe loads faults.py STANDALONE (importlib from path) so the
# bench.probe fault point fires before the heavyweight package / jax
# import — a delay:-wedged probe dies on its subprocess timeout in
# seconds, not after a backend init.
_PROBE_CODE = """\
import importlib.util, sys, time
spec = importlib.util.spec_from_file_location("bench_probe_faults", {fp!r})
mod = importlib.util.module_from_spec(spec)
spec.loader.exec_module(mod)
mod.fire("bench.probe")
t0 = time.perf_counter()
import jax
v = float(jax.jit(lambda x: x + 1.0)(1.0))
assert v == 2.0, v
print("PROBE_OK %.1f" % ((time.perf_counter() - t0) * 1000.0))
"""


def probe_device(timeout_s: float = 120.0,
                 python: Optional[str] = None) -> Dict[str, Any]:
    """Up-front device liveness check: a tiny jitted op, its result
    fetched to the host, in a throwaway subprocess under its own
    timeout. Returns ``{"device": "ok", "probe_ms": ...}`` or
    ``{"device": "dead", "error": ...}`` — it never hangs the caller."""
    faults_path = os.path.join(_repo_root(), "deeplearning4j_tpu",
                               "utils", "faults.py")
    code = _PROBE_CODE.format(fp=faults_path)
    try:
        out = subprocess.run([python or sys.executable, "-c", code],
                             capture_output=True, text=True,
                             timeout=timeout_s, env=dict(os.environ))
    except subprocess.TimeoutExpired:
        return {"device": "dead", "timeout_s": timeout_s,
                "error": f"probe exceeded {timeout_s:.0f}s"}
    except OSError as e:
        return {"device": "dead", "error": f"probe spawn failed: {e}"}
    last = out.stdout.strip().splitlines()[-1] if out.stdout.strip() else ""
    if out.returncode == 0 and last.startswith("PROBE_OK"):
        try:
            ms = float(last.split()[1])
        except (IndexError, ValueError):
            ms = -1.0
        return {"device": "ok", "probe_ms": ms}
    return {"device": "dead", "rc": out.returncode,
            "error": (out.stderr or out.stdout)[-500:]}


def host_sentinel_ms(n: int = 3) -> Tuple[float, float]:
    """Fixed busy-loop calibration: the same ~50 ms of pure-Python work
    every time, timed `n` times → (median, min) in ms. A median far
    above min means the host is contended and wall-clock numbers carry
    that noise."""
    times = []
    for _ in range(n):
        t0 = time.perf_counter()
        s = 0
        for i in range(1_200_000):
            s += i * i
        times.append(time.perf_counter() - t0)
    times.sort()
    return times[len(times) // 2] * 1000, times[0] * 1000


# ---------------------------------------------------------------------------
# Run ledger (append-only BENCH_ledger.jsonl)
# ---------------------------------------------------------------------------
_REQUIRED_FIELDS: Dict[str, Any] = {
    "schema": int,
    "ts": (int, float),
    "git_sha": str,
    "host": str,
    "backend": str,
    "workload": str,
    "status": str,
    "timeout": bool,
    "repeats": list,
}
_OPTIONAL_FIELDS: Dict[str, Any] = {
    "metric": str,
    "value": (int, float),
    "unit": str,
    "vs_baseline": (int, float),
    "failure": str,
    "probe": dict,
    "spread": dict,
    "extras": dict,
}


def ledger_path(repo_dir: Optional[str] = None) -> str:
    return (os.environ.get("DL4JTPU_BENCH_LEDGER")
            or os.path.join(repo_dir or _repo_root(),
                            "BENCH_ledger.jsonl"))


def baseline_path(repo_dir: Optional[str] = None) -> str:
    return (os.environ.get("DL4JTPU_BENCH_BASELINE")
            or os.path.join(repo_dir or _repo_root(),
                            "BENCH_baseline.json"))


def git_sha(repo_dir: Optional[str] = None) -> str:
    try:
        out = subprocess.run(
            ["git", "rev-parse", "--short", "HEAD"],
            capture_output=True, text=True, timeout=10,
            cwd=repo_dir or _repo_root())
        sha = out.stdout.strip()
        return sha if out.returncode == 0 and sha else "unknown"
    except Exception:
        return "unknown"


def make_row(workload: str, status: str, metric: Optional[str] = None,
             value: Optional[float] = None, unit: Optional[str] = None,
             *, timeout: bool = False,
             repeats: Sequence[float] = (), failure: Optional[str] = None,
             probe: Optional[Dict[str, Any]] = None,
             spread: Optional[Dict[str, Any]] = None,
             extras: Optional[Dict[str, Any]] = None,
             vs_baseline: Optional[float] = None,
             backend: Optional[str] = None,
             ts: Optional[float] = None) -> Dict[str, Any]:
    """Build a provenance-stamped ledger row (schema version, git sha,
    host, backend) from one bench outcome. `backend` is what the child
    that measured reported (`jax.default_backend()`); a row with no
    measuring child (dead device, wedged first child) says "none" —
    never a guess from the environment."""
    row: Dict[str, Any] = {
        "schema": SCHEMA_VERSION,
        "ts": float(ts if ts is not None else time.time()),
        "git_sha": git_sha(),
        "host": socket.gethostname(),
        "backend": backend or "none",
        "workload": workload,
        "status": status,
        "timeout": bool(timeout),
        "repeats": [float(v) for v in repeats],
    }
    for key, val in (("metric", metric), ("value", value), ("unit", unit),
                     ("vs_baseline", vs_baseline), ("failure", failure),
                     ("probe", probe), ("spread", spread),
                     ("extras", extras)):
        if val is not None:
            row[key] = val
    return row


def validate_row(row: Any) -> List[str]:
    """Schema check; returns a list of problems (empty = valid). Strict
    on purpose: unknown keys are rejected so validation means
    something."""
    if not isinstance(row, dict):
        return ["row is not an object"]
    problems = []
    for field, types in _REQUIRED_FIELDS.items():
        if field not in row:
            problems.append(f"missing required field {field!r}")
        elif not isinstance(row[field], types) or isinstance(
                row[field], bool) != (types is bool):
            problems.append(
                f"field {field!r} has type {type(row[field]).__name__}")
    for field, val in row.items():
        if field in _REQUIRED_FIELDS:
            continue
        types = _OPTIONAL_FIELDS.get(field)
        if types is None:
            problems.append(f"unknown field {field!r}")
        elif not isinstance(val, types) or (
                isinstance(val, bool) and types != bool):
            problems.append(
                f"field {field!r} has type {type(val).__name__}")
    if row.get("schema") not in (None, SCHEMA_VERSION):
        problems.append(f"unsupported schema {row.get('schema')!r}")
    status = row.get("status")
    if isinstance(status, str) and status not in STATUSES:
        problems.append(f"unknown status {status!r}")
    if status == "ok":
        for field in ("metric", "value", "unit"):
            if row.get(field) is None:
                problems.append(f"ok row is missing {field!r}")
    if isinstance(row.get("repeats"), list) and any(
            not isinstance(v, (int, float)) or isinstance(v, bool)
            for v in row["repeats"]):
        problems.append("repeats entries must be numbers")
    problems.extend(_validate_row_kind(row))
    return problems


# Per-workload extras contracts: a healthy row of these kinds without
# its comparison/accuracy extras is a schema violation, not a style
# choice — the quantized-serving A/B is only trustworthy if every row
# records the drift the precision introduced alongside the speedup
# (docs/serving.md §quantized: speed without an accuracy receipt is
# how silent quality regressions ship).
_ROW_KIND_EXTRAS: Dict[str, Tuple[str, ...]] = {
    "serving_quant": ("quant_speedup_int8", "quant_speedup_bf16",
                      "max_drift_int8", "max_drift_bf16"),
    "quant_matmul_ab": ("winner", "dispatch_verdict",
                        "int8_arms_bit_exact"),
    # The self-tuning A/B (docs/observability.md §"The serving control
    # loop"): a speedup without both arms' p99, the verdict, and the
    # tuner's own decision trail is unauditable.
    "serving_autotune": ("static_p99_ms", "tuned_p99_ms", "tuner_win",
                         "decision_trail"),
    # The decode A/B (docs/serving.md §decode): a tokens/sec headline
    # without the naive-recompute arm, the speedup ratio, the
    # inter-token tail, and the KV-cache utilization receipt doesn't
    # prove the paged cache earned its complexity.
    "serving_decode": ("tokens_per_sec", "naive_tokens_per_sec",
                       "kv_cache_speedup", "inter_token_p99_ms",
                       "kv_utilization"),
    # The federation chaos row (docs/serving.md §"Replica federation"):
    # an aggregate-rps headline without the single-replica baseline,
    # the eviction/failover counter receipts, and an explicit zero
    # non-typed-failure count doesn't prove the fleet scaled OR that
    # the SIGKILL arm degraded in a typed, retryable way.
    "serving_federation": ("aggregate_rps", "single_replica_rps",
                           "evictions", "failover_retries",
                           "non_typed_failures"),
}


def _validate_row_kind(row: Dict[str, Any]) -> List[str]:
    required = _ROW_KIND_EXTRAS.get(row.get("workload"))
    if not required or row.get("status") != "ok":
        return []  # failure rows carry no measurement to receipt
    extras = row.get("extras")
    if not isinstance(extras, dict):
        return [f"{row['workload']} row is missing extras "
                f"({', '.join(required)})"]
    return [f"{row['workload']} row extras missing {key!r}"
            for key in required if key not in extras]


def append_row(row: Dict[str, Any], path: Optional[str] = None) -> None:
    """Validate and append one row to the append-only ledger (write +
    flush + fsync — a crash can tear at most the final line, which
    :func:`read_ledger` tolerates). Bumps ``bench_rows_total{status}``."""
    problems = validate_row(row)
    if problems:
        raise ValueError("invalid ledger row: " + "; ".join(problems))
    with open(path or ledger_path(), "a") as f:
        f.write(json.dumps(row) + "\n")
        f.flush()
        os.fsync(f.fileno())
    registry().counter("bench_rows_total").labels(
        status=row["status"]).inc()


def read_ledger(path: Optional[str] = None) -> List[Dict[str, Any]]:
    """All parseable rows, in append order. Torn/corrupt lines are
    skipped (counted into bench_baseline_corrupt_total's sibling spirit:
    a ledger read must never crash the sentinel)."""
    p = path or ledger_path()
    rows: List[Dict[str, Any]] = []
    try:
        with open(p, "r", errors="replace") as f:
            lines = f.readlines()
    except OSError:
        return rows
    for line in lines:
        line = line.strip()
        if not line:
            continue
        try:
            row = json.loads(line)
        except ValueError:
            continue
        if isinstance(row, dict):
            rows.append(row)
    return rows


# ---------------------------------------------------------------------------
# Best-so-far baseline (BENCH_baseline.json) — atomic + corruption-tolerant
# ---------------------------------------------------------------------------
def baseline_key(metric: str, backend: str) -> str:
    """Baseline table key: unsuffixed keys are TPU numbers; every other
    backend namespaces as ``metric@backend`` so a CPU-rig run is never
    scored against TPU throughput. A row always names the backend that
    measured it."""
    if backend == "tpu":
        return metric
    return f"{metric}@{backend}"


def load_baseline(path: Optional[str] = None) -> Dict[str, float]:
    """Best-so-far table; a corrupt/truncated/mistyped file degrades to
    empty with a ``bench_baseline_corrupt_total`` bump instead of
    crashing the scoreboard."""
    p = path or baseline_path()
    if not os.path.exists(p):
        return {}
    try:
        with open(p) as f:
            table = json.load(f)
        if isinstance(table, dict):
            if "metric" in table:  # migrate old single-metric format
                return {str(table["metric"]): float(table["value"])}
            return {str(k): float(v) for k, v in table.items()}
    except (ValueError, TypeError, OSError):
        pass
    registry().counter("bench_baseline_corrupt_total").inc()
    return {}


def save_baseline(table: Dict[str, float],
                  path: Optional[str] = None) -> None:
    """Atomic replace (same-dir tmp + fsync + os.replace, the
    utils/model_serializer discipline) — a crash mid-write can no
    longer leave a truncated baseline behind."""
    p = path or baseline_path()
    tmp = f"{p}.tmp.{os.getpid()}"
    try:
        with open(tmp, "w") as f:
            json.dump(table, f)
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, p)
    finally:
        try:
            os.unlink(tmp)
        except OSError:
            pass


# ---------------------------------------------------------------------------
# Regression sentinel + trajectory report
# ---------------------------------------------------------------------------
def check_rows(rows: Sequence[Dict[str, Any]],
               baseline: Dict[str, float], band: float = 0.03,
               metrics: Optional[Sequence[str]] = None
               ) -> Tuple[List[str], List[str]]:
    """Compare the freshest healthy row per metric against best-so-far
    with a noise band. The band widens to the row's own recorded
    process-to-process spread when that is larger (the round-4
    6852-vs-7014 lesson: drift without spread data reads as
    regression). Returns (regressed_metrics, report_lines)."""
    latest: Dict[str, Dict[str, Any]] = {}
    for row in rows:
        metric = row.get("metric")
        if metric and row.get("status") == "ok":
            latest[metric] = row  # append order: last healthy row wins
    failures: List[str] = []
    lines: List[str] = []
    for metric in sorted(latest):
        if metrics and metric not in metrics:
            continue
        row = latest[metric]
        value = float(row.get("value") or 0.0)
        key = baseline_key(metric, row.get("backend"))
        best = baseline.get(key)
        if not best or best <= 0:
            lines.append(f"  --  {metric}: no baseline under {key!r} "
                         f"(recorded {value:g})")
            continue
        eff_band = band
        spread = row.get("spread") or {}
        if value > 0 and isinstance(spread.get("min"), (int, float)) \
                and isinstance(spread.get("max"), (int, float)):
            eff_band = max(band,
                           (spread["max"] - spread["min"]) / value)
        ratio = value / best
        if ratio < 1.0 - eff_band:
            failures.append(metric)
            lines.append(
                f"  REG {metric}: {value:g} vs best {best:g} "
                f"(x{ratio:.3f}, band {eff_band:.3f})")
        else:
            lines.append(
                f"  ok  {metric}: {value:g} vs best {best:g} "
                f"(x{ratio:.3f}, band {eff_band:.3f})")
    return failures, lines


def _tier_extras_lines(row: Dict[str, Any]) -> List[str]:
    """Per-tier latency / shed / starvation detail for rows whose
    extras carry it (the serving_multimodel A/B) — one indented line
    per tier plus a shed/starvation summary, so `bench.py report`
    surfaces the tier SLO picture without re-running the bench."""
    extras = row.get("extras") or {}
    tiers = extras.get("tier_latency_ms")
    out: List[str] = []
    if isinstance(tiers, dict):
        for tier in sorted(tiers, key=lambda t:
                           {"critical": 0, "standard": 1,
                            "batch": 2}.get(t, 9)):
            v = tiers.get(tier) or {}
            out.append(f"      tier {tier}: p50 {v.get('p50', 0):g}ms  "
                       f"p99 {v.get('p99', 0):g}ms")
    bits = []
    if "tier_sheds" in extras:
        bits.append(f"sheds {extras['tier_sheds']}")
    if "starvation_total" in extras:
        bits.append(f"starvation {extras['starvation_total']}")
    if "fused_speedup" in extras:
        bits.append(f"fused x{extras['fused_speedup']:g}")
    # Quantized-serving A/B detail (the serving_quant / quant_matmul_ab
    # rows): speedup-with-drift so the report shows the accuracy cost
    # next to the throughput win, and the dispatch verdict for the
    # op-level row.
    if "quant_speedup_int8" in extras:
        bits.append(f"int8 x{extras['quant_speedup_int8']:g} "
                    f"(drift {extras.get('max_drift_int8', 0):g})")
    if "quant_speedup_bf16" in extras:
        bits.append(f"bf16 x{extras['quant_speedup_bf16']:g} "
                    f"(drift {extras.get('max_drift_bf16', 0):g})")
    if "dispatch_verdict" in extras:
        bits.append(f"dispatch {extras['dispatch_verdict']}")
    if bits:
        out.append("      " + "  ".join(bits))
    return out


def render_report(rows: Sequence[Dict[str, Any]],
                  baseline: Dict[str, float]) -> str:
    """Round-over-round trajectory per metric from the ledger: one
    chronological line per row with provenance and status flags."""
    by_metric: Dict[str, List[Dict[str, Any]]] = {}
    anon: List[Dict[str, Any]] = []
    for row in rows:
        metric = row.get("metric")
        if metric:
            by_metric.setdefault(metric, []).append(row)
        else:
            anon.append(row)
    out: List[str] = []
    for metric in sorted(by_metric):
        history = by_metric[metric]
        best = baseline.get(baseline_key(
            metric, history[-1].get("backend")))
        head = f"{metric}"
        if best:
            head += f"  (best {best:g})"
        out.append(head)
        for row in history:
            ts = time.strftime("%Y-%m-%dT%H:%M:%S",
                               time.localtime(row.get("ts", 0)))
            flags = row.get("status", "?")
            if row.get("timeout") and flags != "timeout":
                flags += ",timeout"
            value = row.get("value")
            val = f"{value:g} {row.get('unit', '')}".strip() \
                if value is not None else "-"
            ratio = ""
            if best and value:
                ratio = f"  x{value / best:.3f}"
            out.append(f"  {ts}  sha={row.get('git_sha', '?')}  "
                       f"backend={row.get('backend', '?')}  "
                       f"[{flags}]  {val}{ratio}")
            out.extend(_tier_extras_lines(row))
    for row in anon:
        ts = time.strftime("%Y-%m-%dT%H:%M:%S",
                           time.localtime(row.get("ts", 0)))
        out.append(f"{row.get('workload', '?')}  {ts}  "
                   f"[{row.get('status', '?')}]  "
                   f"{row.get('failure', '')}".rstrip())
    return "\n".join(out) if out else "(empty ledger)"
