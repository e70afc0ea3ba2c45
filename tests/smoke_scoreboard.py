"""Smoke: the bench scoreboard plane names a wedged child and fails.

Recreates the round-5 failure (a bench child that goes silent
mid-measurement) on demand with a `delay:` fault on `bench.child`,
then asserts the fail-safe path holds end to end:

* the watchdog kills the wedged child in seconds, not at the budget
* bench.py exits NON-ZERO and prints no artifact line: nothing was
  measured at the full config, and there is no reduced-config stand-in
* stderr names the wedge
* the ledger got exactly one schema-valid typed `status: "wedged"` row
  with no value and backend "none" (nobody measured)

Run: JAX_PLATFORMS=cpu python tests/smoke_scoreboard.py
Run by runtests.sh as a separate step (no test_ prefix on purpose).
"""
import json
import os
import signal
import subprocess
import sys
import tempfile

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from deeplearning4j_tpu.optimize import scoreboard  # noqa: E402

# Worst observed: ~6 s to wedge-kill the child on a contended CPU rig.
HARD_TIMEOUT_S = 420


def _alarm(signum, frame):
    print(f"SMOKE FAIL: scoreboard smoke exceeded {HARD_TIMEOUT_S}s "
          "hard timeout", flush=True)
    os._exit(2)


signal.signal(signal.SIGALRM, _alarm)
signal.alarm(HARD_TIMEOUT_S)


def main() -> int:
    failures = []
    with tempfile.TemporaryDirectory(prefix="dl4jtpu_smoke_sb_") as tmp:
        env = dict(os.environ)
        env.update(
            JAX_PLATFORMS="cpu",
            BENCH_REPEATS="1",
            # watchdog converts beat-then-silence to "wedged" in ~5 s
            BENCH_STALL_S="5",
            # beat 1 (start) passes; every later bench.child call wedges
            # for 600 s — life, then silence, the round-5 hang on demand
            DL4JTPU_FAULT_BENCH_CHILD="delay:2/1@600000",
            DL4JTPU_BENCH_PROBE="0",
            DL4JTPU_BENCH_LEDGER=os.path.join(tmp, "ledger.jsonl"),
            DL4JTPU_BENCH_BASELINE=os.path.join(tmp, "baseline.json"),
        )
        out = subprocess.run(
            [sys.executable, os.path.join(REPO, "bench.py"), "lenet_tiny"],
            capture_output=True, text=True, env=env, cwd=REPO)

        if out.returncode == 0:
            failures.append("bench.py exited 0 with nothing measured")
        if out.stdout.strip():
            failures.append("bench.py printed an artifact line with "
                            f"nothing measured: {out.stdout[-400:]!r}")
        if "nothing measured" not in out.stderr \
                or "wedged" not in out.stderr:
            failures.append("stderr does not name the wedge: "
                            f"{out.stderr[-400:]!r}")

        ledger_rows = scoreboard.read_ledger(
            os.path.join(tmp, "ledger.jsonl"))
        if len(ledger_rows) != 1:
            failures.append(f"ledger has {len(ledger_rows)} row(s), "
                            "wanted exactly 1")
        else:
            lrow = ledger_rows[0]
            if lrow.get("status") != "wedged":
                failures.append(f"ledger row status {lrow.get('status')!r},"
                                " wanted 'wedged'")
            if "value" in lrow or lrow.get("backend") != "none":
                failures.append("ledger row claims a measurement: "
                                f"{lrow!r}")
            problems = scoreboard.validate_row(lrow)
            if problems:
                failures.append(f"ledger row failed schema: {problems}")

    signal.alarm(0)
    if failures:
        print("SMOKE FAIL: bench scoreboard plane")
        for f in failures:
            print(f"  - {f}")
        return 1
    print("SMOKE OK: wedged bench child -> killed, typed ledger row, "
          "no artifact line, non-zero exit")
    return 0


if __name__ == "__main__":
    sys.exit(main())
