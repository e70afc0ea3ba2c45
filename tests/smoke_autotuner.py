"""Smoke: the serving control loop closes end to end on a live gateway.

Builds a deliberately mis-tuned gateway (standard-tier `app` stuck
with a fat collector linger under a tight tier SLO, flight recorder
armed), arms the AutoTuner at a fast cadence against a temp ledger,
and drives a chaos-shifted workload — a batch-tier `bulk` flood joins
mid-run. Asserts the loop actually closed:

* the tuner made >= 1 ledgered move, and every ledger row is
  schema-valid (the auditable-trail contract)
* NO move ever left its knob's [lo, hi] guardrails, and the live
  config agrees with the ledger's final word for each knob
* the tuner measurably tightened the mis-tuned linger
* GET /debug/tuner renders the state + knob table + decision trail
  over live HTTP, and GET /metrics carries the tuner families
* a clean run never froze: serving_tuner_frozen == 0

Run: JAX_PLATFORMS=cpu python tests/smoke_autotuner.py
Run by runtests.sh as a separate step (no test_ prefix on purpose).
"""
import json
import os
import signal
import sys
import tempfile
import threading
import time
import urllib.request

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import numpy as np  # noqa: E402

HARD_TIMEOUT_S = 120

RUN_S = 4.0
SHIFT_S = 1.5
LINGER_MS = 8.0
STANDARD_SLO_MS = 6.0


def _alarm(signum, frame):
    print(f"SMOKE FAIL: autotuner smoke exceeded {HARD_TIMEOUT_S}s "
          "hard timeout", flush=True)
    os._exit(2)


signal.signal(signal.SIGALRM, _alarm)
signal.alarm(HARD_TIMEOUT_S)


class _EchoStub:
    """Device-free forward: the smoke measures the control loop, not
    XLA (the chaos-suite stub idiom)."""

    _initialized = True

    def output(self, x):
        return np.asarray(x) * 2.0


def _get_json(url):
    with urllib.request.urlopen(url, timeout=10) as r:
        return r.status, json.loads(r.read())


def main() -> int:
    from deeplearning4j_tpu.optimize.metrics import registry
    from deeplearning4j_tpu.serving import ServingGateway, SLOMonitor
    from deeplearning4j_tpu.serving import flight_recorder
    from deeplearning4j_tpu.serving.autotuner import (read_ledger,
                                                      validate_entry)

    failures = []
    rng = np.random.default_rng(0)
    payloads = [rng.standard_normal((1, 8)).astype(np.float32)
                for _ in range(8)]

    with tempfile.TemporaryDirectory(prefix="dl4jtpu_smoke_at_") as tmp:
        ledger = os.path.join(tmp, "autotune_ledger.jsonl")
        flight_recorder.enable()
        gw = ServingGateway()
        gw.add_model("app", _EchoStub(), batch_limit=8,
                     batch_timeout_ms=LINGER_MS, tier="standard")
        gw.add_model("bulk", _EchoStub(), batch_limit=16,
                     batch_timeout_ms=LINGER_MS, tier="batch")
        gw.pool.reconfigure_scheduler(
            tier_slo_ms={"standard": STANDARD_SLO_MS, "batch": 500.0})
        tuner = gw.attach_tuner(
            ledger_path=ledger, interval_s=0.2, settle_ticks=1,
            breach_freeze_factor=10.0,
            monitor=SLOMonitor(gw.pool, window_s=1.5, min_samples=3))
        try:
            with gw:  # live HTTP — /debug/tuner must render mid-flight
                stop = time.perf_counter() + RUN_S
                shift_at = time.perf_counter() + SHIFT_S
                errs = []

                def app_client():
                    try:
                        i = 0
                        while time.perf_counter() < stop:
                            gw.predict("app", payloads[i % len(payloads)])
                            i += 1
                    except Exception as e:  # TierShedError included: typed
                        if "TierShed" not in type(e).__name__:
                            errs.append(repr(e))

                def bulk_client():
                    try:
                        i = 0
                        while time.perf_counter() < shift_at:
                            time.sleep(0.02)
                        while time.perf_counter() < stop:
                            try:
                                gw.predict("bulk",
                                           payloads[i % len(payloads)])
                            except Exception as e:
                                if "TierShed" not in type(e).__name__:
                                    raise
                                time.sleep(0.001)
                            i += 1
                    except Exception as e:
                        errs.append(repr(e))

                ts = [threading.Thread(target=app_client)
                      for _ in range(2)]
                ts.append(threading.Thread(target=bulk_client))
                for t in ts:
                    t.start()
                for t in ts:
                    t.join()
                if errs:
                    failures.append(f"client errors: {errs[:3]}")

                code, dbg = _get_json(gw.url + "/debug/tuner")
                if code != 200 or dbg.get("enabled") is not True:
                    failures.append(
                        f"/debug/tuner: code={code} enabled="
                        f"{dbg.get('enabled')!r}, wanted 200/True")
                if not isinstance(dbg.get("trail"), list) or \
                        not dbg["trail"]:
                    failures.append("/debug/tuner trail is empty — the "
                                    "decision trail never rendered")
                if not dbg.get("knobs"):
                    failures.append("/debug/tuner knob table is empty")
                guardrails = {k["name"]: (k["lo"], k["hi"])
                              for k in dbg.get("knobs", [])}

                with urllib.request.urlopen(gw.url + "/metrics",
                                            timeout=10) as r:
                    scrape = r.read().decode()
                for fam in ("serving_tuner_moves_total",
                            "serving_tuner_frozen",
                            "serving_slo_verdict"):
                    if fam not in scrape:
                        failures.append(
                            f"/metrics scrape missing {fam!r}")
        finally:
            tuner.stop()
            gw.pool.shutdown()
            flight_recorder.disable()

        rows = read_ledger(ledger)
        moves = [r for r in rows if r.get("kind") == "move"]
        if not moves:
            failures.append("tuner made ZERO ledgered moves in "
                            f"{RUN_S}s at 0.2s cadence")
        for r in rows:
            problems = validate_entry(r)
            if problems:
                failures.append(f"ledger row seq={r.get('seq')} failed "
                                f"schema: {problems}")
        for m in moves:
            lo_hi = guardrails.get(m["knob"])
            if lo_hi is None:
                failures.append(f"move on unknown knob {m['knob']!r}")
            elif not (lo_hi[0] <= m["new"] <= lo_hi[1]):
                failures.append(
                    f"GUARDRAIL VIOLATION: move seq={m['seq']} set "
                    f"{m['knob']}={m['new']} outside {lo_hi}")

        final_linger = tuner_final_linger = None
        for k in (tuner.describe())["knobs"]:
            if k["name"] == "linger_ms:app":
                tuner_final_linger = k["value"]
        final_linger = tuner_final_linger
        if final_linger is None:
            failures.append("linger_ms:app knob missing from describe()")
        elif final_linger >= LINGER_MS:
            failures.append(f"tuner never tightened the mis-tuned linger "
                            f"({final_linger} >= {LINGER_MS})")

        frozen = registry().gauge("serving_tuner_frozen").value()
        if frozen != 0.0:
            failures.append(f"clean run ended frozen "
                            f"(serving_tuner_frozen={frozen})")

    signal.alarm(0)
    if failures:
        print("SMOKE FAIL: serving control loop")
        for f in failures:
            print(f"  - {f}")
        return 1
    print(f"SMOKE OK: control loop closed — {len(moves)} ledgered "
          f"move(s), all inside guardrails, linger {LINGER_MS} -> "
          f"{final_linger}, /debug/tuner trail live, never froze")
    return 0


if __name__ == "__main__":
    sys.exit(main())
