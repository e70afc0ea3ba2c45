"""Compile-cache smoke: the warm-start acceptance check, end to end.

Runs a tiny `fit` (LeNet-shaped MLP, two batches) TWICE as fresh
`python -c` subprocesses sharing one temporary persistent-cache
directory. The cold run populates the cache (misses); the warm run must
report cache HITS > 0 — proving a new process deserializes XLA
executables from disk instead of recompiling — and both runs must
finish under a wall-clock ceiling and print `compile_cache.status()`
with the final score as valid JSON.

Run by runtests.sh as a separate step (no test_ prefix on purpose —
this is a cross-process end-to-end smoke, not a pytest unit). Exits
nonzero on any failed expectation.

Usage: JAX_PLATFORMS=cpu python tests/smoke_compile_cache.py
Env:   DL4JTPU_SMOKE_CEILING_S  per-run wall ceiling, default 300.
"""
import json
import os
import subprocess
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

CHILD = """
import json
import numpy as np
from deeplearning4j_tpu import (Adam, DenseLayer, InputType,
                                MultiLayerNetwork, NeuralNetConfiguration,
                                OutputLayer)
from deeplearning4j_tpu.optimize import compile_cache
compile_cache.enable()
conf = (NeuralNetConfiguration.builder().seed(1).updater(Adam(0.01)).list()
        .layer(DenseLayer(n_out=32, activation="relu"))
        .layer(OutputLayer(n_out=10, activation="softmax", loss="mcxent"))
        .set_input_type(InputType.feed_forward(64)).build())
rng = np.random.default_rng(0)
x = rng.standard_normal((32, 64)).astype(np.float32)
y = np.eye(10, dtype=np.float32)[rng.integers(0, 10, 32)]
net = MultiLayerNetwork(conf).init()
net.fit(x, y, epochs=1, batch_size=16)
print(json.dumps({"value": float(net.score_value),
                  "compile_cache": compile_cache.status()}))
"""


def run_once(cache_dir: str, ceiling: float):
    env = dict(os.environ)
    env.update(JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=cache_dir)
    t0 = time.monotonic()
    out = subprocess.run(
        [sys.executable, "-c", CHILD],
        capture_output=True, text=True, env=env, cwd=REPO,
        timeout=ceiling + 60)
    wall = time.monotonic() - t0
    if out.returncode != 0:
        print(f"SMOKE FAIL: child rc={out.returncode}\n"
              f"{out.stderr[-3000:]}", file=sys.stderr)
        sys.exit(1)
    try:
        row = json.loads(out.stdout.strip().splitlines()[-1])
    except (ValueError, IndexError):
        print(f"SMOKE FAIL: child stdout is not JSON:\n"
              f"{out.stdout[-2000:]}", file=sys.stderr)
        sys.exit(1)
    return row, wall


def main() -> int:
    ceiling = float(os.environ.get("DL4JTPU_SMOKE_CEILING_S", "300"))
    failures = []
    with tempfile.TemporaryDirectory(prefix="dl4jtpu_cc_smoke_") as d:
        cold, cold_wall = run_once(d, ceiling)
        warm, warm_wall = run_once(d, ceiling)

    for name, row, wall in (("cold", cold, cold_wall),
                            ("warm", warm, warm_wall)):
        if wall > ceiling:
            failures.append(f"{name} run took {wall:.0f}s "
                            f"(ceiling {ceiling:.0f}s)")
        cc = row.get("compile_cache") or {}
        if not cc.get("enabled"):
            failures.append(f"{name} run: compile cache not enabled "
                            f"({cc})")
        if not (isinstance(row.get("value"), (int, float))
                and row["value"] > 0):
            failures.append(f"{name} run: bad metric value "
                            f"{row.get('value')!r}")

    cold_cc = cold.get("compile_cache") or {}
    warm_cc = warm.get("compile_cache") or {}
    if not cold_cc.get("misses", 0) > 0:
        failures.append("cold run reported no cache misses "
                        f"({cold_cc}) — cache not actually in the loop")
    if not warm_cc.get("hits", 0) > 0:
        failures.append("warm run reported no cache hits "
                        f"({warm_cc}) — persistent cache did not "
                        "survive across processes")

    if failures:
        for f in failures:
            print(f"SMOKE FAIL: {f}", file=sys.stderr)
        return 1
    print(f"compile-cache smoke OK: cold {cold_wall:.0f}s "
          f"(misses={cold_cc.get('misses')}, entries="
          f"{cold_cc.get('entries')}), warm {warm_wall:.0f}s "
          f"(hits={warm_cc.get('hits')}, misses={warm_cc.get('misses')})")
    return 0


if __name__ == "__main__":
    sys.exit(main())
