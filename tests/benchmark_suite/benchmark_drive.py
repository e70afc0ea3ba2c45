"""Drives the rest of a run - everything after the harness's look for a
chip - at tiny sizes on the CPU's devices, in a temporary root, and puts
the process back as it found it (the compile cache, the program's tracing
and flight recorder, and the matmul precision a decoder configuration
sets, are process-wide)."""
from __future__ import annotations

import contextlib
import json

import benchmark_tiny


@contextlib.contextmanager
def tiny_root(tmp_path, monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jaxcache"))
    from deeplearning4j_tpu.optimize import compile_cache, tracing
    from deeplearning4j_tpu.serving import flight_recorder
    try:
        yield benchmark_tiny.make_root(str(tmp_path / "root"))
    finally:
        import jax
        jax.config.update("jax_default_matmul_precision", None)
        flight_recorder.disable()
        tracing.disable()
        tracing.clear()
        compile_cache.disable()


def drive(man, cell: str, seed: int, seconds: float, trace: bool,
          controls=()) -> dict:
    import jax
    from benchmark import run
    chips = man.cell(cell)["chips"]
    result = run.run_cell(man, cell, seed, seconds, trace,
                          jax.devices()[:chips], controls=controls)
    line = json.dumps(result)            # the line the driver would read
    back = json.loads(line)
    assert list(back)[-1] == "compared"
    for key in ("correct", "attempted", "failed", "metrics", "device"):
        assert key in back
    return back
