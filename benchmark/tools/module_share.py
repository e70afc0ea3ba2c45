#!/usr/bin/env python3
"""Builder's tool for a look by hand, on the chip. Not run by the driver.

    python3 benchmark/tools/module_share.py <cell> <seconds> <seed> <module> [<module> ...]

One traced run of the cell, as `benchmark/run.py --trace 1` makes it, that
also prints, for each jitted module named (a part of its name:
`_step_pure`, `_prefill_pure`), the device's busy seconds inside its runs
in the traced window, the runs, and the share of the window: which of a
decoder's two programs the device spends its time in. The result line
is the run's own, last on standard output.
"""
from __future__ import annotations

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def main(cell: str, seconds: str, seed: str, *modules: str) -> int:
    sys.path.insert(0, ROOT)
    from benchmark import run
    read = run.read_layer_metrics

    def with_shares(ctx, reading):
        tr = reading["probe"].reduced
        if tr is not None and tr.ops and tr.window:
            dev, window = tr.fullest(), tr.window_s()
            for m in modules:
                busy, runs = tr.module_busy(dev, m)
                run.log(f"info module {m}: busy_s={busy:.4f} runs={runs} "
                        f"ms_a_run={1e3 * busy / max(runs, 1):.3f} "
                        f"share_of_window={100.0 * busy / window:.2f}%")
        return read(ctx, reading)

    run.read_layer_metrics = with_shares
    try:
        return run.main(["--workload", cell, "--seed", seed, "--seconds",
                         seconds, "--trace", "1"])
    finally:
        run.read_layer_metrics = read


if __name__ == "__main__":
    sys.exit(main(*sys.argv[1:]))
