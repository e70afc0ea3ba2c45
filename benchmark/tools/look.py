#!/usr/bin/env python3
"""Builder's tools for a look by hand, on the chip. Not run by the driver.

    python3 benchmark/tools/look.py runs <cell> <seconds> <trace> <seed> [<seed> ...]
        the cell once per seed, each in a process of its own (the chip
        belongs to one process at a time); result lines to
        chiprun_out/<cell>.runs.jsonl, tagged with $LOOK_SET
    python3 benchmark/tools/look.py controls <cell> <seconds> <controls> <seed> [...]
        program and controls read in one process a seed (a process that
        has served the decoder does not give all of its 5.2 GB back): the
        cell's own short window, then the reference, then the reference in
        each of <controls> (comma-separated: bfloat16, float8_e4m3fn,
        bfloat16_stored, or `half`: half of each batch left out; `-` for
        none) put in the program's place and held to the cell's limits;
        a seed written `<seed>:-` reads the program alone. Numbers and
        verdicts to chiprun_out/<cell>.controls.jsonl; exits 1 if one came
        out correct, unless its name is written with a leading `~` (a
        look, not a control)
    python3 benchmark/tools/look.py trace <cell> <seconds> <seed>
        a traced run that keeps the trace's planes, lines and first events
        in chiprun_out/<cell>.trace.txt
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
OUT = os.path.join(ROOT, "chiprun_out")


def runs(cell: str, seconds: str, trace: str, seeds) -> int:
    os.makedirs(OUT, exist_ok=True)
    rc = 0
    for seed in seeds:
        t0 = time.time()
        p = subprocess.run(
            [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
             "--workload", cell, "--seed", seed, "--seconds", seconds,
             "--trace", trace], capture_output=True, text=True, cwd=ROOT)
        wall = time.time() - t0
        lines = p.stdout.strip().splitlines()
        info = [ln for ln in lines if ln.startswith("info ")]
        last = lines[-1] if lines else ""
        print(f"== {cell} seed={seed} trace={trace} rc={p.returncode} "
              f"wall={wall:.1f}s")
        print("\n".join(info[-14:]))
        print(p.stderr.strip()[-1500:])
        print(last[:6000], flush=True)
        with open(os.path.join(OUT, cell + ".runs.jsonl"), "a") as f:
            f.write(json.dumps({"set": os.environ.get("LOOK_SET", ""),
                                "seed": seed, "trace": trace,
                                "seconds": seconds, "rc": p.returncode,
                                "wall": wall, "line": last}) + "\n")
        rc = rc or p.returncode
    return rc


def trace(cell: str, seconds: str, seed: str) -> int:
    sys.path.insert(0, ROOT)
    from benchmark import probes, run, trace_reduce
    os.makedirs(OUT, exist_ok=True)
    keep = probes.shutil.rmtree
    probes.shutil.rmtree = lambda *a, **k: None     # keep the trace to look at
    try:
        rc = run.main(["--workload", cell, "--seed", seed, "--seconds",
                       seconds, "--trace", "1"])
    finally:
        probes.shutil.rmtree = keep
    with open(os.path.join(OUT, cell + ".trace.txt"), "w") as f:
        f.write(trace_reduce.describe(run.TRACE_DIR, n=40))
    keep(run.TRACE_DIR, ignore_errors=True)
    return rc


def controls(cell: str, seconds: str, control: str, seeds) -> int:
    """Each seed in a process of its own; this one stays off JAX."""
    rc = 0
    for seed in seeds:
        p = subprocess.run([sys.executable, os.path.abspath(__file__),
                            "control", cell, seconds, control, seed],
                           cwd=ROOT)
        rc = rc or p.returncode
    return rc


def control(cell: str, seconds: str, control: str, seed: str) -> int:
    sys.path.insert(0, ROOT)
    from benchmark import manifest, run
    os.makedirs(OUT, exist_ok=True)
    man = manifest.Manifest()
    devices = run.find_devices(int(man.cell(cell)["chips"]))
    if devices is None:
        return 2
    seed, _, alone = seed.partition(":")
    names = [] if alone or control == "-" else control.split(",")
    looks = {n[1:] for n in names if n.startswith("~")}
    t0 = time.time()
    r = run.run_cell(man, cell, int(seed), float(seconds), False, devices,
                     controls=[n.lstrip("~") for n in names])
    values = lambda t: {k: v["value"] for k, v in t.items()}
    row = {"seed": seed, "wall": time.time() - t0,
           "correct": r["correct"], "compared": values(r["compared"]),
           "controls": {c: {"correct": v["correct"],
                            "compared": values(v["compared"])}
                        for c, v in r.get("controls", {}).items()},
           "metrics": values(r["metrics"])}
    print("controls " + json.dumps(row), flush=True)
    with open(os.path.join(OUT, cell + ".controls.jsonl"), "a") as f:
        f.write(json.dumps(row) + "\n")
    # what the cell's limits pass is no control
    return int(any(v["correct"] for c, v in row["controls"].items()
                   if c not in looks))


if __name__ == "__main__":
    cmd, args = sys.argv[1], sys.argv[2:]
    if cmd == "runs":
        sys.exit(runs(args[0], args[1], args[2], args[3:]))
    if cmd == "controls":
        sys.exit(controls(args[0], args[1], args[2], args[3:]))
    if cmd == "control":
        sys.exit(control(*args))
    if cmd == "trace":
        sys.exit(trace(args[0], args[1], args[2]))
    sys.exit(f"unknown command {cmd!r}")
