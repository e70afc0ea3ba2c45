#!/usr/bin/env python3
"""Run one cell once.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process holds the cell's chips. Set-up (weights and inputs from the
seed, every shape warmed) ends where the measured window starts; the
window lasts `--seconds`; then the device's memory peak is read, the
program's state is freed, and the plain reference decides `correct`. The
last line of standard output is the result.
"""
from __future__ import annotations

import time

T_PROCESS_START = time.perf_counter()

import argparse
import importlib
import json
import os
import sys
from dataclasses import dataclass

if __package__ in (None, ""):      # run as a file: make the checkout importable
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

from benchmark import compare, manifest
from benchmark.peaks import peaks_for
from benchmark.probes import Probe

TRACE_DIR = os.path.join(manifest.ROOT, ".benchmark_out", "trace")


@dataclass
class Context:
    cell: dict
    cfg: dict
    traffic: dict
    seed: int
    chips: int
    man: manifest.Manifest


def log(msg: str) -> None:
    print(msg, flush=True)


def find_devices(chips: int):
    """The accelerator JAX found, or None: no TPU, or fewer chips than the
    cell asks for."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < chips:
        print(f"no accelerator for this cell: platform "
              f"{devs[0].platform!r}, {len(devs)} device(s), cell needs "
              f"{chips} TPU chip(s)", file=sys.stderr)
        return None
    return devs[:chips]


def memory_bytes(devices, key: str) -> int:
    """The allocator's `key` on the fullest chip."""
    return max(int((d.memory_stats() or {}).get(key, 0)) for d in devices)


def read_layer_metrics(ctx: Context, reading: dict) -> dict:
    """Every per-layer metric of this cell through its own reader. A
    reader that finds nothing to read returns None and the metric is left
    out of the line."""
    out = {}
    for m in ctx.man.metrics_for(ctx.cell["name"], "per_layer"):
        spec = manifest.data_file("layer_metrics", m["name"], ctx.man.root)
        value = manifest.resolve(spec["reader"])(reading,
                                                 **spec.get("args", {}))
        if value is None:
            log(f"info layer metric {m['name']}: nothing to read")
            continue
        out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def run_cell(man: manifest.Manifest, workload: str, seed: int,
             seconds: float, trace: bool, devices, controls=()) -> dict:
    """Everything of a run after the look for a chip. `devices` are the
    chips in use (the CPU's in a rehearsal). `controls` is never set by
    the benchmark's own command: the builder's tool and the tests name
    lower precisions (or `half`, a planted fault), and for each the
    reference computed so is put in the program's place and held to the
    cell's own limits, under `controls` in the result. Each has to come
    out as not correct."""
    import jax
    from deeplearning4j_tpu.optimize import compile_cache, telemetry

    cell = man.cell(workload)
    ctx = Context(cell=cell, seed=seed, chips=int(cell["chips"]), man=man,
                  cfg=manifest.data_file("configs", cell["config"], man.root),
                  traffic=manifest.data_file("traffic", cell["traffic"],
                                             man.root))
    cache_dir = compile_cache.enable()          # before the first compile
    telemetry.compilation_count()               # attaches the listener
    kind = importlib.import_module(
        f"benchmark.kinds.{ctx.traffic['kind']}").Kind(ctx)
    log(f"info cell={workload} seed={seed} seconds={seconds} "
        f"trace={int(trace)} platform={devices[0].platform} "
        f"kind={devices[0].device_kind!r} count={len(devices)} "
        f"compile_cache={cache_dir}")

    kind.setup()
    cache = compile_cache.status()

    offset = float(cell.get("trace_offset_s", 2.0))
    probe = Probe(trace, TRACE_DIR, kind.host_spans, offset,
                  min(float(cell.get("trace_seconds", 4.0)),
                      max(0.5, seconds - offset - 0.5)))
    win = kind.run(seconds, probe)
    # process start to the start of the window: a mix's ramp counts too
    setup_s = win["t0"] - T_PROCESS_START
    log(f"info setup_s={setup_s:.3f} cache_hits={cache.get('hits')} "
        f"cache_misses={cache.get('misses')}")
    log(f"info compilations_in_window={probe.compilations}")
    log(f"info selected {json.dumps(probe.selected(), sort_keys=True)}")
    if win.get("errors"):
        log(f"info request errors: {win['errors']}")

    allocator_peak = memory_bytes(devices, "peak_bytes_in_use")
    temporaries = kind.temporaries_bytes()
    resident = memory_bytes(devices, "bytes_in_use")
    mem_peak = max(allocator_peak, resident + temporaries)
    log(f"info memory allocator_peak={allocator_peak} resident={resident} "
        f"step_temporaries={temporaries} memory_peak_bytes={mem_peak}")

    e2e = dict(win["metrics"])
    e2e["setup_s"] = setup_s
    on_tpu = devices[0].platform == "tpu"
    reading = dict(ctx=ctx, window=win, probe=probe, e2e=e2e,
                   peaks=peaks_for(devices[0].device_kind) if on_tpu
                   else None, records=getattr(kind, "records", []))
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": mem_peak}
    result = {"correct": False, "attempted": win["attempted"],
              "failed": win["failed"], "metrics": {}, "device": device}
    if trace:
        tr = probe.reduced
        device["busy_s"] = tr.mean_busy_s() if tr and tr.ops else 0.0
        device["window_s"] = tr.window_s() if tr and tr.window else 0.0
        result["metrics"] = read_layer_metrics(ctx, reading)
        if tr and tr.ops:
            result["breakdown"] = {
                "device_ops": tr.top_ops(10),
                "idle_gaps": tr.idle_gaps(10)}
    else:
        units = {m["name"]: m["unit"]
                 for m in man.metrics_for(workload, "end_to_end")}
        result["metrics"] = {k: {"value": float(v),
                                 "unit": units.get(k, "")}
                             for k, v in e2e.items()}

    kind.release()
    limits = dict(cell.get("limits", {}))
    limits.setdefault("compilations_in_window", 0.0)

    def judged(control=None):
        numbers = kind.check(control)
        numbers["compilations_in_window"] = float(probe.compilations)
        ok, table = compare.verdict(numbers, limits, win["failed"])
        return ok and win["attempted"] > 0, table

    t_ref = time.perf_counter()
    correct, table = judged()
    log(f"info reference_s={time.perf_counter() - t_ref:.3f}")
    for c in controls:
        ok, compared = judged(c)
        result.setdefault("controls", {})[c] = {"correct": ok,
                                                "compared": compared}
    result["correct"] = correct
    result["compared"] = table            # comes last in the line
    compare.print_compared(table, correct)
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    man = manifest.Manifest()
    cell = man.cell(args.workload)
    devices = find_devices(int(cell["chips"]))
    if devices is None:
        return 2
    result = run_cell(man, args.workload, args.seed, args.seconds,
                      bool(args.trace), devices)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
