"""The comparisons that decide `correct`. Each returns the numbers it
compared; `verdict` holds each to its limit and prints them."""
from __future__ import annotations

import json
import math
import statistics
import sys
from typing import Dict, Tuple

# A leaf whose reference gradient is under this share of the median leaf's
# is nought to rounding (a convolution's bias under BatchNorm): RmsProp
# moves it by round-off alone, so it is left out of the change.
DEAD_LEAF_SHARE = 1e-3


def _norm_gaps(prog: Dict[str, float], ref: Dict[str, float],
               keys=None) -> Dict[str, float]:
    """Per leaf the gap between the program's norm and the reference's
    (never the norm of their difference), against the reference's norm of
    that leaf or of the median leaf, whichever is larger."""
    med = statistics.median(ref.values())
    return {k: abs(prog[k] - ref[k]) / max(ref[k], med)
            for k in (ref if keys is None else keys)}


def training_gaps(prog: tuple, ref: tuple) -> Dict[str, float]:
    """`prog` and `ref` are (per-step losses, per-leaf norm of the first
    gradient, per-leaf norm of the parameters' change, per-leaf norm of
    the change of BatchNorm's running mean and variance). Gaps are taken
    by the worst leaf, and by the median leaf beside it."""
    (pl, pg, pc, ps), (rl, rg, rc, rs) = prog, ref
    if len(pl) != len(rl) or set(pg) != set(rg) or set(pc) != set(rc) \
            or set(ps) != set(rs):
        raise ValueError("program and reference followed different steps "
                         "or hold different leaves")
    med_g = statistics.median(rg.values())
    live = [k for k in rc if rg[k] >= DEAD_LEAF_SHARE * med_g]
    grad = _norm_gaps(pg, rg)
    change = _norm_gaps(pc, rc, live)
    state = _norm_gaps(ps, rs)
    return {
        "loss_gap": max(abs(a - b) / abs(b) for a, b in zip(pl, rl)),
        "loss1_gap": abs(pl[0] - rl[0]) / abs(rl[0]),
        "grad_gap": max(grad.values()),
        "grad_gap_median": statistics.median(grad.values()),
        "change_gap": max(change.values()),
        "change_gap_median": statistics.median(change.values()),
        "bn_state_gap": max(state.values()),
        "bn_state_gap_median": statistics.median(state.values()),
    }


def verdict(numbers: Dict[str, float], limits: Dict[str, float],
            failed: int = 0) -> Tuple[bool, Dict[str, dict]]:
    """Every number that has a limit is held to it (a number without one
    is printed and not judged). -> (correct, {name: {value, limit}})."""
    table, ok = {}, failed == 0
    for name, value in numbers.items():
        limit = limits.get(name)
        finite = value is not None and math.isfinite(value)
        # a number that is not finite is written as null (the line stays
        # JSON) and fails its limit
        table[name] = {"value": value if finite else None, "limit": limit}
        if limit is not None and not (finite and value <= limit):
            ok = False
    missing = [k for k in limits if k not in numbers]
    if missing:
        ok = False
        for k in missing:
            table[k] = {"value": None, "limit": limits[k]}
    return ok, table


def print_compared(table: Dict[str, dict], correct: bool) -> None:
    """Each number compared beside its limit, as the last lines on
    standard error."""
    for name, row in table.items():
        print(f"compared {name} = {row['value']} limit {row['limit']}",
              file=sys.stderr)
    print(f"correct = {json.dumps(correct)}", file=sys.stderr, flush=True)
