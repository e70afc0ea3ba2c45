"""Reader over the device time of one named kernel. The TPU's trace names
an operation by its whole HLO instruction, whose own name (the part before
` = `) a Pallas kernel takes from its `pallas_call(name=...)`: that is
what is matched, never the operands after it, which name other
instructions."""
from __future__ import annotations

from benchmark import trace_reduce


def kernel_busy_ms(reading, module: str, kernel: str):
    """Device busy milliseconds, per execution of the modules whose name
    contains `module`, inside the operations whose instruction name
    contains `kernel`, on the busiest device. None where the trace holds
    no such run or no such operation."""
    tr = reading["probe"].reduced
    if tr is None or not tr.ops or not tr.window:
        return None
    dev = tr.fullest()
    mine = [ev for ev in tr.ops[dev]
            if kernel in ev[0].split(" = ", 1)[0]]
    if not mine:
        return None
    only = trace_reduce.Trace(ops={dev: mine}, modules=tr.modules,
                              window=tr.window)
    busy, runs = only.module_busy(dev, module)
    return 1e3 * busy / runs if runs and busy > 0 else None
