"""Readers over the reduced profiler trace (`trace_reduce.Trace`) and the
yardstick's own FLOP and byte functions. Each returns None where the trace
holds nothing to read; none returns 0 for a share of a peak."""
from __future__ import annotations

from benchmark import flops, stats


def _trace(reading):
    tr = reading["probe"].reduced
    return tr if tr is not None and tr.ops and tr.window else None


def idle_share(reading):
    """1 - union of operation intervals over the traced window, on the
    busiest device, in percent."""
    tr = _trace(reading)
    if tr is None:
        return None
    return 100.0 * stats.idle_share(tr.busy_s(tr.fullest()), tr.window_s())


def module_busy_ms(reading, module: str):
    """Device busy milliseconds per execution of the modules whose name
    contains `module`, on the busiest device."""
    tr = _trace(reading)
    if tr is None:
        return None
    busy, runs = tr.module_busy(tr.fullest(), module)
    return 1e3 * busy / runs if runs else None


def train_step_mfu(reading, flops_per_sample: str):
    """Model FLOPs of the whole step over the chips' bf16 peak: samples/s
    of this (traced) run x FLOPs per sample / (chips x peak), in percent."""
    if reading["peaks"] is None:
        return None
    ctx = reading["ctx"]
    per = getattr(flops, flops_per_sample)(ctx.cfg)
    rate = reading["e2e"]["train_samples_per_s"]
    return 100.0 * rate * per / (ctx.chips * reading["peaks"]["bf16_flops"])


def generate_step_mfu(reading):
    """2 x matrix parameters (+ attention) x tokens the window processed
    (prompt and output tokens of the requests it completed) per second,
    over the bf16 peak, in percent."""
    if reading["peaks"] is None:
        return None
    w, cfg = reading["window"], reading["ctx"].cfg
    toks = w["tokens"] + w["prompt_tokens"]
    if toks <= 0:
        return None
    context = reading["ctx"].traffic["prompt_len"]["median"]
    rate = toks / (w["t1"] - w["t0"])
    return 100.0 * rate * flops.decoder_flops_per_token(cfg, context) \
        / reading["peaks"]["bf16_flops"]


def decode_step_roofline(reading, module: str):
    """Bytes one decode step must read (every weight once, the K and V
    views once) over the HBM peak, over the device busy time of a step,
    in percent."""
    tr = _trace(reading)
    if tr is None or reading["peaks"] is None:
        return None
    busy, runs = tr.module_busy(tr.fullest(), module)
    if not runs or busy <= 0:
        return None
    ctx = reading["ctx"]
    c = reading["probe"].counters
    rows = ctx.cfg["engine"]["max_decode_batch"]
    steps = c.get("serving_decode_steps_total", 0.0)
    if steps > 0:
        rows = min(rows, max(1.0, c.get("serving_decode_tokens_total", 0.0)
                             / steps))
    kv = ctx.traffic["prompt_len"]["median"] \
        + ctx.traffic["max_new_tokens"] / 2
    least = flops.decoder_step_bytes(ctx.cfg, rows, kv) \
        / reading["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least * runs / busy
