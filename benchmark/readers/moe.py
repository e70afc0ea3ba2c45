"""Readers for a sparse-expert decoder with window and full layers: the
program's routing, cache and key counters over the window, laid against
the device time of the decode step and of its kernels in the traced
run, with the work from `benchmark/work_moe.py`. A program that has no
such counter (the parent of the PR that added them) reads 0 there and
each reader returns None."""
from __future__ import annotations

from benchmark import work_moe
from benchmark.readers.device import _trace
from benchmark.readers.kernels import kernel_busy_ms

STEPS = "serving_decode_steps_total"
TOUCHED = "serving_moe_experts_touched_total"
KV_TOKENS = "serving_decode_kv_tokens_total{kind=%s}"


def _per_step(reading, name: str):
    """A counter's rise over the window, per decode step; None where
    either is nought."""
    c = reading["probe"].counters
    steps, n = c.get(STEPS, 0.0), c.get(name, 0.0)
    return n / steps if steps > 0 and n > 0 else None


def percent(reading, numerator: str, denominator: str):
    c = reading["probe"].counters
    num, den = c.get(numerator, 0.0), c.get(denominator, 0.0)
    return 100.0 * num / den if num > 0 and den > 0 else None


def experts_touched_share(reading):
    """Experts with a token, of all experts of all layers, per step."""
    cfg = reading["ctx"].cfg
    touched = _per_step(reading, TOUCHED)
    if touched is None:
        return None
    return 100.0 * touched / (cfg["num_hidden_layers"] * cfg["num_experts"])


def load_peak_over_mean(reading):
    """The fullest expert's tokens over the mean expert's, per layer and
    step: 1 is an even load."""
    c = reading["probe"].counters
    peak = c.get("serving_moe_expert_load_peak_total", 0.0)
    assigned = c.get("serving_moe_assignments_total", 0.0)
    if peak <= 0 or assigned <= 0:
        return None
    return peak * reading["ctx"].cfg["num_experts"] / assigned


def step_mfu(reading):
    """Active matrix FLOPs of the tokens the window processed (prompt and
    output; the head for output tokens only) per second over the bf16
    peak, in percent: the share of the whole step."""
    if reading["peaks"] is None or TOUCHED not in reading["probe"].counters:
        return None
    w, ctx = reading["window"], reading["ctx"]
    if w["tokens"] + w["prompt_tokens"] <= 0:
        return None
    keys = work_moe.mean_keys(ctx.traffic, ctx.cfg["sliding_window"])
    flops = (w["tokens"] + w["prompt_tokens"]) \
        * work_moe.flops_per_token(ctx.cfg, keys) \
        + w["tokens"] * work_moe.head_flops(ctx.cfg)
    return 100.0 * flops / (w["t1"] - w["t0"]) \
        / reading["peaks"]["bf16_flops"]


def _step_kv_tokens(reading):
    out = {}
    for kind in ("full", "sliding"):
        n = _per_step(reading, KV_TOKENS % kind)
        if n is not None:
            out[kind] = n
    return out


def decode_step_roofline(reading, module: str):
    """Bytes a decode step must read over the HBM peak, over the device
    busy time of a step's run, in percent."""
    tr = _trace(reading)
    touched, kv = _per_step(reading, TOUCHED), _step_kv_tokens(reading)
    if tr is None or reading["peaks"] is None or touched is None or not kv:
        return None
    busy, runs = tr.module_busy(tr.fullest(), module)
    if not runs or busy <= 0:
        return None
    least = work_moe.decode_step_bytes(reading["ctx"].cfg, touched, kv) \
        / reading["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least * runs / busy


def experts_roofline(reading, module: str, kernel: str):
    """Inside decode-step runs: the touched experts' bytes over the HBM
    peak, over the grouped product's device time, in percent."""
    touched = _per_step(reading, TOUCHED)
    ms = kernel_busy_ms(reading, module, kernel)
    if touched is None or ms is None or reading["peaks"] is None:
        return None
    least = touched * work_moe.expert_params(reading["ctx"].cfg) \
        * work_moe.ELEM / reading["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least / (ms * 1e-3)


def decode_attention_roofline(reading, module: str, kernel: str, kind: str):
    """Inside decode-step runs: the keys and values a step's rows may see
    in the layers of `kind` over the HBM peak, over that kind's decode
    kernel's device time, in percent."""
    kv = _step_kv_tokens(reading).get(kind)
    ms = kernel_busy_ms(reading, module, kernel)
    if kv is None or ms is None or reading["peaks"] is None:
        return None
    cfg = reading["ctx"].cfg
    least = kv * work_moe.layer_counts(cfg)[kind] \
        * work_moe.kv_bytes_per_token(cfg) \
        / reading["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least / (ms * 1e-3)
