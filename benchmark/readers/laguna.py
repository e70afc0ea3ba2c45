"""Readers for a decoder that holds a share of its routed experts, with
query heads by kind of layer, a gate, a dense layer and a shared expert:
the program's routing and key counters over the window, laid against the
device time of the decode step in the traced run, with the work from
`benchmark/work_laguna.py`. A configuration of another kind, and a
program that has no such counter, give each reader nothing to read: it
returns None."""
from __future__ import annotations

from benchmark import work_laguna
from benchmark.readers.device import _trace
from benchmark.readers.moe import TOUCHED, _per_step, _step_kv_tokens


def _cfg(reading):
    """The configuration, if it names its heads a layer and a share."""
    cfg = reading["ctx"].cfg
    return cfg if "num_attention_heads_per_layer" in cfg \
        and "experts_held" in cfg else None


def step_mfu(reading):
    """Active matrix and attention FLOPs of the tokens the window
    processed (prompt and output; the head for output tokens only) per
    second over the bf16 peak, in percent: the share of the whole
    step, at the share of the experts held here."""
    cfg, w = _cfg(reading), reading["window"]
    if cfg is None or reading["peaks"] is None \
            or w["tokens"] + w["prompt_tokens"] <= 0:
        return None
    keys = work_laguna.mean_keys(reading["ctx"].traffic,
                                 cfg["sliding_window"])
    flops = (w["tokens"] + w["prompt_tokens"]) \
        * work_laguna.flops_per_token(cfg, keys) \
        + w["tokens"] * work_laguna.head_flops(cfg)
    return 100.0 * flops / (w["t1"] - w["t0"]) \
        / reading["peaks"]["bf16_flops"]


def decode_step_roofline(reading, module: str):
    """Bytes a decode step must read over the HBM peak, over the device
    busy time of a step's run, in percent."""
    cfg, tr = _cfg(reading), _trace(reading)
    touched, kv = _per_step(reading, TOUCHED), _step_kv_tokens(reading)
    if cfg is None or tr is None or reading["peaks"] is None \
            or touched is None or not kv:
        return None
    busy, runs = tr.module_busy(tr.fullest(), module)
    if not runs or busy <= 0:
        return None
    least = work_laguna.decode_step_bytes(cfg, touched, kv) \
        / reading["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least * runs / busy


def held_touched_share(reading):
    """Held experts with a token, of the experts held in the sparse
    layers, per step, in percent."""
    cfg, touched = _cfg(reading), _per_step(reading, TOUCHED)
    if cfg is None or touched is None:
        return None
    sparse = sum(ffn == "moe" for _, ffn, _ in work_laguna.layers(cfg))
    return 100.0 * touched / (sparse * len(cfg["experts_held"]))
