"""Readers for a latent-attention decoder with a leading dense layer,
routed experts and a shared expert: the program's routing, cache and
position counters over the window, laid against the device time of the
decode step and of its attention kernel in the traced run, with the work
from `benchmark/work_mla.py`. A configuration of another kind, and a
program that has no such counter (the parent of the PR that added
them), give each reader nothing to read: it returns None."""
from __future__ import annotations

from benchmark import work_mla
from benchmark.readers.device import _trace
from benchmark.readers.kernels import kernel_busy_ms
from benchmark.readers.moe import TOUCHED, _per_step

LATENT_TOKENS = "serving_decode_kv_tokens_total{kind=latent}"
LATENT_BLOCKS = "serving_kv_block_steps_total{kind=latent}"


def _cfg(reading):
    """The configuration, if it is a latent-attention one."""
    cfg = reading["ctx"].cfg
    return cfg if "kv_lora_rank" in cfg else None


def step_mfu(reading):
    """Active matrix and attention FLOPs of the tokens the window
    processed (prompt and output; the head for output tokens only) per
    second over the bf16 peak, in percent: the share of the whole
    step."""
    cfg, w = _cfg(reading), reading["window"]
    if cfg is None or reading["peaks"] is None \
            or w["tokens"] + w["prompt_tokens"] <= 0:
        return None
    keys = work_mla.mean_keys(reading["ctx"].traffic)
    flops = (w["tokens"] + w["prompt_tokens"]) \
        * work_mla.flops_per_token(cfg, keys) \
        + w["tokens"] * work_mla.head_flops(cfg)
    return 100.0 * flops / (w["t1"] - w["t0"]) \
        / reading["peaks"]["bf16_flops"]


def decode_step_roofline(reading, module: str):
    """Bytes a decode step must read over the HBM peak, over the device
    busy time of a step's run, in percent."""
    cfg, tr = _cfg(reading), _trace(reading)
    touched = _per_step(reading, TOUCHED)
    seen = _per_step(reading, LATENT_TOKENS)
    if cfg is None or tr is None or reading["peaks"] is None \
            or touched is None or seen is None:
        return None
    busy, runs = tr.module_busy(tr.fullest(), module)
    if not runs or busy <= 0:
        return None
    least = work_mla.decode_step_bytes(cfg, touched, seen) \
        / reading["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least * runs / busy


def decode_attention_roofline(reading, module: str, kernel: str):
    """Inside decode-step runs: the larger of the cached entries' bytes
    over the HBM peak and the absorbed products' operations over the
    bf16 peak, over the kernel's device time, in percent."""
    cfg = _cfg(reading)
    seen = _per_step(reading, LATENT_TOKENS)
    ms = kernel_busy_ms(reading, module, kernel)
    if cfg is None or seen is None or ms is None \
            or reading["peaks"] is None:
        return None
    work = work_mla.latent_kernel_work(cfg, seen * cfg["num_hidden_layers"])
    least = max(work["bytes"] / reading["peaks"]["hbm_bytes_per_s"],
                work["flops"] / reading["peaks"]["bf16_flops"])
    return 100.0 * least / (ms * 1e-3)


def latent_blocks_share(reading):
    """Blocks the latent cache held, a step, of the blocks its arena
    has, in percent: how full the cache the cell reserves is."""
    cfg, held = _cfg(reading), _per_step(reading, LATENT_BLOCKS)
    if cfg is None or held is None:
        return None
    return 100.0 * held / cfg["engine"]["kv_max_blocks"]["latent"]


def routed_touched_share(reading):
    """Routed experts with a token, of all routed experts of the sparse
    layers, per step, in percent."""
    cfg, touched = _cfg(reading), _per_step(reading, TOUCHED)
    if cfg is None or touched is None:
        return None
    return 100.0 * touched / (work_mla.layer_counts(cfg)["moe"]
                              * cfg["n_routed_experts"])
