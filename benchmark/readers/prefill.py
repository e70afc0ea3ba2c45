"""Readers of a prefill chunk's attention work: the program's counts of
the query-key pairs its masks leave visible, by kind of layer and by the
arm that computes them (`serving_decode_prefill_pairs_total{kind, arm}`),
and of the pairs inside the tiles and key blocks the prefill kernel runs
(`serving_decode_prefill_pairs_run_total{kind}`), laid against the device
time of the prefill kernels and of the chunk's whole program in the
traced run, with the work from `benchmark/work_{moe,mla,laguna}.py`. A
configuration of no such kind, a program that has no such counter (the
parent of the PR that added them), and a trace that holds no chunk give
each reader nothing to read: it returns None."""
from __future__ import annotations

from benchmark import work_laguna, work_mla, work_moe
from benchmark.readers.device import _trace
from benchmark.readers.kernels import kernel_busy_ms

CHUNKS = "serving_decode_prefill_chunks_total"
TOKENS = "serving_decode_prefill_tokens_total"
PAIRS = "serving_decode_prefill_pairs_total{arm=%s,kind=%s}"
PAIRS_RUN = "serving_decode_prefill_pairs_run_total{kind=%s}"
KINDS = ("full", "sliding", "latent")


def _work(cfg):
    """(matrix FLOPs of a token through every layer at no keys, {kind:
    FLOPs of one visible pair in a layer of the kind, all heads}), or
    None for a configuration of another kind. A pair is QK^T and PV at
    the kind's query heads; latent attention's in the published form."""
    if "kv_lora_rank" in cfg:
        return work_mla.flops_per_token(cfg, 0.0), \
            {"latent": work_mla.pair_flops(cfg)}
    none = {"full": 0.0, "sliding": 0.0}
    if "num_attention_heads_per_layer" in cfg and "experts_held" in cfg:
        heads = {kind: hh for kind, _, hh in work_laguna.layers(cfg)}
        return work_laguna.flops_per_token(cfg, none), \
            {k: 4.0 * hh * cfg["head_dim"] for k, hh in heads.items()}
    if "num_experts" in cfg and "layer_types" in cfg:
        pair = 4.0 * cfg["num_attention_heads"] * cfg["head_dim"]
        return work_moe.flops_per_token(cfg, none), \
            {k: pair for k in work_moe.layer_counts(cfg)}
    return None


def _per_chunk(reading, name: str):
    """A counter's rise over the window, per prefill chunk; None where
    either is nought."""
    c = reading["probe"].counters
    chunks, n = c.get(CHUNKS, 0.0), c.get(name, 0.0)
    return n / chunks if chunks > 0 and n > 0 else None


def attention_roofline(reading, module: str, kernel: str, kind: str):
    """Inside runs of the chunk's program: the visible pairs of the parts
    that take the kernel in the layers of `kind`, a chunk, times a pair's
    FLOPs over the bf16 peak, over that kind's prefill kernel's device
    time a run, in percent."""
    work = _work(reading["ctx"].cfg)
    pairs = _per_chunk(reading, PAIRS % ("kernel", kind))
    ms = kernel_busy_ms(reading, module, kernel)
    if work is None or kind not in work[1] or pairs is None or ms is None \
            or reading["peaks"] is None:
        return None
    least = pairs * work[1][kind] / reading["peaks"]["bf16_flops"]
    return 100.0 * least / (ms * 1e-3)


def visible_pairs_share(reading):
    """Visible pairs of the parts that take the prefill kernel, of the
    pairs inside the blocks it runs, over every kind, in percent: the
    kernel's useful share of what it computes."""
    c = reading["probe"].counters
    seen = sum(c.get(PAIRS % ("kernel", k), 0.0) for k in KINDS)
    run = sum(c.get(PAIRS_RUN % k, 0.0) for k in KINDS)
    return 100.0 * seen / run if seen > 0 and run > 0 else None


def chunk_mfu(reading, module: str):
    """The least FLOPs a run of the chunk's program must do (its prompt
    positions through every layer's matrices, and its visible pairs by
    kind, both arms; not the head, which a chunk passes only for the
    last position of each prompt it finishes), over the device busy
    time of a run, over the bf16 peak, in percent."""
    work, tr = _work(reading["ctx"].cfg), _trace(reading)
    tokens = _per_chunk(reading, TOKENS)
    if work is None or tr is None or tokens is None \
            or reading["peaks"] is None:
        return None
    per_token, per_pair = work
    pairs = sum((_per_chunk(reading, PAIRS % (arm, kind)) or 0.0) * flops
                for kind, flops in per_pair.items()
                for arm in ("kernel", "dense"))
    busy, runs = tr.module_busy(tr.fullest(), module)
    if pairs <= 0 or not runs or busy <= 0:
        return None
    least = (tokens * per_token + pairs) / reading["peaks"]["bf16_flops"]
    return 100.0 * least * runs / busy
