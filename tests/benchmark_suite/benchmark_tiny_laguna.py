"""The tiny root of `benchmark_tiny` with the configuration, mix and cell
of the decoder that holds a share of its experts beside the others, at
sizes a CPU test can hold: tiny widths in the published shape (6 query
heads on a full layer and 10 on a sliding one over 2 KV heads: groups 3
and 5; half of a full layer's head turned; a gate a head), 6 layers F S S
S F S of which the first is dense, window 8, chunks of 16, blocks of 4,
2 of 8 routed experts held with 3 a token and one shared, a vocabulary
of 96 rows standing for a slice. Files and manifest entries only; the
cell is held to the real cell's own limits."""
from __future__ import annotations

import json
import os

import benchmark_tiny
from benchmark import manifest

REAL_CELL = "laguna-s-2.1.generate.out1k-c64"
REAL_CONFIG = "laguna-s-2.1"
TINY_LAGUNA = {
    "hidden_size": 32, "head_dim": 8, "num_key_value_heads": 2,
    "num_attention_heads": 6,
    "num_attention_heads_per_layer": [6, 10, 10, 10] * 12,
    "intermediate_size": 64, "moe_intermediate_size": 16,
    "shared_expert_intermediate_size": 16, "num_experts_published": 8,
    "num_experts": 2, "experts_held": [2, 5], "num_experts_per_tok": 3,
    "vocab_size": 96, "vocab_size_published": 384, "sliding_window": 8,
    "num_hidden_layers": 6, "max_position_embeddings": 63,
    "max_context": 63,
    # logits several times as wide as the real cell's: the real cell's
    # limit leaves room over what one flipped choice of an expert costs
    # a bfloat16 program at the real size (`PERF.md`, PR 35), and this
    # cell's controls have to lie past that same limit
    "init_std": 1.0,
    # float32 where the real configuration states bfloat16: with 8
    # experts and 3 a token one flipped choice moves a third of a layer's
    # routed output, so at this size bfloat16 reads like the control;
    # the control here is bfloat16, the nearest below
    "dtype": "float32",
    "engine": {"max_decode_batch": 2, "pack_bucket": 16,
               "kv_block_tokens": 4,
               "kv_max_blocks": {"full": 64, "sliding": 32},
               "queue_limit": 64},
}
# prompts 4-40: shorter than the window of 8, longer than it, longer than
# a chunk of 16 and than two; 10 tokens out, more than the window
TINY_OUT = {"kind": "generate_closed", "clients": 3, "pool": 96,
            "grid": 8, "order_seed": 35,
            "prompt_len": {"median": 12, "sigma": 0.8, "min": 4,
                           "max": 40},
            "max_new_tokens": 10, "ramp_seconds": 0.2, "http_pool": 4,
            "check_requests": 24}
LIMITS = manifest.data_file("cells", REAL_CELL)["limits"]


def add_to(man: manifest.Manifest) -> manifest.Manifest:
    """`man` (a root that `benchmark_tiny.make_root` made) with the tiny
    share-holding decoder's files and entries added."""
    tmp, doc = man.root, man.doc
    cfg = manifest.data_file("configs", REAL_CONFIG)
    cfg.update(TINY_LAGUNA, name="tiny-laguna")
    benchmark_tiny._dump(os.path.join(tmp, "benchmark", "configs",
                                      "tiny-laguna.json"), cfg)
    doc["configs"].append({"name": "tiny-laguna", "source": cfg["source"],
                           "file": "benchmark/configs/tiny-laguna.json",
                           "reduced": [], "why": "tiny"})
    benchmark_tiny._dump(os.path.join(tmp, "benchmark", "traffic",
                                      "tiny-out.json"), TINY_OUT)
    benchmark_tiny._dump(
        os.path.join(tmp, "benchmark", "cells", "tiny.laguna.json"),
        {"trace_offset_s": 0.3, "trace_seconds": 1.0, "limits": LIMITS})
    doc["workloads"].append({"name": "tiny.laguna", "config": "tiny-laguna",
                             "traffic": "tiny-out", "chips": 1,
                             "why": "tiny decoder holding a share"})
    for m in doc["end_to_end"] + doc["per_layer"]:
        if REAL_CELL in m.get("workloads", ()):
            m["workloads"].append("tiny.laguna")
    with open(os.path.join(tmp, "BENCHMARK.json"), "w") as f:
        json.dump(doc, f)
    return manifest.Manifest(tmp)
