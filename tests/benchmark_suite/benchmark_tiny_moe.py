"""The tiny root of `benchmark_tiny` with the sparse, windowed decoder's
configuration, mix and cell beside the others, at sizes a CPU test can
hold: tiny widths, 8 layers in the published pattern S S S F, window 8,
chunks of 16, 8 experts with 2 a token. Files and manifest entries only;
the cell is held to the real cell's own limits."""
from __future__ import annotations

import json
import os

import benchmark_tiny
from benchmark import manifest

REAL_CELL = "mellum2-12b-a2.5b.generate.mixed8k-c64"
TINY_MOE = {
    "hidden_size": 32, "head_dim": 8, "num_attention_heads": 4,
    "num_key_value_heads": 2, "moe_intermediate_size": 16,
    "intermediate_size": 64, "num_experts": 8, "num_experts_per_tok": 2,
    "vocab_size": 128, "sliding_window": 8, "num_hidden_layers": 8,
    "max_position_embeddings": 63, "max_context": 63,
    # logits some units wide, as the real cell's are (see benchmark_tiny)
    "init_std": 0.25,
    # float32 where the real configuration states bfloat16: with 8 experts
    # and 2 a token one flipped choice moves half of a layer's output, so
    # at this size bfloat16 reads like the control (served_gap 1.1, five
    # layers of 32 wide); the control here is bfloat16, the nearest below
    "dtype": "float32",
    "engine": {"max_decode_batch": 2, "pack_bucket": 16,
               "kv_block_tokens": 4,
               "kv_max_blocks": {"full": 64, "sliding": 32},
               "queue_limit": 64},
}
# prompts 4-40: shorter than the window of 8, longer than it, longer than a
# chunk of 16 and than two
TINY_MIXED = {"kind": "generate_closed", "clients": 3, "pool": 96,
              "grid": 8, "order_seed": 29,
              "prompt_len": {"median": 12, "sigma": 0.8, "min": 4,
                             "max": 40},
              "max_new_tokens": 6, "ramp_seconds": 0.2, "http_pool": 4,
              "check_requests": 24}
LIMITS = manifest.data_file("cells", REAL_CELL)["limits"]


def add_to(man: manifest.Manifest) -> manifest.Manifest:
    """`man` (a root that `benchmark_tiny.make_root` made) with the tiny
    sparse decoder's files and entries added."""
    tmp, doc = man.root, man.doc
    cfg = manifest.data_file("configs", "mellum2-12b-a2.5b-instruct")
    cfg.update(TINY_MOE, name="tiny-moe")
    benchmark_tiny._dump(os.path.join(tmp, "benchmark", "configs",
                                      "tiny-moe.json"), cfg)
    doc["configs"].append({"name": "tiny-moe", "source": cfg["source"],
                           "file": "benchmark/configs/tiny-moe.json",
                           "reduced": [], "why": "tiny"})
    benchmark_tiny._dump(os.path.join(tmp, "benchmark", "traffic",
                                      "tiny-mixed.json"), TINY_MIXED)
    benchmark_tiny._dump(
        os.path.join(tmp, "benchmark", "cells", "tiny.moe.json"),
        {"trace_offset_s": 0.3, "trace_seconds": 1.0, "limits": LIMITS})
    doc["workloads"].append({"name": "tiny.moe", "config": "tiny-moe",
                             "traffic": "tiny-mixed", "chips": 1,
                             "why": "tiny sparse windowed decoder"})
    for m in doc["end_to_end"] + doc["per_layer"]:
        if REAL_CELL in m.get("workloads", ()):
            m["workloads"].append("tiny.moe")
    with open(os.path.join(tmp, "BENCHMARK.json"), "w") as f:
        json.dump(doc, f)
    return manifest.Manifest(tmp)
