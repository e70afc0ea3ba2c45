"""The tiny root of `benchmark_tiny` with the latent-attention decoder's
configuration, mix and cell beside the others, at sizes a CPU test can
hold: tiny widths in the published proportions (a latent wider than a
head, a rope part half a nope part), 3 layers (the leading dense one and
two sparse), chunks of 16, blocks of 4, 8 routed experts with 2 a token
and one shared. Files and manifest entries only; the cell is held to the
real cell's own limits."""
from __future__ import annotations

import json
import os

import benchmark_tiny
from benchmark import manifest

REAL_CELL = "kanana-2-30b-a3b.generate.long32k-c32"
REAL_CONFIG = "kanana-2-30b-a3b-instruct-2601"
TINY_MLA = {
    "hidden_size": 32, "num_attention_heads": 4, "num_key_value_heads": 4,
    "kv_lora_rank": 16, "qk_nope_head_dim": 8, "qk_rope_head_dim": 4,
    "qk_head_dim": 12, "head_dim": 4, "v_head_dim": 8,
    "intermediate_size": 64, "moe_intermediate_size": 16,
    "n_routed_experts": 8, "num_experts_per_tok": 2, "n_shared_experts": 1,
    "vocab_size": 128, "num_hidden_layers": 3,
    "max_position_embeddings": 63, "max_context": 63,
    # logits several times as wide as the real cell's (std 0.9 there): the
    # real cell's limit has to leave room over what one flipped choice of
    # an expert costs a bfloat16 program at the real size (`PERF.md`, PR
    # 33), and this cell's controls have to lie past that same limit
    "init_std": 1.25,
    # float32 where the real configuration states bfloat16: with 8 experts
    # and 2 a token one flipped choice moves half of a layer's routed
    # output, so at this size bfloat16 reads like the control; the control
    # here is bfloat16, the nearest below
    "dtype": "float32",
    "engine": {"max_decode_batch": 2, "pack_bucket": 16,
               "kv_block_tokens": 4, "kv_max_blocks": {"latent": 64},
               "queue_limit": 64},
}
# prompts 4-40: shorter than a chunk of 16, longer than it and than two,
# so later chunks read their cached latents
TINY_LONG = {"kind": "generate_closed", "clients": 3, "pool": 96,
             "grid": 8, "order_seed": 33,
             "prompt_len": {"median": 14, "sigma": 0.8, "min": 4,
                            "max": 40},
             "max_new_tokens": 6, "ramp_seconds": 0.2, "http_pool": 4,
             "check_requests": 24}
LIMITS = manifest.data_file("cells", REAL_CELL)["limits"]


def add_to(man: manifest.Manifest) -> manifest.Manifest:
    """`man` (a root that `benchmark_tiny.make_root` made) with the tiny
    latent-attention decoder's files and entries added."""
    tmp, doc = man.root, man.doc
    cfg = manifest.data_file("configs", REAL_CONFIG)
    cfg.update(TINY_MLA, name="tiny-mla")
    benchmark_tiny._dump(os.path.join(tmp, "benchmark", "configs",
                                      "tiny-mla.json"), cfg)
    doc["configs"].append({"name": "tiny-mla", "source": cfg["source"],
                           "file": "benchmark/configs/tiny-mla.json",
                           "reduced": [], "why": "tiny"})
    benchmark_tiny._dump(os.path.join(tmp, "benchmark", "traffic",
                                      "tiny-long.json"), TINY_LONG)
    benchmark_tiny._dump(
        os.path.join(tmp, "benchmark", "cells", "tiny.mla.json"),
        {"trace_offset_s": 0.3, "trace_seconds": 1.0, "limits": LIMITS})
    doc["workloads"].append({"name": "tiny.mla", "config": "tiny-mla",
                             "traffic": "tiny-long", "chips": 1,
                             "why": "tiny latent-attention decoder"})
    for m in doc["end_to_end"] + doc["per_layer"]:
        if REAL_CELL in m.get("workloads", ()):
            m["workloads"].append("tiny.mla")
    with open(os.path.join(tmp, "BENCHMARK.json"), "w") as f:
        json.dump(doc, f)
    return manifest.Manifest(tmp)
