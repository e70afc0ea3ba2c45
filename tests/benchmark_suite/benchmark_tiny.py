"""A copy of the benchmark's manifest and data files at sizes a CPU test
can hold, in a temporary root. Adds files and manifest entries only: the
harness under `benchmark/` is the one under test, unedited - which is also
the proof that a new cell, configuration, traffic mix or per-layer metric
needs nothing else."""
from __future__ import annotations

import json
import os
import shutil

from benchmark import manifest

TINY_RESNET = {
    "image": [32, 32, 3], "labels": 10, "dtype": "float32",
    "sample": "one image of 32x32x3",
}
TINY_DECODER = {
    "hidden_size": 64, "ffn_dim": 128, "num_hidden_layers": 2,
    "num_attention_heads": 4, "vocab_size": 128, "word_embed_proj_dim": 64,
    "max_position_embeddings": 63, "max_context": 63,
    # logits some units wide, as the real cell's are: a token that a lower
    # precision picks wrongly then lies as far below the best as it does there
    "init_std": 0.25,
    "engine": {"max_decode_batch": 2, "pack_bucket": 32,
               "kv_block_tokens": 8, "kv_max_blocks": 64, "queue_limit": 64},
}
TINY_TRAFFIC = {
    "tiny-ring": {"kind": "fit_ring", "rows": 8, "ring": 4,
                  "follow_steps": 3},
    "tiny-closed": {"kind": "generate_closed", "clients": 3, "pool": 96,
                    "prompt_len": {"median": 8, "sigma": 0.4, "min": 4,
                                   "max": 16},
                    "max_new_tokens": 6, "ramp_seconds": 0.2,
                    "http_pool": 4, "check_requests": 40},
    "tiny-open": {"kind": "generate_open", "rate": 12.0, "pool": 96,
                  "burst": {"every_s": 0.5, "size": 3}, "senders": 8,
                  "prompt_len": {"median": 8, "sigma": 0.4, "min": 4,
                                 "max": 16},
                  "max_new_tokens": 6, "ramp_seconds": 0.2,
                  "http_pool": 8, "check_requests": 6},
}
# The tiny cells are held to the real cells' own limits, but for two of the
# fit cell's. At 8 rows of 32x32 the first loss is steady too, and the
# half-batch fault, which moves it, is read at that size: one limit more.
# And stage 5 runs at 1x1 pixels there, so BatchNorm's variance is over 8
# values and its worst leaf swings by 0.03-0.04 even in float32 (five
# seeds): the fault that number is for, a state left unchanged, reads 1.
TINY_FIT_LIMITS = {"loss1_gap": 0.01, "bn_state_gap": 0.2}
FIT_LIMITS = {**manifest.data_file("cells", "resnet50.fit.b512")["limits"],
              **TINY_FIT_LIMITS}
GENERATE_LIMITS = manifest.data_file(
    "cells", "decoder-at-opt-1.3b.generate.short-c16")["limits"]
TINY_CELLS = {
    "tiny.fit": {"config": "tiny-resnet", "traffic": "tiny-ring",
                 "chips": 1, "why": "tiny fit for a CPU test",
                 "trace_offset_s": 0.2, "trace_seconds": 1.0,
                 "limits": FIT_LIMITS},
    "tiny.fit.dp4": {"config": "tiny-resnet", "traffic": "tiny-ring",
                     "chips": 4, "why": "tiny data-parallel fit",
                     "trace_offset_s": 0.2, "trace_seconds": 1.0,
                     "limits": FIT_LIMITS},
    "tiny.closed": {"config": "tiny-decoder", "traffic": "tiny-closed",
                    "chips": 1, "why": "tiny closed loop for a CPU test",
                    "trace_offset_s": 0.3, "trace_seconds": 1.0,
                    "limits": GENERATE_LIMITS},
    "tiny.open": {"config": "tiny-decoder", "traffic": "tiny-open",
                  "chips": 1, "why": "tiny open loop for a CPU test",
                  "trace_offset_s": 0.3, "trace_seconds": 1.0,
                  "limits": GENERATE_LIMITS},
}


def _dump(path, obj):
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(obj, f)


def make_root(tmp: str) -> manifest.Manifest:
    """A root holding the real manifest's metrics and readers, and tiny
    configurations, mixes and cells beside them."""
    real = manifest.Manifest()
    shutil.copytree(os.path.join(manifest.ROOT, "benchmark",
                                 "layer_metrics"),
                    os.path.join(tmp, "benchmark", "layer_metrics"))
    doc = json.loads(json.dumps(real.doc))
    for name, base, over in (("tiny-resnet", "resnet50-zoo", TINY_RESNET),
                             ("tiny-decoder", "decoder-at-opt-1.3b",
                              TINY_DECODER)):
        cfg = manifest.data_file("configs", base)
        cfg.update(over, name=name)
        _dump(os.path.join(tmp, "benchmark", "configs", name + ".json"), cfg)
        doc["configs"].append({"name": name, "source": cfg["source"],
                               "file": f"benchmark/configs/{name}.json",
                               "reduced": [], "why": "tiny"})
    for name, t in TINY_TRAFFIC.items():
        _dump(os.path.join(tmp, "benchmark", "traffic", name + ".json"), t)
    fit = {"tiny.fit", "tiny.fit.dp4"}
    for name, c in TINY_CELLS.items():
        _dump(os.path.join(tmp, "benchmark", "cells", name + ".json"),
              {k: v for k, v in c.items()
               if k not in ("config", "traffic", "chips", "why")})
        doc["workloads"].append({"name": name, **{
            k: c[k] for k in ("config", "traffic", "chips", "why")}})
        e2e = ("train_samples_per_s",) if name in fit else \
            ("generate_tokens_per_s", "request_p95_ms")
        for m in doc["end_to_end"] + doc["per_layer"]:
            if "workloads" in m and (m["name"] in e2e
                                     or m.get("moves") in e2e):
                m["workloads"].append(name)
    _dump(os.path.join(tmp, "BENCHMARK.json"), doc)
    return manifest.Manifest(tmp)
