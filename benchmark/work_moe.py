"""Operations and bytes that a sparse-expert decoder with window and
full layers needs, from the configuration's shapes and from the
program's own counts of what a run touched. The yardstick's half of the
new cell's utilization and roofline shares; never in the program.

Conventions as in `flops.py`: a multiply-add is 2 operations; bytes are
each operand read once, in the type the configuration serves (bfloat16:
2 bytes). A roofline's bytes are the least any implementation must read:
only the experts that had a token, only the keys a row may see.
"""
from __future__ import annotations

import math
from statistics import NormalDist
from typing import Dict

ELEM = 2        # bytes of a bfloat16


def layer_counts(cfg: dict) -> Dict[str, int]:
    """Layers run, by kind."""
    kinds = [t.split("_")[0]
             for t in cfg["layer_types"][:cfg["num_hidden_layers"]]]
    return {k: kinds.count(k) for k in ("full", "sliding")}


def attention_params(cfg: dict) -> int:
    """The four attention matrices of a layer."""
    d, dh = cfg["hidden_size"], cfg["head_dim"]
    return 2 * d * cfg["num_attention_heads"] * dh \
        + 2 * d * cfg["num_key_value_heads"] * dh


def expert_params(cfg: dict) -> int:
    """One expert's three matrices."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def router_params(cfg: dict) -> int:
    return cfg["hidden_size"] * cfg["num_experts"]


def head_params(cfg: dict) -> int:
    return cfg["hidden_size"] * cfg["vocab_size"]


def kv_bytes_per_token(cfg: dict) -> int:
    """A cached position's key and value in one layer."""
    return 2 * cfg["num_key_value_heads"] * cfg["head_dim"] * ELEM


def flops_per_token(cfg: dict, keys: Dict[str, float]) -> float:
    """Active matrix FLOPs of one token through every layer (the four
    attention matrices, the router, the experts a token takes) and
    attention's QK^T and PV over `keys[kind]` keys in a layer of each
    kind. The head is counted apart: a prompt's positions do not pass
    it."""
    n = layer_counts(cfg)
    per_layer = 2.0 * (attention_params(cfg) + router_params(cfg)
                       + cfg["num_experts_per_tok"] * expert_params(cfg))
    q_width = cfg["num_attention_heads"] * cfg["head_dim"]
    return sum(n[k] * (per_layer + 4.0 * q_width * keys[k]) for k in n)


def head_flops(cfg: dict) -> float:
    return 2.0 * head_params(cfg)


def mean_keys(traffic: dict, window: int) -> Dict[str, float]:
    """Keys a position attends to, averaged over every position of the
    mix's requests (prompt and output): position p sees p + 1 keys in a
    full layer and min(p + 1, window) in a sliding one. The mix's sizes
    are its quantile grid, as its generator draws them."""
    p, grid = traffic["prompt_len"], int(traffic.get("grid",
                                                     traffic["pool"]))
    nd = NormalDist(math.log(p["median"]), p["sigma"])
    total = {"full": 0.0, "sliding": 0.0}
    positions = 0
    for i in range(grid):
        n = int(min(p["max"], max(p["min"], round(math.exp(
            nd.inv_cdf((i + 0.5) / grid)))))) + int(traffic["max_new_tokens"])
        positions += n
        total["full"] += n * (n + 1) / 2
        w = min(n, window)
        total["sliding"] += w * (w + 1) / 2 + (n - w) * window
    return {k: v / positions for k, v in total.items()}


def decode_step_bytes(cfg: dict, experts_touched: float,
                      kv_tokens: Dict[str, float]) -> float:
    """Bytes one decode step must read: every layer's attention and
    router matrices, the experts that had a token (`experts_touched`,
    summed over the layers), the head, and each row's visible keys and
    values (`kv_tokens[kind]`: keys the step's rows see in one layer of
    the kind)."""
    n = layer_counts(cfg)
    weights = sum(n.values()) * (attention_params(cfg) + router_params(cfg)) \
        + experts_touched * expert_params(cfg) + head_params(cfg)
    kv = sum(n[k] * kv_tokens.get(k, 0.0) for k in n) \
        * kv_bytes_per_token(cfg)
    return float(weights * ELEM + kv)
