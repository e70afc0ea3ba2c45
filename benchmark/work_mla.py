"""Operations and bytes that a latent-attention decoder with a leading
dense layer, routed experts and a shared expert needs, from the
configuration's shapes and from the program's own counts of what a run
touched. The yardstick's half of the cell's utilization and roofline
shares; never in the program.

Conventions as in `flops.py` and `work_moe.py`: a multiply-add is 2
operations; bytes are each operand read once in bfloat16. What is
counted is the least any implementation needs: a cached position is its
576 values (whatever lanes the program lays them on), attention in a
prefill is the published form's products over the visible pairs (a
query-key head of 192, a value head of 128: the absorbed form does 3.4
times as many), the up-projection of a latent is applied once a token,
and only experts that had a token are read.
"""
from __future__ import annotations

from typing import Dict

from benchmark import work_moe

ELEM = 2        # bytes of a bfloat16


def layer_counts(cfg: dict) -> Dict[str, int]:
    """Layers run, by feed-forward."""
    dense = min(cfg["first_k_dense_replace"], cfg["num_hidden_layers"])
    return {"dense": dense, "moe": cfg["num_hidden_layers"] - dense}


def attention_params(cfg: dict) -> int:
    """Wq, Wkv_a, Wkv_b and Wo of a layer."""
    d, hh = cfg["hidden_size"], cfg["num_attention_heads"]
    rank, nope = cfg["kv_lora_rank"], cfg["qk_nope_head_dim"]
    rope, vd = cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    return d * hh * (nope + rope) + d * (rank + rope) \
        + rank * hh * (nope + vd) + hh * vd * d


def dense_params(cfg: dict) -> int:
    return 3 * cfg["hidden_size"] * cfg["intermediate_size"]


def expert_params(cfg: dict) -> int:
    """One routed expert's three matrices."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def shared_params(cfg: dict) -> int:
    return cfg["n_shared_experts"] * expert_params(cfg)


def router_params(cfg: dict) -> int:
    return cfg["hidden_size"] * cfg["n_routed_experts"]


def head_params(cfg: dict) -> int:
    return cfg["hidden_size"] * cfg["vocab_size"]


def fixed_params(cfg: dict) -> int:
    """Every matrix a token passes whatever it is routed to: attention
    in all layers, the dense layers, the routers and shared experts."""
    n = layer_counts(cfg)
    return sum(n.values()) * attention_params(cfg) \
        + n["dense"] * dense_params(cfg) \
        + n["moe"] * (router_params(cfg) + shared_params(cfg))


def latent_bytes(cfg: dict) -> int:
    """A cached position in one layer: the latent and the rotated key."""
    return (cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]) * ELEM


def pair_flops(cfg: dict) -> int:
    """Attention's products for one query over one visible key in one
    layer, all heads, in the published (expanded) form."""
    return 2 * cfg["num_attention_heads"] * (
        cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
        + cfg["v_head_dim"])


def absorbed_pair_flops(cfg: dict) -> int:
    """The same in the absorbed form, which is what a decode step over
    the latent cache computes: every head over the whole entry, and its
    latent part again for the value."""
    return 2 * cfg["num_attention_heads"] * (
        2 * cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"])


def flops_per_token(cfg: dict, keys: float) -> float:
    """Active matrix FLOPs of one token through every layer and
    attention over `keys` visible keys a layer. The head is counted
    apart: a prompt's positions do not pass it."""
    n = layer_counts(cfg)
    matrices = fixed_params(cfg) + n["moe"] * cfg["num_experts_per_tok"] \
        * expert_params(cfg)
    return 2.0 * matrices + sum(n.values()) * pair_flops(cfg) * keys


def head_flops(cfg: dict) -> float:
    return 2.0 * head_params(cfg)


def mean_keys(traffic: dict) -> float:
    """Keys a position attends to in a layer, averaged over every
    position of the mix's requests (prompt and output)."""
    return work_moe.mean_keys(traffic, 1 << 40)["full"]


def decode_step_bytes(cfg: dict, experts_touched: float,
                      latent_positions: float) -> float:
    """Bytes one decode step must read: the matrices every token passes,
    the routed experts that had a token (`experts_touched`, summed over
    the sparse layers), the head, and the cached entries its rows see
    (`latent_positions`: in one layer)."""
    weights = fixed_params(cfg) + experts_touched * expert_params(cfg) \
        + head_params(cfg)
    return float(weights * ELEM + cfg["num_hidden_layers"]
                 * latent_positions * latent_bytes(cfg))


def latent_kernel_work(cfg: dict, latent_positions: float) -> Dict[str, float]:
    """The absorbed decode kernel over `latent_positions` entries (all
    its calls of a step together): bytes and operations."""
    return {"bytes": latent_positions * latent_bytes(cfg),
            "flops": latent_positions * absorbed_pair_flops(cfg)}
