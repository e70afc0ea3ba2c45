"""Operations and bytes that the Laguna-S-2.1 block needs at the share
of a deployment this chip holds, from the configuration's shapes and from
the program's own counts of what a run touched. The yardstick's half of
the cell's utilization and roofline shares; never in the program.

What differs from `work_moe.py`: query heads by kind of layer (and so
`wq`, `wo` and attention's products), a gate a head, a leading dense
layer, a shared expert, a HELD share of the routed experts (a token's 10
choices fall on the experts held here in proportion held / published)
and a sliced head. Conventions as there: a multiply-add is 2 operations;
bytes are each operand read once in bfloat16; a roofline's bytes are the
least any implementation must read: only the held experts that had a
token, only the keys a row may see.
"""
from __future__ import annotations

from typing import Dict

from benchmark import work_moe

ELEM = work_moe.ELEM
layer_counts = work_moe.layer_counts
kv_bytes_per_token = work_moe.kv_bytes_per_token
expert_params = work_moe.expert_params
head_params = work_moe.head_params      # over the rows held: `vocab_size`
mean_keys = work_moe.mean_keys


def layers(cfg: dict):
    """(kind, feed-forward, query heads) of each layer that is run."""
    n = cfg["num_hidden_layers"]
    return [(t.split("_")[0], "dense" if f == "dense" else "moe", hh)
            for t, f, hh in zip(cfg["layer_types"][:n],
                                cfg["mlp_layer_types"][:n],
                                cfg["num_attention_heads_per_layer"][:n])]


def attention_params(cfg: dict, heads: int) -> int:
    """A layer's attention matrices at `heads` query heads: wq and wo,
    wk and wv, and the gate's one column a head."""
    d, dh = cfg["hidden_size"], cfg["head_dim"]
    return 2 * d * heads * dh + 2 * d * cfg["num_key_value_heads"] * dh \
        + d * heads


def dense_params(cfg: dict) -> int:
    return 3 * cfg["hidden_size"] * cfg["intermediate_size"]


def shared_params(cfg: dict) -> int:
    return 3 * cfg["hidden_size"] * cfg["shared_expert_intermediate_size"]


def router_params(cfg: dict) -> int:
    return cfg["hidden_size"] * cfg["num_experts_published"]


def held_share(cfg: dict) -> float:
    """The part of a token's choices that falls on the experts held
    here, routing even over the shares."""
    return len(cfg["experts_held"]) / cfg["num_experts_published"]


def always_read_params(cfg: dict) -> int:
    """Parameters every token of a step passes whatever it chooses: each
    layer's attention and gate, the dense layer, the routers, the shared
    experts."""
    total = 0
    for _, ffn, hh in layers(cfg):
        total += attention_params(cfg, hh)
        total += dense_params(cfg) if ffn == "dense" \
            else router_params(cfg) + shared_params(cfg)
    return total


def flops_per_token(cfg: dict, keys: Dict[str, float]) -> float:
    """Active matrix FLOPs of one token through the layers here: what it
    always passes, the held experts' share of its 10 choices, and
    attention's QK^T and PV over `keys[kind]` keys at the kind's heads.
    The head is counted apart: a prompt's positions do not pass it."""
    routed = cfg["num_experts_per_tok"] * held_share(cfg) \
        * expert_params(cfg)
    total = 2.0 * always_read_params(cfg)
    for kind, ffn, hh in layers(cfg):
        total += 4.0 * hh * cfg["head_dim"] * keys[kind]
        if ffn == "moe":
            total += 2.0 * routed
    return total


def head_flops(cfg: dict) -> float:
    return 2.0 * head_params(cfg)


def decode_step_bytes(cfg: dict, experts_touched: float,
                      kv_tokens: Dict[str, float]) -> float:
    """Bytes one decode step must read: what every token passes, the
    held experts that had a token (`experts_touched`, summed over the
    sparse layers), the sliced head, and each row's visible keys and
    values (`kv_tokens[kind]`: keys the step's rows see in one layer of
    the kind)."""
    n = layer_counts(cfg)
    weights = always_read_params(cfg) \
        + experts_touched * expert_params(cfg) + head_params(cfg)
    kv = sum(n[k] * kv_tokens.get(k, 0.0) for k in n) \
        * kv_bytes_per_token(cfg)
    return float(weights * ELEM + kv)
