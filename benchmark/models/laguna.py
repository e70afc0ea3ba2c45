"""Builder of the Laguna-S-2.1 configuration: the program's
`TransformerDecoder` set to the configuration's block (RMS norm, query
heads by kind of layer over 8 KV heads, rotary on a kind's rotated width
with a plain and a YaRN table, a gate a head, a leading dense SwiGLU
layer, then 256-way softmax routing with 10 a token of which this chip
holds a share, a shared expert beside them, an untied head over the rows
of the vocabulary held here, bfloat16), behind `ServingGateway`, holding
the benchmark's weights: made on the device, a jitted call a layer from
`fold_in(key, layer)`, by the reference's own functions, so that the
reference can make the same layer again when it needs it."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmark.models import seed_key
from benchmark.reference import laguna as ref

GATES = {"per-head": "head"}


def make_weights(seed: int, cfg: dict):
    """What the reference compares with: the key and the configuration.
    It draws each layer again, one at a time."""
    return {"key": seed_key(seed), "cfg": cfg}


def build(cfg: dict, seed: int, chips: int = 1):
    from deeplearning4j_tpu.serving.decode import TransformerDecoder
    if chips != 1:
        raise ValueError("this configuration serves from one chip")
    dtype = jnp.dtype(cfg["dtype"])
    rope = cfg["rope_parameters"]
    kinds, ffns = ref.layer_kinds(cfg), ref.ffn_kinds(cfg)
    # the decoder first, the weights after: a program that lacks one of
    # these settings refuses here, at once, and not after the draw
    model = TransformerDecoder(
        params={}, vocab=cfg["vocab_size"],
        layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        heads=ref.heads_by_kind(cfg), kv_heads=cfg["num_key_value_heads"],
        head_dim=cfg["head_dim"], gate=GATES[cfg["gating"]],
        layer_types=kinds, window=cfg["sliding_window"],
        max_context=cfg["max_context"], norm="rms",
        norm_eps=cfg["rms_norm_eps"], position="rotary",
        rope={"full": rope["full_attention"],
              "sliding": rope["sliding_attention"]},
        mlp_types=ffns, dense_ff=cfg["intermediate_size"],
        ff=cfg["moe_intermediate_size"], experts=ref.router_width(cfg),
        experts_per_token=cfg["num_experts_per_tok"],
        experts_held=ref.held(cfg),
        shared_ff=cfg["shared_expert_intermediate_size"],
        router="softmax", route_scale=cfg["moe_routed_scaling_factor"],
        tied=cfg["tie_word_embeddings"], dtype=dtype, row_buckets="full")
    key = seed_key(seed)
    layer = {p: jax.jit(lambda k, li, p=p: ref.init_layer(k, li, cfg, p))
             for p in set(zip(kinds, ffns))}
    params = jax.jit(lambda k: ref.init_outer(k, cfg))(key)
    params["layers"] = [layer[p](key, li)
                        for li, p in enumerate(zip(kinds, ffns))]
    if dtype != jnp.bfloat16:   # the draw is bfloat16's values, cast up
        params = jax.tree_util.tree_map(lambda a: a.astype(dtype), params)
    model.params_tree = params
    return model
