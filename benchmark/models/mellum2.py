"""Builder of the Mellum2 configuration: the program's
`TransformerDecoder` set to the configuration's block (RMS norm, rotary
with a plain and a YaRN table, 32 query heads over 4 KV heads, 64 SwiGLU
experts with 8 a token, three sliding layers then a full one, untied
head, bfloat16), behind `ServingGateway`, holding the benchmark's
weights: made on the device, a jitted call a layer from
`fold_in(key, layer)`, by the reference's own functions, so that the
reference can make the same layer again when it needs it."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmark.models import seed_key
from benchmark.reference import mellum2 as ref


def make_weights(seed: int, cfg: dict):
    """What the reference compares with: the key and the configuration.
    It draws each layer again, one at a time (8 layers in float32 do not
    fit beside each other)."""
    return {"key": seed_key(seed), "cfg": cfg}


def build(cfg: dict, seed: int, chips: int = 1):
    from deeplearning4j_tpu.serving.decode import TransformerDecoder
    if chips != 1:
        raise ValueError("this configuration serves from one chip")
    key = seed_key(seed)
    layer = jax.jit(lambda k, li: ref.init_layer(k, li, cfg))
    params = jax.jit(lambda k: ref.init_outer(k, cfg))(key)
    params["layers"] = [layer(key, li)
                        for li in range(cfg["num_hidden_layers"])]
    dtype = jnp.dtype(cfg["dtype"])
    if dtype != jnp.bfloat16:   # the draw is bfloat16's values, cast up
        params = jax.tree_util.tree_map(lambda a: a.astype(dtype), params)
    rope = cfg["rope_parameters"]
    return TransformerDecoder(
        params=params, vocab=cfg["vocab_size"],
        layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        heads=cfg["num_attention_heads"],
        kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        ff=cfg["moe_intermediate_size"], max_context=cfg["max_context"],
        norm="rms", norm_eps=cfg["rms_norm_eps"], position="rotary",
        rope={"full": rope["full_attention"],
              "sliding": rope["sliding_attention"]},
        mlp="moe", experts=cfg["num_experts"],
        experts_per_token=cfg["num_experts_per_tok"],
        experts_held=ref.held(cfg), tied=cfg["tie_word_embeddings"],
        dtype=dtype, layer_types=ref.layer_kinds(cfg),
        window=cfg["sliding_window"], row_buckets="full")
