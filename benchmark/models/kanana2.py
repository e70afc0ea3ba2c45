"""Builder of the kanana-2-30b-a3b configuration: the program's
`TransformerDecoder` set to the configuration's block (RMS norm, latent
attention with rotary on the rope part, a leading dense SwiGLU layer,
then 128 SwiGLU experts with 6 a token under the sigmoid router and a
shared expert beside them, untied head, bfloat16), behind
`ServingGateway`, holding the benchmark's weights: made on the device, a
jitted call a layer from `fold_in(key, layer)`, by the reference's own
functions, so that the reference can make the same layer again when it
needs it. The one thing done to them: the published rotary turns
interleaved pairs and the program turns halves, so the rope columns of
`wq` (each head's) and of `wkv_a` are taken evens first; q_pe and k_pe
are permuted alike and every score is the same sum."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.models import seed_key
from benchmark.reference import kanana2 as ref


def make_weights(seed: int, cfg: dict):
    """What the reference compares with: the key and the configuration.
    It draws each layer again, one at a time."""
    return {"key": seed_key(seed), "cfg": cfg}


def halves_from_pairs(lp: dict, cfg: dict) -> dict:
    """The layer with the rope columns of `wq` and `wkv_a` reordered from
    interleaved pairs (x0 y0 x1 y1 ...) to halves (x0 x1 ... y0 y1 ...)."""
    hh, rank = cfg["num_attention_heads"], cfg["kv_lora_rank"]
    nope, rope = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    order = np.concatenate([np.arange(0, rope, 2), np.arange(1, rope, 2)])
    head = np.concatenate([np.arange(nope), nope + order])
    q_cols = (np.arange(hh)[:, None] * (nope + rope) + head).reshape(-1)
    a_cols = np.concatenate([np.arange(rank), rank + order])
    return {**lp, "wq": lp["wq"][:, q_cols], "wkv_a": lp["wkv_a"][:, a_cols]}


def build(cfg: dict, seed: int, chips: int = 1):
    from deeplearning4j_tpu.serving.decode import TransformerDecoder
    if chips != 1:
        raise ValueError("this configuration serves from one chip")
    dtype = jnp.dtype(cfg["dtype"])
    # the decoder first, the weights after: a program that lacks one of
    # these settings refuses here, at once, and not after the draw
    model = TransformerDecoder(
        params={}, vocab=cfg["vocab_size"],
        layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        heads=cfg["num_attention_heads"], attention="latent",
        kv_lora_rank=cfg["kv_lora_rank"],
        qk_nope_head_dim=cfg["qk_nope_head_dim"],
        qk_rope_head_dim=cfg["qk_rope_head_dim"],
        v_head_dim=cfg["v_head_dim"], layer_types=("latent",),
        max_context=cfg["max_context"], norm="rms",
        norm_eps=cfg["rms_norm_eps"], position="rotary",
        rope={"latent": {"rope_theta": cfg["rope_theta"]}},
        mlp_types=ref.ffn_kinds(cfg), dense_ff=cfg["intermediate_size"],
        ff=cfg["moe_intermediate_size"], experts=cfg["n_routed_experts"],
        experts_per_token=cfg["num_experts_per_tok"],
        experts_held=ref.held(cfg),
        shared_ff=cfg["n_shared_experts"] * cfg["moe_intermediate_size"],
        router=cfg["scoring_func"],
        route_scale=cfg["routed_scaling_factor"],
        tied=cfg["tie_word_embeddings"], dtype=dtype, row_buckets="full")
    key = seed_key(seed)
    layer = {kind: jax.jit(lambda k, li, kind=kind: halves_from_pairs(
        ref.init_layer(k, li, cfg, kind), cfg)) for kind in ("dense", "moe")}
    params = jax.jit(lambda k: ref.init_outer(k, cfg))(key)
    params["layers"] = [layer[kind](key, li)
                        for li, kind in enumerate(ref.ffn_kinds(cfg))]
    if dtype != jnp.bfloat16:   # the draw is bfloat16's values, cast up
        params = jax.tree_util.tree_map(
            lambda a: a.astype(dtype) if a.dtype == jnp.bfloat16 else a,
            params)
    model.params_tree = params
    return model
