"""Builder of a decoder configuration: the program's `TransformerDecoder`
at the configuration's published sizes behind `ServingGateway`, holding
the benchmark's weights (made on the device in one jitted call from the
seed, float32), and multiplying as the configuration states.

The configuration states float32. jax's default on the MXU rounds the
operands of a float32 product to bfloat16, so the builder sets
`jax_default_matmul_precision` to the configuration's `matmul_precision`
(`highest`: float32 products) before the program traces anything: the
program's `@`, its einsums and the dots inside its Pallas kernel all take
the process's default. With it `correct` tells float32 from the bfloat16
control; without it the two read alike (PERF.md section 6, PR 24).

The program's constructor draws every weight with numpy on the host (1.3 B
normals, most of a minute, and none of it serves a request), so the
builder constructs the class with no layers and a two-row vocabulary and
assigns the tree: params ride as an argument of its two jitted functions,
and the class documents a swap as a tree assignment. `PERF.md` lists the
constructor for the tracing issue."""
from __future__ import annotations

import jax

from benchmark.models import seed_key
from benchmark.reference import decoder as ref


def make_weights(seed: int, cfg: dict):
    return jax.jit(lambda k: ref.init_weights(k, cfg))(seed_key(seed))


def build(cfg: dict, seed: int, chips: int = 1):
    from deeplearning4j_tpu.serving.decode import TransformerDecoder
    if chips != 1:
        raise ValueError("the decoder serves from one chip")
    jax.config.update("jax_default_matmul_precision",
                      cfg["matmul_precision"])
    heads = cfg["num_attention_heads"]
    model = TransformerDecoder(
        vocab=2, layers=0, heads=heads,
        head_dim=cfg["hidden_size"] // heads, ff=cfg["ffn_dim"],
        max_context=cfg["max_context"], seed=0)
    model.vocab = cfg["vocab_size"]
    model.n_layers = cfg["num_hidden_layers"]
    model.params_tree = make_weights(seed, cfg)
    return model
