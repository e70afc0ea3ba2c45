"""Builder of the `resnet50-zoo` configuration: the program's own zoo
ResNet50 behind `ComputationGraph` (and `ParallelWrapper` on 4 chips),
with the benchmark's weights (made on the device, one jitted call from the
seed, in bfloat16) assigned in place of the program's own init."""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from benchmark.models import seed_key
from benchmark.reference import resnet50 as ref


def make_weights(seed: int, cfg: dict):
    dtype = jnp.dtype(cfg["dtype"])
    return jax.jit(lambda k: ref.init_weights(
        k, tuple(cfg["image"]), cfg["labels"], dtype))(seed_key(seed))


def build(cfg: dict, seed: int, chips: int = 1):
    """-> (net, fit_callable). `fit_callable(iterator)` is the entry a
    trainer calls: ComputationGraph.fit, or ParallelWrapper.fit on a
    data-parallel mesh when the cell holds more than one chip."""
    from deeplearning4j_tpu.models import ResNet50
    from deeplearning4j_tpu.nn.graph.graph import ComputationGraph
    conf = ResNet50(num_labels=cfg["labels"],
                    input_shape=tuple(cfg["image"])).conf()
    for node in conf.nodes.values():
        if node.is_layer() and node.layer.updater is not None:
            node.layer.updater = dataclasses.replace(
                node.layer.updater, learning_rate=cfg["learning_rate"])
    net = ComputationGraph(conf).init(dtype=jnp.dtype(cfg["dtype"]))
    weights = make_weights(seed, cfg)
    tree = dict(net.params_tree)
    for name, leaves in weights.items():
        have = tree[name]
        for leaf, v in leaves.items():
            if have[leaf].shape != v.shape or have[leaf].dtype != v.dtype:
                raise ValueError(
                    f"{name}/{leaf}: program has {have[leaf].shape} "
                    f"{have[leaf].dtype}, configuration {v.shape} {v.dtype}")
        tree[name] = dict(leaves)
    extra = [n for n, d in tree.items() if d and n not in weights]
    if extra:
        raise ValueError(f"program layers the reference lacks: {extra}")
    net.params_tree = tree
    if chips == 1:
        return net, lambda it: net.fit(it, epochs=1)
    from deeplearning4j_tpu.parallel import ParallelWrapper, data_parallel_mesh
    wrapper = ParallelWrapper(net, mesh=data_parallel_mesh(chips))
    return net, lambda it: wrapper.fit(it, epochs=1)
