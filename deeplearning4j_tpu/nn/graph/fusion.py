"""Sibling-convolution branch fusion for ComputationGraph configs.

Inception-style blocks (reference zoo/model/GoogLeNet.java:83-180,
Szegedy et al.) fan one activation out into several small parallel
convolutions: every `_inception` block's cnn1/cnn2/cnn3 are 1×1
ConvolutionLayers reading the SAME input vertex. On TPU that shape is
doubly wasteful: the [B,H,W,C] activation is read from HBM once per
branch, and each small-n_out contraction underfills the 128-lane MXU
(round-5 profile: GoogLeNet's conv fusions run at 1.24× their byte
bound, docs/perf_googlenet.md). Because the branches share input,
geometry, and activation, they are algebraically ONE convolution whose
kernel is the channel-concatenation of the branch kernels:

    conv(x, W1) ++ conv(x, W2) ++ conv(x, W3)  ==  conv(x, W1++W2++W3)

(channel concat on the HWIO output axis; bias and elementwise activation
distribute over the concat). This module rewrites a built
ComputationGraphConfiguration accordingly: the N sibling layer nodes
become one fused ConvolutionLayer node plus N SubsetVertex slices that
KEEP the original node names, so downstream consumers, serde round-trips
and network_outputs are untouched. `fuse_params`/`unfuse_params` move
existing params / optimizer state across the boundary exactly (pure
concat/slice — fwd and bwd stay numerically identical to the unfused
graph), and `fuse_graph` applies the whole transform to an initialized
ComputationGraph.

Exactness gates (a group is only fused when the rewrite is provably the
same math): identical conv geometry + activation + regularization +
updater config, per-element gradient-normalization-free updaters only
(a per-layer norm would couple the branches through the concat), no
dropout (branch dropout draws per-node rng), no preprocessor, not
frozen-mixed, not a network output. Everything else is left alone and
counted as rejected in `sibling_conv_fusion_total{outcome=}`.

This sibling-merge machinery is also the substrate ROADMAP item 3 names
for multi-model serving batching (docs/serving.md): co-served models
with shared-input heads batch through the same concat-then-slice
rewrite.
"""
from __future__ import annotations

import copy
from dataclasses import dataclass
from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp

from ...utils import serde
from ..conf.graph_conf import ComputationGraphConfiguration, GraphNode, \
    _toposort
from ..layers.convolution import ConvolutionLayer
from ..layers.core import DenseLayer
from ..updaters import GradientNormalization
from .vertices import MergeVertex, SubsetVertex


@dataclass(frozen=True)
class FusionGroup:
    """One fused sibling set: `members` (original node names, in topo
    order) now read `fused_name` through SubsetVertex slices of width
    `n_outs[i]` starting at `offsets[i]`."""

    fused_name: str
    input: str
    members: Tuple[str, ...]
    n_outs: Tuple[int, ...]

    @property
    def offsets(self) -> Tuple[int, ...]:
        out, off = [], 0
        for n in self.n_outs:
            out.append(off)
            off += n
        return tuple(out)


def _count_fusion(outcome: str, n: int = 1) -> None:
    from ...optimize.metrics import registry
    registry().counter(
        "sibling_conv_fusion_total",
        "Sibling-conv fusion pass decisions (groups fused / candidates "
        "rejected)",
    ).labels(outcome=outcome).inc(n)


def register_metrics() -> None:
    """Pre-register the fusion counter family at 0."""
    from ...optimize.metrics import registry
    fam = registry().counter(
        "sibling_conv_fusion_total",
        "Sibling-conv fusion pass decisions (groups fused / candidates "
        "rejected)")
    for outcome in ("fused", "rejected"):
        fam.labels(outcome=outcome)


def _fusion_key(layer):
    """Everything that must MATCH for the concat rewrite to be exact.
    Serde JSON covers nested configs (updater, dist) without bespoke
    equality. Conv siblings additionally match on the full spatial
    geometry; dense siblings (the multi-model serving heads) need only
    the shared contraction shape."""
    base = (
        type(layer).__name__,
        layer.n_in, layer.activation,
        layer.l1, layer.l2, layer.l1_bias, layer.l2_bias,
        layer.frozen,
        serde.to_json(layer.updater) if layer.updater else None,
        serde.to_json(layer.dist) if layer.dist else None,
        layer.weight_init,
    )
    if isinstance(layer, ConvolutionLayer):
        base += (
            tuple(layer.kernel_size), tuple(layer.stride),
            tuple(layer.padding), tuple(layer.dilation),
            layer._mode().value, layer.conv_algo,
        )
    return base


# Strict types only: OutputLayer subclasses DenseLayer but carries a
# loss head whose training walk differs — excluded by `type(...) is`.
_FUSIBLE_TYPES = (ConvolutionLayer, DenseLayer)


def _fusible(node: GraphNode, name: str,
             conf: ComputationGraphConfiguration) -> bool:
    if not node.is_layer() or type(node.layer) not in _FUSIBLE_TYPES:
        return False
    if len(node.inputs) != 1 or node.preprocessor is not None:
        return False
    if name in conf.network_outputs:
        return False
    layer = node.layer
    if layer.n_out <= 0:
        return False  # unbuilt config; nothing to size the slices with
    if layer.dropout_rate:  # branch dropout draws per-node rng
        return False
    gn = layer.gradient_normalization
    if gn is not None and gn != GradientNormalization.NONE:
        return False  # per-layer norms don't distribute over the concat
    return True


def find_sibling_conv_groups(conf: ComputationGraphConfiguration
                             ) -> List[FusionGroup]:
    """Detect same-input sibling ConvolutionLayers whose fusion is exact.
    Members are grouped by (input, fusion key) in topo order; singleton
    groups are not fusion candidates."""
    buckets: Dict[tuple, List[str]] = {}
    for name in conf.topo_order:
        node = conf.nodes[name]
        if _fusible(node, name, conf):
            buckets.setdefault((node.inputs[0],) + _fusion_key(node.layer),
                               []).append(name)
    groups = []
    for key, members in buckets.items():
        if len(members) < 2:
            continue
        fused_name = "+".join(members)
        if fused_name in conf.nodes or fused_name in conf.network_inputs:
            _count_fusion("rejected", len(members))
            continue
        groups.append(FusionGroup(
            fused_name=fused_name, input=key[0], members=tuple(members),
            n_outs=tuple(conf.nodes[m].layer.n_out for m in members)))
    return groups


def fuse_sibling_convs(conf: ComputationGraphConfiguration
                       ) -> Tuple[ComputationGraphConfiguration,
                                  List[FusionGroup]]:
    """Return (fused config, groups). The input config is not mutated;
    with no fusible groups the clone comes back unchanged. The fused
    config round-trips through serde like any other (ConvolutionLayer +
    SubsetVertex are both registered)."""
    new = conf.clone()
    groups = find_sibling_conv_groups(new)
    for grp in groups:
        proto = new.nodes[grp.members[0]].layer
        fused_layer = copy.deepcopy(proto)
        fused_layer.n_out = sum(grp.n_outs)
        fused_layer.name = grp.fused_name
        new.nodes[grp.fused_name] = GraphNode(inputs=[grp.input],
                                              layer=fused_layer)
        for m, n, off in zip(grp.members, grp.n_outs, grp.offsets):
            new.nodes[m] = GraphNode(
                inputs=[grp.fused_name],
                vertex=SubsetVertex(from_idx=off, to_idx=off + n - 1))
        _count_fusion("fused")
    if groups:
        new.topo_order = _toposort(new.nodes, new.network_inputs)
    return new, groups


# ---------------------------------------------------------------------------
# Parameter / optimizer-state transfer across the fusion boundary
# ---------------------------------------------------------------------------

def _concat_leaves(*leaves):
    """Channel-concat per-branch leaves: HWIO kernels (rank 4) join on
    the output-channel axis, dense kernels (rank 2, [n_in, n_out]) on
    the output-feature axis, biases (rank 1) end to end; anything else
    (scalar schedules etc.) must already agree branch-to-branch."""
    a = leaves[0]
    if a.ndim == 4:
        return jnp.concatenate(leaves, axis=3)
    if a.ndim == 2:
        return jnp.concatenate(leaves, axis=1)
    if a.ndim == 1:
        return jnp.concatenate(leaves, axis=0)
    for other in leaves[1:]:
        if other.shape != a.shape:
            raise ValueError(
                f"Cannot fuse rank-{a.ndim} state leaves of shapes "
                f"{[l.shape for l in leaves]}")
    return a


def fuse_params(groups: Sequence[FusionGroup], tree: Dict[str, dict]
                ) -> Dict[str, dict]:
    """Map an UNFUSED per-node tree (params / opt state / layer state)
    onto the fused graph: member entries concat into the fused node's
    entry, everything else passes through. Pure concat — the fused
    network computes bitwise the same forward."""
    member_names = {m for g in groups for m in g.members}
    out = {k: v for k, v in tree.items() if k not in member_names}
    for grp in groups:
        out[grp.fused_name] = jax.tree_util.tree_map(
            _concat_leaves, *[tree[m] for m in grp.members])
    return out


def _slice_leaf(leaf, off: int, n: int):
    if leaf.ndim == 4:
        return leaf[:, :, :, off:off + n]
    if leaf.ndim == 2:
        return leaf[:, off:off + n]
    if leaf.ndim == 1:
        return leaf[off:off + n]
    return leaf


def unfuse_params(groups: Sequence[FusionGroup], tree: Dict[str, dict]
                  ) -> Dict[str, dict]:
    """Inverse of fuse_params: slice the fused node's entry back into
    per-member entries (checkpoints cross the fused/unfused boundary in
    either direction)."""
    fused_names = {g.fused_name for g in groups}
    out = {k: v for k, v in tree.items() if k not in fused_names}
    for grp in groups:
        sub = tree[grp.fused_name]
        for m, n, off in zip(grp.members, grp.n_outs, grp.offsets):
            out[m] = jax.tree_util.tree_map(
                lambda leaf: _slice_leaf(leaf, off, n), sub)
    return out


# ---------------------------------------------------------------------------
# Multi-model serving merge (serving/model_pool.py FusedModelGroup substrate)
# ---------------------------------------------------------------------------

# Name of the synthetic concat head the merged serving graph ends in.
SERVING_CONCAT = "serving_concat"


class FusionIneligibleError(ValueError):
    """The member set cannot be merged into one fused serving forward
    (geometry/type/init mismatch). ModelPool catches this and falls back
    to independent per-model entries — never a hard failure."""


def _serving_member_ok(name: str, net) -> None:
    """Raise FusionIneligibleError unless `net` is a single-input,
    single-output, initialized ComputationGraph whose head is a sized
    layer (the shapes the column slicing needs)."""
    conf = getattr(net, "conf", None)
    if not isinstance(conf, ComputationGraphConfiguration):
        raise FusionIneligibleError(
            f"member {name!r} is not a ComputationGraph (only graph "
            "models can merge into a fused serving forward)")
    if not getattr(net, "_initialized", False):
        raise FusionIneligibleError(f"member {name!r} is not init()ed")
    if len(conf.network_inputs) != 1 or len(conf.network_outputs) != 1:
        raise FusionIneligibleError(
            f"member {name!r} must have exactly one input and one "
            f"output (has {len(conf.network_inputs)}/"
            f"{len(conf.network_outputs)})")
    if not conf.input_types:
        raise FusionIneligibleError(
            f"member {name!r} was built without set_input_types(...) — "
            "the fused engine cannot warm its buckets")
    head = conf.nodes[conf.network_outputs[0]]
    if not head.is_layer() or getattr(head.layer, "n_out", 0) <= 0:
        raise FusionIneligibleError(
            f"member {name!r} head {conf.network_outputs[0]!r} has no "
            "sized n_out to slice columns by")


def merge_serving_conf(named_members: Sequence[Tuple[str, object]]
                       ) -> Tuple[ComputationGraphConfiguration,
                                  Dict[str, Tuple[int, int]]]:
    """Merge N same-input-geometry single-head graphs into ONE inference
    config: every member's nodes are cloned under a ``{member}/`` name
    prefix, all members read one shared network input, and a final
    MergeVertex (``serving_concat``) channel-concatenates the member
    heads so one forward yields every member's output side by side.

    Returns (merged_conf, col_slices) where ``col_slices[member] =
    (offset, width)`` locates that member's columns in the concat.

    The merged config is INFERENCE-ONLY: a MergeVertex consuming
    OutputLayer heads is illegal for training (GraphBuilder's sink rule
    exists for the training walk) — the serving walk runs heads as
    plain forwards, which is exactly the semantics the gateway needs.

    Raises :class:`FusionIneligibleError` when members diverge (not
    graphs, different input types, duplicate names, <2 members)."""
    if len(named_members) < 2:
        raise FusionIneligibleError("a fused group needs >= 2 members")
    names = [nm for nm, _ in named_members]
    if len(set(names)) != len(names):
        raise FusionIneligibleError(f"duplicate member names in {names}")
    for nm, net in named_members:
        _serving_member_ok(nm, net)
    first = named_members[0][1].conf
    for nm, net in named_members[1:]:
        if net.conf.input_types != first.input_types:
            raise FusionIneligibleError(
                f"member {nm!r} input type {net.conf.input_types} != "
                f"{first.input_types} — fused batching needs identical "
                "input geometry")
    shared_input = first.network_inputs[0]
    nodes: Dict[str, GraphNode] = {}
    heads: List[str] = []
    col_slices: Dict[str, Tuple[int, int]] = {}
    off = 0
    for nm, net in named_members:
        conf = net.conf
        own_input = conf.network_inputs[0]
        remap = lambda inp: shared_input if inp == own_input \
            else f"{nm}/{inp}"
        for node_name, node in conf.nodes.items():
            nodes[f"{nm}/{node_name}"] = GraphNode(
                inputs=[remap(i) for i in node.inputs],
                layer=copy.deepcopy(node.layer),
                vertex=copy.deepcopy(node.vertex),
                preprocessor=copy.deepcopy(node.preprocessor))
        head = conf.network_outputs[0]
        heads.append(f"{nm}/{head}")
        width = conf.nodes[head].layer.n_out
        col_slices[nm] = (off, width)
        off += width
    nodes[SERVING_CONCAT] = GraphNode(inputs=heads, vertex=MergeVertex())
    merged = ComputationGraphConfiguration(
        network_inputs=[shared_input],
        network_outputs=[SERVING_CONCAT],
        nodes=nodes,
        topo_order=_toposort(nodes, [shared_input]),
        input_types=copy.deepcopy(first.input_types),
        seed=first.seed)
    return merged, col_slices


def fused_trees_from_members(groups: Sequence[FusionGroup],
                             named_members: Sequence[Tuple[str, object]]
                             ) -> Tuple[Dict[str, dict], Dict[str, dict]]:
    """(params_tree, state_tree) for the fused serving graph, built from
    the members' CURRENT trees (namespace-prefix then fuse_params).
    Leaves are copied, never aliased — the solo members stay the source
    of truth and mutate independently (hot-swap rebuilds through here)."""
    merged_p: Dict[str, dict] = {}
    merged_s: Dict[str, dict] = {}
    for nm, net in named_members:
        for node, sub in net.params_tree.items():
            merged_p[f"{nm}/{node}"] = sub
        for node, sub in net.state_tree.items():
            merged_s[f"{nm}/{node}"] = sub
    own = lambda tree: jax.tree_util.tree_map(jnp.copy, tree)
    return (own(fuse_params(groups, merged_p)),
            own(fuse_params(groups, merged_s)))


def build_fused_serving_net(named_members: Sequence[Tuple[str, object]]):
    """Members -> ONE inference-only ComputationGraph serving all of
    them: merge under name prefixes, run the sibling-fusion pass over
    the merged config (same-geometry first layers collapse into one
    concat-weight matmul/conv), and transfer the members' live params.

    Returns (fused_net, groups, col_slices): run ``fused_net.output(x)``
    once, slice ``[:, off:off+width]`` per member. Raises
    :class:`FusionIneligibleError` when the member set cannot merge."""
    from .graph import ComputationGraph
    merged, col_slices = merge_serving_conf(named_members)
    fused_conf, groups = fuse_sibling_convs(merged)
    net = ComputationGraph(fused_conf).init(
        dtype=named_members[0][1]._dtype)
    net.params_tree, net.state_tree = fused_trees_from_members(
        groups, named_members)
    return net, groups, col_slices


def fuse_graph(net):
    """Initialized ComputationGraph -> fused ComputationGraph carrying
    the SAME params, layer state, and optimizer state (concatenated, not
    re-initialized), plus iteration/epoch counters. Returns the input
    unchanged when nothing is fusible."""
    from .graph import ComputationGraph
    fused_conf, groups = fuse_sibling_convs(net.conf)
    if not groups:
        return net
    out = ComputationGraph(fused_conf).init(dtype=net._dtype)
    # Deep-copy the leaves: pass-through entries would otherwise ALIAS
    # the donor's buffers, and the first donating train step on either
    # network would delete the other's params out from under it.
    own = lambda tree: jax.tree_util.tree_map(jnp.copy, tree)
    out.params_tree = own(fuse_params(groups, net.params_tree))
    out.state_tree = own(fuse_params(groups, net.state_tree))
    out.opt_state = own(fuse_params(groups, net.opt_state))
    out.iteration = net.iteration
    out.epoch = net.epoch
    return out
