"""ComputationGraph: DAG network runtime.

Reference parity: nn/graph/ComputationGraph.java (3,063 LoC) — vertices
array + per-vertex param views (:365-402), fit(MultiDataSetIterator) (:867),
computeGradientAndScore walking topologicalOrder (:1161),
calcBackpropGradients in reverse topo order (:1170), map-based feedForward
(:1212-1241), multi-input/multi-output, score as the SUM over output layers.

TPU-native redesign: the topo walk is a pure function building an
activations dict; autodiff replaces the reverse-order epsilon plumbing and
vertex doBackward entirely; params/opt-state/state are name-keyed dicts
(pytrees) jitted into ONE train step, exactly like MultiLayerNetwork but
DAG-shaped. Masks propagate along the walk via vertex.output_mask.
"""
from __future__ import annotations

import logging

from typing import Any, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ...data.dataset import DataSet, MultiDataSet
from ...data.iterators import AsyncMultiDataSetIterator
from ...optimize import compile_cache as compile_cache_mod
from ...optimize import metrics as metrics_mod
from ...optimize import telemetry as telemetry_mod
from ...utils import params as param_utils
from ..conf.builders import BackpropType
from ..conf.graph_conf import ComputationGraphConfiguration
from ..graph.vertices import LastTimeStepVertex
from ..multilayer import RnnStateMismatchError, _regularization_score
from ..updaters import normalize_layer_gradients
from ..stepping import Trainer
from ..layers.recurrent import RECURRENT_CARRY_KEYS

Array = jax.Array


class _SlicingMultiIterator:
    """Re-iterable minibatch views over one MultiDataSet (host numpy
    slices — cheap views feeding the async prefetch thread)."""

    def __init__(self, mds: MultiDataSet, batch_size: int):
        self._mds = mds
        self._batch = int(batch_size)

    def __iter__(self):
        mds, B = self._mds, self._batch
        n = mds.num_examples()
        for start in range(0, n, B):
            sl = slice(start, min(start + B, n))
            yield MultiDataSet(
                [f[sl] for f in mds.features],
                [l[sl] for l in mds.labels],
                None if mds.features_masks is None else
                [None if m is None else m[sl] for m in mds.features_masks],
                None if mds.labels_masks is None else
                [None if m is None else m[sl] for m in mds.labels_masks])


class ComputationGraph(Trainer):
    # What Trainer asks of a front end (nn/stepping.py).
    _TRAIN_JIT_ATTRS = (
        "_train_step_fn", "_train_step_raw",
        "_multi_step_stacked_fn", "_multi_step_repeat_fn",
    )
    _STEP_LABEL = "graph_train_step"
    _ASYNC_ITERATOR = AsyncMultiDataSetIterator
    _FUSES_TBPTT = False

    def __init__(self, conf: ComputationGraphConfiguration):
        self.conf = conf
        self.params_tree: Optional[Dict[str, dict]] = None
        self.state_tree: Optional[Dict[str, dict]] = None
        self.opt_state: Optional[Dict[str, Any]] = None
        self.iteration = 0
        self.epoch = 0
        self.listeners: List[Any] = []
        self.score_value = None
        # Data-pipeline wait for the most recent batch (reference
        # lastEtlTime), split host-wait vs h2d-wait when the device
        # prefetcher is active.
        self.last_etl_ms: float = 0.0
        self.last_etl_host_ms: float = 0.0
        self.last_etl_h2d_ms: float = 0.0
        self._dtype = jnp.float32
        self._rng = None
        self._probe_tag = f"{id(self) & 0xffff:04x}"
        self._initialized = False
        self._layer_nodes = [n for n in conf.topo_order
                             if conf.nodes[n].is_layer()]
        # Streaming/tBPTT recurrent carry, keyed by node name (the MLN
        # _rnn_carry analog; reference ComputationGraph rnn state maps).
        self._rnn_carry: Optional[Dict[str, dict]] = None

    # ------------------------------------------------------------------ init
    def init(self, seed: Optional[int] = None, dtype=jnp.float32
             ) -> "ComputationGraph":
        self._dtype = dtype
        base = jax.random.PRNGKey(self.conf.seed if seed is None else seed)

        # One jitted init (single device program; see MultiLayerNetwork.init)
        def init_all(base_key):
            keys = jax.random.split(base_key, len(self._layer_nodes) + 1)
            params = {
                name: self.conf.nodes[name].layer.init_params(k, dtype)
                for name, k in zip(self._layer_nodes, keys[:-1])}
            states = {
                name: self.conf.nodes[name].layer.init_state(dtype)
                for name in self._layer_nodes}
            opt = {
                name: self.conf.nodes[name].layer.updater.init(params[name])
                for name in self._layer_nodes}
            return params, states, opt, keys[-1]

        (self.params_tree, self.state_tree, self.opt_state,
         self._rng) = jax.jit(init_all)(base)
        self.iteration = 0
        self.epoch = 0
        self._build_jitted()
        self._initialized = True
        return self

    def _check_init(self):
        if not self._initialized:
            raise RuntimeError("Call graph.init() first")

    # --------------------------------------------------------- pure functions
    def _walk(self, params, state, inputs: Dict[str, Array], train: bool,
              rng, fmasks: Dict[str, Optional[Array]], *,
              for_score: bool = False):
        """Topological forward walk. Returns (activations dict, new state,
        masks dict, and — when for_score — dict of output-layer INPUT
        activations for loss heads)."""
        conf = self.conf
        acts: Dict[str, Array] = dict(inputs)
        masks: Dict[str, Optional[Array]] = {
            name: fmasks.get(name) for name in conf.network_inputs}
        new_state = {}
        head_inputs: Dict[str, Array] = {}
        for i, name in enumerate(conf.topo_order):
            # the vertex's own name on its operations in the device's trace
            with jax.named_scope(name):
                node = conf.nodes[name]
                in_acts = [acts[n] for n in node.inputs]
                in_masks = [masks.get(n) for n in node.inputs]
                if node.is_layer():
                    a = in_acts[0]
                    if node.preprocessor is not None:
                        a = node.preprocessor(a)
                    sub = None if rng is None \
                        else jax.random.fold_in(rng, i)
                    is_out = node.layer.is_output_layer()
                    if for_score and is_out:
                        if train and node.layer.dropout_rate \
                                and sub is not None:
                            from ..layers.core import dropout
                            a = dropout(a, node.layer.dropout_rate, train,
                                        sub)
                        head_inputs[name] = a
                        new_state[name] = state[name]
                        acts[name] = a  # unused downstream (outputs are sinks)
                    else:
                        out, st = node.layer.forward(
                            params[name], state[name], a, train=train,
                            rng=sub, mask=in_masks[0])
                        acts[name] = out
                        new_state[name] = st
                    masks[name] = in_masks[0]
                else:
                    vertex = node.vertex
                    if isinstance(vertex, LastTimeStepVertex) and \
                            vertex.mask_input is not None:
                        in_masks = [masks.get(vertex.mask_input)]
                    acts[name] = vertex.forward(in_acts, train=train,
                                                masks=in_masks)
                    masks[name] = vertex.output_mask(in_masks)
        return acts, new_state, masks, head_inputs

    def _loss_pure(self, params, state, inputs, labels, fmasks, lmasks, rng,
                   train: bool):
        """Sum of output-layer losses + regularization (reference
        computeGradientAndScore :1161 sums IOutputLayer scores)."""
        _, new_state, _, head_inputs = self._walk(
            params, state, inputs, train, rng, fmasks, for_score=True)
        with jax.named_scope("loss"):
            total = jnp.asarray(0.0, jnp.float32)
            for out_name, y in labels.items():
                node = self.conf.nodes[out_name]
                if not node.layer.is_output_layer():
                    raise ValueError(f"Output node {out_name!r} is not an "
                                     "output layer")
                with jax.named_scope(out_name):     # loss/<head>
                    total = total + node.layer.compute_score(
                        params[out_name], head_inputs[out_name], y,
                        lmasks.get(out_name))
            reg = _regularization_score(
                [self.conf.nodes[n].layer for n in self._layer_nodes],
                [params[n] for n in self._layer_nodes])
            return total + reg, new_state

    def _build_jitted(self):
        """(Re)build the inference jits and invalidate the training
        jits (rebuilt lazily via Trainer.__getattr__ — see
        MultiLayerNetwork._build_jitted)."""
        conf = self.conf
        for name in self._TRAIN_JIT_ATTRS:
            self.__dict__.pop(name, None)
        self._output_fn = compile_cache_mod.PrecompiledDispatch(
            jax.jit(lambda params, state, inputs, fmasks:
                    [self._walk(params, state, inputs, False, None,
                                fmasks)[0][n]
                     for n in conf.network_outputs]),
            f"graph_output#{self._probe_tag}")
        self._ff_named_fn = jax.jit(
            lambda params, state, inputs:
            self._walk(params, state, inputs, False, None, {})[0])
        self._loss_fn_jit = compile_cache_mod.PrecompiledDispatch(
            jax.jit(lambda params, state, inputs, labels, fmasks, lmasks:
                    self._loss_pure(params, state, inputs, labels, fmasks,
                                    lmasks, None, False)[0]),
            f"graph_loss#{self._probe_tag}")

        def rnn_step(params, state, inputs):
            acts, new_state, _, _ = self._walk(params, state, inputs,
                                               False, None, {})
            return [acts[n] for n in conf.network_outputs], new_state

        self._rnn_step_fn = jax.jit(rnn_step)

    def _build_training_jits(self):
        layer_nodes = self._layer_nodes
        conf = self.conf

        def train_step(params, opt_state, state, iteration, rng, inputs,
                       labels, fmasks, lmasks):
            rng, step_rng = jax.random.split(rng)
            (loss, new_state), grads = jax.value_and_grad(
                self._loss_pure, has_aux=True)(
                    params, state, inputs, labels, fmasks, lmasks, step_rng,
                    True)
            new_params = {}
            new_opt = {}
            with jax.named_scope("updater"):
                for name in layer_nodes:
                    layer = conf.nodes[name].layer
                    g = normalize_layer_gradients(
                        grads[name], layer.gradient_normalization,
                        layer.gradient_normalization_threshold)
                    updates, opt_i = layer.updater.update(
                        g, opt_state[name], iteration)
                    if layer.frozen:
                        new_params[name] = params[name]
                        new_opt[name] = opt_state[name]
                    else:
                        new_params[name] = jax.tree_util.tree_map(
                            lambda p, u: p - u.astype(p.dtype),
                            params[name], updates)
                        new_opt[name] = opt_i
            return (new_params, new_opt, new_state, iteration + 1, rng, loss)

        # Donate params/opt/state (see MultiLayerNetwork._build_jitted).
        self._train_step_fn = compile_cache_mod.PrecompiledDispatch(
            jax.jit(train_step, donate_argnums=(0, 1, 2)),
            f"graph_train_step#{self._probe_tag}")
        metrics_mod.register_jit_probe(
            f"graph_train_step#{self._probe_tag}",
            self._train_step_fn)
        # Unjitted step for wrappers that trace under their own context
        # (SequenceParallelWrapper) without polluting this cache.
        self._train_step_raw = train_step

        # Fused multi-step training: K optimizer steps per device dispatch
        # via lax.scan — the MaxText-style jitted training loop. Amortizes
        # per-call host dispatch over K device steps. Two flavors:
        # scan over K stacked minibatches (fit_batches), and K steps on one
        # resident minibatch (fit_batch_repeated; xs=None so the batch is
        # not replicated in HBM).
        def multi_step_stacked(params, opt_state, state, iteration, rng,
                               s_inputs, s_labels, s_fmasks, s_lmasks):
            def body(carry, xs):
                out = train_step(*carry, *xs)
                return out[:5], out[5]
            carry, losses = jax.lax.scan(
                body, (params, opt_state, state, iteration, rng),
                (s_inputs, s_labels, s_fmasks, s_lmasks))
            return (*carry, losses)

        def multi_step_repeat(params, opt_state, state, iteration, rng,
                              inputs, labels, fmasks, lmasks, length):
            def body(carry, _):
                out = train_step(*carry, inputs, labels, fmasks, lmasks)
                return out[:5], out[5]
            carry, losses = jax.lax.scan(
                body, (params, opt_state, state, iteration, rng), None,
                length=length)
            return (*carry, losses)

        self._multi_step_stacked_fn = jax.jit(
            multi_step_stacked, donate_argnums=(0, 1, 2))
        self._multi_step_repeat_fn = compile_cache_mod.PrecompiledDispatch(
            jax.jit(multi_step_repeat, donate_argnums=(0, 1, 2),
                    static_argnums=(9,)),
            f"graph_multi_step_repeat#{self._probe_tag}",
            static_argnums=(9,))

    # ---------------------------------------------------------- precompile
    def _input_structs(self, batch_size: int,
                       time_steps: Optional[int] = None) -> Dict[str, Any]:
        """Abstract input dict inferred from conf.input_types (one per
        network input, the set_input_types contract)."""
        from ..conf.inputs import (ConvolutionalFlatType, ConvolutionalType,
                                   FeedForwardType, RecurrentType)
        conf = self.conf
        if not conf.input_types or \
                len(conf.input_types) != len(conf.network_inputs):
            raise ValueError(
                "precompile() needs set_input_types(...) on the graph "
                "builder (one InputType per network input)")
        b = int(batch_size)
        structs = {}
        for name, it in zip(conf.network_inputs, conf.input_types):
            if isinstance(it, ConvolutionalType):
                shape = (b, it.height, it.width, it.channels)
            elif isinstance(it, ConvolutionalFlatType):
                shape = (b, it.flat_size)
            elif isinstance(it, RecurrentType):
                t = time_steps or it.timeseries_length
                if not t:
                    raise ValueError(
                        "precompile() on a recurrent graph needs "
                        "time_steps= (or RecurrentType with "
                        "timeseries_length)")
                shape = (b, int(t), it.size)
            elif isinstance(it, FeedForwardType):
                shape = (b, it.size)
            else:
                raise ValueError(
                    f"precompile() cannot size input {name!r} from "
                    f"{type(it).__name__}")
            structs[name] = jax.ShapeDtypeStruct(shape, self._dtype)
        return structs

    def precompile(self, batch_size: int, *,
                   time_steps: Optional[int] = None,
                   repeat_steps: Optional[int] = None, train: bool = True,
                   inference: bool = True) -> "ComputationGraph":
        """AOT-compile the train/output/loss steps for one batch
        signature (the MultiLayerNetwork.precompile analog; see
        docs/perf_compile_cache.md). Covers the maskless signature and
        the fit loop's synthesized ones-mask signature; user-masked
        batches fall through to normal jit dispatch."""
        self._check_init()
        if train and self.conf.backprop_type == BackpropType.TRUNCATED_BPTT:
            raise NotImplementedError(
                "precompile() does not support truncated-BPTT graphs; "
                "precompile(train=False) still covers inference")
        inputs_s = self._input_structs(batch_size, time_steps)
        params_s = compile_cache_mod.abstract_like(self.params_tree)
        state_s = compile_cache_mod.abstract_like(self.state_tree)
        outs_s = jax.eval_shape(
            lambda p, s, i: [self._walk(p, s, i, False, None, {})[0][n]
                             for n in self.conf.network_outputs],
            params_s, state_s, inputs_s)
        labels_s = {name: jax.ShapeDtypeStruct(o.shape, o.dtype)
                    for name, o in zip(self.conf.network_outputs, outs_s)}
        if inference:
            self._output_fn.precompile(params_s, state_s, inputs_s, {})
            # Inference-only graphs may end in plain vertices (the fused
            # serving concat, nn/graph/fusion.py) — no score path exists
            # to compile for them.
            scoreable = all(
                self.conf.nodes[n].is_layer()
                and self.conf.nodes[n].layer.is_output_layer()
                for n in self.conf.network_outputs)
            if scoreable:
                self._loss_fn_jit.precompile(params_s, state_s, inputs_s,
                                             labels_s, {}, {})
        if not train:
            return self
        opt_s = compile_cache_mod.abstract_like(self.opt_state)
        it_s = jax.ShapeDtypeStruct((), jnp.int32)
        rng_s = jax.ShapeDtypeStruct(tuple(self._rng.shape),
                                     self._rng.dtype)
        # Two signatures: maskless, and the per-output ones-(b,1)
        # labels masks the default fit loop's pad-to-bucket iterator
        # synthesizes on every batch (data/iterators.py) — the _pack
        # contract turns those into this dict shape.
        lm_s = {name: jax.ShapeDtypeStruct((int(batch_size), 1),
                                           jnp.float32)
                for name in self.conf.network_outputs}
        for lmasks in ({}, lm_s):
            self._train_step_fn.precompile(
                params_s, opt_s, state_s, it_s, rng_s, inputs_s,
                labels_s, {}, lmasks)
        if repeat_steps:
            self._multi_step_repeat_fn.precompile(
                params_s, opt_s, state_s, it_s, rng_s, inputs_s,
                labels_s, {}, {}, int(repeat_steps))
        return self

    # ----------------------------------------------------------------- data
    def _coerce(self, data, labels=None) -> MultiDataSet:
        if isinstance(data, MultiDataSet):
            return data
        if isinstance(data, DataSet):
            return MultiDataSet.from_dataset(data)
        if labels is not None:
            f = [np.asarray(a) for a in (data if isinstance(data, (list, tuple))
                                         else [data])]
            l = [np.asarray(a) for a in (labels if isinstance(labels,
                                                              (list, tuple))
                                         else [labels])]
            return MultiDataSet(f, l)
        raise ValueError("Expected MultiDataSet / DataSet / (features, labels)")

    def _pack(self, mds: MultiDataSet):
        conf = self.conf
        if len(mds.features) != len(conf.network_inputs):
            raise ValueError(f"Graph has {len(conf.network_inputs)} inputs, "
                             f"got {len(mds.features)} feature arrays")
        if len(mds.labels) != len(conf.network_outputs):
            raise ValueError(f"Graph has {len(conf.network_outputs)} outputs, "
                             f"got {len(mds.labels)} label arrays")
        inputs, fmasks = self._pack_inputs(mds.features, mds.features_masks)
        labels = {name: jnp.asarray(arr)
                  for name, arr in zip(conf.network_outputs, mds.labels)}
        lmasks = {}
        if mds.labels_masks is not None:
            for name, m in zip(conf.network_outputs, mds.labels_masks):
                if m is not None:
                    lmasks[name] = jnp.asarray(m)
        return inputs, labels, fmasks, lmasks

    def _pack_inputs(self, features, features_masks=None):
        """Shared input coercion for training and inference paths."""
        conf = self.conf
        inputs = {}
        for name, arr in zip(conf.network_inputs, features):
            a = jnp.asarray(arr)
            if jnp.issubdtype(a.dtype, jnp.floating):
                a = a.astype(self._dtype)
            inputs[name] = a
        fmasks = {}
        if features_masks is not None:
            for name, m in zip(conf.network_inputs, features_masks):
                if m is not None:
                    fmasks[name] = jnp.asarray(m)
        return inputs, fmasks

    # ------------------------------------------------------------------- fit
    # `fit` itself is Trainer's (nn/stepping.py); these are its hooks.
    def _batches(self, data, labels, batch_size, epochs):
        if hasattr(data, "__iter__") and not isinstance(
                data, (DataSet, MultiDataSet, list, tuple, np.ndarray)):
            if epochs > 1 and not hasattr(data, "reset"):
                # Plain generator: materialize so later epochs see data.
                return list(data)
            return data
        return _SlicingMultiIterator(self._coerce(data, labels), batch_size)

    @staticmethod
    def _batch_signature(m: MultiDataSet):
        # .shape directly — np.asarray on device-resident arrays
        # would force d2h copies per batch in the hot loop
        def _shape(a):
            return a.shape if hasattr(a, "shape") else np.asarray(a).shape
        return (tuple(_shape(f) for f in m.features),
                tuple(_shape(l) for l in m.labels),
                m.features_masks is None, m.labels_masks is None)

    def _input_shapes(self, batch_size, time_steps):
        return [s.shape for s in
                self._input_structs(batch_size, time_steps).values()]

    def fit_batch(self, mds: MultiDataSet, do_step=None):
        """One training batch. `do_step(inputs, labels, fmasks, lmasks)`
        lets ParallelWrapper substitute a sharded step while REUSING the
        tBPTT windowing below (the MultiLayerNetwork._fit_batch do_step
        contract)."""
        mds = self._coerce(mds)
        do_step = do_step or self._run_and_commit
        if self.conf.backprop_type == BackpropType.TRUNCATED_BPTT:
            # ANY rank-3 input triggers windowing (static rank-2 inputs
            # pass whole into every window — _fit_tbptt handles the mix).
            # np.ndim reads .ndim without materializing — np.asarray on a
            # device-resident array would force a d2h copy per batch.
            any_seq = any(np.ndim(f) == 3 for f in mds.features)
            labels_rank3 = all(np.ndim(l) == 3 for l in mds.labels)
            if any_seq and labels_rank3:
                self._fit_tbptt(mds, do_step)
                return
            if not getattr(self, "_warned_tbptt_labels", False):
                logging.getLogger(__name__).warning(
                    "Truncated BPTT requires rank-3 features and labels; "
                    "using standard BPTT")
                self._warned_tbptt_labels = True
        self._rnn_carry = None  # standard BPTT: every batch starts fresh
        do_step(*self._pack(mds))

    _fit_batch = fit_batch      # the name Trainer.fit steps through

    def fit_batches(self, batches: Sequence) -> "ComputationGraph":
        """K optimizer steps over K minibatches in ONE device dispatch
        (jitted lax.scan; see _build_jitted). All batches must share
        shapes; masks must be uniformly present or absent. Listeners fire
        per step afterwards with the per-step losses."""
        self._check_init()
        packed = [self._pack(self._coerce(b)) for b in batches]
        if self.conf.backprop_type == BackpropType.TRUNCATED_BPTT:
            raise NotImplementedError(
                "fit_batches does not support truncated BPTT windows; "
                "call fit_batch per batch")
        stack = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *packed)
        self._rnn_carry = None
        out = self._multi_step_stacked_fn(
            self.params_tree, self.opt_state, self.state_tree,
            self._iteration_device(None), self._rng, *stack)
        self._commit_multi(out, len(batches))
        return self

    def fit_batch_repeated(self, mds, steps: int) -> "ComputationGraph":
        """`steps` optimizer steps on one device-resident minibatch in one
        dispatch (the batch is NOT replicated; lax.scan with a closed-over
        batch). The multi-dispatch equivalent of calling fit_batch in a
        loop."""
        self._check_init()
        if self.conf.backprop_type == BackpropType.TRUNCATED_BPTT:
            raise NotImplementedError(
                "fit_batch_repeated does not support truncated BPTT")
        packed = self._pack(self._coerce(mds))
        self._rnn_carry = None
        out = self._multi_step_repeat_fn(
            self.params_tree, self.opt_state, self.state_tree,
            self._iteration_device(None), self._rng, *packed, int(steps))
        self._commit_multi(out, int(steps))
        return self

    def _fit_tbptt(self, mds: MultiDataSet, do_step=None):
        """Truncated BPTT over the graph: slide tbptt_fwd_length windows
        over the time axis of every rank-3 array, one optimizer step per
        window with recurrent state carried between windows (the
        MultiLayerNetwork._fit_tbptt analog; reference ComputationGraph
        doTruncatedBPTT). Rank-2 (static) inputs pass whole into every
        window."""
        do_step = do_step or self._run_and_commit
        T = max(np.asarray(f).shape[1] for f in mds.features
                if np.asarray(f).ndim == 3)
        L = self.conf.tbptt_fwd_length
        batch = np.asarray(mds.features[0]).shape[0]
        self.rnn_clear_previous_state()
        self._seed_recurrent_states(batch)
        sl3 = lambda a, s, e: None if a is None else \
            (a[:, s:e] if np.asarray(a).ndim >= 2 and
             np.asarray(a).shape[1] >= T else a)
        for start in range(0, T, L):
            end = min(start + L, T)
            win = MultiDataSet(
                [f[:, start:end] if np.asarray(f).ndim == 3 else f
                 for f in mds.features],
                [l[:, start:end] for l in mds.labels],
                None if mds.features_masks is None else
                [sl3(m, start, end) for m in mds.features_masks],
                None if mds.labels_masks is None else
                [sl3(m, start, end) for m in mds.labels_masks])
            do_step(*self._pack(win))
        self.rnn_clear_previous_state()

    # ------------------------------------------------------------- rnn state
    def _seed_recurrent_states(self, batch: int):
        if self._rnn_carry is None:
            self._rnn_carry = {
                name: self.conf.nodes[name].layer.seed_recurrent_state(
                    batch, self._dtype)
                for name in self._layer_nodes
                if self.conf.nodes[name].layer.is_recurrent()}

    def rnn_clear_previous_state(self):
        """Reference ComputationGraph.rnnClearPreviousState()."""
        self._rnn_carry = None

    def _merged_state(self):
        if self._rnn_carry is None:
            return self.state_tree
        return {name: {**st, **self._rnn_carry.get(name, {})}
                for name, st in self.state_tree.items()}

    def _commit_state(self, new_state):
        if self._rnn_carry is None:
            self.state_tree = new_state
            return
        base, carry = {}, {}
        for name, st in new_state.items():
            carry[name] = {k: v for k, v in st.items() if k in RECURRENT_CARRY_KEYS}
            base[name] = {k: v for k, v in st.items()
                          if k not in RECURRENT_CARRY_KEYS}
        self.state_tree = base
        self._rnn_carry = {k: v for k, v in carry.items() if v}

    def rnn_time_step(self, *features) -> List[np.ndarray]:
        """Streaming inference with carried recurrent state (reference
        ComputationGraph.rnnTimeStep)."""
        self._check_init()
        for name in self._layer_nodes:
            layer = self.conf.nodes[name].layer
            if not layer.supports_streaming():
                raise NotImplementedError(
                    f"{type(layer).__name__} ({name!r}) does not support "
                    "rnn_time_step")
        if len(features) == 1 and isinstance(features[0], (list, tuple)):
            features = tuple(features[0])
        inputs, fmasks = self._pack_inputs(features)
        batch = next(iter(inputs.values())).shape[0]
        if self._rnn_carry is not None:
            for carry in self._rnn_carry.values():
                if "h" in carry and carry["h"].shape[0] != batch:
                    stored = carry["h"].shape[0]
                    # Typed error + explicit reset (same contract as
                    # MultiLayerNetwork.rnn_time_step): never leave a
                    # stale carry to poison the next streaming caller.
                    self._rnn_carry = None
                    raise RnnStateMismatchError(
                        f"rnn_time_step batch size {batch} != stored state "
                        f"batch size {stored}; stored recurrent state has "
                        "been reset")
        self._seed_recurrent_states(batch)
        outs, new_state = self._rnn_step_fn(
            self.params_tree, self._merged_state(), inputs)
        self._commit_state(new_state)
        return [np.asarray(o) for o in outs]

    # ------------------------------------------------------------- inference
    def outputs(self, *features, features_masks=None) -> List[np.ndarray]:
        """All network outputs (reference ComputationGraph.output(...))."""
        self._check_init()
        conf = self.conf
        if len(features) == 1 and isinstance(features[0], (list, tuple)):
            features = tuple(features[0])
        if len(features) != len(conf.network_inputs):
            raise ValueError(f"Graph has {len(conf.network_inputs)} inputs, "
                             f"got {len(features)}")
        inputs, fmasks = self._pack_inputs(features, features_masks)
        telemetry_mod.note_step_signature(
            f"graph_output#{self._probe_tag}",
            telemetry_mod.shape_signature(*inputs.values(),
                                          *fmasks.values()))
        outs = self._output_fn(self.params_tree, self.state_tree, inputs,
                               fmasks)
        return [np.asarray(o) for o in outs]

    def output(self, *features, features_masks=None) -> np.ndarray:
        return self.outputs(*features, features_masks=features_masks)[0]

    def feed_forward_named(self, *features) -> Dict[str, np.ndarray]:
        """{node name: activation} for one inference forward pass over
        EVERY vertex, inputs included (reference
        ComputationGraph.feedForward() returning the activations map).
        Jitted once; the public surface listeners use to inspect
        intermediate activations (ui.convolutional)."""
        self._check_init()
        conf = self.conf
        if len(features) == 1 and isinstance(features[0], (list, tuple)):
            features = tuple(features[0])
        if len(features) != len(conf.network_inputs):
            raise ValueError(f"Graph has {len(conf.network_inputs)} inputs, "
                             f"got {len(features)}")
        # _pack_inputs applies the same net-dtype cast every other
        # forward path uses: on a bf16 net the probe forward must match
        # training precision, not trace a second f32 jit variant
        inputs, _ = self._pack_inputs(features)
        acts = self._ff_named_fn(self.params_tree, self.state_tree, inputs)
        return {n: np.asarray(a) for n, a in acts.items()}

    def predict(self, *features) -> np.ndarray:
        return np.argmax(self.output(*features), axis=-1)

    # ----------------------------------------------------------------- score
    def score(self, data=None) -> float:
        self._check_init()
        if data is None:
            if self.score_value is None:
                raise ValueError("No data given and no cached score")
            return float(self.score_value)
        mds = self._coerce(data)
        inputs, labels, fmasks, lmasks = self._pack(mds)
        return float(self._loss_fn_jit(self.params_tree, self.state_tree,
                                       inputs, labels, fmasks, lmasks))

    def compute_gradient_and_score(self, data):
        self._check_init()
        mds = self._coerce(data)
        inputs, labels, fmasks, lmasks = self._pack(mds)
        (loss, _), grads = jax.value_and_grad(
            self._loss_pure, has_aux=True)(
                self.params_tree, self.state_tree, inputs, labels, fmasks,
                lmasks, None, False)
        return grads, float(loss)

    # ------------------------------------------------------------ evaluation
    def evaluate(self, data, labels=None, batch_size: int = 128,
                 output_index: int = 0):
        """Classification metrics for one network output (mask-aware).
        `output_index` selects which output to evaluate for multi-output
        graphs (reference evaluates output 0 unless given an index)."""
        from ...eval.evaluation import Evaluation
        self._check_init()
        mds = self._coerce(data, labels)
        ev = Evaluation()
        n = mds.num_examples()
        for start in range(0, n, batch_size):
            sl = slice(start, min(start + batch_size, n))
            fms = None if mds.features_masks is None else \
                [None if m is None else m[sl] for m in mds.features_masks]
            outs = self.outputs(*[f[sl] for f in mds.features],
                                features_masks=fms)
            lm = None
            if mds.labels_masks is not None and \
                    mds.labels_masks[output_index] is not None:
                lm = mds.labels_masks[output_index][sl]
            ev.eval(mds.labels[output_index][sl], outs[output_index], mask=lm)
        return ev

    # ------------------------------------------------------------ param view
    def params(self) -> np.ndarray:
        self._check_init()
        return np.asarray(param_utils.flatten_params(self.params_tree))

    def set_params(self, flat) -> None:
        self._check_init()
        self.params_tree = param_utils.unflatten_params(
            self.params_tree, jnp.asarray(flat))

    def num_params(self) -> int:
        self._check_init()
        return param_utils.num_params(self.params_tree)

    def summary(self) -> str:
        lines = ["name | type | params"]
        for name in self.conf.topo_order:
            node = self.conf.nodes[name]
            kind = (type(node.layer).__name__ if node.is_layer()
                    else type(node.vertex).__name__)
            n = (param_utils.num_params(self.params_tree[name])
                 if self._initialized and node.is_layer() else 0)
            lines.append(f"{name} | {kind} | {n}")
        if self._initialized:
            lines.append(f"Total params: {self.num_params()}")
        return "\n".join(lines)
