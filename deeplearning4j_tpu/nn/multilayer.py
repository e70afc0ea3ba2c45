"""MultiLayerNetwork: sequential-stack network with fit/output/evaluate.

Reference parity: nn/multilayer/MultiLayerNetwork.java (2,853 LoC) —
`init()` (:442-536), `fit(DataSetIterator)` (:1019-1115), `output` (:1664),
`score` (:1985), `computeGradientAndScore` (:1995), feedForward family
(:725-833). The Solver/StochasticGradientDescent/StepFunction chain
(optimize/Solver.java:43-60, solvers/StochasticGradientDescent.java:56-100)
collapses here into ONE jitted pure train step.

TPU-native redesign:
  * The whole optimize loop body — forward, loss, backward (autodiff),
    gradient normalization, updater math, parameter update — is a single
    pure function compiled once per input shape by jax.jit. XLA fuses what
    DL4J orchestrates imperatively (flat views, workspaces, updater blocks).
  * Parameters/optimizer state/batchnorm state are pytrees (tuple of
    per-layer dicts); the flat `params()` view exists only at the API
    boundary (utils/params.py).
  * Dropout RNG is an explicit key threaded through the step (reference uses
    stateful ND4J RNG).
  * Host→device overlap comes from jax async dispatch + AsyncDataSetIterator
    (reference wraps fit iterators the same way, MultiLayerNetwork.java:1024).
"""
from __future__ import annotations

import functools
import logging
import math
from typing import Any, Callable, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..data.dataset import DataSet
from ..data.iterators import (AsyncDataSetIterator, DataSetIterator,
                              as_iterator)
from ..optimize import compile_cache as compile_cache_mod
from ..optimize import metrics as metrics_mod
from ..optimize import telemetry as telemetry_mod
from ..utils import params as param_utils
from .conf.builders import BackpropType, MultiLayerConfiguration
from .layers import core as core_layers
from .updaters import normalize_layer_gradients
from .stepping import Trainer
from .layers.recurrent import RECURRENT_CARRY_KEYS

Array = jax.Array

log = logging.getLogger(__name__)

def _regularization_score(layers, params) -> Array:
    """L1 + 0.5*L2 penalty over all parameters (reference
    BaseLayer.calcL1/calcL2 summed into score at MultiLayerNetwork.java:1995)."""
    total = jnp.asarray(0.0, jnp.float32)
    for layer, lp in zip(layers, params):
        for name, p in lp.items():
            l1, l2 = layer.param_reg(name)
            if l1:
                total = total + l1 * jnp.sum(jnp.abs(p))
            if l2:
                total = total + 0.5 * l2 * jnp.sum(p * p)
    return total


def _scope_name(i: int, layer) -> str:
    """`<index>_<layer type>`: the name a layer's operations carry in the
    device's trace (`jax.named_scope`; metadata only)."""
    return f"{i}_{type(layer).__name__}"


class RnnStateMismatchError(ValueError):
    """rnn_time_step was called with a batch size that does not match
    the stored recurrent carry. The carry is RESET before this raises:
    a failed streaming request must not poison state for the next
    caller (the pre-fix behaviour left the stale per-layer carry
    behind, silently corrupting the following sequence)."""


class MultiLayerNetwork(Trainer):
    # What Trainer asks of a front end (nn/stepping.py).
    _TRAIN_JIT_ATTRS = (
        "_train_step_fn", "_train_step_raw",
        "_multi_step_stacked_fn", "_multi_step_repeat_fn",
        "_multi_step_repeat_tbptt_fn", "_multi_step_stacked_tbptt_fn",
    )
    _STEP_LABEL = "mln_train_step"
    _ASYNC_ITERATOR = AsyncDataSetIterator
    _FUSES_TBPTT = True

    def __init__(self, conf: MultiLayerConfiguration):
        self.conf = conf
        self.layers = list(conf.layers)
        if not self.layers:
            raise ValueError("Configuration has no layers")
        self.params_tree: Optional[Tuple[dict, ...]] = None
        self.state_tree: Optional[Tuple[dict, ...]] = None
        # Streaming/tbptt recurrent carry (reference stateMap). Kept OUT of
        # state_tree so output()/score()/standard fit() are always stateless.
        self._rnn_carry: Optional[Tuple[dict, ...]] = None
        self.opt_state: Optional[Tuple[Any, ...]] = None
        self.iteration = 0
        self.epoch = 0
        self.listeners: List[Any] = []
        self.score_value: Optional[float] = None
        # Data-pipeline wait for the most recent batch (reference
        # lastEtlTime), split producer-side into host-wait vs h2d-wait
        # when the device prefetcher is active.
        self.last_etl_ms: float = 0.0
        self.last_etl_host_ms: float = 0.0
        self.last_etl_h2d_ms: float = 0.0
        self._dtype = jnp.float32
        self._rng: Optional[Array] = None
        # Training jits are NOT listed here: they are lazy attributes
        # (Trainer.__getattr__) so inference-only nets skip their compiles.
        self._output_fn = None
        self._loss_fn_jit = None
        self._probe_tag = f"{id(self) & 0xffff:04x}"
        self._initialized = False

    # ------------------------------------------------------------------ init
    def init(self, seed: Optional[int] = None, dtype=jnp.float32) -> "MultiLayerNetwork":
        """Initialize parameters/optimizer state (reference init():442)."""
        self._dtype = dtype
        base = jax.random.PRNGKey(self.conf.seed if seed is None else seed)

        # One jitted init: a single device program instead of hundreds of
        # small eager dispatches.
        def init_all(base_key):
            keys = jax.random.split(base_key, len(self.layers) + 1)
            params = tuple(layer.init_params(k, dtype)
                           for layer, k in zip(self.layers, keys[:-1]))
            states = tuple(layer.init_state(dtype) for layer in self.layers)
            opt = tuple(layer.updater.init(p)
                        for layer, p in zip(self.layers, params))
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
            raise RuntimeError("Call net.init() before using the network")

    # --------------------------------------------------------- pure functions
    def _forward_pure(self, params, state, x, train: bool, rng, fmask):
        """Run all layers; returns (final activation, new_state, activations)."""
        a = x
        new_states = []
        activations = []
        for i, layer in enumerate(self.layers):
            with jax.named_scope(_scope_name(i, layer)):
                p = self.conf.preprocessor(i)
                if p is not None:
                    a = p(a)
                sub = None if rng is None else jax.random.fold_in(rng, i)
                a, st = layer.forward(params[i], state[i], a, train=train,
                                      rng=sub, mask=fmask)
            new_states.append(st)
            activations.append(a)
        return a, tuple(new_states), activations

    def _loss_pure(self, params, state, x, y, fmask, lmask, rng, train: bool):
        """Score = output-layer loss + regularization (reference
        computeGradientAndScore:1995)."""
        a = x
        new_states = []
        n = len(self.layers)
        for i, layer in enumerate(self.layers[:-1]):
            with jax.named_scope(_scope_name(i, layer)):
                p = self.conf.preprocessor(i)
                if p is not None:
                    a = p(a)
                sub = None if rng is None else jax.random.fold_in(rng, i)
                a, st = layer.forward(params[i], state[i], a, train=train,
                                      rng=sub, mask=fmask)
            new_states.append(st)
        out_layer = self.layers[-1]
        if not out_layer.is_output_layer():
            raise ValueError("Last layer must be an output layer to compute score")
        with jax.named_scope("loss"):
            p = self.conf.preprocessor(n - 1)
            if p is not None:
                a = p(a)
            if train and out_layer.dropout_rate and rng is not None:
                a = core_layers.dropout(a, out_layer.dropout_rate, train,
                                        jax.random.fold_in(rng, n - 1))
            loss = out_layer.compute_score(params[n - 1], a, y, lmask)
            new_states.append(state[n - 1])
            reg = _regularization_score(self.layers, params)
            return loss + reg, tuple(new_states)

    def _build_jitted(self):
        """(Re)build the inference jits and invalidate the training
        jits. Training jits rebuild lazily on first touch
        (Trainer.__getattr__ → _build_training_jits) so inference-only
        nets — the ParallelInference serving path — never pay their
        compiles, and a post-init retrace stays cheap until training
        actually resumes."""
        for name in self._TRAIN_JIT_ATTRS:
            self.__dict__.pop(name, None)
        self._output_fn = compile_cache_mod.PrecompiledDispatch(
            jax.jit(lambda params, state, x, fmask:
                    self._forward_pure(params, state, x, False, None,
                                       fmask)[0]),
            f"mln_output#{self._probe_tag}")
        self._rnn_step_fn = jax.jit(
            lambda params, state, x:
            self._forward_pure(params, state, x, False, None, None)[:2])
        self._loss_fn_jit = compile_cache_mod.PrecompiledDispatch(
            jax.jit(lambda params, state, x, y, fmask, lmask:
                    self._loss_pure(params, state, x, y, fmask, lmask,
                                    None, False)[0]),
            f"mln_loss#{self._probe_tag}")

    def _build_training_jits(self):
        layers = self.layers

        def train_step(params, opt_state, state, iteration, rng, x, y, fmask, lmask):
            rng, step_rng = jax.random.split(rng)
            (loss, new_state), grads = jax.value_and_grad(
                self._loss_pure, has_aux=True)(
                    params, state, x, y, fmask, lmask, step_rng, True)
            new_params = []
            new_opt = []
            with jax.named_scope("updater"):
                for i, layer in enumerate(layers):
                    g = normalize_layer_gradients(
                        grads[i], layer.gradient_normalization,
                        layer.gradient_normalization_threshold)
                    updates, opt_i = layer.updater.update(
                        g, opt_state[i], iteration)
                    if layer.frozen:
                        new_params.append(params[i])
                        new_opt.append(opt_state[i])
                    else:
                        new_params.append(jax.tree_util.tree_map(
                            lambda p, u: p - u.astype(p.dtype), params[i],
                            updates))
                        new_opt.append(opt_i)
            return (tuple(new_params), tuple(new_opt), new_state,
                    iteration + 1, rng, loss)

        # Donate params/opt/state: the step consumes and replaces them, so
        # XLA reuses the buffers in place — less HBM churn per step (the
        # workspace-reuse role of the reference's MemoryWorkspace). Trees
        # crossing network boundaries (clone, transfer learning) are
        # deep-copied at those seams so donation can never kill a shared
        # buffer.
        self._train_step_fn = compile_cache_mod.PrecompiledDispatch(
            jax.jit(train_step, donate_argnums=(0, 1, 2)),
            f"mln_train_step#{self._probe_tag}")
        metrics_mod.register_jit_probe(
            f"mln_train_step#{self._probe_tag}", self._train_step_fn)
        # Unjitted step: wrappers that must trace under their OWN context
        # (SequenceParallelWrapper's ring-attention routing) re-jit this
        # so the net's cached trace is never polluted.
        self._train_step_raw = train_step

        # Fused multi-step training (see ComputationGraph._build_jitted):
        # K optimizer steps per dispatch via lax.scan.
        def multi_step_stacked(params, opt_state, state, iteration, rng,
                               s_x, s_y, s_fmask, s_lmask):
            def body(carry, xs):
                out = train_step(*carry, *xs)
                return out[:5], out[5]
            carry, losses = jax.lax.scan(
                body, (params, opt_state, state, iteration, rng),
                (s_x, s_y, s_fmask, s_lmask))
            return (*carry, losses)

        def multi_step_repeat(params, opt_state, state, iteration, rng,
                              x, y, fmask, lmask, length):
            def body(carry, _):
                out = train_step(*carry, x, y, fmask, lmask)
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
            f"mln_multi_step_repeat#{self._probe_tag}",
            static_argnums=(9,))

        def _tbptt_pass(p, o, s, it, r, x, y, fmask, lmask):
            """One full tBPTT batch pass: seed a fresh recurrent carry,
            unroll the window schedule (static from the traced shapes),
            strip the carry — exactly the fit_batch/_fit_tbptt
            semantics. Returns (p, o, state_without_carry, it, r, loss
            of the last window)."""
            T = x.shape[1]
            L = self.conf.tbptt_fwd_length
            batch = x.shape[0]

            def seed_merge(st_tuple):
                return tuple(
                    {**st, **(layer.seed_recurrent_state(batch,
                                                         self._dtype)
                              if layer.is_recurrent() else {})}
                    for layer, st in zip(layers, st_tuple))

            def strip(st_tuple):
                return tuple({k: v for k, v in st.items()
                              if k not in RECURRENT_CARRY_KEYS}
                             for st in st_tuple)

            ms = seed_merge(s)
            loss = jnp.asarray(0.0, jnp.float32)
            for start in range(0, T, L):
                end = min(start + L, T)
                fm = None if fmask is None else fmask[:, start:end]
                lm = None if lmask is None else lmask[:, start:end]
                p, o, ms, it, r, loss = train_step(
                    p, o, ms, it, r, x[:, start:end],
                    y[:, start:end], fm, lm)
            return p, o, strip(ms), it, r, loss

        def multi_step_repeat_tbptt(params, opt_state, state, iteration,
                                    rng, x, y, fmask, lmask, length):
            # One dispatch for `length` full tBPTT passes of ONE batch
            # (closed over — not replicated in HBM).
            def body(carry, _):
                out = _tbptt_pass(*carry, x, y, fmask, lmask)
                return out[:5], out[5]

            carry, losses = jax.lax.scan(
                body, (params, opt_state, state, iteration, rng), None,
                length=length)
            return (*carry, losses)

        def multi_step_stacked_tbptt(params, opt_state, state, iteration,
                                     rng, s_x, s_y, s_fmask, s_lmask):
            # One dispatch for K DIFFERENT same-shaped tBPTT batches
            # (the steps_per_dispatch iterator grouping): each scan step
            # is one full window schedule on its batch.
            def body(carry, xs):
                out = _tbptt_pass(*carry, *xs)
                return out[:5], out[5]

            carry, losses = jax.lax.scan(
                body, (params, opt_state, state, iteration, rng),
                (s_x, s_y, s_fmask, s_lmask))
            return (*carry, losses)

        self._multi_step_repeat_tbptt_fn = jax.jit(
            multi_step_repeat_tbptt, donate_argnums=(0, 1, 2),
            static_argnums=(9,))
        self._multi_step_stacked_tbptt_fn = jax.jit(
            multi_step_stacked_tbptt, donate_argnums=(0, 1, 2))

    # ---------------------------------------------------------- precompile
    def _feature_struct(self, batch_size: int,
                        time_steps: Optional[int] = None):
        """Abstract feature batch inferred from conf.input_type (or the
        first layer's n_in when no input type was declared)."""
        from .conf.inputs import (ConvolutionalFlatType, ConvolutionalType,
                                  FeedForwardType, RecurrentType)
        b = int(batch_size)
        it = getattr(self.conf, "input_type", None)
        if isinstance(it, ConvolutionalType):
            shape = (b, it.height, it.width, it.channels)
        elif isinstance(it, ConvolutionalFlatType):
            shape = (b, it.flat_size)
        elif isinstance(it, RecurrentType):
            t = time_steps or it.timeseries_length
            if not t:
                raise ValueError(
                    "precompile() on a recurrent net needs time_steps= "
                    "(or a RecurrentType with timeseries_length)")
            shape = (b, int(t), it.size)
        elif isinstance(it, FeedForwardType):
            shape = (b, it.size)
        else:
            n_in = getattr(self.layers[0], "n_in", None)
            if not n_in:
                raise ValueError(
                    "precompile() cannot infer the input shape: declare "
                    "an input type on the configuration")
            if getattr(self.layers[0], "input_kind", lambda: "ff")() \
                    == "rnn":
                if not time_steps:
                    raise ValueError(
                        "precompile() on a recurrent net needs "
                        "time_steps=")
                shape = (b, int(time_steps), int(n_in))
            else:
                shape = (b, int(n_in))
        return jax.ShapeDtypeStruct(shape, self._dtype)

    def precompile(self, batch_size: int, *, time_steps: Optional[int] = None,
                   repeat_steps: Optional[int] = None, train: bool = True,
                   inference: bool = True) -> "MultiLayerNetwork":
        """AOT-compile the train/output/loss steps for one batch
        signature ahead of the first batch (reference has no analog —
        DL4J compiles nothing; on XLA this moves the multi-second
        compile off the serving/training critical path).

        Uses `jit.lower(ShapeDtypeStruct...).compile()` and stores the
        executables on the PrecompiledDispatch wrappers, so the later
        `fit`/`output` calls with matching shapes run with ZERO
        additional XLA compilations (`xla_compilations_total` stays
        flat). For truncated-BPTT nets every distinct window length of
        the schedule is precompiled. `repeat_steps` additionally
        precompiles the fused `fit_batch_repeated(steps=repeat_steps)`
        dispatch."""
        self._check_init()
        x_s = self._feature_struct(batch_size, time_steps)
        params_s = compile_cache_mod.abstract_like(self.params_tree)
        state_s = compile_cache_mod.abstract_like(self.state_tree)
        y_s = jax.eval_shape(
            lambda p, s, x: self._forward_pure(p, s, x, False, None,
                                               None)[0],
            params_s, state_s, x_s)
        y_s = jax.ShapeDtypeStruct(y_s.shape, y_s.dtype)
        if inference:
            self._output_fn.precompile(params_s, state_s, x_s, None)
            self._loss_fn_jit.precompile(params_s, state_s, x_s, y_s,
                                         None, None)
        if not train:
            return self
        opt_s = compile_cache_mod.abstract_like(self.opt_state)
        it_s = jax.ShapeDtypeStruct((), jnp.int32)
        rng_s = jax.ShapeDtypeStruct(tuple(self._rng.shape),
                                     self._rng.dtype)
        tbptt = (self.conf.backprop_type == BackpropType.TRUNCATED_BPTT
                 and len(x_s.shape) == 3 and len(y_s.shape) == 3)
        if tbptt:
            # One executable per distinct window length of the schedule,
            # against the carry-merged state (what _fit_tbptt passes).
            b = x_s.shape[0]
            carry = tuple(
                layer.seed_recurrent_state(b, self._dtype)
                if layer.is_recurrent() else {} for layer in self.layers)
            merged_s = tuple(
                {**st, **compile_cache_mod.abstract_like(c)}
                for st, c in zip(state_s, carry))
            T, L = x_s.shape[1], self.conf.tbptt_fwd_length
            for w in sorted({min(L, T)} | {T % L} - {0}):
                self._train_step_fn.precompile(
                    params_s, opt_s, merged_s, it_s, rng_s,
                    jax.ShapeDtypeStruct((b, w, x_s.shape[2]),
                                         x_s.dtype),
                    jax.ShapeDtypeStruct((b, w, y_s.shape[2]),
                                         y_s.dtype),
                    None, None)
        else:
            # Two signatures: maskless (direct _do_step), and
            # the ones-(b,1) labels mask the default fit loop's
            # pad-to-bucket iterator synthesizes on EVERY batch (see
            # data/iterators.py: uniform mask structure across the
            # epoch) — without the latter, a plain fit() after
            # precompile() would still pay one compile.
            lm_s = jax.ShapeDtypeStruct((x_s.shape[0], 1), jnp.float32)
            for lmask in (None, lm_s):
                self._train_step_fn.precompile(
                    params_s, opt_s, state_s, it_s, rng_s, x_s, y_s,
                    None, lmask)
            if repeat_steps:
                self._multi_step_repeat_fn.precompile(
                    params_s, opt_s, state_s, it_s, rng_s, x_s, y_s,
                    None, None, int(repeat_steps))
        return self

    # ------------------------------------------------------------------- fit
    # `fit` itself is Trainer's (nn/stepping.py); these are its hooks.
    def _batches(self, data, labels, batch_size, epochs):
        return as_iterator(data, labels, batch_size)

    def _coerce(self, ds: DataSet) -> DataSet:
        return ds

    @staticmethod
    def _batch_signature(ds: DataSet):
        # .shape directly — np.asarray on a device-resident array
        # would force a d2h copy per batch in the hot loop
        f, l = ds.features, ds.labels
        return (f.shape if hasattr(f, "shape") else np.asarray(f).shape,
                l.shape if hasattr(l, "shape") else np.asarray(l).shape,
                ds.features_mask is None, ds.labels_mask is None)

    def _input_shapes(self, batch_size, time_steps):
        return [self._feature_struct(batch_size, time_steps).shape]

    def fit_batches(self, batches: Sequence) -> "MultiLayerNetwork":
        """K optimizer steps over K same-shaped DataSets in ONE device
        dispatch (jitted lax.scan; the ComputationGraph.fit_batches
        analog). Listeners fire per step afterwards. Truncated-BPTT
        batches (rank-3 features AND labels) fuse too: each scan step
        runs its batch's full window schedule with a fresh carry —
        scan-vs-loop bit-identical to calling fit per batch."""
        self._check_init()
        packed = [(self._cast_features(b.features), jnp.asarray(b.labels),
                   None if b.features_mask is None
                   else jnp.asarray(b.features_mask),
                   None if b.labels_mask is None
                   else jnp.asarray(b.labels_mask))
                  for b in (batches if isinstance(batches, (list, tuple))
                            else list(batches))]
        stack = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *packed)
        self._rnn_carry = None
        if self.conf.backprop_type == BackpropType.TRUNCATED_BPTT and \
                packed[0][0].ndim == 3 and packed[0][1].ndim == 3:
            T = packed[0][0].shape[1]
            windows = -(-T // self.conf.tbptt_fwd_length)
            out = self._multi_step_stacked_tbptt_fn(
                self.params_tree, self.opt_state, self.state_tree,
                self._iteration_device(None), self._rng, *stack)
            self._commit_multi(out, len(packed) * windows,
                               listener_events=len(packed))
            return self
        if self.conf.backprop_type == BackpropType.TRUNCATED_BPTT and \
                not getattr(self, "_warned_tbptt_labels", False):
            log.warning(
                "Truncated BPTT requires rank-3 (time-series) features "
                "and labels — using standard BPTT")
            self._warned_tbptt_labels = True
        out = self._multi_step_stacked_fn(
            self.params_tree, self.opt_state, self.state_tree,
            self._iteration_device(None), self._rng, *stack)
        self._commit_multi(out, len(packed))
        return self

    def fit_batch_repeated(self, ds: DataSet, steps: int
                           ) -> "MultiLayerNetwork":
        """`steps` repeats of one device-resident minibatch in one
        dispatch (lax.scan with the batch closed over — not replicated
        in HBM). For truncated-BPTT batches each repeat runs the full
        window schedule with a fresh recurrent carry (one optimizer step
        PER WINDOW, so model.iteration advances steps*ceil(T/L))."""
        self._check_init()
        self._rnn_carry = None
        args = (self._cast_features(ds.features), jnp.asarray(ds.labels),
                None if ds.features_mask is None
                else jnp.asarray(ds.features_mask),
                None if ds.labels_mask is None
                else jnp.asarray(ds.labels_mask))
        # shape metadata only — np.asarray here would d2h-copy a
        # device-resident batch inside benchmarks' timed regions
        if self.conf.backprop_type == BackpropType.TRUNCATED_BPTT and \
                args[0].ndim == 3:
            if args[1].ndim != 3:
                # mirror _fit_batch's rank-2-labels fallback, loudly
                if not getattr(self, "_warned_tbptt_labels", False):
                    log.warning(
                        "Truncated BPTT requires rank-3 (time-series) "
                        "labels; got rank-%d — using standard BPTT",
                        args[1].ndim)
                    self._warned_tbptt_labels = True
            else:
                T = args[0].shape[1]
                windows = -(-T // self.conf.tbptt_fwd_length)
                out = self._multi_step_repeat_tbptt_fn(
                    self.params_tree, self.opt_state, self.state_tree,
                    self._iteration_device(None), self._rng, *args,
                    int(steps))
                self._commit_multi(out, int(steps) * windows,
                                   listener_events=int(steps))
                return self
        out = self._multi_step_repeat_fn(
            self.params_tree, self.opt_state, self.state_tree,
            self._iteration_device(None), self._rng, *args, int(steps))
        self._commit_multi(out, int(steps))
        return self

    def fit_solver(self, x, y, *, max_iterations: int = 100,
                   tolerance: float = 1e-6, fmask=None, lmask=None) -> float:
        """Full-batch optimization with the configured non-SGD solver
        (reference Solver.java:43-60 dispatch; LINE_GRADIENT_DESCENT /
        CONJUGATE_GRADIENT / LBFGS). Returns the final score."""
        from ..optimize.solvers import solver_for
        solver = solver_for(self.conf.optimization_algo,
                            max_iterations=max_iterations,
                            tolerance=tolerance)
        return solver.optimize(self, x, y, fmask, lmask)

    # -------------------------------------------------------------- pretrain
    def pretrain(self, data, *, epochs: int = 1, batch_size: int = 32
                 ) -> "MultiLayerNetwork":
        """Greedy layerwise unsupervised pretraining (reference
        MultiLayerNetwork.pretrain(DataSetIterator):1036): for each
        pretrainable layer in order, feed the frozen prefix's activations
        and step that layer's own pretrain objective with its own updater.
        Labels in `data` are ignored (features-only, like the reference)."""
        self._check_init()
        if isinstance(data, np.ndarray):  # features-only array is fine here
            data = DataSet(data, np.zeros((data.shape[0], 1), np.float32))
        for i, layer in enumerate(self.layers):
            if not layer.is_pretrainable() or layer.frozen:
                continue  # frozen: transfer-learning protection, like fit()
            prefix = jax.jit(functools.partial(self._prefix_activations, i))
            step = self._pretrain_step_fn(i, layer)
            params_i = self.params_tree[i]
            opt_i = layer.updater.init(params_i)
            it_count = jnp.asarray(0, jnp.int32)
            rng = self._rng
            last = None
            for _ in range(epochs):
                it = as_iterator(data, None, batch_size)
                for ds in it:
                    x = prefix(self.params_tree, self.state_tree,
                               self._cast_features(ds.features))
                    params_i, opt_i, it_count, rng, last = step(
                        params_i, opt_i, it_count, rng, x)
            self._rng = rng
            if last is not None:
                self.score_value = last
            self.params_tree = tuple(
                params_i if j == i else p
                for j, p in enumerate(self.params_tree))
        return self

    def _prefix_activations(self, i, params, state, x):
        """Inference-mode activations feeding layer i (its preprocessor
        included)."""
        a = x
        for j in range(i):
            p = self.conf.preprocessor(j)
            if p is not None:
                a = p(a)
            a, _ = self.layers[j].forward(params[j], state[j], a,
                                          train=False, rng=None, mask=None)
        p = self.conf.preprocessor(i)
        if p is not None:
            a = p(a)
        return a

    def _pretrain_step_fn(self, i, layer):
        def step(params_i, opt_i, iteration, rng, x):
            rng, sub = jax.random.split(rng)
            loss, grads = layer.pretrain_grads(params_i, x, sub)
            g = normalize_layer_gradients(
                grads, layer.gradient_normalization,
                layer.gradient_normalization_threshold)
            updates, opt2 = layer.updater.update(g, opt_i, iteration)
            new_p = jax.tree_util.tree_map(
                lambda p, u: p - u.astype(p.dtype), params_i, updates)
            return new_p, opt2, iteration + 1, rng, loss
        return jax.jit(step)

    def _fit_batch(self, ds: DataSet, do_step=None):
        do_step = do_step or self._do_step
        if self.conf.backprop_type == BackpropType.TRUNCATED_BPTT and \
                ds.features.ndim == 3:
            if ds.labels.ndim == 3:
                self._fit_tbptt(ds, do_step)
                return
            # Reference doTruncatedBPTT requires rank-3 labels and falls
            # back with a warning; slicing 2-D labels on axis 1 would window
            # the class axis instead of time.
            if not getattr(self, "_warned_tbptt_labels", False):
                log.warning(
                    "Truncated BPTT requires rank-3 (time-series) labels; "
                    "got rank-%d — using standard BPTT", ds.labels.ndim)
                self._warned_tbptt_labels = True
        self._rnn_carry = None  # standard BPTT: every batch starts fresh
        do_step(ds.features, ds.labels, ds.features_mask, ds.labels_mask)

    def _fit_tbptt(self, ds: DataSet, do_step):
        """Truncated BPTT: slide a window of tbptt_fwd_length over the time
        axis, one optimizer step per window (reference doTruncatedBPTT:1266).
        Recurrent state carry across windows rides the state tree, seeded
        here (the reference's rnnActivateUsingStoredState)."""
        T = ds.features.shape[1]
        L = self.conf.tbptt_fwd_length
        self.rnn_clear_previous_state()
        self._seed_recurrent_states(ds.features.shape[0])
        for start in range(0, T, L):
            end = min(start + L, T)
            fm = None if ds.features_mask is None else ds.features_mask[:, start:end]
            lm = None if ds.labels_mask is None else ds.labels_mask[:, start:end]
            do_step(ds.features[:, start:end], ds.labels[:, start:end], fm, lm)
        self.rnn_clear_previous_state()

    def _cast_features(self, x):
        x = jnp.asarray(x)
        if jnp.issubdtype(x.dtype, jnp.floating):
            x = x.astype(self._dtype)
        return x

    def _do_step(self, x, y, fmask, lmask):
        self._run_and_commit(
            self._cast_features(x), jnp.asarray(y),
            None if fmask is None else jnp.asarray(fmask),
            None if lmask is None else jnp.asarray(lmask))

    # The recurrent carry is merged into the state only on stateful paths
    # (tbptt windows, rnn_time_step) and split back out on commit, so the
    # canonical state_tree never contains h/c.
    def _merged_state(self):
        if self._rnn_carry is None:
            return self.state_tree
        return tuple({**st, **carry} for st, carry in
                     zip(self.state_tree, self._rnn_carry))

    def _commit_state(self, new_state):
        if self._rnn_carry is None:
            self.state_tree = new_state
            return
        base, carry = [], []
        for st in new_state:
            carry.append({k: v for k, v in st.items() if k in RECURRENT_CARRY_KEYS})
            base.append({k: v for k, v in st.items() if k not in RECURRENT_CARRY_KEYS})
        self.state_tree = tuple(base)
        self._rnn_carry = tuple(carry)

    # ------------------------------------------------------------- inference
    def output(self, x, train: bool = False, features_mask=None) -> np.ndarray:
        """Forward pass, inference mode (reference output():1664)."""
        self._check_init()
        xa = jnp.asarray(x)
        fm = None if features_mask is None else jnp.asarray(features_mask)
        telemetry_mod.note_step_signature(
            f"mln_output#{self._probe_tag}",
            telemetry_mod.shape_signature(xa, fm))
        out = self._output_fn(self.params_tree, self.state_tree, xa, fm)
        return np.asarray(out)

    def feed_forward(self, x, train: bool = False) -> List[np.ndarray]:
        """All layer activations incl. input (reference feedForward():725)."""
        self._check_init()
        _, _, acts = self._forward_pure(
            self.params_tree, self.state_tree, jnp.asarray(x), train, None, None)
        return [np.asarray(x)] + [np.asarray(a) for a in acts]

    def predict(self, x) -> np.ndarray:
        """Argmax class predictions (reference predict())."""
        return np.argmax(self.output(x), axis=-1)

    # ----------------------------------------------------------------- score
    def score(self, ds: DataSet | None = None, x=None, y=None) -> float:
        """Mean loss + regularization (reference score():1985)."""
        self._check_init()
        if ds is not None:
            x, y = ds.features, ds.labels
            fmask, lmask = ds.features_mask, ds.labels_mask
        else:
            fmask = lmask = None
        if x is None:
            if self.score_value is None:
                raise ValueError("No data given and no cached score")
            return float(self.score_value)
        loss = self._loss_fn_jit(
            self.params_tree, self.state_tree, jnp.asarray(x), jnp.asarray(y),
            None if fmask is None else jnp.asarray(fmask),
            None if lmask is None else jnp.asarray(lmask))
        return float(loss)

    def compute_gradient_and_score(self, ds: DataSet):
        """(gradients pytree, score) without updating params (reference
        computeGradientAndScore():1995 + gradient())."""
        self._check_init()
        (loss, _), grads = jax.value_and_grad(self._loss_pure, has_aux=True)(
            self.params_tree, self.state_tree,
            jnp.asarray(ds.features), jnp.asarray(ds.labels),
            None if ds.features_mask is None else jnp.asarray(ds.features_mask),
            None if ds.labels_mask is None else jnp.asarray(ds.labels_mask),
            None, False)
        return grads, float(loss)

    # ------------------------------------------------------------ evaluation
    def evaluate(self, data, labels=None, batch_size: int = 128):
        from ..eval.evaluation import Evaluation
        self._check_init()
        it = as_iterator(data, labels, batch_size)
        ev = Evaluation()
        for ds in it:
            out = self.output(ds.features, features_mask=ds.features_mask)
            ev.eval(ds.labels, out, mask=ds.labels_mask)
        return ev

    def evaluate_regression(self, data, labels=None, batch_size: int = 128):
        from ..eval.evaluation import RegressionEvaluation
        self._check_init()
        it = as_iterator(data, labels, batch_size)
        ev = RegressionEvaluation()
        for ds in it:
            out = self.output(ds.features, features_mask=ds.features_mask)
            ev.eval(ds.labels, out, mask=ds.labels_mask)
        return ev

    # ------------------------------------------------------------ param view
    def params(self) -> np.ndarray:
        """Flat parameter vector (reference params())."""
        self._check_init()
        return np.asarray(param_utils.flatten_params(self.params_tree))

    def set_params(self, flat) -> None:
        self._check_init()
        self.params_tree = param_utils.unflatten_params(
            self.params_tree, jnp.asarray(flat))

    def num_params(self) -> int:
        self._check_init()
        return param_utils.num_params(self.params_tree)

    # ------------------------------------------------------------- rnn state
    def _seed_recurrent_states(self, batch: int):
        """Activate the recurrent carry with zeroed state (the reference's
        stateMap initialization)."""
        if self._rnn_carry is None:
            self._rnn_carry = tuple(
                layer.seed_recurrent_state(batch, self._dtype)
                if layer.is_recurrent() else {}
                for layer in self.layers)

    def rnn_clear_previous_state(self):
        """Drop recurrent carries (reference rnnClearPreviousState())."""
        self._rnn_carry = None

    def rnn_time_step(self, x) -> np.ndarray:
        """Streaming inference with carried recurrent state (reference
        rnnTimeStep()). Accepts [batch, features] (one step) or
        [batch, time, features]. Raises for layers that cannot stream
        (GravesBidirectionalLSTM, like the reference)."""
        self._check_init()
        for layer in self.layers:
            # any full-sequence layer (bidirectional LSTM, attention)
            # must reject streaming, recurrent or not
            if not layer.supports_streaming():
                raise NotImplementedError(
                    f"{type(layer).__name__} does not support rnn_time_step "
                    "(needs the full sequence)")
        x = self._cast_features(x)
        if self._rnn_carry is not None:
            for carry in self._rnn_carry:
                if "h" in carry and carry["h"].shape[0] != x.shape[0]:
                    stored = carry["h"].shape[0]
                    # Typed error + explicit reset: leaving the stale
                    # carry behind would corrupt the NEXT streaming
                    # caller (stored-state poisoning).
                    self._rnn_carry = None
                    raise RnnStateMismatchError(
                        f"rnn_time_step batch size {x.shape[0]} != stored "
                        f"state batch size {stored}; stored recurrent "
                        "state has been reset")
        self._seed_recurrent_states(x.shape[0])
        out, new_state = self._rnn_step_fn(
            self.params_tree, self._merged_state(), x)
        self._commit_state(new_state)
        return np.asarray(out)

    # --------------------------------------------------------------- helpers
    def set_listeners(self, *listeners):
        self.listeners = list(listeners)
        return self

    def add_listener(self, listener):
        self.listeners.append(listener)
        return self

    def clone(self) -> "MultiLayerNetwork":
        net = MultiLayerNetwork(self.conf.clone())
        if self._initialized:
            net.init(dtype=self._dtype)
            # Deep-copy: the donated train step reuses buffers in place,
            # so shared arrays across nets would die on first fit.
            net.params_tree = param_utils.tree_copy(self.params_tree)
            net.opt_state = param_utils.tree_copy(self.opt_state)
            net.state_tree = param_utils.tree_copy(self.state_tree)
            net.iteration = self.iteration
        return net

    def summary(self) -> str:
        lines = ["idx | layer | params"]
        for i, layer in enumerate(self.layers):
            n = param_utils.num_params(self.params_tree[i]) if self._initialized else "?"
            lines.append(f"{i} | {type(layer).__name__} | {n}")
        if self._initialized:
            lines.append(f"Total params: {self.num_params()}")
        return "\n".join(lines)
