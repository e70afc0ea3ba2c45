"""ParallelWrapper: synchronous data-parallel training over a device mesh.

Reference parity: parallelism/ParallelWrapper.java:48-264 — replicate the
model across N devices (one trainer thread each, DefaultTrainer.java),
round-robin minibatches, average parameters + updater state every
`averagingFrequency` iterations via Nd4j.averageAndPropagate (:219). The
reference's own test TestCompareParameterAveragingSparkVsSingleMachine
proves averaging at frequency 1 equals large-batch single-machine SGD.

TPU-native redesign: that equivalence is taken as the design license — the
N-replica thread zoo collapses into ONE jitted train step whose batch input
is sharded over the mesh's "data" axis. XLA inserts the gradient allreduce
(psum over ICI) exactly where the reference does a parameter average; params
stay replicated, so there is no separate "propagate" step and no thread
synchronization.

averaging_frequency > 1 (ParallelWrapper.java:417-424; Spark
ParameterAveragingTrainingMaster splits so each worker runs
`averagingFrequency` minibatches between syncs, :346-357) is local SGD:
params/updater-state/layer-state get a leading replica axis sharded over
"data", the per-replica step is the SAME jitted train step vmapped over
that axis (so each device takes independent local steps with zero
cross-device traffic), and every F steps a jitted mean-over-replicas +
re-broadcast performs the parameter average (XLA lowers it to an
allreduce over ICI — the averageAndPropagate analog).
"""
from __future__ import annotations

import logging
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from . import mesh as mesh_lib
# The pad primitives live with the data pipeline (data/padding.py) so the
# pad-to-bucket iterator and the DP/SP wrappers share ONE contract; the
# historical names stay importable from here (sequence.py does).
from ..data.padding import pad_lmask_zero_weight, repeat_tail_rows  # noqa: F401
from ..nn.layers.recurrent import RECURRENT_CARRY_KEYS
from ..optimize import metrics as metrics_mod

log = logging.getLogger(__name__)


class ParallelWrapper:
    """Drop-in DP trainer for MultiLayerNetwork / ComputationGraph
    (reference ParallelWrapper.Builder surface, minus the thread zoo)."""

    def __init__(self, model, mesh: Optional[Mesh] = None,
                 workers: Optional[int] = None,
                 averaging_frequency: int = 1,
                 prefetch_buffer: int = 8):
        self.model = model
        self.mesh = mesh if mesh is not None else \
            mesh_lib.data_parallel_mesh(workers)
        if mesh_lib.DATA_AXIS not in self.mesh.axis_names:
            raise ValueError(
                f"ParallelWrapper needs a mesh with a '{mesh_lib.DATA_AXIS}' "
                f"axis; got axes {self.mesh.axis_names}")
        self.data_shards = int(self.mesh.shape[mesh_lib.DATA_AXIS])
        # Multi-host: every process feeds its LOCAL data partition; the
        # global batch is their concatenation (Spark partition semantics).
        self.multiprocess = mesh_lib.is_multiprocess(self.mesh)
        if self.multiprocess:
            nproc = jax.process_count()
            if self.data_shards % nproc != 0 or self.data_shards < nproc:
                raise ValueError(
                    f"multi-host mesh: data axis size ({self.data_shards}) "
                    f"must be a positive multiple of the process count "
                    f"({nproc}) so every process owns an equal slice")
            self.local_shards = self.data_shards // nproc
        else:
            self.local_shards = self.data_shards
        if int(averaging_frequency) < 1:
            raise ValueError("averaging_frequency must be >= 1")
        self.averaging_frequency = int(averaging_frequency)
        self.prefetch_buffer = prefetch_buffer
        # Called with the model's iteration after every fit_batch — the
        # cluster health plane wires its step-progress watchdog here
        # (parallel/cluster_health.py), and it stays open for listeners
        # that need the wrapper (not net) step granularity.
        self.step_hooks = []
        self._warned_pad = False
        self._placed = False
        # ---- local-SGD (averaging_frequency > 1) machinery ----
        self._stacked = None          # (params, opt, state) with replica axis
        self._stacked_rngs = None
        self._synced_params_ref = None
        self._since_avg = 0
        self._stacked_step = None
        self._jit_helpers = None

    # ------------------------------------------------------------------ build
    @staticmethod
    def builder(model) -> "ParallelWrapperBuilder":
        return ParallelWrapperBuilder(model)

    def _place_model(self):
        """Replicate params/opt/state across the mesh once (the reference
        clones the model per device at zoo creation, ParallelWrapper:460).
        Multi-host, this is the broadcast-NetBroadcastTuple analog: every
        process holds identical values (same-seed init or restore) and the
        assembled global arrays are replicated over all devices."""
        net = self.model
        net.params_tree = mesh_lib.replicate(self.mesh, net.params_tree)
        net.opt_state = mesh_lib.replicate(self.mesh, net.opt_state)
        net.state_tree = mesh_lib.replicate(self.mesh, net.state_tree)
        net._rng = mesh_lib.replicate(self.mesh, net._rng)
        self._placed = True

    def _check_local_divisible(self, n: int):
        """Multi-host SPMD requires every process to compile and run the
        SAME program — per-process zero-weight pad masks could differ
        between processes, so non-divisible local batches are rejected
        (the reference repartitions to balance, BalancedPartitioner)."""
        if n % self.local_shards != 0:
            raise ValueError(
                f"multi-host training requires the per-process batch ({n}) "
                f"to be divisible by the process-local shard count "
                f"({self.local_shards}); repartition your data")

    def _shard_arr(self, a, cast_dtype=None):
        if a is None:
            return None
        if self.multiprocess:
            a = np.asarray(a)
            self._check_local_divisible(a.shape[0])
            if cast_dtype is not None and a.dtype.kind == "f":
                a = a.astype(cast_dtype)
            return mesh_lib.place(a, mesh_lib.batch_sharded(self.mesh),
                                  self.mesh)
        if isinstance(a, jax.Array) and a.shape[0] % self.data_shards == 0:
            # Already device-resident and evenly divisible: reshard
            # device-to-device, never touching the host.
            if cast_dtype is not None and jnp.issubdtype(a.dtype, jnp.floating):
                a = a.astype(cast_dtype)
            return jax.device_put(a, mesh_lib.batch_sharded(self.mesh))
        a = np.asarray(a)
        if cast_dtype is not None and a.dtype.kind == "f":
            a = a.astype(cast_dtype)
        padded, _ = mesh_lib.pad_batch_to_multiple(a, self.data_shards)
        return jax.device_put(padded, mesh_lib.batch_sharded(self.mesh))

    def _pad_lmask(self, lmask, n: int):
        """Zero-weight labels mask covering `pad` appended rows, constructed
        so the LOSS (numerator and normalization) exactly matches
        single-device training on the original batch:
          * no user mask  -> ones (n,1) + zero pad rows; the rank-2 mask
            path divides by sum(mask) = n, the unpadded mean.
          * rank-1 user mask (per-example weights) -> zero-padded and
            scaled by padded_n/n; the rank-1 mean path then yields
            sum(sa*m)/n, the unpadded value (exact by linearity).
          * rank>=2 user mask -> zero pad rows; sum(mask) is unchanged.
        Caveat (hence the warning): pad rows still traverse the FORWARD
        pass, so batch-statistics state (BatchNormalization train-mode
        mean/var and committed running stats) and shape-dependent dropout
        draws include them — use divisible batch sizes for bit-exact
        equivalence on BN/dropout models."""
        pad = (-n) % self.data_shards
        if pad == 0:
            return lmask
        if not self._warned_pad:
            log.warning(
                "Batch size %d not divisible by %d data shards; padding with "
                "zero-loss-weight copies of the tail example. Loss/gradients "
                "match single-device exactly, but BatchNorm batch statistics "
                "and dropout draws include the pad rows — use divisible "
                "batch sizes for bit-exact equivalence", n, self.data_shards)
            self._warned_pad = True
        return pad_lmask_zero_weight(lmask, n, pad)

    # -------------------------------------------------------------------- fit
    def fit(self, data, labels=None, *, epochs: int = 1,
            batch_size: int = 128) -> "ParallelWrapper":
        """Reuses the single-device epoch/listener loop with the sharded
        step substituted, so loop semantics can never diverge."""
        self.model._check_init()
        # Device prefetch stages batches already sharded over the mesh
        # (device_put with the batch NamedSharding on the producer
        # thread); _shard_arr then sees a correctly-sharded jax.Array
        # and passes it through without a host round-trip. Indivisible
        # ragged batches bypass staging (batch_divisor) and take the
        # host-side zero-weight pad path as before. Multi-host meshes
        # keep host feeding: per-process placement happens inside
        # _shard_arr and cannot run on a producer thread safely.
        prefetch = dict(prefetch_to_device=not self.multiprocess,
                        prefetch_sharding=None if self.multiprocess
                        else mesh_lib.batch_sharded(self.mesh),
                        prefetch_divisor=self.data_shards)
        self.model.fit(data, labels, epochs=epochs, batch_size=batch_size,
                       async_queue_size=self.prefetch_buffer,
                       step_fn=self.fit_batch, **prefetch)
        self.finalize()
        return self

    def fit_batch(self, ds) -> None:
        """One DP step. With averaging_frequency == 1 this is a globally-
        synchronous sharded step (tBPTT windowing included, via the net's
        own dispatch). With frequency > 1 it is one LOCAL step per replica
        (see module docstring). Accepts a DataSet for MultiLayerNetwork or
        a MultiDataSet/DataSet for ComputationGraph."""
        net = self.model
        if self.averaging_frequency > 1:
            self._local_round(ds)
            self._fire_step_hooks()
            return
        metrics_mod.registry().counter(
            "data_parallel_steps_total",
            "ParallelWrapper optimizer steps by mode"
            ).labels(mode="sync", workers=str(self.data_shards)).inc()
        if not self._placed:
            net._check_init()
            self._place_model()
        if hasattr(net, "_pack"):  # ComputationGraph
            # reuse the graph's own dispatch (tBPTT windowing included)
            # with the sharded step substituted — the MLN do_step pattern
            net.fit_batch(net._coerce(ds), do_step=self._sync_graph_step)
            self._fire_step_hooks()
            return
        net._fit_batch(ds, do_step=self._sync_step)
        self._fire_step_hooks()

    def _fire_step_hooks(self):
        """Report the model's iteration to every registered hook. The
        int() here reads a host-side counter (net.iteration is python),
        so no device sync is added to the step path."""
        if not self.step_hooks:
            return
        it = int(self.model.iteration)
        for h in list(self.step_hooks):
            h(it)

    def _sync_graph_step(self, inputs, labels, fm, lm):
        """Sharded analog of ComputationGraph._run_and_commit for one
        (possibly tBPTT-windowed) packed batch."""
        net = self.model
        n = next(iter(inputs.values())).shape[0]
        if self.multiprocess:
            self._check_local_divisible(n)
        elif n % self.data_shards != 0:
            if net._rnn_carry is not None:
                # the recurrent carry is sized to the true batch; padding
                # the data but not the carry would shape-mismatch in jit
                raise ValueError(
                    f"truncated-BPTT batch size {n} must divide the "
                    f"{self.data_shards}-way data mesh")
            lm = {name: self._pad_lmask(lm.get(name), n) for name in labels}
        shard = lambda d: {k: self._shard_arr(v) for k, v in d.items()}
        net._run_and_commit(shard(inputs), shard(labels), shard(fm),
                            shard(lm), mesh=self.mesh)

    def _prep_graph_batch(self, ds):
        """Pack a (Multi)DataSet for the graph and zero-weight any pad rows
        (shared by the sync and local-SGD paths so the padding rule can
        never diverge between them)."""
        net = self.model
        inputs, labels, fm, lm = net._pack(net._coerce(ds))
        n = next(iter(inputs.values())).shape[0]
        if self.multiprocess:
            self._check_local_divisible(n)
        elif n % self.data_shards != 0:
            # Every output head gets a zero-weight mask over pad rows.
            lm = {name: self._pad_lmask(lm.get(name), n) for name in labels}
        return inputs, labels, fm, lm, n

    def _sync_step(self, x, y, fmask, lmask) -> None:
        """Sharded analog of MultiLayerNetwork._do_step: shard the inputs
        over the mesh's data axis, then delegate invoke+commit to the net
        so the commit tail can never diverge from the single-device path."""
        net = self.model
        if self.multiprocess:
            self._check_local_divisible(x.shape[0])
        elif x.shape[0] % self.data_shards != 0:
            if net._rnn_carry is not None:
                raise ValueError(
                    f"truncated-BPTT batch size {x.shape[0]} must divide "
                    f"the {self.data_shards}-way data mesh")
            lmask = self._pad_lmask(lmask, x.shape[0])
        net._run_and_commit(
            self._shard_arr(x, cast_dtype=net._dtype), self._shard_arr(y),
            self._shard_arr(fmask), self._shard_arr(lmask), mesh=self.mesh)

    # ----------------------------------------------------- local SGD (freq>1)
    def _mark_local_step(self):
        """Telemetry for one local-SGD round: every replica took one
        independent step (worker-labeled, the reference's per-trainer
        iteration counters), and the nets' commit paths were bypassed so
        the global iteration counter is bumped here."""
        reg = metrics_mod.registry()
        c = reg.counter("data_parallel_worker_steps_total",
                        "Local-SGD steps per replica (worker-labeled)")
        for w in range(self.data_shards):
            c.labels(worker=str(w)).inc()
        reg.counter("data_parallel_steps_total",
                    "ParallelWrapper optimizer steps by mode"
                    ).labels(mode="local_sgd",
                             workers=str(self.data_shards)).inc()
        metrics_mod.record_train_step(1)

    def _mark_average(self):
        metrics_mod.registry().counter(
            "data_parallel_averages_total",
            "Parameter averages across replicas (averageAndPropagate)"
            ).labels(workers=str(self.data_shards)).inc()

    def _build_local_machinery(self, n_data_args: int):
        """Jitted helpers for the replica-stacked representation."""
        from jax.sharding import NamedSharding, PartitionSpec
        W = self.data_shards
        stacked_sh = NamedSharding(self.mesh, PartitionSpec(mesh_lib.DATA_AXIS))
        tmap = jax.tree_util.tree_map

        # Per-replica local step: the net's own jitted step, vmapped over
        # the replica axis. iteration is shared (in_axes None); params/opt/
        # state/rng/data are per-replica (axis 0, sharded over "data"), so
        # each device computes its replica with no collective ops.
        in_axes = (0, 0, 0, None, 0) + (0,) * n_data_args
        self._stacked_step = jax.jit(jax.vmap(
            self.model._train_step_fn, in_axes=in_axes,
            out_axes=(0, 0, 0, None, 0, 0)))

        def stack(t):  # replicate net trees onto the replica axis
            return tmap(lambda a: jnp.broadcast_to(a[None], (W,) + a.shape), t)

        def avg_one(a):
            m = jnp.mean(a, axis=0) if jnp.issubdtype(a.dtype, jnp.floating) \
                else a[0]
            return jnp.broadcast_to(m[None], a.shape)

        def avg(t):  # averageAndPropagate: mean over replicas, re-broadcast
            return tmap(avg_one, t)

        def _avg_keep(st):
            return {k: (v if k in RECURRENT_CARRY_KEYS else avg_one(v))
                    for k, v in st.items()}

        def avg_keep_carry(t):
            # tBPTT variant: params/opt/BN-stats average, but each
            # replica's recurrent carry (h/c) belongs to ITS data shard
            # and must never be averaged across replicas. State is a
            # tuple of dicts for MultiLayerNetwork, a dict of dicts for
            # ComputationGraph.
            params, opt, state = t
            if isinstance(state, dict):
                state = {name: _avg_keep(st) for name, st in state.items()}
            else:
                state = tuple(_avg_keep(st) for st in state)
            return tmap(avg_one, params), tmap(avg_one, opt), state

        def _strip(st):
            return {k: v for k, v in st.items()
                    if k not in RECURRENT_CARRY_KEYS}

        def strip_carry(state):
            if isinstance(state, dict):
                return {name: _strip(st) for name, st in state.items()}
            return tuple(_strip(st) for st in state)

        def take0(t):  # replicas are equal post-average; unstack view
            return tmap(lambda a: a[0], t)

        # take0 outputs replicate so they stay addressable on every process
        # (replica 0's device may be remote under multi-host).
        self._jit_helpers = {
            "stack": jax.jit(stack, out_shardings=stacked_sh),
            "avg": jax.jit(avg, out_shardings=stacked_sh),
            "avg_keep_carry": jax.jit(avg_keep_carry,
                                      out_shardings=stacked_sh),
            "strip_carry": jax.jit(strip_carry, out_shardings=stacked_sh),
            "take0": jax.jit(take0,
                             out_shardings=mesh_lib.replicated(self.mesh)),
            "split_rngs": jax.jit(lambda k: jax.random.split(k, W),
                                  out_shardings=stacked_sh),
        }

    def _ensure_stacked(self, n_data_args: int):
        net = self.model
        if self._stacked is not None:
            # Restack if the net's params were swapped behind our back
            # (checkpoint restore, direct net.fit, transfer surgery...):
            # the cached replica stack would silently discard them.
            if net.params_tree is self._synced_params_ref:
                return
            self._stacked = None
        if self._stacked_step is None:
            self._build_local_machinery(n_data_args)
        if not self._placed:
            self._place_model()  # stack-jit inputs must be mesh-global
        h = self._jit_helpers
        self._stacked = h["stack"]((net.params_tree, net.opt_state,
                                    self._net_state_tree()))
        self._synced_params_ref = net.params_tree
        self._stacked_rngs = h["split_rngs"](net._rng)
        self._since_avg = 0

    def _net_state_tree(self):
        net = self.model
        return net._merged_state() if hasattr(net, "_merged_state") \
            else net.state_tree

    def _stack_data(self, a, n: int):
        """Pad (repeating the tail row) + reshape (n,...) → (W, n/W, ...).
        Device-resident arrays are padded/reshaped with jnp ops so they
        never round-trip through host memory."""
        if a is None:
            return None
        W = self.data_shards
        if self.multiprocess:
            # Local rows → (local_shards, chunk, ...); the global replica
            # axis (W rows) is assembled across processes.
            a = np.asarray(a)
            self._check_local_divisible(a.shape[0])
            stacked = a.reshape((self.local_shards, -1) + a.shape[1:])
            return mesh_lib.place(stacked, mesh_lib.batch_sharded(self.mesh),
                                  self.mesh)
        if isinstance(a, jax.Array):
            pad = (-a.shape[0]) % W
            if pad:
                a = jnp.concatenate(
                    [a, jnp.broadcast_to(a[-1:], (pad,) + a.shape[1:])], 0)
            stacked = a.reshape((W, -1) + a.shape[1:])
        else:
            a = np.asarray(a)
            padded, _ = mesh_lib.pad_batch_to_multiple(a, W)
            stacked = padded.reshape((W, -1) + padded.shape[1:])
        return jax.device_put(stacked, mesh_lib.batch_sharded(self.mesh))

    def _local_round(self, ds) -> None:
        """One local step on every replica; average every F-th round.
        Mapping to the reference: each replica plays one DefaultTrainer /
        Spark worker, its shard of this batch is the worker's minibatch,
        and F rounds between averages = averagingFrequency iterations
        (ParallelWrapper.java:417-424)."""
        net = self.model
        net._check_init()
        if hasattr(net, "_pack"):  # ComputationGraph
            from ..nn.conf.builders import BackpropType
            mds = net._coerce(ds)
            if net.conf.backprop_type == BackpropType.TRUNCATED_BPTT:
                # np.ndim reads metadata — no d2h copy of device batches
                if any(np.ndim(f) == 3 for f in mds.features) and \
                        all(np.ndim(l) == 3 for l in mds.labels):
                    self._local_round_tbptt_graph(mds)
                    return
                # mirror the single-device warn-once fallback
                # (graph.py fit_batch): rank-2 labels run standard BPTT
                if not getattr(net, "_warned_tbptt_labels", False):
                    log.warning(
                        "Truncated BPTT requires rank-3 features and "
                        "labels; using standard BPTT")
                    net._warned_tbptt_labels = True
            inputs, labels, fm, lm, n = self._prep_graph_batch(ds)
            data = tuple({k: self._stack_data(v, n) for k, v in d.items()}
                         for d in (inputs, labels, fm, lm))
        else:
            from ..nn.conf.builders import BackpropType
            if net.conf.backprop_type == BackpropType.TRUNCATED_BPTT and \
                    np.ndim(ds.features) == 3 and \
                    np.ndim(ds.labels) == 3:
                self._local_round_tbptt(ds)
                return
            x, y = ds.features, ds.labels
            fmask, lmask = ds.features_mask, ds.labels_mask
            n = np.shape(x)[0]
            if self.multiprocess:
                self._check_local_divisible(n)
            elif n % self.data_shards != 0:
                lmask = self._pad_lmask(lmask, n)
            x = np.asarray(x)
            if x.dtype.kind == "f":
                x = x.astype(np.dtype(net._dtype))
            data = tuple(self._stack_data(a, n)
                         for a in (x, y, fmask, lmask))
        self._ensure_stacked(len(data))
        params, opt, state = self._stacked
        with self.mesh:
            (params, opt, state, _, self._stacked_rngs,
             losses) = self._stacked_step(
                params, opt, state, jnp.asarray(net.iteration, jnp.int32),
                self._stacked_rngs, *data)
        self._stacked = (params, opt, state)
        self._since_avg += 1
        net.iteration += 1
        net.score_value = jnp.mean(losses)
        self._mark_local_step()
        if self._since_avg >= self.averaging_frequency:
            self._stacked = self._jit_helpers["avg"](self._stacked)
            self._since_avg = 0
            self._mark_average()
        # Sync the canonical trees every round (post-average they hold the
        # averaged values; mid-window, replica 0's — the per-worker view a
        # reference listener would see), so Checkpoint/Evaluative listeners
        # never observe parameters stale by a whole averaging window.
        self._sync_net_from_stacked()
        for lst in net.listeners:
            lst.iteration_done(net, net.iteration)

    def _local_round_tbptt(self, ds) -> None:
        """Local SGD over a truncated-BPTT batch (MultiLayerNetwork):
        every replica runs the SAME window schedule on its shard of the
        batch, with the recurrent carry riding the replica-stacked state
        between windows — one optimizer step per window per replica,
        averaging every F windows (matching how a reference worker would
        count its tBPTT iterations)."""
        net = self.model
        x = np.asarray(ds.features)
        n = x.shape[0]
        if self.multiprocess:
            self._check_local_divisible(n)
        elif n % self.data_shards != 0:
            raise ValueError(
                f"truncated-BPTT batch size {n} must divide the "
                f"{self.data_shards}-way data mesh")
        chunk = (n // self.local_shards if self.multiprocess
                 else n // self.data_shards)
        # seed the carry at per-replica chunk size, then (re)stack the
        # state so every replica starts this batch with zero h/c
        net.rnn_clear_previous_state()
        net._seed_recurrent_states(chunk)
        self._ensure_stacked(4)
        params, opt, _ = self._stacked
        with self.mesh:
            state = self._jit_helpers["stack"](net._merged_state())
        self._stacked = (params, opt, state)
        T = x.shape[1]
        L = net.conf.tbptt_fwd_length
        y = np.asarray(ds.labels)
        fmask = None if ds.features_mask is None \
            else np.asarray(ds.features_mask)
        lmask = None if ds.labels_mask is None \
            else np.asarray(ds.labels_mask)
        xc = x.astype(np.dtype(net._dtype)) if x.dtype.kind == "f" else x
        for start in range(0, T, L):
            end = min(start + L, T)
            data = tuple(
                self._stack_data(None if a is None else a[:, start:end], n)
                for a in (xc, y, fmask, lmask))
            params, opt, state = self._stacked
            with self.mesh:
                (params, opt, state, _, self._stacked_rngs,
                 losses) = self._stacked_step(
                    params, opt, state,
                    jnp.asarray(net.iteration, jnp.int32),
                    self._stacked_rngs, *data)
            self._stacked = (params, opt, state)
            self._since_avg += 1
            net.iteration += 1
            net.score_value = jnp.mean(losses)
            self._mark_local_step()
            if self._since_avg >= self.averaging_frequency:
                self._stacked = self._jit_helpers["avg_keep_carry"](
                    self._stacked)
                self._since_avg = 0
                self._mark_average()
            self._sync_net_from_stacked()
            for lst in net.listeners:
                lst.iteration_done(net, net.iteration)
        # batch over: drop the carry (net + next batch reseeds the stack)
        net.rnn_clear_previous_state()
        params, opt, state = self._stacked
        with self.mesh:
            self._stacked = (params, opt,
                             self._jit_helpers["strip_carry"](state))

    def _local_round_tbptt_graph(self, mds) -> None:
        """Local SGD over a truncated-BPTT batch for ComputationGraph —
        the _local_round_tbptt analog (reference behavior: Spark workers
        train tBPTT graphs between averages,
        ParameterAveragingTrainingMaster.java:346-357). Every replica
        runs the SAME window schedule on its shard with the recurrent
        carry riding the replica-stacked state; one optimizer step per
        window per replica; params/opt/non-carry state average every F
        windows. Window slicing mirrors ComputationGraph._fit_tbptt
        (rank-2 static inputs pass whole into every window)."""
        net = self.model
        n = np.shape(mds.features[0])[0]
        if self.multiprocess:
            self._check_local_divisible(n)
        elif n % self.data_shards != 0:
            raise ValueError(
                f"truncated-BPTT batch size {n} must divide the "
                f"{self.data_shards}-way data mesh")
        chunk = (n // self.local_shards if self.multiprocess
                 else n // self.data_shards)
        # Seed a CHUNK-sized carry and stack it per replica before
        # handing control to the graph's own window loop (each replica's
        # carry covers its shard of the batch).
        net.rnn_clear_previous_state()
        net._seed_recurrent_states(chunk)
        self._ensure_stacked(4)
        params, opt, _ = self._stacked
        with self.mesh:
            state = self._jit_helpers["stack"](net._merged_state())
        self._stacked = (params, opt, state)
        net.rnn_clear_previous_state()

        def window_step(inputs, labels, fm, lm):
            # one stacked local step for this window across all replicas
            data = tuple({k: self._stack_data(v, n) for k, v in d.items()}
                         for d in (inputs, labels, fm, lm))
            params, opt, state = self._stacked
            with self.mesh:
                (params, opt, state, _, self._stacked_rngs,
                 losses) = self._stacked_step(
                    params, opt, state,
                    jnp.asarray(net.iteration, jnp.int32),
                    self._stacked_rngs, *data)
            self._stacked = (params, opt, state)
            self._since_avg += 1
            net.iteration += 1
            net.score_value = jnp.mean(losses)
            self._mark_local_step()
            if self._since_avg >= self.averaging_frequency:
                self._stacked = self._jit_helpers["avg_keep_carry"](
                    self._stacked)
                self._since_avg = 0
                self._mark_average()
            self._sync_net_from_stacked()
            for lst in net.listeners:
                lst.iteration_done(net, net.iteration)

        # Reuse the graph's OWN window slicing (_fit_tbptt's documented
        # do_step contract) so the schedule can never drift from the
        # single-device path. Its batch-sized net-carry seeding is
        # irrelevant here (window_step reads only the stacked state) and
        # it clears the net carry when the batch ends.
        net._fit_tbptt(mds, do_step=window_step)
        # batch over: drop the carry (next batch reseeds the stack)
        params, opt, state = self._stacked
        with self.mesh:
            self._stacked = (params, opt,
                             self._jit_helpers["strip_carry"](state))

    def _sync_net_from_stacked(self):
        net = self.model
        (params, opt, state), rng = self._jit_helpers["take0"](
            (self._stacked, self._stacked_rngs))
        net.params_tree, net.opt_state = params, opt
        if hasattr(net, "_commit_state"):
            net._commit_state(state)
        else:
            net.state_tree = state
        net._rng = rng
        self._synced_params_ref = net.params_tree

    def _average_and_sync(self):
        """Average params/updater-state/layer-state across replicas and
        refresh the net's canonical (unstacked) trees."""
        self._stacked = self._jit_helpers["avg"](self._stacked)
        self._since_avg = 0
        self._mark_average()
        self._sync_net_from_stacked()

    def finalize(self):
        """Flush pending local steps: average if mid-window and sync the
        net. The reference averages once more when fit() drains
        (ParallelWrapper.java:231-263)."""
        if self._stacked is not None and self._since_avg > 0:
            self._average_and_sync()

    # --------------------------------------------------------------- shutdown
    def shutdown(self):
        """Reference ParallelWrapper.shutdown(): averages any pending local
        window, then forgets placement. No threads were harmed in this
        design."""
        self.finalize()
        self._placed = False
        self._stacked = None
        self._stacked_rngs = None


class ParallelWrapperBuilder:
    """Fluent builder mirroring reference ParallelWrapper.Builder."""

    def __init__(self, model):
        self._model = model
        self._workers = None
        self._avg_freq = 1
        self._prefetch = 8
        self._mesh = None

    def workers(self, n: int):
        self._workers = int(n)
        return self

    def averaging_frequency(self, n: int):
        self._avg_freq = int(n)
        return self

    def prefetch_buffer(self, n: int):
        self._prefetch = int(n)
        return self

    def mesh(self, m: Mesh):
        self._mesh = m
        return self

    def build(self) -> ParallelWrapper:
        return ParallelWrapper(self._model, mesh=self._mesh,
                               workers=self._workers,
                               averaging_frequency=self._avg_freq,
                               prefetch_buffer=self._prefetch)
