"""The trainer under both networks: `Trainer` is the base of
MultiLayerNetwork and ComputationGraph and owns what training is for
either: the device-resident iteration counter, the lazy training jits,
the commit of a step's results, and `fit`, the one epoch loop (every
parallel wrapper runs it too, through `step_fn`).

A front end supplies what truly differs, as class attributes and small
methods the base calls:

  `_TRAIN_JIT_ATTRS`   names `_build_training_jits()` sets
  `_STEP_LABEL`        prefix of the label `note_step_signature` gets
  `_ASYNC_ITERATOR`    host-side prefetch iterator class
  `_FUSES_TBPTT`       whether `fit_batches` takes truncated-BPTT batches
  `_batches(data, labels, batch_size, epochs)`  -> re-iterable of batches
  `_coerce(item)`      one item of that iterable -> the batch type
  `_batch_signature(batch)`  shapes that must match to fuse a group
  `_fit_batch(batch)`  one batch -> the step's operands -> `_run_and_commit`
  `_input_shapes(batch_size, time_steps)`  what `warmup` pushes through
  `fit_batches`, `precompile`, `output`, `_merged_state`, `_commit_state`

The iteration counter: the jitted train step takes the iteration (for
LR schedules / bias correction) and returns iteration+1. Re-uploading a
fresh host scalar every step costs a DevicePut + convert_element_type
dispatch per step (~4.5 ms/step of host-side overhead in the profiled
ResNet50 loop, docs/perf_resnet50.md) — so the returned device scalar is
cached and fed straight back in. Assigning `net.iteration = n`
(checkpoint restore, transfer learning) drops the cache; the next step
re-uploads once. The cache is also keyed by the mesh it was produced
under so ParallelWrapper's sharded steps never feed a foreign-sharded
scalar into a single-device program. Under a mesh the fresh scalar is
placed replicated over it — the placement the step's own output has — so
step 2 presents the signature step 1 compiled for (a single-device
scalar there cost a second full compile of the train step).
"""
from __future__ import annotations

import collections
import contextlib
import logging
import time

import jax
import jax.numpy as jnp
import numpy as np

from ..data.iterators import DevicePrefetchIterator, PadToBucketIterator
from ..optimize import metrics as metrics_mod
from ..optimize import telemetry as telemetry_mod
from ..optimize import tracing
from .conf.builders import BackpropType

log = logging.getLogger(__name__)


# How many launches (a step, or a fused group of `steps_per_dispatch`) `fit`
# leaves unfinished behind it. A producer that stages faster than the device
# steps would otherwise let the loop run ahead until the runtime's own
# limits stop it, every queued launch holding its batches. One already
# hides the host's part of a cycle behind the running launch, and read the
# same rate on the chip; two and not one for slack alone: a hiccup of the
# host then has to outlast a whole launch more before the device runs dry
# (PERF.md section 6, PR 32, has the numbers).
_STEPS_IN_FLIGHT = 2


def _is_none(a) -> bool:
    return a is None


class Trainer:
    _TRAIN_JIT_ATTRS: tuple = ()

    _iteration: int = 0
    _iteration_dev = None
    _iteration_dev_mesh = None

    @property
    def iteration(self) -> int:
        return self._iteration

    @iteration.setter
    def iteration(self, value):
        self._iteration = int(value)
        self._iteration_dev = None
        self._iteration_dev_mesh = None

    def _iteration_device(self, mesh=None):
        if self._iteration_dev is None or self._iteration_dev_mesh is not mesh:
            if mesh is None:
                return jnp.asarray(self._iteration, jnp.int32)
            from ..parallel.mesh import replicate
            return replicate(mesh, np.asarray(self._iteration, np.int32))
        return self._iteration_dev

    def _commit_iteration(self, new_iter, mesh=None):
        self._iteration += 1
        self._iteration_dev = new_iter
        self._iteration_dev_mesh = mesh

    def __getattr__(self, name):
        # Lazy training jits: first touch of any train-path jit builds
        # them all (they share one traced train_step closure), so an
        # inference-only net (the ParallelInference serving path) never
        # pays their compiles. Guarded on _initialized so pre-init
        # access still raises cleanly.
        if name in self._TRAIN_JIT_ATTRS and \
                self.__dict__.get("_initialized"):
            self._build_training_jits()
            return self.__dict__[name]
        raise AttributeError(
            f"{type(self).__name__!r} object has no attribute {name!r}")

    def warmup(self, batch_size: int = 1, *, time_steps=None):
        """Serving cold-start eliminator: AOT-compile the inference path
        for `batch_size` and push one concrete zero batch through
        `output()` so the first real request pays neither compile nor
        first-dispatch cost. The batch is host float32, as a request
        delivers it: on a bf16 net that also warms the per-shape input
        cast, which is an XLA compilation of its own."""
        self._check_init()
        self.precompile(batch_size, time_steps=time_steps, train=False)
        self.output(*[np.zeros(shape, np.float32) for shape in
                      self._input_shapes(batch_size, time_steps)])
        return self

    # ------------------------------------------------------------------- fit
    def fit(self, data, labels=None, *, epochs: int = 1, batch_size: int = 32,
            use_async: bool = True, async_queue_size: int = 8,
            step_fn=None, steps_per_dispatch: int = 1,
            pad_to_bucket: bool = True, prefetch_to_device: bool = True,
            prefetch_depth: int = 2, prefetch_sharding=None,
            prefetch_divisor: int = 1,
            checkpoint=None, resume: bool = False, sentinel=None):
        """Train (reference MultiLayerNetwork.fit(DataSetIterator):1019,
        ComputationGraph.fit(MultiDataSetIterator):867). The list network
        accepts a DataSetIterator, a DataSet, or (features, labels)
        arrays; the graph a MultiDataSet, a DataSet, (features, labels)
        arrays, or an iterable of either (a plain generator is
        materialized when `epochs > 1`). `step_fn` lets the parallel
        wrappers reuse this loop with a sharded step.

        Fault tolerance (docs/robustness.md): `checkpoint` attaches a
        resilience.CheckpointManager (periodic atomic saves at its
        configured cadence); with `resume=True` the newest valid
        checkpoint is restored first and the loop fast-forwards past the
        epochs/batches it already covers — on a deterministic,
        unshuffled pipeline the resumed run is bitwise-identical to an
        uninterrupted one (`epochs` counts TOTAL epochs for the run, not
        additional ones). `sentinel` attaches a DivergenceSentinel
        checking each step for non-finite loss/params. Both require
        steps_per_dispatch=1 (per-step hook cadence).

        Input pipeline (docs/perf_data_pipeline.md): `pad_to_bucket`
        pads ragged batches (the short final batch) up to the epoch's
        canonical shape under the zero-weight mask contract — loss and
        gradients match the unpadded batch exactly, and the whole epoch
        reuses ONE compiled train step. Batches prefetch on a background
        thread (`async_queue_size` deep); `prefetch_to_device` upgrades
        that thread to stage batches onto the device (`jax.device_put` +
        transfer fence off the training thread, `prefetch_depth` deep).
        That thread moves bytes and dispatches nothing: a batch is staged
        in the host's dtype, and the cast to the network's dtype is
        enqueued here, on the fit thread, immediately before the step
        that reads it, so nothing can stand between two steps in the
        device's queue.
        `prefetch_sharding`/`prefetch_divisor` let ParallelWrapper stage
        mesh-sharded batches. Both honor use_async=False (no threads)
        and AsyncShield iterators.

        `steps_per_dispatch > 1` groups that many same-shaped minibatches
        into ONE fused device dispatch (fit_batches' lax.scan —
        bit-identical math, amortized dispatch latency). Odd-shaped
        batches (e.g. a short final batch) flush the group and run
        singly; incompatible with step_fn. The list network fuses
        truncated-BPTT batches too (their whole window schedules; one
        iteration_done per BATCH, iteration advancing by the window
        count — per-window listener events require
        steps_per_dispatch=1); the graph raises NotImplementedError."""
        self._check_init()
        spd = int(steps_per_dispatch)
        if spd > 1 and step_fn is not None:
            raise ValueError("steps_per_dispatch cannot combine with a "
                             "custom step_fn")
        if spd > 1 and (checkpoint is not None or sentinel is not None):
            raise ValueError("checkpoint=/sentinel= need per-step hooks; "
                             "use steps_per_dispatch=1")
        if resume and checkpoint is None:
            raise ValueError("resume=True requires checkpoint=a "
                             "CheckpointManager to resume from")
        tbptt = self.conf.backprop_type == BackpropType.TRUNCATED_BPTT
        if spd > 1 and tbptt and not self._FUSES_TBPTT:
            raise NotImplementedError(
                "steps_per_dispatch > 1 does not support truncated BPTT "
                "iterators; use fit_batch_repeated for resident batches")
        skip_batches = 0
        if resume:
            rec = checkpoint.restore_into(self)
            if rec is not None:
                epochs = max(0, int(epochs) - int(self.epoch))
                skip_batches = int(rec.get("batches_into_epoch", 0) or 0)
                log.info("auto-resume: restored %s (iteration %d, %d "
                         "epoch(s) done, %d batch(es) into the next); "
                         "%d epoch(s) remain", rec.get("file"),
                         self.iteration, self.epoch, skip_batches, epochs)
        it = self._batches(data, labels, batch_size, epochs)
        if pad_to_bucket and not tbptt:
            # tBPTT slices the labels mask on the time axis; the (n,1)
            # zero-weight mask cannot window — ragged tBPTT batches keep
            # the flush-and-recompile path (loudly documented).
            it = PadToBucketIterator(it)
        if use_async and getattr(it, "async_supported", lambda: True)():
            wrapped = DevicePrefetchIterator(
                it, depth=max(1, int(prefetch_depth)),
                sharding=prefetch_sharding,
                batch_divisor=prefetch_divisor) if prefetch_to_device \
                else self._ASYNC_ITERATOR(it, async_queue_size)
        else:
            wrapped = it
        step = step_fn or self._fit_batch
        coerce = self._coerce
        group_sig = self._batch_signature
        group = []

        def flush_group():
            if not group:
                return
            if len(group) == 1:
                step(group[0])
            else:
                self.fit_batches(group)
            group.clear()

        reg = metrics_mod.registry()
        unfinished = collections.deque()    # losses of the newest launches
        fit_sp = tracing.begin("fit", epochs=epochs)
        try:
            for _ in range(epochs):
                epoch_sp = tracing.begin("epoch", epoch=self.epoch)
                # Resumed run: re-consume (and discard) the batches the
                # restored checkpoint already covers — first epoch only.
                to_skip, skip_batches = skip_batches, 0
                batches_done = to_skip
                it_epoch = iter(wrapped)
                while True:
                    # The step span opens BEFORE the iterator is polled
                    # so its etl child nests inside it; an exhausted
                    # iterator cancels the empty span.
                    step_sp = tracing.begin("step",
                                            step_num=self.iteration)
                    # Track time blocked on the data pipeline (reference
                    # lastEtlTime, MultiLayerNetwork.java:1063-1065);
                    # PerformanceListener reports it.
                    t0 = time.perf_counter()
                    try:
                        ds = next(it_epoch)
                    except StopIteration:
                        step_sp.cancel()
                        break
                    if to_skip > 0:
                        to_skip -= 1
                        step_sp.cancel()
                        continue
                    etl_s = time.perf_counter() - t0
                    self.last_etl_ms = etl_s * 1000.0
                    # Device-prefetched batches carry the producer-side
                    # split: host-wait (base iterator) vs h2d-wait
                    # (device_put + transfer fence). Host-fed batches
                    # attribute the whole wait to the host side.
                    self.last_etl_host_ms = getattr(
                        ds, "_etl_host_ms", self.last_etl_ms)
                    self.last_etl_h2d_ms = getattr(ds, "_etl_h2d_ms", 0.0)
                    tracing.add_span("etl", t0, etl_s)
                    batch = coerce(ds)
                    metrics_mod.record_etl(
                        reg, self.last_etl_ms, self.last_etl_host_ms,
                        self.last_etl_h2d_ms, metrics_mod.batch_rows(batch))
                    t1 = time.perf_counter()
                    if sentinel is not None:
                        sentinel.before_step(self)
                    with tracing.span("dispatch"):
                        if spd <= 1:
                            step(batch)
                        else:
                            if group and \
                                    group_sig(batch) != group_sig(group[0]):
                                flush_group()
                            group.append(batch)
                            if len(group) >= spd:
                                flush_group()
                    reg.histogram(
                        "train_step_dispatch_ms",
                        "Host-side enqueue time per fit-loop batch "
                        "(async: device time needs the fence)").observe(
                            (time.perf_counter() - t1) * 1000.0)
                    w = tracing.fence(self.iteration, self.score_value)
                    if w is not None:
                        reg.gauge(
                            "device_fence_wait_ms",
                            "Dispatch-queue drain at the last sampled "
                            "fence (device-compute backlog)").set(w)
                    if sentinel is not None:
                        sentinel.after_step(self)
                    if not unfinished or \
                            self.score_value is not unfinished[-1]:
                        # a launch put a new loss there; a batch that
                        # only joined its group launched nothing, and
                        # waiting then would hold the next group back
                        unfinished.append(self.score_value)
                        if len(unfinished) > _STEPS_IN_FLIGHT:
                            # outside `dispatch`: this is the device's
                            # time, not the host's enqueue
                            jax.block_until_ready(unfinished.popleft())
                    batches_done += 1
                    if checkpoint is not None:
                        checkpoint.on_batch(self, batches_done)
                    step_sp.end()
                if group:  # end of epoch: run the partial group
                    with tracing.span("dispatch", flush="epoch_tail"):
                        flush_group()
                self.epoch += 1
                reg.counter("train_epochs_total",
                            "Completed fit epochs").inc()
                for lst in self.listeners:
                    if hasattr(lst, "on_epoch_end"):
                        lst.on_epoch_end(self, self.epoch)
                if checkpoint is not None:
                    checkpoint.on_epoch(self)
                epoch_sp.end()
        finally:
            fit_sp.end()
            if wrapped is not it:  # what this call wrapped, it shuts down
                wrapped.shutdown()
        return self

    def _run_and_commit(self, *operands, mesh=None):
        """Invoke the jitted step on one batch's operands (features,
        labels, features mask(s), labels mask(s)) and commit results +
        listeners. Shared by the single-device path and the parallel
        wrappers' sharded paths."""
        label = f"{self._STEP_LABEL}#{self._probe_tag}"
        telemetry_mod.note_step_signature(
            label, telemetry_mod.shape_signature(*jax.tree_util.tree_leaves(
                operands, is_leaf=_is_none)))  # an absent mask stays a None
        step = self._train_step_fn
        if mesh is not None:
            # Mesh-sharded inputs must not hit an AOT executable lowered
            # for single-device placement — take the jit path, which
            # reshards freely.
            step = getattr(step, "jit", step)
        with (mesh if mesh is not None else contextlib.nullcontext()):
            out = step(
                self.params_tree, self.opt_state, self._merged_state(),
                self._iteration_device(mesh), self._rng, *operands)
        (self.params_tree, self.opt_state, new_state, new_iter, self._rng,
         loss) = out
        self._commit_state(new_state)
        self._commit_iteration(new_iter, mesh)
        self.score_value = loss
        # samples are counted at the fit-loop seam (record_etl), never
        # here — the wrapper's sharded path funnels through both
        metrics_mod.record_train_step(1)
        for lst in self.listeners:
            lst.iteration_done(self, self.iteration)

    def _commit_multi(self, out, steps: int, listener_events=None):
        """Commit one fused dispatch. `steps` = optimizer iterations
        taken; `listener_events` = how many per-scan losses exist (tBPTT
        repeats record one loss per REPEAT while taking several window
        steps)."""
        (self.params_tree, self.opt_state, self.state_tree, it, self._rng,
         losses) = out
        events = steps if listener_events is None else listener_events
        self._iteration += steps
        metrics_mod.record_train_step(steps)
        self._iteration_dev = it
        self._iteration_dev_mesh = None
        self.score_value = losses[-1]
        if self.listeners:
            per = steps // max(events, 1)
            for k in range(events):
                self.score_value = losses[k]
                for lst in self.listeners:
                    lst.iteration_done(
                        self, self._iteration - steps + (k + 1) * per)
            self.score_value = losses[-1]
