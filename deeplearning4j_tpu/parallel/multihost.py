"""Multi-host training runner: the Spark-driver / TrainingMaster role.

Reference parity: dl4j-spark's SparkDl4jMultiLayer.fit(JavaRDD) →
ParameterAveragingTrainingMaster (ParameterAveragingTrainingMaster.java:
346-357 split sizing, :867-896 treeAggregate + param/updater averaging) —
a driver JVM broadcasts (conf, params, updaterState) to executor JVMs,
each executor trains on its RDD partition, results aggregate over the
Spark shuffle.

TPU-native redesign: there is no driver/executor asymmetry. Every host
runs the SAME SPMD program over a global jax.sharding.Mesh spanning all
processes' devices (jax.distributed); XLA collectives over ICI (intra-
slice) / DCN (inter-slice) replace the broadcast + treeAggregate
transport. "Broadcast" degenerates to same-seed init (or same checkpoint)
+ replicated placement; "aggregate" is the gradient allreduce (sync DP,
averaging_frequency=1) or the every-F-steps parameter average (local SGD)
that ParallelWrapper already implements — this runner only adds the
process bootstrap, per-process data partitioning contract, lockstep
guards, and chief-only checkpointing.

Launch contract (one process per host, like one Spark executor per node):

    runner = MultiHostRunner(coordinator_address="host0:1234",
                             num_processes=4, process_id=rank)
    runner.initialize()
    net = MultiLayerNetwork(conf).init(seed=SAME_EVERYWHERE)
    runner.fit(net, local_x, local_y, epochs=..., batch_size=...)
    runner.save_checkpoint(net, "gs://.../model.zip")   # chief writes

Env fallbacks: JAX_COORDINATOR_ADDRESS / JAX_NUM_PROCESSES /
JAX_PROCESS_ID. On TPU pods, pass auto_detect=True to let jax's cluster
detection fill everything in.

Not run on the chip: every multi-process proof of this module is two OS
processes over gloo on CPU devices (tests/test_multihost.py). On one
host a chip belongs to one process at a time, so the one-host layout is
one process driving all local chips (ParallelWrapper over a mesh), which
is what chip_smoke.py --devices 4 exercises.
"""
from __future__ import annotations

import collections
import logging
import os
import signal
import threading
from typing import Optional

import jax
import numpy as np

from . import cluster_health as health_lib
from . import mesh as mesh_lib
from .cluster_health import HealthConfig
from .wrapper import ParallelWrapper

log = logging.getLogger(__name__)


class StepCheckpointManager:
    """Step-numbered checkpoint directory with atomic writes and a
    retention bound — the substrate of the auto-resume story (the
    reference has no elastic recovery at all, SURVEY.md §5.3; this is
    deliberate beyond-parity scope: checkpoint-restart is the realistic
    TPU preemption baseline).

    Distinct from :class:`deeplearning4j_tpu.optimize.resilience.\
CheckpointManager` (manifest + sha256 + cadence/retention policy, the
    single-process fit-loop integration): this one is the *multihost*
    flavor — bare ``checkpoint_step<N>.zip`` files, chief-written under
    cluster barriers (docs/robustness.md §cluster-health). The old
    ``CheckpointManager`` name is kept as a deprecated alias."""

    PATTERN = "checkpoint_step%d.zip"

    def __init__(self, directory: str, keep: int = 3):
        self.directory = directory
        self.keep = int(keep)
        if self.keep < 1:
            # keep=0 would make the retention slice [:-0] == [:0] a
            # silent no-op (keeps everything); reject instead of surprising
            raise ValueError("keep must be >= 1, got %d" % self.keep)
        os.makedirs(directory, exist_ok=True)

    def _entries(self):
        import re
        out = []
        for name in os.listdir(self.directory):
            m = re.match(r"^checkpoint_step(\d+)\.zip$", name)
            if m:
                out.append((int(m.group(1)),
                            os.path.join(self.directory, name)))
        return sorted(out)

    def latest(self):
        """(step, path) of the newest checkpoint, or None."""
        entries = self._entries()
        return entries[-1] if entries else None

    def latest_valid(self):
        """(step, path) of the newest checkpoint that passes structural
        validation. A torn newest file (e.g. a kill during a non-atomic
        copy INTO the directory — the writer itself is atomic) must not
        crash resume on every process: it is skipped with a warning and
        a ``checkpoint_corrupt_total`` bump, falling back to the
        next-newest — matching
        ``optimize.resilience.CheckpointManager.latest_valid()``."""
        from ..optimize import resilience
        from ..utils.model_serializer import (CheckpointCorruptError,
                                              validate_checkpoint)
        for step, path in reversed(self._entries()):
            try:
                validate_checkpoint(path, deep=True)
            except CheckpointCorruptError as e:
                resilience.counter("checkpoint_corrupt_total").inc()
                log.warning("skipping torn/corrupt checkpoint %s: %s",
                            path, e)
                continue
            return step, path
        return None

    def save(self, model, step: int) -> str:
        """Atomic write (tmp + rename — a killed writer can never leave
        a truncated 'latest' checkpoint) + retention prune."""
        from ..utils.model_serializer import save_model
        final = os.path.join(self.directory, self.PATTERN % step)
        tmp = final + ".tmp"
        save_model(model, tmp)
        os.replace(tmp, final)
        for _, path in self._entries()[:-self.keep]:
            try:
                os.remove(path)
            except OSError:
                pass
        return final

    def restore_into(self, model) -> Optional[int]:
        """Load the newest *valid* checkpoint's trees INTO the caller's
        model object (the restart path keeps its own net instance).
        Returns the restored step, or None when no valid checkpoint
        exists."""
        entry = self.latest_valid()
        if entry is None:
            return None
        step, path = entry
        from ..utils.model_serializer import restore_model
        restored = restore_model(path)
        model.params_tree = restored.params_tree
        model.state_tree = restored.state_tree
        model.opt_state = restored.opt_state
        model.iteration = restored.iteration
        model.epoch = restored.epoch
        if restored._rng is not None:
            # same-final-params resume for rng-consuming models
            # (dropout): post-resume steps must split from the SAME key
            # stream position the uninterrupted run had
            model._rng = restored._rng
        return step


#: Deprecated alias (pre-round-9 name). It collided with
#: ``optimize.resilience.CheckpointManager``; new code should import
#: :class:`StepCheckpointManager`.
CheckpointManager = StepCheckpointManager


class MultiHostRunner:
    def __init__(self, coordinator_address: Optional[str] = None,
                 num_processes: Optional[int] = None,
                 process_id: Optional[int] = None,
                 auto_detect: bool = False,
                 health: Optional[object] = None):
        self.coordinator_address = coordinator_address or \
            os.environ.get("JAX_COORDINATOR_ADDRESS")
        self.num_processes = num_processes if num_processes is not None else \
            int(os.environ["JAX_NUM_PROCESSES"]) \
            if "JAX_NUM_PROCESSES" in os.environ else None
        self.process_id = process_id if process_id is not None else \
            int(os.environ["JAX_PROCESS_ID"]) \
            if "JAX_PROCESS_ID" in os.environ else None
        self.auto_detect = auto_detect
        self._initialized = False
        self._mesh = None
        # Cluster health plane (docs/robustness.md §cluster-health):
        # health=True/HealthConfig arms it explicitly; health=None defers
        # to the DL4JTPU_HEARTBEAT env knob; health=False disables.
        if health is False:
            self.health_config: Optional[HealthConfig] = None
        elif isinstance(health, HealthConfig):
            self.health_config = health
        elif health is True or health_lib.health_enabled_from_env():
            self.health_config = HealthConfig.from_env()
        else:
            self.health_config = None
        self._monitor: Optional[health_lib.ClusterHealthMonitor] = None
        self.last_grace_step: Optional[int] = None
        # Bounded LRU: wrappers pin their models, so an unbounded cache
        # would leak every model ever fit (hyperparameter sweeps).
        self._wrappers = collections.OrderedDict()
        self._wrapper_cache_size = 4

    def _wrapper_for(self, model, averaging_frequency: int) -> ParallelWrapper:
        """Reuse one wrapper per (model, frequency) so repeated fit calls
        keep their jitted helpers instead of recompiling every time."""
        key = (id(model), int(averaging_frequency))
        w = self._wrappers.get(key)
        if w is not None and w.model is model:
            self._wrappers.move_to_end(key)
            return w
        w = ParallelWrapper(model, mesh=self.mesh(),
                            averaging_frequency=averaging_frequency)
        self._wrappers[key] = w
        while len(self._wrappers) > self._wrapper_cache_size:
            self._wrappers.popitem(last=False)
        return w

    # ------------------------------------------------------------- bootstrap
    def initialize(self) -> "MultiHostRunner":
        """Join the cluster (idempotent). jax.distributed.initialize must
        run BEFORE any jax call that touches the backend, so this method
        makes no jax queries until after the join. Explicit
        coordinator/num/id is the spark-master-URL analog; auto_detect=True
        defers entirely to jax's cluster detection (TPU pods)."""
        if self._initialized:
            return self
        if self.num_processes is not None and self.num_processes > 1:
            jax.distributed.initialize(
                coordinator_address=self.coordinator_address,
                num_processes=self.num_processes,
                process_id=self.process_id)
        elif self.auto_detect:
            jax.distributed.initialize(
                coordinator_address=self.coordinator_address)
        self._initialized = True
        log.info("MultiHostRunner: process %d/%d, %d local / %d global devices",
                 jax.process_index(), jax.process_count(),
                 jax.local_device_count(), jax.device_count())
        return self

    @property
    def is_chief(self) -> bool:
        """Process 0 — the only writer for checkpoints/logs (the driver
        role's one surviving asymmetry)."""
        return jax.process_index() == 0

    def mesh(self):
        """Global data-parallel mesh over every device of every process."""
        if self._mesh is None:
            self.initialize()
            self._mesh = mesh_lib.create_mesh(
                [jax.device_count()], (mesh_lib.DATA_AXIS,), jax.devices())
        return self._mesh

    # -------------------------------------------------------- cluster health
    def start_health(self, on_failure=None
                     ) -> Optional[health_lib.ClusterHealthMonitor]:
        """Start the heartbeat watchdog (idempotent; no-op when the
        plane is disabled or the job is single-process). Process 0
        hosts the beat channel at the coordinator host on
        ``health_config.port`` (default: coordinator port + 1)."""
        if self.health_config is None or jax.process_count() <= 1:
            return None
        if self._monitor is not None:
            return self._monitor
        host, port = self._beat_endpoint()
        if host is None:
            log.warning("cluster health enabled but no coordinator "
                        "address/port to derive the beat channel from; "
                        "set DL4JTPU_HEARTBEAT_PORT — watchdog disabled")
            return None
        transport = health_lib.HttpBeatTransport(
            jax.process_index(), host, port, chief=self.is_chief)
        self._monitor = health_lib.ClusterHealthMonitor(
            jax.process_index(), jax.process_count(), transport,
            config=self.health_config, on_failure=on_failure).start()
        log.info("cluster health watchdog up: beat channel %s "
                 "(interval %.1fs, timeout %.1fs)", transport.url,
                 self.health_config.interval_s, self.health_config.timeout_s)
        return self._monitor

    def stop_health(self) -> None:
        """Stop the watchdog thread and (on the chief) the beat server.
        Call at orderly job shutdown so a fast-exiting chief is not
        misread as lost by peers still finishing up."""
        if self._monitor is not None:
            self._monitor.stop()
            self._monitor = None

    def _beat_endpoint(self):
        port = self.health_config.port if self.health_config else None
        addr = self.coordinator_address
        if addr and ":" in addr:
            host, _, coord_port = addr.rpartition(":")
            return host, (port if port else int(coord_port) + 1)
        if addr and port:
            return addr, port
        return (None, None) if not port else ("127.0.0.1", port)

    def _timed(self, fn, name: str):
        """Run a blocking collective under the health plane's deadline
        (pass-through when the plane is off): the known blocking points
        fail typed instead of hanging forever."""
        cfg = self.health_config
        if cfg is None or not cfg.barrier_timeout_s:
            return fn()
        return health_lib.timed_collective(
            fn, name=name, timeout_s=cfg.barrier_timeout_s,
            monitor=self._monitor)

    # ------------------------------------------------------------- lockstep
    def _assert_lockstep(self, *values: int):
        """All processes must agree on loop bounds, or SPMD deadlocks
        (the Spark analog: TrainingMaster sizes every split identically)."""
        if jax.process_count() == 1:
            return
        from jax.experimental import multihost_utils
        mine = np.asarray(values, np.int64)
        all_vals = self._timed(
            lambda: multihost_utils.process_allgather(mine), "lockstep")
        if not (all_vals == all_vals[0]).all():
            raise ValueError(
                f"Processes disagree on batch/epoch counts: {all_vals.tolist()}"
                " — every process must feed identically-shaped local "
                "partitions (repartition your data)")

    def barrier(self, name: str = "barrier",
                timeout_s: Optional[float] = None):
        """Cluster barrier. With the health plane armed (or an explicit
        `timeout_s`) the wait is bounded: expiry raises a typed
        :class:`cluster_health.BarrierTimeoutError` (or the watchdog's
        richer PeerLost/Desync diagnosis) instead of wedging forever."""
        if jax.process_count() <= 1:
            return
        from jax.experimental import multihost_utils
        fn = lambda: multihost_utils.sync_global_devices(name)  # noqa: E731
        if timeout_s is not None:
            health_lib.timed_collective(
                fn, name=f"barrier:{name}", timeout_s=timeout_s,
                monitor=self._monitor)
        else:
            self._timed(fn, f"barrier:{name}")

    # ------------------------------------------------------------------- fit
    def fit(self, model, local_features, local_labels=None, *,
            epochs: int = 1, batch_size: int = 32,
            averaging_frequency: int = 1,
            checkpoint_dir: Optional[str] = None,
            checkpoint_every: Optional[int] = None,
            resume: bool = True) -> ParallelWrapper:
        """Train over the global mesh; THIS process contributes
        `local_features/labels` (its partition — the executor's RDD split).
        Global batch per step = batch_size × num_processes.

        Elastic story (beyond the reference, which has none — SURVEY.md
        §5.3): with `checkpoint_dir`, training auto-checkpoints every
        `checkpoint_every` optimizer steps (chief writes, cluster
        barriers) and a RESTARTED job auto-resumes from the newest
        checkpoint — already-trained steps are skipped by replaying the
        (deterministic) data order without stepping, so a preempted run
        reaches the same final parameters as an uninterrupted one
        (tested by killing and restarting a 2-process gloo job).

        Cluster health (docs/robustness.md §cluster-health): with the
        health plane armed (`health=`/`DL4JTPU_HEARTBEAT=1`), a
        heartbeat watchdog runs for the duration of fit — a dead peer
        raises a typed `PeerLostError` (and hard-exits, code 17) instead
        of wedging this process at the next collective, and SIGTERM
        triggers one coordinated grace checkpoint (barrier → chief save
        → barrier) before a clean exit 0; the restart resumes
        bitwise-identically through the replay-skip path above."""
        wrapper = self._wrapper_for(model, averaging_frequency)
        if hasattr(local_features, "num_examples"):     # DataSet
            n = local_features.num_examples()
        elif hasattr(local_features, "shape"):          # array
            n = np.asarray(local_features).shape[0]
        else:                                           # opaque iterator
            n = -1  # caller must guarantee equal batch counts per process
        if n >= 0:
            # n itself must match (not just the batch COUNT): unequal
            # last-batch sizes compile different SPMD programs and hang the
            # cluster at the collective.
            self._assert_lockstep(n, batch_size, epochs)
        else:
            self._assert_lockstep(epochs)
        monitor = self.start_health()
        hook = None
        if monitor is not None:
            hook = monitor.notify_step
            wrapper.step_hooks.append(hook)
        try:
            return self._fit_guarded(wrapper, model, local_features,
                                     local_labels, epochs=epochs,
                                     batch_size=batch_size,
                                     checkpoint_dir=checkpoint_dir,
                                     checkpoint_every=checkpoint_every,
                                     resume=resume, monitor=monitor)
        finally:
            if hook is not None and hook in wrapper.step_hooks:
                wrapper.step_hooks.remove(hook)

    def _fit_guarded(self, wrapper, model, local_features, local_labels, *,
                     epochs, batch_size, checkpoint_dir, checkpoint_every,
                     resume, monitor):
        if checkpoint_dir is None:
            # Delegate the epoch/listener loop to the net's own fit (via
            # the wrapper) so loop semantics exist in exactly one place.
            # No grace handler: there is nowhere to write the checkpoint.
            wrapper.fit(local_features, local_labels, epochs=epochs,
                        batch_size=batch_size)
            return wrapper
        mgr = StepCheckpointManager(checkpoint_dir)
        skip = 0
        if resume:
            restored = mgr.restore_into(model)
            if restored is not None:
                skip = int(model.iteration)
                # the fit loop below re-runs every epoch (replay-skipping
                # trained batches); epoch counting restarts with it so
                # the final epoch equals an uninterrupted run's
                model.epoch = 0
                log.info("resumed from checkpoint step %d", restored)
        self._assert_lockstep(skip)  # all processes see the same files

        def steps_in(ds):
            # optimizer steps one batch will take: tBPTT batches window
            # into ceil(T / fwd_length) steps each (skip counts must be
            # in the same unit as model.iteration)
            from ..nn.conf.builders import BackpropType
            if model.conf.backprop_type != BackpropType.TRUNCATED_BPTT:
                return 1
            feats = ds.features if hasattr(ds, "features") else None
            if feats is None or np.asarray(feats).ndim != 3:
                return 1
            T = np.asarray(feats).shape[1]
            L = model.conf.tbptt_fwd_length
            return -(-T // L)

        remaining = [skip]
        grace_flag = [False]    # set by the SIGTERM handler
        calls = [0]
        cfg = self.health_config
        grace_every = max(1, int(cfg.grace_every)) if cfg else 1

        def grace_poll() -> bool:
            """Cluster-wide agreement on the preemption flag. Called at
            the SAME cadence on every process (replay steps included) so
            the allgather counts always match; any process's flag stops
            the whole cluster at the same step, deterministically."""
            local = grace_flag[0] or (monitor is not None
                                      and monitor.grace_requested())
            if jax.process_count() <= 1:
                return local
            from jax.experimental import multihost_utils
            votes = multihost_utils.process_allgather(
                np.asarray([1 if local else 0], np.int32))
            return bool(np.asarray(votes).any())

        def grace_checkpoint():
            step = int(model.iteration)
            log.info("preemption grace: coordinated checkpoint at step %d",
                     step)
            self.barrier("grace-pre-checkpoint")
            if self.is_chief:
                mgr.save(model, step)
            self.barrier("grace-post-checkpoint")
            health_lib._counter("cluster_grace_checkpoints_total").inc()
            self.last_grace_step = step
            raise health_lib.GraceCheckpointed(step)

        def elastic_step(ds):
            calls[0] += 1
            if calls[0] % grace_every == 0 and grace_poll():
                grace_checkpoint()
            if remaining[0] > 0:
                n = steps_in(ds)  # replay-skip: trained pre-restart
                if n > remaining[0]:
                    raise ValueError(
                        "checkpoint iteration falls inside a tBPTT "
                        "batch's window sequence — checkpoints from a "
                        "different batch/window schedule cannot resume "
                        "this run")
                remaining[0] -= n
                return
            wrapper.fit_batch(ds)
            if monitor is not None:
                # surface a recorded typed failure in the main thread
                # too, while it is still alive to see it
                monitor.check()
            if checkpoint_every and \
                    model.iteration % int(checkpoint_every) == 0:
                self.barrier("pre-checkpoint")
                if self.is_chief:
                    mgr.save(model, int(model.iteration))
                self.barrier("post-checkpoint")

        # SIGTERM → grace flag, checked at the next step boundary.
        # signal.signal only works from the main thread; elsewhere (e.g.
        # a fit driven from a server worker) grace still arms via a
        # peer's flag riding the beat table.
        prev_handler = None
        installed = False
        if threading.current_thread() is threading.main_thread():
            def _on_sigterm(signum, frame):
                grace_flag[0] = True
                if monitor is not None:
                    monitor.request_grace()
            try:
                prev_handler = signal.signal(signal.SIGTERM, _on_sigterm)
                installed = True
            except ValueError:   # exotic embeddings: no handler, no grace
                pass
        try:
            model.fit(local_features, local_labels, epochs=epochs,
                      batch_size=batch_size, step_fn=elastic_step,
                      use_async=False)
        except health_lib.GraceCheckpointed as g:
            log.info("grace checkpoint written at step %d — exiting 0 "
                     "for the restarter (resume=True picks it up)", g.step)
            self.stop_health()
            raise SystemExit(0)
        finally:
            if installed:
                signal.signal(signal.SIGTERM, prev_handler)
        wrapper.finalize()
        return wrapper

    # ------------------------------------------------------------ evaluation
    def evaluate(self, model, local_features, local_labels=None, *,
                 batch_size: int = 128):
        """Distributed evaluation: every process evaluates ITS partition
        locally, per-process confusion statistics allgather across the
        cluster, and the merged Evaluation returns everywhere (the
        reference's evaluation flatmap + reduce —
        `spark/impl/multilayer/evaluation/` evaluate() aggregating
        per-partition Evaluation objects via merge)."""
        local = model.evaluate(local_features, local_labels,
                               batch_size=batch_size)
        if jax.process_count() == 1:
            return local
        import pickle

        from jax.experimental import multihost_utils
        blob = np.frombuffer(pickle.dumps(local), np.uint8)
        # fixed-size lockstep transport: allgather needs equal shapes
        size = np.asarray([blob.size], np.int64)
        sizes = multihost_utils.process_allgather(size).reshape(-1)
        cap = int(sizes.max())
        padded = np.zeros(cap, np.uint8)
        padded[:blob.size] = blob
        gathered = multihost_utils.process_allgather(padded)
        merged = None
        for row, n in zip(np.asarray(gathered).reshape(-1, cap), sizes):
            ev = pickle.loads(bytes(row[:int(n)]))
            merged = ev if merged is None else merged.merge(ev)
        return merged

    # --------------------------------------------------------- repartitioning
    @staticmethod
    def balanced_partition(n: int, num_partitions: int, partition: int
                           ) -> slice:
        """Row slice for `partition` under balanced partitioning
        (reference impl/common/repartition/BalancedPartitioner.java:
        each partition gets floor(n/P) elements, the first n%P get one
        more). Use to FIX unbalanced local data instead of being
        rejected by the lockstep guards."""
        if not 0 <= partition < num_partitions:
            raise ValueError(f"partition {partition} not in "
                             f"[0, {num_partitions})")
        base, extra = divmod(n, num_partitions)
        start = partition * base + min(partition, extra)
        return slice(start, start + base + (1 if partition < extra else 0))

    def my_partition(self, *arrays, drop_remainder: bool = True):
        """Balanced-repartition helper bound to THIS process: slice each
        array to this process's share of the global rows. With
        drop_remainder (default) every process gets EXACTLY floor(n/P)
        rows, which is what the SPMD lockstep contract requires — the
        dropped tail (< P rows) is logged."""
        P = jax.process_count()
        p = jax.process_index()
        out = []
        for a in arrays:
            a = np.asarray(a)
            n = a.shape[0]
            if n < P:
                raise ValueError(
                    f"cannot partition {n} rows over {P} processes — "
                    "every process would train on (almost) nothing")
            if drop_remainder:
                per = n // P
                if per * P != n:
                    log.info("my_partition: dropping %d tail rows "
                             "(%d rows over %d processes)",
                             n - per * P, n, P)
                out.append(a[p * per:(p + 1) * per])
            else:
                out.append(a[self.balanced_partition(n, P, p)])
        return out[0] if len(out) == 1 else tuple(out)

    # ------------------------------------------------------------ checkpoint
    def save_checkpoint(self, model, path: str):
        """Chief-only write + cluster barrier (reference: only the Spark
        driver persists, ModelSerializer.java:37-127)."""
        self.barrier("pre-checkpoint")
        if self.is_chief:
            from ..utils.model_serializer import ModelSerializer
            ModelSerializer.write_model(model, path)
        self.barrier("post-checkpoint")

    def materialize_local(self, model):
        """Pull the model's (replicated) trees back to process-local
        arrays so single-process inference/eval works after training."""
        import jax.numpy as jnp
        to_local = lambda t: jax.tree_util.tree_map(
            lambda a: jnp.asarray(np.asarray(a)), t)
        model.params_tree = to_local(model.params_tree)
        model.opt_state = to_local(model.opt_state)
        model.state_tree = to_local(model.state_tree)
        model._rng = jnp.asarray(np.asarray(model._rng))
        return model
