"""Device-resident input pipeline (docs/perf_data_pipeline.md):
pad-to-bucket ragged batches (one compiled train step per epoch, loss
normalization by REAL rows), DevicePrefetchIterator staging/lifecycle,
sharded prefetch on the virtual mesh, and compile/ETL telemetry."""
import queue
import threading
import time

import jax
import numpy as np
import pytest

from deeplearning4j_tpu import (Adam, DataSet, DenseLayer, InputType,
                                MultiLayerNetwork, NeuralNetConfiguration,
                                OutputLayer)
from deeplearning4j_tpu.data.iterators import (AsyncDataSetIterator,
                                               DevicePrefetchIterator,
                                               ListDataSetIterator,
                                               PadToBucketIterator)
from deeplearning4j_tpu.data.padding import (pad_dataset_rows,
                                             pad_lmask_zero_weight)
from deeplearning4j_tpu.optimize.telemetry import (CompilationTracker,
                                                   compilation_count,
                                                   jit_cache_size)


def _net(seed=7, n_in=12):
    conf = (NeuralNetConfiguration.builder().seed(seed).updater(Adam(0.01))
            .list()
            .layer(DenseLayer(n_out=16, activation="relu"))
            .layer(OutputLayer(n_out=3, activation="softmax", loss="mcxent"))
            .set_input_type(InputType.feed_forward(n_in)).build())
    return MultiLayerNetwork(conf).init()


def _xy(n=1050, n_in=12, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.standard_normal((n, n_in)).astype(np.float32)
    y = np.eye(3, dtype=np.float32)[rng.integers(0, 3, n)]
    return x, y


class TestPadToBucket:
    def test_ragged_epoch_compiles_once_with_score_parity(self):
        """THE acceptance invariant: 1050 rows at batch 32 (32 full
        batches + a 26-row tail) compile exactly ONE train-step
        executable, and params/score match the flush-and-recompile
        path bit-for-bit."""
        x, y = _xy(1050)
        net = _net()
        with CompilationTracker() as trk:
            net.fit(x, y, epochs=1, batch_size=32)
        assert jit_cache_size(net._train_step_fn) == 1, \
            f"ragged epoch compiled {jit_cache_size(net._train_step_fn)} " \
            f"train-step shapes (tracker saw {trk.count} total compiles)"

        legacy = _net()
        legacy.fit(x, y, epochs=1, batch_size=32,
                   pad_to_bucket=False, prefetch_to_device=False)
        assert jit_cache_size(legacy._train_step_fn) == 2  # the old cost
        for pa, pb in zip(jax.tree_util.tree_leaves(net.params_tree),
                          jax.tree_util.tree_leaves(legacy.params_tree)):
            np.testing.assert_array_equal(np.asarray(pa), np.asarray(pb))
        assert float(net.score_value) == float(legacy.score_value)

    def test_score_normalizes_by_real_rows(self):
        """The padded tail batch's score divides by the 26 real rows,
        not the 32 padded ones: fitting JUST the tail through the
        pipeline equals fitting it raw."""
        x, y = _xy(1050)
        tail_x, tail_y = x[1024:], y[1024:]  # 26 rows
        a = _net()
        a.fit(tail_x, tail_y, epochs=1, batch_size=32)  # single batch: no pad
        b = _net()
        ds = pad_dataset_rows(DataSet(tail_x, tail_y), 32)
        b._fit_batch(ds)
        assert float(a.score_value) == pytest.approx(
            float(b.score_value), abs=1e-6)

    def test_single_batch_dataset_never_padded(self):
        """Canonical target = FIRST batch's rows, so a dataset smaller
        than batch_size keeps its true shape (no BN-stats surprises)."""
        it = PadToBucketIterator(
            ListDataSetIterator(DataSet(*_xy(10)), batch_size=32))
        batches = list(it)
        assert len(batches) == 1
        assert batches[0].features.shape[0] == 10

    def test_uniform_mask_structure_and_zero_weight_tail(self):
        it = PadToBucketIterator(
            ListDataSetIterator(DataSet(*_xy(70)), batch_size=32))
        batches = list(it)
        assert [b.features.shape[0] for b in batches] == [32, 32, 32]
        for b in batches:  # every batch carries the rank-2 mask
            assert b.labels_mask is not None
            assert np.ndim(b.labels_mask) == 2
        m = np.asarray(batches[-1].labels_mask)
        assert m[:6].sum() == 6 and m[6:].sum() == 0  # 6 real, 26 pad

    def test_graph_frontend_ragged_epoch_compiles_once(self):
        """Same invariant through the ComputationGraph front-end."""
        from deeplearning4j_tpu import ComputationGraph

        def build(seed=3):
            conf = (NeuralNetConfiguration.builder().seed(seed)
                    .updater(Adam(0.01))
                    .graph_builder().add_inputs("in")
                    .add_layer("d", DenseLayer(n_out=16, activation="relu"),
                               "in")
                    .add_layer("out", OutputLayer(n_out=3,
                                                  activation="softmax",
                                                  loss="mcxent"), "d")
                    .set_outputs("out")
                    .set_input_types(InputType.feed_forward(12)).build())
            return ComputationGraph(conf).init()

        x, y = _xy(1050)
        g = build()
        g.fit(x, y, epochs=1, batch_size=32)
        assert jit_cache_size(g._train_step_fn) == 1
        legacy = build()
        legacy.fit(x, y, epochs=1, batch_size=32,
                   pad_to_bucket=False, prefetch_to_device=False)
        assert jit_cache_size(legacy._train_step_fn) == 2
        for pa, pb in zip(jax.tree_util.tree_leaves(g.params_tree),
                          jax.tree_util.tree_leaves(legacy.params_tree)):
            np.testing.assert_array_equal(np.asarray(pa), np.asarray(pb))

    def test_existing_rank2_mask_preserved(self):
        m = pad_lmask_zero_weight(np.ones((5, 4), np.float32), 5, 3)
        assert m.shape == (8, 4)
        assert m.sum() == 20  # denominator unchanged by pad rows


class _CastCalls:
    """Wraps a front end's cast of the features (`front.cast_name`): which
    thread called it, what it was given, what it returned."""

    def __init__(self, net, name):
        self.seen = []
        inner = getattr(net, name)

        def wrapped(features, *rest):
            out = inner(features, *rest)
            given = features if name == "_cast_features" else features[0]
            got = out if name == "_cast_features" \
                else next(iter(out[0].values()))
            self.seen.append((threading.current_thread(), given, got))
            return out

        setattr(net, name, wrapped)


def _step_features(net):
    """Spy on `_run_and_commit`: (calling thread, the step's first feature
    operand) of every step."""
    seen = []
    run = net._run_and_commit
    net._run_and_commit = lambda *ops, **kw: (
        seen.append((threading.current_thread(),
                     jax.tree_util.tree_leaves(ops[0])[0])),
        run(*ops, **kw))
    return seen


class TestDevicePrefetchIterator:
    def test_stages_on_device_with_etl_breakdown(self):
        """A staged batch is the host's batch moved, nothing else: the
        host's dtype, on the device, landed when it is handed over."""
        x, y = _xy(64)
        it = DevicePrefetchIterator(
            ListDataSetIterator(DataSet(x, y), batch_size=32))
        batches = list(it)
        assert len(batches) == 2
        for b in batches:
            assert isinstance(b.features, jax.Array)
            assert isinstance(b.labels, jax.Array)
            assert b.features.is_ready() and b.labels.is_ready()
            assert b.features.dtype == b.labels.dtype == np.float32
            assert b._etl_host_ms >= 0.0 and b._etl_h2d_ms >= 0.0
        np.testing.assert_array_equal(np.asarray(batches[0].features),
                                      x[:32])

    @pytest.mark.parametrize("host_dtype", ["float16", "int32"])
    def test_a_staged_batch_keeps_the_hosts_dtype(self, host_dtype):
        x, y = _xy(64)
        it = DevicePrefetchIterator(
            ListDataSetIterator(DataSet(x.astype(host_dtype), y),
                                batch_size=32))
        for b in it:
            assert isinstance(b.features, jax.Array)
            assert b.features.dtype == np.dtype(host_dtype)

    def test_cast_dtype_is_no_argument(self):
        """The producer casts nothing, so it takes no dtype to cast to."""
        import jax.numpy as jnp
        base = ListDataSetIterator(DataSet(*_xy(64)), batch_size=32)
        with pytest.raises(TypeError, match="cast_dtype"):
            DevicePrefetchIterator(base, cast_dtype=jnp.bfloat16)

    def test_staging_a_new_shape_compiles_nothing(self):
        """`device_put` is a transfer; a cast would be an executable, and
        the first of its shape a compilation."""
        x, y = _xy(74, n_in=37)       # a shape no other test stages
        it = DevicePrefetchIterator(
            ListDataSetIterator(DataSet(x, y), batch_size=37))
        with CompilationTracker() as trk:
            batches = list(it)
        assert len(batches) == 2
        assert trk.count == 0

    def test_the_cast_runs_on_the_fit_thread_before_its_step(self, front):
        """A bfloat16 network fed float32 host batches: the producer
        stages float32, the front end's cast is called by the thread that
        called `fit` and by no other, and the step gets bfloat16."""
        import jax.numpy as jnp
        net = front.net(n_in=12, dtype="bfloat16")
        calls = _CastCalls(net, front.cast_name)
        operands = _step_features(net)
        x, y = _xy(96)
        net.fit(x, y, epochs=1, batch_size=32)
        assert len(calls.seen) == 3 and len(operands) == 3
        for thread, given, got in calls.seen:
            assert thread is threading.current_thread()
            assert isinstance(given, jax.Array)       # staged ...
            assert given.dtype == np.float32          # ... as the host's
            assert got.dtype == jnp.bfloat16
        assert all(f.dtype == jnp.bfloat16 for _, f in operands)

    def test_shutdown_mid_epoch(self):
        it = DevicePrefetchIterator(
            ListDataSetIterator(DataSet(*_xy(320)), batch_size=32), depth=2)
        stream = iter(it)
        next(stream)
        it.shutdown()
        assert it._thread is None  # producer joined, queue drained

    def test_base_error_propagates(self):
        class Exploding(ListDataSetIterator):
            def __next__(self):
                raise RuntimeError("disk on fire")

        it = DevicePrefetchIterator(
            Exploding(DataSet(*_xy(64)), batch_size=32))
        with pytest.raises(RuntimeError, match="disk on fire"):
            list(it)

    def test_reset_and_reuse(self):
        it = DevicePrefetchIterator(
            ListDataSetIterator(DataSet(*_xy(96)), batch_size=32))
        assert len(list(it)) == 3
        assert len(list(it)) == 3  # __iter__ resets; epoch 2 sees all data

    def test_sharded_staging_and_indivisible_passthrough(self):
        from deeplearning4j_tpu.parallel import data_parallel_mesh
        from deeplearning4j_tpu.parallel.mesh import batch_sharded
        mesh = data_parallel_mesh(8)
        sh = batch_sharded(mesh)
        # 80 rows / batch 32 -> 32, 32, 16: full batches stage sharded
        # 8 ways; the 16-row tail ALSO divides 8 and stages; a 30-row
        # tail would not. Exercise both.
        it = DevicePrefetchIterator(
            ListDataSetIterator(DataSet(*_xy(80)), batch_size=32),
            sharding=sh, batch_divisor=8)
        batches = list(it)
        assert [b.features.shape[0] for b in batches] == [32, 32, 16]
        for b in batches:
            assert b.features.sharding.is_equivalent_to(sh, b.features.ndim)
            # the host's array went straight under the sharding: committed
            # there, an eighth of the rows a device, the host's dtype
            assert b.features.committed and b.features.dtype == np.float32
            rows = b.features.shape[0] // 8
            assert [s.data.shape for s in b.features.addressable_shards] \
                == [(rows, 12)] * 8
            assert {s.data.dtype for s in b.features.addressable_shards} \
                == {np.dtype("float32")}
        # indivisible tail (30 % 8 != 0) passes through as host arrays
        it2 = DevicePrefetchIterator(
            ListDataSetIterator(DataSet(*_xy(94)), batch_size=32),
            sharding=sh, batch_divisor=8)
        tail = list(it2)[-1]
        assert tail.features.shape[0] == 30
        assert not isinstance(tail.features, jax.Array)

    def test_async_supported_false_prevents_double_wrap(self):
        it = DevicePrefetchIterator(
            ListDataSetIterator(DataSet(*_xy(64)), batch_size=32))
        assert it.async_supported() is False


class TestParallelWrapperPrefetch:
    def test_sharded_epoch_training_with_ragged_tail(self):
        from deeplearning4j_tpu.parallel import (ParallelWrapper,
                                                 data_parallel_mesh)
        x, y = _xy(80, n_in=12)
        net = _net()
        pw = ParallelWrapper(net, mesh=data_parallel_mesh(8))
        pw.fit(x, y, epochs=2, batch_size=32)
        ref = _net()
        ref.fit(x, y, epochs=2, batch_size=32, use_async=False)
        for pa, pb in zip(jax.tree_util.tree_leaves(net.params_tree),
                          jax.tree_util.tree_leaves(ref.params_tree)):
            np.testing.assert_allclose(np.asarray(pa), np.asarray(pb),
                                       rtol=2e-5, atol=2e-6)

    def test_a_sharded_step_casts_each_shard_of_a_float32_staging(
            self, front):
        """Under the wrapper the host's float32 batch is staged straight
        under the mesh's sharding and the wrapper's step casts it there:
        the step's features are bfloat16, sharded 8 ways, and the cast is
        the fit thread's."""
        import jax.numpy as jnp
        from deeplearning4j_tpu.parallel import (ParallelWrapper,
                                                 data_parallel_mesh)
        from deeplearning4j_tpu.parallel.mesh import batch_sharded
        net = front.net(n_in=12, dtype="bfloat16")
        mesh = data_parallel_mesh(8)
        staged, operands = [], _step_features(net)
        pw = ParallelWrapper(net, mesh=mesh)
        step = pw.fit_batch
        pw.fit_batch = lambda ds: (staged.append(ds), step(ds))
        x, y = _xy(64)
        pw.fit(x, y, epochs=1, batch_size=32)
        assert len(staged) == 2 and len(operands) == 2
        sh = batch_sharded(mesh)
        for ds in staged:
            f = ds.features[0] if isinstance(ds.features, list) \
                else ds.features
            assert f.dtype == np.float32 and f.sharding.is_equivalent_to(sh, 2)
        for thread, f in operands:
            assert thread is threading.current_thread()
            assert f.dtype == jnp.bfloat16
            assert f.sharding.is_equivalent_to(sh, 2)
            assert {s.data.shape for s in f.addressable_shards} == {(4, 12)}


class TestTelemetry:
    def test_compilation_tracker_counts_fresh_compiles(self):
        import jax.numpy as jnp

        @jax.jit
        def f(a):
            return a * 2 + 1

        with CompilationTracker() as trk:
            f(jnp.ones((3,))).block_until_ready()
        assert trk.count >= 1
        before = compilation_count()
        f(jnp.ones((3,))).block_until_ready()  # cached: no new compile
        assert compilation_count() == before

    def test_performance_listener_reports_breakdown(self, front):
        from deeplearning4j_tpu.optimize.listeners import PerformanceListener
        lines = []
        lst = PerformanceListener(frequency=1, printer=lines.append)
        net = front.net(n_in=12)
        net.listeners.append(lst)
        x, y = _xy(96)
        net.fit(x, y, epochs=1, batch_size=32)
        assert any("host" in ln and "h2d" in ln for ln in lines)
