"""Compile-cost control plane tests (docs/perf_compile_cache.md).

Covers the four tentpole legs: the persistent XLA cache round trip,
AOT precompile leaving the fit path compile-silent, lazy training-jit
construction for inference-only nets, and the recompile-churn guard.
"""
import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu import (Adam, DataSet, DenseLayer, InputType,
                                MultiLayerNetwork, NeuralNetConfiguration,
                                OutputLayer, WeightInit)
from deeplearning4j_tpu.optimize import compile_cache, telemetry
from deeplearning4j_tpu.optimize.metrics import registry



def mlp_conf(seed=42):
    return (NeuralNetConfiguration.builder().seed(seed)
            .updater(Adam(learning_rate=0.05))
            .weight_init(WeightInit.XAVIER)
            .list()
            .layer(DenseLayer(n_out=16, activation="tanh"))
            .layer(OutputLayer(n_out=3, activation="softmax",
                               loss="mcxent"))
            .set_input_type(InputType.feed_forward(4))
            .build())


def small_batch(n=16, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.standard_normal((n, 4)).astype(np.float32)
    y = np.eye(3, dtype=np.float32)[rng.integers(0, 3, n)]
    return x, y


class TestPersistentCache:
    def test_roundtrip_hits_in_process(self, tmp_path, monkeypatch):
        """Two structurally identical jits: the first populates the
        persistent cache (miss), the second deserializes from it (hit).
        Same-process round trip — the cross-process case is
        tests/smoke_compile_cache.py's job."""
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR",
                           str(tmp_path / "xla"))
        hits0 = registry().counter("compile_cache_hits_total", "h").value()
        misses0 = registry().counter("compile_cache_misses_total",
                                     "m").value()
        compile_cache.enable()
        try:
            x = jnp.asarray(np.arange(7.0, dtype=np.float32) + 1.0)
            f1 = jax.jit(lambda a: a * 3.0 + 1.0)
            np.testing.assert_allclose(np.asarray(f1(x)),
                                       np.asarray(x) * 3.0 + 1.0)
            misses = registry().counter("compile_cache_misses_total",
                                        "m").value()
            assert misses > misses0, "first compile should miss the cache"
            assert compile_cache.status()["entries"] >= 1
            # a NEW jit object with identical structure: the executable
            # comes back from disk, not from a fresh XLA compile
            f2 = jax.jit(lambda a: a * 3.0 + 1.0)
            np.testing.assert_allclose(np.asarray(f2(x)),
                                       np.asarray(x) * 3.0 + 1.0)
            hits = registry().counter("compile_cache_hits_total",
                                      "h").value()
            assert hits > hits0, "identical program should hit the cache"
        finally:
            compile_cache.disable()

    def test_status_reflects_enable_disable(self, tmp_path, monkeypatch):
        d = str(tmp_path / "xla2")
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", d)
        assert compile_cache.enable() == d
        try:
            st = compile_cache.status()
            assert st["enabled"] and st["dir"] == d
        finally:
            compile_cache.disable()
        assert compile_cache.status()["enabled"] is False



class TestPrecompile:
    def test_fit_zero_compiles_after_precompile(self):
        """The acceptance criterion: precompile() then fit shows ZERO
        additional XLA compilations for the precompiled signature —
        including the REAL fit() loop, whose pad-to-bucket iterator
        synthesizes a ones (b,1) labels mask on every batch (a second
        pytree signature precompile must cover)."""
        net = MultiLayerNetwork(mlp_conf()).init()
        net.precompile(16)
        assert net._train_step_fn.aot_signatures == 2  # maskless + ones
        x, y = small_batch(48)
        xj, yj = jnp.asarray(x), jnp.asarray(y)  # pre-stage the arrays
        with telemetry.CompilationTracker() as trk:
            net.fit(xj, yj, epochs=2, batch_size=16)
            net._do_step(jnp.asarray(x[:16]), jnp.asarray(y[:16]),
                         None, None)
            float(net.score_value)
        assert trk.count == 0, \
            f"precompiled step still compiled {trk.count}x"
        # the jit's own executable cache stayed EMPTY — dispatch went to
        # the AOT executable, not through jit tracing
        assert telemetry.jit_cache_size(net._train_step_fn) == 0
        tag = net._probe_tag
        assert registry().counter("precompiled_dispatch_hits_total",
                                  "h").value(
            fn=f"mln_train_step#{tag}") >= 1
        # and training still actually works
        s0 = float(net.score_value)
        for _ in range(5):
            net._do_step(xj, yj, None, None)
        assert float(net.score_value) < s0

    def test_precompiled_matches_jit_numerics(self):
        """AOT dispatch and plain jit dispatch are the same lowered
        program — identical results from identical state."""
        x, y = small_batch(16)
        a = MultiLayerNetwork(mlp_conf(7)).init()
        b = MultiLayerNetwork(mlp_conf(7)).init()
        a.precompile(16)
        for _ in range(3):
            a._fit_batch(DataSet(x, y))
            b._fit_batch(DataSet(x, y))
        np.testing.assert_allclose(np.asarray(a.score_value),
                                   np.asarray(b.score_value), rtol=1e-6)
        np.testing.assert_allclose(a.output(x), b.output(x), rtol=1e-6)

    def test_new_shape_falls_back_to_jit(self):
        net = MultiLayerNetwork(mlp_conf()).init()
        net.precompile(16)
        x, y = small_batch(24)  # different batch: no AOT signature
        net._fit_batch(DataSet(x, y))
        assert telemetry.jit_cache_size(net._train_step_fn) == 1
        assert np.isfinite(float(net.score_value))

    def test_warmup_inference_only(self):
        net = MultiLayerNetwork(mlp_conf()).init()
        net.warmup(8)
        assert "_train_step_fn" not in net.__dict__, \
            "warmup must not build training jits"
        x, _ = small_batch(8)
        with telemetry.CompilationTracker() as trk:
            out = net.output(x)
        assert out.shape == (8, 3)
        assert trk.count == 0

    def test_graph_precompile_zero_compiles(self):
        from deeplearning4j_tpu.nn.graph.graph import ComputationGraph
        g_conf = (NeuralNetConfiguration.builder().seed(3)
                  .updater(Adam(learning_rate=0.05))
                  .weight_init(WeightInit.XAVIER)
                  .graph_builder()
                  .add_inputs("in")
                  .add_layer("d", DenseLayer(n_out=16, activation="tanh"),
                             "in")
                  .add_layer("out", OutputLayer(n_out=3,
                                                activation="softmax",
                                                loss="mcxent"), "d")
                  .set_outputs("out")
                  .set_input_types(InputType.feed_forward(4))
                  .build())
        g = ComputationGraph(g_conf).init()
        g.precompile(16)
        x, y = small_batch(16)
        ds = DataSet(jnp.asarray(x), jnp.asarray(y))
        with telemetry.CompilationTracker() as trk:
            g.fit_batch(ds)
            float(g.score_value)
        assert trk.count == 0
        assert telemetry.jit_cache_size(g._train_step_fn) == 0

    def test_dispatch_bypasses_under_vmap(self):
        """A transform tracing through a PrecompiledDispatch must take
        the jit path (AOT executables cannot run on tracers)."""
        disp = compile_cache.PrecompiledDispatch(
            jax.jit(lambda a: a * 2.0), "test_vmap")
        disp.precompile(jax.ShapeDtypeStruct((4,), jnp.float32))
        batched = jax.vmap(disp)
        x = jnp.asarray(np.ones((3, 4), np.float32))
        np.testing.assert_allclose(np.asarray(batched(x)),
                                   np.ones((3, 4)) * 2.0)

    def test_static_argnums_signature(self):
        disp = compile_cache.PrecompiledDispatch(
            jax.jit(lambda a, n: a * n, static_argnums=(1,)),
            "test_static", static_argnums=(1,))
        disp.precompile(jax.ShapeDtypeStruct((4,), jnp.float32), 3)
        x = jnp.asarray(np.ones(4, np.float32))
        np.testing.assert_allclose(np.asarray(disp(x, 3)), 3.0 * np.ones(4))
        assert disp._cache_size() == 0  # served by the AOT executable
        np.testing.assert_allclose(np.asarray(disp(x, 5)), 5.0 * np.ones(4))
        assert disp._cache_size() == 1  # new static value -> jit path


class TestLazyTrainingJits:
    def test_inference_only_builds_no_training_jits(self):
        net = MultiLayerNetwork(mlp_conf()).init()
        assert "_train_step_fn" not in net.__dict__
        x, _ = small_batch(8)
        net.output(x)
        net.score(x=x, y=np.eye(3, dtype=np.float32)[np.zeros(8, int)])
        assert all(a not in net.__dict__
                   for a in ("_train_step_fn", "_multi_step_stacked_fn",
                             "_multi_step_repeat_fn"))

    def test_training_jits_build_on_first_fit(self):
        net = MultiLayerNetwork(mlp_conf()).init()
        x, y = small_batch(8)
        net._fit_batch(DataSet(x, y))
        assert "_train_step_fn" in net.__dict__
        assert np.isfinite(float(net.score_value))

    def test_rebuild_invalidates_training_jits(self):
        net = MultiLayerNetwork(mlp_conf()).init()
        x, y = small_batch(8)
        net._fit_batch(DataSet(x, y))
        net._build_jitted()  # the retrace path
        assert "_train_step_fn" not in net.__dict__
        net._fit_batch(DataSet(x, y))  # lazily rebuilt, still trains
        assert np.isfinite(float(net.score_value))

    def test_graph_inference_only_lazy(self):
        from deeplearning4j_tpu.nn.graph.graph import ComputationGraph
        g_conf = (NeuralNetConfiguration.builder().seed(3)
                  .updater(Adam(learning_rate=0.05))
                  .graph_builder()
                  .add_inputs("in")
                  .add_layer("out", OutputLayer(n_in=4, n_out=3,
                                                activation="softmax",
                                                loss="mcxent"), "in")
                  .set_outputs("out")
                  .build())
        g = ComputationGraph(g_conf).init()
        x, _ = small_batch(8)
        g.output(x)
        assert "_train_step_fn" not in g.__dict__


class TestChurnGuard:
    def test_fires_at_threshold(self, caplog, monkeypatch):
        monkeypatch.setenv(telemetry.ENV_CHURN_THRESHOLD, "3")
        telemetry.reset_churn()
        try:
            label = "test_step#churn"
            import logging
            with caplog.at_level(logging.WARNING,
                                 logger="deeplearning4j_tpu.optimize"
                                        ".telemetry"):
                for t in range(1, 6):
                    sig = telemetry.shape_signature(
                        np.zeros((8, t), np.float32))
                    telemetry.note_step_signature(label, sig)
            warnings = [r for r in caplog.records
                        if "RECOMPILE CHURN" in r.message]
            assert len(warnings) == 1, "churn warning must be one-shot"
            # 5 signatures, threshold 3 -> signatures 4 and 5 counted
            assert registry().counter("recompile_churn_total",
                                      "c").value(fn=label) == 2
            assert (label, 5) in telemetry.churn_offenders()
        finally:
            telemetry.reset_churn()

    def test_repeat_signature_is_free(self, monkeypatch):
        monkeypatch.setenv(telemetry.ENV_CHURN_THRESHOLD, "2")
        telemetry.reset_churn()
        try:
            sig = telemetry.shape_signature(np.zeros((4, 4), np.float32),
                                            None)
            for _ in range(10):
                n = telemetry.note_step_signature("test_step#stable", sig)
            assert n == 1
            assert registry().counter("recompile_churn_total",
                                      "c").value(fn="test_step#stable") == 0
        finally:
            telemetry.reset_churn()

    def test_train_step_records_signatures(self):
        telemetry.reset_churn()
        try:
            net = MultiLayerNetwork(mlp_conf()).init()
            x, y = small_batch(8)
            net._fit_batch(DataSet(x, y))
            label = f"mln_train_step#{net._probe_tag}"
            assert dict(telemetry.churn_offenders(100)).get(label) == 1
        finally:
            telemetry.reset_churn()
