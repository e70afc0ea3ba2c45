"""Serving gateway tests (docs/serving.md).

Covers the tentpole legs: continuous batching correctness under
concurrent clients, SLO-aware shedding (admission-time and in-queue),
per-model routing, checkpoint-gated hot-swap with zero dropped/errored
requests under live traffic, zero-compile steady state after warmup(),
and the satellite fixes (shared pow2 bucket rule, ParallelInference
shutdown draining, pooled/graceful JsonHttpServer).

Device work per test is deliberately tiny (a 4->16->3 MLP on CPU) per
the ROADMAP maintenance note; the sustained HTTP storm is `slow`.
"""
import json
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from deeplearning4j_tpu import (Adam, DataSet, DenseLayer, InputType,
                                MultiLayerNetwork, NeuralNetConfiguration,
                                OutputLayer, WeightInit)
from deeplearning4j_tpu.data.iterators import PadToBucketIterator
from deeplearning4j_tpu.data.padding import next_pow2_bucket
from deeplearning4j_tpu.optimize.metrics import registry
from deeplearning4j_tpu.optimize.resilience import CheckpointManager
from deeplearning4j_tpu.parallel.inference import (DeadlineExceededError,
                                                   InferenceMode,
                                                   ParallelInference,
                                                   QueueFullError,
                                                   ServerClosedError,
                                                   _next_bucket)
from deeplearning4j_tpu.serving import (ModelPool, ServingGateway, SwapError)
from deeplearning4j_tpu.utils.http_server import JsonHttpServer


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


def make_net(seed=42, train_seed=None):
    net = MultiLayerNetwork(mlp_conf(seed)).init()
    if train_seed is not None:
        rng = np.random.default_rng(train_seed)
        x = rng.standard_normal((16, 4)).astype(np.float32)
        y = np.eye(3, dtype=np.float32)[rng.integers(0, 3, 16)]
        net.fit(x, y, epochs=1, batch_size=16)
    return net


def rand_x(n, seed=0):
    return np.random.default_rng(seed).standard_normal(
        (n, 4)).astype(np.float32)


def post_json(url, payload):
    body = json.dumps(payload).encode()
    req = urllib.request.Request(url, body,
                                 {"Content-Type": "application/json"})
    try:
        with urllib.request.urlopen(req) as r:
            return r.status, json.loads(r.read())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read())


class _StubModel:
    """Forward-only stand-in so queue/shutdown semantics are testable
    without device work or timing luck."""

    _initialized = True

    def __init__(self, block_s=0.0, gate=None):
        self.block_s = block_s
        self.gate = gate  # threading.Event the forward waits on
        self.forward_entered = threading.Event()

    def output(self, x):
        self.forward_entered.set()
        if self.gate is not None:
            self.gate.wait(timeout=10)
        if self.block_s:
            time.sleep(self.block_s)
        return np.asarray(x) * 2.0


# ---------------------------------------------------------------------------
# Satellite: one shared pow2 bucket rule
# ---------------------------------------------------------------------------
class TestBucketRule:
    def test_next_pow2_bucket_values(self):
        assert [next_pow2_bucket(n) for n in (1, 2, 3, 4, 5, 8, 9, 31, 33)] \
            == [1, 2, 4, 4, 8, 8, 16, 32, 64]
        with pytest.raises(ValueError):
            next_pow2_bucket(0)

    def test_parallel_inference_shares_the_helper(self):
        assert _next_bucket is next_pow2_bucket

    def test_pad_to_bucket_iterator_pow2_mode(self):
        sizes = [5, 3, 8, 1]
        batches = [DataSet(rand_x(n, seed=n),
                           np.eye(3, dtype=np.float32)[[0] * n])
                   for n in sizes]
        out = list(PadToBucketIterator(batches, bucket_rows="pow2"))
        assert [ds.num_examples() for ds in out] == [8, 4, 8, 1]
        # default mode unchanged: first batch's count is the epoch target
        out_first = list(PadToBucketIterator(batches))
        assert [ds.num_examples() for ds in out_first] == [5, 5, 8, 5]

    def test_pow2_mode_rejects_bad_mode(self):
        with pytest.raises(ValueError):
            PadToBucketIterator([], bucket_rows="fibonacci")


# ---------------------------------------------------------------------------
# Satellite: ParallelInference deadline/queue/shutdown semantics
# ---------------------------------------------------------------------------
class TestParallelInferenceServing:
    def test_expired_deadline_sheds_in_queue(self):
        pi = ParallelInference(_StubModel(), batch_timeout_ms=1.0)
        try:
            with pytest.raises(DeadlineExceededError):
                pi.output(rand_x(2), deadline=time.monotonic() - 1.0)
            assert pi.total_shed == 1
        finally:
            pi.shutdown()

    def test_sequential_deadline_sheds(self):
        pi = ParallelInference(_StubModel(),
                               inference_mode=InferenceMode.SEQUENTIAL)
        with pytest.raises(DeadlineExceededError):
            pi.output(rand_x(1), deadline=time.monotonic() - 1.0)
        pi.shutdown()

    def test_queue_full_is_typed(self):
        gate = threading.Event()
        pi = ParallelInference(_StubModel(gate=gate), queue_limit=1,
                               batch_limit=1, batch_timeout_ms=0.0)
        try:
            results = []
            t = threading.Thread(
                target=lambda: results.append(pi.output(rand_x(1))))
            t.start()
            # wait until the collector picked up the first request and
            # is blocked in the forward (a bare queue-depth poll races:
            # on a loaded host it reads 0 before the request even
            # enqueued), then fill the 1-slot queue
            assert pi.model.forward_entered.wait(timeout=5)
            deadline = time.monotonic() + 5
            while pi.queue_depth() > 0 and time.monotonic() < deadline:
                time.sleep(0.005)
            blocked = threading.Thread(
                target=lambda: results.append(pi.output(rand_x(1))))
            blocked.start()
            deadline = time.monotonic() + 5
            while pi.queue_depth() < 1 and time.monotonic() < deadline:
                time.sleep(0.005)
            with pytest.raises(QueueFullError):
                pi.output(rand_x(1))
        finally:
            gate.set()
            t.join(timeout=5)
            blocked.join(timeout=5)
            pi.shutdown()

    def test_shutdown_serves_stragglers(self):
        pi = ParallelInference(_StubModel(block_s=0.01), batch_limit=2,
                               batch_timeout_ms=1.0)
        outs = []
        ts = [threading.Thread(
            target=lambda i=i: outs.append(pi.output(rand_x(1, seed=i))))
            for i in range(4)]
        for t in ts:
            t.start()
        time.sleep(0.02)
        pi.shutdown()
        for t in ts:
            t.join(timeout=5)
        assert len(outs) == 4  # every queued caller got a real answer

    def test_shutdown_fails_stranded_callers_instead_of_hanging(self):
        gate = threading.Event()
        pi = ParallelInference(_StubModel(gate=gate), batch_limit=1,
                               batch_timeout_ms=0.0, queue_limit=8)
        errors = []
        done = threading.Event()

        def call():
            try:
                pi.output(rand_x(1))
            except ServerClosedError as e:
                errors.append(e)
            finally:
                done.set()

        first = threading.Thread(target=lambda: pi.output(rand_x(1)))
        first.start()  # occupies the collector (gate closed)
        time.sleep(0.05)
        stranded = threading.Thread(target=call)
        stranded.start()
        time.sleep(0.05)
        # collector is wedged in the forward: the short join window
        # expires and the queued request must FAIL, not hang
        pi.shutdown(join_timeout=0.05)
        assert done.wait(timeout=5), "stranded caller still hanging"
        assert errors and "shut down" in str(errors[0])
        gate.set()
        first.join(timeout=5)

    def test_coalescing_never_overshoots_warmed_buckets(self):
        # Regression: two queued 5-row requests used to coalesce to 10
        # rows -> bucket 16, which warmup (batch_limit=8) never
        # precompiled -> a steady-state XLA compile. The collector must
        # carry the overflowing request to the NEXT batch instead.
        gate = threading.Event()
        pi = ParallelInference(_StubModel(gate=gate), batch_limit=8,
                               batch_timeout_ms=0.0, queue_limit=16)
        try:
            ts = [threading.Thread(
                target=lambda i=i: pi.output(rand_x(5, seed=i)))
                for i in range(4)]
            for t in ts:
                t.start()
            time.sleep(0.1)  # first request wedged in the forward,
            gate.set()       # three more queued — now release
            for t in ts:
                t.join(timeout=10)
            assert pi.executed_batch_sizes, "nothing executed"
            assert max(pi.executed_batch_sizes) <= 8, \
                (f"coalesced past the warmed bucket ceiling: "
                 f"{list(pi.executed_batch_sizes)}")
        finally:
            gate.set()
            pi.shutdown()

    def test_ewma_and_wait_estimate(self):
        pi = ParallelInference(_StubModel(block_s=0.02),
                               batch_timeout_ms=0.0)
        try:
            assert pi.estimate_wait_s() == 0.0  # cold: admit everything
            pi.output(rand_x(2))
            assert pi.estimate_wait_s() > 0.0
        finally:
            pi.shutdown()


# ---------------------------------------------------------------------------
# Gateway: routing, batching correctness, shedding
# ---------------------------------------------------------------------------
class TestGateway:
    def test_routes_by_model_name(self):
        a, b = make_net(seed=1), make_net(seed=2)
        gw = ServingGateway()
        gw.add_model("a", a, batch_limit=4)
        gw.add_model("b", b, batch_limit=4)
        try:
            x = rand_x(2, seed=3)
            np.testing.assert_array_equal(gw.predict("a", x), a.output(x))
            np.testing.assert_array_equal(gw.predict("b", x), b.output(x))
            with pytest.raises(KeyError):
                gw.predict("nope", x)
            with pytest.raises(ValueError):
                gw.add_model("a", a)  # duplicate name
        finally:
            gw.pool.shutdown()

    def test_concurrent_mixed_buckets_match_direct_output(self):
        net = make_net(train_seed=0)
        gw = ServingGateway()
        gw.add_model("m", net, batch_limit=8)
        gw.warmup()
        errs = []

        def hammer(i):
            try:
                xi = rand_x(1 + (i % 5), seed=i)
                got = gw.predict("m", xi, deadline_ms=30_000)
                np.testing.assert_allclose(got, net.output(xi),
                                           rtol=0, atol=1e-6)
            except Exception as e:  # surface in the main thread
                errs.append(e)

        try:
            ts = [threading.Thread(target=hammer, args=(i,))
                  for i in range(16)]
            for t in ts:
                t.start()
            for t in ts:
                t.join(timeout=30)
            assert not errs, errs[:3]
            entry = gw.pool.get("m")
            assert entry.engine.total_forwards >= 1
        finally:
            gw.pool.shutdown()

    def test_admission_shed_on_hopeless_deadline(self):
        net = make_net()
        gw = ServingGateway()
        gw.add_model("m", net, batch_limit=4)
        entry = gw.pool.get("m")
        entry.engine._ewma_batch_s = 10.0  # pretend service is slow
        shed0 = registry().counter("serving_shed_total", "").labels(
            model="m", reason="admission").value()
        try:
            with pytest.raises(DeadlineExceededError):
                gw.predict("m", rand_x(1), deadline_ms=5)
            assert registry().counter("serving_shed_total", "").labels(
                model="m", reason="admission").value() == shed0 + 1
            # no deadline -> no shed, even with a huge estimate
            out = gw.predict("m", rand_x(1))
            assert out.shape == (1, 3)
        finally:
            gw.pool.shutdown()

    def test_default_deadline_applies(self):
        net = make_net()
        gw = ServingGateway(default_deadline_ms=5)
        gw.add_model("m", net, batch_limit=4)
        gw.pool.get("m").engine._ewma_batch_s = 10.0
        try:
            with pytest.raises(DeadlineExceededError):
                gw.predict("m", rand_x(1))
        finally:
            gw.pool.shutdown()

    def test_zero_compiles_after_warmup(self):
        from deeplearning4j_tpu.optimize.telemetry import CompilationTracker
        net = make_net(train_seed=1)
        gw = ServingGateway()
        gw.add_model("m", net, batch_limit=8)
        gw.warmup()
        try:
            with CompilationTracker() as trk:
                for i in range(12):
                    gw.predict("m", rand_x(1 + (i % 7), seed=i))
            assert trk.count == 0, \
                f"steady-state serving compiled {trk.count}x"
        finally:
            gw.pool.shutdown()

    def test_latency_metrics_and_stats(self):
        net = make_net()
        gw = ServingGateway()
        gw.add_model("m", net, batch_limit=4)
        try:
            for i in range(5):
                gw.predict("m", rand_x(1, seed=i))
            st = gw.stats()
            assert st["latency"]["m"]["count"] == 5
            assert st["latency"]["m"]["p99_ms"] >= st["latency"]["m"]["p50_ms"]
            text = registry().prometheus_text()
            for family in ("serving_requests_total", "serving_admitted_total",
                           "serving_latency_ms_bucket", "serving_queue_depth",
                           "serving_latency_p50_ms", "serving_latency_p99_ms"):
                assert family in text, f"{family} missing from exposition"
        finally:
            gw.pool.shutdown()


# ---------------------------------------------------------------------------
# Hot-swap
# ---------------------------------------------------------------------------
class TestHotSwap:
    def test_swap_requires_manager_and_valid_checkpoint(self, tmp_path):
        gw = ServingGateway()
        gw.add_model("m", make_net())
        try:
            with pytest.raises(SwapError, match="no CheckpointManager"):
                gw.swap("m")
            empty = CheckpointManager(str(tmp_path / "empty"))
            with pytest.raises(SwapError, match="no valid checkpoint"):
                gw.swap("m", manager=empty)
        finally:
            gw.pool.shutdown()

    def test_swap_skips_torn_checkpoint(self, tmp_path):
        mgr = CheckpointManager(str(tmp_path / "ckpt"), keep_last=5)
        donor = make_net(seed=7, train_seed=7)
        rec = mgr.save(donor)
        # tear the only checkpoint on disk: manifest sha no longer matches
        import os
        p = os.path.join(mgr.directory, rec["file"])
        with open(p, "r+b") as f:
            f.seek(0)
            f.write(b"\0\0\0\0")
        gw = ServingGateway()
        gw.add_model("m", make_net())
        try:
            with pytest.raises(SwapError):
                gw.swap("m", manager=mgr)
        finally:
            gw.pool.shutdown()

    def test_swap_rejects_architecture_mismatch(self, tmp_path):
        other_conf = (NeuralNetConfiguration.builder().seed(1)
                      .updater(Adam(learning_rate=0.05))
                      .weight_init(WeightInit.XAVIER).list()
                      .layer(DenseLayer(n_out=9, activation="tanh"))
                      .layer(OutputLayer(n_out=3, activation="softmax",
                                         loss="mcxent"))
                      .set_input_type(InputType.feed_forward(4)).build())
        donor = MultiLayerNetwork(other_conf).init()
        mgr = CheckpointManager(str(tmp_path / "ckpt"))
        mgr.save(donor)
        net = make_net()
        gw = ServingGateway()
        gw.add_model("m", net, checkpoints=mgr)
        try:
            ref = net.output(rand_x(2))
            with pytest.raises(SwapError, match="cannot serve"):
                gw.swap("m")
            # old params still serving after the refused swap
            np.testing.assert_array_equal(gw.predict("m", rand_x(2)), ref)
        finally:
            gw.pool.shutdown()

    def test_swap_is_idempotent_per_checkpoint(self, tmp_path):
        donor = make_net(seed=9, train_seed=9)
        mgr = CheckpointManager(str(tmp_path / "ckpt"))
        mgr.save(donor)
        gw = ServingGateway()
        gw.add_model("m", make_net(), checkpoints=mgr)
        try:
            assert gw.swap("m")["swapped"] is True
            again = gw.swap("m")
            assert again["swapped"] is False
            assert "already serving" in again["reason"]
        finally:
            gw.pool.shutdown()

    def test_hot_swap_under_live_traffic_zero_drops(self, tmp_path):
        """The acceptance-criteria test: swap while concurrent clients
        hammer the gateway; every request gets a real answer (zero
        errors/drops), each answer matches exactly one of the two param
        versions, and post-swap the served params are bitwise the new
        net's (its answers agree to float32 rounding across executable
        shapes)."""
        net_v1 = make_net(seed=42)
        net_v2 = make_net(seed=42, train_seed=5)  # same arch, new params
        mgr = CheckpointManager(str(tmp_path / "pub"))
        mgr.save(net_v2)

        gw = ServingGateway()
        gw.add_model("m", net_v1, checkpoints=mgr, batch_limit=8)
        gw.warmup()
        probes = [rand_x(1 + (i % 4), seed=100 + i) for i in range(6)]
        ref_v1 = [net_v1.output(p) for p in probes]
        # NOTE: net_v2's own output — the gateway must serve exactly
        # these bytes after the swap.
        ref_v2 = [net_v2.output(p) for p in probes]
        for a, b in zip(ref_v1, ref_v2):
            assert not np.array_equal(a, b), "versions must differ"

        stop = threading.Event()
        failures = []
        answered = []

        def close(a, b):
            # tolerance, not bitwise: a coalesced forward shares its
            # batch with other clients' rows
            return np.allclose(a, b, rtol=0, atol=1e-5)

        def client(i):
            k = i % len(probes)
            while not stop.is_set():
                try:
                    got = gw.predict("m", probes[k])
                except Exception as e:
                    failures.append(e)
                    return
                if close(got, ref_v1[k]) or close(got, ref_v2[k]):
                    answered.append(1)
                else:
                    failures.append(AssertionError(
                        "response matches neither param version"))
                    return

        try:
            ts = [threading.Thread(target=client, args=(i,))
                  for i in range(6)]
            for t in ts:
                t.start()
            time.sleep(0.2)  # live traffic flowing
            res = gw.swap("m")
            assert res["swapped"] is True
            time.sleep(0.2)  # keep hammering post-swap
            stop.set()
            for t in ts:
                t.join(timeout=30)
            assert not failures, failures[:3]
            assert len(answered) > 20
            # post-swap: the new checkpoint's answers. A probe of r rows
            # is served from the pow2-bucket executable and `want` came
            # from net_v2's own r-row executable — two executables of
            # different shape agree to float32 rounding (1 ulp here),
            # not bitwise. What must be exact, the served PARAMS, is
            # checked bitwise below.
            for p, want in zip(probes, ref_v2):
                np.testing.assert_allclose(gw.predict("m", p), want,
                                           rtol=0, atol=1e-6)
            import jax
            leaves_live = [np.asarray(a) for a in
                           jax.tree_util.tree_leaves(net_v1.params_tree)]
            leaves_ckpt = [np.asarray(a) for a in
                           jax.tree_util.tree_leaves(net_v2.params_tree)]
            for a, b in zip(leaves_live, leaves_ckpt):
                np.testing.assert_array_equal(a, b)
            assert registry().counter("serving_swaps_total", "").labels(
                model="m", outcome="ok", precision="fp32").value() >= 1
        finally:
            stop.set()
            gw.pool.shutdown()


# ---------------------------------------------------------------------------
# HTTP surface + pooled/graceful JsonHttpServer
# ---------------------------------------------------------------------------
class TestHttpSurface:
    def test_predict_swap_health_models_metrics(self, tmp_path):
        donor = make_net(seed=3, train_seed=3)
        mgr = CheckpointManager(str(tmp_path / "ckpt"))
        mgr.save(donor)
        net = make_net(seed=3)
        gw = ServingGateway()
        gw.add_model("default", net, checkpoints=mgr, batch_limit=4)
        gw.warmup()
        with gw:
            x = rand_x(2, seed=1)
            code, body = post_json(gw.url + "/predict",
                                   {"features": x.tolist()})
            assert code == 200 and body["status"] == "ok"
            assert body["version"] == "initial"
            np.testing.assert_allclose(
                np.asarray(body["predictions"], np.float32),
                net.output(x), rtol=0, atol=1e-6)

            code, body = post_json(gw.url + "/predict",
                                   {"model": "ghost",
                                    "features": x.tolist()})
            assert code == 404

            code, body = post_json(gw.url + "/swap", {"model": "default"})
            assert code == 200 and body["swapped"] is True
            code, body = post_json(gw.url + "/predict",
                                   {"features": x.tolist()})
            assert code == 200
            assert body["version"].startswith("checkpoint-")
            np.testing.assert_array_equal(
                np.asarray(body["predictions"], np.float32),
                donor.output(x))

            with urllib.request.urlopen(gw.url + "/health") as r:
                assert json.loads(r.read())["models"] == ["default"]
            with urllib.request.urlopen(gw.url + "/models") as r:
                desc = json.loads(r.read())["models"][0]
                assert desc["swaps"] == 1
            with urllib.request.urlopen(gw.url + "/metrics") as r:
                text = r.read().decode()
                assert r.headers["Content-Type"].startswith("text/plain")
                for family in ("serving_requests_total",
                               "serving_queue_depth",
                               "serving_swaps_total",
                               "serving_latency_ms_bucket"):
                    assert family in text

    def test_shed_maps_to_distinct_status(self):
        net = make_net()
        gw = ServingGateway()
        gw.add_model("m", net)
        gw.pool.get("m").engine._ewma_batch_s = 10.0
        with gw:
            code, body = post_json(gw.url + "/predict",
                                   {"model": "m",
                                    "features": rand_x(1).tolist(),
                                    "deadline_ms": 5})
            assert code == 503
            assert body["status"] == "shed"
            assert body["reason"] == "deadline"

    def test_graceful_stop_finishes_inflight_handlers(self):
        release = threading.Event()

        def slow_route(_):
            release.wait(timeout=5)
            return 200, {"done": True}

        srv = JsonHttpServer(get_routes={"/slow": slow_route},
                             post_routes={}, pool_size=2).start()
        url = srv.url + "/slow"
        results = []

        def call():
            with urllib.request.urlopen(url) as r:
                results.append(json.loads(r.read()))

        t = threading.Thread(target=call)
        t.start()
        time.sleep(0.1)  # handler is in flight, parked on the event
        stopper = threading.Thread(target=srv.stop)
        stopper.start()
        time.sleep(0.05)
        release.set()  # let the in-flight handler finish
        stopper.join(timeout=5)
        t.join(timeout=5)
        assert results == [{"done": True}], \
            "graceful stop dropped an in-flight response"

    def test_knn_and_keras_servers_expose_metrics(self):
        from deeplearning4j_tpu.serving import NearestNeighborsServer
        pts = np.random.default_rng(0).standard_normal(
            (16, 3)).astype(np.float32)
        with NearestNeighborsServer(pts, use_device=False) as srv:
            with urllib.request.urlopen(srv.url + "/metrics") as r:
                assert b"process_start_time_seconds" in r.read()


@pytest.mark.slow
class TestSustainedStorm:
    def test_sustained_http_storm_with_swap(self, tmp_path):
        """Heavier end-to-end: HTTP clients at sustained load across a
        swap; zero 5xx besides deliberate sheds, zero dropped sockets."""
        donor = make_net(seed=11, train_seed=11)
        mgr = CheckpointManager(str(tmp_path / "ckpt"))
        mgr.save(donor)
        gw = ServingGateway(pool_size=8)
        gw.add_model("default", make_net(seed=11), checkpoints=mgr,
                     batch_limit=8)
        gw.warmup()
        failures, oks = [], []
        stop = threading.Event()

        def client(i):
            x = rand_x(1 + (i % 4), seed=i).tolist()
            while not stop.is_set():
                try:
                    code, body = post_json(gw.url + "/predict",
                                           {"features": x})
                except Exception as e:
                    failures.append(e)
                    return
                if code != 200:
                    failures.append(AssertionError((code, body)))
                    return
                oks.append(1)

        with gw:
            ts = [threading.Thread(target=client, args=(i,))
                  for i in range(8)]
            for t in ts:
                t.start()
            time.sleep(0.5)
            assert post_json(gw.url + "/swap", {})[1]["swapped"] is True
            time.sleep(0.5)
            stop.set()
            for t in ts:
                t.join(timeout=30)
        assert not failures, failures[:3]
        assert len(oks) > 50
