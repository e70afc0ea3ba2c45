"""Unified observability: MetricsRegistry, span tracing, scrape
endpoints (docs/observability.md).

Covers the tentpole contracts: thread-safe labeled families with
Prometheus text exposition, the fit-loop span taxonomy
fit/epoch/step/{etl,dispatch,device} with nesting, the sampled device
fence, PerformanceListener report contents (compile delta, ETL
host/h2d split, dispatch-side mode), and live GET /metrics / GET /trace
off a running UIServer."""
import json
import re
import threading
import urllib.request

import numpy as np
import pytest

from deeplearning4j_tpu import (Adam, DenseLayer, InputType,
                                MultiLayerNetwork, NeuralNetConfiguration,
                                OutputLayer)
from deeplearning4j_tpu.data.dataset import DataSet
from deeplearning4j_tpu.optimize import metrics as metrics_mod
from deeplearning4j_tpu.optimize import tracing
from deeplearning4j_tpu.optimize.listeners import PerformanceListener
from deeplearning4j_tpu.optimize.metrics import (MetricsRegistry,
                                                 device_memory_stats,
                                                 host_rss_bytes, registry)


def _net(seed=7, n_in=6, classes=3):
    conf = (NeuralNetConfiguration.builder().seed(seed)
            .updater(Adam(0.01)).list()
            .layer(DenseLayer(n_out=8, activation="tanh"))
            .layer(OutputLayer(n_out=classes, activation="softmax",
                               loss="mcxent"))
            .set_input_type(InputType.feed_forward(n_in)).build())
    return MultiLayerNetwork(conf).init()


def _data(n=48, n_in=6, classes=3, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.standard_normal((n, n_in)).astype(np.float32)
    y = np.eye(classes, dtype=np.float32)[rng.integers(0, classes, n)]
    return DataSet(x, y)


@pytest.fixture(autouse=True)
def _tracing_off():
    """Every test starts and ends with tracing disabled and an empty
    ring — the module is process-global state."""
    tracing.disable()
    tracing.clear()
    yield
    tracing.disable()
    tracing.clear()


# ---------------------------------------------------------------------------
# Registry primitives
# ---------------------------------------------------------------------------
class TestRegistry:
    def test_counter_gauge_histogram_basics(self):
        reg = MetricsRegistry()
        c = reg.counter("c_total", "help")
        c.inc()
        c.inc(2)
        assert c.value() == 3
        with pytest.raises(ValueError):
            c.inc(-1)
        g = reg.gauge("g", "help")
        g.set(1.5)
        g.inc(0.5)
        assert g.value() == 2.0
        h = reg.histogram("h_ms", "help", buckets=(1, 10, 100))
        for v in (0.5, 5, 50, 500):
            h.observe(v)
        assert h.count == 4 and h.sum == 555.5

    def test_same_name_same_family_kind_conflict_raises(self):
        reg = MetricsRegistry()
        assert reg.counter("x_total") is reg.counter("x_total")
        with pytest.raises(TypeError):
            reg.gauge("x_total")

    def test_labeled_children_are_independent(self):
        reg = MetricsRegistry()
        c = reg.counter("steps_total", "help")
        c.labels(worker="0").inc(3)
        c.labels(worker="1").inc(5)
        assert c.value(worker="0") == 3
        assert c.value(worker="1") == 5
        # label order is irrelevant to identity
        g = reg.gauge("q", "help")
        g.labels(a="1", b="2").set(7)
        assert g.value(b="2", a="1") == 7

    def test_concurrent_increments_lose_nothing(self):
        """8 threads hammering one counter (and labeled children): the
        total must be exact — a torn read/write would show here."""
        reg = MetricsRegistry()
        c = reg.counter("conc_total", "help")
        h = reg.histogram("conc_ms", "help")
        n, per = 8, 1000
        barrier = threading.Barrier(n)

        def work(wid):
            mine = c.labels(worker=str(wid))
            barrier.wait()
            for _ in range(per):
                c.inc()
                mine.inc()
                h.observe(1.0)

        threads = [threading.Thread(target=work, args=(i,))
                   for i in range(n)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert c.value() == n * per
        for i in range(n):
            assert c.value(worker=str(i)) == per
        assert h.count == n * per

    def test_prometheus_text_parses(self):
        """Every line of the exposition is a comment or
        `name{labels} value`; histogram buckets are cumulative and end
        at +Inf == _count."""
        reg = MetricsRegistry()
        reg.counter("a_total", "things").inc(2)
        reg.gauge("b_bytes", 'quoted "help"').labels(
            device='cpu:0"x"\ny').set(10)
        h = reg.histogram("c_ms", "lat", buckets=(1, 10))
        for v in (0.5, 5, 50):
            h.observe(v)
        text = reg.prometheus_text()
        sample = re.compile(
            r'^[a-zA-Z_:][a-zA-Z0-9_:]*'
            r'(\{[a-zA-Z_][a-zA-Z0-9_]*="(\\.|[^"\\])*"'
            r'(,[a-zA-Z_][a-zA-Z0-9_]*="(\\.|[^"\\])*")*\})? '
            r'-?[0-9]+(\.[0-9]+)?([eE][+-]?[0-9]+)?$')
        for line in text.strip().splitlines():
            if line.startswith("# HELP ") or line.startswith("# TYPE "):
                continue
            assert sample.match(line), f"unparseable line: {line!r}"
        assert "# TYPE a_total counter" in text
        assert "# TYPE c_ms histogram" in text
        buckets = [int(l.rsplit(" ", 1)[1]) for l in text.splitlines()
                   if l.startswith("c_ms_bucket")]
        assert buckets == sorted(buckets)  # cumulative
        assert buckets[-1] == 3  # +Inf == observation count
        assert "c_ms_count 3" in text

    def test_snapshot_flat_dict(self):
        reg = MetricsRegistry()
        reg.counter("s_total").inc(4)
        reg.histogram("lat_ms", buckets=(1,)).observe(2.5)
        snap = reg.snapshot()
        assert snap["s_total"] == 4
        assert snap["lat_ms_count"] == 1
        assert snap["lat_ms_sum"] == 2.5

    def test_broken_collector_never_fails_a_scrape(self):
        reg = MetricsRegistry()
        reg.register_collector(lambda r: 1 / 0)
        reg.counter("ok_total").inc()
        assert "ok_total 1" in reg.prometheus_text()

    def test_host_and_device_samplers(self):
        # > 1 MiB of RSS proves the Linux KiB branch scaled to bytes
        # (the raw KiB figure would read as < 1 MiB of "bytes")
        assert host_rss_bytes() > 1024 * 1024
        devs = device_memory_stats()
        assert len(devs) >= 1  # conftest forces an 8-device CPU mesh
        for d in devs:
            assert d["bytes_in_use"] >= 0
            assert d["peak_bytes_in_use"] >= 0

    def test_global_registry_exposes_runtime_gauges(self):
        text = registry().prometheus_text()
        assert "host_rss_bytes" in text
        assert "device_bytes_in_use" in text
        assert "device_peak_bytes_in_use" in text
        assert "xla_compilations_total" in text


# ---------------------------------------------------------------------------
# Tracing
# ---------------------------------------------------------------------------
class TestTracing:
    def test_disabled_span_is_shared_noop(self):
        assert tracing.span("x") is tracing.span("y")
        assert tracing.begin("z") is tracing.span("x")
        tracing.add_span("w", 0.0, 1.0)
        assert tracing.export_trace_events()["traceEvents"] == []

    def test_ring_bound_respected(self):
        tracing.enable(ring_size=8, fence_every=0)
        for i in range(20):
            tracing.add_span(f"s{i}", float(i), 0.5)
        events = tracing.export_trace_events()["traceEvents"]
        assert len(events) == 8
        assert events[0]["name"] == "s12"  # oldest evicted

    def test_fence_sampling_and_gating(self):
        import jax.numpy as jnp
        val = jnp.ones((4,))
        # tracing off: never fences
        assert tracing.fence(16, val) is None
        tracing.enable(fence_every=4)
        assert tracing.fence(3, val) is None
        w = tracing.fence(4, val)
        assert w is not None and w >= 0.0
        names = [e["name"] for e in
                 tracing.export_trace_events()["traceEvents"]]
        assert names == ["device"]
        # fence_every=0 disables fencing even with tracing on
        tracing.enable(fence_every=0)
        assert tracing.fence(4, val) is None

    def test_fit_emits_nested_taxonomy(self, front):
        tracing.enable(fence_every=2)
        front.net(n_in=6).fit(_data(), epochs=2, batch_size=16)
        doc = tracing.export_trace_events()
        json.loads(json.dumps(doc))  # serializable
        events = doc["traceEvents"]
        by_name = {}
        for e in events:
            assert e["ph"] == "X" and e["dur"] >= 0
            by_name.setdefault(e["name"], []).append(e)
        assert len(by_name["fit"]) == 1
        assert len(by_name["epoch"]) == 2
        assert len(by_name["step"]) == 6  # 48/16 batches x 2 epochs
        assert len(by_name["etl"]) == 6
        assert len(by_name["dispatch"]) == 6
        assert len(by_name["device"]) == 3  # steps 2, 4, 6

        def contains(outer, inner, slack_us=500.0):
            return (outer["ts"] - slack_us <= inner["ts"] and
                    inner["ts"] + inner["dur"]
                    <= outer["ts"] + outer["dur"] + slack_us)

        fit = by_name["fit"][0]
        for ep in by_name["epoch"]:
            assert contains(fit, ep)
        for st in by_name["step"]:
            assert any(contains(ep, st) for ep in by_name["epoch"])
        for etl in by_name["etl"]:
            assert any(contains(st, etl) for st in by_name["step"])

    def test_dump_writes_valid_json(self, tmp_path):
        tracing.enable()
        with tracing.span("outer", k=1):
            with tracing.span("inner"):
                pass
        p = tracing.dump(str(tmp_path / "trace.json"))
        with open(p) as f:
            doc = json.load(f)
        names = [e["name"] for e in doc["traceEvents"]]
        assert names == ["outer", "inner"]  # sorted by start time
        # args survive export, beside the span's id and its parent's
        outer, inner = doc["traceEvents"]
        assert outer["args"] == {"k": 1, "span_id": outer["args"]["span_id"],
                                 "parent_id": 0}
        assert inner["args"]["parent_id"] == outer["args"]["span_id"]
        assert set(doc["clock"]) == {"perf_counter_s", "unix_ns"}

    def test_fit_records_step_metrics(self, front):
        reg = registry()
        before = reg.counter("train_iterations_total").value()
        ep_before = reg.counter("train_epochs_total").value()
        front.net(n_in=6).fit(_data(), epochs=2, batch_size=16)
        assert reg.counter("train_iterations_total").value() - before == 6
        assert reg.counter("train_epochs_total").value() - ep_before == 2
        snap = reg.snapshot()
        assert snap["train_step_dispatch_ms_count"] > 0
        assert "etl_ms" in snap


# ---------------------------------------------------------------------------
# PerformanceListener reports
# ---------------------------------------------------------------------------
class _StubModel:
    def __init__(self):
        self.score_value = 0.25
        self.last_etl_ms = 3.0
        self.last_etl_host_ms = 2.0
        self.last_etl_h2d_ms = 1.0


class TestPerformanceListener:
    def test_report_contents_and_compile_delta(self):
        import jax
        import jax.numpy as jnp
        msgs = []
        pl = PerformanceListener(frequency=1, printer=msgs.append)
        pl.set_batch_size(32)
        model = _StubModel()
        pl.iteration_done(model, 1)  # baseline report (no interval yet)
        # a FRESH jitted shape between reports => nonzero compile delta
        jax.jit(lambda x: x * 3.5)(jnp.ones((3, 3)))
        pl.iteration_done(model, 2)
        msg = msgs[-1]
        assert "batches/sec" in msg and "ms/iter" in msg
        assert "samples/sec" in msg
        assert "etl 3.00 ms (host 2.00 ms, h2d 1.00 ms)" in msg
        assert re.search(r"\d+ xla compilations", msg)
        assert pl.last_compile_delta >= 1
        assert "[dispatch-side]" not in msg
        # fenced report published the score to the registry
        assert registry().gauge("train_score").value() == 0.25

    def test_fence_false_is_dispatch_side_only(self):
        registry().gauge("train_score").set(-1.0)
        msgs = []
        pl = PerformanceListener(frequency=1, printer=msgs.append,
                                 fence=False)
        model = _StubModel()
        model.score_value = 0.75
        pl.iteration_done(model, 1)
        pl.iteration_done(model, 2)
        assert "[dispatch-side]" in msgs[-1]
        # no fenced score read: the registry gauge was not touched
        assert registry().gauge("train_score").value() == -1.0

    def test_throughput_gauges_written(self):
        msgs = []
        pl = PerformanceListener(frequency=1, printer=msgs.append)
        pl.set_batch_size(16)
        model = _StubModel()
        pl.iteration_done(model, 1)
        pl.iteration_done(model, 2)
        snap = registry().snapshot()
        assert snap["train_batches_per_sec"] > 0
        assert snap["train_ms_per_iter"] > 0
        assert snap["train_samples_per_sec"] > 0


# ---------------------------------------------------------------------------
# Live scrape endpoints
# ---------------------------------------------------------------------------
class TestScrapeEndpoints:
    def test_metrics_and_trace_over_http(self):
        from deeplearning4j_tpu.ui.server import UIServer
        tracing.enable(fence_every=2)
        _net().fit(_data(), epochs=2, batch_size=16)
        server = UIServer(port=0).start()
        try:
            with urllib.request.urlopen(server.url + "/metrics",
                                        timeout=10) as r:
                assert r.status == 200
                assert "text/plain" in r.headers["Content-Type"]
                text = r.read().decode()
            with urllib.request.urlopen(server.url + "/trace",
                                        timeout=10) as r:
                assert "application/json" in r.headers["Content-Type"]
                trace = json.loads(r.read())
        finally:
            server.stop()
        families = {ln.split()[2] for ln in text.splitlines()
                    if ln.startswith("# TYPE ")}
        assert len(families) >= 10
        for needed in ("train_iterations_total", "train_epochs_total",
                       "xla_compilations_total", "device_bytes_in_use",
                       "device_peak_bytes_in_use", "host_rss_bytes",
                       "etl_ms", "train_step_dispatch_ms"):
            assert needed in families, f"{needed} missing from /metrics"
        m = re.search(r"^train_iterations_total (\d+)", text, re.M)
        assert m and int(m.group(1)) >= 6
        # per-device gauges: one labeled sample per local device
        dev_lines = [l for l in text.splitlines()
                     if l.startswith("device_bytes_in_use{")]
        import jax
        assert len(dev_lines) == len(jax.local_devices())
        names = {e["name"] for e in trace["traceEvents"]}
        assert {"fit", "epoch", "step"} <= names
