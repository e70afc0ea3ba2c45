"""Flight-recorder tests (docs/observability.md §"Request flight
recorder"): RequestTrace cut-point mechanics (contiguous / monotonic /
sum-to-wall by construction), phase ATTRIBUTION correctness under
`delay:` chaos (a delay at serve.schedule must land in sched_wait, at
serve.forward in device — not just "some phase got slower"), the
bounded exemplar ring (capture rules + eviction), the gateway surfaces
(/debug/requests + /trace gating, response-embedded timelines, and
the always-on SLO burn counter).

Everything here runs against stub models — no jax device work — so the
whole file stays tier-1 fast (ROADMAP budget note)."""
import time

import numpy as np
import pytest

from deeplearning4j_tpu.optimize import tracing
from deeplearning4j_tpu.optimize.metrics import registry
from deeplearning4j_tpu.parallel.inference import (BatchExecutionError,
                                                   InferenceMode,
                                                   ParallelInference)
from deeplearning4j_tpu.serving import ModelPool, ServingGateway
from deeplearning4j_tpu.serving import flight_recorder as fr
from deeplearning4j_tpu.serving.scheduler import DeviceScheduler
from deeplearning4j_tpu.utils import faults
from deeplearning4j_tpu.utils.http_server import JsonHttpServer, json_request


class _StubModel:
    _initialized = True

    def __init__(self, delay_s: float = 0.0):
        self.delay_s = delay_s

    def output(self, x, **kw):
        if self.delay_s:
            time.sleep(self.delay_s)
        return np.asarray(x) * 2.0

    def warmup(self, b, time_steps=None):
        pass


@pytest.fixture
def recorder():
    """Recorder armed with a small exemplar ring; always disarmed (and
    chaos reset) on the way out so the rest of the suite sees the
    default-off state."""
    fr.enable(exemplar_ring=8)
    fr.clear()
    tracing.clear()
    yield fr
    fr.disable()
    faults.reset()


def _engine_with_scheduler(model, name="m"):
    pi = ParallelInference(model, batch_timeout_ms=0.0, batch_limit=4)
    sch = DeviceScheduler()
    sch.register(name, tier="standard")
    pi.scheduler = sch
    pi.sched_name = name
    return pi


# ---------------------------------------------------------------------------
# RequestTrace mechanics
# ---------------------------------------------------------------------------
class TestRequestTrace:
    def test_segments_contiguous_monotonic_and_sum_to_span(self):
        tr = fr.RequestTrace(1, "m", "standard")
        for ph in ("admission", "queue_wait", "pack"):
            time.sleep(0.001)
            tr.mark(ph)
        segs = tr.segments()
        assert [p for p, _, _ in segs] == ["admission", "queue_wait",
                                           "pack"]
        prev_end = tr.t0
        for _, start, dur in segs:
            assert start == pytest.approx(prev_end, abs=1e-9)
            assert dur >= 0.0
            prev_end = start + dur
        total = sum(d for _, _, d in segs)
        assert total == pytest.approx(tr.marks[-1][1] - tr.t0, abs=1e-9)

    def test_phase_ms_aggregates_repeated_segments(self):
        # a solo retry re-enters earlier phases: segments of the same
        # phase must SUM, not overwrite
        tr = fr.RequestTrace(1, "m", "standard")
        t = tr.t0
        tr.mark("device", t + 0.010)
        tr.mark("queue_wait", t + 0.015)
        tr.mark("device", t + 0.035)
        pm = tr.phase_ms()
        assert pm["device"] == pytest.approx(30.0, abs=1e-6)
        assert pm["queue_wait"] == pytest.approx(5.0, abs=1e-6)

    def test_new_trace_none_when_disabled(self):
        assert not fr.is_enabled()
        assert fr.new_trace("m") is None
        assert fr.complete(None, "ok", 1.0) is None


# ---------------------------------------------------------------------------
# Exemplar store
# ---------------------------------------------------------------------------
class TestExemplarStore:
    def test_ring_bound_and_eviction(self, recorder):
        ids = []
        for _ in range(12):
            t = fr.new_trace("m", "standard")
            t.mark("admission")
            ids.append(t.rid)
            fr.complete(t, "error", 1.0)
        ex = fr.exemplars()
        assert len(ex) == 8  # fixture ring size — oldest 4 evicted
        assert [e["id"] for e in ex] == ids[-8:]

    def test_captures_only_over_slo_or_not_ok(self, recorder):
        ok = fr.new_trace("m", "standard")
        ok.mark("admission")
        fr.complete(ok, "ok", 5.0, slo_ms=250.0)
        assert fr.exemplars() == []  # fast + ok: no exemplar
        slow = fr.new_trace("m", "standard")
        slow.mark("admission")
        fr.complete(slow, "ok", 400.0, slo_ms=250.0)
        shed = fr.new_trace("m", "standard")
        shed.mark("admission")
        fr.complete(shed, "shed", 0.2, slo_ms=250.0)
        got = fr.exemplars()
        assert [e["id"] for e in got] == [slow.rid, shed.rid]
        assert got[0]["slo_ms"] == 250.0 and got[0]["wall_ms"] == 400.0

    def test_filters_by_model_and_tier(self, recorder):
        a = fr.new_trace("a", "critical")
        a.mark("admission")
        fr.complete(a, "error", 1.0)
        b = fr.new_trace("b", "batch")
        b.mark("admission")
        fr.complete(b, "error", 1.0)
        assert [e["model"] for e in fr.exemplars(model="a")] == ["a"]
        assert [e["tier"] for e in fr.exemplars(tier="batch")] == ["batch"]
        assert len(fr.exemplars()) == 2

    def test_histogram_exposition_carries_exemplar_comment(self, recorder):
        t = fr.new_trace("exm", "standard")
        t.mark("admission")
        fr.complete(t, "ok", 500.0, slo_ms=250.0)
        txt = registry().prometheus_text()
        assert "# EXEMPLAR serving_phase_ms" in txt
        assert f'trace_id="{t.rid}"' in txt
        assert "see=/debug/requests" in txt

    def test_complete_emits_serve_spans(self, recorder):
        t = fr.new_trace("m", "standard")
        t.mark("admission")
        fr.complete(t, "ok", 1.0)
        evs = tracing.export_trace_events()["traceEvents"]
        serve = [e for e in evs if e.get("cat") == "serve"]
        assert any(e["name"] == "serve/admission" for e in serve)


# ---------------------------------------------------------------------------
# Phase ATTRIBUTION under chaos (the satellite's core claim: a delay at
# a known seam shows up in the RIGHT phase, not just somewhere)
# ---------------------------------------------------------------------------
class TestPhaseAttribution:
    def test_delay_at_schedule_lands_in_sched_wait(self, recorder):
        pi = _engine_with_scheduler(_StubModel())
        try:
            with faults.injected("serve.schedule", "delay:1@80"):
                tr = fr.new_trace("m", "standard")
                tr.mark("admission")
                pi.output(np.ones((1, 4), np.float32), trace=tr)
            pm = tr.phase_ms()
            assert pm["sched_wait"] >= 50.0, pm
            assert pm.get("device", 0.0) < 50.0, pm
        finally:
            pi.shutdown()

    def test_delay_at_forward_lands_in_device(self, recorder):
        pi = _engine_with_scheduler(_StubModel())
        try:
            with faults.injected("serve.forward", "delay:1@80"):
                tr = fr.new_trace("m", "standard")
                tr.mark("admission")
                pi.output(np.ones((1, 4), np.float32), trace=tr)
            pm = tr.phase_ms()
            assert pm["device"] >= 50.0, pm
            assert pm.get("sched_wait", 0.0) < 50.0, pm
        finally:
            pi.shutdown()

    def test_batched_trace_walks_all_seven_phases(self, recorder):
        pi = ParallelInference(_StubModel(), batch_timeout_ms=0.0)
        try:
            tr = fr.new_trace("m", "standard")
            tr.mark("admission")
            pi.output(np.ones((2, 3), np.float32), trace=tr)
            assert [p for p, _ in tr.marks] == list(fr.ONESHOT_PHASES)
            assert tr.ctx["batch_rows"] == 2 and tr.ctx["bucket"] == 2
        finally:
            pi.shutdown()

    def test_sequential_mode_marks_device_phases_only(self, recorder):
        pi = ParallelInference(_StubModel(),
                               inference_mode=InferenceMode.SEQUENTIAL)
        try:
            tr = fr.new_trace("m", "standard")
            tr.mark("admission")
            pi.output(np.ones((1, 4), np.float32), trace=tr)
            assert [p for p, _ in tr.marks] == [
                "admission", "sched_wait", "dispatch", "device", "unpack"]
        finally:
            pi.shutdown()

    def test_failed_forward_closes_window_and_counts_attempt(
            self, recorder):
        pi = ParallelInference(_StubModel(), batch_timeout_ms=0.0)
        try:
            with faults.injected("serve.forward", "fail:1"):
                tr = fr.new_trace("m", "standard")
                tr.mark("admission")
                with pytest.raises(BatchExecutionError):
                    pi.output(np.ones((1, 4), np.float32), trace=tr)
            assert tr.ctx["failed_attempts"] == 1
            assert tr.marks[-1][0] == "device"  # window closed, not torn
        finally:
            pi.shutdown()

    def test_untraced_output_identical(self, recorder):
        # recorder ON but this request carries no trace: the engine path
        # must behave exactly as before (trace plumbing is per-request)
        pi = ParallelInference(_StubModel(), batch_timeout_ms=0.0)
        try:
            out = pi.output(np.ones((2, 3), np.float32))
            np.testing.assert_array_equal(out,
                                          np.full((2, 3), 2.0, np.float32))
        finally:
            pi.shutdown()


# ---------------------------------------------------------------------------
# Gateway surfaces
# ---------------------------------------------------------------------------
class TestGatewaySurfaces:
    def test_debug_and_trace_routes_gated_when_disabled(self):
        assert not fr.is_enabled()
        pool = ModelPool()
        pool.add("m", _StubModel())
        gw = ServingGateway(pool)
        try:
            code, resp = gw._debug_requests_route(None)
            assert code == 404 and resp["enabled"] is False
            code, ctype, body = gw._trace_route()
            assert code == 404
            # and /predict responses carry no trace key
            code, resp = gw._predict_route(
                {"model": "m", "features": [[1.0, 2.0, 3.0]]})
            assert code == 200 and "trace" not in resp
        finally:
            pool.shutdown()

    def test_predict_embeds_trace_and_debug_route_filters(self, recorder):
        pool = ModelPool()
        pool.add("m", _StubModel())
        gw = ServingGateway(pool)
        try:
            code, resp = gw._predict_route(
                {"model": "m", "features": [[1.0, 2.0, 3.0]]})
            assert code == 200
            phases = [p["phase"] for p in resp["trace"]["phases"]]
            assert phases == list(fr.ONESHOT_PHASES)
            # wall_ms covers the phase sum (phases end at unpack; wall
            # adds only the caller wake-up)
            s = sum(p["ms"] for p in resp["trace"]["phases"])
            assert s <= resp["trace"]["wall_ms"] + 1e-6
            # fast + ok request: not an exemplar
            code, dbg = gw._debug_requests_route({"model": "m"})
            assert code == 200 and dbg["count"] == 0
            code, ctype, body = gw._trace_route()
            assert code == 200 and b"serve/device" in body
        finally:
            pool.shutdown()

    def test_slo_breach_counter_counts_at_response_time(self):
        # always-on satellite: no recorder involved
        assert not fr.is_enabled()
        sch = DeviceScheduler(tier_slo_ms={"standard": 1.0})
        pool = ModelPool(sch)
        pool.add("slowm", _StubModel(delay_s=0.02))
        gw = ServingGateway(pool)
        c = registry().counter("serving_slo_breach_total")
        before = c.value(model="slowm", tier="standard")
        try:
            gw.predict("slowm", np.ones((1, 4), np.float32))
        finally:
            pool.shutdown()
        assert c.value(model="slowm", tier="standard") == before + 1

    def test_get_query_string_parsed_into_params(self):
        seen = {}

        def route(params):
            seen["params"] = params
            return 200, {"ok": True}

        srv = JsonHttpServer({"/q": route}, {})
        with srv:
            json_request(srv.url + "/q?model=a&tier=b")
            assert seen["params"] == {"model": "a", "tier": "b"}
            json_request(srv.url + "/q")
            assert seen["params"] is None
