"""Serving control-loop suite (docs/observability.md §"The serving
control loop").

Covers the PR-17 tentpole legs with NO devices and NO sleeps on the
fast paths: the autotune ledger's strict schema (unknown
field/kind, wrong type, out-of-vocab outcome all reject; torn tail
lines never do), windowed histogram quantiles with explicit ``t=``
stamps, fake-clock SLOMonitor verdicts (aging, born-floor, shed-rate
deltas, breaker reporting), the AutoTuner hill-climb state machine
against a synthetic latency model (converge / guardrail-refuse /
bitwise-revert / freeze / thaw), the POST /config scheduler-knob +
GET /debug/tuner HTTP contract, and the chaos leg: a ``fail:2/5``
storm on ``serve.forward`` opens a breaker and must FREEZE the tuner
at its known-good config. The live-traffic convergence loop is `slow`.
"""
import json
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from deeplearning4j_tpu.optimize.metrics import registry
from deeplearning4j_tpu.serving import ServingGateway
from deeplearning4j_tpu.serving.autotuner import (LEDGER_SCHEMA_VERSION,
                                                  AutoTuner, Knob,
                                                  MonitorReport,
                                                  SLOMonitor, TierVerdict,
                                                  append_entry,
                                                  default_knobs,
                                                  read_ledger,
                                                  validate_entry)
from deeplearning4j_tpu.utils import faults

from test_serving_gateway import post_json, rand_x


@pytest.fixture(autouse=True)
def _clean_faults():
    faults.reset()
    yield
    faults.reset()


def get_json(url):
    try:
        with urllib.request.urlopen(url) as r:
            return r.status, json.loads(r.read())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read())


# Each fake-clock test gets its own epoch far from real time.monotonic()
# AND far from every other test's epoch, so the process-global registry
# rings can never leak observations across tests (the same born-floor
# discipline the monitor applies to earlier bench arms).
_EPOCH = [10_000_000.0]


def fresh_t0():
    _EPOCH[0] += 100_000.0
    return _EPOCH[0]


# ---------------------------------------------------------------------------
# Stubs: a pool the tuner can hold without any engine/device behind it
# ---------------------------------------------------------------------------
class _StubEngine:
    def __init__(self, linger=8.0):
        self.batch_timeout_ms = linger


class _StubBreaker:
    def __init__(self, state="closed"):
        self.state = state


class _StubEntry:
    def __init__(self, name, tier, breaker=None):
        self.name = name
        self.tier = tier
        self.engine = _StubEngine()
        self.breaker = breaker
        self.group = None
        self.weight = 1.0


class _StubSched:
    def __init__(self, slos):
        self.tier_slo_ms = dict(slos)
        self.quantum = 1.0
        self.shed_depth = 16


class _StubPool:
    def __init__(self, entries=(), scheduler=None):
        self._entries = list(entries)
        self.scheduler = scheduler

    def entries(self):
        return list(self._entries)


class _EchoStub:
    """Device-free forward for real-gateway tests (chaos-suite idiom)."""

    _initialized = True

    def output(self, x):
        return np.asarray(x) * 2.0


# ---------------------------------------------------------------------------
# Ledger: strict schema in, torn lines tolerated out
# ---------------------------------------------------------------------------
def _move_row(**over):
    row = {"schema": LEDGER_SCHEMA_VERSION, "ts": 1.0, "seq": 1,
           "kind": "move", "knob": "linger_ms:app", "old": 8.0,
           "new": 6.0, "direction": -1, "evidence": {}}
    row.update(over)
    return row


def _outcome_row(**over):
    row = {"schema": LEDGER_SCHEMA_VERSION, "ts": 2.0, "seq": 2,
           "kind": "outcome", "ref": 1, "knob": "linger_ms:app",
           "outcome": "kept", "old": 8.0, "new": 6.0,
           "before_score": 2.0, "after_score": 1.5, "reverted": False,
           "evidence": {}}
    row.update(over)
    return row


class TestLedger:
    def test_roundtrip_in_order(self, tmp_path):
        path = str(tmp_path / "ledger.jsonl")
        rows = [
            _move_row(),
            _outcome_row(),
            {"schema": LEDGER_SCHEMA_VERSION, "ts": 3.0, "seq": 3,
             "kind": "refusal", "knob": "quantum", "candidate": 0.1,
             "lo": 0.25, "hi": 8.0, "reason": "guardrail"},
            {"schema": LEDGER_SCHEMA_VERSION, "ts": 4.0, "seq": 4,
             "kind": "freeze", "reason": "breaker_open", "evidence": {},
             "restored": {"quantum": 1.0}},
            {"schema": LEDGER_SCHEMA_VERSION, "ts": 5.0, "seq": 5,
             "kind": "unfreeze", "healthy_s": 60.0},
        ]
        for r in rows:
            assert validate_entry(r) == []
            append_entry(r, path)
        back = read_ledger(path)
        assert back == rows

    def test_unknown_field_rejected(self, tmp_path):
        with pytest.raises(ValueError, match="unknown field 'zap'"):
            append_entry(_move_row(zap=1), str(tmp_path / "l.jsonl"))

    def test_unknown_kind_rejected(self):
        assert any("unknown kind" in p
                   for p in validate_entry(_move_row(kind="vibes")))

    def test_missing_field_rejected(self):
        row = _move_row()
        del row["direction"]
        assert any("missing field 'direction'" in p
                   for p in validate_entry(row))

    def test_wrong_type_rejected(self):
        assert any("has type" in p
                   for p in validate_entry(_move_row(old="8.0")))

    def test_out_of_vocab_outcome_and_reason_rejected(self):
        assert any("outcome" in p for p in validate_entry(
            _outcome_row(outcome="sideways")))
        assert any("freeze reason" in p for p in validate_entry(
            {"schema": LEDGER_SCHEMA_VERSION, "ts": 1.0, "seq": 1,
             "kind": "freeze", "reason": "vibes", "evidence": {},
             "restored": {}}))

    def test_wrong_schema_version_rejected(self):
        assert any("schema" in p
                   for p in validate_entry(_move_row(schema=99)))

    def test_torn_tail_line_tolerated(self, tmp_path):
        path = str(tmp_path / "torn.jsonl")
        append_entry(_move_row(), path)
        with open(path, "a") as f:
            f.write('{"schema": 1, "ts": 2.0, "seq"')  # crash mid-append
        assert read_ledger(path) == [_move_row()]

    def test_missing_file_reads_empty(self, tmp_path):
        assert read_ledger(str(tmp_path / "nope.jsonl")) == []


# ---------------------------------------------------------------------------
# Windowed histogram quantiles (optimize/metrics.py satellite)
# ---------------------------------------------------------------------------
class TestWindowedQuantiles:
    def test_quantile_sees_only_the_window(self):
        t0 = fresh_t0()
        h = registry().histogram("autotune_test_win_ms").labels(model="wq")
        for v in range(1, 10):
            h.observe(float(v), t=t0)
        for v in (100.0, 101.0, 102.0):
            h.observe(v, t=t0 + 1000.0)
        now = t0 + 1005.0
        assert h.quantile(0.99, window_s=10.0, now=now) == 102.0
        assert h.quantile(0.0, window_s=10.0, now=now) == 100.0
        # no window: every ringed observation counts
        assert h.quantile(0.0, now=now) == 1.0
        assert h.window_values(10.0, now=now) == [100.0, 101.0, 102.0]

    def test_empty_window_quantile_is_zero(self):
        t0 = fresh_t0()
        h = registry().histogram("autotune_test_win_ms").labels(
            model="wq_empty")
        h.observe(5.0, t=t0)
        assert h.quantile(0.99, window_s=1.0, now=t0 + 100.0) == 0.0

    def test_ring_is_bounded(self):
        t0 = fresh_t0()
        h = registry().histogram("autotune_test_win_ms").labels(
            model="wq_ring")
        n = type(h).RING
        for i in range(n + 50):
            h.observe(float(i), t=t0)
        vals = h.window_values(now=t0 + 1.0)
        assert len(vals) == n
        assert vals[0] == 50.0  # oldest 50 evicted


# ---------------------------------------------------------------------------
# SLOMonitor: fake-clock windowed verdicts
# ---------------------------------------------------------------------------
class TestSLOMonitor:
    def test_windowed_breach_verdict(self):
        t0 = fresh_t0()
        now = [t0]
        pool = _StubPool([_StubEntry("smv", "gold")],
                         _StubSched({"gold": 5.0}))
        mon = SLOMonitor(pool, window_s=30.0, min_samples=5,
                         clock=lambda: now[0])
        h = registry().histogram("serving_latency_ms").labels(tier="gold")
        now[0] = t0 + 10.0
        for v in (2.0,) * 9 + (8.0,):
            h.observe(v, t=now[0])
        now[0] = t0 + 11.0
        rep = mon.tick()
        v = rep.verdicts["gold"]
        assert (v.requests, v.p99_ms, v.slo_ms) == (10, 8.0, 5.0)
        assert v.breach and v.ratio == pytest.approx(1.6)
        assert rep.score == pytest.approx(1.6)
        assert not rep.healthy
        assert registry().gauge("serving_slo_verdict").value(
            tier="gold") == 1.0

    def test_observations_age_out_of_the_window(self):
        t0 = fresh_t0()
        now = [t0]
        pool = _StubPool([_StubEntry("sma", "gold")],
                         _StubSched({"gold": 5.0}))
        mon = SLOMonitor(pool, window_s=30.0, min_samples=5,
                         clock=lambda: now[0])
        h = registry().histogram("serving_latency_ms").labels(tier="gold")
        now[0] = t0 + 5.0
        for _ in range(6):
            h.observe(9.0, t=now[0])
        now[0] = t0 + 6.0
        assert mon.tick().verdicts["gold"].requests == 6
        now[0] = t0 + 100.0  # the whole window has rolled past
        rep = mon.tick()
        assert rep.verdicts["gold"].requests == 0
        assert rep.verdicts["gold"] not in rep.sampled()

    def test_born_floor_excludes_preexisting_observations(self):
        t0 = fresh_t0()
        now = [t0]
        h = registry().histogram("serving_latency_ms").labels(tier="gold")
        h.observe(9.0, t=t0 - 5.0)  # stamped BEFORE the monitor existed
        pool = _StubPool([_StubEntry("smb", "gold")],
                         _StubSched({"gold": 5.0}))
        mon = SLOMonitor(pool, window_s=30.0, min_samples=1,
                         clock=lambda: now[0])
        now[0] = t0 + 2.0  # well inside 30s of the stale observation
        assert mon.tick().verdicts["gold"].requests == 0

    def test_shed_rate_is_a_delta_between_ticks(self):
        t0 = fresh_t0()
        now = [t0]
        pool = _StubPool([_StubEntry("smshed", "bronze")],
                         _StubSched({"bronze": 50.0}))
        mon = SLOMonitor(pool, window_s=30.0, min_samples=1,
                         clock=lambda: now[0])
        req_c = registry().counter("serving_requests_total")
        shed_c = registry().counter("serving_shed_total")
        req_c.labels(model="smshed", status="ok").inc(10)
        now[0] = t0 + 1.0
        assert mon.tick().verdicts["bronze"].shed_rate == 0.0  # no baseline
        req_c.labels(model="smshed", status="ok").inc(10)
        shed_c.labels(model="smshed").inc(5)
        # windowed latency traffic makes the tier SAMPLED — only sampled
        # tiers can drag down report.healthy
        registry().histogram("serving_latency_ms").labels(
            tier="bronze").observe(1.0, t=t0 + 1.5)
        now[0] = t0 + 2.0
        rep = mon.tick()
        assert rep.verdicts["bronze"].shed_rate == pytest.approx(0.5)
        assert not rep.healthy  # shedding half the tier is not health

    def test_open_breaker_reported(self):
        t0 = fresh_t0()
        now = [t0]
        pool = _StubPool(
            [_StubEntry("smbrk", "gold", breaker=_StubBreaker("open"))],
            _StubSched({"gold": 5.0}))
        mon = SLOMonitor(pool, clock=lambda: now[0])
        rep = mon.tick()
        assert rep.breakers_open == ["smbrk"]
        assert not rep.healthy


# ---------------------------------------------------------------------------
# AutoTuner: the hill-climb state machine on a synthetic latency model
# ---------------------------------------------------------------------------
class _ScriptedMonitor:
    """p99 = latency_fn() against a fixed SLO; ts advances 1s per tick.
    Mutate .breakers/.canary/.shed mid-test to script incidents."""

    def __init__(self, latency_fn, slo=5.0, tier="gold"):
        self.latency_fn = latency_fn
        self.slo = float(slo)
        self.tier = tier
        self.breakers = []
        self.canary = 0
        self.shed = 0.0
        self.t = 0.0

    def tick(self):
        self.t += 1.0
        v = TierVerdict(self.tier, float(self.latency_fn()), self.slo,
                        requests=100, shed_rate=self.shed)
        return MonitorReport(self.t, {self.tier: v},
                             breakers_open=list(self.breakers),
                             canary_rejections=self.canary,
                             min_samples=1)


def _mk_tuner(tmp_path, store, latency_fn, *, name="v", slo=5.0, **kw):
    knob = Knob(name, get=lambda: store["v"],
                set=lambda x: store.__setitem__("v", x),
                lo=0.0, hi=16.0, step=2.0, mode="add", direction=-1)
    mon = _ScriptedMonitor(latency_fn, slo=slo)
    clock = [0.0]
    tuner = AutoTuner(_StubPool(), monitor=mon, knobs=[knob],
                      ledger_path=str(tmp_path / "ledger.jsonl"),
                      settle_ticks=1, clock=lambda: clock[0], **kw)
    return tuner, knob, mon


class TestHillClimb:
    def test_converges_then_rests_when_healthy(self, tmp_path):
        store = {"v": 10.0}
        tuner, knob, _ = _mk_tuner(tmp_path, store,
                                   lambda: 2.0 + store["v"],
                                   name="hc_conv")
        for _ in range(20):
            tuner.tick()
        # stops at v=2 (p99 4ms < 5ms SLO) — health, not the optimum
        assert store["v"] == 2.0
        rows = read_ledger(str(tmp_path / "ledger.jsonl"))
        assert [r["kind"] for r in rows] == ["move", "outcome"] * 4
        assert all(r["outcome"] == "kept" for r in rows
                   if r["kind"] == "outcome")
        assert all(knob.lo <= r["new"] <= knob.hi for r in rows
                   if r["kind"] == "move")
        d = tuner.describe()
        assert d["state"] == "watching"
        assert d["known_good"] == {"hc_conv": 2.0}
        assert all(validate_entry(r) == [] for r in rows)

    def test_guardrail_refusal_flips_direction(self, tmp_path):
        store = {"v": 0.0}  # already pinned at the lo rail
        tuner, knob, _ = _mk_tuner(tmp_path, store, lambda: 8.0,
                                   name="hc_rail")
        tuner.tick()
        assert store["v"] == 0.0  # never moved out of range
        assert knob.direction == 1  # flipped: try the other way next
        last = read_ledger(str(tmp_path / "ledger.jsonl"))[-1]
        assert (last["kind"], last["reason"]) == ("refusal", "guardrail")
        assert registry().counter("serving_tuner_moves_total").total(
            knob="hc_rail", outcome="refused") == 1

    def test_regression_reverts_bitwise_and_flips(self, tmp_path):
        store = {"v": 10.0}
        # inverted model: lowering the knob makes latency WORSE
        tuner, knob, _ = _mk_tuner(tmp_path, store,
                                   lambda: 25.0 - store["v"],
                                   name="hc_rev", slo=8.0)
        r0 = registry().counter("serving_tuner_reverts_total").total()
        tuner.tick()  # move 10 -> 8
        assert store["v"] == 8.0
        assert tuner.describe()["state"] == "settling"
        tuner.tick()  # settle verdict: score regressed -> revert
        assert store["v"] == 10.0  # the exact prior value, bitwise
        assert knob.direction == 1
        last = read_ledger(str(tmp_path / "ledger.jsonl"))[-1]
        assert (last["kind"], last["outcome"]) == ("outcome", "reverted")
        assert last["reverted"] is True
        assert registry().counter(
            "serving_tuner_reverts_total").total() == r0 + 1

    def test_neutral_keeps_the_move(self, tmp_path):
        store = {"v": 10.0}
        tuner, _, _ = _mk_tuner(tmp_path, store, lambda: 8.0,
                                name="hc_neu")
        tuner.tick()
        tuner.tick()  # constant score: inside the tolerance dead-band
        assert store["v"] == 8.0  # kept, not reverted
        last = read_ledger(str(tmp_path / "ledger.jsonl"))[-1]
        assert last["outcome"] == "neutral"

    def test_freeze_on_breaker_restores_known_good(self, tmp_path):
        store = {"v": 2.0}
        tuner, _, mon = _mk_tuner(tmp_path, store, lambda: 4.0,
                                  name="hc_frz")
        f0 = registry().counter("serving_tuner_freezes_total").total(
            reason="breaker_open")
        tuner.tick()  # healthy: v=2 becomes the known-good config
        store["v"] = 9.0  # config drifts out from under the tuner
        mon.breakers = ["m"]
        tuner.tick()
        assert store["v"] == 2.0  # known-good restored, bitwise
        d = tuner.describe()
        assert (d["state"], d["frozen_reason"]) == ("frozen",
                                                    "breaker_open")
        assert registry().gauge("serving_tuner_frozen").value() == 1.0
        assert registry().counter("serving_tuner_freezes_total").total(
            reason="breaker_open") == f0 + 1
        rows = read_ledger(str(tmp_path / "ledger.jsonl"))
        assert rows[-1]["kind"] == "freeze"
        assert rows[-1]["reason"] == "breaker_open"
        assert rows[-1]["restored"] == {"hc_frz": 2.0}
        # frozen means frozen: the incident continuing adds no rows
        tuner.tick()
        assert len(read_ledger(str(tmp_path / "ledger.jsonl"))) \
            == len(rows)

    def test_hard_slo_breach_freezes_mild_tunes(self, tmp_path):
        # mild breach (2x the SLO, factor 3): the tuning signal
        store = {"v": 10.0}
        tuner, _, _ = _mk_tuner(tmp_path, store, lambda: 10.0,
                                name="hc_mild")
        tuner.tick()
        assert tuner.describe()["state"] == "settling"
        # hard breach (3.5x): an incident — stop touching production
        store2 = {"v": 10.0}
        tuner2, _, _ = _mk_tuner(tmp_path, store2, lambda: 17.5,
                                 name="hc_hard")
        tuner2.tick()
        d = tuner2.describe()
        assert (d["state"], d["frozen_reason"]) == ("frozen", "slo_breach")
        assert store2["v"] == 10.0

    def test_canary_rejection_freezes(self, tmp_path):
        store = {"v": 10.0}
        tuner, _, mon = _mk_tuner(tmp_path, store, lambda: 4.0,
                                  name="hc_can")
        mon.canary = 1
        tuner.tick()
        assert tuner.describe()["frozen_reason"] == "canary_rejected"

    def test_unfreeze_after_cooldown_then_tunes_again(self, tmp_path):
        store = {"v": 10.0}
        tuner, _, mon = _mk_tuner(tmp_path, store, lambda: 4.0,
                                  name="hc_thaw", freeze_cooldown_s=10.0)
        mon.breakers = ["m"]
        tuner.tick()
        assert tuner.describe()["state"] == "frozen"
        mon.breakers = []
        tuner.tick()  # first healthy tick starts the cooldown clock
        tuner.tick()  # 1s healthy: still frozen
        assert tuner.describe()["state"] == "frozen"
        mon.t += 11.0  # fake clock: ride past the cooldown
        tuner.tick()
        assert tuner.describe()["state"] == "watching"
        rows = read_ledger(str(tmp_path / "ledger.jsonl"))
        assert rows[-1]["kind"] == "unfreeze"
        # and the loop is live again: a breach now produces a move
        mon.latency_fn = lambda: 2.0 + store["v"]
        tuner.tick()
        assert tuner.describe()["state"] == "settling"

    def test_manual_unfreeze(self, tmp_path):
        store = {"v": 10.0}
        tuner, _, mon = _mk_tuner(tmp_path, store, lambda: 4.0,
                                  name="hc_manual")
        mon.breakers = ["m"]
        tuner.tick()
        assert tuner.describe()["state"] == "frozen"
        tuner.unfreeze()
        assert tuner.describe()["state"] == "watching"

    def test_default_knobs_skip_fused_members(self):
        e1 = _StubEntry("solo", "standard")
        e2 = _StubEntry("member", "standard")
        e2.group = object()
        pool = _StubPool([e1, e2], _StubSched({"standard": 50.0}))
        names = [k.name for k in default_knobs(pool)]
        assert "linger_ms:solo" in names and "weight:solo" in names
        assert "quantum" in names and "shed_depth" in names
        assert not any(n.endswith(":member") for n in names)


# ---------------------------------------------------------------------------
# HTTP contract: POST /config scheduler knobs + GET /debug/tuner
# ---------------------------------------------------------------------------
class TestConfigAndDebugHTTP:
    @pytest.fixture()
    def gw(self):
        g = ServingGateway()
        g.add_model("cfg_app", _EchoStub(), batch_timeout_ms=0.5,
                    tier="standard")
        g.add_model("cfg_bulk", _EchoStub(), batch_timeout_ms=0.5,
                    tier="batch")
        with g:
            yield g

    def test_scheduler_knobs_roundtrip(self, gw):
        code, body = post_json(gw.url + "/config",
                               {"quantum": 2.0, "shed_depth": 8,
                                "tier_slo_ms": {"standard": 25.0}})
        assert code == 200 and body["status"] == "ok"
        sch = body["scheduler"]
        assert (sch["quantum"], sch["shed_depth"]) == (2.0, 8)
        assert sch["tier_slo_ms"]["standard"] == 25.0
        assert gw.pool.scheduler.quantum == 2.0
        assert registry().gauge("serving_tier_slo_ms").value(
            tier="standard") == 25.0

    def test_entry_weight_and_linger_live(self, gw):
        code, body = post_json(gw.url + "/config",
                               {"model": "cfg_app", "weight": 3.0,
                                "batch_timeout_ms": 2.5})
        assert code == 200
        assert set(body["reconfigured"]) == {"weight", "batch_timeout_ms"}
        entry = gw.pool.get("cfg_app")
        assert entry.weight == 3.0
        assert entry.engine.batch_timeout_ms == 2.5

    def test_unknown_knob_400(self, gw):
        code, body = post_json(gw.url + "/config",
                               {"model": "cfg_app", "zap": 1})
        assert (code, body["reason"]) == (400, "unknown_knob")

    @pytest.mark.parametrize("req", [
        {"quantum": "fast"},            # uncoercible type
        {"quantum": -1.0},              # scheduler validates > 0
        {"shed_depth": 0},              # scheduler validates >= 1
        {"tier_slo_ms": [1, 2]},        # must be a {tier: ms} object
        {"tier_slo_ms": {"ghost": 5.0}},  # unknown tier
    ])
    def test_invalid_values_400_typed(self, gw, req):
        code, body = post_json(gw.url + "/config", req)
        assert (code, body["reason"]) == (400, "invalid_value")

    def test_invalid_value_mutates_nothing(self, gw):
        before = gw.pool.scheduler.config()
        code, _ = post_json(gw.url + "/config",
                            {"quantum": 3.0,
                             "tier_slo_ms": {"ghost": 5.0}})
        assert code == 400
        assert gw.pool.scheduler.config() == before  # validate-then-mutate

    def test_no_knobs_400(self, gw):
        code, body = post_json(gw.url + "/config", {"model": "cfg_app"})
        assert code == 400 and body["status"] == "error"

    def test_debug_tuner_404_until_attached_then_trail(self, gw,
                                                       tmp_path):
        code, body = get_json(gw.url + "/debug/tuner")
        assert code == 404 and body["enabled"] is False
        tuner = gw.attach_tuner(
            start=False, ledger_path=str(tmp_path / "l.jsonl"),
            monitor=SLOMonitor(gw.pool, window_s=5.0, min_samples=1))
        tuner.tick()
        code, body = get_json(gw.url + "/debug/tuner")
        assert code == 200 and body["enabled"] is True
        assert body["state"] in ("watching", "settling", "frozen")
        knobs = {k["name"]: k for k in body["knobs"]}
        assert "linger_ms:cfg_app" in knobs and "quantum" in knobs
        assert knobs["linger_ms:cfg_app"]["lo"] == 0.0
        assert knobs["linger_ms:cfg_app"]["hi"] == 20.0
        assert isinstance(body["trail"], list)
        assert body["known_good"]["linger_ms:cfg_app"] == 0.5


# ---------------------------------------------------------------------------
# Chaos: an injected forward-fault storm must freeze the control loop
# ---------------------------------------------------------------------------
class TestChaosFreeze:
    def test_serve_forward_storm_opens_breaker_and_freezes(self,
                                                           tmp_path):
        gw = ServingGateway()
        gw.add_model("chaos_m", _EchoStub(), batch_timeout_ms=0.5,
                     tier="standard", breaker_threshold=1,
                     breaker_reset_s=30.0)
        tuner = gw.attach_tuner(
            start=False, ledger_path=str(tmp_path / "l.jsonl"),
            monitor=SLOMonitor(gw.pool, window_s=5.0, min_samples=1))
        faults.inject("serve.forward", "fail:2/5")
        try:
            seen = []
            for _ in range(4):
                try:
                    gw.predict("chaos_m", rand_x(1))
                    seen.append("ok")
                except Exception as e:
                    seen.append(type(e).__name__)
            # call 2 was injection-failed; threshold 1 opened the breaker
            assert gw.pool.get("chaos_m").breaker.state != "closed"
            rep = tuner.tick()
            assert rep.breakers_open == ["chaos_m"]
            d = tuner.describe()
            assert (d["state"], d["frozen_reason"]) == ("frozen",
                                                        "breaker_open")
            assert registry().gauge("serving_tuner_frozen").value() == 1.0
            rows = read_ledger(str(tmp_path / "l.jsonl"))
            assert rows[-1]["kind"] == "freeze"
            assert rows[-1]["reason"] == "breaker_open"
            assert rows[-1]["evidence"]["breakers_open"] == ["chaos_m"]
            # frozen means frozen: no knob ever moved under the storm
            tuner.tick()
            assert all(r["kind"] != "move"
                       for r in read_ledger(str(tmp_path / "l.jsonl")))
        finally:
            gw.pool.shutdown()


# ---------------------------------------------------------------------------
# Live loop (slow): a running tuner thread walks a fat linger down
# ---------------------------------------------------------------------------
@pytest.mark.slow
class TestLiveLoop:
    def test_tuner_thread_tightens_linger_under_live_traffic(self,
                                                             tmp_path):
        gw = ServingGateway()
        gw.add_model("live_app", _EchoStub(), batch_limit=4,
                     batch_timeout_ms=6.0, tier="standard")
        gw.pool.reconfigure_scheduler(tier_slo_ms={"standard": 3.0})
        tuner = gw.attach_tuner(
            ledger_path=str(tmp_path / "l.jsonl"), interval_s=0.05,
            settle_ticks=1, breach_freeze_factor=10.0,
            monitor=SLOMonitor(gw.pool, window_s=1.0, min_samples=3))
        try:
            end = time.perf_counter() + 3.0
            while time.perf_counter() < end:
                gw.predict("live_app", rand_x(1))
            linger = gw.pool.get("live_app").engine.batch_timeout_ms
            assert linger < 6.0, "tuner never tightened the linger"
            rows = read_ledger(str(tmp_path / "l.jsonl"))
            moves = [r for r in rows if r["kind"] == "move"]
            assert moves, "no ledgered decision"
            assert all(validate_entry(r) == [] for r in rows)
        finally:
            tuner.stop()
            gw.pool.shutdown()
