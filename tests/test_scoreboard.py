"""Bench scoreboard plane (optimize/scoreboard.py, docs/observability.md).

Fast rows drive the watchdog on a fake clock and the ledger/baseline/
sentinel machinery on tmp files — no device work. The end-to-end rows
(a real bench.py run with a fault-wedged child; the check CLI) spawn
jax-importing subprocesses and are @pytest.mark.slow per the tier-1
budget note in ROADMAP.md.
"""
import json
import os
import subprocess
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from deeplearning4j_tpu.optimize import scoreboard as sb  # noqa: E402
from deeplearning4j_tpu.utils import faults  # noqa: E402

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _clean_faults():
    faults.reset()
    yield
    faults.reset()


@pytest.fixture()
def tmp_store(tmp_path, monkeypatch):
    """Point the ledger + baseline at tmp so tests never touch the real
    scoreboard history."""
    ledger = tmp_path / "ledger.jsonl"
    baseline = tmp_path / "baseline.json"
    monkeypatch.setenv("DL4JTPU_BENCH_LEDGER", str(ledger))
    monkeypatch.setenv("DL4JTPU_BENCH_BASELINE", str(baseline))
    return ledger, baseline


class FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


class TestChildWatchdog:
    def test_alive_within_deadline(self):
        clk = FakeClock()
        wd = sb.ChildWatchdog(10, 3, clock=clk)
        clk.t = 5
        assert wd.decide() == sb.ALIVE

    def test_no_beats_past_deadline_is_timeout_not_wedged(self):
        # a child that never beat (e.g. still importing jax) gives the
        # watchdog nothing to distinguish slow from dead: timeout, and
        # never a false "wedged"
        clk = FakeClock()
        wd = sb.ChildWatchdog(10, 3, clock=clk)
        clk.t = 11
        assert wd.decide() == sb.TIMEOUT

    def test_beats_then_silence_is_wedged(self):
        clk = FakeClock()
        wd = sb.ChildWatchdog(100, 3, clock=clk)
        clk.t = 1
        wd.observe({"phase": "warm"})
        clk.t = 5  # silent for 4 > stall 3, well before the deadline
        assert wd.decide() == sb.WEDGED

    def test_fresh_beats_past_deadline_extend(self):
        clk = FakeClock()
        wd = sb.ChildWatchdog(10, 3, hard_cap_s=20, clock=clk)
        clk.t = 9
        wd.observe({"phase": "measure"})
        clk.t = 11  # past deadline but beating: alive-but-slow
        assert wd.decide() == sb.ALIVE
        assert wd.extended is True

    def test_extension_bounded_by_hard_cap(self):
        clk = FakeClock()
        wd = sb.ChildWatchdog(10, 100, hard_cap_s=20, clock=clk)
        clk.t = 18
        wd.observe({})
        clk.t = 21  # beating (stall 100 not hit) but past the hard cap
        assert wd.decide() == sb.TIMEOUT

    def test_ages_use_parent_clock_not_beat_ts(self):
        # a beat with an absurd child-side timestamp must not trip
        # anything: ages come from the parent's clock only
        clk = FakeClock()
        wd = sb.ChildWatchdog(10, 3, clock=clk)
        clk.t = 1
        wd.observe({"ts": -1e12})
        clk.t = 2
        assert wd.decide() == sb.ALIVE


class TestHeartbeats:
    def test_writer_noop_when_channel_unarmed(self, tmp_path, monkeypatch):
        monkeypatch.delenv("DL4JTPU_BENCH_HB_FILE", raising=False)
        faults.inject("bench.child", "fail:1")
        sb.child_heartbeat(repeat=1)  # must not raise, must not fire
        assert faults.call_count("bench.child") == 0

    def test_writer_emits_position_and_fires_fault_point(
            self, tmp_path, monkeypatch):
        hb = tmp_path / "hb.jsonl"
        monkeypatch.setenv("DL4JTPU_BENCH_HB_FILE", str(hb))
        sb.child_heartbeat(repeat=2, step=7, phase="measure")
        beats, off = sb.read_heartbeats(str(hb), 0)
        assert len(beats) == 1
        assert beats[0]["repeat"] == 2 and beats[0]["step"] == 7
        assert beats[0]["phase"] == "measure" and "ts" in beats[0]
        faults.inject("bench.child", "fail:1")
        with pytest.raises(faults.FaultInjected):
            sb.child_heartbeat(repeat=3)

    def test_reader_is_incremental_and_torn_tail_tolerant(
            self, tmp_path, monkeypatch):
        hb = tmp_path / "hb.jsonl"
        monkeypatch.setenv("DL4JTPU_BENCH_HB_FILE", str(hb))
        sb.child_heartbeat(repeat=1)
        beats, off = sb.read_heartbeats(str(hb), 0)
        assert len(beats) == 1
        with open(hb, "a") as f:
            f.write('{"torn')  # no newline: a write in flight
        beats2, off2 = sb.read_heartbeats(str(hb), off)
        assert beats2 == [] and off2 == off  # tail re-read next poll
        with open(hb, "a") as f:
            f.write('": 1}\n')
        beats3, off3 = sb.read_heartbeats(str(hb), off2)
        assert len(beats3) == 1 and off3 > off2

    def test_run_child_collects_beats_and_stdout(self, tmp_path):
        code = ("import json, os\n"
                "p = os.environ['DL4JTPU_BENCH_HB_FILE']\n"
                "open(p, 'a').write(json.dumps({'phase': 'x'}) + '\\n')\n"
                "print(json.dumps({'metric': 'm', 'value': 1.0}))\n")
        res = sb.run_child([sys.executable, "-c", code], deadline_s=30,
                           stall_timeout_s=30, poll_s=0.05)
        assert res.status == "ok" and res.returncode == 0
        assert res.beats >= 1
        assert json.loads(res.stdout.strip())["value"] == 1.0

    def test_run_child_kills_wedged_child(self, tmp_path):
        # one beat, then sleep far past the stall timeout → wedged +
        # killed in ~stall seconds, not at the deadline
        code = ("import json, os, time\n"
                "p = os.environ['DL4JTPU_BENCH_HB_FILE']\n"
                "open(p, 'a').write(json.dumps({'phase': 'x'}) + '\\n')\n"
                "time.sleep(120)\n")
        res = sb.run_child([sys.executable, "-c", code], deadline_s=60,
                           stall_timeout_s=1.5, poll_s=0.05)
        assert res.status == sb.WEDGED
        assert res.beats >= 1
        assert res.duration_s < 30


class TestProbe:
    def test_delay_wedged_probe_reports_dead_device(self, monkeypatch):
        # the fault fires before the probe subprocess touches jax, so
        # this costs ~the 2s timeout, not a backend init
        monkeypatch.setenv("DL4JTPU_FAULT_BENCH_PROBE", "delay:1@600000")
        out = sb.probe_device(timeout_s=2)
        assert out["device"] == "dead"
        assert "error" in out

    @pytest.mark.slow
    def test_healthy_probe_reports_ok(self, monkeypatch):
        monkeypatch.delenv("DL4JTPU_FAULT_BENCH_PROBE", raising=False)
        out = sb.probe_device(timeout_s=120)
        assert out["device"] == "ok"
        assert out["probe_ms"] > 0


class TestLedger:
    def test_row_round_trip(self, tmp_store):
        ledger, _ = tmp_store
        row = sb.make_row("lenet", "ok", "m", 2.5, "u",
                          repeats=[2.4, 2.5, 2.6],
                          spread={"n": 3, "min": 2.4, "max": 2.6})
        assert sb.validate_row(row) == []
        sb.append_row(row)
        rows = sb.read_ledger()
        assert len(rows) == 1
        got = rows[0]
        assert got["metric"] == "m" and got["repeats"] == [2.4, 2.5, 2.6]
        assert got["schema"] == sb.SCHEMA_VERSION
        assert got["git_sha"] and got["host"]

    def test_validation_rejects_bad_rows(self):
        row = sb.make_row("lenet", "ok", "m", 1.0, "u")
        assert sb.validate_row({"nope": 1})
        bad_status = dict(row, status="exploded")
        assert any("status" in p for p in sb.validate_row(bad_status))
        unknown = dict(row, surprise=1)
        assert any("unknown" in p for p in sb.validate_row(unknown))
        missing = {k: v for k, v in row.items() if k != "backend"}
        assert any("backend" in p for p in sb.validate_row(missing))
        # ok rows must carry the measurement triple
        bare = sb.make_row("lenet", "ok")
        assert any("metric" in p for p in sb.validate_row(bare))
        # but typed failures legally have none
        wedged = sb.make_row("lenet", "wedged", failure="wedged",
                             timeout=True)
        assert sb.validate_row(wedged) == []
        # a row nobody measured names no backend — never a guess from
        # the JAX_PLATFORMS variable
        assert wedged["backend"] == "none"
        # the reduced-config stand-in row is gone from the schema
        assert "degraded" not in row
        assert any("status" in p for p in sb.validate_row(
            dict(row, status="degraded")))

    def test_append_rejects_invalid_and_tolerates_corrupt_lines(
            self, tmp_store):
        ledger, _ = tmp_store
        with pytest.raises(ValueError):
            sb.append_row({"schema": 1})
        sb.append_row(sb.make_row("lenet", "ok", "m", 1.0, "u"))
        with open(ledger, "a") as f:
            f.write("not json\n")
        sb.append_row(sb.make_row("lenet", "ok", "m", 2.0, "u"))
        rows = sb.read_ledger()
        assert [r["value"] for r in rows] == [1.0, 2.0]


class TestBaseline:
    def test_atomic_save_and_load(self, tmp_store):
        _, baseline = tmp_store
        sb.save_baseline({"m": 3.0})
        assert sb.load_baseline() == {"m": 3.0}
        assert not [p for p in os.listdir(baseline.parent)
                    if ".tmp." in p], "tmp file left behind"

    def test_corrupt_baseline_degrades_to_empty_with_counter(
            self, tmp_store):
        from deeplearning4j_tpu.optimize.metrics import registry
        _, baseline = tmp_store
        baseline.write_text('{"m": 3.0')  # truncated write
        before = registry().counter("bench_baseline_corrupt_total").total()
        assert sb.load_baseline() == {}
        after = registry().counter("bench_baseline_corrupt_total").total()
        assert after == before + 1

    def test_legacy_single_metric_migration(self, tmp_store):
        _, baseline = tmp_store
        baseline.write_text(json.dumps({"metric": "m", "value": 7.0}))
        assert sb.load_baseline() == {"m": 7.0}

    def test_backend_namespacing(self):
        assert sb.baseline_key("m", "tpu") == "m"  # unsuffixed = TPU
        assert sb.baseline_key("m", "cpu") == "m@cpu"
        # only a row that says "tpu" scores against TPU numbers
        assert sb.baseline_key("m", "none") == "m@none"
        assert sb.baseline_key("m", "unknown") == "m@unknown"


class TestCheckRows:
    def _row(self, value, **kw):
        return sb.make_row("lenet", kw.pop("status", "ok"), "m", value,
                           "u", backend="tpu", **kw)

    def test_regression_flagged_outside_band(self):
        fails, lines = sb.check_rows([self._row(90.0)], {"m": 100.0})
        assert fails == ["m"]
        assert any("REG" in ln for ln in lines)

    def test_within_band_passes(self):
        fails, _ = sb.check_rows([self._row(98.0)], {"m": 100.0})
        assert fails == []

    def test_recorded_spread_widens_band(self):
        # -10% would regress at the 3% default band, but the row's own
        # process spread covers it (the round-4 drift lesson)
        row = self._row(90.0, spread={"n": 3, "min": 85.0, "max": 100.0})
        fails, _ = sb.check_rows([row], {"m": 100.0})
        assert fails == []

    def test_failure_rows_never_scored(self):
        wedged = self._row(1.0, status="wedged", timeout=True)
        fails, lines = sb.check_rows([wedged], {"m": 100.0})
        assert fails == [] and lines == []

    def test_latest_row_wins_and_metric_filter(self):
        rows = [self._row(50.0), self._row(99.0)]
        fails, _ = sb.check_rows(rows, {"m": 100.0})
        assert fails == []  # append order: the newer 99.0 is scored
        fails2, _ = sb.check_rows([self._row(50.0)], {"m": 100.0},
                                  metrics=["other"])
        assert fails2 == []  # filtered out

    def test_report_renders_trajectory(self):
        rows = [self._row(50.0),
                self._row(1.0, status="wedged", timeout=True)]
        text = sb.render_report(rows, {"m": 100.0})
        assert "m" in text and "best 100" in text
        assert "wedged,timeout" in text and "x0.500" in text


class TestMetricsFamilies:
    def test_register_metrics_pre_registers_every_status_at_zero(self):
        from deeplearning4j_tpu.optimize.metrics import registry
        sb.register_metrics()
        snap = registry().snapshot()
        for status in sb.STATUSES:
            assert f'bench_rows_total{{status="{status}"}}' in snap
        assert "bench_regressions_total" in snap
        assert "bench_baseline_corrupt_total" in snap


@pytest.mark.slow
class TestEndToEnd:
    """Real bench.py subprocesses — minutes each on this rig."""

    def _env(self, tmp_path):
        env = dict(os.environ)
        env.update(JAX_PLATFORMS="cpu",
                   DL4JTPU_BENCH_PROBE="0",
                   DL4JTPU_BENCH_LEDGER=str(tmp_path / "ledger.jsonl"),
                   DL4JTPU_BENCH_BASELINE=str(tmp_path / "baseline.json"),
                   JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
        return env

    def test_wedged_child_fails_with_typed_ledger_row(self, tmp_path):
        """A fault-wedged first child means nothing was measured at the
        full config: a schema-valid typed ledger row, no artifact line,
        and a non-zero exit — no reduced-config stand-in."""
        env = self._env(tmp_path)
        # beat 1 (the start beat) passes, every later beat wedges 600s:
        # the watchdog sees life then silence — the round-5 hang, on
        # demand
        env.update(DL4JTPU_FAULT_BENCH_CHILD="delay:2/1@600000",
                   BENCH_STALL_S="5", BENCH_REPEATS="1")
        out = subprocess.run(
            [sys.executable, os.path.join(REPO, "bench.py"), "lenet_tiny"],
            capture_output=True, text=True, env=env, cwd=REPO,
            timeout=560)
        assert out.returncode != 0
        assert "nothing measured" in out.stderr and "wedged" in out.stderr
        assert out.stdout.strip() == ""
        ledger_rows = [json.loads(ln) for ln in
                       open(tmp_path / "ledger.jsonl")]
        assert ledger_rows[-1]["status"] == "wedged"
        assert "value" not in ledger_rows[-1]
        assert sb.validate_row(ledger_rows[-1]) == []

    def test_check_cli_exit_codes(self, tmp_path):
        env = self._env(tmp_path)
        ledger = tmp_path / "ledger.jsonl"
        with open(ledger, "w") as f:
            f.write(json.dumps(sb.make_row(
                "lenet", "ok", "m", 90.0, "u", backend="cpu")) + "\n")
        (tmp_path / "baseline.json").write_text(
            json.dumps({"m@cpu": 100.0}))
        out = subprocess.run(
            [sys.executable, os.path.join(REPO, "bench.py"), "check"],
            capture_output=True, text=True, env=env, cwd=REPO,
            timeout=180)
        assert out.returncode == 1, out.stdout  # synthetic regression
        assert "regression" in out.stdout
        (tmp_path / "baseline.json").write_text(
            json.dumps({"m@cpu": 90.0}))
        out2 = subprocess.run(
            [sys.executable, os.path.join(REPO, "bench.py"), "check"],
            capture_output=True, text=True, env=env, cwd=REPO,
            timeout=180)
        assert out2.returncode == 0, out2.stdout
        assert "bench check: ok" in out2.stdout
