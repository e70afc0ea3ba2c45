"""Fault-tolerance control plane tests (docs/robustness.md): atomic
checkpoints + manifest retention, corrupt detection and skip-to-older
restore, bitwise auto-resume, divergence sentinel policies, retry/backoff
timing on a fake clock, parameter-server chaos (injected transport faults,
worker respawn), and prefetch-thread retry — all driven by the
deterministic utils/faults.py injection registry."""
import os
import signal
import subprocess
import sys
import zipfile

import numpy as np
import pytest

from deeplearning4j_tpu import (Adam, DenseLayer, InputType,
                                MultiLayerNetwork, NeuralNetConfiguration,
                                OutputLayer)
from deeplearning4j_tpu.data.dataset import DataSet
from deeplearning4j_tpu.data.iterators import (AsyncDataSetIterator,
                                               ListDataSetIterator)
from deeplearning4j_tpu.earlystopping import LocalFileModelSaver
from deeplearning4j_tpu.optimize import metrics as metrics_mod
from deeplearning4j_tpu.optimize.resilience import (CheckpointManager,
                                                    DivergenceError,
                                                    DivergenceSentinel,
                                                    RetryPolicy, retry_call)
from deeplearning4j_tpu.parallel.param_server import (
    HttpParameterServerClient, ParameterServer, ParameterServerHttpNode,
    ParameterServerTrainer, remote_worker_fit)
from deeplearning4j_tpu.utils import faults
from deeplearning4j_tpu.utils.model_serializer import (
    CheckpointCorruptError, META_ENTRY, PARAMS_ENTRY, restore_model,
    save_model, validate_checkpoint)


@pytest.fixture(autouse=True)
def _clean_faults():
    faults.reset()
    yield
    faults.reset()


def _mknet(seed=7):
    conf = (NeuralNetConfiguration.builder().seed(seed)
            .updater(Adam(0.05)).list()
            .layer(DenseLayer(n_out=16, activation="relu"))
            .layer(OutputLayer(n_out=3, activation="softmax",
                               loss="mcxent"))
            .set_input_type(InputType.feed_forward(4)).build())
    return MultiLayerNetwork(conf).init()


def _data(n=64, seed=42):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(n, 4)).astype(np.float32)
    y = np.eye(3, dtype=np.float32)[rng.integers(0, 3, size=n)]
    return DataSet(x, y)


def _truncate(path, frac=0.5):
    with open(path, "r+b") as f:
        f.truncate(int(os.path.getsize(path) * frac))


# ---------------------------------------------------------------------------
# faults registry
# ---------------------------------------------------------------------------

class TestFaults:
    def test_plan_selectors(self):
        faults.inject("p", "fail:2,4-5")
        hits = []
        for i in range(1, 7):
            try:
                faults.fire("p")
                hits.append(False)
            except faults.FaultInjected:
                hits.append(True)
        assert hits == [False, True, False, True, True, False]
        assert faults.call_count("p") == 6
        assert faults.fired_count("p") == 3

    def test_always_and_check(self):
        faults.inject("q", "fail:*")
        assert faults.check("q") and faults.check("q")
        faults.clear("q")
        assert not faults.check("q")

    def test_bad_specs(self):
        with pytest.raises(ValueError):
            faults.inject("p", "explode:1")
        with pytest.raises(ValueError):
            faults.inject("p", "fail:0")
        with pytest.raises(ValueError):
            faults.inject("p", "fail:x")

    def test_env_arming(self, monkeypatch):
        monkeypatch.setenv("DL4JTPU_FAULT_SOME_POINT", "fail:1")
        with pytest.raises(faults.FaultInjected):
            faults.fire("some.point")
        faults.fire("some.point")  # only call 1 covered

    def test_unarmed_is_noop(self):
        faults.fire("never.armed")
        assert not faults.check("never.armed")


# ---------------------------------------------------------------------------
# atomic writes + corrupt detection
# ---------------------------------------------------------------------------

class TestAtomicCheckpoint:
    def test_no_temp_residue(self, tmp_path):
        net = _mknet()
        p = str(tmp_path / "m.zip")
        save_model(net, p)
        assert os.path.exists(p)
        assert [f for f in os.listdir(tmp_path)] == ["m.zip"]

    def test_failed_write_preserves_previous(self, tmp_path):
        net = _mknet()
        p = str(tmp_path / "m.zip")
        save_model(net, p)
        before = open(p, "rb").read()
        net.iteration = 99
        with faults.injected("checkpoint.write", "fail:1"):
            with pytest.raises(faults.FaultInjected):
                save_model(net, p)
        # the interrupted write left neither a torn final file nor junk
        assert open(p, "rb").read() == before
        assert os.listdir(tmp_path) == ["m.zip"]
        assert restore_model(p).iteration == 0

    def test_truncated_archive_raises_corrupt(self, tmp_path):
        net = _mknet()
        p = str(tmp_path / "m.zip")
        save_model(net, p)
        _truncate(p)
        with pytest.raises(CheckpointCorruptError):
            restore_model(p)

    def test_missing_entry_named(self, tmp_path):
        net = _mknet()
        src = str(tmp_path / "m.zip")
        dst = str(tmp_path / "noparams.zip")
        save_model(net, src)
        with zipfile.ZipFile(src) as zin, \
                zipfile.ZipFile(dst, "w") as zout:
            for n in zin.namelist():
                if n != PARAMS_ENTRY:
                    zout.writestr(n, zin.read(n))
        with pytest.raises(CheckpointCorruptError, match=PARAMS_ENTRY):
            restore_model(dst)

    def test_bad_format_version(self, tmp_path):
        net = _mknet()
        src = str(tmp_path / "m.zip")
        dst = str(tmp_path / "future.zip")
        save_model(net, src)
        import json
        with zipfile.ZipFile(src) as zin, \
                zipfile.ZipFile(dst, "w") as zout:
            for n in zin.namelist():
                if n == META_ENTRY:
                    meta = json.loads(zin.read(n))
                    meta["format_version"] = 999
                    zout.writestr(n, json.dumps(meta))
                else:
                    zout.writestr(n, zin.read(n))
        with pytest.raises(CheckpointCorruptError, match="format_version"):
            validate_checkpoint(dst)

    def test_not_a_zip(self, tmp_path):
        p = str(tmp_path / "junk.zip")
        open(p, "wb").write(b"this is not a zip archive")
        with pytest.raises(CheckpointCorruptError):
            restore_model(p)

    def test_saver_falls_back_to_latest(self, tmp_path, caplog):
        net = _mknet()
        saver = LocalFileModelSaver(str(tmp_path))
        saver.save_best_model(net, 0.5)
        net.iteration = 7
        saver.save_latest_model(net, 0.6)
        _truncate(saver.best_path)
        import logging
        with caplog.at_level(logging.WARNING):
            back = saver.get_best_model()
        assert back is not None and back.iteration == 7
        assert any("falling back" in r.message for r in caplog.records)


# ---------------------------------------------------------------------------
# CheckpointManager: manifest, retention, corrupt skip
# ---------------------------------------------------------------------------

class TestCheckpointManager:
    def test_keep_last_prunes(self, tmp_path):
        net = _mknet()
        mgr = CheckpointManager(str(tmp_path), keep_last=2)
        for it in (1, 2, 3, 4):
            net.iteration = it
            mgr.save(net)
        files = sorted(f for f in os.listdir(tmp_path) if f.endswith(".zip"))
        assert files == ["checkpoint-00000003.zip", "checkpoint-00000004.zip"]
        assert [r["iteration"] for r in mgr.checkpoints()] == [3, 4]

    def test_keep_every_n_epochs_pins(self, tmp_path):
        net = _mknet()
        mgr = CheckpointManager(str(tmp_path), keep_last=1,
                                keep_every_n_epochs=2)
        for it, ep in ((10, 1), (20, 2), (30, 3), (40, 4)):
            net.iteration, net.epoch = it, ep
            mgr.save(net)
        its = sorted(r["iteration"] for r in mgr.checkpoints())
        # epoch-2 and epoch-4 boundaries pinned, plus the newest
        assert its == [20, 40]

    def test_latest_valid_skips_torn(self, tmp_path):
        net = _mknet()
        mgr = CheckpointManager(str(tmp_path), keep_last=5)
        for it in (1, 2, 3):
            net.iteration = it
            mgr.save(net)
        _truncate(str(tmp_path / "checkpoint-00000003.zip"))
        rec = mgr.latest_valid()
        assert rec["iteration"] == 2

    def test_manifest_fallback_directory_scan(self, tmp_path):
        net = _mknet()
        mgr = CheckpointManager(str(tmp_path), keep_last=5)
        for it in (1, 2):
            net.iteration = it
            mgr.save(net)
        os.unlink(mgr.manifest_path)
        rec = CheckpointManager(str(tmp_path)).latest_valid()
        assert rec["file"] == "checkpoint-00000002.zip"

    def test_restore_into_roundtrip(self, front, tmp_path):
        net = front.net()
        net.fit(_data(32), epochs=1, batch_size=16)
        mgr = CheckpointManager(str(tmp_path))
        mgr.save(net)
        other = front.net(seed=99)
        rec = mgr.restore_into(other)
        assert rec["iteration"] == net.iteration
        assert other.iteration == net.iteration
        np.testing.assert_array_equal(other.params(), net.params())

    def test_empty_dir_restores_nothing(self, tmp_path):
        mgr = CheckpointManager(str(tmp_path))
        assert mgr.latest_valid() is None
        assert mgr.restore_into(_mknet()) is None
        assert mgr.restore_latest() == (None, None)

    def test_listener_adapter_drives_manager(self, front, tmp_path):
        net = front.net()
        mgr = CheckpointManager(str(tmp_path), save_every_n_iterations=2,
                                keep_last=10)
        lst = mgr.listener()
        for it in (1, 2, 3, 4):
            net.iteration = it
            lst.iteration_done(net, it)
        assert len(mgr.checkpoints()) == 2
        net.epoch = 1
        lst.on_epoch_end(net, 1)
        assert mgr.checkpoints()[-1]["batches_into_epoch"] == 0


# ---------------------------------------------------------------------------
# auto-resume (in-process: interrupted run + torn newest checkpoints)
# ---------------------------------------------------------------------------

class TestAutoResume:
    def test_resume_after_corruption_is_bitwise_identical(self, front,
                                                          tmp_path):
        ds = _data()
        # "interrupted" run: 2 of 3 epochs with per-iteration checkpoints
        part = front.net()
        part.fit(ds, epochs=2, batch_size=8,
                 checkpoint=CheckpointManager(
                     str(tmp_path), save_every_n_iterations=1, keep_last=5))
        # tear the newest two checkpoints (mid-write crash analog)
        for f in ("checkpoint-00000016.zip", "checkpoint-00000015.zip"):
            _truncate(str(tmp_path / f))
        resumed = front.net()
        resumed.fit(ds, epochs=3, batch_size=8,
                    checkpoint=CheckpointManager(
                        str(tmp_path), save_every_n_iterations=1,
                        keep_last=5),
                    resume=True)
        straight = front.net()
        straight.fit(ds, epochs=3, batch_size=8)
        assert resumed.iteration == straight.iteration == 24
        assert resumed.epoch == straight.epoch == 3
        np.testing.assert_array_equal(resumed.params(), straight.params())

    def test_resume_with_no_checkpoint_trains_from_scratch(self, front,
                                                           tmp_path):
        ds = _data(32)
        net = front.net()
        net.fit(ds, epochs=1, batch_size=16,
                checkpoint=CheckpointManager(str(tmp_path)), resume=True)
        assert net.iteration == 2 and net.epoch == 1

    def test_resume_of_finished_run_is_noop(self, front, tmp_path):
        ds = _data(32)
        mgr = CheckpointManager(str(tmp_path))
        net = front.net()
        net.fit(ds, epochs=2, batch_size=16, checkpoint=mgr)
        p_done = np.asarray(net.params())
        again = front.net()
        again.fit(ds, epochs=2, batch_size=16,
                  checkpoint=CheckpointManager(str(tmp_path)), resume=True)
        assert again.epoch == 2
        np.testing.assert_array_equal(again.params(), p_done)

    def test_arg_validation(self, front, tmp_path):
        net = front.net()
        ds = _data(32)
        with pytest.raises(ValueError, match="resume"):
            net.fit(ds, resume=True)
        with pytest.raises(ValueError, match="step_fn"):
            net.fit(ds, steps_per_dispatch=2, step_fn=lambda batch: None)
        with pytest.raises(ValueError, match="steps_per_dispatch"):
            net.fit(ds, steps_per_dispatch=2,
                    checkpoint=CheckpointManager(str(tmp_path)))
        with pytest.raises(ValueError, match="steps_per_dispatch"):
            net.fit(ds, steps_per_dispatch=2,
                    sentinel=DivergenceSentinel("warn"))


# ---------------------------------------------------------------------------
# divergence sentinel
# ---------------------------------------------------------------------------

class TestDivergenceSentinel:
    def test_policy_validation(self):
        with pytest.raises(ValueError):
            DivergenceSentinel("explode")
        with pytest.raises(ValueError):
            DivergenceSentinel("rollback")  # needs checkpoint
        with pytest.raises(ValueError):
            DivergenceSentinel("skip_step", check_every=4)

    def test_warn_counts_and_continues(self, front):
        net = front.net()
        sent = DivergenceSentinel("warn")
        with faults.injected("step.nonfinite", "fail:2,4"):
            net.fit(_data(), epochs=1, batch_size=8, sentinel=sent)
        assert sent.nonfinite_steps == 2
        assert net.iteration == 8  # no steps dropped

    def test_real_nan_detected(self):
        net = _mknet()
        sent = DivergenceSentinel("warn")
        net.score_value = float("nan")
        assert sent.after_step(net)
        net.score_value = 0.5
        assert not sent.after_step(net)

    def test_skip_step_drops_update(self, front):
        net = front.net()
        sent = DivergenceSentinel("skip_step")
        with faults.injected("step.nonfinite", "fail:3"):
            net.fit(_data(), epochs=1, batch_size=8, sentinel=sent)
        assert sent.nonfinite_steps == 1
        # 8 batches, one update dropped and iteration rolled back
        assert net.iteration == 7

    def test_rollback_restores_and_backs_off_lr(self, front, tmp_path):
        net = front.net()
        mgr = CheckpointManager(str(tmp_path), save_every_n_iterations=1,
                                keep_last=3)
        lr0 = front.first_layer(net).updater.learning_rate
        sent = DivergenceSentinel("rollback", checkpoint=mgr,
                                  lr_backoff=0.5, max_rollbacks=2)
        with faults.injected("step.nonfinite", "fail:5"):
            net.fit(_data(), epochs=1, batch_size=8,
                    checkpoint=mgr, sentinel=sent)
        assert sent.rollbacks == 1
        assert front.first_layer(net).updater.learning_rate == \
            pytest.approx(lr0 / 2)
        snap = metrics_mod.registry().snapshot()
        assert snap.get("rollbacks_total", 0) >= 1
        assert snap.get('nonfinite_steps_total{policy="rollback"}', 0) >= 1

    def test_rollback_budget_exhausted_raises(self, front, tmp_path):
        net = front.net()
        mgr = CheckpointManager(str(tmp_path), save_every_n_iterations=1)
        sent = DivergenceSentinel("rollback", checkpoint=mgr,
                                  max_rollbacks=1)
        with faults.injected("step.nonfinite", "fail:3,5"):
            with pytest.raises(DivergenceError, match="budget"):
                net.fit(_data(), epochs=1, batch_size=8,
                        checkpoint=mgr, sentinel=sent)

    def test_rollback_without_checkpoint_on_disk_raises(self, front,
                                                        tmp_path):
        net = front.net()
        mgr = CheckpointManager(str(tmp_path))  # never saved into
        sent = DivergenceSentinel("rollback", checkpoint=mgr)
        net.score_value = float("nan")
        with pytest.raises(DivergenceError, match="no valid checkpoint"):
            sent.after_step(net)


# ---------------------------------------------------------------------------
# retry/backoff (fake clock — no real sleeping)
# ---------------------------------------------------------------------------

class _FakeClock:
    def __init__(self):
        self.now = 0.0
        self.sleeps = []

    def clock(self):
        return self.now

    def sleep(self, s):
        self.sleeps.append(s)
        self.now += s


class TestRetryBackoff:
    def test_exponential_growth_and_cap(self):
        fc = _FakeClock()
        calls = []

        def flaky():
            calls.append(1)
            if len(calls) < 6:
                raise OSError("transient")
            return "ok"

        pol = RetryPolicy(max_retries=5, base_delay=0.1, multiplier=2.0,
                          max_delay=0.5, jitter=0.0, deadline=None)
        out = retry_call(flaky, edge="test", policy=pol,
                         clock=fc.clock, sleep=fc.sleep)
        assert out == "ok" and len(calls) == 6
        assert fc.sleeps == pytest.approx([0.1, 0.2, 0.4, 0.5, 0.5])

    def test_budget_exhausted_reraises(self):
        fc = _FakeClock()
        pol = RetryPolicy(max_retries=2, base_delay=0.1, jitter=0.0,
                          deadline=None)
        with pytest.raises(OSError):
            retry_call(lambda: (_ for _ in ()).throw(OSError("down")),
                       edge="test", policy=pol,
                       clock=fc.clock, sleep=fc.sleep)
        assert len(fc.sleeps) == 2

    def test_deadline_aborts_early(self):
        fc = _FakeClock()
        pol = RetryPolicy(max_retries=50, base_delay=1.0, multiplier=1.0,
                          max_delay=1.0, jitter=0.0, deadline=3.5)
        with pytest.raises(OSError):
            retry_call(lambda: (_ for _ in ()).throw(OSError("down")),
                       edge="test", policy=pol,
                       clock=fc.clock, sleep=fc.sleep)
        # 1s sleeps until the next one would pass the 3.5s deadline
        assert fc.sleeps == pytest.approx([1.0, 1.0, 1.0])

    def test_non_retryable_propagates_immediately(self):
        fc = _FakeClock()

        def bug():
            raise KeyError("programming error")

        with pytest.raises(KeyError):
            retry_call(bug, edge="test",
                       policy=RetryPolicy(max_retries=5, jitter=0.0),
                       clock=fc.clock, sleep=fc.sleep)
        assert fc.sleeps == []

    def test_jitter_bounds(self):
        pol = RetryPolicy(base_delay=1.0, multiplier=1.0, max_delay=1.0,
                          jitter=0.25)
        for _ in range(50):
            assert 0.75 <= pol.delay(0) <= 1.25

    def test_policy_from_env(self, monkeypatch):
        monkeypatch.setenv("DL4JTPU_RETRY_MAX", "9")
        monkeypatch.setenv("DL4JTPU_RETRY_BASE_MS", "10")
        monkeypatch.setenv("DL4JTPU_RETRY_DEADLINE_S", "7")
        pol = RetryPolicy.from_env()
        assert pol.max_retries == 9
        assert pol.base_delay == pytest.approx(0.01)
        assert pol.deadline == pytest.approx(7.0)

    def test_retries_counter_labeled_by_edge(self):
        fc = _FakeClock()
        calls = []

        def flaky():
            calls.append(1)
            if len(calls) < 2:
                raise OSError("transient")
            return 1

        before = metrics_mod.registry().snapshot().get(
            'retries_total{edge="unit.edge"}', 0)
        retry_call(flaky, edge="unit.edge",
                   policy=RetryPolicy(jitter=0.0),
                   clock=fc.clock, sleep=fc.sleep)
        after = metrics_mod.registry().snapshot()[
            'retries_total{edge="unit.edge"}']
        assert after == before + 1


# ---------------------------------------------------------------------------
# parameter-server chaos
# ---------------------------------------------------------------------------

_FAST = RetryPolicy(max_retries=4, base_delay=0.001, multiplier=2.0,
                    max_delay=0.005, jitter=0.0, deadline=10.0)


class TestParameterServerChaos:
    def test_http_client_absorbs_transient_faults(self):
        net = _mknet()
        node = ParameterServerHttpNode(ParameterServer(net), port=0).start()
        try:
            client = HttpParameterServerClient(node.url, net.params_tree,
                                               retry=_FAST)
            with faults.injected("ps.pull", "fail:1"):
                version, params = client.pull()
                assert faults.fired_count("ps.pull") == 1
            assert version == 0
        finally:
            node.stop()

    def test_remote_worker_fit_zero_failures_under_budget(self):
        net = _mknet()
        node = ParameterServerHttpNode(ParameterServer(net), port=0).start()
        try:
            # transient faults on both edges, all within the retry budget
            with faults.injected("ps.pull", "fail:1,3"), \
                    faults.injected("ps.push", "fail:2"):
                applied = remote_worker_fit(net, node.url, _data(),
                                            epochs=1, batch_size=16,
                                            retry=_FAST)
            assert applied == 4  # every batch trained despite the faults
        finally:
            node.stop()

    def test_exhausted_retries_surface(self):
        net = _mknet()
        node = ParameterServerHttpNode(ParameterServer(net), port=0).start()
        client = HttpParameterServerClient(node.url, net.params_tree,
                                           retry=_FAST)
        try:
            with faults.injected("ps.pull", "fail:*"):
                with pytest.raises(faults.FaultInjected):
                    client.pull()
        finally:
            node.stop()

    def test_worker_respawn_recovers(self):
        net = _mknet()
        tr = ParameterServerTrainer(net, workers=2, max_worker_restarts=2)
        with faults.injected("ps.pull", "fail:1"):
            tr.fit(_data(), epochs=1, batch_size=16)
        assert tr.server.version > 0
        snap = metrics_mod.registry().snapshot()
        assert snap.get("worker_respawns_total", 0) >= 1

    def test_worker_errors_aggregated_and_threads_joined(self):
        import threading
        net = _mknet()
        tr = ParameterServerTrainer(net, workers=2, max_worker_restarts=0)
        before = threading.active_count()
        with faults.injected("ps.pull", "fail:*"):
            with pytest.raises(RuntimeError) as ei:
                tr.fit(_data(), epochs=1, batch_size=16)
        assert "worker error 0" in str(ei.value)
        assert "FaultInjected" in str(ei.value)
        # no orphaned daemon threads holding the queue
        assert threading.active_count() <= before


# ---------------------------------------------------------------------------
# prefetch-thread retry
# ---------------------------------------------------------------------------

class _FlakyIterator(ListDataSetIterator):
    """Base iterator that raises once at a chosen poll (then works)."""

    def __init__(self, ds, batch_size, fail_at):
        super().__init__(ds, batch_size)
        self.fail_at = fail_at
        self.polls = 0

    def __next__(self):
        self.polls += 1
        if self.polls == self.fail_at:
            raise OSError("transient storage hiccup")
        return super().__next__()


class TestPrefetchRetry:
    def test_one_retry_absorbs_transient(self):
        base = _FlakyIterator(_data(48), 16, fail_at=2)
        out = list(AsyncDataSetIterator(base, queue_size=2))
        # the retry re-polls, so the failed poll consumes no batch
        assert len(out) == 3
        snap = metrics_mod.registry().snapshot()
        assert snap.get('retries_total{edge="etl.next"}', 0) >= 1

    def test_persistent_failure_propagates(self):
        base = _data(48)
        it = AsyncDataSetIterator(ListDataSetIterator(base, 16),
                                  queue_size=2)
        with faults.injected("etl.next", "fail:2,3"):
            with pytest.raises(faults.FaultInjected):
                list(it)

    def test_injected_single_fault_invisible(self):
        it = AsyncDataSetIterator(ListDataSetIterator(_data(48), 16),
                                  queue_size=2)
        with faults.injected("etl.next", "fail:2"):
            assert len(list(it)) == 3


# ---------------------------------------------------------------------------
# kill-and-resume (subprocess, SIGKILL mid-checkpoint-write)
# ---------------------------------------------------------------------------

@pytest.mark.slow
class TestKillResume:
    def test_sigkill_mid_write_then_resume_bitwise(self, tmp_path):
        worker = os.path.join(os.path.dirname(__file__),
                              "resilience_worker.py")
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        ckpt = str(tmp_path / "ckpt")
        out_resumed = str(tmp_path / "resumed.npz")
        out_straight = str(tmp_path / "straight.npz")

        # 1) fresh run killed by SIGKILL during the 13th checkpoint write
        env_kill = dict(env, DL4JTPU_FAULT_CHECKPOINT_WRITE="kill:13")
        r = subprocess.run([sys.executable, worker, ckpt, "/dev/null",
                            "fresh"], env=env_kill, capture_output=True,
                           text=True, timeout=600)
        assert r.returncode == -signal.SIGKILL, r.stderr

        # 2) auto-resume to completion
        r = subprocess.run([sys.executable, worker, ckpt, out_resumed,
                            "resume"], env=env, capture_output=True,
                           text=True, timeout=600)
        assert r.returncode == 0, r.stderr

        # 3) uninterrupted control run
        r = subprocess.run([sys.executable, worker,
                            str(tmp_path / "ckpt2"), out_straight,
                            "fresh"], env=env, capture_output=True,
                           text=True, timeout=600)
        assert r.returncode == 0, r.stderr

        a = np.load(out_resumed)
        b = np.load(out_straight)
        assert int(a["iteration"]) == int(b["iteration"]) == 24
        np.testing.assert_array_equal(a["params"], b["params"])
