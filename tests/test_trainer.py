"""The one fit loop (`Trainer.fit`, nn/stepping.py) held to its contract
on both front ends: MultiLayerNetwork and a two-vertex ComputationGraph of
the same layers (the `front` fixture, conftest.py). What the loop does
with threads, spans, grouping, resume, feeding and its counters is the
same code for both; what a batch is differs."""
import threading

import jax
import numpy as np
import pytest

from deeplearning4j_tpu.data.dataset import DataSet
from deeplearning4j_tpu.data.iterators import (ExistingDataSetIterator,
                                               ListDataSetIterator)
from deeplearning4j_tpu.optimize import metrics as metrics_mod
from deeplearning4j_tpu.optimize import tracing
from deeplearning4j_tpu.optimize.resilience import CheckpointManager


@pytest.fixture(autouse=True)
def _tracing_off():
    tracing.disable()
    tracing.clear()
    yield
    tracing.disable()
    tracing.clear()


def _data(n=64, n_in=4, seed=42):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(n, n_in)).astype(np.float32)
    y = np.eye(3, dtype=np.float32)[rng.integers(0, 3, size=n)]
    return DataSet(x, y)


def _leaves(net):
    return [np.asarray(a) for a in jax.tree_util.tree_leaves(net.params_tree)]


class _Iterations:
    """Listener: every iteration number seen, and how many an epoch."""

    def __init__(self):
        self.seen = []
        self.per_epoch = []
        self.epochs = []

    def iteration_done(self, model, it):
        self.seen.append(it)

    def on_epoch_end(self, model, epoch):
        self.epochs.append(epoch)
        self.per_epoch.append(len(self.seen) - sum(self.per_epoch))


class _CountingIterator(ListDataSetIterator):
    """Counts the batches the loop pulled from it."""

    def __init__(self, ds, batch_size):
        super().__init__(ds, batch_size)
        self.pulled = 0

    def __next__(self):
        ds = super().__next__()
        self.pulled += 1
        return ds


def _checkpoint_three_batches_in(front, directory, ds):
    """A checkpoint as an interrupted run leaves it: 3 batches of 8 into
    epoch 0."""
    part = front.net()
    part.fit(DataSet(ds.features[:24], ds.labels[:24]), batch_size=8)
    part.epoch = 0
    CheckpointManager(directory).save(part, batches_into_epoch=3)
    return part


class TestShutdown:
    def test_step_that_raises_leaves_no_thread_and_ends_the_fit_span(
            self, front):
        before = set(threading.enumerate())
        calls = []

        def step(batch):
            calls.append(batch)
            if len(calls) == 3:
                raise RuntimeError("boom")

        tracing.enable(fence_every=0)
        with pytest.raises(RuntimeError, match="boom"):
            front.net().fit(_data(), batch_size=8, step_fn=step)
        assert [t for t in threading.enumerate()
                if t not in before and t.is_alive()] == []
        names = [e["name"] for e in
                 tracing.export_trace_events()["traceEvents"]]
        assert names.count("fit") == 1
        assert tracing._open() == []        # nothing left open behind it

    def test_use_async_false_starts_no_thread(self, front):
        before = set(threading.enumerate())
        during = []

        class Threads:
            def iteration_done(self, model, it):
                during.append([t for t in threading.enumerate()
                               if t not in before])

        net = front.net()
        net.listeners.append(Threads())
        net.fit(_data(), batch_size=8, use_async=False)
        assert len(during) == 8 and not any(during)
        # the control: the default feeding does run a producer thread
        del during[:]
        net.fit(_data(), batch_size=8)
        assert any(during)


class TestRunAhead:
    def test_the_loop_leaves_two_steps_unfinished_behind_it_and_no_more(
            self, front):
        """A producer that stages faster than the device steps must not
        let the host queue steps without bound (each holds its batch on
        the device): after dispatching step n the loop waits for the
        loss of step n - 2, in order, once each."""
        dispatched, waited = [], []

        class Loss:
            def __init__(self, n):
                self.n = n

            def block_until_ready(self):
                waited.append((self.n, len(dispatched)))
                return self

        net = front.net()

        def step(batch):
            dispatched.append(batch)
            net.score_value = Loss(len(dispatched))

        net.fit(_data(), batch_size=8, step_fn=step)
        assert len(dispatched) == 8
        assert waited == [(n, n + 2) for n in range(1, 7)]


class TestGrouping:
    def test_flushes_at_a_change_of_signature_and_at_the_epoch_tail(
            self, front):
        """7 batches of 8 rows and one of 5, three a dispatch: two full
        groups, then the seventh alone (the odd one ends its group), then
        the odd one alone at the epoch's tail."""
        net = front.net()
        rec = _Iterations()
        net.listeners.append(rec)
        dispatched = []
        fit_batches, fit_batch = net.fit_batches, net._fit_batch
        net.fit_batches = lambda group: (
            dispatched.append([b.num_examples() for b in group]),
            fit_batches(group))
        net._fit_batch = lambda b: (
            dispatched.append(b.num_examples()), fit_batch(b))
        tracing.enable(fence_every=0)
        net.fit(_data(61), batch_size=8, steps_per_dispatch=3,
                pad_to_bucket=False)
        assert dispatched == [[8, 8, 8], [8, 8, 8], 8, 5]
        assert rec.seen == list(range(1, 9))
        tails = [e for e in tracing.export_trace_events()["traceEvents"]
                 if e["name"] == "dispatch"
                 and e["args"].get("flush") == "epoch_tail"]
        assert len(tails) == 1

    def test_a_group_is_waited_for_once_two_more_are_launched_not_before(
            self, front, monkeypatch):
        """The bound on run-ahead counts launches, not batches. A batch
        that joins a filling group launches nothing; a wait there, for
        the group just flushed, would leave the device idle while the
        host gathers, stacks and dispatches the next group: the latency
        `steps_per_dispatch` exists to hide."""
        net = front.net()
        launched, waited = [], []
        fit_batches, ready = net.fit_batches, jax.block_until_ready

        def launch(group):
            fit_batches(group)
            launched.append(net.score_value)

        def wait(x):
            waited.extend((n, len(launched))
                          for n, loss in enumerate(launched, 1) if x is loss)
            return ready(x)

        net.fit_batches = launch
        monkeypatch.setattr(jax, "block_until_ready", wait)
        net.fit(_data(192), batch_size=8, steps_per_dispatch=4)
        assert len(launched) == 6
        assert waited == [(n, n + 2) for n in range(1, 5)]

    def test_truncated_bptt_batches_fuse_on_the_list_network_only(
            self, front):
        """The one difference in behaviour between the front ends."""
        net = front.tbptt_net(window=5)
        rec = _Iterations()
        net.listeners.append(rec)
        rng = np.random.default_rng(1)
        x = rng.standard_normal((32, 10, 4)).astype(np.float32)
        y = np.eye(3, dtype=np.float32)[rng.integers(0, 3, (32, 10))]
        if front.kind == "graph":
            with pytest.raises(NotImplementedError, match="truncated BPTT"):
                net.fit(x, y, batch_size=16, steps_per_dispatch=2)
            assert net.iteration == 0 and net.epoch == 0
            return
        net.fit(x, y, batch_size=16, steps_per_dispatch=2)
        # 2 batches x 2 windows in one dispatch; one event a batch, the
        # iteration advancing by the window count
        assert net.iteration == 4
        assert rec.seen == [2, 4]


class TestResume:
    def test_discards_the_covered_batches_and_steps_none_of_them(
            self, front, tmp_path):
        ds = _data()
        part = _checkpoint_three_batches_in(front, str(tmp_path), ds)
        net = front.net(seed=99)
        rec = _Iterations()
        net.listeners.append(rec)
        it = _CountingIterator(ds, 8)
        net.fit(it, epochs=1, checkpoint=CheckpointManager(str(tmp_path)),
                resume=True)
        assert it.pulled == 8
        assert rec.seen == [4, 5, 6, 7, 8]
        straight = front.net()
        straight.fit(ds, batch_size=8)
        assert part.iteration == 3
        for a, b in zip(_leaves(net), _leaves(straight)):
            np.testing.assert_array_equal(a, b)

    def test_skips_in_the_first_resumed_epoch_alone(self, front, tmp_path):
        ds = _data()
        _checkpoint_three_batches_in(front, str(tmp_path), ds)
        net = front.net(seed=99)
        rec = _Iterations()
        net.listeners.append(rec)
        net.fit(ds, epochs=2, batch_size=8,
                checkpoint=CheckpointManager(str(tmp_path)), resume=True)
        assert rec.per_epoch == [5, 8]
        assert rec.seen == list(range(4, 17))
        assert net.epoch == 2


class TestFeeding:
    def test_a_plain_generator_trains_every_epoch(self, front):
        """A generator is spent after one pass: the graph takes it bare
        and keeps what it yields, the list network takes it through
        ExistingDataSetIterator, which does the same."""
        ds = _data(32)
        gen = (DataSet(ds.features[i:i + 8], ds.labels[i:i + 8])
               for i in range(0, 32, 8))
        net = front.net()
        net.fit(gen if front.kind == "graph"
                else ExistingDataSetIterator(gen), epochs=2)
        assert net.iteration == 8 and net.epoch == 2

    def test_step_fn_gets_each_batch_once_in_the_front_ends_type(
            self, front):
        got = []
        net = front.net()
        net.fit(_data(), batch_size=8, step_fn=got.append)
        assert len(got) == 8
        assert all(isinstance(b, front.batch_type) for b in got)
        assert sum(b.num_examples() for b in got) == 64
        assert net.iteration == 0       # the loop itself steps nothing

    @pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
    def test_three_ways_of_feeding_end_in_the_same_parameters(self, front,
                                                              dtype):
        """Host batches are float32 either way: a bfloat16 network's cast
        is the same `convert` on the fit thread whether the batch came
        from the host or was staged on the device by the producer."""
        ds = _data(60)                  # a ragged tail: 7 x 8 + 4
        ends = []
        for how in (dict(use_async=False), dict(prefetch_to_device=False),
                    dict()):
            net = front.net(dtype=dtype)
            net.fit(ds, batch_size=8, **how)
            assert net.iteration == 8
            ends.append(_leaves(net))
            assert {str(a.dtype) for a in ends[-1]} == {dtype}
        for other in ends[1:]:
            for a, b in zip(ends[0], other):
                np.testing.assert_array_equal(a, b)

    def test_etl_split_is_the_staged_batchs_or_all_host(self, front):
        staged = []

        class Split:
            def iteration_done(self, model, it):
                staged.append((model.last_etl_ms, model.last_etl_host_ms,
                               model.last_etl_h2d_ms))

        got = []
        net = front.net()
        net.listeners.append(Split())
        fit_batch = net._fit_batch
        net._fit_batch = lambda b: (got.append(b), fit_batch(b))
        net.fit(_data(32), batch_size=8)
        assert len(got) == 4
        for b, (_, host, h2d) in zip(got, staged):
            assert h2d == b._etl_h2d_ms > 0.0
            assert host == b._etl_host_ms
        del staged[:]
        net.fit(_data(32), batch_size=8, prefetch_to_device=False)
        assert len(staged) == 4
        for etl, host, h2d in staged:
            assert h2d == 0.0 and host == etl


class TestEpochs:
    def test_epoch_end_and_the_counter_fire_once_an_epoch(self, front):
        counter = metrics_mod.registry().counter("train_epochs_total")
        before = counter.value()
        net = front.net()
        rec = _Iterations()
        net.listeners.append(rec)
        net.fit(_data(32), epochs=3, batch_size=8)
        assert rec.epochs == [1, 2, 3]
        assert rec.per_epoch == [4, 4, 4]
        assert net.epoch == 3
        assert counter.value() - before == 3
