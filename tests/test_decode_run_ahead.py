"""The decode engine keeps one step in flight (docs/serving.md §decode):
step n+1 is launched before step n is fetched, its input tokens stay on
the device. The order must not change one output token, under churn and
in every setting of the decoder; what is learned a step late (a
non-finite row, a deadline) is handled at that commit and reaches no
reply; a pause, a shutdown and an idle loop leave nothing unfetched; the
compiled signatures are what they were."""
import sys
import threading
import time

import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import manifest
from deeplearning4j_tpu import (LSTM, InputType, MultiLayerNetwork,
                                NeuralNetConfiguration, RnnOutputLayer, Sgd)
from deeplearning4j_tpu.optimize import telemetry, tracing
from deeplearning4j_tpu.optimize.metrics import registry
from deeplearning4j_tpu.parallel.inference import (DeadlineExceededError,
                                                   DecodeStepError,
                                                   NonFiniteOutputError)
from deeplearning4j_tpu.serving.breaker import (CLOSED, HALF_OPEN, OPEN,
                                                CircuitBreaker)
from deeplearning4j_tpu.serving.decode import (DecodeEngine, PagedKVCache,
                                               RecurrentAdapter,
                                               TransformerAdapter,
                                               TransformerDecoder,
                                               naive_generate)
from deeplearning4j_tpu.serving.model_pool import ModelPool
from deeplearning4j_tpu.utils import faults

PACK, BT, CONTEXT = 16, 4, 96
SETTINGS = {
    "dense-full": {},
    "dense-sliding": dict(layer_types=("sliding", "full"), window=8),
    "sparse-sliding": dict(
        layer_types=("sliding", "full"), window=8, mlp="moe", experts=4,
        experts_per_token=2, norm="rms", position="rotary", tied=False,
        kv_heads=1, init_std=0.25,
        rope={k: {"rope_theta": 10000.0} for k in ("full", "sliding")}),
}


def _engine(setting="dense-full", rows=3, name="ahead", **engine_kw):
    model = TransformerDecoder(vocab=48, layers=2, heads=2, head_dim=8,
                               ff=16, max_context=CONTEXT, seed=4,
                               **SETTINGS[setting])
    cache = PagedKVCache(
        layers=2, heads=model.kv_heads, head_dim=8, block_tokens=BT,
        layer_kinds=model.layer_kinds(), window=model.window,
        max_blocks={"full": 96, "sliding": 32})
    ad = TransformerAdapter(model, cache, pack_bucket=PACK, max_rows=rows)
    return DecodeEngine(ad, name=name, max_decode_batch=rows, **engine_kw)


def _overlapped(name):
    return registry().counter(
        "serving_decode_steps_overlapped_total").labels(model=name).value()


def _ask_all(eng, jobs, stagger=0.0, deadlines=()):
    """Every (prompt, max_new_tokens) of `jobs` from a thread of its own;
    a reply is the tokens or the exception."""
    out = [None] * len(jobs)
    deadlines = dict(deadlines)

    def run(i):
        time.sleep(stagger * i)
        try:
            out[i] = eng.generate(jobs[i][0], max_new_tokens=jobs[i][1],
                                  deadline=deadlines.get(i))
        except Exception as e:  # noqa: BLE001 — the test reads it
            out[i] = e
    ts = [threading.Thread(target=run, args=(i,)) for i in range(len(jobs))]
    for t in ts:
        t.start()
    for t in ts:
        t.join(timeout=300)
    assert not any(t.is_alive() for t in ts)
    return out


def _reply(eng, job):
    try:
        return eng.generate(job[0], max_new_tokens=job[1])
    except Exception as e:  # noqa: BLE001 — the test reads it
        return e


def _start_and_wait_queued(eng, threads):
    """Inside a pause: all are in before any runs. The loop takes its
    admits off the queue before it asks for the step lock, so the first
    taken may be in neither count while the pause holds it."""
    for t in threads:
        t.start()
    limit = time.monotonic() + 30
    while eng.queue_depth() < len(threads) - 1 and time.monotonic() < limit:
        time.sleep(0.005)


def _trails(model, prompt, tokens):
    """By how much each served token's logit lies below the best one of
    the plain forward over the prompt and the tokens before it."""
    seq = list(prompt) + list(tokens)
    row = np.zeros((1, CONTEXT), np.int32)
    seg = np.zeros((1, CONTEXT), np.int32)
    pos = np.zeros((1, CONTEXT), np.int32)
    row[0, :len(seq)], seg[0, :len(seq)] = seq, 1
    pos[0, :len(seq)] = np.arange(len(seq))
    logits = np.asarray(model.logits(row, seg, pos)[0])
    at = len(prompt) - 1 + np.arange(len(tokens))
    return logits[at].max(axis=-1) - logits[at, tokens]


# rows come and go at different steps (5 requests on 3 rows, 3 to 19
# tokens each, arriving apart), and one prompt takes three chunks
CHURN = [(5, 9), (40, 6), (11, 19), (3, 3), (16, 12)]


@pytest.mark.parametrize("setting", list(SETTINGS))
def test_tokens_under_churn_are_the_plain_forwards(setting):
    rng = np.random.default_rng(7)
    jobs = [(rng.integers(0, 48, n).tolist(), new) for n, new in CHURN]
    with _engine(setting, name=f"churn-{setting}") as eng:
        before = _overlapped(f"churn-{setting}")
        served = _ask_all(eng, jobs, stagger=0.02)
        ahead = _overlapped(f"churn-{setting}") - before
        model, cache = eng.adapter.model, eng.adapter.cache
    assert cache.blocks_in_use() == 0 and cache.free_blocks() == \
        (128 if "sliding" in setting else 96)
    assert not eng.adapter.in_flight() and not eng.adapter._slot_of
    assert ahead > 0
    for (prompt, new), got in zip(jobs, served):
        assert isinstance(got, list) and len(got) == new, got
        if setting == "dense-full":
            assert got == naive_generate(model, prompt, new, pad_to=CONTEXT)
        else:   # two float32 programs of different shape agree to rounding
            assert _trails(model, prompt, got).max() <= 2e-4


def _stream_net(n_in=4, seed=3):
    conf = (NeuralNetConfiguration.builder().seed(seed).updater(Sgd(0.1))
            .list()
            .layer(LSTM(n_out=6, activation="tanh"))
            .layer(RnnOutputLayer(n_out=n_in, activation="identity",
                                  loss="mse"))
            .set_input_type(InputType.recurrent(n_in)).build())
    return MultiLayerNetwork(conf).init()


def test_the_recurrent_arm_rides_the_same_loop_and_never_runs_ahead():
    rng = np.random.default_rng(4)
    jobs = [(rng.standard_normal((n, 4)).astype(np.float32), new)
            for n, new in ((3, 5), (6, 2), (2, 7))]
    before = _overlapped("stream")
    with DecodeEngine(RecurrentAdapter(_stream_net(), feature_dim=4),
                      name="stream", max_decode_batch=2) as eng:
        served = _ask_all(eng, jobs, stagger=0.01)
        assert not eng.adapter._carries and not eng.adapter._last_out
    assert _overlapped("stream") == before
    for (prompt, new), got in zip(jobs, served):
        net = _stream_net()
        for t in range(prompt.shape[0]):
            last = net.rnn_time_step(prompt[t][None, :])[0]
        want = []
        for _ in range(new):
            want.append(last)
            last = net.rnn_time_step(last[None, :])[0]
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-6)


def test_a_stream_prompt_that_prefills_non_finite_fails_alone():
    """The stream arm reports a chunk's own trouble with the call that
    ran it: that prompt fails typed, it never becomes a row, and the
    others are served."""
    good = np.ones((3, 4), np.float32)
    bad = np.full((2, 4), np.inf, np.float32)
    with DecodeEngine(RecurrentAdapter(_stream_net(), feature_dim=4),
                      name="stream-bad", max_decode_batch=2) as eng:
        out = _ask_all(eng, [(good, 3), (bad, 3), (good, 2)], stagger=0.01)
        assert not eng.adapter._carries and not eng.active_count()
    assert isinstance(out[1], NonFiniteOutputError)
    assert np.asarray(out[0]).shape == (3, 4) \
        and np.asarray(out[2]).shape == (2, 4)
    np.testing.assert_allclose(out[0][:2], out[2], rtol=1e-6)


def test_a_launch_begins_before_the_fetch_of_the_step_before_it_ends():
    """From the program's own spans, and the counter counts it: a lone
    request's steps are all launched ahead, the first of its own chunk's
    fetch. The chunk is numbered like a step: it is launch 1."""
    tracing.enable(ring_size=1 << 12, fence_every=0)
    try:
        with _engine(name="spans-ahead") as eng:
            before = _overlapped("spans-ahead")
            eng.generate([3, 1, 4, 1, 5], max_new_tokens=9)
            ahead = _overlapped("spans-ahead") - before
        tracing_events = tracing.export_trace_events()["traceEvents"]
        ev = [e for e in tracing_events if "seq" in e.get("args", {})]
    finally:
        tracing.disable()
        tracing.clear()
    by = {name: {e["args"]["seq"]: e for e in ev if e["name"] == name}
          for name in ("decode/launch", "decode/fetch", "decode/commit")}
    # the chunk, then 8 steps; the chunk's fetch lies under the first step
    assert sorted(by["decode/launch"]) == sorted(by["decode/fetch"]) == \
        sorted(by["decode/commit"]) == list(range(1, 10))
    for seq in range(1, 9):
        nxt, fetch = by["decode/launch"][seq + 1], by["decode/fetch"][seq]
        assert nxt["ts"] + nxt["dur"] <= fetch["ts"] + 1.0   # microseconds
    assert ahead == 8
    # what the chunk computed: 5 x 6 / 2 visible pairs in each of the two
    # full layers, and on a CPU no part takes the kernel
    chunk = by["decode/launch"][1]["args"]
    assert chunk["pairs"] == {"full": 30} and chunk["pairs_run"] == {}
    assert all("pairs" not in by["decode/launch"][seq]["args"]
               for seq in range(2, 10))


def test_a_newcomer_takes_the_row_once_its_holders_last_step_is_launched():
    """One row, two requests: the second is prefilled while the first's
    last step is in flight and rides the very next step, whose span
    fetches that last step."""
    jobs = [([3, 1, 4, 1, 5], 4), ([9, 2, 6], 3)]
    tracing.enable(ring_size=1 << 12, fence_every=0)
    try:
        with _engine(rows=1, name="row-free") as eng:
            threads = [threading.Thread(target=_reply, args=(eng, j))
                       for j in jobs]
            with eng.paused():          # both are in, in this order
                for t in threads:
                    t.start()
                    time.sleep(0.2)
            for t in threads:
                t.join(timeout=120)
            assert not eng.adapter.in_flight()
        ev = sorted(tracing.export_trace_events()["traceEvents"],
                    key=lambda e: e["ts"])
    finally:
        tracing.disable()
        tracing.clear()
    steps = [e for e in ev if e["name"] == "decode/step"]
    # 3 steps for the first request, 2 for the second, one row each
    assert [s["args"]["rows"] for s in steps] == [1] * 5
    first, second = steps[0]["args"]["rids"], steps[-1]["args"]["rids"]
    assert [s["args"]["rids"] for s in steps] == [first] * 3 + [second] * 2
    fetch = {e["args"]["seq"]: e for e in ev if e["name"] == "decode/fetch"
             and "seq" in e["args"]}
    prefill = [e for e in ev if e["name"] == "decode/prefill"]
    assert len(prefill) == 2 and prefill[1]["args"]["rids"] == second
    # the newcomer's chunk was launched before the first request's last
    # step (the fourth launch, after its chunk) was fetched: under the
    # chunk's own span
    assert fetch[4]["args"]["parent_id"] == prefill[1]["args"]["span_id"]


def _poison(adapter, call, row):
    """The `call`-th step from now reports `row` non-finite."""
    real, calls = adapter.model.step, [0]

    def step(*args):
        picked, finite, *rest = real(*args)
        calls[0] += 1
        if calls[0] == call:
            finite = finite.at[row].set(False)
        return (picked, finite, *rest)
    adapter.model.step = step


def test_a_non_finite_row_is_failed_a_step_late_and_its_next_token_dropped():
    """The adapter's two halves by hand: step n reports row 0 non-finite,
    which the host learns with step n+1 already launched for both."""
    with _engine(name="late") as eng, eng.paused():
        ad, cache = eng.adapter, eng.adapter.cache
        prompts = {1: np.arange(1, 7, dtype=np.int32),
                   2: np.arange(9, 18, dtype=np.int32)}
        assert ad.prefill_group(list(prompts.items())) == ({}, {})
        toks = {r: [] for r in prompts}
        _poison(ad, 2, 0)
        done = [ad.step([1, 2]), ad.step([1, 2]), ad.step([1, 2])]
        # the chunk had nothing before it; the first step returns the
        # chunk's first tokens, the second the first's tokens, the third
        # the second's: row 0 failed
        assert sorted(done[0][0]) == [1, 2] and not done[0][1]
        assert sorted(done[1][0]) == [1, 2] and not done[1][1]
        out, fails = done[2]
        assert list(out) == [2] and list(fails) == [1]
        assert isinstance(fails[1], NonFiniteOutputError)
        held = cache.blocks_of(1)
        ad.free(1)                              # as the engine does
        assert held > 0 and cache.blocks_of(1) == 0
        out, fails = ad.collect()               # step 3 computed both rows
        assert list(out) == [2] and not fails
        ad.free(1)                              # idempotent: once is once
        for o, _ in done + [(out, fails)]:
            toks[2].append(o[2])
        assert toks[2] == naive_generate(ad.model, prompts[2].tolist(), 4,
                                         pad_to=CONTEXT)
        ad.free(2)
        assert cache.blocks_in_use() == 0 and cache.free_blocks() == 96


def test_the_engine_fails_a_late_non_finite_row_typed_and_serves_the_rest():
    rng = np.random.default_rng(1)
    jobs = [(rng.integers(0, 48, 6).tolist(), 12),
            (rng.integers(0, 48, 9).tolist(), 12)]
    with _engine(name="late-engine") as eng:
        ad, cache = eng.adapter, eng.adapter.cache
        freed = []
        real_free = cache.free

        def free(rid):
            freed.append((rid, cache.blocks_of(rid)))
            real_free(rid)
        cache.free = free
        out = [None, None]
        threads = [threading.Thread(
            target=lambda i=i: out.__setitem__(i, _reply(eng, jobs[i])))
            for i in range(2)]
        with eng.paused():      # both are queued before either is stepped
            _poison(ad, 4, 0)
            _start_and_wait_queued(eng, threads)
        for t in threads:
            t.join(timeout=120)
        assert not any(t.is_alive() for t in threads)
    bad = [o for o in out if isinstance(o, NonFiniteOutputError)]
    good = [(i, o) for i, o in enumerate(out) if isinstance(o, list)]
    assert len(bad) == 1 and len(good) == 1
    i, tokens = good[0]
    assert tokens == naive_generate(ad.model, jobs[i][0], 12, pad_to=CONTEXT)
    # each request's blocks went back once, and nothing is left
    gave = [rid for rid, blocks in freed if blocks]
    assert sorted(gave) == sorted(set(gave)) and len(gave) == 2
    assert cache.blocks_in_use() == 0 and cache.free_blocks() == 96
    assert not ad.in_flight() and not ad._slot_of


def _slowed(adapter, seconds):
    """Every step takes at least `seconds` from launch to fetch."""
    real = adapter.model.step

    def step(*args):
        time.sleep(seconds)
        return real(*args)
    adapter.model.step = step


def test_a_deadline_that_passes_with_a_step_in_flight():
    """The first request's deadline passes inside the launch of its
    third step (no clock: the launch sets it back), so the loop learns
    of it with that step in flight."""
    rng = np.random.default_rng(2)
    jobs = [(rng.integers(0, 48, 5).tolist(), 40),
            (rng.integers(0, 48, 8).tolist(), 40)]
    with _engine(name="deadline") as eng:
        ad = eng.adapter
        real, launches = ad.step, []

        def step(rids):
            victim = [r for r in eng._active if list(r.prompt) == jobs[0][0]]
            launches.append(len(rids))
            if victim and victim[0].launched == 3:
                victim[0].deadline = time.monotonic() - 1.0
            return real(rids)
        ad.step = step
        out = _ask_all(eng, jobs, deadlines={0: time.monotonic() + 3600})
        cache = ad.cache
    assert isinstance(out[0], DeadlineExceededError)
    # its third step was launched and its token never served
    assert "after 3 token(s)" in str(out[0])
    assert out[1] == naive_generate(ad.model, jobs[1][0], 40, pad_to=CONTEXT)
    assert cache.blocks_in_use() == 0 and not ad.in_flight() \
        and not ad._slot_of


def test_an_injected_fault_isolates_the_riders_one_by_one():
    """The batch launch fails and then the second rider's solo launch:
    the fault point fires once an attempt (the batch, then each rider
    alone), one rider dies typed, the others serve the clean run's
    tokens though a step was in flight when the batch launch raised."""
    rng = np.random.default_rng(3)
    jobs = [(rng.integers(0, 48, n).tolist(), 8) for n in (5, 7, 4)]
    with _engine(name="isolate") as eng:
        eng.warmup()
        out = [None] * 3
        threads = [threading.Thread(
            target=lambda i=i: out.__setitem__(i, _reply(eng, jobs[i])))
            for i in range(3)]
        with eng.paused():
            _start_and_wait_queued(eng, threads)
            # attempt 3 is a batch of three (after two batches, or after
            # the first request's step alone and one batch) and fails;
            # 4-6 are its riders alone, of which the second fails
            faults.inject("serve.decode_step", "fail:3,5")
        try:
            for t in threads:
                t.join(timeout=120)
            fired, calls = (faults.fired_count("serve.decode_step"),
                            faults.call_count("serve.decode_step"))
        finally:
            faults.clear("serve.decode_step")
        ad = eng.adapter
    died = [o for o in out if isinstance(o, DecodeStepError)]
    lived = [(i, o) for i, o in enumerate(out) if isinstance(o, list)]
    assert len(died) == 1 and len(lived) == 2 and fired == 2
    # once an attempt: 7 steps a request, so 2 + 1 + 3 + 4 attempts, and
    # one more if the first request's first step ran alone
    assert calls == eng._step_no and calls in (10, 11)
    for i, tokens in lived:
        assert tokens == naive_generate(ad.model, jobs[i][0], 8,
                                        pad_to=CONTEXT)
    assert ad.cache.blocks_in_use() == 0 and not ad.in_flight()


def test_a_pause_and_a_shutdown_return_only_with_nothing_in_flight():
    rng = np.random.default_rng(5)
    jobs = [(rng.integers(0, 48, 6).tolist(), 30),
            (rng.integers(0, 48, 4).tolist(), 30)]
    eng = _engine(name="pause")
    ad = eng.adapter
    eng.generate(jobs[0][0], max_new_tokens=2)          # compiled
    _slowed(ad, 0.004)
    out = [None, None]
    threads = [threading.Thread(
        target=lambda i=i: out.__setitem__(i, _reply(eng, jobs[i])))
        for i in range(2)]
    for t in threads:
        t.start()
    limit = time.monotonic() + 30
    while eng.active_count() < 2 and time.monotonic() < limit:
        time.sleep(0.002)
    pauses = seen_ahead = 0
    while any(t.is_alive() for t in threads) and pauses < 12:
        seen_ahead += ad.in_flight()
        with eng.paused():
            assert not ad.in_flight()
            had = [eng.active_count(), sum(len(r.generated)
                                           for r in eng._active)]
            time.sleep(0.01)            # the loop stalls between steps
            assert not ad.in_flight()
            assert had == [eng.active_count(), sum(len(r.generated)
                                                   for r in eng._active)]
        pauses += 1
        time.sleep(0.006)
    for t in threads:
        t.join(timeout=60)
    assert seen_ahead > 0 and pauses >= 3
    for job, got in zip(jobs, out):
        assert got == naive_generate(ad.model, job[0], 30, pad_to=CONTEXT)
    # shut down in the middle of a generation: the loop drains it (its
    # caller may be told the engine closed) and leaves nothing unfetched
    late = threading.Thread(target=lambda: out.append(_reply(eng, jobs[0])))
    late.start()
    while not (eng.active_count() and ad.in_flight()) \
            and time.monotonic() < limit:
        time.sleep(0.001)
    eng.shutdown(join_timeout=60)
    assert not ad.in_flight() and not eng._worker.is_alive()
    late.join(timeout=60)
    assert not late.is_alive() and out[2] is not None
    assert ad.cache.blocks_in_use() == 0 and not ad._slot_of


def test_callers_and_pausers_under_a_short_switch_interval():
    """More threads than cores, the interpreter switching every 10 us:
    callers, a pauser and the loop share the step lock and the count of
    pausers. Every reply is the clean run's and nothing is left held."""
    rng = np.random.default_rng(6)
    jobs = [(rng.integers(0, 48, int(n)).tolist(), int(new))
            for n, new in zip(rng.integers(2, 30, 24),
                              rng.integers(1, 12, 24))]
    was = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        with _engine(name="stress", rows=4, queue_limit=64) as eng:
            ad = eng.adapter
            stop = threading.Event()

            def pauser():
                while not stop.is_set():
                    with eng.paused():
                        assert not ad.in_flight()
                    time.sleep(0.003)
            p = threading.Thread(target=pauser)
            p.start()
            t0 = time.monotonic()
            served = _ask_all(eng, jobs)
            stop.set()
            p.join(timeout=30)
            assert not p.is_alive() and time.monotonic() - t0 < 240
    finally:
        sys.setswitchinterval(was)
    for (prompt, new), got in zip(jobs, served):
        assert got == naive_generate(ad.model, prompt, new, pad_to=CONTEXT)
    assert ad.cache.blocks_in_use() == 0 and not ad._slot_of \
        and sorted(ad._free_slots) == list(range(8))


# ------------------------------------------------- signatures and the metric
GEOMETRY = {
    # the benchmark's two engines at tiny widths: 8 rows in pow2 buckets,
    # blocks of 16 to 255 positions; 32 rows in one bucket, blocks of 256
    # to 8,320 positions
    "dense": (dict(max_context=255), dict(block_tokens=16, max_blocks=16),
              8, 128, 4, 5),
    "mellum": (dict(max_context=8320, row_buckets="full"),
               dict(block_tokens=256, max_blocks=2), 32, 2048, 1, 7),
}


@pytest.mark.parametrize("which", list(GEOMETRY))
def test_the_compiled_signatures_are_what_they_were(which):
    model_kw, cache_kw, rows, pack, n_rows, n_kvs = GEOMETRY[which]
    model = TransformerDecoder(vocab=16, layers=1, heads=1, head_dim=4,
                               ff=4, **model_kw)
    cache = PagedKVCache(layers=1, heads=1, head_dim=4, **cache_kw)
    ad = TransformerAdapter(model, cache, pack_bucket=pack, max_rows=rows)
    row_buckets, kvs = ad.warm_signatures(rows, model.max_context)
    assert (len(row_buckets), len(kvs)) == (n_rows, n_kvs)
    ad.warmup(rows, model.max_context)
    # the feed's length does not depend on the row bucket: one prefill
    # signature and rows x views step signatures, 21 and 8 in all
    assert telemetry.jit_cache_size(model._prefill_fn) == 1
    assert telemetry.jit_cache_size(model._step_fn) == n_rows * n_kvs
    assert 1 + n_rows * n_kvs == {"dense": 21, "mellum": 8}[which]
    assert ad.prefill_group([(1, np.arange(5, dtype=np.int32))]) == ({}, {})
    first, fails = ad.step([1])
    out, late = ad.collect()
    assert not fails and not late and list(first) == list(out) == [1]
    assert telemetry.jit_cache_size(model._prefill_fn) == 1
    assert telemetry.jit_cache_size(model._step_fn) == n_rows * n_kvs
    assert ad._feed.shape == (2 * rows + 1,) and ad._feed.dtype == jnp.int32


def test_the_run_ahead_share_is_a_data_file_over_two_counters():
    spec = manifest.data_file("layer_metrics",
                              "decode.steps_overlapped_share")
    read = manifest.resolve(spec["reader"])
    entry = [m for m in manifest.Manifest().doc["per_layer"]
             if m["name"] == "decode.steps_overlapped_share"]
    assert len(entry) == 1 and entry[0]["moves"] == "generate_tokens_per_s"
    probe = lambda **c: {"probe": type("P", (), {"counters": c})()}
    names = spec["args"]
    assert read(probe(**{names["numerator"]: 30.0,
                         names["denominator"]: 31.0}), **names) == \
        pytest.approx(100.0 * 30 / 31)
    # the parent of this PR has no such counter: nothing to read, no raise
    assert read(probe(**{names["denominator"]: 31.0}), **names) is None


# ---------------------------------------------------------------------------
# The pool's breaker hears of a launch where its outcome is known: at the
# commit, a launch late (the gateway's guarantee, docs/serving.md)
# ---------------------------------------------------------------------------
class _Held:
    """A device output whose copy back waits for `gate` and then raises
    `error`, or gives `value` if there is none."""

    def __init__(self, value, gate, error):
        self.value, self.gate, self.error = value, gate, error
        self.nbytes = value.nbytes

    def __array__(self, *args, **kwargs):
        assert self.gate.wait(timeout=60)
        if self.error is not None:
            raise self.error
        return np.asarray(self.value)


def _sicken(adapter, how):
    """Every chunk and every step of the model from now on comes back
    non-finite, or its fetch waits for the gate returned and then raises
    (`lost`) or serves (`slow`)."""
    gate = threading.Event()
    for name in ("prefill", "step"):
        real = getattr(adapter.model, name)

        def sick(*args, _real=real):
            picked, finite, *rest = _real(*args)
            if how == "non-finite":
                return (picked, jnp.zeros_like(finite), *rest)
            return (_Held(picked, gate, RuntimeError("the link is lost")
                          if how == "lost" else None), finite, *rest)
        setattr(adapter.model, name, sick)
    return gate


def _pooled(name, breaker):
    """The tiny decoder as the gateway holds it: behind
    ``ModelPool.add_decode``, the engine's hooks feeding `breaker`."""
    pool = ModelPool()
    model = TransformerDecoder(vocab=48, layers=2, heads=2, head_dim=8,
                               ff=16, max_context=CONTEXT, seed=4)
    entry = pool.add_decode(name, model, max_decode_batch=3,
                            pack_bucket=PACK, kv_block_tokens=BT,
                            kv_max_blocks=96, max_context=CONTEXT,
                            breaker=breaker)
    return pool, entry.engine


@pytest.mark.parametrize("how", ["non-finite", "lost"])
def test_a_model_whose_every_commit_fails_opens_the_breaker(how):
    """One rider at a time, so every failure is learned with the next
    launch already made: no launch counts as a success, the run of
    failures builds, and nothing is reported served."""
    br = CircuitBreaker("sick-" + how, failure_threshold=3,
                        reset_timeout_s=3600.0)
    pool, eng = _pooled("sick-" + how, br)
    try:
        eng.generate([1, 2, 3], max_new_tokens=4)       # healthy, compiled
        assert br.state == CLOSED and br.consecutive_failures == 0
        served = []
        hook = eng.on_batch
        eng.on_batch = lambda reqs, *a: (served.append(len(reqs)),
                                         hook(reqs, *a))
        with eng.paused():
            gate = _sicken(eng.adapter, how)
        gate.set()
        replies = [_reply(eng, ([5, 6, 7, 8], 6)) for _ in range(3)]
        assert all(isinstance(r, NonFiniteOutputError if how == "non-finite"
                              else DecodeStepError) for r in replies)
        assert br.state == OPEN and not served
        # a non-finite commit trips at once; the others build the run
        assert br.consecutive_failures >= (1 if how == "non-finite" else 3)
        ad = eng.adapter
    finally:
        pool.shutdown()
    assert ad.cache.blocks_in_use() == 0 and not ad.in_flight() \
        and not ad._slot_of


@pytest.mark.parametrize("how", ["slow", "lost"])
def test_a_half_open_probe_is_judged_by_its_token_not_by_its_launch(how):
    """The probe's chunk and its first step are launched and nothing is
    fetched yet: the breaker still waits. The fetch then decides."""
    now = [0.0]
    br = CircuitBreaker("probe-" + how, failure_threshold=1,
                        reset_timeout_s=10.0, clock=lambda: now[0])
    pool, eng = _pooled("probe-" + how, br)
    closed = registry().counter("serving_breaker_transitions_total").labels(
        model="probe-" + how, to=CLOSED)
    try:
        eng.generate([1, 2, 3], max_new_tokens=4)       # compiled
        br.record_failure()
        now[0] = 11.0
        assert br.state == OPEN and br.allow() and br.state == HALF_OPEN
        before, steps = closed.value(), eng.adapter._launches
        with eng.paused():
            gate = _sicken(eng.adapter, how)
        replies = []
        probe = threading.Thread(
            target=lambda: replies.append(_reply(eng, ([5, 6, 7, 8], 6))))
        probe.start()
        limit = time.monotonic() + 60
        while eng.adapter._launches < steps + 2 and time.monotonic() < limit:
            time.sleep(0.002)
        # its chunk and its first step are up; the chunk's fetch waits
        assert eng.adapter._launches == steps + 2 and eng.adapter.in_flight()
        time.sleep(0.05)
        assert br.state == HALF_OPEN and closed.value() == before
        gate.set()
        probe.join(timeout=60)
        assert not probe.is_alive()
        if how == "slow":
            assert replies[0] == naive_generate(eng.adapter.model,
                                                [5, 6, 7, 8], 6,
                                                pad_to=CONTEXT)
            assert br.state == CLOSED and closed.value() == before + 1
        else:
            assert isinstance(replies[0], DecodeStepError)
            assert br.state == OPEN and closed.value() == before
    finally:
        pool.shutdown()
