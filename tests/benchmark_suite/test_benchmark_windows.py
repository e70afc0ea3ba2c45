"""The windows' arithmetic with the program faked out: a rate is all the
work over all the time, a tail is the tail of all requests, and a window
with a stall lowers every end-to-end number."""
import time
import types

import pytest

from benchmark.kinds import _generate, fit_ring, generate_open


class _Probe:
    trace = False

    def open(self):
        pass

    def close(self):
        pass


def _fit_kind(step_s, stall_at=None, stall_s=0.0):
    ctx = types.SimpleNamespace(traffic={"rows": 4, "ring": 2}, cfg={},
                                seed=1, chips=1)
    kind = fit_ring.Kind(ctx)
    net = types.SimpleNamespace(listeners=[], score_value=1.0)

    def fit(it):
        for i, _ in enumerate(it):
            time.sleep(step_s + (stall_s if i == stall_at else 0.0))
            for lst in net.listeners:
                lst.iteration_done(net, i)

    kind.net, kind.fit, kind.sets = net, fit, [object(), object()]
    return kind


def test_fit_window_counts_all_steps_over_all_time():
    win = _fit_kind(0.01).run(0.3, _Probe())
    assert win["samples"] == win["steps"] * 4
    assert win["metrics"]["train_samples_per_s"] == pytest.approx(
        win["samples"] / (win["t1"] - win["t0"]))
    assert win["t1"] - win["t0"] >= 0.3
    assert win["attempted"] == win["steps"] > 10 and win["failed"] == 0


def test_a_stall_lowers_the_training_rate():
    steady = _fit_kind(0.01).run(0.4, _Probe())
    stalled = _fit_kind(0.01, stall_at=3, stall_s=0.2).run(0.4, _Probe())
    assert stalled["metrics"]["train_samples_per_s"] < \
        0.8 * steady["metrics"]["train_samples_per_s"]


def test_ring_iterator_cycles_counts_and_stops_at_the_deadline():
    it = fit_ring.RingIterator(["a", "b", "c"], count=5, start=1)
    assert list(it) == ["b", "c", "a", "b", "c"]
    it = fit_ring.RingIterator(["a"], deadline=time.perf_counter() - 1)
    assert list(it) == []


def test_ring_batches_come_from_the_seed_and_all_differ():
    a = fit_ring.make_ring(2 ** 31 + 5, 3, 4, (2, 2, 1), 5)
    b = fit_ring.make_ring(2 ** 31 + 5, 3, 4, (2, 2, 1), 5)
    c = fit_ring.make_ring(7, 3, 4, (2, 2, 1), 5)
    assert all((x[0] == y[0]).all() and (x[1] == y[1]).all()
               for x, y in zip(a, b))
    assert not (a[0][0] == c[0][0]).all()
    assert not (a[0][0] == a[1][0]).all()
    assert a[0][0].dtype == "float32" and a[0][1].sum(1).tolist() == [1] * 4


def _gen_kind(records, prompts=8):
    ctx = types.SimpleNamespace(traffic={}, cfg={}, seed=1, chips=1)
    kind = _generate.GenerateKind(ctx)
    kind.requests = [{"prompt": [0] * prompts}] * 128
    kind.records = records
    return kind


def _rec(idx, due, done, ok=True, n=32):
    return dict(idx=idx, t_due=due, t_send=due, t_done=done, ok=ok,
                tokens=list(range(n)) if ok else [], trace=None)


def test_generate_window_rate_and_tail_cover_all_requests():
    recs = [_rec(i, 10.0 + i * 0.5, 10.0 + i * 0.5 + 1.0) for i in range(20)]
    recs.append(_rec(20, 9.0, 11.0))          # sent in the ramp, done inside
    recs.append(_rec(21, 19.0, 24.0))         # sent inside, done after
    win = _gen_kind(recs).reduce(10.0, 20.0, {})
    # each request's tokens lie evenly over its time in flight: 19 whole
    # ones, half of the last of the twenty (19.5-20.5), half of the ramp's
    # (9-11), a fifth of the late one (19-24)
    assert win["tokens"] == pytest.approx((19 + 0.5 + 0.5 + 0.2) * 32)
    assert win["metrics"]["generate_tokens_per_s"] == pytest.approx(
        20.2 * 32 / 10.0)
    assert win["prompt_tokens"] == pytest.approx(20.2 * 8)
    assert win["completed"] == 19             # whole replies inside
    # the tail is over every request due in the window, the late one too
    assert win["attempted"] == 21 and win["failed"] == 0
    assert max(win["latencies_ms"]) == pytest.approx(5000.0)
    assert win["metrics"]["request_p95_ms"] == pytest.approx(1000.0)


def test_lock_stepped_waves_read_the_same_at_any_phase():
    """Eight rows that finish together every 11 s: the rate must not jump
    with where the window's edges fall between two waves."""
    def waves(phase):
        recs, i = [], 0
        for w in range(-2, 8):
            for _ in range(8):
                start = phase + 11.0 * w
                recs.append(_rec(i % 128, start, start + 22.0))
                i += 1
        return recs
    rates = [_gen_kind(waves(ph)).reduce(10.0, 61.0, {})["metrics"][
        "generate_tokens_per_s"] for ph in (0.0, 2.5, 5.0, 8.0, 10.9)]
    assert max(rates) - min(rates) < 1e-9
    assert rates[0] == pytest.approx(16 * 32 / 22.0)


def test_a_window_that_opens_inside_the_first_wave_reads_too_high():
    """16 callers on 8 rows from a cold start: the first eight replies wait
    for no row and are in flight half as long as every later one, so their
    tokens lie twice as dense. A window that opens while they are in
    flight reads above what the rows make (8 x 32 tokens every 11 s); one
    that opens once they are done reads just that. Hence the mix's ramp."""
    recs, i = [], 0
    for c in range(8):                         # first wave: no queueing
        recs.append(_rec(i, 0.0, 11.0)); i += 1
    for w in range(8):                         # every later wave queues 11 s
        for c in range(8):
            start = 0.0 if w == 0 else 11.0 * w
            recs.append(_rec(i % 128, start, 11.0 * w + 22.0)); i += 1
    made = 8 * 32 / 11.0
    rate = lambda t0: _gen_kind(recs).reduce(t0, t0 + 51.0, {})["metrics"][
        "generate_tokens_per_s"]
    assert rate(3.0) > 1.05 * made
    assert rate(14.0) == pytest.approx(made)


def test_a_failed_request_counts_as_the_worst():
    recs = [_rec(i, 10.0 + i, 10.5 + i) for i in range(9)]
    recs.append(_rec(9, 19.0, 19.1, ok=False))
    win = _gen_kind(recs).reduce(10.0, 20.0, {})
    assert win["failed"] == 1 and win["attempted"] == 10
    assert max(win["latencies_ms"]) == pytest.approx(10000.0)
    assert win["metrics"]["request_p95_ms"] == pytest.approx(10000.0)


def test_a_stall_lowers_every_serving_number():
    steady = [_rec(i, 10.0 + i * 0.1, 10.5 + i * 0.1) for i in range(90)]
    # the same arrivals; the server stalls for 3 s at t = 14
    stalled = []
    for r in steady:
        done = r["t_done"] + (3.0 if 14.0 <= r["t_done"] < 17.0 else 0.0)
        stalled.append(_rec(r["idx"], r["t_due"], done))
    a = _gen_kind(steady).reduce(10.0, 19.5, {})["metrics"]
    b = _gen_kind(stalled).reduce(10.0, 19.5, {})["metrics"]
    assert b["request_p95_ms"] > 2 * a["request_p95_ms"]
    assert b["generate_tokens_per_s"] <= a["generate_tokens_per_s"]
    late = [_rec(r["idx"], r["t_due"], r["t_done"] + 3.0) for r in steady]
    c = _gen_kind(late).reduce(10.0, 19.5, {})["metrics"]
    assert c["generate_tokens_per_s"] < a["generate_tokens_per_s"]


def test_requests_have_the_same_sizes_under_every_seed():
    t = {"pool": 64, "max_new_tokens": 4,
         "prompt_len": {"median": 40, "sigma": 0.45, "min": 16, "max": 96}}
    a = _generate.draw_requests(1, t, 1000)
    b = _generate.draw_requests(2 ** 31 + 9, t, 1000)
    la, lb = [len(r["prompt"]) for r in a], [len(r["prompt"]) for r in b]
    assert sorted(la) == sorted(lb) and la != lb
    assert 16 <= min(la) and max(la) <= 96
    assert a == _generate.draw_requests(1, t, 1000)
    assert all(0 <= tok < 1000 for r in a for tok in r["prompt"])


def test_a_mix_that_fixes_its_order_sends_the_same_sizes_under_every_seed():
    t = {"pool": 40, "grid": 8, "order_seed": 3, "max_new_tokens": 4,
         "prompt_len": {"median": 40, "sigma": 0.45, "min": 16, "max": 96}}
    a = _generate.draw_requests(1, t, 1000)
    b = _generate.draw_requests(2 ** 31 + 9, t, 1000)
    la, lb = [len(r["prompt"]) for r in a], [len(r["prompt"]) for r in b]
    assert la == lb and len(la) == 40
    assert [r["prompt"] for r in a] != [r["prompt"] for r in b]
    # every block of `grid` requests holds the whole grid, in its own order
    blocks = [la[i:i + 8] for i in range(0, 40, 8)]
    assert all(sorted(blk) == sorted(blocks[0]) for blk in blocks)
    assert len(set(map(tuple, blocks))) > 1
    # without `order_seed` the seed draws the order too
    del t["order_seed"]
    lc = [len(r["prompt"]) for r in _generate.draw_requests(1, t, 1000)]
    ld = [len(r["prompt"]) for r in _generate.draw_requests(2, t, 1000)]
    assert sorted(lc) == sorted(ld) == sorted(la) and lc != ld


def test_open_loop_schedule_is_seeded_and_bursty():
    t = {"rate": 10.0, "burst": {"every_s": 1.0, "size": 4}}
    a = generate_open.schedule(5, t, 5.0)
    assert a == generate_open.schedule(5, t, 5.0) and a == sorted(a)
    assert a != generate_open.schedule(6, t, 5.0)
    assert sum(1 for d in a if d == 2.0) == 4
    steady = generate_open.schedule(5, {"rate": 10.0}, 5.0)
    assert 25 <= len(steady) <= 80 and len(a) == len(steady) + 16
