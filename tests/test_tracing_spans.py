"""The program's own spans (PR 25): ids and parents in the ring, the
decode engine's `decode/*` spans and link-byte counters, the prefetcher's
`etl/*` spans on the producer's thread, compilations as spans, and the
`jax.named_scope` names in the lowered step programs. CPU, tiny sizes."""
import re
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu import (ComputationGraph, DenseLayer,
                                ElementWiseVertex, InputType,
                                MultiLayerNetwork, NeuralNetConfiguration,
                                OutputLayer, Sgd)
from deeplearning4j_tpu.data.dataset import DataSet
from deeplearning4j_tpu.data.iterators import (DataSetIterator,
                                               DevicePrefetchIterator)
from deeplearning4j_tpu.optimize import telemetry, tracing
from deeplearning4j_tpu.optimize.metrics import registry
from deeplearning4j_tpu.serving import flight_recorder
from deeplearning4j_tpu.serving.decode import (DecodeEngine, PagedKVCache,
                                               TransformerAdapter,
                                               TransformerDecoder)

L, H, DH = 2, 2, 8
BT = 8      # the engine's KV block, tokens


@pytest.fixture(autouse=True)
def _clean():
    tracing.disable()
    tracing.clear()
    yield
    flight_recorder.disable()
    tracing.disable()
    tracing.clear()


def _events():
    return tracing.export_trace_events()["traceEvents"]


def _named(events, name):
    return [e for e in events if e["name"] == name]


# ---------------------------------------------------------------- the record
def test_ids_and_parents_nest_across_span_add_span_and_threads():
    tracing.enable(fence_every=0)
    other = {}

    def worker():
        with tracing.span("w-outer") as sp:
            other["outer"] = sp.id
            other["retro"] = tracing.add_span("w-retro", time.perf_counter(),
                                              0.0)

    with tracing.span("outer", k=1) as outer:
        with tracing.span("inner") as inner:
            retro = tracing.add_span("retro", time.perf_counter(), 0.001)
            child = tracing.add_span("retro-child", time.perf_counter(),
                                     0.0, parent=retro)
            t = threading.Thread(target=worker)
            t.start()
            t.join(timeout=10)
            assert not t.is_alive()
        tracing.add_spans([("bulk-a", 0.0, 0.0), ("bulk-b", 0.0, 0.0)],
                          cat="serve", rid=7)
    after = tracing.add_span("after", time.perf_counter(), 0.0)
    by = {e["name"]: e["args"] for e in _events()}
    assert by["outer"] == {"k": 1, "span_id": outer.id, "parent_id": 0}
    assert by["inner"]["parent_id"] == outer.id
    assert by["retro"] == {"span_id": retro, "parent_id": inner.id}
    assert by["retro-child"] == {"span_id": child, "parent_id": retro}
    # another thread's stack is its own: no parent from this thread
    assert by["w-outer"] == {"span_id": other["outer"], "parent_id": 0}
    assert by["w-retro"]["parent_id"] == other["outer"]
    assert by["bulk-a"]["parent_id"] == by["bulk-b"]["parent_id"] == outer.id
    assert by["bulk-a"]["rid"] == 7
    assert by["after"] == {"span_id": after, "parent_id": 0}
    ids = [a["span_id"] for a in by.values()]
    assert len(set(ids)) == len(ids) and min(ids) >= 1


def test_a_span_that_ends_takes_what_was_left_open_inside_with_it():
    tracing.enable(fence_every=0)
    fit = tracing.begin("fit")
    tracing.begin("epoch")          # never ended: an exception in the loop
    tracing.begin("step").cancel()  # a cancelled span leaves no event
    fit.end()
    fit.end()                       # a second end records nothing
    with tracing.span("next") as nxt:
        assert nxt.parent == 0
    assert [e["name"] for e in _events()] == ["fit", "next"]
    assert tracing.begin is tracing.span


def test_ids_stay_unique_and_parents_stay_per_thread_under_contention():
    """More threads than cores, a short switch interval: an id handed out
    twice, or a parent taken from another thread's stack, would show."""
    import sys
    tracing.enable(ring_size=1 << 16, fence_every=0)
    n_threads, n_spans = 24, 200

    def worker(k):
        for i in range(n_spans):
            with tracing.span("outer", k=k) as outer:
                with tracing.span("inner", k=k) as inner:
                    assert inner.parent == outer.id
                    assert tracing.add_span("retro", 0.0, 0.0, k=k) > inner.id

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        ts = [threading.Thread(target=worker, args=(k,))
              for k in range(n_threads)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(timeout=60)
        assert not any(t.is_alive() for t in ts)
    finally:
        sys.setswitchinterval(old)
    ev = _events()
    assert len(ev) == n_threads * n_spans * 3
    by_id = {e["args"]["span_id"]: e for e in ev}
    assert len(by_id) == len(ev)                # no id handed out twice
    for e in ev:
        if e["name"] != "outer":                # a parent of the same worker
            assert by_id[e["args"]["parent_id"]]["args"]["k"] == e["args"]["k"]


def test_annotate_reaches_the_innermost_open_span_only():
    tracing.annotate(lost=1)        # off: nothing, no error
    tracing.enable(fence_every=0)
    tracing.annotate(lost=1)        # no span open: nothing
    with tracing.span("a"):
        with tracing.span("b", x=0):
            tracing.annotate(x=1, y=2)
    by = {e["name"]: e["args"] for e in _events()}
    assert (by["b"]["x"], by["b"]["y"]) == (1, 2)
    assert set(by["a"]) == {"span_id", "parent_id"}


def test_the_export_carries_one_reading_of_both_clocks():
    before = (time.perf_counter(), time.time_ns())
    tracing.enable(fence_every=0)
    after = (time.perf_counter(), time.time_ns())
    clock = tracing.export_trace_events()["clock"]
    assert before[0] <= clock["perf_counter_s"] <= after[0]
    assert before[1] <= clock["unix_ns"] <= after[1]


def test_off_costs_the_shared_null_span_and_records_nothing():
    assert tracing.span("x", a=1) is tracing.span("y")
    assert tracing.add_span("x", 0.0, 1.0) == 0
    tracing.add_spans([("x", 0.0, 1.0)])
    assert _events() == []


# ---------------------------------------------------------------- the engine
def _step_bytes(row_bucket, kv_bucket):
    """What one transformer step hands to the device: four int32 rows
    (tokens, positions, the block table's `kv_bucket / BT` entries a row,
    lengths). The K/V stay in the device arena."""
    return row_bucket * (3 + kv_bucket // BT) * 4


def _counter(name, phase):
    return registry().counter(name).labels(phase=phase).value()


def _engine(seed=3):
    model = TransformerDecoder(vocab=61, layers=L, heads=H, head_dim=DH,
                               ff=24, max_context=64, seed=seed)
    cache = PagedKVCache(layers=L, heads=H, head_dim=DH, block_tokens=BT,
                         max_blocks=32)
    return DecodeEngine(TransformerAdapter(model, cache, pack_bucket=32),
                        name="spans", max_decode_batch=2)


def _serve(traced: bool):
    """Two requests one after the other (what repeats exactly), then two
    at once with flight-recorder timelines. Returns the tokens, the rise
    of the step-phase h2d counter over the first part and over all of it,
    the pair's flight-recorder ids, and the events."""
    if traced:
        tracing.enable(ring_size=1 << 14, fence_every=0)
        flight_recorder.enable()
    telemetry.compilation_count()           # the listener is attached
    h0 = _counter("serving_decode_h2d_bytes_total", "step")
    d0 = _counter("serving_decode_d2h_bytes_total", "step")
    ahead = registry().counter(
        "serving_decode_steps_overlapped_total").labels(model="spans")
    o0 = ahead.value()
    with _engine() as eng:
        tokens = [eng.generate([5, 9, 2, 40, 7], max_new_tokens=6),
                  eng.generate(list(range(1, 12)), max_new_tokens=8)]
        h1 = _counter("serving_decode_h2d_bytes_total", "step")
        # (timelines no request takes: the recorder's ids then differ
        # from the engine's own 3 and 4 for the pair)
        traces = [flight_recorder.new_trace("spans") for _ in range(12)][-2:]
        out = [None, None]

        def ask(i, prompt):
            out[i] = eng.generate(prompt, max_new_tokens=5, trace=traces[i])
            flight_recorder.complete(traces[i], "ok", 1.0)  # the gateway's

        with eng.paused():                  # both queue before either runs
            ts = [threading.Thread(target=ask, args=(i, p)) for i, p in
                  enumerate(([3, 1, 4, 1, 5, 9], [2, 7, 1, 8]))]
            for t in ts:
                t.start()
            deadline = time.monotonic() + 5.0
            while eng.queue_depth() + eng.active_count() < 2 \
                    and time.monotonic() < deadline:
                time.sleep(0.005)
        for t in ts:
            t.join(timeout=120)
        assert not any(t.is_alive() for t in ts)
    h2 = _counter("serving_decode_h2d_bytes_total", "step")
    d2 = _counter("serving_decode_d2h_bytes_total", "step")
    events = _events()
    flight_recorder.disable()
    tracing.disable()
    tracing.clear()
    return dict(tokens=tokens + out, first=h1 - h0, whole=h2 - h0,
                down=d2 - d0, overlapped=ahead.value() - o0, events=events,
                rids=[t.rid for t in traces if t is not None])


@pytest.fixture(scope="module")
def served():
    tracing.disable()
    tracing.clear()
    return _serve(True), _serve(True), _serve(False)


def test_a_step_span_launches_its_step_and_fetches_the_work_before(served):
    """Each `decode/step` gathers and launches its own step and then
    fetches and commits what was launched before it (a step, or the
    chunk that admitted its rows), while its own runs; a step nothing
    was launched after is fetched alone. Every step and chunk is fetched
    and committed once, under its `seq`."""
    ev = served[0]["events"]
    steps = _named(ev, "decode/step")
    assert [s["args"]["step"] for s in steps] == \
        list(range(1, len(steps) + 1))
    # 5 + 7 steps alone (the first token comes from the prefill), then
    # the pair: 4 steps if they ran together, up to 8 if not
    assert 12 + 4 <= len(steps) <= 12 + 8
    rows_of, overlapped = {}, 0
    for s in steps:
        kids = sorted((e for e in ev
                       if e["args"]["parent_id"] == s["args"]["span_id"]
                       and e["name"].startswith("decode/")),
                      key=lambda e: e["ts"])
        assert [k["name"] for k in kids] in (
            ["decode/gather", "decode/launch"],
            ["decode/gather", "decode/launch", "decode/fetch",
             "decode/commit"])
        edge = s["ts"]
        for k in kids:
            assert k["ts"] >= edge - 1.0 and k["cat"] == "serve"
            edge = k["ts"] + k["dur"]
        assert edge <= s["ts"] + s["dur"] + 1.0
        a = s["args"]
        assert a["rows"] == len(a["rids"]) <= a["row_bucket"]
        # gather: the table and the lengths; launch: those, the row
        # slots and the positions
        assert kids[0]["args"]["bytes"] == _step_bytes(
            a["row_bucket"], a["kv_bucket"]) - 2 * a["row_bucket"] * 4
        assert kids[1]["args"]["bytes"] == _step_bytes(a["row_bucket"],
                                                       a["kv_bucket"])
        seq = kids[1]["args"]["seq"]
        rows_of[seq] = a["row_bucket"]
        if len(kids) == 4:      # the work before, begun before this launch
            assert kids[2]["args"].get("seq", seq - 1) == \
                kids[3]["args"].get("seq", seq - 1) == seq - 1
            assert kids[1]["ts"] < kids[2]["ts"] + kids[2]["dur"]
            overlapped += 1
    # steps and chunks are numbered by one count, in launch order; a
    # chunk's launch carries the pairs it computes
    launches = {e["args"]["seq"]: e for e in _named(ev, "decode/launch")}
    assert sorted(launches) == list(range(1, len(launches) + 1))
    chunks = set(launches) - set(rows_of)
    assert len(chunks) == len(_named(ev, "decode/prefill"))
    assert all(launches[c]["args"]["pairs"] for c in chunks)
    by_seq = {f["args"]["seq"]: f for f in _named(ev, "decode/fetch")}
    assert sorted(by_seq) == sorted(launches)        # once each
    assert sorted(c["args"]["seq"] for c in _named(ev, "decode/commit")) \
        == sorted(launches)
    for seq, f in by_seq.items():
        # fetch: an int32 token and a flag a row of the step it fetches
        assert seq in chunks or f["args"]["bytes"] == rows_of[seq] * 5
        assert f["ts"] >= launches[seq]["ts"] + launches[seq]["dur"] - 1.0
    # a request alone runs ahead from its first step (launched before its
    # chunk is fetched) to its last
    assert overlapped >= 5 + 7 + 4
    assert served[0]["overlapped"] == overlapped


def test_rids_are_the_flight_recorders_where_it_is_on(served):
    run = served[0]
    steps = _named(run["events"], "decode/step")
    alone = [s for s in steps[:12]]
    # with no timeline on the request, the engine's own numbering
    assert {tuple(s["args"]["rids"]) for s in alone} == {(1,), (2,)}
    pair = [s for s in steps[12:]]
    assert {r for s in pair for r in s["args"]["rids"]} == set(run["rids"])
    assert not set(run["rids"]) & {3, 4}
    assert any(len(s["args"]["rids"]) == 2 for s in pair)
    # the spans of one request share one identifier
    served_rids = {e["args"]["rid"] for e in run["events"]
                   if e["name"] == "serve/queue_wait"}
    assert served_rids == set(run["rids"])
    pre = _named(run["events"], "decode/prefill")
    assert sum(p["args"]["rows"] for p in pre) == 4
    assert sorted(p["args"]["tokens"] for p in pre[:2]) == [5, 11]
    for p in pre:
        kids = [e["name"] for e in sorted(run["events"],
                                          key=lambda e: e["ts"])
                if e["args"]["parent_id"] == p["args"]["span_id"]
                and e["name"].startswith("decode/")]
        # its own launch, then the fetch and commit of the step that was
        # in flight when it was launched, if one was
        assert kids in (["decode/launch"],
                        ["decode/launch", "decode/fetch", "decode/commit"])
    admits = _named(run["events"], "decode/admit")
    assert sum(a["args"]["admitted"] for a in admits) == 4


def test_a_compilation_is_a_span_inside_the_step_that_caused_it(served):
    ev = served[0]["events"]
    by_id = {e["args"]["span_id"]: e for e in ev}
    comp = _named(ev, "compile")
    assert comp and all(c["cat"] == "compile" for c in comp)

    def ancestors(e):
        names = []
        while e["args"]["parent_id"]:
            e = by_id[e["args"]["parent_id"]]
            names.append(e["name"])
        return names

    chains = [ancestors(c) for c in comp]
    # the engine was not warmed: the first step's launch compiled it
    assert ["decode/launch", "decode/step"] in chains
    first = next(c for c, ch in zip(comp, chains)
                 if ch == ["decode/launch", "decode/step"])
    step = by_id[by_id[first["args"]["parent_id"]]["args"]["parent_id"]]
    assert step["args"]["step"] == 1
    assert any(ch[:2] == ["decode/launch", "decode/prefill"]
               for ch in chains)


def test_h2d_bytes_are_the_formula_over_the_steps_own_buckets(served):
    a, b, off = served
    for run in (a, b):
        steps = _named(run["events"], "decode/step")
        assert run["whole"] == sum(
            _step_bytes(s["args"]["row_bucket"], s["args"]["kv_bucket"])
            for s in steps)
        assert run["first"] == sum(
            _step_bytes(s["args"]["row_bucket"], s["args"]["kv_bucket"])
            for s in steps[:12])
    # the count repeats exactly from run to run, traced or not
    assert a["first"] == b["first"] == off["first"] > 0
    assert off["whole"] > off["first"] and off["down"] > 0


def test_with_tracing_off_the_ring_stays_empty_and_the_tokens_agree(served):
    a, b, off = served
    assert off["events"] == [] and off["rids"] == []
    assert a["tokens"] == b["tokens"] == off["tokens"]
    assert [len(t) for t in off["tokens"]] == [6, 8, 5, 5]


# ------------------------------------------------------------ the prefetcher
class _Slow(DataSetIterator):
    """A list of batches, each `delay` seconds in the making."""

    def __init__(self, sets, delay):
        self._sets, self._delay, self._i = sets, delay, 0

    def reset(self):
        self._i = 0

    def __next__(self):
        if self._i >= len(self._sets):
            raise StopIteration
        time.sleep(self._delay)
        self._i += 1
        return self._sets[self._i - 1]


def _prefetch(base_delay, consumer_delay, n=6):
    rng = np.random.default_rng(0)
    sets = [DataSet(rng.standard_normal((4, 5)).astype(np.float32),
                    np.eye(3, dtype=np.float32)[rng.integers(0, 3, 4)])
            for _ in range(n)]
    tracing.enable(fence_every=0)
    b0 = registry().counter("etl_h2d_bytes_total").value()
    it = DevicePrefetchIterator(_Slow(sets, base_delay), depth=1)
    got = 0
    for ds in it:
        assert isinstance(ds.features, jax.Array)
        time.sleep(consumer_delay)
        got += 1
    it.shutdown()
    assert got == n
    ev = _events()
    total = {name: sum(e["dur"] for e in _named(ev, name)) * 1e-6
             for name in ("etl/produce", "etl/stage", "etl/handoff")}
    moved = registry().counter("etl_h2d_bytes_total").value() - b0
    return ev, total, moved


def test_a_slow_consumer_shows_as_handoff_on_the_producers_thread():
    ev, total, moved = _prefetch(0.0, 0.04)
    assert total["etl/handoff"] > 3 * (total["etl/produce"]
                                       + total["etl/stage"])
    assert total["etl/handoff"] > 0.1           # 4+ waits of ~40 ms
    me = threading.get_ident()
    for name in ("etl/produce", "etl/stage", "etl/handoff"):
        spans = _named(ev, name)
        assert len(spans) == 6 and all(e["cat"] == "train" for e in spans)
        assert {e["tid"] for e in spans} != {me}
        assert len({e["tid"] for e in spans}) == 1
    assert moved == 6 * (4 * 5 + 4 * 3) * 4
    for st in _named(ev, "etl/stage"):
        assert st["args"]["bytes"] == (4 * 5 + 4 * 3) * 4
        assert st["args"]["rows"] == 4
        kids = sorted((e for e in ev
                       if e["args"]["parent_id"] == st["args"]["span_id"]),
                      key=lambda e: e["ts"])
        assert [k["name"] for k in kids] == ["etl/stage/put",
                                             "etl/stage/fence"]
        assert abs(kids[0]["dur"] + kids[1]["dur"] - st["dur"]) < 1.0


def test_a_slow_base_iterator_leaves_handoff_with_none_of_the_time():
    _, total, _ = _prefetch(0.04, 0.0)
    assert total["etl/produce"] > 0.2           # six pulls of 40 ms
    assert total["etl/handoff"] < 0.2 * total["etl/produce"]


# ---------------------------------------------------- names on the device work
def _lowered_text(fn, *args):
    return jax.jit(fn).lower(*args).as_text(debug_info=True)


def _scoped(text, name):
    """Is `name` a whole scope of some operation's name stack? Under
    `value_and_grad` a scope reads `jvp(name)` or `transpose(jvp(name))`."""
    return re.search(r"[/(]" + re.escape(name) + r"[/)]", text) is not None


def test_a_graphs_train_step_carries_every_vertex_loss_and_updater():
    conf = (NeuralNetConfiguration.builder().seed(5).updater(Sgd(0.1))
            .graph_builder().add_inputs("in")
            .add_layer("trunk_a", DenseLayer(n_out=8, activation="tanh"),
                       "in")
            .add_layer("trunk_b", DenseLayer(n_out=8, activation="relu"),
                       "trunk_a")
            .add_vertex("skip_add", ElementWiseVertex(op="add"), "trunk_a",
                        "trunk_b")
            .add_layer("scores", OutputLayer(n_out=3, activation="softmax",
                                             loss="mcxent"), "skip_add")
            .set_outputs("scores")
            .set_input_types(InputType.feed_forward(8)).build())
    g = ComputationGraph(conf).init()
    x = jnp.ones((4, 8), jnp.float32)
    y = jnp.asarray(np.eye(3, dtype=np.float32)[[0, 1, 2, 0]])
    text = _lowered_text(
        g._train_step_raw, g.params_tree, g.opt_state, g._merged_state(),
        jnp.asarray(0, jnp.int32), g._rng, {"in": x}, {"scores": y}, {}, {})
    for name in ("trunk_a", "trunk_b", "skip_add", "scores", "loss",
                 "updater"):
        assert _scoped(text, name), name
    assert not _scoped(text, "in")          # an input is no vertex
    # the backward pass keeps the vertex's name inside jax's own wrapper
    assert "transpose(jvp(trunk_b))" in text


def test_a_multilayer_train_step_names_each_layer_by_index_and_type():
    conf = (NeuralNetConfiguration.builder().seed(1).updater(Sgd(0.1))
            .list().layer(DenseLayer(n_out=6, activation="tanh"))
            .layer(OutputLayer(n_out=3, activation="softmax", loss="mcxent"))
            .set_input_type(InputType.feed_forward(5)).build())
    net = MultiLayerNetwork(conf).init()
    x = jnp.ones((4, 5), jnp.float32)
    y = jnp.asarray(np.eye(3, dtype=np.float32)[[0, 1, 2, 0]])
    text = _lowered_text(
        net._train_step_raw, net.params_tree, net.opt_state, net.state_tree,
        jnp.asarray(0, jnp.int32), net._rng, x, y, None, None)
    for name in ("0_DenseLayer", "loss", "updater"):
        assert _scoped(text, name), name
    out = _lowered_text(
        lambda p, s, a: net._forward_pure(p, s, a, False, None, None)[0],
        net.params_tree, net.state_tree, x)
    assert "/0_DenseLayer/" in out and "/1_OutputLayer/" in out


def test_the_decoders_programs_name_their_parts_and_decode_attention():
    m = TransformerDecoder(vocab=32, layers=L, heads=H, head_dim=DH, ff=16,
                           max_context=32)
    b = 2
    arenas = {"full": (jnp.zeros((L, 5, BT, H * DH), jnp.float32),) * 2}
    z = jnp.zeros((b,), jnp.int32)
    feed = jnp.zeros((b + 1,), jnp.int32)
    step = _lowered_text(m._step_pure, m.params_tree, z, z, arenas,
                         {"full": jnp.zeros((b, 2), jnp.int32)}, {}, z + 1,
                         feed)
    row = jnp.zeros((16,), jnp.int32)
    prefill = _lowered_text(m._prefill_pure, m.params_tree, row, row, row,
                            arenas, {"full": (row, row)},
                            {"full": jnp.zeros((4,), jnp.int32)}, {},
                            jnp.int32(0), row, feed, row)
    for text in (step, prefill):
        for name in ("embed", "layer_0/attn_full", "layer_0/mlp",
                     "layer_1/attn_full", "layer_1/mlp", "head",
                     "kv_scatter"):
            assert f"/{name}/" in text, name
    # a step reads the cache through the table inside its kernel; a chunk
    # attends over itself and, under a loop, gathers a slab of the slices
    # before it a trip and attends over that
    assert "/layer_1/attn_full/decode_attention_full/" in step
    assert "/kv_context/" not in step
    assert "/layer_1/attn_full/prefill_attention_full/" in prefill
    assert "/layer_1/attn_full/while/body/kv_context/" in prefill
    assert "/layer_1/attn_full/while/body/prefill_attention_full/" in prefill
    assert "/decode_attention_full/" not in prefill


def test_a_sparse_windowed_decoder_names_its_layer_kinds_and_experts():
    m = TransformerDecoder(
        vocab=32, layers=4, heads=4, kv_heads=2, head_dim=DH, d_model=12,
        ff=8, max_context=32, norm="rms", position="rotary", mlp="moe",
        experts=4, experts_per_token=2, tied=False, window=4,
        layer_types=("sliding", "full"),
        rope={k: {"rope_theta": 10000.0} for k in ("full", "sliding")})
    b = 2
    arena = (jnp.zeros((2, 5, BT, 2 * DH), jnp.float32),) * 2
    z = jnp.zeros((b,), jnp.int32)
    tables = jnp.zeros((b, 2), jnp.int32)
    step = _lowered_text(m._step_pure, m.params_tree, z, z,
                         {"full": arena, "sliding": arena},
                         {"full": tables, "sliding": tables},
                         {"sliding": z}, z + 1, jnp.zeros((b + 1,), jnp.int32))
    for name in ("layer_0/attn_sliding/decode_attention_sliding",
                 "layer_1/attn_full/decode_attention_full",
                 "layer_2/attn_sliding", "layer_3/attn_full",
                 "layer_0/moe/route", "layer_0/moe/experts",
                 "layer_3/moe/experts", "head", "kv_scatter"):
        assert f"/{name}/" in step, name
    assert "/mlp/" not in step
