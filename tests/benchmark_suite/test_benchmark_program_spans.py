"""The per-layer metrics that read the program's own spans and counters
(PR 25): the tiny traced cells report each one that needs no device
trace, the counts agree with the shapes, and the reader that lays the
`decode/launch` spans beside the device's module runs returns a planted
latency and brackets a planted clock offset."""
import types

import pytest

from benchmark import trace_reduce
from benchmark.readers import aligned, kernels, phases
from benchmark_drive import drive, tiny_root
from benchmark_tiny import TINY_DECODER


def _run(tmp_path_factory, cell, seed):
    from deeplearning4j_tpu.optimize import tracing
    mp = pytest.MonkeyPatch()
    try:
        with tiny_root(tmp_path_factory.mktemp(cell), mp) as man:
            r = drive(man, cell, seed, 2.0, True)
            return r, tracing.export_trace_events()["traceEvents"]
    finally:
        mp.undo()


@pytest.fixture(scope="module")
def closed(tmp_path_factory):
    return _run(tmp_path_factory, "tiny.closed", 2 ** 31 + 25)


@pytest.fixture(scope="module")
def fit(tmp_path_factory):
    return _run(tmp_path_factory, "tiny.fit", 2 ** 31 + 26)


@pytest.mark.parametrize("metric", [
    "decode.gather_ms", "decode.fetch_ms", "decode.h2d_bytes_per_step",
    "decode.queue_wait_ms"])
def test_the_generate_cell_reports(closed, metric):
    r, _ = closed
    assert r["correct"] is True, r["compared"]
    assert r["metrics"][metric]["value"] > 0
    # no device plane on the CPU: left out, never 0
    assert "decode.launch_to_device_ms" not in r["metrics"]


@pytest.mark.parametrize("metric", ["fit.stage_ms",
                                    "fit.handoff_wait_share"])
def test_the_fit_cell_reports(fit, metric):
    r, events = fit
    assert r["correct"] is True, r["compared"]
    assert r["metrics"][metric]["value"] > 0
    names = {e["name"] for e in events}
    assert {"etl/produce", "etl/stage", "etl/stage/put", "etl/stage/fence",
            "etl/handoff", "etl", "dispatch", "step"} <= names


def test_h2d_bytes_per_step_lies_between_the_buckets_the_run_used(closed):
    r, events = closed
    c = TINY_DECODER
    row = c["hidden_size"] * c["num_hidden_layers"] * 4   # H x Dh x L floats
    steps = [e["args"] for e in events if e["name"] == "decode/step"]
    assert len(steps) > 20
    per = sorted(2 * a["row_bucket"] * a["kv_bucket"] * row
                 + 3 * a["row_bucket"] * 4 for a in steps)
    got = r["metrics"]["decode.h2d_bytes_per_step"]["value"]
    assert per[0] <= got <= per[-1]
    assert per[0] < per[-1]         # the run did use more than one bucket


def test_queue_wait_is_part_of_the_time_to_the_first_token(closed):
    m = closed[0]["metrics"]
    assert 0 < m["decode.queue_wait_ms"]["value"] \
        <= m["gateway.first_token_ms"]["value"]


def test_the_programs_spans_and_the_benchmarks_wrappers_agree(closed):
    """`decode/gather` is the program's span around `cache.batch_view`,
    `kv/batch_view` the benchmark's wrapper on it: the same calls."""
    _, events = closed
    inner = [e["dur"] for e in events if e["name"] == "kv/batch_view"]
    outer = [e["dur"] for e in events if e["name"] == "decode/gather"]
    assert len(inner) == len(outer) > 20
    assert sum(inner) <= sum(outer)
    assert all(e["cat"] == "bench" for e in events
               if e["name"] in ("kv/batch_view", "engine/step", "model/step"))


# ----------------------------------------------------- the aligned reader
def _probe(offset, latency=0.012, n=9, period=0.35, device=0.014,
           t0=1000.0):
    """`n` steps every `period` s: the launch span begins, the module run
    starts `latency` later and takes `device`, the fetch span returns 2 ms
    after it. The trace's zero truly lies `offset` s after `session_t0`."""
    spans, runs = [], []
    for i in range(n):
        start = t0 + 0.5 + i * period           # perf_counter
        spans.append(dict(name="decode/launch", ts=start, dur=0.010))
        run = start + latency
        spans.append(dict(name="decode/fetch", ts=start + 0.010,
                          dur=latency + device + 0.002 - 0.010))
        runs.append(("jit__step_pure(123)", run - t0 - offset, device))
        runs.append(("jit__prefill_pure(7)", run - t0 - offset + 0.1, 0.05))
    tr = trace_reduce.Trace(ops={0: [(nm, s, d) for nm, s, d in runs]},
                            modules={0: runs}, window=(0.0, n * period + 1))
    return types.SimpleNamespace(reduced=tr, spans=spans, session_t0=t0)


ARGS = dict(module="_step_pure", launch="decode/launch",
            fetch="decode/fetch")


@pytest.mark.parametrize("offset", [0.0, 0.004, -0.0015])
def test_aligned_returns_the_planted_latency_and_brackets_the_offset(
        offset, capsys):
    probe = _probe(offset)
    pairs = aligned.align(probe, **ARGS)
    assert len(pairs) == 9                      # the prefill runs are not read
    lo = -min(p[0] for p in pairs)
    hi = min(p[1] for p in pairs)
    assert lo <= offset <= hi
    assert hi - lo == pytest.approx(0.012 + 0.002)  # latency + fetch's tail
    got = aligned.launch_to_device_ms({"probe": probe}, **ARGS)
    # laid by session_t0 the latency reads short by the offset
    assert got == pytest.approx(1e3 * (0.012 - offset))
    assert "info clock: launch->device over 9 runs" in capsys.readouterr().out


def test_aligned_says_so_when_the_clocks_are_a_whole_step_apart(capsys):
    """Laid 50 ms early, each run pairs with the step before its own: a
    latency near the period, and an interval that leaves session_t0 out."""
    got = aligned.launch_to_device_ms({"probe": _probe(0.05)}, **ARGS)
    assert got > 100.0
    assert "NOT at session_t0" in capsys.readouterr().out
    aligned.launch_to_device_ms({"probe": _probe(0.004)}, **ARGS)
    assert "NOT" not in capsys.readouterr().out


@pytest.mark.parametrize("case", ["no trace", "no spans", "no runs"])
def test_aligned_reads_none_never_zero_where_there_is_nothing(case):
    probe = _probe(0.0)
    if case == "no trace":
        probe.reduced = None
    elif case == "no spans":
        probe.spans = [s for s in probe.spans if s["name"] != "decode/launch"]
    else:
        probe.reduced.modules = {0: [m for m in probe.reduced.modules[0]
                                     if "_step_pure" not in m[0]]}
    assert aligned.launch_to_device_ms({"probe": probe}, **ARGS) is None


def test_phase_median_reads_none_without_timelines():
    reading = {"window": {"t0": 0.0, "t1": 10.0}, "records": [
        dict(ok=True, trace=None, t_due=1.0),
        dict(ok=True, t_due=2.0, trace={"phases": [
            {"phase": "admission", "start_ms": 0.0, "ms": 1.0}]})]}
    assert phases.phase_median_ms(reading, phase="queue_wait") is None
    reading["records"].append(dict(ok=True, t_due=3.0, trace={"phases": [
        {"phase": "queue_wait", "start_ms": 1.0, "ms": 4.0},
        {"phase": "queue_wait", "start_ms": 9.0, "ms": 2.0}]}))
    assert phases.phase_median_ms(reading, phase="queue_wait") == 6.0


# ------------------------------------------------------ the kernel's reader
def test_kernel_busy_reads_the_instructions_own_name_inside_its_module():
    call = "%flash_attention_fwd.{} = (f32[2]) custom-call(f32[2] %x)"
    ops = [(call.format(24), 1.000, 0.001),
           # an operand that names the kernel is not the kernel
           ("%gte = f32[2] get-tuple-element(%flash_attention_fwd.24)",
            1.001, 0.002),
           (call.format(25), 1.004, 0.001),
           (call.format(9), 3.0, 0.5)]          # inside the prefill's run
    tr = trace_reduce.Trace(
        ops={0: ops}, window=(0.0, 5.0),
        modules={0: [("jit__step_pure(1)", 0.99, 0.03),
                     ("jit__prefill_pure(2)", 2.9, 1.0)]})
    reading = {"probe": types.SimpleNamespace(reduced=tr)}
    assert kernels.kernel_busy_ms(
        reading, module="_step_pure",
        kernel="flash_attention_fwd") == pytest.approx(2.0)
    # a parent whose kernels carry no name: nothing to read, never 0
    assert kernels.kernel_busy_ms(reading, module="_step_pure",
                                  kernel="lrn_fwd") is None
    assert kernels.kernel_busy_ms(reading, module="train_step",
                                  kernel="flash_attention_fwd") is None
    assert kernels.kernel_busy_ms(
        {"probe": types.SimpleNamespace(reduced=None)},
        module="_step_pure", kernel="flash_attention_fwd") is None
