"""The reduction from a profiler trace to busy time, idle gaps, top
operations and per-module busy time, on a small checked-in trace
(`data/small_trace.textproto`: two devices; times in the comments there).
The window and the program's spans are laid beside it from the host's own
clock, as `probes.Probe` does in a traced run."""
import os

import pytest

from benchmark import trace_reduce

HERE = os.path.dirname(os.path.abspath(__file__))


@pytest.fixture(scope="module")
def trace(tmp_path_factory):
    from jax.profiler import ProfileData
    with open(os.path.join(HERE, "data", "small_trace.textproto")) as f:
        blob = ProfileData.text_proto_to_serialized_xspace(f.read())
    d = tmp_path_factory.mktemp("trace") / "plugins" / "profile" / "run1"
    d.mkdir(parents=True)
    (d / "host.xplane.pb").write_bytes(blob)
    tr = trace_reduce.load(str(tmp_path_factory.getbasetemp() / "trace0"))
    tr.window = (1e-6, 11e-6)
    tr.host = [("step", 1.5e-6, 6e-6), ("etl", 4.2e-6, 1.7e-6)]
    return tr


def test_window_and_devices(trace):
    assert trace.devices() == [0, 1]
    assert trace.window_s() == pytest.approx(10e-6)


def test_busy_is_the_union_clipped_to_the_window(trace):
    # device 0: [0,3) clipped to [1,3), [2,4) overlaps, [6,8), [10,12)
    # clipped to [10,11): 3 + 2 + 1 = 6 us
    assert trace.busy_s(0) == pytest.approx(6e-6)
    assert trace.busy_s(1) == pytest.approx(2e-6)
    assert trace.mean_busy_s() == pytest.approx(4e-6)
    assert trace.fullest() == 0


def test_top_ops_by_name(trace):
    top = dict(trace.top_ops())
    assert top["convolution.2"] == pytest.approx(4e-6)   # [2,4) + [6,8)
    assert top["fusion.1"] == pytest.approx(3e-6)        # [1,3) + [10,11)
    assert list(top)[0] == "convolution.2"


def test_idle_gaps_go_to_the_innermost_host_span(trace):
    gaps = dict(trace.idle_gaps())
    # gaps of device 0 in the window: [4,6) under etl (inside step),
    # [8,10) under no named span
    assert gaps == pytest.approx({"etl": 2e-6, "(none)": 2e-6})


def test_module_busy_counts_whole_runs_in_the_window(trace):
    # train_step ran [2,4.5) and [6,8.5) inside the window, and [10,12)
    # across its end, which does not count
    busy, runs = trace.module_busy(0, "train_step")
    assert runs == 2
    assert busy == pytest.approx(4e-6)
    assert trace.module_busy(0, "no_such_module") == (0.0, 0)


def test_a_trace_without_device_planes_reads_nothing(tmp_path):
    from jax.profiler import ProfileData
    blob = ProfileData.text_proto_to_serialized_xspace(
        'planes { id: 1 name: "/host:CPU" }')
    p = tmp_path / "x.xplane.pb"
    p.write_bytes(blob)
    tr = trace_reduce.load(str(p))
    assert tr.ops == {} and tr.window is None
