"""`fit_ring` end to end at tiny size on the CPU through the harness's own
functions: the rest of a run after the look for a chip, traced."""
import pytest

from benchmark_drive import drive, tiny_root


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    mp = pytest.MonkeyPatch()
    try:
        with tiny_root(tmp_path_factory.mktemp("fit"), mp) as man:
            yield man, drive(man, "tiny.fit", 2 ** 31 + 11, 2.0, True)
    finally:
        mp.undo()


def test_the_run_is_correct_and_counts_its_steps(traced):
    _, r = traced
    assert r["correct"] is True, r["compared"]
    assert r["attempted"] > 0 and r["failed"] == 0
    c = r["compared"]
    assert c["compilations_in_window"] == {"value": 0.0, "limit": 0.0}
    assert c["loss1_gap"]["value"] < 1e-3
    assert 0 <= c["change_gap_median"]["value"] < \
        c["change_gap_median"]["limit"]
    assert c["grad_gap"]["limit"] is None       # printed, not judged


def test_a_traced_line_reports_the_layer_metrics_it_can_read(traced):
    man, r = traced
    want = {m["name"] for m in man.metrics_for("tiny.fit", "per_layer")}
    assert {"fit.etl_wait_share", "fit.dispatch_ms"} <= set(r["metrics"])
    assert set(r["metrics"]) <= want
    # no device plane on the CPU: device metrics are left out, never 0
    assert "device.idle_share.train" not in r["metrics"]
    assert "train.step_mfu" not in r["metrics"]
    for m in r["metrics"].values():
        assert m["value"] > 0 and m["unit"]
    assert r["device"]["platform"] == "cpu" and r["device"]["count"] == 1
    assert r["device"]["window_s"] > 0
