"""The four-chip cell's path (ParallelWrapper over a data-parallel mesh)
at tiny size on four of the CPU's virtual devices."""
from benchmark_drive import drive, tiny_root


def test_data_parallel_fit_is_correct_on_four_devices(tmp_path, monkeypatch):
    with tiny_root(tmp_path, monkeypatch) as man:
        r = drive(man, "tiny.fit.dp4", 12345, 1.5, False)
    assert r["device"]["count"] == 4
    assert r["correct"] is True, r["compared"]
    assert set(r["metrics"]) == {"train_samples_per_s", "setup_s"}
    assert r["metrics"]["train_samples_per_s"]["value"] > 0
