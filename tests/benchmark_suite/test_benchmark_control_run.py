"""The controls through the rest of a run: the reference computed in the
nearest precision below the one the configuration states is put in the
program's place, goes through the run's own comparison, and comes out as
not correct against the real cells' own limits."""
import benchmark_tiny
from benchmark_drive import drive, tiny_root


def _over(table: dict) -> list:
    """The numbers of a verdict's table that fail their limit."""
    return [k for k, row in table.items() if row["limit"] is not None
            and not (row["value"] is not None
                     and row["value"] <= row["limit"])]


def test_bfloat16_in_the_float32_decoders_place_is_not_correct(
        tmp_path, monkeypatch):
    from benchmark import manifest
    real = manifest.data_file(
        "cells", "decoder-at-opt-1.3b.generate.short-c16")["limits"]
    with tiny_root(tmp_path, monkeypatch) as man:
        assert man.cell("tiny.closed")["limits"] == real
        r = drive(man, "tiny.closed", 2, 2.0, False, controls=["bfloat16"])
    assert r["correct"] is True, r["compared"]
    control = r["controls"]["bfloat16"]
    assert control["correct"] is False
    assert _over(control["compared"]) == ["served_gap"]
    assert list(r)[-1] == "compared"


def test_float8_in_the_fit_cells_place_is_not_correct(tmp_path, monkeypatch):
    from benchmark import manifest
    real = manifest.data_file("cells", "resnet50.fit.b512")["limits"]
    with tiny_root(tmp_path, monkeypatch) as man:
        limits = man.cell("tiny.fit")["limits"]
        assert all(limits[k] == v for k, v in real.items()
                   if k not in benchmark_tiny.TINY_FIT_LIMITS)
        r = drive(man, "tiny.fit", 3, 1.0, False,
                  controls=["float8_e4m3fn"])
    assert r["correct"] is True, r["compared"]
    control = r["controls"]["float8_e4m3fn"]
    assert control["correct"] is False
    assert set(_over(control["compared"])) & \
        (set(real) - set(benchmark_tiny.TINY_FIT_LIMITS))
