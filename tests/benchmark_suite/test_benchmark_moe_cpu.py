"""The sparse, windowed decoder's cell end to end at tiny size on the
CPU, through real HTTP and the harness as it stands (`generate_closed`
drives it; nothing of the harness is edited): chunked prefill, decoding
through the two kinds of cache, the plain reference deciding `correct`
under the real cell's own limits, the control in the nearest precision
below, and one planted fault."""
import pytest

import benchmark_tiny_moe
from benchmark_drive import drive, tiny_root


@pytest.fixture
def moe_root(tmp_path, monkeypatch):
    with tiny_root(tmp_path, monkeypatch) as man:
        yield benchmark_tiny_moe.add_to(man)


def _over(table: dict) -> list:
    return [k for k, row in table.items() if row["limit"] is not None
            and not (row["value"] is not None
                     and row["value"] <= row["limit"])]


def test_the_cell_is_correct_and_its_control_is_not(moe_root):
    from benchmark import manifest
    assert moe_root.cell("tiny.moe")["limits"] == manifest.data_file(
        "cells", benchmark_tiny_moe.REAL_CELL)["limits"]
    r = drive(moe_root, "tiny.moe", 2 ** 31 + 29, 2.0, True,
              controls=["bfloat16", "float8_e4m3fn"])
    assert r["correct"] is True, r["compared"]
    assert r["attempted"] > 5 and r["failed"] == 0
    assert r["compared"]["kv_blocks_left"]["value"] == 0
    for control in r["controls"].values():
        assert control["correct"] is False
        assert _over(control["compared"]) == ["served_gap"]
    got = set(r["metrics"])
    # what the new readers read off the chip too: the program's counters
    assert {"moe.experts_touched_share", "moe.load_peak_over_mean",
            "cache.sliding_blocks_share", "decode.prefill_share",
            "decode.rows_per_step", "gateway.first_token_ms"} <= got
    assert r["metrics"]["cache.sliding_blocks_share"]["value"] < 100.0
    assert 1.0 <= r["metrics"]["moe.load_peak_over_mean"]["value"] <= 8.0
    assert 0 < r["metrics"]["moe.experts_touched_share"]["value"] <= 100.0
    # nothing to read without a device trace
    assert not {m for m in got if m.startswith("kernels.")}


def test_a_window_that_is_ignored_is_not_correct(moe_root, monkeypatch):
    from deeplearning4j_tpu.serving import decode
    for name in ("prefill_attention", "paged_decode_attention"):
        real = getattr(decode, name)
        monkeypatch.setattr(
            decode, name, lambda *a, _real=real, **kw: _real(
                *a, **{**kw, "window": None}))
    r = drive(moe_root, "tiny.moe", 5, 1.5, False)
    assert r["failed"] == 0
    assert r["correct"] is False
    assert _over(r["compared"]) == ["served_gap"]
