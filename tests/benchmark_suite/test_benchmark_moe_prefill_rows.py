"""`moe.prefill_rows_useful_share` on synthetic counters: the held
assignments the chunks' expert layers computed over the rows their
grouped gate and up products ran, through the metric's own file and
reader, and nothing where the program has no such counter (the parent of
the PR that added them reads them as 0) or ran no chunk."""
from types import SimpleNamespace

import pytest

from benchmark import manifest

ASSIGNED = "serving_moe_prefill_assignments_total"
ROWS = "serving_moe_prefill_rows_computed_total"


def _read(counters):
    spec = manifest.data_file("layer_metrics",
                              "moe.prefill_rows_useful_share")
    reading = {"probe": SimpleNamespace(counters=counters)}
    return manifest.resolve(spec["reader"])(reading,
                                            **spec.get("args", {}))


@pytest.mark.parametrize("assigned,rows,share", [
    # Mellum's chunks: 16,384 assignments a layer in ~96 tiles of 256
    (8 * 16384.0, 8 * 96 * 256.0, 100 * 16384 / (96 * 256)),
    # every row a tile holds is an assignment
    (4096.0, 4096.0, 100.0),
    (1.0, 4.0, 25.0)])
def test_the_share_is_assignments_over_rows_computed(assigned, rows, share):
    got = _read({ASSIGNED: assigned, ROWS: rows,
                 "serving_moe_assignments_total": 1e9})
    assert got == pytest.approx(share)


@pytest.mark.parametrize("counters", [
    {},                                       # the parent: no counter
    {"serving_moe_assignments_total": 5e5},   # a step's only
    {ASSIGNED: 0.0, ROWS: 0.0},               # no chunk in the window
    {ASSIGNED: 100.0}])
def test_nothing_to_read_is_none(counters):
    assert _read(counters) is None


def test_the_metric_is_listed_for_the_sparse_cells_alone():
    metric = next(m for m in manifest.Manifest().doc["per_layer"]
                  if m["name"] == "moe.prefill_rows_useful_share")
    assert metric["layer"] == "kernels"
    assert metric["moves"] == "generate_tokens_per_s"
    assert sorted(metric["workloads"]) == [
        "kanana-2-30b-a3b.generate.long32k-c32",
        "laguna-s-2.1.generate.out1k-c64",
        "mellum2-12b-a2.5b.generate.mixed8k-c64"]
