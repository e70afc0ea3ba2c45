"""BENCHMARK.json and every data file it names: they load, they keep to
the contract's names, units and cross-references, and the harness finds
each by name with no list of its own."""
import glob
import json
import os
import re

import pytest

from benchmark import manifest

ROOT = manifest.ROOT
PATH_RE = re.compile(r"^[A-Za-z0-9_.\-/]+$")


@pytest.fixture(scope="module")
def man():
    return manifest.Manifest()


def test_names_units_and_references(man):
    assert manifest.check_names(man.doc) == []


def test_top_level_keys_and_limits(man):
    doc = man.doc
    assert set(doc) == {"command", "paths", "run_seconds", "configs",
                        "workloads", "end_to_end", "per_layer"}
    assert 1 <= doc["run_seconds"] <= 51 and isinstance(doc["run_seconds"],
                                                        int)
    assert len(json.dumps(doc)) < 64 * 1024
    for p in doc["paths"]:
        assert PATH_RE.match(p) and not p.startswith("/") and ".." not in p
        assert os.path.isdir(os.path.join(ROOT, p))
    for word in doc["command"]:
        assert 1 <= len(word) <= 200 and not word.startswith("/")
    four = [w for w in doc["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(doc["workloads"]) // 4)


def test_every_metric_entry_has_just_the_keys_allowed(man):
    for m in man.doc["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    for m in man.doc["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert "\n" not in m["layer"] and 1 <= len(m["layer"]) <= 200
    names = [m["name"] for m in man.doc["end_to_end"]]
    assert "setup_s" in names
    setup = next(m for m in man.doc["end_to_end"] if m["name"] == "setup_s")
    assert "workloads" not in setup


def test_roofline_and_mfu_names(man):
    """A kernel's share of its roofline is `<kernel>_roofline` in %, and
    beside the rooflines that move a metric stands the whole step's share
    of the peak, `mfu` a part of its name of its own, moving the same."""
    roof = [m for m in man.doc["per_layer"] if "roofline" in m["name"]]
    assert roof
    for m in roof:
        assert m["name"].endswith("_roofline") and m["unit"] == "%"
        mfus = [x for x in man.doc["per_layer"]
                if "mfu" in re.split(r"[._\-]", x["name"])
                and x["moves"] == m["moves"]]
        assert mfus, f"{m['name']} has no mfu beside it"
        assert set(m["workloads"]) <= set().union(
            *(set(x["workloads"]) for x in mfus))


def test_every_cell_reports_what_the_contract_asks(man):
    for cell in man.cells:
        e2e = [m["name"] for m in man.metrics_for(cell, "end_to_end")]
        assert "setup_s" in e2e and len(e2e) >= 2
        layer = man.metrics_for(cell, "per_layer")
        assert layer
        for m in layer:
            assert m["moves"] in e2e, (cell, m["name"])


def test_every_named_file_loads_and_is_found_by_name(man):
    for cell in man.cells:
        entry = man.cell(cell)
        cfg = manifest.data_file("configs", entry["config"])
        traffic = manifest.data_file("traffic", entry["traffic"])
        assert cfg["name"] == entry["config"]
        assert os.path.isfile(os.path.join(
            ROOT, "benchmark", "kinds", traffic["kind"] + ".py"))
        for key in ("builder", "weights", "reference"):
            assert callable(manifest.resolve(cfg[key]))
        assert isinstance(entry.get("limits"), dict) and entry["limits"]
    for c in man.doc["configs"]:
        cfg = json.load(open(os.path.join(ROOT, c["file"])))
        assert c["file"].startswith(tuple(man.doc["paths"]))
        assert cfg["source"] == c["source"]
        assert cfg["reduced"] == c["reduced"]
        assert isinstance(cfg["assumed"], dict)
        assert any(w["config"] == c["name"] for w in man.doc["workloads"])
    for m in man.doc["per_layer"]:
        spec = manifest.data_file("layer_metrics", m["name"])
        assert callable(manifest.resolve(spec["reader"]))


def test_a_cell_is_defined_in_one_place(man, tmp_path):
    """`workloads` carries config, traffic, chips and why; the cell's own
    file carries the limits and the traced window; a key in both, or a
    cell file with no entry, is an error."""
    for path in glob.glob(os.path.join(ROOT, "benchmark", "cells", "*.json")):
        name = os.path.basename(path)[:-5]
        assert name in man.cells, f"{name}: a cell file with no entry"
        own = json.load(open(path))
        assert set(own) <= {"limits", "trace_offset_s", "trace_seconds"}
    with pytest.raises(manifest.ManifestError):
        man.cell("resnet50.fit.dp4-b2048")          # not (yet) a cell
    name = next(iter(man.cells))
    os.makedirs(tmp_path / "benchmark" / "cells")
    json.dump(man.doc, open(tmp_path / "BENCHMARK.json", "w"))
    json.dump({"limits": {}, "chips": 1},
              open(tmp_path / "benchmark" / "cells" / (name + ".json"), "w"))
    with pytest.raises(manifest.ManifestError, match="chips"):
        manifest.Manifest(str(tmp_path)).cell(name)


def test_files_under_paths_have_only_the_characters_of_a_name(man):
    for p in man.doc["paths"]:
        for d, _, files in os.walk(os.path.join(ROOT, p)):
            if "__pycache__" in d:
                continue
            for f in files:
                rel = os.path.relpath(os.path.join(d, f), ROOT)
                assert PATH_RE.match(rel), rel


def test_unknown_names_are_errors(man):
    with pytest.raises(manifest.ManifestError):
        manifest.data_file("cells", "no-such-cell")
    with pytest.raises(manifest.ManifestError):
        manifest.data_file("cells", "../BENCHMARK")
