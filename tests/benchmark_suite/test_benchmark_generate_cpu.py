"""`generate_closed` and `generate_open` end to end at tiny size on the
CPU, through real HTTP; and a per-layer metric added by files alone."""
import json
import os

from benchmark_drive import drive, tiny_root


def test_closed_loop_traced_and_a_metric_added_by_a_file(tmp_path,
                                                         monkeypatch):
    with tiny_root(tmp_path, monkeypatch) as man:
        # a later PR's per-layer metric: one data file, one manifest entry
        with open(os.path.join(man.root, "benchmark", "layer_metrics",
                               "decode.prefills_per_step.json"), "w") as f:
            json.dump({"reader": "benchmark.readers.counters:ratio",
                       "args": {"numerator": "serving_decode_prefills_total",
                                "denominator": "serving_decode_steps_total"}},
                      f)
        doc = man.doc
        doc["per_layer"].append({
            "name": "decode.prefills_per_step", "unit": "1", "better":
            "lower", "source": "program_counter", "layer": "engines",
            "moves": "generate_tokens_per_s", "workloads": ["tiny.closed"]})
        with open(os.path.join(man.root, "BENCHMARK.json"), "w") as f:
            json.dump(doc, f)
        from benchmark import manifest
        man = manifest.Manifest(man.root)
        r = drive(man, "tiny.closed", 2 ** 31 + 3, 2.0, True)
    assert r["correct"] is True, r["compared"]
    assert r["attempted"] > 5 and r["failed"] == 0
    assert r["compared"]["served_gap"]["value"] <= 1e-3
    assert r["compared"]["kv_blocks_left"]["value"] == 0
    got = set(r["metrics"])
    assert {"gateway.overhead_ms", "gateway.first_token_ms",
            "decode.rows_per_step", "decode.inter_token_ms",
            "decode.prefills_per_step"} <= got
    assert "device.idle_share.generate" not in got      # nothing to read
    assert 1.0 <= r["metrics"]["decode.rows_per_step"]["value"] <= 4.0


def test_open_loop_times_from_when_each_request_was_due(tmp_path,
                                                        monkeypatch):
    with tiny_root(tmp_path, monkeypatch) as man:
        r = drive(man, "tiny.open", 77, 2.0, False)
    assert r["correct"] is True, r["compared"]
    assert set(r["metrics"]) == {"generate_tokens_per_s", "request_p95_ms",
                                 "setup_s"}
    assert r["attempted"] >= 10 and r["failed"] == 0
