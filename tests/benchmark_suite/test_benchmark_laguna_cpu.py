"""The cell of the decoder that holds a share of its experts, end to end
at tiny size on the CPU, through real HTTP and the harness as it stands
(`generate_closed` drives it; nothing of the harness is edited): query
heads by kind of layer, the gate, half rotary, the dense layer, a share
of the experts and a slice of the vocabulary through chunked prefill and
both kinds of cache, the plain reference deciding `correct` under the
real cell's own limits, the controls in the precisions below, one
planted fault, and the new readers."""
import pytest

import benchmark_tiny_laguna
from benchmark_drive import drive, tiny_root


@pytest.fixture
def laguna_root(tmp_path, monkeypatch):
    with tiny_root(tmp_path, monkeypatch) as man:
        yield benchmark_tiny_laguna.add_to(man)


def _over(table: dict) -> list:
    return [k for k, row in table.items() if row["limit"] is not None
            and not (row["value"] is not None
                     and row["value"] <= row["limit"])]


def test_the_cell_is_correct_and_its_controls_are_not(laguna_root):
    from benchmark import manifest
    assert laguna_root.cell("tiny.laguna")["limits"] == manifest.data_file(
        "cells", benchmark_tiny_laguna.REAL_CELL)["limits"]
    r = drive(laguna_root, "tiny.laguna", 2 ** 31 + 35, 2.0, True,
              controls=["bfloat16", "float8_e4m3fn"])
    assert r["correct"] is True, r["compared"]
    assert r["attempted"] > 5 and r["failed"] == 0
    assert r["compared"]["kv_blocks_left"]["value"] == 0
    for control in r["controls"].values():
        assert control["correct"] is False
        assert _over(control["compared"]) == ["served_gap"]
    got = {k: v["value"] for k, v in r["metrics"].items()}
    # what the new readers read off the chip too: the program's counters
    assert {"moe.held_assignments_share", "moe.held_touched_share",
            "cache.sliding_blocks_share", "decode.rows_per_step",
            "gateway.first_token_ms"} <= set(got)
    # 2 of 8 experts are held: a quarter of the choices if routing is even
    assert 5.0 < got["moe.held_assignments_share"] < 60.0
    assert 0 < got["moe.held_touched_share"] <= 100.0
    assert got["cache.sliding_blocks_share"] < 100.0
    # nothing to read without a device trace or the chip's peaks, and
    # neither of the two that divide by all the experts is listed
    assert not {m for m in got if m.startswith("kernels.") or "mfu" in m
                or m in ("moe.experts_touched_share",
                         "moe.load_peak_over_mean", "decode.prefill_share")}


def test_a_gate_that_is_left_out_is_not_correct(laguna_root, monkeypatch):
    from benchmark.models import laguna
    monkeypatch.setitem(laguna.GATES, "per-head", None)
    r = drive(laguna_root, "tiny.laguna", 5, 1.5, False)
    assert r["failed"] == 0
    assert r["correct"] is False
    assert _over(r["compared"]) == ["served_gap"]


def test_the_work_functions_count_the_published_block():
    """`work_laguna` at the published sizes against the issue's count."""
    from benchmark import manifest, work_laguna as w
    cfg = manifest.data_file("configs", benchmark_tiny_laguna.REAL_CONFIG)
    assert w.attention_params(cfg, 48) == 44_187_648
    assert w.attention_params(cfg, 72) == 63_135_744
    assert w.expert_params(cfg) == 9_437_184 == w.shared_params(cfg)
    assert w.router_params(cfg) == 786_432
    assert w.dense_params(cfg) == 113_246_208
    assert w.head_params(cfg) == 25_088 * 3_072
    assert w.held_share(cfg) == 0.25
    kinds = [(k, f) for k, f, _ in w.layers(cfg)]
    assert kinds == [("full", "dense")] + [("sliding", "moe")] * 3 \
        + [("full", "moe"), ("sliding", "moe")]
    # every parameter of the six layers but the routed experts: 2 full
    # and 4 sliding attentions, a dense layer, 5 routers and shared ones
    always = 2 * 44_187_648 + 4 * 63_135_744 + 113_246_208 \
        + 5 * (786_432 + 9_437_184)
    assert w.always_read_params(cfg) == always
    # the whole of what the chip holds: 3,679 M parameters
    total = always + 5 * 64 * 9_437_184 + 2 * w.head_params(cfg)
    assert round(total / 1e6) == 3679
    # a step that touches every held expert and sees no key reads the
    # weights once, without the embedding
    step = w.decode_step_bytes(cfg, 5 * 64, {"full": 0.0, "sliding": 0.0})
    assert step == 2 * (total - w.head_params(cfg))
    # a token: 2 FLOPs a parameter it passes, a quarter of its 10 choices
    keys = {"full": 0.0, "sliding": 0.0}
    assert w.flops_per_token(cfg, keys) == 2.0 * (
        always + 5 * 2.5 * 9_437_184)
    more = w.flops_per_token(cfg, {"full": 100.0, "sliding": 10.0})
    assert more - w.flops_per_token(cfg, keys) == 4.0 * 128 * (
        2 * 48 * 100.0 + 4 * 72 * 10.0)


def test_the_other_decoders_cells_read_nothing_of_this_one():
    """Where the configuration is another decoder's the new readers find
    nothing and say so."""
    from benchmark.readers import laguna

    class Ctx:
        cfg = {"num_hidden_layers": 2, "num_experts": 8}
        traffic = {}

    class Probe:
        counters = {"serving_decode_steps_total": 4.0,
                    "serving_moe_experts_touched_total": 9.0}
        reduced = None

    reading = dict(ctx=Ctx, probe=Probe, peaks=None,
                   window={"tokens": 5.0, "prompt_tokens": 9.0, "t0": 0.0,
                           "t1": 1.0})
    assert laguna.step_mfu(reading) is None
    assert laguna.held_touched_share(reading) is None
    assert laguna.decode_step_roofline(reading, "_step_pure") is None
