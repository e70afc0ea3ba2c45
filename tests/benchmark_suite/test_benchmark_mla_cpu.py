"""The latent-attention decoder's cell end to end at tiny size on the
CPU, through real HTTP and the harness as it stands (`generate_closed`
drives it; nothing of the harness is edited): chunked prefill over
cached latents, decoding in the absorbed form through the latent cache,
the plain reference (expanded form, interleaved rotary) deciding
`correct` under the real cell's own limits, the controls in the
precisions below, and one planted fault."""
import pytest

import benchmark_tiny_mla
from benchmark_drive import drive, tiny_root


@pytest.fixture
def mla_root(tmp_path, monkeypatch):
    with tiny_root(tmp_path, monkeypatch) as man:
        yield benchmark_tiny_mla.add_to(man)


def _over(table: dict) -> list:
    return [k for k, row in table.items() if row["limit"] is not None
            and not (row["value"] is not None
                     and row["value"] <= row["limit"])]


def test_the_cell_is_correct_and_its_controls_are_not(mla_root):
    from benchmark import manifest
    assert mla_root.cell("tiny.mla")["limits"] == manifest.data_file(
        "cells", benchmark_tiny_mla.REAL_CELL)["limits"]
    r = drive(mla_root, "tiny.mla", 2 ** 31 + 33, 2.0, True,
              controls=["bfloat16", "float8_e4m3fn"])
    assert r["correct"] is True, r["compared"]
    assert r["attempted"] > 5 and r["failed"] == 0
    assert r["compared"]["kv_blocks_left"]["value"] == 0
    for control in r["controls"].values():
        assert control["correct"] is False
        assert _over(control["compared"]) == ["served_gap"]
    got = set(r["metrics"])
    # what the new readers read off the chip too: the program's counters
    assert {"prefill.context_tokens_per_chunk", "cache.latent_blocks_share",
            "moe.routed_touched_share", "decode.prefill_share",
            "decode.rows_per_step", "gateway.first_token_ms"} <= got
    assert 0 < r["metrics"]["cache.latent_blocks_share"]["value"] <= 100.0
    assert 0 < r["metrics"]["moe.routed_touched_share"]["value"] <= 100.0
    # a chunk reads no more than the longest prompt's earlier chunks
    assert 0 < r["metrics"]["prefill.context_tokens_per_chunk"]["value"] < 32
    # nothing to read without a device trace, nor for another decoder
    assert not {m for m in got if m.startswith("kernels.")
                or m.startswith("moe.experts") or "mfu" in m}


def test_a_selection_bias_that_is_ignored_is_not_correct(mla_root,
                                                         monkeypatch):
    from deeplearning4j_tpu.ops import moe
    real = moe.route
    monkeypatch.setattr(moe, "route", lambda *a, **kw: real(
        *a, **{**kw, "select_bias": None}))
    r = drive(mla_root, "tiny.mla", 5, 1.5, False)
    assert r["failed"] == 0
    assert r["correct"] is False
    assert _over(r["compared"]) == ["served_gap"]


def test_the_other_decoders_cells_read_nothing_of_this_one(mla_root):
    """The new readers are listed for every tiny decode cell; where the
    configuration is another decoder's they find nothing and say so."""
    from benchmark.readers import mla

    class Ctx:
        cfg = {"num_hidden_layers": 2}
        traffic = {}

    class Probe:
        counters = {"serving_decode_steps_total": 4.0,
                    "serving_moe_experts_touched_total": 9.0}
        reduced = None

    reading = dict(ctx=Ctx, probe=Probe, peaks=None,
                   window={"tokens": 5.0, "prompt_tokens": 9.0, "t0": 0.0,
                           "t1": 1.0})
    assert mla.step_mfu(reading) is None
    assert mla.routed_touched_share(reading) is None
    assert mla.latent_blocks_share(reading) is None
    assert mla.decode_step_roofline(reading, "_step_pure") is None
    assert mla.decode_attention_roofline(reading, "_step_pure", "x") is None
