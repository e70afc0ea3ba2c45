"""`benchmark/readers/prefill.py` on synthetic readings: the program's
pair counters over a window and a small reduced trace of two runs of the
chunk's program. Each share is the work the counters price over the
device time the trace holds, and nothing where a counter, the trace, a
run or the chip's peaks are missing (the parent of the PR that added the
counters reads them as 0)."""
from types import SimpleNamespace

import pytest

from benchmark import manifest, trace_reduce, work_mla, work_moe
from benchmark.peaks import PEAKS
from benchmark.readers import prefill

PEAK = PEAKS["TPU v5 lite"]["bf16_flops"]
CONFIGS = {"latent": "kanana-2-30b-a3b-instruct-2601",
           "mellum": "mellum2-12b-a2.5b-instruct",
           "laguna": "laguna-s-2.1", "dense": "decoder-at-opt-1.3b"}


def _trace(kernels):
    """Two runs of `_prefill_pure` of 50 ms each inside a window of 3 s,
    each holding `kernels[name]` seconds of that kernel; one step's run
    of another kernel of the same name lies outside them."""
    call = "%{}.{} = (bf16[2]) custom-call(bf16[2] %x)"
    modules, ops = [], []
    for i, t in enumerate((1.0, 2.0)):
        modules.append((f"jit__prefill_pure({i})", t, 0.05))
        ops.append((f"%fusion.{i} = bf16[2] fusion(%p)", t, 0.05))
        for j, (name, s) in enumerate(kernels.items()):
            ops.append((call.format(name, 10 * i + j), t + 0.001, s))
    modules.append(("jit__step_pure(9)", 2.5, 0.01))
    ops.append((call.format(next(iter(kernels)), 99), 2.5, 0.01))
    return trace_reduce.Trace(ops={0: ops}, modules={0: modules},
                              window=(0.0, 3.0))


def _reading(config, counters, trace, peaks=True):
    return {"ctx": SimpleNamespace(cfg=manifest.data_file(
        "configs", CONFIGS[config])),
        "probe": SimpleNamespace(counters=counters, reduced=trace),
        "peaks": PEAKS["TPU v5 lite"] if peaks else None}


LATENT = {"serving_decode_prefill_chunks_total": 10.0,
          "serving_decode_prefill_tokens_total": 10 * 2048.0,
          "serving_decode_prefill_pairs_total{arm=kernel,kind=latent}": 5e9,
          "serving_decode_prefill_pairs_total{arm=dense,kind=latent}": 0.0,
          "serving_decode_prefill_pairs_run_total{kind=latent}": 8e9}


def test_the_latent_chunks_shares():
    r = _reading("latent", LATENT,
                 _trace({"prefill_attention_latent": 0.02}))
    # 5e8 pairs a chunk x 20,480 FLOP (32 heads x (192 + 128) x 2) over
    # 197 TFLOP/s, against the kernel's 20 ms a run
    assert work_mla.pair_flops(r["ctx"].cfg) == 20480
    assert prefill.attention_roofline(
        r, "_prefill_pure", "prefill_attention_latent", "latent") == \
        pytest.approx(100 * 5e8 * 20480 / 197e12 / 0.02)
    assert prefill.visible_pairs_share(r) == pytest.approx(62.5)
    # 2,048 positions through every matrix and the pairs, over the 50 ms
    # of a run
    least = 2048 * work_mla.flops_per_token(r["ctx"].cfg, 0.0) \
        + 5e8 * 20480
    assert prefill.chunk_mfu(r, "_prefill_pure") == pytest.approx(
        100 * least / PEAK / 0.05)


def test_mellums_kinds_are_priced_at_their_heads_and_both_arms_count():
    c = {"serving_decode_prefill_chunks_total": 4.0,
         "serving_decode_prefill_tokens_total": 4 * 2000.0,
         "serving_decode_prefill_pairs_total{arm=kernel,kind=full}": 4e8,
         "serving_decode_prefill_pairs_total{arm=kernel,kind=sliding}": 8e8,
         "serving_decode_prefill_pairs_total{arm=dense,kind=sliding}": 4e7,
         "serving_decode_prefill_pairs_run_total{kind=full}": 8e8,
         "serving_decode_prefill_pairs_run_total{kind=sliding}": 2e9}
    r = _reading("mellum", c, _trace({"prefill_attention_full": 0.004,
                                      "prefill_attention_sliding": 0.008}))
    pair = 4 * 32 * 128
    assert prefill.attention_roofline(
        r, "_prefill_pure", "prefill_attention_full", "full") == \
        pytest.approx(100 * 1e8 * pair / 197e12 / 0.004)
    assert prefill.attention_roofline(
        r, "_prefill_pure", "prefill_attention_sliding", "sliding") == \
        pytest.approx(100 * 2e8 * pair / 197e12 / 0.008)
    # the dense arm's pairs are work of the chunk, not of the kernel
    assert prefill.visible_pairs_share(r) == pytest.approx(
        100 * 1.2e9 / 2.8e9)
    least = 2000 * work_moe.flops_per_token(
        r["ctx"].cfg, {"full": 0.0, "sliding": 0.0}) + (1e8 + 2.1e8) * pair
    assert prefill.chunk_mfu(r, "_prefill_pure") == pytest.approx(
        100 * least / PEAK / 0.05)


def test_lagunas_sliding_pairs_are_priced_at_its_72_heads():
    c = {"serving_decode_prefill_chunks_total": 2.0,
         "serving_decode_prefill_pairs_total{arm=kernel,kind=sliding}": 2e8,
         "serving_decode_prefill_pairs_run_total{kind=sliding}": 4e8}
    r = _reading("laguna", c, _trace({"prefill_attention_sliding": 0.01}))
    assert prefill.attention_roofline(
        r, "_prefill_pure", "prefill_attention_sliding", "sliding") == \
        pytest.approx(100 * 1e8 * 4 * 72 * 128 / 197e12 / 0.01)
    assert prefill.visible_pairs_share(r) == pytest.approx(50.0)


@pytest.mark.parametrize("case", ["parent", "no trace", "no run", "no peaks",
                                  "no kernel", "another decoder"])
def test_nothing_to_read_is_none(case):
    counters, trace = dict(LATENT), _trace({"prefill_attention_latent": 0.02})
    config, peaks = "latent", True
    if case == "parent":        # a program without the pair counters
        counters = {k: v for k, v in counters.items() if "pairs" not in k}
    elif case == "no trace":
        trace = None
    elif case == "no run":
        trace.modules = {0: [m for m in trace.modules[0]
                             if "_prefill_pure" not in m[0]]}
    elif case == "no peaks":
        peaks = False
    elif case == "no kernel":
        trace = _trace({"prefill_attention_full": 0.02})
    else:
        config = "dense"
    r = _reading(config, counters, trace, peaks)
    assert prefill.attention_roofline(
        r, "_prefill_pure", "prefill_attention_latent", "latent") is None
    assert (prefill.chunk_mfu(r, "_prefill_pure") is None) == \
        (case != "no kernel")
    # the share reads counters alone
    assert (prefill.visible_pairs_share(r) is None) == (case == "parent")
