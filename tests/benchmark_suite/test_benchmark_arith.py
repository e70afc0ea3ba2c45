"""Percentile, rate, spread and idle-share arithmetic, the peaks table and
the FLOP and byte functions, on hand-made inputs. No JAX work."""
import math

import pytest

from benchmark import compare, flops, peaks, stats


def test_percentile_is_nearest_rank_over_all_values():
    xs = list(range(1, 101))
    assert stats.percentile(xs, 95) == 95
    assert stats.percentile(xs, 100) == 100
    assert stats.percentile([5.0], 95) == 5.0
    assert stats.percentile([1, 2, 3, 4], 50) == 2
    # 20 requests: the 95th percentile is the 19th, a request that happened
    assert stats.percentile(list(range(20)), 95) == 18
    with pytest.raises(ValueError):
        stats.percentile([], 95)


def test_rate_is_all_work_over_all_time():
    assert stats.rate(1000, 2.0, 12.0) == 100.0
    with pytest.raises(ValueError):
        stats.rate(1, 3.0, 3.0)


def test_iqr_spread_uses_pythons_quartiles():
    vals = [100, 101, 99, 102, 98, 100]
    import statistics
    q1, _, q3 = statistics.quantiles(vals, n=4)
    assert stats.iqr_spread(vals) == (q3 - q1) / 100


def test_union_merge_gaps_and_idle_share():
    iv = [(0, 2), (1, 3), (5, 6), (5.5, 5.8)]
    assert stats.union_length(iv) == 4.0
    assert stats.merge_intervals(iv) == [(0, 3), (5, 6)]
    assert stats.gaps([(0, 3), (5, 6)], 0, 10) == [(3, 5), (6, 10)]
    assert stats.gaps([(1, 2)], 0, 3) == [(0, 1), (2, 3)]
    assert stats.idle_share(4.0, 10.0) == 0.6


def test_peaks_table_and_unknown_kind():
    p = peaks.peaks_for("TPU v5 lite")
    assert p["bf16_flops"] == 197e12 and p["int8_ops"] == 393e12
    assert p["hbm_bytes_per_s"] == 819e9 and p["hbm_bytes"] == 16e9
    with pytest.raises(KeyError):
        peaks.peaks_for("cpu")


def test_resnet50_flops_from_shapes():
    cfg = {"image": [224, 224, 3], "labels": 1000}
    work = flops.resnet50_conv_work(cfg)
    assert len(work) == 54                      # 53 convolutions + the head
    fwd = sum(w["flops"] / (2 if i == 0 else 3) for i, w in enumerate(work))
    assert abs(fwd - 2.221e9) < 2e6             # the zoo's, not 8.18 GFLOP
    per = flops.resnet50_train_flops_per_sample(cfg)
    assert per == 3 * fwd - work[0]["flops"] / 2      # no input gradient in the stem


def test_decoder_parameters_and_bytes():
    cfg = {"hidden_size": 2048, "ffn_dim": 8192, "num_hidden_layers": 24,
           "vocab_size": 50272}
    n = flops.decoder_matmul_params(cfg)
    assert n == 24 * (4 * 2048 ** 2 + 2 * 2048 * 8192) + 50272 * 2048
    assert flops.decoder_flops_per_token(cfg) == 2.0 * n
    assert flops.decoder_flops_per_token(cfg, 100) > 2.0 * n
    b = flops.decoder_step_bytes(cfg, rows=8, kv_tokens=128)
    assert b == (n + 2 * 8 * 128 * 24 * 2048) * 4


def test_training_gaps_are_gaps_of_norms_by_the_worst_leaf():
    ref = ([10.0, 9.0, 8.0], {"a/W": 4.0, "b/W": 2.0, "c/b": 1e-6},
           {"a/W": 1.0, "b/W": 0.5, "c/b": 0.2},
           {"bn/mean": 3.0, "bn/var": 6.0})
    same = compare.training_gaps(ref, ref)
    assert all(v == 0.0 for v in same.values())
    prog = ([10.1, 9.0, 8.4], {"a/W": 4.4, "b/W": 2.0, "c/b": 0.5},
            {"a/W": 1.0, "b/W": 0.25, "c/b": 0.0},
            {"bn/mean": 3.0, "bn/var": 4.5})
    g = compare.training_gaps(prog, ref)
    assert math.isclose(g["loss_gap"], 0.05)
    assert math.isclose(g["loss1_gap"], 0.01)
    # c/b's gradient is nought in the reference: held against the median
    # leaf (2.0), and left out of the change altogether
    assert math.isclose(g["grad_gap"], (0.5 - 1e-6) / 2.0)
    assert math.isclose(g["change_gap"], 0.25 / 0.5)
    assert math.isclose(g["bn_state_gap"], 1.5 / 6.0)
    assert math.isclose(g["bn_state_gap_median"], 0.125)
    # an unchanged state reads 1 by this measure
    still = (ref[0], ref[1], {k: 0.0 for k in ref[2]},
             {k: 0.0 for k in ref[3]})
    unmoved = compare.training_gaps(still, ref)
    assert unmoved["change_gap"] == 1.0 and unmoved["bn_state_gap"] == 1.0


def test_verdict_holds_each_number_to_its_limit():
    ok, table = compare.verdict({"a": 0.1, "b": 5.0}, {"a": 0.2})
    assert ok and table["b"]["limit"] is None
    assert not compare.verdict({"a": 0.3}, {"a": 0.2})[0]
    assert not compare.verdict({"a": float("nan")}, {"a": 0.2})[0]
    assert not compare.verdict({}, {"a": 0.2})[0]          # never compared
    assert not compare.verdict({"a": 0.1}, {"a": 0.2}, failed=1)[0]
