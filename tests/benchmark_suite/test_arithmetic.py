"""Percentile, throughput, spread, worst-leaf gap, FLOP and byte counts
against hand counts, and the peaks table."""
import json

import pytest

from benchmark import flops, stats


def test_percentile_linear_between_ranks():
    assert stats.percentile([1, 2, 3, 4, 5], 50) == 3
    assert stats.percentile([10, 20], 90) == pytest.approx(19.0)
    assert stats.percentile(list(range(1, 101)), 90) == pytest.approx(90.1)
    assert stats.percentile([7.0], 90) == 7.0
    with pytest.raises(ValueError):
        stats.percentile([], 50)


def test_throughput_is_whole_steps_over_first_dispatch_to_last_completion():
    assert stats.tokens_per_s_per_chip(10, 32768, 100.0, 102.0, 1) \
        == pytest.approx(163840.0)
    assert stats.tokens_per_s_per_chip(10, 32768, 100.0, 102.0, 4) \
        == pytest.approx(40960.0)
    with pytest.raises(ValueError):
        stats.tokens_per_s_per_chip(0, 32768, 1.0, 2.0, 1)


def test_quartile_spread_is_the_contracts():
    import statistics
    vals = [100.0, 101.0, 99.0, 100.5, 99.5, 100.2]
    q1, _, q3 = statistics.quantiles(vals, n=4)
    assert stats.quartile_spread(vals) == pytest.approx(
        (q3 - q1) / statistics.median(vals))


def test_worst_leaf_gap_uses_the_median_leaf_as_floor():
    ref = {"a": 1.0, "b": 2.0, "tiny": 1e-6}
    prog = {"a": 1.1, "b": 2.0, "tiny": 3e-6}
    gap, leaf = stats.worst_leaf_gap(prog, ref)
    # tiny's gap is measured against the median leaf (1.0), not 1e-6
    assert leaf == "a" and gap == pytest.approx(0.1)
    gap, leaf = stats.worst_leaf_gap({"a": float("nan"), "b": 2.0,
                                      "tiny": 1e-6}, ref)
    assert leaf == "a" and gap != gap
    with pytest.raises(ValueError):
        stats.worst_leaf_gap({"a": 1.0}, ref)


def test_bert_train_flops_hand_count():
    # hidden 4, 1 layer, ff 8, vocab 10, batch 2, seq 3, 1 prediction
    tokens = 6
    fwd = (8 * tokens * 16) + (4 * 2 * 9 * 4) + (4 * tokens * 4 * 8) \
        + 2 * 2 * 1 * 4 * 10 + 2 * 2 * 1 * 4 * 4
    assert flops.bert_train_flops(4, 1, 8, 10, 2, 3, 1) == 3 * fwd
    # BERT-base phase 1: 6 * 85M encoder parameters * 32768 tokens is the
    # bulk of it
    full = flops.bert_train_flops(768, 12, 3072, 30522, 256, 128, 20)
    assert 1.7e13 < full < 2.1e13


def test_gpt_train_flops_counts_half_the_score_matrix():
    tokens = 8
    fwd = (8 * tokens * 16) + (4 * 2 * 16 * 4) // 2 + (4 * tokens * 4 * 8)
    fwd += 2 * tokens * 4 * 10
    assert flops.gpt_train_flops(4, 1, 8, 10, 2, 4) == 3 * fwd


def test_flash_call_counts():
    fwd, bwd = flops.flash_call_flops(1, 2, 8, 4, causal=False)
    assert fwd == 2 * (2 * 1 * 2 * 8 * 8 * 4) and bwd == 5 * (fwd // 2)
    cf, cb = flops.flash_call_flops(1, 2, 8, 4, causal=True)
    assert (cf, cb) == (fwd // 2, bwd // 2)
    fb, bb = flops.flash_call_bytes(1, 2, 8, 4, 2)
    assert fb == 4 * 128 and bb == 8 * 128


def test_roofline_says_which_bound_applies():
    peaks = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    assert flops.roofline_seconds(1000.0, 10.0, peaks) == (10.0, "flops")
    assert flops.roofline_seconds(10.0, 1000.0, peaks) == (100.0, "bytes")


def test_peaks_are_keyed_by_the_exact_device_kind(tmp_path):
    v5e = flops.peaks_for("TPU v5 lite")
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9
    for kind in ("TPU v5", "tpu v5 lite", "cpu", ""):
        with pytest.raises(KeyError):
            flops.peaks_for(kind)
    other = tmp_path / "peaks.json"
    other.write_text(json.dumps({"device_kinds": {"X": {"a": 1}}}))
    assert flops.peaks_for("X", str(other)) == {"a": 1}
