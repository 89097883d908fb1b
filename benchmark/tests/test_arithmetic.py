"""Percentiles, spreads, FLOPs and peaks: the yardstick's arithmetic."""
import json
import os

import pytest

from benchmark import flops, peaks, spec, stats


def cfg(name):
    with open(os.path.join(spec.HERE, "configs", name + ".json")) as f:
        return json.load(f)


def test_percentile_interpolates():
    xs = [4, 1, 3, 2]
    assert stats.percentile(xs, 0) == 1 and stats.percentile(xs, 100) == 4
    assert stats.percentile(xs, 50) == pytest.approx(2.5)
    assert stats.percentile(xs, 90) == pytest.approx(3.7)
    assert stats.spread([10, 11, 12, 13, 14]) == pytest.approx(2 / 12)
    with pytest.raises(ValueError):
        stats.percentile([], 50)
    with pytest.raises(ValueError):
        stats.percentile([1], 101)


def test_parameter_counts_match_the_published_models():
    intern = flops.param_counts(cfg("internlm2-1.8b-serve"))
    assert intern["total"] == pytest.approx(1.889e9, rel=1e-3)   # "1.8B"
    mistral = cfg("mistral-7b-v0.3-train")
    assert flops.param_counts(mistral, layers=32)["total"] == pytest.approx(
        7.248e9, rel=1e-3)                                       # "7B"
    assert flops.param_counts(mistral)["total"] == pytest.approx(
        704.7e6, rel=1e-3)                                       # depth 2
    assert flops.param_counts(mistral, layers=4)["total"] == pytest.approx(
        1.141e9, rel=1e-3)


def test_train_flops_per_token():
    mistral = cfg("mistral-7b-v0.3-train")
    # 6 x 570.4 M matmul parameters + 3 x 2 layers x 4 x 2048 x 4096
    assert flops.train_flops_per_token(mistral, 4096) == pytest.approx(
        3.624e9, rel=1e-3)
    assert flops.train_flops_per_token(mistral, 4096, layers=4) \
        == pytest.approx(6.442e9, rel=1e-3)


def test_serving_bytes():
    intern = cfg("internlm2-1.8b-serve")
    assert flops.kv_bytes_per_token(intern, 2) == 98304
    # one decode step streams every weight but the embedding table
    assert flops.decode_step_bytes(intern, 2, 2, 0) == pytest.approx(
        (1.889e9 - 92544 * 2048) * 2, rel=1e-3)


def test_peaks_raise_on_an_unknown_device():
    assert peaks.peaks_for("TPU v5 lite")["bf16_flops"] == 197e12
    assert peaks.peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks.peaks_for("cpu")
