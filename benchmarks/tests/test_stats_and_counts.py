import json
import os

import pytest

from benchmarks.harness import counts, peaks, stats

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _config(name):
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        return json.load(f)


def test_percentile_is_nearest_rank():
    values = list(range(1, 201))             # 1..200
    assert stats.percentile(values, 0.95) == 190     # ten beyond it
    assert stats.percentile(values, 0.5) == 100
    assert stats.percentile([3.0], 0.95) == 3.0
    assert stats.percentile([], 0.95) is None
    assert stats.percentile([5, 1, 9], 1.0) == 9


def test_spread_is_the_contracts():
    import statistics

    values = [100.0, 101.0, 102.0, 103.0, 104.0, 110.0]
    q1, _, q3 = statistics.quantiles(values, n=4)
    assert stats.spread(values) == (q3 - q1) / statistics.median(values)


def test_parameter_counts_by_hand():
    serve = _config("mistral-7b-v0.3-int8")
    train = _config("mistral-7b-v0.3-train-24l")
    # one layer: q 4096x4096, k and v 4096x1024, o 4096x4096,
    # three feed-forward matrices 4096x14336, two norms of 4096
    layer = (16_777_216 + 2 * 4_194_304 + 16_777_216 + 3 * 58_720_256
             + 8_192)
    assert layer == 218_112_000 == counts.layer_params(serve)
    head, table = 4096 * 32768, 32768 * 4096
    assert counts.matmul_params(serve) == 32 * layer + head + 4096
    assert counts.total_params(serve) == 7_248_023_552    # "7.25 B"
    assert counts.matmul_params(train) == 24 * layer + head + 4096
    assert counts.total_params(train) == 5_503_127_552    # "5.50 B"
    assert counts.total_params(train) - counts.matmul_params(train) == table


def test_train_flops_per_token_by_hand():
    train = _config("mistral-7b-v0.3-train-24l")
    dense = 6 * 5_368_909_824
    attention = 6 * 24 * 4096 * 2048     # causal: half of 12 L d S
    assert counts.train_flops_per_token(train, 2048) == dense + attention
    assert round(counts.train_flops_per_token(train, 2048) / 1e9, 1) == 33.4


def test_decode_bytes_by_hand():
    serve = _config("mistral-7b-v0.3-int8")
    # 32 layers x 2 (k, v) x 8 heads x 128 x 2 bytes = 128 KiB a position
    assert counts.kv_bytes_per_token(serve) == 131_072
    matrices = 32 * (218_112_000 - 8_192) + 4096 * 32768
    scales = 4 * (32 * (4096 + 2 * 1024 + 4096 + 2 * 14336 + 4096) + 32768)
    norms = 2 * (2 * 32 * 4096 + 4096)
    assert counts.decode_weight_bytes(serve) == matrices + scales + norms
    assert counts.decode_step_bytes(serve, 3000) == (
        matrices + scales + norms + 3000 * 131_072)
    # bf16 weights: twice the matrices, no scales
    assert counts.decode_weight_bytes(serve, 2) == 2 * matrices + norms


def test_peaks_refuse_an_unknown_device():
    assert peaks.peaks_of("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks.peaks_of("cpu")
