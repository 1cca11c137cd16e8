"""The benchmark's own work counts, against a hand count for one
2560x6912 projection on 32 rows."""

import pytest

from tiny_cell import ROOT  # noqa: F401
from bench import workcount
from bench.adapters import gqa_decoder
from bench.peaks import PEAKS, peaks_for

S50W8 = {"sparsity": 0.5, "bits": 8, "bk": 128, "bn": 128}


def test_dense_projection_by_hand():
    w = workcount.projection(2560, 6912, 32, {})
    assert w["nnz"] == 2560 * 6912
    assert w["flops"] == 2 * 32 * 2560 * 6912 == 1_132_462_080
    # float32 weights + 32 rows in + 32 rows out, float32
    assert w["bytes"] == 70_778_880 + 1_212_416


def test_sparse_int8_projection_by_hand():
    w = workcount.projection(2560, 6912, 32, S50W8)
    # 54 output blocks x 10 of 20 k-blocks kept x 128 x 128
    assert w["nnz"] == 8_847_360
    assert w["flops"] == 566_231_040
    # int8 codes + 6912 float32 scales + 540 int32 indices + activations
    assert w["bytes"] == 8_847_360 + 27_648 + 2_160 + 1_212_416


def test_least_time_is_memory_bound_for_decode():
    peaks = peaks_for("TPU v5 lite")
    w = workcount.projection(2560, 6912, 32, S50W8)
    assert workcount.least_time_s(w, peaks) == pytest.approx(
        w["bytes"] / 819e9)


def test_unknown_device_is_an_error():
    with pytest.raises(KeyError):
        peaks_for("TPU v9000")
    assert set(PEAKS["TPU v5 lite"]) >= {"bf16_flops", "hbm_bytes", "source"}


def test_model_flops_per_token():
    m = {"n_layers": 1, "d_model": 2560, "n_heads": 32, "n_kv_heads": 8,
         "head_dim": 80, "d_ff": 6912, "vocab": 32000}
    per_layer = 2560 * 2560 * 2 + 2560 * 640 * 2 + 3 * 2560 * 6912
    assert workcount.model_flops_per_token(gqa_decoder, m, {}) == \
        2 * (per_layer + 2560 * 32000)
    assert gqa_decoder.attention_flops(m, 1000) == 4 * 32 * 80 * 1000
