"""The operation and byte counts against hand counts."""
import pytest

import tiny  # noqa: F401  (puts the benchmark on the path)
from chipbench import counts, peaks

SIZES = [784, 200, 200, 10]


def test_mlp_counts():
    assert counts.mlp_matmul_weights(SIZES) == 784 * 200 + 200 * 200 + 200 * 10
    assert counts.mlp_matmul_weights(SIZES) == 198_800
    assert counts.mlp_params(SIZES) == 199_210
    # forward 2 FLOPs per multiply-add, backward twice the forward
    assert counts.mlp_train_flops_per_row(SIZES) == 3 * 2 * 198_800
    assert counts.mlp_train_flops_per_row(SIZES, mc_samples=8) == 8 * 1_192_800


def test_eq6_counts_torus256():
    n, p, nnz = 256, 199_210, 256 * 5  # 16x16 torus: self + 4 neighbours
    assert counts.eq6_bytes(n, p, nnz) == 16 * 256 * 199_210 + 4 * 1280
    assert counts.eq6_bytes(n, p, nnz) == 815_969_280
    assert counts.eq6_flops(nnz, p) == 1_019_955_200
    t, bound = counts.roofline_seconds(counts.eq6_flops(nnz, p),
                                       counts.eq6_bytes(n, p, nnz),
                                       peaks.peaks_for("TPU v5 lite"))
    assert bound == "memory"
    assert t == pytest.approx(815_969_280 / 819e9)
    assert 0.99e-3 < t < 1.0e-3


def test_peak_table():
    v5e = peaks.peaks_for("TPU v5 lite")
    assert v5e["flops_bf16_per_s"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9
    assert v5e["hbm_bytes"] == 16e9
    assert v5e["ici_bits_per_s"] == 1600e9
    with pytest.raises(peaks.UnknownDevice):
        peaks.peaks_for("TPU v9 imaginary")
