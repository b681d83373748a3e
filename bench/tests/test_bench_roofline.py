"""Roofline counts at the cells' shapes, and the table of peaks."""

from pathlib import Path

import pytest

import roofline

BENCH = Path(roofline.__file__).parent
GIB = 2**30


def test_fill_bytes_per_test_point_at_the_cells():
    # one chip, n=50,000: the (n, n) f32 state read and written once,
    # 8 n^2 B = 18.6 GiB, and g and the ranks read (4 B each over 2n)
    n = 50000
    one = roofline.fill_bytes(1, n, n)
    assert one == 8 * n * n + 4 * 4 * n
    assert 18.6 * GIB < one < 18.7 * GIB
    # the same state row-sharded over four chips: each chip's (n/4, n)
    # block is read and written for every test point of the batch
    per_chip = roofline.fill_bytes(1, n // 4, n)
    assert per_chip == 8 * (n // 4) * n + 4 * (2 * n + 2 * (n // 4))
    assert roofline.fill_bytes(256, n, n) == pytest.approx(256 * one)


def test_distance_work_at_the_cells():
    flops, nbytes = roofline.distance_work(256, 50000, 768)
    assert flops == 2 * 256 * 50000 * 768
    assert nbytes == 4 * (50000 * 768 + 256 * 768 + 256 * 50000)
    flops, nbytes = roofline.distance_work(256, 50000, 2048)  # knn
    assert flops == 2 * 256 * 50000 * 2048
    assert nbytes == 4 * (50000 * 2048 + 256 * 2048 + 256 * 50000)
    flops, _ = roofline.distance_work(64, 50000, 768)  # a chip's slice
    assert flops == pytest.approx(2 * 64 * 50000 * 768)


def test_peaks_of_the_v5e_and_an_unknown_kind():
    p = roofline.peaks(BENCH, "TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12
    assert p["hbm_bytes_per_s"] == 819e9
    assert p["hbm_bytes"] == 16e9
    assert p["ici_bits_per_s"] == 1600e9
    with pytest.raises(KeyError, match="no peaks"):
        roofline.peaks(BENCH, "cpu")


def test_min_seconds_takes_the_larger_bound():
    p = roofline.peaks(BENCH, "TPU v5 lite")
    # the fill at n=50,000: 256 test points of 20 GB at 819 GB/s
    t = roofline.min_seconds(0.0, roofline.fill_bytes(256, 50000, 50000), p)
    assert t == pytest.approx(6.251, rel=1e-3)
    flops, nbytes = roofline.distance_work(256, 50000, 768)
    assert roofline.min_seconds(flops, nbytes, p) == pytest.approx(
        max(flops / 197e12, nbytes / 819e9))
