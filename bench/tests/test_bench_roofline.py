"""Peaks lookup and the roofline formulas against hand counts."""
from __future__ import annotations

import pytest

import roofline


def test_peaks_of_the_v5e():
    p = roofline.peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12
    assert p["int8_ops_per_s"] == 393e12
    assert p["hbm_bytes_per_s"] == 819e9
    assert p["hbm_bytes"] == 16e9


@pytest.mark.parametrize("kind", ["cpu", "TPU v4", "TPU v5p", ""])
def test_unknown_device_is_an_error(kind):
    with pytest.raises(roofline.UnknownDevice):
        roofline.peaks(kind)


def test_scoregrid_work_by_hand():
    # 6 candidates x 4096 f64 words = 49,152 u32 words; per u32 word
    # 32 planes x (ones, flips) x (extract, add) = 128, one xor, 4 bytes x
    # (shift, mask, count) = 12: 141 operations
    w = roofline.scoregrid_work(6, 4096, 8)
    assert w["ops"] == 6 * 4096 * 2 * 141
    # the grid read once (6 x 4096 x 8 bytes), 6 x 384 int32 stats written
    assert w["bytes"] == 6 * 4096 * 8 + 6 * 384 * 4


def test_least_time_names_its_bound():
    peak = roofline.peaks("TPU v5 lite")
    t, bound = roofline.least_time({"ops": 393e12, "bytes": 1.0}, peak)
    assert (t, bound) == (1.0, "compute")
    t, bound = roofline.least_time({"ops": 1.0, "bytes": 819e9}, peak)
    assert (t, bound) == (1.0, "memory")
