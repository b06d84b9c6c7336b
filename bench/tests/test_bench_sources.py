"""The benchmark's data and request generators: the same draws from the
same seed, no two files alike, the source's layout and digits, and YCSB's
key generator."""
from __future__ import annotations

import numpy as np
import pytest

import spec

SEED = 2**31 + 977


def table_module():
    return spec.module("sources", "sensor_table")


def serve_module():
    return spec.module("drivers", "serve")


def test_ar1_matches_the_recurrence():
    z = np.random.default_rng(0).standard_normal(5000)
    ref, c = np.empty(5000), 0.0
    for i, d in enumerate(z):
        c = 0.98 * c + d
        ref[i] = c
    np.testing.assert_allclose(table_module().ar1(z, 0.98, block=64), ref,
                               rtol=0, atol=1e-12)


@pytest.mark.parametrize("x", [0.32663, 1018.7, -6.2299, 0.00012345, 1100.9,
                               0.0])
def test_five_significant_digits_are_the_parsed_text(x):
    """Rounding gives the double a CSV parser gives for the decimal text."""
    r = table_module().round_significant(np.array([x * (1 + 3e-7)]), 5)[0]
    assert r == float(f"{x:.5g}")


def test_table_layout_and_determinism(small_cell):
    cell = small_cell("gas_turbine_f64.ingest")
    cfg = cell.config
    a, b = spec.source(cell, SEED), spec.source(cell, SEED)
    ncol = len(cfg["data"]["columns"])
    nf = len(cfg["data"]["file_rows"])
    seen = set()
    for k in range(3 * nf):
        name, x = a.item(k)
        assert np.array_equal(x, b.item(k)[1]) and name == b.item(k)[0]
        assert x.dtype == np.float64
        assert x.size == cfg["data"]["file_rows"][k % nf] * ncol
        assert x.tobytes() not in seen             # every file is new
        seen.add(x.tobytes())
        rows = x.reshape(-1, ncol)                  # row-major, as the CSV
        for j, c in enumerate(cfg["data"]["columns"]):
            # in range, to the rounding of the fifth significant digit
            tol = 5e-5 * max(abs(c["min"]), abs(c["max"]))
            assert c["min"] - tol <= rows[:, j].min()
            assert rows[:, j].max() <= c["max"] + tol
    assert a.name(0) != a.name(nf) and a.name(0).endswith("gt_2011")
    other = spec.source(cell, SEED + 1)
    assert not np.array_equal(a.item(0)[1], other.item(0)[1])


def test_files_are_slices_that_never_start_on_the_same_row():
    """Past the first pass a file is a slice of the pool; the first pass is
    the same whether the pool is drawn yet or not, and no two of a long
    window's files start at the same row (so no chunk repeats)."""
    cell = spec.cell("gas_turbine_f64.ingest")
    a, b = spec.source(cell, SEED), spec.source(cell, SEED)
    n = a.files_per_pass
    first = [a.item(k)[1].copy() for k in range(n)]     # before the pool
    b.item(n)                                           # the pool is drawn
    for k in range(n):
        assert np.array_equal(first[k], b.item(k)[1])
    starts = {((k // n) * a.stride) % a.span + int(a.first_row[k % n])
              for k in range(5000)}
    assert len(starts) == 5000
    x = b.item(7 * n + 2)[1]
    assert x.flags.c_contiguous and x.size == a.rows[2] * len(a.cols)


def test_full_table_has_the_stated_means():
    """At the configuration's own size, each column's mean is near the one
    the source states (the spread is assumed, so within a third of it)."""
    cell = spec.cell("gas_turbine_f64.ingest")
    cfg = cell.config
    src = spec.source(cell, SEED)
    ncol = len(cfg["data"]["columns"])
    table = np.concatenate([src.item(k)[1].reshape(-1, ncol)
                            for k in range(src.files_per_pass)])
    assert table.shape[0] == sum(cfg["data"]["file_rows"]) == 36733
    for j, c in enumerate(cfg["data"]["columns"]):
        assert abs(table[:, j].mean() - c["mean"]) < c["sd"] / 3, c["name"]


def _fnv_java(val: int) -> int:
    """``Utils.fnvhash64`` as YCSB's Java writes it, on 64-bit longs."""
    m = (1 << 64) - 1
    h = 0xCBF29CE484222325
    for _ in range(8):
        h ^= val & 0xFF
        val >>= 8
        h = (h * 1099511628211) & m
    h = h - (1 << 64) if h >> 63 else h
    return abs(h)


def test_fnvhash64_is_ycsbs():
    vals = np.array([0, 1, 2, 255, 256, 123456789, 9_999_999_999])
    got = serve_module().fnvhash64(vals)
    assert [int(g) for g in got] == [_fnv_java(int(v)) for v in vals]


def test_zipfian_draws_ycsbs_head():
    """Item 0 comes with probability 1/zeta(n), item 1 with 0.5^theta times
    that, and the draws stay in range."""
    sm = serve_module()
    u = np.random.default_rng(3).random(400_000)
    z = sm.zipfian(u)
    assert z.min() >= 0 and z.max() <= sm.ITEM_COUNT
    p0 = 1 / sm.ZETAN
    assert abs(np.mean(z == 0) - p0) < 0.002
    assert abs(np.mean(z == 1) - p0 * 0.5 ** 0.99) < 0.002
    keys = sm.scrambled_zipfian(np.random.default_rng(4), 100_000, 36733)
    assert keys.min() >= 0 and keys.max() < 36733
    # scrambled: the hottest key is item 0's hash, not key 0
    hot = np.bincount(keys, minlength=36733).argmax()
    assert hot == _fnv_java(0) % 36733


def test_requests_are_drawn_from_the_seed(small_cell, tmp_path):
    cell = small_cell("gas_turbine_f64.serve_cold")
    keys = []
    for _ in range(2):
        d = spec.driver(cell)(cell, SEED, tmp_path / "w", False)
        d.keys = 1799
        d._keys = iter(())
        keys.append([d._next_key() for _ in range(5000)])
    assert keys[0] == keys[1]
    assert len(set(keys[0])) > 500


def test_unknown_kinds_are_errors():
    import dataclasses

    cell = spec.cell("gas_turbine_f64.ingest")
    with pytest.raises(spec.SpecError):
        spec.source(dataclasses.replace(cell, config={"data": {"kind": "nope"}}), 0)
    with pytest.raises(spec.SpecError):
        spec.store(dataclasses.replace(cell, config={"store": {"kind": "../run"}}),
                   None)
