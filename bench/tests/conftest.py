"""Fixtures for the benchmark's own tests: the harness's modules on the
path, and each cell cut to a size a CPU test run holds (a few short files,
small chunks); only the sizes change, every path stays the cell's own."""
from __future__ import annotations

import copy
import dataclasses
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
for p in (BENCH.parent / "src", BENCH):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

import spec  # noqa: E402


def small(cell: spec.Cell) -> spec.Cell:
    cfg = copy.deepcopy(cell.config)
    cfg["data"]["file_names"] = cfg["data"]["file_names"][:3]
    cfg["data"]["file_rows"] = [600, 600, 599]
    cfg["store"]["chunk"] = 2048
    traffic = dict(cell.traffic)
    if traffic["kind"] == "serve":
        traffic["cache_bytes"] = 2048 * 8          # one chunk of three
    return dataclasses.replace(cell, config=cfg, traffic=traffic)


@pytest.fixture
def small_cell():
    return lambda name: small(spec.cell(name))
