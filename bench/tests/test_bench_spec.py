"""BENCHMARK.json against the contract it is held to, and the loader that
finds every part of a cell by name."""
from __future__ import annotations

import json
import re
import shutil

import pytest

import spec

BENCH = spec.load_benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def test_top_level_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["bench"]
    assert 1 <= BENCH["run_seconds"] <= 51


def test_names_and_units():
    names = [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
    names += [w["name"] for w in BENCH["workloads"]]
    names += [c["name"] for c in BENCH["configs"]]
    assert len(names) == len(set(names))
    for n in names:
        assert NAME.match(n), n
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")


def test_bounds():
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    for m in e2e.values():
        assert 0.01 <= m["bound"] <= 0.25, m
        assert m["source"] in ("host_clock", "device_trace")


@pytest.mark.parametrize("w", BENCH["workloads"], ids=lambda w: w["name"])
def test_every_cell_resolves_and_reports(w):
    c = spec.cell(w["name"])
    assert c.chips == 1
    e2e = {m["name"] for m in c.end_to_end}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert c.per_layer
    for m in c.per_layer:
        assert m["moves"] in e2e, (m["name"], w["name"])
        assert callable(spec.metric_reader(m["name"]))


NEW_DRIVER = """
import time


class Driver:
    def __init__(self, cell, seed, work, control=False):
        self.src = spec_source(cell, seed)
        self.store = spec_store(cell, work / "store", control)
        self.attempted = self.failed = 0

    def setup(self):
        pass

    def window(self, seconds, span, limit=None):
        t0 = time.perf_counter()
        for k in range(3):
            self.store.write(str(k), self.src.item(k))
        self.attempted += 3
        dt = time.perf_counter() - t0
        return {"window_s": dt, "metrics": {"ingest_mb_s": 1.0,
                                            "stored_ratio": 1.0}}

    def free(self):
        pass

    def check(self, checks):
        bad = sum(self.store.read(str(k)) != self.src.item(k)
                  for k in range(3))
        checks.add("mismatched_items", bad, 0)


import spec  # noqa: E402

spec_source, spec_store = spec.source, spec.store
"""

NEW_SOURCE = """
class Source:
    def __init__(self, config, seed):
        self.base = config["data"]["base"] + seed

    def item(self, k):
        return self.base + k
"""

NEW_STORE = """
class Store:
    def __init__(self, root, p):
        self.d = {}

    def write(self, name, x):
        self.d[name] = x

    def read(self, name):
        return self.d[name]
"""


def test_a_cell_added_as_files_only(tmp_path):
    """A new configuration, traffic mix, metric, and a new kind of driver,
    data and store are new files and entries only: no file that is already
    there changes, and the new cell runs through the harness."""
    import run

    shutil.copytree(spec.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("out", "tests", "__pycache__"))
    before = {p: p.read_bytes() for p in (tmp_path / "bench").rglob("*")
              if p.is_file()}
    bench = json.loads(json.dumps(BENCH))
    b = tmp_path / "bench"
    (b / "configs/counter.json").write_text(json.dumps(
        {"data": {"kind": "counter", "base": 10},
         "store": {"kind": "memory", "control": "memory"}}))
    (b / "traffic/replay.json").write_text(json.dumps({"kind": "replay"}))
    (b / "drivers/replay.py").write_text(NEW_DRIVER)
    (b / "sources/counter.py").write_text(NEW_SOURCE)
    (b / "stores/memory.py").write_text(NEW_STORE)
    (b / "metrics/new_metric.py").write_text(
        "def read(ctx):\n    return 42.0\n")
    bench["configs"].append({"name": "counter", "source": "x",
                             "file": "bench/configs/counter.json",
                             "reduced": [], "why": "x"})
    bench["workloads"].append({"name": "counter.replay", "config": "counter",
                               "traffic": "replay", "chips": 1, "why": "x"})
    bench["per_layer"].append({"name": "new_metric", "unit": "%",
                               "better": "higher", "source": "device_trace",
                               "layer": "device", "moves": "ingest_mb_s",
                               "workloads": ["counter.replay"]})
    for m in bench["end_to_end"]:
        if m["name"] == "ingest_mb_s":
            m["workloads"].append("counter.replay")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))

    c = spec.cell("counter.replay", root=tmp_path)
    assert c.traffic["kind"] == "replay"
    assert [m["name"] for m in c.per_layer] == ["new_metric"]
    assert spec.metric_reader("new_metric", root=tmp_path)({}) == 42.0
    assert {m["name"] for m in c.end_to_end} == {"ingest_mb_s", "stored_ratio",
                                                 "setup_s"}
    r = run.run_cell(c, 5, 0.1, False, tmp_path / "work")
    assert r["correct"] and r["attempted"] == 3
    assert set(r["metrics"]) == {"ingest_mb_s", "stored_ratio", "setup_s"}
    changed = [p for p, b in before.items() if p.read_bytes() != b]
    assert changed == []


def test_missing_parts_are_errors(tmp_path):
    with pytest.raises(spec.SpecError):
        spec.cell("no_such.cell")
    with pytest.raises(spec.SpecError):
        spec.metric_reader("no_such_metric")
    with pytest.raises(spec.SpecError):
        spec.part("drivers", "no_such_kind", "Driver")
    with pytest.raises(spec.SpecError):
        spec.part("drivers", "../run", "Driver")
