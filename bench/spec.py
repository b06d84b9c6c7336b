"""Find a cell's parts by name.

``BENCHMARK.json`` at the checkout's root names every configuration,
traffic mix, cell and metric.  Each lives in a file of its own, found by
its name alone, so a later cell is added with new files and a new
``workloads`` entry and no edit to a file that is already here:

* ``bench/configs/<config>.json``  the configuration (sizes, source, what
  was assumed); its ``data`` kind names the generator
  ``bench/sources/<kind>.py``, its ``store`` kind the write and read path
  ``bench/stores/<kind>.py`` and its ``store`` control the reference that
  the control run puts in the program's place, ``bench/stores/<control>.py``;
* ``bench/traffic/<mix>.json``     the traffic's parameters; its ``kind``
  names the driver ``bench/drivers/<kind>.py``;
* ``bench/metrics/<metric>.py``    one per-layer metric: a ``read(ctx)``
  that returns a number, or ``None`` where the run holds nothing to read.

Drivers, generators and stores each define one class of a fixed name
(``Driver``, ``Source``, ``Store``), so a new kind is a new file too.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import re
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


class SpecError(RuntimeError):
    """A name in BENCHMARK.json has no file, or a file is malformed."""


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    config: dict
    traffic: dict
    chips: int
    end_to_end: list      # the end-to-end metric entries this cell reports
    per_layer: list       # the per-layer metric entries this cell reports
    root: Path = ROOT     # the checkout whose bench/ holds its parts


def load_benchmark(root: Path = ROOT) -> dict:
    p = root / "BENCHMARK.json"
    try:
        return json.loads(p.read_text())
    except (OSError, ValueError) as e:
        raise SpecError(f"cannot read {p}: {e}") from None


def _json(path: Path) -> dict:
    try:
        return json.loads(path.read_text())
    except FileNotFoundError:
        raise SpecError(f"no file {path}") from None
    except ValueError as e:
        raise SpecError(f"{path} is not JSON: {e}") from None


def _reports(metric: dict, cell: str, e2e_in_cell: set) -> bool:
    if "workloads" in metric:
        return cell in metric["workloads"]
    return metric.get("moves", metric["name"]) in e2e_in_cell


def cell(name: str, bench: dict | None = None, root: Path = ROOT) -> Cell:
    """The cell ``name`` with its configuration and traffic read from their
    files, and the metrics it reports."""
    bench = bench if bench is not None else load_benchmark(root)
    w = next((w for w in bench["workloads"] if w["name"] == name), None)
    if w is None:
        raise SpecError(f"no workload {name!r} in BENCHMARK.json")
    c = next((c for c in bench["configs"] if c["name"] == w["config"]), None)
    if c is None:
        raise SpecError(f"workload {name!r} names no configuration "
                        f"{w['config']!r}")
    config = _json(root / c["file"])
    config.setdefault("name", c["name"])
    traffic = _json(root / "bench" / "traffic" / f"{w['traffic']}.json")
    traffic.setdefault("name", w["traffic"])
    e2e = [m for m in bench["end_to_end"]
           if "workloads" not in m or name in m["workloads"]]
    names = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"] if _reports(m, name, names)]
    return Cell(name, config, traffic, int(w["chips"]), e2e, per_layer, root)


_MODULES: dict = {}


def module(folder: str, name: str, root: Path = ROOT):
    """The module ``bench/<folder>/<name>.py``, loaded once."""
    if not NAME.match(str(name)):
        raise SpecError(f"{name!r} is not a name")
    path = (root / "bench" / folder / f"{name}.py").resolve()
    if path not in _MODULES:
        if not path.is_file():
            raise SpecError(f"no file {path} for {folder} {name!r}")
        spec = importlib.util.spec_from_file_location(
            f"bench_{folder}_{name.replace('.', '_')}", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        _MODULES[path] = mod
    return _MODULES[path]


def part(folder: str, name: str, attr: str, root: Path = ROOT):
    """``attr`` of ``bench/<folder>/<name>.py``: ``part("drivers", "serve",
    "Driver")`` is the serve driver's class."""
    obj = getattr(module(folder, name, root), attr, None)
    if obj is None:
        raise SpecError(f"bench/{folder}/{name}.py defines no {attr}")
    return obj


def metric_module(name: str, root: Path = ROOT):
    """The module ``bench/metrics/<name>.py``."""
    mod = module("metrics", name, root)
    if not callable(getattr(mod, "read", None)):
        raise SpecError(f"bench/metrics/{name}.py has no read(ctx)")
    return mod


def metric_reader(name: str, root: Path = ROOT):
    """The ``read(ctx)`` function of ``bench/metrics/<name>.py``."""
    return metric_module(name, root).read


def source(cell: Cell, seed: int):
    """The cell's data, made from ``seed``."""
    return part("sources", cell.config["data"]["kind"], "Source",
                cell.root)(cell.config, seed)


def store(cell: Cell, path: Path, control: bool = False):
    """The cell's store at ``path``; with ``control``, the reference that
    the control run puts in the program's place."""
    p = cell.config["store"]
    return part("stores", p["control"] if control else p["kind"], "Store",
                cell.root)(path, p)


def driver(cell: Cell):
    """The class that drives the cell's traffic."""
    return part("drivers", cell.traffic["kind"], "Driver", cell.root)
