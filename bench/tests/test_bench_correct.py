"""``correct`` comes out false where it has to.

Each test drives a whole run of a cell (set-up, window, comparison) at a
CPU size, without the harness's look for a chip: once sound, once with the
plain reference one precision down in the program's place (the control),
and once for each fault the cell can have, planted underneath the timed
path.  The exchange between chips is no fault here: every cell runs on one
chip.
"""
from __future__ import annotations

import numpy as np
import pytest

import run
import spec

SEED = 2**31 + 4242
CELLS = ["gas_turbine_f64.ingest", "gas_turbine_f64.serve_cold"]


def _run(cell, tmp_path, control=False):
    return run.run_cell(cell, SEED, 0.5, False, tmp_path / "work",
                        control=control)


@pytest.mark.parametrize("name", CELLS)
def test_sound_run_is_correct(name, small_cell, tmp_path):
    r = _run(small_cell(name), tmp_path)
    assert r["correct"], r["checks"]
    assert list(r)[-1] == "checks"
    assert all(c["limit"] == 0 for c in r["checks"].values())
    assert r["attempted"] >= 1 and r["failed"] == 0


@pytest.mark.parametrize("name", CELLS)
def test_control_is_not_correct(name, small_cell, tmp_path):
    r = _run(small_cell(name), tmp_path, control=True)
    assert not r["correct"]
    assert r["checks"]["mismatched_words"]["value"] > 0


def _flip_first_bit(a):
    a = np.array(a, copy=True)
    a.reshape(-1).view(np.uint8)[0] ^= 1
    return a


def _ingest_faults(monkeypatch, fault):
    from repro.container import io as cio
    from repro.core import streaming
    from repro.data import dataset

    if fault == "state_unchanged":
        # the write returns, and nothing was committed
        monkeypatch.setattr(dataset.DatasetWriter, "write",
                            lambda self, pieces, shape=None: {})
    elif fault == "half_left_out":
        real = streaming.stream_chunks

        def every_other(writer, chunks, queue_depth=None):
            return real(writer, (c for i, c in enumerate(chunks) if i % 2 == 0),
                        queue_depth)

        monkeypatch.setattr(streaming, "stream_chunks", every_other)
    elif fault == "answer_altered":
        real = cio.ContainerWriter.encode_record

        def altered(self, chunk):
            return real(self, _flip_first_bit(np.asarray(chunk)))

        monkeypatch.setattr(cio.ContainerWriter, "encode_record", altered)


def _serve_faults(monkeypatch, fault):
    from repro.serving import server

    real = server.TensorServer._decode_span
    if fault == "state_unchanged":
        first = {}

        def stale(self, name, lo, hi):
            if "a" not in first:
                first["a"] = real(self, name, lo, hi)
            return first["a"]

        monkeypatch.setattr(server.TensorServer, "_decode_span", stale)
    elif fault == "half_left_out":
        def first_half(self, name, lo, hi):
            a = real(self, name, lo, hi)
            return a[:a.size // 2]

        monkeypatch.setattr(server.TensorServer, "_decode_span", first_half)
    elif fault == "answer_altered":
        # the lowest bit of every value decoded: a read of any record sees it
        def altered(self, name, lo, hi):
            a = np.array(real(self, name, lo, hi), copy=True)
            w = a.view(f"u{a.itemsize}")
            w ^= 1
            return a

        monkeypatch.setattr(server.TensorServer, "_decode_span", altered)


FAULTS = ["state_unchanged", "half_left_out", "answer_altered"]


@pytest.mark.parametrize("fault", FAULTS)
@pytest.mark.parametrize("name", CELLS)
def test_fault_is_not_correct(name, fault, small_cell, tmp_path, monkeypatch):
    cell = small_cell(name)
    if cell.traffic["kind"] == "serve":
        # the fault sits in the read path; the store is written soundly
        plant = _serve_faults
    else:
        plant = _ingest_faults
    driver = spec.driver(cell)
    real_setup = driver.setup

    def setup_then_break(self):
        real_setup(self)
        plant(monkeypatch, fault)

    monkeypatch.setattr(driver, "setup", setup_then_break)
    r = _run(cell, tmp_path)
    assert not r["correct"], (fault, r["checks"])
