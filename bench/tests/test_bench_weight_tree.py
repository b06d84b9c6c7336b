"""The ``whisper_base_bf16.ingest`` cell: its weight tree, its store and
control, and ``correct`` coming out false where it has to.

The runs here use a CPU-size cut of the configuration, made in this file:
fewer and narrower tensors and small chunks, through the cell's own source,
store, control and driver."""
from __future__ import annotations

import copy
import dataclasses

import numpy as np
import pytest

import run
import spec

CELL = "whisper_base_bf16.ingest"
SEED = 2**31 + 4242


def _source(cell, seed=SEED):
    return spec.source(cell, seed)


def small(cell: spec.Cell) -> spec.Cell:
    """Every kind of tensor the tree has, at a size a CPU test holds: one
    layer each side, widths cut, a vocabulary of three blocks (the last one
    short), chunks of 2,048."""
    cfg = copy.deepcopy(cell.config)
    cfg["data"].update(d_model=64, encoder_layers=1, decoder_layers=1,
                       encoder_ffn_dim=128, decoder_ffn_dim=128,
                       vocab_size=700, num_mel_bins=8,
                       max_source_positions=40, max_target_positions=24,
                       vocab_block_rows=256)
    cfg["store"]["chunk"] = 2048
    traffic = dict(cell.traffic, warm_scan=60)
    return dataclasses.replace(cell, config=cfg, traffic=traffic)


@pytest.fixture
def cell():
    return small(spec.cell(CELL))


def test_tree_is_the_published_checkpoint():
    """270 items a pass: the 245 tensors, with the token embedding as 26
    blocks of rows; 72,593,920 values in 10 shapes, none over 2 MiB."""
    src = _source(spec.cell(CELL))
    items = src.items
    assert len(items) == 270
    sizes = [int(np.prod(s)) for _n, s, _law in items]
    assert sum(sizes) == 72_593_920
    assert len({s for _n, s, _law in items}) == 10
    assert max(sizes) * 2 == 2 * 2**20
    blocks = [s for n, s, _law in items if ".embed_tokens." in n]
    assert len(blocks) == 26 and blocks[-1] == (665, 512)
    assert sum(s[0] for s in blocks) == 51_865
    names = [n for n, _s, _law in items]
    assert len(set(names)) == 270
    assert not any("k_proj.bias" in n or "proj_out" in n for n in names)


def test_same_words_from_the_same_seed_and_new_words_each_pass(cell):
    a, b = _source(cell), _source(cell)
    other = _source(cell, SEED + 1)
    n = a.items_per_pass
    for k in range(n):
        name, x = a.item(k)
        name2, y = b.item(k)
        assert name == name2 and x.dtype == y.dtype and x.shape == y.shape
        xw = np.asarray(x).view(np.uint16)
        assert np.array_equal(xw, np.asarray(y).view(np.uint16))
        law = a.items[k][2]
        again = np.asarray(a.item(k + n)[1]).view(np.uint16)
        seeded = np.asarray(other.item(k)[1]).view(np.uint16)
        if law == "sinusoid":
            # the encoder's positions are fixed, not drawn
            assert np.array_equal(xw, again) and np.array_equal(xw, seeded)
        else:
            assert not np.array_equal(xw, again), name
            assert not np.array_equal(xw, seeded), name


def test_laws():
    src = _source(spec.cell(CELL))
    by_name = {n: k for k, (n, _s, _law) in enumerate(src.items)}
    for name, sd, mean in [
            ("model.encoder.layers.0.fc1.weight", 1 / np.sqrt(512), 0.0),
            ("model.encoder.layers.0.fc2.weight", 1 / np.sqrt(2048), 0.0),
            ("model.encoder.conv1.weight", 1 / np.sqrt(240), 0.0),
            ("model.decoder.embed_tokens.weight.block00", 0.02, 0.0),
            ("model.decoder.layers.0.fc1.bias", 0.02, 0.0),
            ("model.decoder.layers.0.final_layer_norm.weight", 0.02, 1.0)]:
        x = np.asarray(src.item(by_name[name])[1], np.float32)
        assert abs(x.mean() - mean) < 5 * sd / np.sqrt(x.size) + 0.004, name
        assert abs(x.std() / sd - 1) < 0.1, name
    pos = np.asarray(src.item(by_name["model.encoder.embed_positions.weight"])[1],
                     np.float32)
    assert pos.shape == (1500, 512)
    assert np.array_equal(pos[0], np.r_[np.zeros(256), np.ones(256)])


def _flip_first_bit(a):
    a = np.array(a, copy=True)
    a.reshape(-1).view(np.uint8)[0] ^= 1
    return a


def _plant(monkeypatch, fault):
    from repro.container import io as cio
    from repro.reliability import durable

    if fault == "rename_lost":
        # staged and fsynced, never renamed into place
        def no_rename(self):
            self.file.close()
            self._done = True

        monkeypatch.setattr(durable.DurableFile, "commit", no_rename)
    elif fault == "half_written":
        real = durable.DurableFile.commit

        def half(self):
            self.file.flush()
            self.file.truncate(self.file.tell() // 2)
            real(self)

        monkeypatch.setattr(durable.DurableFile, "commit", half)
    elif fault == "word_altered":
        real = cio.ContainerWriter.encode_record

        def altered(self, chunk):
            return real(self, _flip_first_bit(np.asarray(chunk)))

        monkeypatch.setattr(cio.ContainerWriter, "encode_record", altered)


@pytest.mark.parametrize("case", ["sound", "control", "rename_lost",
                                  "half_written", "word_altered"])
def test_correct_only_when_sound(case, cell, tmp_path, monkeypatch):
    driver = spec.driver(cell)
    if case not in ("sound", "control"):
        real_setup = driver.setup

        def setup_then_break(self):
            real_setup(self)
            _plant(monkeypatch, case)

        monkeypatch.setattr(driver, "setup", setup_then_break)
    r = run.run_cell(cell, SEED, 0.5, False, tmp_path / "work",
                     control=case == "control")
    checks = r["checks"]
    assert all(c["limit"] == 0 for c in checks.values())
    assert r["attempted"] >= 1
    if case == "sound":
        assert r["correct"], checks
        assert r["failed"] == 0
        assert r["compiles_in_window"] == 0
        assert r["metrics"]["stored_ratio"]["value"] > 0
        return
    assert not r["correct"], (case, checks)
    assert (checks["mismatched_words"]["value"] > 0
            or checks["unreadable_items"]["value"] > 0), (case, checks)


def test_cell_loads_and_new_metrics_read_nothing_from_nothing():
    c = spec.cell(CELL)
    assert c.chips == 1 and c.config["data"]["kind"] == "weight_tree"
    assert c.traffic["warm_scan"] == 270 and c.traffic["trace_work"] == 12
    names = {m["name"] for m in c.per_layer}
    assert {"identity_share", "probe_ms_per_mib"} <= names
    for name in ("identity_share", "probe_ms_per_mib"):
        assert spec.metric_reader(name)({}) is None


def test_identity_share_reads_what_phase_2_shipped():
    from repro.core import scoring

    read = spec.metric_reader("identity_share")
    scoring.PHASE2.reset()
    ctx = {"window": {"chunks": 4}, "segment": None}
    assert read(ctx) is None
    scoring.PHASE2.shipped.update({"identity": 3, "multiply_shift": 1})
    assert read(ctx) == pytest.approx(75.0)
    scoring.PHASE2.reset()
