"""Reliability subsystem tests: durable atomic writes, container salvage,
the scrub CLI, typed degenerate-input errors, the decode watchdog, the
retry policy, and checkpoint quarantine — every failure injected
deterministically through ``repro.reliability.faults``.

The crash-matrix (kill -9) companion lives in ``tests/test_crash_matrix.py``.
"""
import logging
import os

import numpy as np
import pytest

from repro.container import (
    ContainerError,
    ContainerReader,
    ContainerWriter,
)
from repro.container import backends as B, format as F, scrub as scrub_mod
from repro.data.shard_store import ShardStore
from repro.reliability import (
    RetryPolicy,
    durable,
    faults,
    repair,
    retry_call,
    watchdog,
)


def _data(n=5000, seed=7):
    rng = np.random.default_rng(seed)
    return rng.standard_normal(n)


def _write_container(path, x, chunk=1000, **kw):
    kw.setdefault("dtype", np.float64)
    with ContainerWriter(path, **kw) as w:
        for i in range(0, x.size, chunk):
            w.append(x[i : i + chunk])


@pytest.fixture
def clean_registry():
    """Snapshot/restore the backend registry around injected backends."""
    before = dict(B._REGISTRY)
    yield
    B._REGISTRY.clear()
    B._REGISTRY.update(before)


def _no_stage_files(directory):
    return [p for p in os.listdir(directory) if p.endswith(".tmp")]


# ---------------------------------------------------------------------------
# durable atomic writes
# ---------------------------------------------------------------------------


class TestDurableWrite:
    def test_write_bytes_roundtrip_and_overwrite(self, tmp_path):
        p = tmp_path / "f.bin"
        durable.write_bytes(p, b"v1")
        assert p.read_bytes() == b"v1"
        durable.write_bytes(p, b"version-two")
        assert p.read_bytes() == b"version-two"
        assert _no_stage_files(tmp_path) == []

    def test_failed_write_preserves_previous_version(self, tmp_path):
        p = tmp_path / "f.bin"
        durable.write_bytes(p, b"old")
        with pytest.raises(RuntimeError):
            with durable.durable_write(p) as f:
                f.write(b"partial new bytes")
                raise RuntimeError("injected mid-write failure")
        assert p.read_bytes() == b"old"
        assert _no_stage_files(tmp_path) == []

    def test_failed_first_write_leaves_no_file(self, tmp_path):
        p = tmp_path / "f.bin"
        with pytest.raises(RuntimeError):
            with durable.durable_write(p) as f:
                f.write(b"x")
                raise RuntimeError("injected")
        assert not p.exists()
        assert _no_stage_files(tmp_path) == []

    def test_fsync_is_actually_called(self, tmp_path, monkeypatch):
        synced = []
        real_fsync = os.fsync
        monkeypatch.setattr(os, "fsync", lambda fd: (synced.append(fd),
                                                     real_fsync(fd))[1])
        durable.write_bytes(tmp_path / "f.bin", b"data")
        # at least the staged file and (POSIX) the directory
        assert len(synced) >= 2

    def test_fsync_false_skips_fsync_but_stays_atomic(self, tmp_path,
                                                      monkeypatch):
        synced = []
        real_fsync = os.fsync
        monkeypatch.setattr(os, "fsync", lambda fd: (synced.append(fd),
                                                     real_fsync(fd))[1])
        durable.write_bytes(tmp_path / "f.bin", b"data", fsync=False)
        assert synced == []
        assert (tmp_path / "f.bin").read_bytes() == b"data"

    def test_enospc_short_write_preserves_previous(self, tmp_path):
        p = tmp_path / "f.bin"
        durable.write_bytes(p, b"old-good-version")
        df = durable.DurableFile(p)
        faulty = faults.FaultyFile(df.file, fail_on=2)
        faulty.write(b"new " * 10)
        with pytest.raises(OSError):
            faulty.write(b"more " * 10)  # short write, then ENOSPC
        df.discard()
        assert p.read_bytes() == b"old-good-version"
        assert _no_stage_files(tmp_path) == []


class TestContainerWriterDurability:
    def test_failed_write_keeps_old_container_bitwise(self, tmp_path,
                                                      clean_registry):
        """THE satellite regression: a backend failure mid-write must leave
        the previous good file readable bitwise-identically."""
        p = tmp_path / "d.fpc"
        v1 = _data(seed=1)
        _write_container(p, v1, method="identity")
        before = p.read_bytes()

        faults.failing_backend("flaky", fail_on=3, exc=OSError("injected"))
        v2 = _data(seed=2)
        with pytest.raises(OSError):
            _write_container(p, v2, method="identity", backend="flaky")
        assert p.read_bytes() == before
        with ContainerReader(p) as r:
            got = r.read_all()
        assert np.array_equal(got.view(np.uint64), v1.view(np.uint64))
        assert _no_stage_files(tmp_path) == []

    def test_shard_store_failed_write_keeps_old_shard(self, tmp_path,
                                                      clean_registry):
        store = ShardStore(tmp_path, backend="zlib")
        v1 = _data(seed=3)
        store.write("s", v1, chunk=1000, method="identity")

        faults.failing_backend("flaky2", fail_on=2, exc=OSError("injected"))
        store2 = ShardStore(tmp_path, backend="flaky2")
        with pytest.raises(OSError):
            store2.write("s", _data(seed=4), chunk=1000, method="identity")
        got = store.read("s")
        assert np.array_equal(got.view(np.uint64), v1.view(np.uint64))
        assert _no_stage_files(tmp_path) == []

    def test_abort_keeps_previous_version(self, tmp_path):
        p = tmp_path / "d.fpc"
        v1 = _data(seed=5)
        _write_container(p, v1, method="identity")
        before = p.read_bytes()
        w = ContainerWriter(p, dtype=np.float64, method="identity")
        w.append(_data(seed=6)[:100])
        w.abort()
        assert p.read_bytes() == before
        assert _no_stage_files(tmp_path) == []

    def test_durable_false_still_atomic(self, tmp_path):
        p = tmp_path / "d.fpc"
        _write_container(p, _data(seed=7), method="identity", durable=False)
        with ContainerReader(p) as r:
            assert r.nchunks == 5
        assert _no_stage_files(tmp_path) == []

    def test_no_partial_destination_before_close(self, tmp_path):
        p = tmp_path / "d.fpc"
        w = ContainerWriter(p, dtype=np.float64, method="identity")
        w.append(_data()[:500])
        assert not p.exists()  # nothing visible until the atomic commit
        w.close()
        assert p.exists()
        with ContainerReader(p) as r:
            assert r.nchunks == 1


# ---------------------------------------------------------------------------
# typed degenerate-input errors
# ---------------------------------------------------------------------------


class TestDegenerateInputs:
    @pytest.mark.parametrize("content", [
        b"",                      # zero-byte file
        b"RF",                    # shorter than the magic
        b"RFPC" + b"\x01",        # shorter than header+footer minimum
        b"not a container file at all, just prose bytes................",
        bytes(range(64)),         # binary garbage
    ])
    def test_degenerate_files_raise_format_error_naming_path(
            self, tmp_path, content):
        p = tmp_path / "bad.fpc"
        p.write_bytes(content)
        with pytest.raises(F.ContainerFormatError) as ei:
            ContainerReader(p)
        assert str(p) in str(ei.value)

    @pytest.mark.parametrize("content", [b"", b"RFPC", bytes(range(48))])
    def test_degenerate_buffers_raise_container_error(self, content):
        # buffers have no path; the error class contract still holds
        # (never struct.error / IndexError for hostile bytes)
        with pytest.raises(ContainerError):
            ContainerReader(content)

    def test_missing_backend_error_names_package(self, tmp_path,
                                                 monkeypatch):
        p = tmp_path / "z.fpc"
        _write_container(p, _data(n=100), chunk=100, method="identity")
        buf = bytearray(p.read_bytes())
        # header backend str8 "zlib" -> "zstd" (same length, not CRC'd)
        off = buf.index(b"\x04zlib")
        assert off < 32
        buf[off + 1 : off + 5] = b"zstd"
        p.write_bytes(bytes(buf))
        monkeypatch.delitem(B._REGISTRY, "zstd", raising=False)
        with pytest.raises(ContainerError) as ei:
            ContainerReader(p)
        msg = str(ei.value)
        assert "zstandard" in msg and "pip install" in msg
        assert str(p) in msg

    def test_unknown_backend_error_is_actionable(self, tmp_path):
        p = tmp_path / "z.fpc"
        _write_container(p, _data(n=100), chunk=100, method="identity")
        buf = bytearray(p.read_bytes())
        off = buf.index(b"\x04zlib")
        buf[off + 1 : off + 5] = b"qqqq"
        p.write_bytes(bytes(buf))
        with pytest.raises(ContainerError) as ei:
            ContainerReader(p)
        assert "qqqq" in str(ei.value)


# ---------------------------------------------------------------------------
# salvage
# ---------------------------------------------------------------------------


def _entries_of(buf):
    with ContainerReader(buf) as r:
        return list(r._entries), [r.read_chunk(i) for i in range(r.nchunks)]


class TestSalvage:
    def test_one_corrupt_chunk_recovers_the_rest(self, tmp_path):
        p = tmp_path / "d.fpc"
        x = _data()
        _write_container(p, x, user_meta={"tag": "hello"})
        buf = bytearray(p.read_bytes())
        entries, chunks = _entries_of(bytes(buf))
        buf[entries[2]["offset"] + 150] ^= 0xFF

        rep = repair.salvage(bytes(buf))
        assert rep.header_ok and rep.index_ok
        assert rep.expected_chunks == 5 and len(rep.entries) == 4
        assert len(rep.damage) == 1 and rep.damage[0].kind == "record"
        assert rep.user_meta == {"tag": "hello"}

        r = ContainerReader(bytes(buf), salvage=True)
        assert r.salvage_report.entries == rep.entries
        got = [r.read_chunk(i) for i in range(r.nchunks)]
        keep = [c for i, c in enumerate(chunks) if i != 2]
        for g, w in zip(got, keep):
            assert np.array_equal(g.view(np.uint64), w.view(np.uint64))

    def test_truncated_index_and_footer_recovers_all_chunks(self, tmp_path):
        p = tmp_path / "d.fpc"
        x = _data()
        _write_container(p, x)
        buf = p.read_bytes()
        entries, chunks = _entries_of(buf)
        last = entries[-1]
        cut = buf[: last["offset"] + 8 + last["length"]]
        with pytest.raises(ContainerError):
            ContainerReader(cut)  # strict mode keeps refusing
        rep = repair.salvage(cut)
        assert not rep.index_ok and len(rep.entries) == len(entries)
        r = ContainerReader(cut, salvage=True)
        got = r.read_all()
        assert np.array_equal(got.view(np.uint64), x.view(np.uint64))

    def test_truncation_mid_record_recovers_prefix(self, tmp_path):
        p = tmp_path / "d.fpc"
        x = _data()
        _write_container(p, x)
        buf = p.read_bytes()
        entries, chunks = _entries_of(buf)
        cut = buf[: entries[-1]["offset"] + 30]  # inside the last record
        rep = repair.salvage(cut)
        assert len(rep.entries) == len(entries) - 1
        r = ContainerReader(cut, salvage=True)
        got = [r.read_chunk(i) for i in range(r.nchunks)]
        for g, w in zip(got, chunks[:-1]):
            assert np.array_equal(g.view(np.uint64), w.view(np.uint64))

    def test_corrupt_header_is_unrecoverable_but_loud(self, tmp_path):
        p = tmp_path / "d.fpc"
        _write_container(p, _data())
        buf = bytearray(p.read_bytes())
        buf[0] ^= 0xFF  # magic
        rep = repair.salvage(bytes(buf))
        assert not rep.header_ok and rep.entries == []
        with pytest.raises(F.ContainerFormatError):
            ContainerReader(bytes(buf), salvage=True)

    def test_salvage_clean_file_is_a_noop_report(self, tmp_path):
        p = tmp_path / "d.fpc"
        _write_container(p, _data())
        rep = repair.salvage(p)
        assert rep.ok and rep.damage == [] and len(rep.entries) == 5

    def test_salvaged_bytes_rewrite_decodes_strict(self, tmp_path):
        p = tmp_path / "d.fpc"
        x = _data()
        _write_container(p, x, user_meta={"k": 1})
        buf = bytearray(p.read_bytes())
        entries, chunks = _entries_of(bytes(buf))
        buf[entries[0]["offset"] + 100] ^= 0x01
        rep = repair.salvage(bytes(buf))
        fixed = repair.salvaged_bytes(rep, bytes(buf))
        with ContainerReader(fixed) as r:  # strict reader
            assert r.user_meta == {"k": 1}
            got = [r.read_chunk(i) for i in range(r.nchunks)]
        for g, w in zip(got, chunks[1:]):
            assert np.array_equal(g.view(np.uint64), w.view(np.uint64))

    def test_salvage_empty_container(self, tmp_path):
        p = tmp_path / "e.fpc"
        with ContainerWriter(p, dtype=np.float64):
            pass
        rep = repair.salvage(p)
        assert rep.ok and rep.entries == []
        r = ContainerReader(p, salvage=True)
        assert r.nchunks == 0 and r.read_all().size == 0


# ---------------------------------------------------------------------------
# scrub CLI
# ---------------------------------------------------------------------------


class TestScrub:
    def _tree(self, root):
        x = _data()
        for name in ("a", "b", "sub/c"):
            p = root / f"{name}.fpc"
            p.parent.mkdir(parents=True, exist_ok=True)
            _write_container(p, x)
        return x

    def test_verify_clean_tree(self, tmp_path, capsys):
        self._tree(tmp_path)
        assert scrub_mod.main([str(tmp_path)]) == 0
        out = capsys.readouterr().out
        assert out.count("ok ") == 3 and "3 clean" in out

    def test_verify_reports_damage_nonzero_exit(self, tmp_path, capsys):
        self._tree(tmp_path)
        p = tmp_path / "b.fpc"
        buf = bytearray(p.read_bytes())
        entries, _ = _entries_of(bytes(buf))
        buf[entries[1]["offset"] + 64] ^= 0xFF
        p.write_bytes(bytes(buf))
        assert scrub_mod.main([str(tmp_path)]) == 1
        out = capsys.readouterr().out
        assert "DAMAGED" in out and "4/5 chunk(s) intact" in out

    def test_repair_rewrites_and_backs_up(self, tmp_path, capsys):
        self._tree(tmp_path)
        p = tmp_path / "b.fpc"
        buf = bytearray(p.read_bytes())
        entries, chunks = _entries_of(bytes(buf))
        buf[entries[1]["offset"] + 64] ^= 0xFF
        p.write_bytes(bytes(buf))
        assert scrub_mod.main([str(tmp_path), "--repair"]) == 0
        assert (tmp_path / "b.fpc.corrupt").read_bytes() == bytes(buf)
        with ContainerReader(p) as r:  # repaired file verifies strictly
            assert r.nchunks == 4
        # and a second scrub is clean
        assert scrub_mod.main([str(tmp_path)]) == 0

    def test_scrub_skips_staging_files(self, tmp_path, capsys):
        self._tree(tmp_path)
        (tmp_path / "inflight.fpc.123.0.tmp").write_bytes(b"partial")
        assert scrub_mod.main([str(tmp_path)]) == 0
        assert "inflight" not in capsys.readouterr().out


# ---------------------------------------------------------------------------
# decode watchdog
# ---------------------------------------------------------------------------


@pytest.fixture
def fast_watchdog(monkeypatch):
    monkeypatch.setattr(watchdog, "SPAN_TIMEOUT", 0.25)
    yield


class TestWatchdog:
    def _slow_container(self, tmp_path, delay, slow_on):
        gate = faults.slow_backend("wedge", delay=delay, slow_on=slow_on)
        x = _data(n=20000, seed=11)
        p = tmp_path / "w.fpc"
        _write_container(p, x, chunk=2500, backend="wedge",
                         method="identity")
        return p, x, gate

    def test_read_all_degrades_to_serial_and_stays_bitwise(
            self, tmp_path, clean_registry, fast_watchdog, caplog,
            monkeypatch):
        # cold adaptive gate: parallel=True must actually engage the pool
        # here (a warm policy may route a span this small to serial, which
        # is correct serving behavior but not what this test exercises)
        from repro.container import io as cio
        monkeypatch.setattr(cio, "POOL_POLICY", cio.AdaptivePoolPolicy())
        p, x, _ = self._slow_container(tmp_path, delay=1.0, slow_on=3)
        with caplog.at_level(logging.WARNING, "repro.reliability"):
            with ContainerReader(p) as r:
                got = r.read_all(parallel=True)
        assert np.array_equal(got.view(np.uint64), x.view(np.uint64))
        assert any("watchdog" in rec.message for rec in caplog.records)

    def test_iter_chunks_degrades_to_serial(self, tmp_path, clean_registry,
                                            fast_watchdog, caplog):
        p, x, _ = self._slow_container(tmp_path, delay=1.0, slow_on=4)
        with caplog.at_level(logging.WARNING, "repro.reliability"):
            with ContainerReader(p) as r:
                got = np.concatenate(list(r.iter_chunks(prefetch=3)))
        assert np.array_equal(got.view(np.uint64), x.view(np.uint64))
        assert any("watchdog" in rec.message for rec in caplog.records)

    def test_no_watchdog_logs_on_healthy_reads(self, tmp_path, fast_watchdog,
                                               caplog):
        x = _data(n=20000, seed=12)
        p = tmp_path / "h.fpc"
        _write_container(p, x, chunk=2500)
        # a first decode compiles its device programs; the watched read
        # below is the steady state
        with ContainerReader(p) as r:
            r.read_all()
        with caplog.at_level(logging.WARNING, "repro.reliability"):
            with ContainerReader(p) as r:
                got = r.read_all(parallel=True)
        assert np.array_equal(got.view(np.uint64), x.view(np.uint64))
        assert not any("watchdog" in rec.message for rec in caplog.records)

    def test_worker_exceptions_still_propagate(self, tmp_path,
                                               fast_watchdog):
        p = tmp_path / "d.fpc"
        x = _data()
        _write_container(p, x, chunk=1000)
        buf = bytearray(p.read_bytes())
        entries, _ = _entries_of(bytes(buf))
        buf[entries[3]["offset"] + 40] ^= 0xFF
        with pytest.raises(ContainerError):
            with ContainerReader(bytes(buf)) as r:
                r.read_all(parallel=True, workers=2)


# ---------------------------------------------------------------------------
# retry policy
# ---------------------------------------------------------------------------


class TestRetry:
    def test_deterministic_backoff_schedule(self):
        sleeps = []
        flaky = faults.FlakyCallable(lambda: "done", fail_times=3)
        pol = RetryPolicy(attempts=5, base_delay=0.05, max_delay=0.15)
        out = retry_call(flaky, policy=pol, sleep=sleeps.append)
        assert out == "done" and flaky.calls == 4
        assert sleeps == [0.05, 0.1, 0.15]  # exponential, capped, no jitter

    def test_exhaustion_raises_last_error(self):
        flaky = faults.FlakyCallable(lambda: "x", fail_times=10,
                                     exc=OSError("still down"))
        pol = RetryPolicy(attempts=3, base_delay=0.0)
        with pytest.raises(OSError, match="still down"):
            retry_call(flaky, policy=pol, sleep=lambda s: None)
        assert flaky.calls == 3

    def test_non_retryable_raises_immediately(self):
        flaky = faults.FlakyCallable(lambda: "x", fail_times=1,
                                     exc=ValueError("corrupt"))
        pol = RetryPolicy(attempts=5, base_delay=0.0, retry_on=(OSError,))
        with pytest.raises(ValueError):
            retry_call(flaky, policy=pol, sleep=lambda s: None)
        assert flaky.calls == 1

    def test_wire_path_retries_transient_fetch(self):
        from repro.distributed.compress import bucket_from_wire, bucket_to_wire

        g = _data(n=2000, seed=13).astype(np.float32)
        blob = bucket_to_wire(g)
        fetch = faults.FlakyCallable(lambda: blob, fail_times=2)
        pol = RetryPolicy(attempts=4, base_delay=0.0)
        got = bucket_from_wire(fetch, retry=pol)
        assert np.array_equal(got, g.reshape(-1)) and fetch.calls == 3

    def test_wire_path_does_not_retry_corruption(self):
        from repro.distributed.compress import bucket_from_wire, bucket_to_wire

        g = _data(n=2000, seed=14).astype(np.float32)
        blob = bytearray(bucket_to_wire(g))
        blob[len(blob) // 2] ^= 0xFF
        calls = faults.FlakyCallable(lambda: bytes(blob), fail_times=0)
        pol = RetryPolicy(attempts=4, base_delay=0.0)
        with pytest.raises(ContainerError):
            bucket_from_wire(calls, retry=pol)
        assert calls.calls == 1  # corruption is not transient


# ---------------------------------------------------------------------------
# checkpoint quarantine
# ---------------------------------------------------------------------------


class TestCheckpointQuarantine:
    def _mgr(self, root, keep=10):
        from repro.checkpoint import CheckpointManager

        return CheckpointManager(root, keep=keep, method="identity")

    def _tree(self, step):
        return {"w": np.arange(256, dtype=np.float32) + step,
                "b": np.full(32, step, np.float64)}

    def _corrupt(self, root, step):
        p = root / f"step_{step:08d}" / "arr_0.fpc"
        buf = bytearray(p.read_bytes())
        buf[70] ^= 0xFF
        p.write_bytes(bytes(buf))

    def test_corrupt_newest_falls_back_with_quarantine(self, tmp_path,
                                                       caplog):
        mgr = self._mgr(tmp_path)
        mgr.save(1, self._tree(1))
        mgr.save(2, self._tree(2))
        self._corrupt(tmp_path, 2)
        with caplog.at_level(logging.WARNING, "repro.reliability"):
            tree, extra = mgr.restore_latest()
        assert extra["step"] == 1
        assert np.array_equal(tree["w"], self._tree(1)["w"])
        assert (tmp_path / "step_00000002.corrupt").is_dir()
        assert not (tmp_path / "step_00000002").exists()
        assert any("quarantined" in r.message for r in caplog.records)
        # quarantined steps never reappear in discovery
        assert mgr.latest_step() == 1

    def test_all_steps_corrupt_returns_none(self, tmp_path):
        mgr = self._mgr(tmp_path)
        mgr.save(1, self._tree(1))
        mgr.save(2, self._tree(2))
        self._corrupt(tmp_path, 1)
        self._corrupt(tmp_path, 2)
        tree, extra = mgr.restore_latest()
        assert tree is None and extra is None
        assert (tmp_path / "step_00000001.corrupt").is_dir()
        assert (tmp_path / "step_00000002.corrupt").is_dir()

    def test_unreadable_manifest_quarantines(self, tmp_path):
        mgr = self._mgr(tmp_path)
        mgr.save(1, self._tree(1))
        mgr.save(2, self._tree(2))
        (tmp_path / "step_00000002" / "manifest.json").write_text("{broken")
        tree, extra = mgr.restore_latest()
        assert extra["step"] == 1

    def test_repeat_quarantine_names_do_not_collide(self, tmp_path):
        mgr = self._mgr(tmp_path)
        mgr.save(1, self._tree(1))
        self._corrupt(tmp_path, 1)
        assert mgr.restore_latest() == (None, None)
        mgr.save(1, self._tree(1))
        self._corrupt(tmp_path, 1)
        assert mgr.restore_latest() == (None, None)
        assert (tmp_path / "step_00000001.corrupt").is_dir()
        assert (tmp_path / "step_00000001.corrupt.2").is_dir()
