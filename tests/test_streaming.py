"""Streaming bounded-memory encode pipeline (core/streaming + data/dataset).

Four contracts under test:

* **Geometry** — ``iter_fixed_chunks`` re-chunks arbitrary piece boundaries
  into exact container geometry, by view where aligned, loudly on dtype
  mismatch.
* **Byte identity** — a container streamed through ``stream_chunks`` over
  ragged pieces is bitwise equal to the one-shot ``append``-loop container
  at equal chunk geometry, across f64/f32/bf16 × every registered backend,
  including when the chunk-window drift-refresh policy fires mid-stream.
* **Bounded memory** — ``ShardStore.write_stream`` ingests a multi-window
  generator with peak traced allocations a small fraction of the logical
  size (the ShardStore.write full-materialization bugfix).
* **Resumability** — a dataset killed (-9) or failed mid-write resumes at
  the last durably committed part: committed containers are never
  re-encoded (bitwise-unchanged files, exact skip watermark) and the final
  dataset reads back bitwise equal to the payload.
"""
import io
import json
import signal
import subprocess
import sys
import tracemalloc
from pathlib import Path

import numpy as np
import pytest

from repro.container import ContainerReader, ContainerWriter, available_backends
from repro.core import streaming as S
from repro.core.float_bits import F64
from repro.data.dataset import DatasetError, DatasetReader, DatasetWriter
from repro.data.shard_store import ShardStore
from tests._helpers import words as _words

REPO = Path(__file__).resolve().parent.parent
CHILD = Path(__file__).resolve().parent / "crash_child.py"

BACKENDS = available_backends()
FLOAT_DTYPES = ("float64", "float32", "bfloat16")


def _resolve(dtype: str):
    if dtype == "bfloat16":
        import ml_dtypes

        return np.dtype(ml_dtypes.bfloat16)
    return np.dtype(dtype)


def _drifting(n: int, dtype: str) -> np.ndarray:
    """Same-binade data whose second half jumps distribution (forces the
    window fingerprint past the drift threshold)."""
    rng = np.random.default_rng(7)
    x = 1.0 + rng.integers(0, 1 << 12, n) / float(1 << 14)
    x[n // 2 :] = x[n // 2 :] * 4096.0 + 3.0
    return x.astype(_resolve(dtype))


# ---------------------------------------------------------------------------
# iter_fixed_chunks: geometry + values
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("piece_sizes", [
    [0], [5], [100], [64, 64, 64], [1, 2, 3, 4, 5], [200, 1, 7],
    [0, 0, 50, 0], [33] * 9,
])
@pytest.mark.parametrize("chunk", [1, 7, 64])
def test_iter_fixed_chunks_geometry(piece_sizes, chunk):
    total = sum(piece_sizes)
    flat = np.arange(total, dtype=np.float64)
    bounds = np.cumsum([0] + piece_sizes)
    pieces = (flat[a:b] for a, b in zip(bounds[:-1], bounds[1:]))
    out = list(S.iter_fixed_chunks(pieces, chunk, dtype=np.float64))
    # every chunk but the last is exactly `chunk`; the tail is the remainder
    assert [c.size for c in out[:-1]] == [chunk] * max(len(out) - 1, 0)
    if total:
        assert out[-1].size == (total % chunk or chunk)
    else:
        assert out == []
    assert sum(c.size for c in out) == total
    if out:
        assert np.array_equal(np.concatenate(out), flat)


def test_iter_fixed_chunks_views_when_aligned():
    """Aligned pieces must stream by view — no copies of the payload."""
    x = np.arange(4 * 64, dtype=np.float64)
    out = list(S.iter_fixed_chunks((x,), 64))
    assert all(c.base is x for c in out)


def test_iter_fixed_chunks_dtype_mismatch_raises():
    with pytest.raises(ValueError, match="dtype"):
        list(S.iter_fixed_chunks([np.zeros(4, np.float32)], 2,
                                 dtype=np.float64))


def test_iter_fixed_chunks_rejects_bad_chunk():
    with pytest.raises(ValueError, match="chunk_elems"):
        list(S.iter_fixed_chunks([np.zeros(4)], 0))


# ---------------------------------------------------------------------------
# WindowPlanner: probe-once, per-window reuse, drift refresh
# ---------------------------------------------------------------------------

def _planner(**kw):
    kw.setdefault("spec", F64)
    kw.setdefault("probe_elems", 256)
    kw.setdefault("probe_threshold", 512)
    kw.setdefault("window_bytes", 1024 * 8)  # one 1024-elem f64 chunk
    return S.WindowPlanner(**kw)


def test_window_planner_probes_once_then_reuses():
    p = _planner()
    rng = np.random.default_rng(0)
    steady = lambda: (1.0 + rng.integers(0, 1 << 12, 1024)
                      / float(1 << 14)).astype(np.float64)
    for _ in range(4):
        p.encode(steady())
    assert p.stats["probes"] == 1
    assert p.picked is not None
    # chunks 2..4 each close a window on steady data: reused, never refreshed
    assert p.stats["windows"] == 3
    assert p.stats["reused_windows"] == 3
    assert p.stats["drift_refreshes"] == 0


def test_window_planner_drift_refresh_fires():
    p = _planner()
    rng = np.random.default_rng(1)
    steady = (1.0 + rng.integers(0, 1 << 12, 1024) / float(1 << 14)
              ).astype(np.float64)
    shifted = (steady * 4096.0 + 3.0).astype(np.float64)
    p.encode(steady)
    p.encode(steady)            # window 1: reuse
    p.encode(shifted)           # window 2: drifted -> re-select
    assert p.stats["drift_refreshes"] == 1
    assert p.stats["reused_windows"] == 1


def test_window_planner_small_chunks_never_window():
    """Sub-threshold chunks run full auto per chunk — no probe, no windows
    (the historical small-array behavior, bit-for-bit)."""
    p = _planner()
    for _ in range(8):
        p.encode(np.linspace(1.0, 2.0, 100))
    assert p.stats == {"probes": 0, "windows": 0, "reused_windows": 0,
                       "drift_refreshes": 0}
    assert p.picked is None


# ---------------------------------------------------------------------------
# byte identity: streamed == one-shot, per dtype x backend
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("dtype", FLOAT_DTYPES)
def test_stream_bitwise_equals_oneshot(backend, dtype, monkeypatch):
    # small window so the drift-refresh policy fires inside the test data
    monkeypatch.setenv("REPRO_STREAM_WINDOW_BYTES", "65536")
    x = _drifting(120000, dtype)
    chunk = 20000  # > probe threshold: the windowed policy is exercised

    one = io.BytesIO()
    with ContainerWriter(one, dtype=x.dtype, backend=backend) as w:
        for s in range(0, x.size, chunk):
            w.append(x[s : s + chunk])

    streamed = io.BytesIO()
    with ContainerWriter(streamed, dtype=x.dtype, backend=backend) as w:
        pieces = (x[i * 31007 : (i + 1) * 31007]
                  for i in range(-(-x.size // 31007)))
        S.stream_chunks(w, S.iter_fixed_chunks(pieces, chunk, dtype=x.dtype))

    assert one.getvalue() == streamed.getvalue(), (
        f"streamed container bytes differ from one-shot for dtype={dtype} "
        f"backend={backend}"
    )
    with ContainerReader(streamed.getvalue()) as r:
        assert np.array_equal(_words(r.read_all()), _words(x))


def test_stream_chunks_propagates_write_failure():
    """An I/O failure on the write-behind thread re-raises in the caller and
    never deadlocks the bounded queue."""
    x = np.linspace(1.0, 2.0, 4096)

    class Boom(RuntimeError):
        pass

    class FailingWriter:
        def __init__(self, inner):
            self.inner = inner
            self.writes = 0

        def encode_record(self, chunk):
            return self.inner.encode_record(chunk)

        def _write_record(self, *rec):
            self.writes += 1
            if self.writes >= 2:
                raise Boom("disk full")
            return self.inner._write_record(*rec)

    with ContainerWriter(io.BytesIO(), dtype=np.float64,
                         method="identity") as w:
        fw = FailingWriter(w)
        with pytest.raises(Boom):
            S.stream_chunks(fw, S.iter_fixed_chunks((x,) * 16, 1024),
                            queue_depth=2)


@pytest.mark.parametrize("writer", ["container", "dataset"])
def test_chunk_encode_error_reaches_writer_caller(writer, tmp_path,
                                                  monkeypatch):
    """Only a TransformError (the data rejected the transform) falls back
    to identity.  Any other error raised inside a chunk encode, such as a
    device program the compiler refused, reaches the caller instead of
    turning into a silent identity write."""
    real = S.pipeline.apply_transform

    class DeviceFault(RuntimeError):
        pass

    def failing(chunk, method, *a, **kw):
        if method != "identity":
            raise DeviceFault("compile refused")
        return real(chunk, method, *a, **kw)

    monkeypatch.setattr(S.pipeline, "apply_transform", failing)
    x = 1.0 + np.arange(4096) / 256.0
    with pytest.raises(DeviceFault):
        if writer == "container":
            with ContainerWriter(tmp_path / "f.fpc", dtype=np.float64,
                                 method="compact_bins",
                                 params={"n_bins": 4}) as w:
                w.append(x)
        else:
            DatasetWriter(tmp_path / "ds", dtype=np.float64, chunk=1024,
                          method="compact_bins").write([x])


def test_shard_write_empty_keeps_single_chunk():
    """Empty shards still carry one empty chunk (pre-streaming layout)."""
    import tempfile

    store = ShardStore(tempfile.mkdtemp())
    store.write("e", np.empty((0,), np.float64))
    m = store.manifest("e")
    assert len(m["chunks"]) == 1 and m["shape"] == [0]
    assert store.read("e").size == 0


# ---------------------------------------------------------------------------
# bounded memory: the ShardStore.write materialization bugfix
# ---------------------------------------------------------------------------

def test_write_stream_memory_stays_under_budget(tmp_path):
    """Streaming a 16 MiB logical tensor must not allocate anywhere near
    16 MiB at once: peak traced allocations stay under a quarter of the
    logical size (chunk + piece + write-behind queue only)."""
    store = ShardStore(tmp_path)
    piece_elems = 1 << 15          # 256 KiB per piece
    n_pieces = 64                  # 16 MiB logical
    logical = piece_elems * n_pieces * 8

    def pieces(n):
        for i in range(n):
            yield 1.0 + np.arange(piece_elems, dtype=np.float64) / (i + 2.0)

    # warm the encode path (jit caches, zlib state) outside the trace
    store.write_stream("warm", pieces(2), np.float64, chunk=1 << 14,
                       method="identity")

    tracemalloc.start()
    tracemalloc.reset_peak()
    store.write_stream("big", pieces(n_pieces), np.float64, chunk=1 << 14,
                       method="identity")
    _, peak = tracemalloc.get_traced_memory()
    tracemalloc.stop()

    budget = logical // 4
    assert peak < budget, (
        f"peak traced memory {peak} bytes >= budget {budget} for a "
        f"{logical}-byte logical stream — ingestion is not bounded"
    )
    got = store.read("big")
    assert got.size == piece_elems * n_pieces
    assert np.array_equal(
        got[:piece_elems], 1.0 + np.arange(piece_elems, dtype=np.float64) / 2.0
    )


# ---------------------------------------------------------------------------
# dataset: round-trip, serving protocol, resume
# ---------------------------------------------------------------------------

def _payload(n=120000, dtype=np.float64):
    return (1.0 + np.arange(n, dtype=np.float64) / 3.0).astype(dtype)


def test_dataset_roundtrip_and_reader_protocol(tmp_path):
    x = _payload()
    w = DatasetWriter(tmp_path / "ds", dtype=np.float64, chunk=10000,
                      part_elems=40000)
    man = w.write([x])
    assert man["complete"] and man["total"] == x.size
    assert [p["n"] for p in man["parts"]] == [40000, 40000, 40000]
    with DatasetReader(tmp_path / "ds") as r:
        assert r.nchunks == 12 and r.n == x.size
        assert r.chunk_offsets()[-1] == x.size
        assert np.array_equal(_words(r.read_all()), _words(x))
        assert np.array_equal(r.read_range(35000, 95001), x[35000:95001])
        assert np.array_equal(r.read_chunk(5), x[50000:60000])
        lo, hi = r.covering_chunks(39999, 40001)  # straddles a part seam
        assert (lo, hi) == (3, 5)
        with pytest.raises(IndexError):
            r.read_range(0, x.size + 1)


def test_dataset_ragged_tail_and_shape(tmp_path):
    x = _payload(95000)
    w = DatasetWriter(tmp_path / "ds", dtype=np.float64, chunk=10000,
                      part_elems=40000)
    man = w.write([x], shape=[95, 1000])
    assert [p["n"] for p in man["parts"]] == [40000, 40000, 15000]
    assert man["shape"] == [95, 1000]
    with DatasetReader(tmp_path / "ds") as r:
        assert r.user_meta["shape"] == [95, 1000]
        assert np.array_equal(r.read_all(), x)


def test_dataset_serves_through_tensor_server(tmp_path):
    from repro.serving import TensorServer

    x = _payload(60000).astype(np.float32)
    DatasetWriter(tmp_path / "big", dtype=np.float32, chunk=8192,
                  part_elems=16384).write([x], shape=[600, 100])
    ShardStore(tmp_path).write("small", x[:100])
    with TensorServer(tmp_path) as srv:
        assert srv.names() == ["big", "small"]
        got = srv.read("big")
        assert got.shape == (600, 100)
        assert np.array_equal(_words(got.reshape(-1)), _words(x))
        # slices cross part boundaries transparently
        assert np.array_equal(srv.read_slice("big", 16000, 33000),
                              x[16000:33000])


def test_dataset_resume_after_midstream_failure(tmp_path):
    x = _payload()

    class Boom(Exception):
        pass

    def broken():
        yield x[:50000]
        raise Boom

    w = DatasetWriter(tmp_path / "ds", dtype=np.float64, chunk=10000,
                      part_elems=20000)
    with pytest.raises(Boom):
        w.write(broken())
    man = w.manifest
    assert not man["complete"]
    assert man["total"] == 40000  # committed watermark is part-aligned
    committed = {p["name"]: (tmp_path / "ds" / p["name"]).read_bytes()
                 for p in man["parts"]}

    w2 = DatasetWriter(tmp_path / "ds")
    man2 = w2.write([x])
    assert w2.stats["skipped_elements"] == 40000
    assert w2.stats["parts_skipped"] == len(committed)
    assert w2.stats["encoded_elements"] == x.size - 40000
    for name, blob in committed.items():
        assert (tmp_path / "ds" / name).read_bytes() == blob, (
            f"committed part {name} was re-encoded on resume"
        )
    assert man2["complete"]
    with DatasetReader(tmp_path / "ds") as r:
        assert np.array_equal(_words(r.read_all()), _words(x))


def test_dataset_complete_is_immutable(tmp_path):
    w = DatasetWriter(tmp_path / "ds", dtype=np.float64, chunk=100)
    w.write([_payload(250)])
    with pytest.raises(DatasetError, match="complete"):
        DatasetWriter(tmp_path / "ds").write([_payload(250)])


def test_dataset_resume_stream_mismatch_raises(tmp_path):
    w = DatasetWriter(tmp_path / "ds", dtype=np.float64, chunk=100,
                      part_elems=200)

    class Boom(Exception):
        pass

    def broken():
        yield _payload(300)
        raise Boom

    with pytest.raises(Boom):
        w.write(broken())
    with pytest.raises(DatasetError, match="committed prefix"):
        DatasetWriter(tmp_path / "ds").write([_payload(50)])  # too short


def test_dataset_empty_stream(tmp_path):
    man = DatasetWriter(tmp_path / "ds", dtype=np.float32,
                        chunk=64).write([])
    assert man["complete"] and man["parts"] == [] and man["shape"] == [0]
    with DatasetReader(tmp_path / "ds") as r:
        assert r.nchunks == 0 and r.read_all().size == 0


# ---------------------------------------------------------------------------
# kill -9 crash matrix for the dataset writer
# ---------------------------------------------------------------------------

def _run_child(dest: Path, point: str):
    import os

    env = dict(os.environ)
    env["PYTHONPATH"] = str(REPO / "src")
    env.setdefault("JAX_PLATFORMS", "cpu")
    return subprocess.run(
        [sys.executable, str(CHILD), "dataset", str(dest), point],
        env=env, capture_output=True, text=True, timeout=120,
    )


def _child_payload():
    return np.arange(1024, dtype=np.float64) * 1 + 1  # crash_child payload(1)


# boundaries of the per-part two-phase commit (hit counts pick the part):
#   dataset.commit:K   — part K-1's container is durable, manifest not yet
#   dataset.manifest:K — manifest naming part K-1 is durable
#   durable.replaced:2 — inside part 0's own rename (hit 1 = the initial
#                        manifest write)
DATASET_POINTS = ["dataset.commit:1", "dataset.commit:2",
                  "dataset.manifest:1", "dataset.manifest:2",
                  "durable.replaced:2"]


def test_dataset_child_sanity_completes(tmp_path):
    r = _run_child(tmp_path, "none")
    assert r.returncode == 0, r.stderr
    with DatasetReader(tmp_path / "ds") as rd:
        assert np.array_equal(rd.read_all(), _child_payload())


@pytest.mark.parametrize("point", DATASET_POINTS)
def test_dataset_kill9_resumes_at_last_committed_part(tmp_path, point):
    r = _run_child(tmp_path, point)
    assert r.returncode == -signal.SIGKILL, (
        f"crash point {point} did not fire: rc={r.returncode}\n{r.stderr}"
    )
    root = tmp_path / "ds"
    man = json.loads((root / "manifest.json").read_bytes())
    assert not man["complete"]
    assert man["total"] % man["chunk"] == 0, (
        "incomplete manifest committed a non-chunk-aligned total"
    )
    committed = {p["name"]: (root / p["name"]).read_bytes()
                 for p in man["parts"]}

    # resume in-process with the identical stream and settings
    w = DatasetWriter(root, method="identity")
    w.write([_child_payload()])
    assert w.stats["skipped_elements"] == man["total"]
    assert w.stats["parts_skipped"] == len(committed)
    for name, blob in committed.items():
        assert (root / name).read_bytes() == blob, (
            f"{point}: committed part {name} was re-encoded on resume"
        )
    with DatasetReader(root) as rd:
        got = rd.read_all()
        assert np.array_equal(got.view(np.uint64),
                              _child_payload().view(np.uint64))
