"""Container format + streaming I/O: round-trips across transform families,
dtypes and backends; random access; error paths (corrupt header, truncated
records, bad checksums); the backend registry."""
import io

import numpy as np
import pytest

import jax.numpy as jnp

from repro import container
from repro.container import (
    ChecksumError,
    ContainerError,
    ContainerFormatError,
    ContainerReader,
    ContainerWriter,
    available_backends,
    deserialize_chunk,
    serialize_chunk,
)
from repro.core import pipeline
from repro.data import chicago_taxi_fares, gas_turbine_emissions

BACKENDS = available_backends()


def _words(x):
    x = np.asarray(x)
    if x.dtype.kind == "V" or str(x.dtype) == "bfloat16":
        return x.view(np.uint16)
    return x.view({8: np.uint64, 4: np.uint32, 2: np.uint16}[x.dtype.itemsize])


# ---------------------------------------------------------------------------
# chunk record round-trips (format layer)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("method,params", [
    ("identity", {}),
    ("compact_bins", {"n_bins": 4}),
    ("multiply_shift", {"D": 4}),
    ("shift_separate", {"D": 2}),
    ("shift_save_even", {"D": 8}),
])
@pytest.mark.parametrize("backend", BACKENDS)
def test_chunk_record_roundtrip_per_family(method, params, backend):
    rng = np.random.default_rng(7)
    x = 1.0 + rng.integers(0, 1 << 20, 3000) / (1 << 22)
    enc = pipeline.apply_transform(x, method, params)
    buf = serialize_chunk(enc, backend)
    enc2 = deserialize_chunk(buf, backend, spec_name=enc.spec_name)
    assert enc2.method == enc.method
    assert enc2.params == enc.params
    assert enc2.n == enc.n and enc2.n_active == enc.n_active
    assert np.array_equal(_words(enc2.data), _words(enc.data))
    back = pipeline.decode(enc2)
    assert np.array_equal(_words(back), _words(x))


def test_chunk_record_passthrough_values():
    x = np.array([0.0, -0.0, np.nan, np.inf, -np.inf, 1.5, -2.25, 1e-300])
    enc = pipeline.encode(x, method="auto")
    enc2 = container.loads(container.dumps(enc))
    assert np.array_equal(_words(pipeline.decode(enc2)), _words(x))


@pytest.mark.parametrize("dtype", [np.float64, np.float32, "bfloat16"])
def test_dumps_loads_dtypes(dtype):
    rng = np.random.default_rng(11)
    if dtype == "bfloat16":
        x = jnp.asarray(rng.uniform(1, 2, 2000), jnp.bfloat16)
    else:
        x = jnp.asarray(rng.uniform(1, 2, 2000), dtype)
    enc = pipeline.encode(x)
    enc2 = container.loads(container.dumps(enc))
    assert enc2.spec_name == enc.spec_name
    assert np.array_equal(_words(pipeline.decode(enc2)),
                          _words(np.asarray(x)))


def test_serialize_rejects_unknown_method():
    enc = pipeline.encode(np.ones(8) * 1.5, method="identity")
    enc.method = "not_a_method"
    with pytest.raises(ContainerFormatError):
        serialize_chunk(enc)


def test_deserialize_rejects_unknown_backend():
    enc = pipeline.encode(np.ones(8) * 1.5, method="identity")
    buf = serialize_chunk(enc, "zlib")
    with pytest.raises(ContainerError, match="not available"):
        deserialize_chunk(buf, "definitely_not_a_backend", spec_name="f64")


# ---------------------------------------------------------------------------
# streaming writer / random-access reader
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", BACKENDS)
def test_writer_reader_streaming(tmp_path, backend):
    x = gas_turbine_emissions(50_000)
    path = tmp_path / "t.fpc"
    with ContainerWriter(path, dtype=np.float64, backend=backend,
                         user_meta={"origin": "turbine"}) as w:
        for i in range(0, x.size, 16384):
            info = w.append(x[i : i + 16384])
            assert info["comp"] > 0
    with ContainerReader(path) as r:
        assert r.backend == backend
        assert r.spec_name == "f64"
        assert r.nchunks == 4 and r.n == x.size
        assert r.user_meta == {"origin": "turbine"}
        assert r.ratio() < 1.0
        # random access decodes one record only
        c2 = r.read_chunk(2).reshape(-1)
        assert np.array_equal(c2, x[2 * 16384 : 3 * 16384])
        back = r.read_all()
    assert np.array_equal(back.view(np.uint64), x.view(np.uint64))


def test_writer_selection_happens_once(tmp_path, monkeypatch):
    """The streaming contract: one probe, then apply per chunk."""
    calls = {"n": 0}
    real = pipeline._rank_candidates

    def counting(*a, **k):
        calls["n"] += 1
        return real(*a, **k)

    monkeypatch.setattr(pipeline, "_rank_candidates", counting)
    x = chicago_taxi_fares(100_000)
    with ContainerWriter(tmp_path / "s.fpc", dtype=np.float64) as w:
        for i in range(0, x.size, 20_000):
            w.append(x[i : i + 20_000])
    assert calls["n"] == 1
    with ContainerReader(tmp_path / "s.fpc") as r:
        assert np.array_equal(r.read_all().view(np.uint64), x.view(np.uint64))


def test_writer_explicit_method_and_fallback(tmp_path):
    # chunk 1 fits compact_bins; chunk 2 (with non-finite) must fall back
    # to identity rather than fail the write
    good = 1.0 + np.arange(100) / 256.0
    bad = np.array([np.nan, np.inf, 0.0, 1.5])
    with ContainerWriter(tmp_path / "f.fpc", dtype=np.float64,
                         method="compact_bins", params={"n_bins": 4}) as w:
        assert w.append(good)["method"] == "compact_bins"
        assert w.append(bad)["method"] == "identity"
    with ContainerReader(tmp_path / "f.fpc") as r:
        assert np.array_equal(_words(r.read_chunk(0)), _words(good))
        assert np.array_equal(_words(r.read_chunk(1)), _words(bad))


def test_writer_strict_mode_raises(tmp_path):
    with ContainerWriter(tmp_path / "x.fpc", dtype=np.float64,
                         method="compact_bins", params={"n_bins": 64},
                         fallback_identity=False) as w:
        with pytest.raises(Exception):
            w.append(np.ones(8) * 1.5)  # n_bins > dataset size


def test_raw_container_int_arrays(tmp_path):
    x = np.arange(10_000, dtype=np.int32) * 3
    with ContainerWriter(tmp_path / "i.fpc", dtype=np.int32) as w:
        assert w.kind == "raw"
        w.append(x[:6000])
        w.append(x[6000:])
    with ContainerReader(tmp_path / "i.fpc") as r:
        assert r.spec_name == ""
        assert np.array_equal(r.read_all(), x)
        with pytest.raises(ContainerError, match="raw chunk"):
            r.read_encoded(0)


def test_empty_container(tmp_path):
    with ContainerWriter(tmp_path / "e.fpc", dtype=np.float64) as w:
        pass
    with ContainerReader(tmp_path / "e.fpc") as r:
        assert r.nchunks == 0
        assert r.read_all().size == 0


def test_interrupted_write_is_not_a_valid_container(tmp_path):
    """__exit__ on an exception must NOT finalize.  Path destinations write
    through a same-directory staging file (durable atomic recipe), so an
    interrupted write leaves NO file at all — nothing partial ever becomes
    visible, and no staging litter survives.  File-object destinations keep
    the caller's handle: their partial bytes have no footer and readers
    reject them loudly instead of serving a plausible-looking partial
    shard."""
    x = gas_turbine_emissions(4000)
    path = tmp_path / "crash.fpc"
    with pytest.raises(RuntimeError, match="simulated"):
        with ContainerWriter(path, dtype=np.float64) as w:
            w.append(x[:2000])
            raise RuntimeError("simulated preemption")
    assert not path.exists()
    assert not list(tmp_path.iterdir())

    bio = io.BytesIO()
    with pytest.raises(RuntimeError, match="simulated"):
        with ContainerWriter(bio, dtype=np.float64) as w:
            w.append(x[:2000])
            raise RuntimeError("simulated preemption")
    with pytest.raises(ContainerFormatError):
        ContainerReader(bio.getvalue())


def test_raw_record_trailing_garbage_rejected():
    import zlib as _zlib

    from repro.container import format as F, serialize_raw_chunk

    rec = serialize_raw_chunk(np.arange(16, dtype=np.int32))[:-4]
    bad = rec + b"\x00\x00\x00\x00"         # garbage the writer checksummed
    bad += _zlib.crc32(bad).to_bytes(4, "little")
    with pytest.raises(ContainerFormatError, match="trailing"):
        deserialize_chunk(bad, dtype=np.int32)


def test_append_after_close_raises(tmp_path):
    w = ContainerWriter(tmp_path / "c.fpc", dtype=np.float64)
    w.close()
    with pytest.raises(ContainerError, match="closed"):
        w.append(np.ones(4))
    w.close()  # idempotent


def test_append_encoded_spec_mismatch():
    enc = pipeline.encode(np.ones(16, np.float32) * 1.5)
    w = ContainerWriter(io.BytesIO(), dtype=np.float64)
    with pytest.raises(ContainerError, match="does not match"):
        w.append_encoded(enc)


# ---------------------------------------------------------------------------
# corruption / trust-nothing decode paths
# ---------------------------------------------------------------------------

def _container_bytes():
    x = gas_turbine_emissions(4000)
    bio = io.BytesIO()
    w = ContainerWriter(bio, dtype=np.float64)
    w.append(x[:2000])
    w.append(x[2000:])
    w.close()
    return bio.getvalue(), x


def test_corrupt_magic_rejected():
    buf, _ = _container_bytes()
    bad = b"XXXX" + buf[4:]
    with pytest.raises(ContainerFormatError, match="magic"):
        ContainerReader(bad)


def test_unsupported_version_rejected():
    buf, _ = _container_bytes()
    bad = buf[:4] + (99).to_bytes(2, "little") + buf[6:]
    with pytest.raises(ContainerFormatError, match="version"):
        ContainerReader(bad)


def test_truncated_file_rejected():
    buf, _ = _container_bytes()
    with pytest.raises(ContainerFormatError):
        ContainerReader(buf[: len(buf) // 2])
    with pytest.raises(ContainerFormatError):
        ContainerReader(buf[:10])


def test_bitflip_in_chunk_payload_is_caught():
    buf, _ = _container_bytes()
    r = ContainerReader(buf)
    off = r._entries[1]["offset"] + 8 + 64  # inside chunk 1's record
    bad = bytearray(buf)
    bad[off] ^= 0xFF
    r2 = ContainerReader(bytes(bad))
    assert np.array_equal(  # untouched chunk still reads fine
        r2.read_chunk(0).view(np.uint64), r.read_chunk(0).view(np.uint64)
    )
    with pytest.raises(ChecksumError):
        r2.read_chunk(1)


def test_bitflip_in_index_is_caught():
    buf, _ = _container_bytes()
    idx_off = len(buf) - container.format.FOOTER_SIZE - 4
    bad = bytearray(buf)
    bad[idx_off] ^= 0x01
    with pytest.raises(ChecksumError):
        ContainerReader(bytes(bad))


def test_decompression_bomb_is_capped():
    """A crafted record whose payload inflates far past the n the header
    declares must be rejected WITHOUT allocating the inflated size."""
    import zlib

    from repro.container import format as F

    enc = pipeline.encode(np.linspace(1, 2, 64), method="identity")
    rec = serialize_chunk(enc)[:-4]  # record body without its crc
    # walk the fields to find where the payload (bytes64) field starts
    cur = F._Cursor(rec)
    cur.u8(); cur.u8(); cur.u64(); cur.u64()
    for _ in range(cur.u8()):          # shape dims
        cur.u64()
    for _ in range(cur.u8()):          # params
        cur.str8(); cur.i64()
    cur.bytes32(); cur.bytes32(); cur.bytes32()   # meta streams
    # splice in a 64 MiB zero bomb (compresses to ~64 KiB) with a valid crc
    bomb = zlib.compress(b"\x00" * (64 << 20), 6)
    b = rec[: cur.pos] + len(bomb).to_bytes(8, "little") + bomb
    b += zlib.crc32(b).to_bytes(4, "little")
    with pytest.raises(ContainerFormatError, match="decompressed"):
        deserialize_chunk(b, spec_name="f64")


def test_writer_rejects_dtype_mismatch(tmp_path):
    with ContainerWriter(tmp_path / "d.fpc", dtype=np.float64) as w:
        with pytest.raises(ContainerError, match="dtype"):
            w.append(np.ones(8, np.float32))


def test_truncated_chunk_record_is_caught():
    enc = pipeline.encode(np.linspace(1, 2, 500))
    rec = serialize_chunk(enc)
    with pytest.raises(ContainerFormatError):
        deserialize_chunk(rec[: len(rec) - 10], spec_name="f64")
    with pytest.raises(ContainerFormatError):
        deserialize_chunk(rec[:2], spec_name="f64")


# ---------------------------------------------------------------------------
# backend registry
# ---------------------------------------------------------------------------

def test_zlib_always_available():
    assert available_backends()[0] == "zlib"


def test_register_custom_backend(tmp_path):
    container.register_backend("nullc", lambda b: b, lambda b: b)
    try:
        assert "nullc" in available_backends()
        x = gas_turbine_emissions(2000)
        with ContainerWriter(tmp_path / "n.fpc", dtype=np.float64,
                             backend="nullc") as w:
            w.append(x)
        with ContainerReader(tmp_path / "n.fpc") as r:
            assert r.backend == "nullc"
            assert np.array_equal(r.read_all().view(np.uint64),
                                  x.view(np.uint64))
    finally:
        container.backends._REGISTRY.pop("nullc", None)


def test_bad_backend_name_rejected():
    with pytest.raises(ContainerError):
        container.register_backend("x" * 40, lambda b: b, lambda b: b)


@pytest.mark.skipif("zstd" in BACKENDS, reason="zstandard installed")
def test_zstd_absent_is_loud():
    with pytest.raises(ContainerError, match="zstd"):
        container.get_backend("zstd")


def test_io_layers_are_pickle_free():
    """The acceptance contract of the container refactor: nothing in the
    serialization path may mention pickle ever again."""
    from pathlib import Path

    import repro

    root = Path(repro.__file__).parent
    for sub in ("checkpoint", "data", "container"):
        for p in (root / sub).rglob("*.py"):
            assert "pickle" not in p.read_text(), f"{p} references pickle"
