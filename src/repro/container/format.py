"""Versioned binary container format for encoded float chunks.

Byte-for-byte layout is specified in ``docs/format.md``; this module is the
single reference implementation.  Everything is explicit little-endian:

* a fixed header (magic, format version, spec name, dtype, backend name),
* length-prefixed self-delimiting chunk records, one per
  :class:`repro.core.pipeline.Encoded` (or raw-bytes chunk), each carrying
  ``{method, params, transform metadata, packed meta streams, payload,
  crc32}``,
* a chunk index (offset/length/elements/method per chunk + a caller
  JSON blob) and a fixed 20-byte footer for O(1) random chunk access.

Per-transform metadata is serialized field by field (see ``_META_CODECS``);
decode therefore needs zero trust in the producer: every record is
checksummed, every length is bounds-checked, and an unknown method/version
fails loudly instead of executing anything.
"""
from __future__ import annotations

import json
import struct
import zlib

import numpy as np

from .. import obs
from ..core import transforms as T
from ..core.pipeline import Encoded
from .backends import Backend, ContainerError, get_backend

MAGIC = b"RFPC"          # repro float-preprocessing container
END_MAGIC = b"CPFR"
VERSION = 1
FOOTER_SIZE = 20         # u64 index_offset | u32 index_crc | u32 nchunks | END_MAGIC

# method ids are part of the on-disk format: append-only, never renumber
METHOD_IDS = {
    "identity": 0,
    "compact_bins": 1,
    "multiply_shift": 2,
    "shift_separate": 3,
    "shift_save_even": 4,
}
RAW_METHOD_ID = 255      # non-float payload: backend-compressed raw bytes
METHOD_NAMES = {v: k for k, v in METHOD_IDS.items()}

_SPEC_DTYPES = {"f64": "float64", "f32": "float32", "bf16": "bfloat16",
                "f16": "float16"}

# sanity bound for any single length field (1 TiB); a corrupt length must
# fail loudly instead of triggering a huge allocation
_MAX_LEN = 1 << 40


class ContainerFormatError(ContainerError):
    """Malformed container bytes (bad magic/version/length/method id)."""


class ChecksumError(ContainerFormatError):
    """Stored CRC32 does not match the record bytes."""


def resolve_dtype(name: str) -> np.dtype:
    if name == "bfloat16":
        import ml_dtypes

        return np.dtype(ml_dtypes.bfloat16)
    try:
        return np.dtype(name)
    except (TypeError, ValueError):
        # the header dtype name is producer data (and not CRC-covered), so a
        # garbled name is corruption, not a programming error
        raise ContainerFormatError(f"unknown container dtype {name!r}") from None


def dtype_name(dt) -> str:
    """Canonical dtype name stored in headers/manifests (inverse of
    :func:`resolve_dtype`); bfloat16 — whether ml_dtypes-registered or
    viewed as 2-byte void — normalizes to ``"bfloat16"``."""
    dt = np.dtype(dt)
    if dt.kind == "V" and dt.itemsize == 2:
        return "bfloat16"
    return str(dt)


# ---------------------------------------------------------------------------
# primitive little-endian readers/writers
# ---------------------------------------------------------------------------

def _w_u8(b: bytearray, v: int) -> None:
    b += struct.pack("<B", v)


def _w_u16(b: bytearray, v: int) -> None:
    b += struct.pack("<H", v)


def _w_u32(b: bytearray, v: int) -> None:
    b += struct.pack("<I", v)


def _w_u64(b: bytearray, v: int) -> None:
    b += struct.pack("<Q", v)


def _w_i64(b: bytearray, v: int) -> None:
    b += struct.pack("<q", v)


def _w_str8(b: bytearray, s: str) -> None:
    raw = s.encode("ascii")
    if len(raw) > 255:
        raise ContainerFormatError(f"string field too long: {s!r}")
    _w_u8(b, len(raw))
    b += raw


def _w_bytes32(b: bytearray, raw: bytes) -> None:
    _w_u32(b, len(raw))
    b += raw


def _w_bytes64(b: bytearray, raw: bytes) -> None:
    _w_u64(b, len(raw))
    b += raw


def _w_i64_array32(b: bytearray, vals: np.ndarray) -> None:
    vals = np.ascontiguousarray(np.asarray(vals, np.int64))
    _w_u32(b, vals.size)
    b += vals.astype("<i8").tobytes()


class _Cursor:
    """Bounds-checked reader over a bytes object."""

    def __init__(self, buf: bytes, pos: int = 0):
        self.buf = buf
        self.pos = pos

    def take(self, n: int) -> bytes:
        if n < 0 or n > _MAX_LEN:
            raise ContainerFormatError(f"implausible length field: {n}")
        if self.pos + n > len(self.buf):
            raise ContainerFormatError(
                f"truncated container: wanted {n} bytes at offset {self.pos}, "
                f"have {len(self.buf) - self.pos}"
            )
        out = self.buf[self.pos : self.pos + n]
        self.pos += n
        return out

    def u8(self) -> int:
        return struct.unpack("<B", self.take(1))[0]

    def u16(self) -> int:
        return struct.unpack("<H", self.take(2))[0]

    def u32(self) -> int:
        return struct.unpack("<I", self.take(4))[0]

    def u64(self) -> int:
        return struct.unpack("<Q", self.take(8))[0]

    def i64(self) -> int:
        return struct.unpack("<q", self.take(8))[0]

    def str8(self) -> str:
        try:
            return self.take(self.u8()).decode("ascii")
        except UnicodeDecodeError:
            raise ContainerFormatError(
                "corrupt string field (non-ASCII bytes)"
            ) from None

    def bytes32(self) -> bytes:
        return self.take(self.u32())

    def bytes64(self) -> bytes:
        return self.take(self.u64())

    def i64_array32(self) -> np.ndarray:
        n = self.u32()
        return np.frombuffer(self.take(8 * n), "<i8").astype(np.int64)


# ---------------------------------------------------------------------------
# header
# ---------------------------------------------------------------------------

def encode_header(spec_name: str, dtype_name: str, backend_name: str) -> bytes:
    b = bytearray()
    b += MAGIC
    _w_u16(b, VERSION)
    _w_u16(b, 0)  # flags, reserved
    _w_str8(b, spec_name)
    _w_str8(b, dtype_name)
    _w_str8(b, backend_name)
    return bytes(b)


def decode_header(cur: _Cursor) -> dict:
    magic = cur.take(4)
    if magic != MAGIC:
        raise ContainerFormatError(
            f"not a container: bad magic {magic!r} (want {MAGIC!r})"
        )
    version = cur.u16()
    if version != VERSION:
        raise ContainerFormatError(
            f"unsupported container format version {version} (reader supports {VERSION})"
        )
    cur.u16()  # flags
    spec_name = cur.str8()
    dtype_name = cur.str8()
    backend_name = cur.str8()
    if spec_name and spec_name not in _SPEC_DTYPES:
        raise ContainerFormatError(f"unknown float spec {spec_name!r}")
    if spec_name and _SPEC_DTYPES[spec_name] != dtype_name:
        # the header stores the dtype redundantly with the float spec; a
        # mismatch (only corruption can produce one — the writer derives
        # both from one dtype) must not silently pick either side
        raise ContainerFormatError(
            f"container header dtype {dtype_name!r} contradicts float "
            f"spec {spec_name!r}"
        )
    return {
        "version": version,
        "spec_name": spec_name,
        "dtype": dtype_name,
        "backend": backend_name,
    }


# ---------------------------------------------------------------------------
# per-transform metadata codecs (explicit fields, nothing opaque)
# ---------------------------------------------------------------------------

def _enc_meta_none(b: bytearray, meta) -> None:
    if meta is not None:
        raise ContainerFormatError("identity/raw chunk must carry no metadata")


def _dec_meta_none(cur: _Cursor, n_active: int):
    return None


def _enc_meta_cb(b: bytearray, meta: T.CompactBinsMeta) -> None:
    _w_i64(b, meta.e_star)
    _w_i64_array32(b, meta.shifts)
    _w_i64_array32(b, meta.thresholds)


def _dec_meta_cb(cur: _Cursor, n_active: int) -> T.CompactBinsMeta:
    return T.CompactBinsMeta(
        e_star=cur.i64(), shifts=cur.i64_array32(), thresholds=cur.i64_array32()
    )


def _enc_meta_ms(b: bytearray, meta: T.MultiplyShiftMeta) -> None:
    _w_i64(b, meta.e_star)
    _w_u32(b, meta.D)
    _w_i64(b, meta.x_max)
    _w_u32(b, meta.n_iter)


def _dec_meta_ms(cur: _Cursor, n_active: int) -> T.MultiplyShiftMeta:
    return T.MultiplyShiftMeta(
        e_star=cur.i64(), D=cur.u32(), x_max=cur.i64(), n_iter=cur.u32()
    )


def _enc_meta_ss(b: bytearray, meta: T.ShiftSeparateMeta) -> None:
    _w_i64(b, meta.e_star)
    _w_u32(b, meta.D)
    _w_i64(b, meta.x_min)
    _w_i64(b, meta.x_max)
    _w_u32(b, meta.n_iter)


def _dec_meta_ss(cur: _Cursor, n_active: int) -> T.ShiftSeparateMeta:
    return T.ShiftSeparateMeta(
        e_star=cur.i64(), D=cur.u32(), x_min=cur.i64(), x_max=cur.i64(),
        n_iter=cur.u32(),
    )


def _enc_meta_sse(b: bytearray, meta: T.ShiftSaveEvenMeta) -> None:
    from ..compression.bitplane import compress_int_stream

    _w_i64(b, meta.e_star)
    _w_u32(b, meta.D)
    _w_i64(b, meta.x_min)
    _w_u64(b, meta.n_chunks)
    _w_bytes32(b, compress_int_stream(np.asarray(meta.chunk_ids, np.int64)))
    _w_bytes32(
        b, zlib.compress(np.packbits(np.asarray(meta.evenness, np.uint8)).tobytes(), 6)
    )


def _dec_meta_sse(cur: _Cursor, n_active: int) -> T.ShiftSaveEvenMeta:
    from ..compression.bitplane import decompress_int_stream

    from .backends import zlib_decompress_capped

    e_star = cur.i64()
    D = cur.u32()
    x_min = cur.i64()
    n_chunks = cur.u64()
    ids = decompress_int_stream(cur.bytes32(), n_active)
    even_raw = zlib_decompress_capped(cur.bytes32(), -(-n_active // 8))
    if len(even_raw) != -(-n_active // 8):
        raise ContainerFormatError("shift_save_even evenness length mismatch")
    even = np.unpackbits(
        np.frombuffer(even_raw, np.uint8)
    )[:n_active].astype(np.uint8)
    if ids.shape[0] != n_active or even.shape[0] != n_active:
        raise ContainerFormatError("shift_save_even metadata length mismatch")
    return T.ShiftSaveEvenMeta(
        e_star=e_star, D=D, x_min=x_min, n_chunks=n_chunks,
        chunk_ids=np.asarray(ids, np.int64), evenness=even,
    )


_META_CODECS = {
    "identity": (_enc_meta_none, _dec_meta_none),
    "compact_bins": (_enc_meta_cb, _dec_meta_cb),
    "multiply_shift": (_enc_meta_ms, _dec_meta_ms),
    "shift_separate": (_enc_meta_ss, _dec_meta_ss),
    "shift_save_even": (_enc_meta_sse, _dec_meta_sse),
}


def _enc_params(b: bytearray, params: dict) -> None:
    _w_u8(b, len(params))
    for k in sorted(params):
        v = params[k]
        if not isinstance(v, (int, np.integer)) or isinstance(v, bool):
            raise ContainerFormatError(
                f"transform params must be plain ints, got {k}={v!r}"
            )
        _w_str8(b, k)
        _w_i64(b, int(v))


def _dec_params(cur: _Cursor) -> dict:
    return {cur.str8(): cur.i64() for _ in range(cur.u8())}


# ---------------------------------------------------------------------------
# chunk records
# ---------------------------------------------------------------------------

def _resolve_backend(backend: str | Backend) -> Backend:
    return backend if isinstance(backend, Backend) else get_backend(backend)


def _decompress_exact(be: Backend, buf: bytes, expected: int) -> bytes:
    """Backend-decompress an untrusted payload whose plaintext size is known
    from the record header.  Capped backends never allocate more than
    ``expected + 1`` bytes (decompression-bomb guard); either way a length
    mismatch is corruption, reported loudly."""
    if be.decompress_capped is not None:
        out = be.decompress_capped(buf, expected)
    else:
        out = be.decompress(buf)
    if len(out) != expected:
        raise ContainerFormatError(
            f"chunk payload decompressed to {len(out)}+ bytes, header "
            f"implies {expected}"
        )
    return out


@obs.span("container.serialize")
def serialize_chunk(enc: Encoded, backend: str | Backend = "zlib") -> bytes:
    """One :class:`Encoded` -> a self-delimiting checksummed record."""
    be = _resolve_backend(backend)
    if enc.method not in METHOD_IDS:
        raise ContainerFormatError(f"unknown transform method {enc.method!r}")
    data = np.asarray(enc.data)
    b = bytearray()
    _w_u8(b, METHOD_IDS[enc.method])
    _w_u8(b, 0)  # reserved
    _w_u64(b, enc.n)
    _w_u64(b, enc.n_active)
    _w_u8(b, data.ndim)
    for d in data.shape:
        _w_u64(b, d)
    _enc_params(b, enc.params or {})
    _META_CODECS[enc.method][0](b, enc.meta)
    _w_bytes32(b, enc.exponents_z)
    _w_bytes32(b, enc.signs_z)
    _w_bytes32(b, enc.passthrough_z)
    payload = getattr(enc, "payload", None)
    if payload is not None and getattr(enc, "payload_backend", "") == be.name:
        # fused device encode already produced this backend's framed stream
        # (byte-identical to compressing ``data`` here — the frame is
        # producer-agnostic, docs/format.md); ship it without re-compressing
        _w_bytes64(b, payload)
    else:
        _w_bytes64(b, be.compress(np.ascontiguousarray(data).tobytes()))
    _w_u32(b, zlib.crc32(b))  # crc32 reads the bytearray buffer, no copy
    return bytes(b)


def serialize_raw_chunk(arr: np.ndarray, backend: str | Backend = "zlib") -> bytes:
    """Non-float chunk: backend-compressed raw bytes, same record framing."""
    be = _resolve_backend(backend)
    arr = np.asarray(arr)
    b = bytearray()
    _w_u8(b, RAW_METHOD_ID)
    _w_u8(b, 0)
    _w_u64(b, arr.size)
    _w_u64(b, 0)
    _w_u8(b, arr.ndim)
    for d in arr.shape:
        _w_u64(b, d)
    _w_u8(b, 0)          # no params
    _w_bytes32(b, b"")   # no meta streams for raw chunks
    _w_bytes32(b, b"")
    _w_bytes32(b, b"")
    _w_bytes64(b, be.compress(np.ascontiguousarray(arr).tobytes()))
    _w_u32(b, zlib.crc32(b))  # crc32 reads the bytearray buffer, no copy
    return bytes(b)


def _decompress_into_exact(be: Backend, buf: bytes, out) -> None:
    """Backend-decompress an untrusted payload straight into ``out`` (whose
    length is the expected plaintext size).  Uses the backend's
    ``decompress_into`` slot when present — no intermediate plaintext
    allocation — else the capped path plus one copy."""
    mv = memoryview(out).cast("B")
    if be.decompress_into is not None:
        got = be.decompress_into(buf, mv)
        if got != len(mv):
            raise ContainerFormatError(
                f"chunk payload decompressed to {got}+ bytes, header "
                f"implies {len(mv)}"
            )
    else:
        mv[:] = _decompress_exact(be, buf, len(mv))


def deserialize_chunk_into(
    buf: bytes,
    backend: str | Backend,
    out: np.ndarray,
    spec_name: str | None = None,
    dtype: np.dtype | str | None = None,
):
    """Decode one record directly into ``out`` (a flat array slice) when the
    record needs no inverse transform — RAW records and identity transform
    records, whose payload *is* the output bytes.  Returns ``None`` on
    success; any other record returns the regular
    :func:`deserialize_chunk` result for the caller to decode and copy.

    Same trust model as :func:`deserialize_chunk`: CRC first, every length
    cross-checked against ``out`` (which the caller sizes from the container
    index), loud :class:`ContainerFormatError` on any disagreement."""
    if len(buf) < 4:
        raise ContainerFormatError("truncated chunk record")
    body, (crc,) = buf[:-4], struct.unpack("<I", buf[-4:])
    if zlib.crc32(body) != crc:
        raise ChecksumError(
            "chunk checksum mismatch: record corrupt or truncated"
        )
    method_id = body[0]
    identity = method_id == METHOD_IDS["identity"]
    if not (identity or method_id == RAW_METHOD_ID):
        return deserialize_chunk(buf, backend, spec_name, dtype)
    be = _resolve_backend(backend)
    cur = _Cursor(body)
    cur.u8()  # method id (peeked above)
    cur.u8()  # reserved
    n = cur.u64()
    n_active = cur.u64()
    ndim = cur.u8()
    shape = tuple(cur.u64() for _ in range(ndim))
    if int(np.prod(shape, dtype=np.int64)) != n:
        raise ContainerFormatError(f"chunk shape {shape} does not hold n={n}")
    if identity:
        # same spec requirements as deserialize_chunk: a transform record
        # (identity included) inside a spec-less container is corruption
        # and must fail identically on the serial and parallel paths
        if spec_name is None:
            raise ContainerFormatError("transform chunk needs the container spec")
        if spec_name not in _SPEC_DTYPES:
            raise ContainerFormatError(f"unknown float spec {spec_name!r}")
        if cur.u8() != 0:
            raise ContainerFormatError("identity chunk carries params")
        _META_CODECS["identity"][1](cur, n_active)
        if n_active != 0 or cur.bytes32() or cur.bytes32() or cur.bytes32():
            # a malformed identity record claiming active samples must take
            # the full decode path's validation, never the fast path
            return deserialize_chunk(buf, backend, spec_name, dtype)
    else:
        if cur.u8() != 0 or cur.bytes32() or cur.bytes32() or cur.bytes32():
            raise ContainerFormatError("raw chunk carries transform fields")
        if dtype is None:
            raise ContainerFormatError("raw chunk needs the container dtype")
    if out.size != n:
        raise ContainerFormatError(
            f"chunk record holds {n} elements, index claims {out.size}"
        )
    payload_z = cur.bytes64()
    if cur.pos != len(body):
        raise ContainerFormatError(
            f"{len(body) - cur.pos} trailing bytes after chunk record"
        )
    _decompress_into_exact(be, payload_z, out.view(np.uint8).data)
    return None


def deserialize_chunk(
    buf: bytes,
    backend: str | Backend = "zlib",
    spec_name: str | None = None,
    dtype: np.dtype | str | None = None,
):
    """Inverse of the serializers: record bytes -> :class:`Encoded`, or a
    raw ``np.ndarray`` for :data:`RAW_METHOD_ID` records.

    ``dtype`` (the container dtype) is required for raw records; transform
    records derive their dtype from the record's float spec when ``dtype``
    is not given.
    """
    if len(buf) < 4:
        raise ContainerFormatError("truncated chunk record")
    body, (crc,) = buf[:-4], struct.unpack("<I", buf[-4:])
    if zlib.crc32(body) != crc:
        raise ChecksumError(
            "chunk checksum mismatch: record corrupt or truncated"
        )
    be = _resolve_backend(backend)
    cur = _Cursor(body)
    method_id = cur.u8()
    cur.u8()  # reserved
    n = cur.u64()
    n_active = cur.u64()
    ndim = cur.u8()
    shape = tuple(cur.u64() for _ in range(ndim))
    if int(np.prod(shape, dtype=np.int64)) != n:
        raise ContainerFormatError(f"chunk shape {shape} does not hold n={n}")

    if method_id == RAW_METHOD_ID:
        if cur.u8() != 0 or cur.bytes32() or cur.bytes32() or cur.bytes32():
            raise ContainerFormatError("raw chunk carries transform fields")
        if dtype is None:
            raise ContainerFormatError("raw chunk needs the container dtype")
        dt = resolve_dtype(dtype) if isinstance(dtype, str) else np.dtype(dtype)
        payload_z = cur.bytes64()
        if cur.pos != len(body):
            raise ContainerFormatError(
                f"{len(body) - cur.pos} trailing bytes after chunk record"
            )
        raw = _decompress_exact(be, payload_z, n * dt.itemsize)
        return np.frombuffer(raw, dt).reshape(shape).copy()

    method = METHOD_NAMES.get(method_id)
    if method is None:
        raise ContainerFormatError(f"unknown method id {method_id}")
    params = _dec_params(cur)
    meta = _META_CODECS[method][1](cur, n_active)
    exponents_z = cur.bytes32()
    signs_z = cur.bytes32()
    passthrough_z = cur.bytes32()
    if spec_name is None:
        raise ContainerFormatError("transform chunk needs the container spec")
    if spec_name not in _SPEC_DTYPES:
        raise ContainerFormatError(f"unknown float spec {spec_name!r}")
    dt = resolve_dtype(_SPEC_DTYPES[spec_name])
    payload_z = cur.bytes64()
    if cur.pos != len(body):
        raise ContainerFormatError(
            f"{len(body) - cur.pos} trailing bytes after chunk record"
        )
    data = np.frombuffer(_decompress_exact(be, payload_z, n * dt.itemsize), dt)
    return Encoded(
        method=method, params=params, data=data.reshape(shape).copy(),
        meta=meta, exponents_z=exponents_z, signs_z=signs_z,
        passthrough_z=passthrough_z, spec_name=spec_name, n=n,
        n_active=n_active,
    )


# ---------------------------------------------------------------------------
# index + footer
# ---------------------------------------------------------------------------

def encode_index(entries: list[dict], user_meta: dict | None) -> bytes:
    """entries: [{offset, length, n, method_id}]; user_meta: caller JSON."""
    b = bytearray()
    _w_bytes32(b, json.dumps(user_meta or {}, sort_keys=True).encode("utf-8"))
    for e in entries:
        _w_u64(b, e["offset"])
        _w_u64(b, e["length"])
        _w_u64(b, e["n"])
        _w_u8(b, e["method_id"])
    return bytes(b)


def decode_index(buf: bytes, nchunks: int) -> tuple[list[dict], dict]:
    cur = _Cursor(buf)
    try:
        user_meta = json.loads(cur.bytes32().decode("utf-8"))
    except (UnicodeDecodeError, json.JSONDecodeError) as e:
        raise ContainerFormatError(f"corrupt container user metadata: {e}")
    entries = [
        {"offset": cur.u64(), "length": cur.u64(), "n": cur.u64(),
         "method_id": cur.u8()}
        for _ in range(nchunks)
    ]
    if cur.pos != len(buf):
        # the footer's nchunks is not CRC-covered; a flipped count that
        # under-reads the index would otherwise truncate the container to a
        # plausible-looking prefix of its chunks
        raise ContainerFormatError(
            f"container index holds {len(buf) - cur.pos} bytes beyond the "
            f"{nchunks} chunk entries the footer declares"
        )
    return entries, user_meta


def encode_footer(index_offset: int, index_crc: int, nchunks: int) -> bytes:
    return struct.pack("<QII", index_offset, index_crc, nchunks) + END_MAGIC


def decode_footer(buf: bytes) -> tuple[int, int, int]:
    if len(buf) != FOOTER_SIZE or buf[-4:] != END_MAGIC:
        raise ContainerFormatError(
            "missing container footer (file truncated or not finalized)"
        )
    index_offset, index_crc, nchunks = struct.unpack("<QII", buf[:-4])
    return index_offset, index_crc, nchunks


def spec_dtype_name(spec_name: str) -> str:
    return _SPEC_DTYPES[spec_name]
