"""Streaming container I/O: ``ContainerWriter.append`` / ``ContainerReader``.

The writer is the *streaming* face of the codec: transform selection runs
once (on a strided sample of the first sizeable chunk) and every subsequent
chunk goes straight through :func:`repro.core.pipeline.apply_transform` —
no whole-array materialization, no re-selection per chunk.  A chunk whose
data rejects the picked transform (domain failure, failed round-trip) falls
back to identity: a container write can never fail on data shape grounds,
and never ships a non-round-tripping chunk (pipeline contract).

The reader is random-access: the footer index gives O(1) seek to any chunk
record, so ``read_chunk(i)`` touches only that record's bytes.

Decode is also *parallel*: record fetch + CRC + backend decompression release
the GIL, so a shared thread pool overlaps them with the (host-side) inverse
transforms.  ``ContainerReader`` is thread-safe (file access is serialized
behind one lock; everything else is per-call state), ``iter_chunks(prefetch=N)``
is an ordered bounded-window prefetch iterator, and ``read_all(parallel=True)``
decodes chunks concurrently into a preallocated output — byte-identical to the
serial path, deterministic chunk order, worker exceptions re-raised in the
caller.  Semantics: docs/format.md §Parallel reads.
"""
from __future__ import annotations

import bisect
import dataclasses
import io as _io
import os
import struct
import threading
import time
import zlib
from concurrent.futures import ThreadPoolExecutor
from pathlib import Path

import numpy as np

from .. import obs
from ..core import pipeline, streaming as _streaming
from ..core.float_bits import BF16, F16, F32, F64
from ..reliability import durable as _durable, faults as _faults, watchdog as _watchdog
from . import format as F
from .backends import ContainerError, get_backend

_FLOAT_SPECS = {"float64": F64, "float32": F32, "float16": F16, "bfloat16": BF16}
_SPEC_NAMES = {"float64": "f64", "float32": "f32", "float16": "f16", "bfloat16": "bf16"}

# selection probe geometry: the policy itself (probe once on the first
# sizeable chunk, reuse per chunk-window with fingerprint-drift refresh)
# lives in core/streaming.WindowPlanner; these re-exports keep the writer's
# historical constants importable from here.
PROBE_ELEMS = _streaming.PROBE_ELEMS
PROBE_THRESHOLD = _streaming.PROBE_THRESHOLD

# -- shared decode pool ------------------------------------------------------
#
# One process-wide pool serves every parallel container read: decode work is
# CPU-bound (zlib/zstd + inverse transforms), so per-reader pools would only
# oversubscribe the host.  Worker threads are tagged by name; a parallel read
# issued FROM a decode worker (e.g. a checkpoint leaf restored in the pool
# that asks for a parallel chunk read) degrades to the serial path instead of
# deadlocking on its own executor.

_POOL_THREAD_PREFIX = "rfpc-decode"
_pool_lock = threading.Lock()
_shared_pool: ThreadPoolExecutor | None = None


def default_decode_workers() -> int:
    """Decode parallelism used when the caller does not pick one."""
    return max(2, min(8, os.cpu_count() or 2))


def shared_decode_pool() -> ThreadPoolExecutor:
    """The lazily-created process-wide decode pool (all consumers share it)."""
    global _shared_pool
    with _pool_lock:
        if _shared_pool is None:
            _shared_pool = ThreadPoolExecutor(
                max_workers=default_decode_workers(),
                thread_name_prefix=_POOL_THREAD_PREFIX,
            )
        return _shared_pool


def in_decode_pool() -> bool:
    """True when the current thread IS a decode worker (nested parallel
    reads must not block on the pool they run in)."""
    return threading.current_thread().name.startswith(_POOL_THREAD_PREFIX)


# ``parallel="auto"`` cold-start threshold: below this much raw (decoded)
# data the pool's wake-up + GIL hand-off cost eats the overlap win, so auto
# mode stays serial until the adaptive policy below has real measurements.
# 4 MiB is conservative — measured crossover on a 2-vCPU CI container is
# ~1-4 MiB; many-core hosts break even earlier (read at call time).
PARALLEL_MIN_BYTES = 4 << 20

# adaptive-policy work threshold: a span whose *estimated serial decode
# time* (from measured throughput) falls below this many microseconds is
# decoded serially even when the caller asked for the pool — the pool's
# scheduling cost would dominate.  Env knob, read at call time
# (docs/knobs.md).
DEFAULT_POOL_MIN_WORK_US = 3000.0


def pool_min_work_us() -> float:
    """Adaptive-gate work threshold (``REPRO_POOL_MIN_WORK_US`` override)."""
    v = os.environ.get("REPRO_POOL_MIN_WORK_US", "").strip()
    return float(v) if v else DEFAULT_POOL_MIN_WORK_US


class AdaptivePoolPolicy:
    """Measured-throughput gate for parallel container decode (the PR 3
    carry: ``parallel=True`` safe to default-on under load).

    PR 3 gated ``parallel="auto"`` on a static byte threshold.  This policy
    replaces that with *probed* span throughput: every ``read_all`` /
    ``read_span`` records its decoded bytes and wall time per path, and the
    gate parallelizes a span only when

    * its **estimated serial decode time** (span bytes / measured serial
      throughput) exceeds :func:`pool_min_work_us` — below that, pool
      wake-up + GIL hand-off cost more than they overlap; and
    * the pool has not **measured slower than serial** on this host (an
      oversubscribed or single-core box demotes itself) — skipped for
      ``parallel=True`` callers, who keep the pool for any non-trivial span.

    Cold (fewer than :data:`MIN_SAMPLES` serial measurements) the gate falls
    back to the static :data:`PARALLEL_MIN_BYTES` prior so process-start
    behavior is deterministic.  Throughputs are EWMAs (bytes/us) so the gate
    tracks load shifts; all state sits behind one lock.  ``decisions`` is a
    cumulative {serial, parallel} counter for tests and serving stats.
    """

    MIN_SAMPLES = 3
    EWMA = 0.2  # weight of the newest sample

    def __init__(self):
        self._lock = threading.Lock()
        self._tp: dict[str, float | None] = {"serial": None, "parallel": None}
        self._n = {"serial": 0, "parallel": 0}
        self.decisions = {"serial": 0, "parallel": 0}

    def record(self, kind: str, nbytes: int, us: float) -> None:
        """Feed one measured decode: ``kind`` in {serial, parallel}."""
        if nbytes <= 0 or us <= 0:
            return
        tp = nbytes / us
        with self._lock:
            cur = self._tp[kind]
            self._tp[kind] = tp if cur is None else (
                (1 - self.EWMA) * cur + self.EWMA * tp
            )
            self._n[kind] += 1

    def throughput(self, kind: str) -> float | None:
        """Current EWMA throughput in bytes/us (None = no samples)."""
        with self._lock:
            return self._tp[kind]

    def samples(self, kind: str) -> int:
        with self._lock:
            return self._n[kind]

    def should_parallel(self, nbytes: int, forced: bool = False) -> bool:
        """Gate one span: ``forced`` is a ``parallel=True`` caller (keeps
        the pool unless the span is below the work threshold)."""
        with self._lock:
            stp, n = self._tp["serial"], self._n["serial"]
            ptp = self._tp["parallel"]
        if n < self.MIN_SAMPLES or not stp:
            par = forced or nbytes >= PARALLEL_MIN_BYTES  # cold prior
        else:
            par = nbytes / stp >= pool_min_work_us()
            if par and not forced and ptp is not None and ptp < stp:
                par = False  # pool measured slower than serial on this host
        with self._lock:
            self.decisions["parallel" if par else "serial"] += 1
        return par

    def reset(self) -> None:
        with self._lock:
            self._tp = {"serial": None, "parallel": None}
            self._n = {"serial": 0, "parallel": 0}
            self.decisions = {"serial": 0, "parallel": 0}


# process-wide policy instance: every reader's measurements sharpen every
# other reader's gate (tests swap in a fresh instance to pin cold behavior)
POOL_POLICY = AdaptivePoolPolicy()


class ContainerWriter:
    """Append-only streaming writer for one container (one logical array).

    ``dtype`` decides the path: f64/f32/bf16 chunks go through the paper
    codec (method selection + transform + verify); any other dtype is
    stored as backend-compressed raw bytes (``RAW`` records).
    """

    def __init__(
        self,
        path_or_file,
        dtype,
        backend: str = "zlib",
        method: str = "auto",
        params: dict | None = None,
        candidates=None,
        user_meta: dict | None = None,
        probe_elems: int = PROBE_ELEMS,
        probe_threshold: int = PROBE_THRESHOLD,
        fallback_identity: bool = True,
        durable: bool = True,
        plan=None,
    ):
        """``plan`` (a :class:`repro.core.plans.EncodePlan`) pre-empts the
        selection probe entirely: every chunk encodes phase-2-only through
        :func:`repro.core.pipeline.encode_with_plan` (winner, then the
        plan's ranked fallbacks, then identity — always verified).  The
        plan's spec must match the container dtype; its backend hint is
        rebased onto this writer's backend."""
        self._dtype_name = F.dtype_name(dtype)
        self._dtype = F.resolve_dtype(self._dtype_name)
        self._spec = _FLOAT_SPECS.get(self._dtype_name)
        self._spec_name = _SPEC_NAMES.get(self._dtype_name, "")
        self._backend = get_backend(backend)
        self._method = method
        self._params = params
        self._candidates = (
            candidates if candidates is not None else pipeline.DEFAULT_CANDIDATES
        )
        self._user_meta = dict(user_meta or {})
        self._probe_elems = probe_elems
        self._probe_threshold = probe_threshold
        self._fallback_identity = fallback_identity
        self._plan = None
        if plan is not None:
            if self._spec is None:
                raise ContainerError(
                    f"container dtype {self._dtype_name!r} takes the raw "
                    "byte path; a float encode plan does not apply"
                )
            if plan.spec_name != self._spec_name:
                raise ContainerError(
                    f"encode plan spec {plan.spec_name!r} does not match "
                    f"container spec {self._spec_name!r}"
                )
            if plan.backend != self._backend.name:
                plan = dataclasses.replace(plan, backend=self._backend.name)
            self._plan = plan
        # selection policy (probe-once + per-window plan reuse with
        # fingerprint-drift refresh) is delegated to the shared streaming
        # core; raw-path containers have no float policy to run
        self._planner = None
        if self._spec is not None:
            self._planner = _streaming.WindowPlanner(
                spec=self._spec, backend=self._backend.name, method=method,
                params=params, candidates=self._candidates, plan=self._plan,
                probe_elems=probe_elems, probe_threshold=probe_threshold,
                fallback_identity=fallback_identity,
            )
        self._entries: list[dict] = []
        self._chunks: list[dict] = []
        self._closed = False

        self._staged: _durable.DurableFile | None = None
        if hasattr(path_or_file, "write"):
            self._f = path_or_file
            self._owns = False
        else:
            # path destinations are written durably: all bytes go to a
            # same-directory staging file, fsynced and atomically renamed
            # onto the destination at close() — a crash or failed write at
            # ANY point leaves the previous file (or no file) intact, never
            # a truncated/partial container (docs/reliability.md).
            # ``durable=False`` keeps the staging+rename atomicity but
            # skips the fsyncs (process-crash-safe, not power-loss-safe).
            self._staged = _durable.DurableFile(Path(path_or_file),
                                                fsync=durable)
            self._f = self._staged.file
            self._owns = True
        self._pos = 0
        self._write(F.encode_header(self._spec_name, self._dtype_name,
                                    self._backend.name))

    # -- byte plumbing ------------------------------------------------------

    def _write(self, b: bytes) -> None:
        self._f.write(b)
        self._pos += len(b)

    def _write_record(self, rec: bytes, n: int, method: str) -> dict:
        off = self._pos
        self._write(struct.pack("<Q", len(rec)))
        self._write(rec)
        method_id = F.RAW_METHOD_ID if method == "raw" else F.METHOD_IDS[method]
        self._entries.append(
            {"offset": off, "length": len(rec), "n": n, "method_id": method_id}
        )
        info = {
            "method": method,
            "raw": int(n * self._dtype.itemsize),
            "comp": len(rec),
        }
        self._chunks.append(info)
        return info

    # -- public API ---------------------------------------------------------

    @property
    def _picked(self) -> tuple[str, dict | None] | None:
        """The probe's (method, params) pick, None before any probe (or on
        the raw path) — readable after close (checkpoint reuses it)."""
        return self._planner.picked if self._planner is not None else None

    def encode_record(self, chunk) -> tuple[bytes, int, str]:
        """The CPU half of ``append``: validate + encode + serialize one
        chunk to ``(record_bytes, n, method)`` with NO file I/O.  The
        streaming pump (:func:`repro.core.streaming.stream_chunks`) runs
        this on the producer thread while ``_write_record`` drains on the
        write-behind thread; ``append`` is the composition of the two.

        Device arrays (anything exposing ``.dtype``/``.size``) are accepted
        without an eager ``np.asarray``: the encode path decides when (and
        whether) to materialize host bytes, so a fused rans-backend encode
        keeps the chunk device-resident through transform + entropy coding."""
        if self._closed:
            raise ContainerError("writer is closed")
        _faults.maybe_crash("container.append")
        dt = getattr(chunk, "dtype", None)
        if dt is None or self._spec is None:
            chunk = np.asarray(chunk)
            dt = chunk.dtype
        if F.dtype_name(dt) != self._dtype_name:
            raise ContainerError(
                f"chunk dtype {dt} does not match container dtype "
                f"{self._dtype_name!r} — a container holds one dtype"
            )
        if self._spec is None:
            rec = F.serialize_raw_chunk(chunk, self._backend)
            return rec, int(chunk.size), "raw"
        enc = self._planner.encode(chunk)
        rec = F.serialize_chunk(enc, self._backend)
        return rec, int(chunk.size), enc.method

    def append(self, chunk) -> dict:
        """Encode + serialize + write one chunk; returns {method, raw, comp}."""
        return self._write_record(*self.encode_record(chunk))

    def append_encoded(self, enc: pipeline.Encoded) -> dict:
        """Serialize an already-encoded chunk (must match the container spec)."""
        if self._closed:
            raise ContainerError("writer is closed")
        if self._spec is None or enc.spec_name != self._spec_name:
            raise ContainerError(
                f"Encoded spec {enc.spec_name!r} does not match container "
                f"spec {self._spec_name!r}"
            )
        rec = F.serialize_chunk(enc, self._backend)
        return self._write_record(rec, enc.n, enc.method)

    def update_user_meta(self, extra: dict) -> None:
        """Merge keys into the container's user metadata.  The index (which
        carries user_meta) is only written at ``close()``, so streaming
        callers may record stream-dependent facts — e.g. the final logical
        shape — after the last chunk, before closing."""
        if self._closed:
            raise ContainerError("writer is closed")
        self._user_meta.update(extra)

    @property
    def chunks(self) -> list[dict]:
        return list(self._chunks)

    @property
    def kind(self) -> str:
        """'float' (codec path) or 'raw' (byte-compressed path)."""
        return "raw" if self._spec is None else "float"

    def close(self) -> None:
        if self._closed:
            return
        index = F.encode_index(self._entries, self._user_meta)
        index_off = self._pos
        try:
            self._write(index)
            self._write(F.encode_footer(index_off, zlib.crc32(index),
                                        len(self._entries)))
            self._f.flush()
        except BaseException:
            # a failed finalize must not leave a half-written destination:
            # path writers discard the stage (previous file intact)
            if self._staged is not None:
                self._staged.discard()
            self._closed = True
            raise
        if self._staged is not None:
            self._staged.commit()  # fsync -> atomic rename -> dir fsync
        elif self._owns:
            self._f.close()
        self._closed = True

    def abort(self) -> None:
        """Stop WITHOUT finalizing: path destinations keep their previous
        content (the staging file is discarded); file-object destinations
        are left with no index/footer, so readers reject the partial bytes
        loudly instead of parsing a half-written container as complete."""
        if self._closed:
            return
        if self._staged is not None:
            self._staged.discard()
        elif self._owns:
            self._f.close()
        self._closed = True

    def __enter__(self):
        return self

    def __exit__(self, exc_type, exc, tb):
        if exc_type is not None:
            self.abort()
        else:
            self.close()


class ContainerReader:
    """Random-access reader over a finalized container.

    Thread-safe: the only shared mutable state is the file handle, and every
    seek+read pair holds ``_io_lock``; decode itself runs on immutable record
    bytes.  Any number of threads may call ``read_chunk`` / ``read_all`` /
    ``iter_chunks`` on one reader concurrently.

    ``salvage=True`` opens a *damaged* container through the salvage engine
    (``reliability.repair``): the reader then serves exactly the intact
    chunks (every record re-validated by CRC32 + structural parse, never
    wrong bytes) even when the index/footer is corrupt or truncated away;
    the analysis is exposed as ``.salvage_report``.  The default strict
    mode keeps refusing damaged files at open."""

    def __init__(self, path_or_buf, salvage: bool = False):
        self._io_lock = threading.Lock()
        self._label = None
        self._offsets: list[int] | None = None
        self.salvage_report = None
        if isinstance(path_or_buf, (bytes, bytearray, memoryview)):
            self._f = _io.BytesIO(bytes(path_or_buf))
            self._owns = True
        elif hasattr(path_or_buf, "read"):
            self._f = path_or_buf
            self._owns = False
        else:
            self._label = str(path_or_buf)
            self._f = open(Path(path_or_buf), "rb")
            self._owns = True
        try:
            self._open(salvage)
        except ContainerError as e:
            if self._owns:
                self._f.close()
            if self._label is not None and self._label not in str(e):
                # degenerate inputs (empty file, truncated file, non-
                # container bytes, missing backend) must name the path
                # they came from
                raise type(e)(f"{self._label}: {e}") from None
            raise

    def _open(self, salvage: bool) -> None:
        if salvage:
            from ..reliability import repair as _repair

            with self._io_lock:
                self._f.seek(0)
                buf = self._f.read()
            report = _repair.salvage(buf)
            if not report.header_ok:
                raise F.ContainerFormatError(
                    "salvage failed: container header unreadable "
                    f"({report.damage[0].detail})"
                )
            self.salvage_report = report
            self.header = report.header
            self._entries = list(report.entries)
            self.user_meta = report.user_meta
        else:
            self._f.seek(0, 2)
            size = self._f.tell()
            if size == 0:
                raise F.ContainerFormatError("file is empty, not a container")
            if size < F.FOOTER_SIZE + len(F.MAGIC):
                raise F.ContainerFormatError(
                    f"file too small to be a container ({size} bytes; even "
                    f"an empty container holds > {F.FOOTER_SIZE + len(F.MAGIC)})"
                )
            self._f.seek(size - F.FOOTER_SIZE)
            index_off, index_crc, nchunks = F.decode_footer(
                self._f.read(F.FOOTER_SIZE)
            )
            if index_off >= size - F.FOOTER_SIZE:
                raise F.ContainerFormatError(
                    "container index offset out of range"
                )

            self._f.seek(0)
            head = self._f.read(min(size, 1024))
            cur = F._Cursor(head)
            self.header = F.decode_header(cur)

            self._f.seek(index_off)
            index_buf = self._f.read(size - F.FOOTER_SIZE - index_off)
            if zlib.crc32(index_buf) != index_crc:
                raise F.ChecksumError("container index checksum mismatch")
            self._entries, self.user_meta = F.decode_index(index_buf, nchunks)
        self.spec_name = self.header["spec_name"]
        self.backend = self.header["backend"]
        self.dtype = F.resolve_dtype(self.header["dtype"])
        self._be = get_backend(self.backend)

    @property
    def nchunks(self) -> int:
        return len(self._entries)

    def __len__(self) -> int:
        return self.nchunks

    @property
    def n(self) -> int:
        """Total elements across all chunks."""
        return sum(e["n"] for e in self._entries)

    def chunk_offsets(self) -> list[int]:
        """Cumulative element offsets: ``offsets[i]`` is the index of chunk
        i's first element, ``offsets[nchunks]`` the total element count.
        Built once per reader (idempotent, so benign under races)."""
        offs = self._offsets
        if offs is None:
            offs = [0]
            for e in self._entries:
                offs.append(offs[-1] + e["n"])
            self._offsets = offs
        return offs

    def covering_chunks(self, start: int, stop: int) -> tuple[int, int]:
        """The minimal chunk range ``[lo, hi)`` whose elements cover the
        element range ``[start, stop)`` — the partial-read unit (and the
        serving layer's cache key granularity).  ``start == stop`` maps to
        the empty range ``(lo, lo)``."""
        offs = self.chunk_offsets()
        total = offs[-1]
        if not 0 <= start <= stop <= total:
            raise IndexError(
                f"element range [{start}, {stop}) out of bounds for a "
                f"container of {total} elements"
            )
        lo = bisect.bisect_right(offs, start) - 1
        if start == stop:
            return lo, lo
        hi = bisect.bisect_left(offs, stop, lo)
        return lo, hi

    def chunk_info(self, i: int) -> dict:
        e = self._entries[i]
        method = ("raw" if e["method_id"] == F.RAW_METHOD_ID
                  else F.METHOD_NAMES[e["method_id"]])
        return {
            "method": method,
            "n": e["n"],
            "raw": e["n"] * self.dtype.itemsize,
            "comp": e["length"],
        }

    def ratio(self) -> float:
        raw = sum(e["n"] for e in self._entries) * self.dtype.itemsize
        comp = sum(e["length"] for e in self._entries)
        return comp / max(raw, 1)

    @obs.span("container.read_record")
    def _record(self, i: int) -> bytes:
        e = self._entries[i]
        with self._io_lock:
            self._f.seek(e["offset"])
            head = self._f.read(8)
            if len(head) != 8:
                raise F.ContainerFormatError(f"chunk {i}: truncated record")
            (ln,) = struct.unpack("<Q", head)
            if ln != e["length"]:
                raise F.ContainerFormatError(
                    f"chunk {i}: record length {ln} disagrees with index "
                    f"{e['length']}"
                )
            rec = self._f.read(ln)
        if len(rec) != ln:
            raise F.ContainerFormatError(f"chunk {i}: truncated record")
        return rec

    def read_encoded(self, i: int) -> pipeline.Encoded:
        obj = F.deserialize_chunk(
            self._record(i), self._be, spec_name=self.spec_name or None,
            dtype=self.dtype,
        )
        if not isinstance(obj, pipeline.Encoded):
            raise ContainerError(f"chunk {i} is a raw chunk, not an Encoded")
        return obj

    def read_chunk(self, i: int) -> np.ndarray:
        """Decode one chunk to its original values (random access)."""
        obj = F.deserialize_chunk(
            self._record(i), self._be, spec_name=self.spec_name or None,
            dtype=self.dtype,
        )
        if isinstance(obj, pipeline.Encoded):
            return pipeline.decode(obj)
        return obj

    def iter_chunks(self, prefetch: int = 0, workers: int | None = None):
        """Ordered iterator over decoded chunks.

        ``prefetch=0`` decodes lazily, one chunk per ``next()`` (the previous
        serial behavior).  ``prefetch=N > 0`` keeps up to N chunks in flight
        on the shared decode pool (a bounded sliding window, so memory stays
        O(prefetch) regardless of container size) and still yields chunks in
        index order.  A chunk whose decode raises re-raises at the point the
        iterator reaches it; in-flight successors are drained, never yielded.
        ``workers`` runs the window on a dedicated pool of that size instead
        of the shared one (0/None both mean the shared default)."""
        workers = workers or None  # 0 means "default", like read_all
        n = self.nchunks
        if prefetch <= 0 or n <= 1 or (workers is None and in_decode_pool()):
            for i in range(n):
                yield self.read_chunk(i)
            return
        own_pool = ThreadPoolExecutor(
            max_workers=workers, thread_name_prefix=_POOL_THREAD_PREFIX
        ) if workers is not None else None
        pool = own_pool or shared_decode_pool()
        pending: list = []
        nxt = 0
        try:
            while nxt < n and len(pending) < prefetch:
                pending.append(pool.submit(self.read_chunk, nxt))
                nxt += 1
            idx = 0
            while pending:
                fut = pending.pop(0)
                # worker exceptions re-raise here; a WEDGED worker instead
                # trips the watchdog and the chunk is re-decoded serially
                # in this thread (byte-identical — same record bytes)
                chunk = _watchdog.await_or_fallback(
                    fut, lambda i=idx: self.read_chunk(i),
                    f"prefetched chunk {idx}",
                )
                idx += 1
                if nxt < n:
                    pending.append(pool.submit(self.read_chunk, nxt))
                    nxt += 1
                yield chunk
        finally:
            # drain, don't abandon: a future that can't be cancelled is
            # already running — wait it out (and discard its result/error)
            # so no worker races a subsequent close() of this reader; a
            # WEDGED worker is only waited for up to the watchdog bound
            for fut in pending:
                if not fut.cancel():
                    try:
                        fut.exception(timeout=_watchdog.span_timeout())
                    except _watchdog.FutureTimeout:
                        pass
            if own_pool is not None:
                own_pool.shutdown(wait=True)

    def read_all(self, parallel: bool | str = False,
                 workers: int | None = None) -> np.ndarray:
        """Decode every chunk, concatenated flat.

        ``parallel=True`` decodes chunks concurrently (shared decode pool, or
        a dedicated ``workers``-sized pool) directly into a preallocated
        output; the result is byte-identical to the serial path and chunk
        order is deterministic by construction (each chunk lands at its
        index-derived offset).  The first failing chunk's exception is
        re-raised here, in the calling thread.

        Both ``parallel="auto"`` and ``parallel=True`` ride the adaptive
        pool gate (:data:`POOL_POLICY`): the pool engages only when the
        span's estimated serial decode time — from *measured* throughput —
        exceeds :func:`pool_min_work_us` (cold processes fall back to the
        static :data:`PARALLEL_MIN_BYTES` prior).  ``parallel=True`` differs
        only in being exempt from the pool-slower-than-serial demotion and
        in its cold default (pool on).  An explicit ``workers`` count always
        forces the dedicated pool; docs/serving.md §Adaptive pool."""
        return self.read_span(0, self.nchunks, parallel=parallel,
                              workers=workers)

    def read_span(self, lo: int, hi: int, parallel: bool | str = False,
                  workers: int | None = None) -> np.ndarray:
        """Decode chunks ``[lo, hi)``, concatenated flat — the partial-read
        primitive under :meth:`read_all` (the full range) and
        :meth:`read_range` (element ranges).  Same parallel semantics and
        byte-identity contract as :meth:`read_all`."""
        workers = workers or None  # 0 means "default"
        if not 0 <= lo <= hi <= self.nchunks:
            raise IndexError(
                f"chunk span [{lo}, {hi}) out of bounds for "
                f"{self.nchunks} chunks"
            )
        n_chunks = hi - lo
        if not n_chunks:
            return np.zeros(0, self.dtype)
        all_offs = self.chunk_offsets()
        span_bytes = (all_offs[hi] - all_offs[lo]) * self.dtype.itemsize
        if parallel == "auto":
            parallel = POOL_POLICY.should_parallel(span_bytes)
        elif parallel and workers is None:
            # an explicit workers count always forces the dedicated pool;
            # bare parallel=True rides the adaptive gate (tiny spans serial)
            parallel = POOL_POLICY.should_parallel(span_bytes, forced=True)
        if not parallel or n_chunks <= 1 or (workers is None
                                             and in_decode_pool()):
            t0 = time.perf_counter()
            out = np.concatenate(
                [self.read_chunk(i).reshape(-1) for i in range(lo, hi)]
            )
            POOL_POLICY.record("serial", span_bytes,
                               (time.perf_counter() - t0) * 1e6)
            return out
        t0 = time.perf_counter()
        sizes = [e["n"] for e in self._entries[lo:hi]]
        offs = [0]
        for s in sizes:
            offs.append(offs[-1] + s)
        out = np.empty(offs[-1], self.dtype)

        def decode_into(k: int) -> None:
            # RAW/identity records (payload == output bytes) decompress
            # straight into the preallocated output through the backend's
            # decompress_into slot — no per-chunk plaintext assembly under
            # the GIL; transform records take the regular decode + copy.
            i = lo + k
            obj = F.deserialize_chunk_into(
                self._record(i), self._be, out[offs[k] : offs[k + 1]],
                spec_name=self.spec_name or None, dtype=self.dtype,
            )
            if obj is None:
                return
            flat = (pipeline.decode(obj)
                    if isinstance(obj, pipeline.Encoded) else obj).reshape(-1)
            if flat.size != sizes[k]:
                raise F.ContainerFormatError(
                    f"chunk {i}: record holds {flat.size} elements, index "
                    f"claims {sizes[k]}"
                )
            out[offs[k] : offs[k + 1]] = flat

        def decode_span(span: range) -> None:
            for k in span:
                decode_into(k)

        # one task per worker over a contiguous span, not one per chunk:
        # chunk-granular futures would pay a sync round-trip per record,
        # which swamps the overlap win when records decode in ~100 us
        nw = min(workers or default_decode_workers(), n_chunks)
        spans = [range(k * n_chunks // nw, (k + 1) * n_chunks // nw)
                 for k in range(nw)]

        def drain(pool) -> None:
            futs = [pool.submit(decode_span, span) for span in spans]
            for k, fut in enumerate(futs):
                # a wedged worker degrades this span to a serial re-decode
                # in the caller (watchdog); each chunk lands at its index-
                # derived offset either way, so even a worker that wakes up
                # late writes the same bytes — the result stays identical
                _watchdog.await_or_fallback(
                    fut, lambda k=k: decode_span(spans[k]),
                    f"decode span {k + 1}/{len(spans)} "
                    f"(chunks {lo + spans[k].start}..{lo + spans[k].stop - 1})",
                )

        if workers is not None:
            with ThreadPoolExecutor(
                max_workers=workers, thread_name_prefix=_POOL_THREAD_PREFIX
            ) as pool:
                drain(pool)
        else:
            drain(shared_decode_pool())
        POOL_POLICY.record("parallel", span_bytes,
                           (time.perf_counter() - t0) * 1e6)
        return out

    def read_range(self, start: int, stop: int | None = None,
                   parallel: bool | str = "auto",
                   workers: int | None = None) -> np.ndarray:
        """Decode only the elements ``[start, stop)`` — a partial-tensor
        read riding the O(1) chunk index: exactly the chunks covering the
        range are fetched and decoded (:meth:`covering_chunks`), everything
        else stays untouched on disk.  ``stop=None`` means "to the end".
        Out-of-bounds ranges raise ``IndexError`` loudly (no Python-slice
        clamping: a serving request past the tensor is a caller bug).
        Byte-identical to ``read_all()[start:stop]`` by construction."""
        offs = self.chunk_offsets()
        if stop is None:
            stop = offs[-1]
        lo, hi = self.covering_chunks(start, stop)
        span = self.read_span(lo, hi, parallel=parallel, workers=workers)
        return span[start - offs[lo] : stop - offs[lo]]

    def close(self) -> None:
        if self._owns:
            self._f.close()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()


def dumps(enc: pipeline.Encoded, backend: str = "zlib") -> bytes:
    """One Encoded -> a complete single-chunk container (in memory)."""
    bio = _io.BytesIO()
    w = ContainerWriter(
        bio, dtype=F.spec_dtype_name(enc.spec_name), backend=backend
    )
    w.append_encoded(enc)
    w.close()
    return bio.getvalue()


def loads(buf: bytes) -> pipeline.Encoded:
    """Inverse of :func:`dumps`."""
    r = ContainerReader(buf)
    if r.nchunks != 1:
        raise ContainerError(f"expected a single-chunk container, got {r.nchunks}")
    return r.read_encoded(0)
