"""Resumable multi-container datasets: arbitrarily large tensors under a
fixed RAM budget.

A *dataset* is a directory of fixed-geometry shard containers
(``part_00000.fpc``, ``part_00001.fpc``, …) plus one JSON ``manifest.json``
naming the parts that are **durably committed** (docs/format.md §Dataset
manifest).  :class:`DatasetWriter` streams an iterable of array pieces
through the bounded-memory core (:mod:`repro.core.streaming`): pieces are
re-chunked to the container geometry by view, encoded under the chunk-window
plan-reuse policy, and written with async write-behind — peak memory is
O(chunk + piece + queue·record) however large the logical tensor is.

Durability is a two-phase commit *per part*: each part container stages,
fsyncs and atomically renames (``reliability.durable.DurableFile``), and only
then is the manifest durably rewritten to include it.  A crash anywhere —
including kill -9 between the two phases — leaves a directory in which the
manifest names only complete, durable containers; :class:`DatasetWriter`
re-opened on that directory **resumes at the last committed part**: the
input stream's already-committed prefix is skipped without re-encoding, a
part that lost the race to the manifest is simply overwritten.  The final
(possibly ragged) part and the ``complete``/``shape`` flags land in one
manifest write, so an incomplete manifest's element total is always
chunk-aligned and the resume watermark is exact.

Each part is planned independently (probe + per-window drift refresh reset
at the part boundary), so the bytes of part *k* do not depend on how many
parts were committed by previous runs — a resumed dataset is byte-identical
to one written in a single run.

:class:`DatasetReader` serves the whole directory as ONE logical container:
it speaks the same protocol as ``ContainerReader`` (``nchunks`` /
``chunk_offsets`` / ``covering_chunks`` / ``read_span`` / ``read_range`` /
``user_meta`` / ``close``), mapping global chunk indices onto lazily-opened
per-part readers — so ``serving.TensorServer`` serves datasets unchanged.
"""
from __future__ import annotations

import bisect
import json
import threading
from pathlib import Path

import numpy as np

from .. import obs
from ..container import ContainerReader, ContainerWriter
from ..container.format import dtype_name, resolve_dtype
from ..core import streaming as _streaming
from ..reliability import durable as _durable, faults as _faults

MANIFEST_NAME = "manifest.json"
DATASET_FORMAT = 1

# parts default to 64 chunk-windows' worth of elements so the per-part
# planner amortizes its probe, rounded to the chunk geometry at runtime
DEFAULT_PART_CHUNKS = 64

_END = object()


class DatasetError(RuntimeError):
    """Malformed dataset directory or misused dataset API."""


def _load_manifest(root: Path) -> dict:
    p = root / MANIFEST_NAME
    try:
        m = json.loads(p.read_bytes())
    except FileNotFoundError:
        raise DatasetError(f"no dataset manifest at {p}") from None
    except (OSError, ValueError) as e:
        raise DatasetError(f"unreadable dataset manifest at {p}: {e}") from None
    if not isinstance(m, dict) or m.get("format") != DATASET_FORMAT:
        raise DatasetError(
            f"unsupported dataset manifest format {m.get('format')!r} at {p}"
        )
    return m


class DatasetWriter:
    """Stream one logical tensor into a resumable multi-container dataset.

    Geometry (``chunk`` elements per record, ``part_elems`` elements per
    container; ``part_elems`` must be a chunk multiple) is fixed at creation
    and recorded in the manifest, so a resuming writer — possibly under a
    different environment — continues with the exact same layout.  Create
    over an existing dataset directory resumes it: the constructor validates
    that dtype/geometry/backend match and :meth:`write` skips the committed
    prefix of the stream.
    """

    def __init__(self, root: str | Path, dtype=None, chunk: int = 65536,
                 part_elems: int | None = None, backend: str = "zlib",
                 method: str = "auto", plan=None):
        self.root = Path(root)
        self._method = method
        self._plan = plan
        self.stats = {"encoded_elements": 0, "skipped_elements": 0,
                      "parts_written": 0, "parts_skipped": 0}
        if (self.root / MANIFEST_NAME).exists():
            m = _load_manifest(self.root)
            # resume: the manifest is authoritative for geometry/backend (a
            # resumed write must match the committed layout whatever the
            # caller's environment says); dtype, if given, must agree
            if dtype is not None and dtype_name(dtype) != m["dtype"]:
                raise DatasetError(
                    f"dataset at {self.root} holds dtype {m['dtype']!r}, "
                    f"not {dtype_name(dtype)!r}"
                )
            self._manifest = m
        else:
            if dtype is None:
                raise DatasetError("a new dataset needs an explicit dtype")
            if chunk < 1:
                raise DatasetError(f"chunk must be >= 1, got {chunk}")
            if part_elems is None:
                part_elems = chunk * DEFAULT_PART_CHUNKS
            if part_elems < chunk or part_elems % chunk:
                raise DatasetError(
                    f"part_elems ({part_elems}) must be a positive multiple "
                    f"of chunk ({chunk})"
                )
            self._manifest = {
                "format": DATASET_FORMAT,
                "dtype": dtype_name(dtype),
                "chunk": int(chunk),
                "part_elems": int(part_elems),
                "backend": backend,
                "shape": None,
                "parts": [],
                "total": 0,
                "complete": False,
            }
            with obs.span("dataset.create"):
                self.root.mkdir(parents=True, exist_ok=True)
                # the initial manifest is durable before any data: a
                # resuming writer always finds the recorded geometry
                self._write_manifest()

    # -- manifest plumbing --------------------------------------------------

    @obs.span("dataset.manifest")
    def _write_manifest(self) -> None:
        _durable.write_bytes(
            self.root / MANIFEST_NAME,
            json.dumps(self._manifest, indent=1).encode("utf-8"),
        )

    @property
    def manifest(self) -> dict:
        return json.loads(json.dumps(self._manifest))

    @property
    def complete(self) -> bool:
        return bool(self._manifest["complete"])

    @property
    def committed_elements(self) -> int:
        """The resume watermark: elements durably committed to the manifest
        (always chunk-aligned while the dataset is incomplete)."""
        return int(self._manifest["total"])

    # -- ingestion ----------------------------------------------------------

    def write(self, pieces, shape=None) -> dict:
        """Stream ``pieces`` (any iterable of array-likes) into the dataset
        and finalize it; returns the final manifest.

        On a resumed dataset the stream must be a repeat of the original:
        its committed prefix is consumed chunk-by-chunk and *skipped*
        (counted in ``stats['skipped_elements']``, never re-encoded), and
        encoding restarts at the watermark.  ``shape`` (optional) is
        validated against the streamed total and recorded in the final
        manifest."""
        if self.complete:
            raise DatasetError(
                f"dataset at {self.root} is already complete; a finished "
                "dataset is immutable"
            )
        m = self._manifest
        chunk, part_elems = int(m["chunk"]), int(m["part_elems"])
        dt = resolve_dtype(m["dtype"])
        it = _streaming.iter_fixed_chunks(pieces, chunk, dtype=dt)

        # skip the committed prefix: the watermark is chunk-aligned (only a
        # complete dataset commits a ragged total), so it is an exact number
        # of full chunks — consume them without touching the encode path
        watermark = self.committed_elements
        skipped = 0
        while skipped < watermark:
            c = next(it, _END)
            if c is _END or skipped + int(c.size) > watermark:
                got = "ended" if c is _END else f"misaligned at {skipped + int(c.size)}"
                raise DatasetError(
                    f"resume stream does not reproduce the committed prefix "
                    f"({watermark} elements committed, stream {got}); a "
                    "resumed write must replay the original stream"
                )
            skipped += int(c.size)
        self.stats["skipped_elements"] += skipped
        self.stats["parts_skipped"] += len(m["parts"])

        nxt = next(it, _END)
        finalized = False
        while nxt is not _END:
            idx = len(m["parts"])
            name = f"part_{idx:05d}.fpc"
            wrote = 0
            nchunks = 0

            def feed():
                nonlocal nxt, wrote, nchunks
                while nxt is not _END and wrote + int(nxt.size) <= part_elems:
                    c, nxt = nxt, next(it, _END)
                    wrote += int(c.size)
                    nchunks += 1
                    yield c

            # phase 1: the part container itself (stage -> fsync -> rename)
            with obs.span("dataset.part", part=idx), ContainerWriter(
                self.root / name, dtype=dt, backend=m["backend"],
                method=self._method, plan=self._plan,
                user_meta={"dtype": m["dtype"], "chunk": chunk, "part": idx},
            ) as w:
                _streaming.stream_chunks(w, feed())
                w.update_user_meta({"shape": [wrote]})
            _faults.maybe_crash("dataset.commit")
            # phase 2: the manifest names the now-durable part; the final
            # part also flips complete/shape in this same write, so an
            # incomplete manifest's total is always chunk-aligned
            m["parts"].append({"name": name, "n": wrote, "chunks": nchunks})
            m["total"] += wrote
            self.stats["encoded_elements"] += wrote
            self.stats["parts_written"] += 1
            if nxt is _END:
                self._finalize(shape)
                finalized = True
            else:
                self._write_manifest()
            _faults.maybe_crash("dataset.manifest")
        if not finalized:
            self._finalize(shape)  # empty stream: zero parts, still a dataset
        return self.manifest

    def _finalize(self, shape) -> None:
        m = self._manifest
        if shape is None:
            shape = [m["total"]]
        elif int(np.prod(shape)) != m["total"]:
            raise DatasetError(
                f"stream produced {m['total']} elements but the declared "
                f"shape {list(shape)} holds {int(np.prod(shape))}"
            )
        m["shape"] = [int(s) for s in shape]
        m["complete"] = True
        self._write_manifest()


class DatasetReader:
    """One logical container over a committed multi-part dataset.

    Speaks the ``ContainerReader`` serving protocol — global chunk indices
    map onto lazily-opened per-part readers, offsets come straight from the
    manifest (no file opens until data is read).  Thread-safe the same way
    the underlying readers are.  ``allow_incomplete=True`` serves the
    committed prefix of an in-progress dataset."""

    def __init__(self, root: str | Path, allow_incomplete: bool = False):
        self.root = Path(root)
        m = _load_manifest(self.root)
        if not m["complete"] and not allow_incomplete:
            raise DatasetError(
                f"dataset at {self.root} is incomplete ({m['total']} elements "
                "committed); pass allow_incomplete=True to read the prefix"
            )
        self._m = m
        self._chunk = int(m["chunk"])
        # global chunk index: parts hold only full chunks plus one optional
        # ragged tail (writer geometry), and the manifest records each
        # part's chunk count — offsets need no file access at all
        self._part_first_chunk = [0]
        self._offsets = [0]
        for p in m["parts"]:
            self._part_first_chunk.append(self._part_first_chunk[-1] + p["chunks"])
            n = int(p["n"])
            full, rag = divmod(n, self._chunk)
            sizes = [self._chunk] * full + ([rag] if rag else [])
            for s in sizes:
                self._offsets.append(self._offsets[-1] + s)
        self._readers: dict[int, ContainerReader] = {}
        self._lock = threading.Lock()
        self._closed = False

    # -- protocol: identity -------------------------------------------------

    @property
    def user_meta(self) -> dict:
        shape = self._m["shape"]
        return {
            "dtype": self._m["dtype"],
            "shape": list(shape) if shape is not None else [self._m["total"]],
            "chunk": self._chunk,
        }

    @property
    def dtype(self) -> np.dtype:
        return resolve_dtype(self._m["dtype"])

    @property
    def nchunks(self) -> int:
        return self._part_first_chunk[-1]

    def __len__(self) -> int:
        return self.nchunks

    @property
    def n(self) -> int:
        return int(self._m["total"])

    def chunk_offsets(self) -> list[int]:
        return self._offsets

    def covering_chunks(self, start: int, stop: int) -> tuple[int, int]:
        offs = self._offsets
        total = offs[-1]
        if not 0 <= start <= stop <= total:
            raise IndexError(
                f"element range [{start}, {stop}) out of bounds for a "
                f"dataset of {total} elements"
            )
        lo = bisect.bisect_right(offs, start) - 1
        hi = bisect.bisect_left(offs, stop) if stop > start else lo
        return lo, max(hi, lo)

    # -- protocol: data -----------------------------------------------------

    def _reader(self, part: int) -> ContainerReader:
        with self._lock:
            if self._closed:
                raise DatasetError("DatasetReader is closed")
            r = self._readers.get(part)
            if r is None:
                r = ContainerReader(self.root / self._m["parts"][part]["name"])
                self._readers[part] = r
            return r

    def read_span(self, lo: int, hi: int, parallel: bool | str = False,
                  workers: int | None = None) -> np.ndarray:
        """Decode global chunks ``[lo, hi)``, concatenated flat — each
        covered part serves its slice of the span (same byte-identity and
        parallel semantics as the single-container reader)."""
        if not 0 <= lo <= hi <= self.nchunks:
            raise IndexError(
                f"chunk span [{lo}, {hi}) out of bounds for "
                f"{self.nchunks} chunks"
            )
        outs = []
        firsts = self._part_first_chunk
        p = bisect.bisect_right(firsts, lo) - 1
        while lo < hi:
            take = min(hi, firsts[p + 1]) - lo
            base = firsts[p]
            outs.append(self._reader(p).read_span(
                lo - base, lo - base + take, parallel=parallel,
                workers=workers))
            lo += take
            p += 1
        if not outs:
            return np.empty(0, self.dtype)
        return outs[0] if len(outs) == 1 else np.concatenate(outs)

    def read_chunk(self, i: int) -> np.ndarray:
        if not 0 <= i < self.nchunks:
            raise IndexError(f"chunk {i} out of bounds for {self.nchunks}")
        p = bisect.bisect_right(self._part_first_chunk, i) - 1
        return self._reader(p).read_chunk(i - self._part_first_chunk[p])

    def read_range(self, start: int, stop: int | None = None,
                   parallel: bool | str = "auto",
                   workers: int | None = None) -> np.ndarray:
        if stop is None:
            stop = self._offsets[-1]
        lo, hi = self.covering_chunks(start, stop)
        span = self.read_span(lo, hi, parallel=parallel, workers=workers)
        off = self._offsets[lo]
        return span[start - off : stop - off]

    def read_all(self, parallel: bool | str = False,
                 workers: int | None = None) -> np.ndarray:
        return self.read_span(0, self.nchunks, parallel=parallel,
                              workers=workers)

    def close(self) -> None:
        with self._lock:
            readers, self._readers = list(self._readers.values()), {}
            self._closed = True
        for r in readers:
            r.close()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
