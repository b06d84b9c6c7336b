"""The shard/tensor server: many concurrent readers over compressed
containers — cache, coalesce, partial-decode.

:class:`TensorServer` is the read front of a :class:`~repro.data.shard_store.
ShardStore` directory (one ``<name>.fpc`` container per tensor).  Every
request flows::

    request (full tensor | element slice)
      -> covering chunk span  (O(1) via the container chunk index)
      -> SpanCache lookup     (hot tensors: no decode at all)
      -> SingleFlight         (concurrent misses of one span: ONE decode)
      -> ContainerReader.read_span(parallel="auto")   (adaptive decode pool)
      -> frozen (read-only) ndarray shared by every reader of the span

Served bytes are **bitwise-identical to a serial ``read_all``** by
construction: the cache stores exactly what the reader decoded, the reader's
parallel path is byte-identical to its serial path (PR 3 contract), and
results are frozen so no consumer can mutate them for the next one.

Thread safety: readers are opened once per tensor under a lock and are
themselves thread-safe; the cache and flight table are locked primitives;
request counters sit behind their own lock.  Any number of threads may call
:meth:`read` / :meth:`read_slice` concurrently.
"""
from __future__ import annotations

import threading
from pathlib import Path

import numpy as np

from .. import obs
from ..container import ContainerReader
from ..container.format import resolve_dtype
from ..data.dataset import DatasetReader
from ..data.shard_store import ShardStore
from .cache import SpanCache
from .coalesce import SingleFlight


class TensorServer:
    """Serve decoded tensors (and slices) from a shard-store directory.

    ``cache_bytes=None`` takes the ``REPRO_SERVE_CACHE_BYTES`` default;
    ``cache_bytes=0`` disables caching (every request decodes — the
    benchmark's uncached baseline).  ``parallel`` is forwarded to the
    container decode ("auto" = the adaptive pool gate; docs/serving.md).
    """

    def __init__(self, root, cache_bytes: int | None = None,
                 parallel: bool | str = "auto"):
        self._store = root if isinstance(root, ShardStore) else ShardStore(root)
        self._parallel = parallel
        self._cache = SpanCache(cache_bytes)
        self._flight = SingleFlight()
        self._readers: dict[str, ContainerReader] = {}
        self._readers_lock = threading.Lock()
        self._stats_lock = threading.Lock()
        self._requests = {"full": 0, "slice": 0}
        self._decodes = 0
        self._decoded_bytes = 0
        self._closed = False

    # -- plumbing -----------------------------------------------------------

    @property
    def root(self) -> Path:
        return self._store.root

    def names(self) -> list[str]:
        """Tensors currently present in the store directory: single-shard
        containers and multi-part dataset directories alike."""
        return sorted({p.stem for p in self.root.glob("*.fpc")}
                      | {p.parent.name
                         for p in self.root.glob("*/manifest.json")})

    def _reader(self, name: str):
        with self._readers_lock:
            if self._closed:
                raise RuntimeError("TensorServer is closed")
            r = self._readers.get(name)
            if r is None:
                path = self._store.path(name)
                if (not path.exists()
                        and (self.root / name / "manifest.json").exists()):
                    # a resumable multi-part dataset (data.dataset): its
                    # reader speaks the ContainerReader serving protocol, so
                    # the cache/coalesce/span machinery below is unchanged
                    r = DatasetReader(self.root / name)
                else:
                    r = ContainerReader(path)
                self._readers[name] = r
            return r

    def _decode_span(self, name: str, lo: int, hi: int) -> np.ndarray:
        """The one place decode happens — tests and the benchmark override
        this seam to gate/observe decodes deterministically."""
        return self._reader(name).read_span(lo, hi, parallel=self._parallel)

    def _span(self, name: str, lo: int, hi: int) -> np.ndarray:
        """Cached + coalesced decoded span of chunks [lo, hi)."""
        key = (name, lo, hi)
        with obs.span("cache.get"):
            arr = self._cache.get(key)
        if arr is not None:
            return arr

        def decode():
            with obs.span("server.decode_span", tensor=name, lo=lo, hi=hi):
                a = self._decode_span(name, lo, hi)
            with self._stats_lock:
                self._decodes += 1
                self._decoded_bytes += a.nbytes
            # freeze-then-cache: even when the span is over budget (put
            # returns False) the result handed out is read-only
            self._cache.put(key, a)
            return a

        arr, _shared = self._flight.do(key, decode)
        return arr

    # -- public API ---------------------------------------------------------

    def meta(self, name: str) -> dict:
        """Shape/dtype/chunking user-meta of one tensor (no decode)."""
        return dict(self._reader(name).user_meta)

    def n_elements(self, name: str) -> int:
        """Flattened element count of one tensor (index only, no decode)."""
        return self._reader(name).chunk_offsets()[-1]

    def read(self, name: str) -> np.ndarray:
        """The full tensor, shaped per the shard's user-meta.  Read-only:
        copy before mutating (the buffer is shared with every other reader
        of this tensor)."""
        with self._stats_lock:
            self._requests["full"] += 1
        r = self._reader(name)
        flat = self._span(name, 0, r.nchunks)
        meta = r.user_meta
        out = flat.reshape(meta["shape"]) if "shape" in meta else flat
        return out.astype(resolve_dtype(meta["dtype"]), copy=False) \
            if "dtype" in meta else out

    def read_slice(self, name: str, start: int, stop: int) -> np.ndarray:
        """Elements ``[start, stop)`` of the flattened tensor, decoding only
        the covering chunks (partial read; read-only).  Byte-identical to
        ``read(name).reshape(-1)[start:stop]`` — the partial-read contract
        (docs/serving.md §Partial reads)."""
        with obs.span("server.read_slice", tensor=name, start=start,
                      stop=stop):
            with self._stats_lock:
                self._requests["slice"] += 1
            r = self._reader(name)
            lo, hi = r.covering_chunks(start, stop)
            span = self._span(name, lo, hi)
            off = r.chunk_offsets()[lo]
            return span[start - off : stop - off]

    def invalidate(self, name: str) -> None:
        """Forget one tensor (rewritten shard): drop its reader and every
        cached span keyed by it."""
        with self._readers_lock:
            r = self._readers.pop(name, None)
        if r is not None:
            r.close()
        for key in self._cache.keys():
            if key[0] == name:
                self._cache.invalidate(key)

    def stats(self) -> dict:
        """Merged counters: requests, decodes, cache, coalescing."""
        with self._stats_lock:
            out = {
                "requests_full": self._requests["full"],
                "requests_slice": self._requests["slice"],
                "decodes": self._decodes,
                "decoded_bytes": self._decoded_bytes,
            }
        out["cache"] = self._cache.stats()
        out["coalesced"] = self._flight.coalesced
        out["flight_leaders"] = self._flight.leaders
        return out

    def reset_stats(self) -> None:
        with self._stats_lock:
            self._requests = {"full": 0, "slice": 0}
            self._decodes = 0
            self._decoded_bytes = 0
        self._cache.reset_stats()
        self._flight.reset_stats()

    @property
    def cache(self) -> SpanCache:
        return self._cache

    def close(self) -> None:
        with self._readers_lock:
            self._closed = True
            readers, self._readers = list(self._readers.values()), {}
        for r in readers:
            r.close()
        self._cache.clear()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
