"""Closed-loop point reads, as YCSB's core workload C runs them: every
operation reads one record, and the record is drawn by YCSB's scrambled
zipfian generator over the whole key space.

Set-up writes one pass of the configuration's items through its store (the
archive being served) and opens the program's server over it with the
traffic's ``cache_bytes``.  A record is one row of the table: ``row_elems``
consecutive values of one item, the records numbered in item order.  The
traffic's ``clients`` threads each send a request, wait for its reply and
send the next.  Keys are drawn from the run's seed, as many as the window
takes, none reused.  A window closes once ``seconds`` have passed and every
reply is in, or after ``limit`` requests.  ``read_p95_ms`` is the 95th
percentile of every request's time from issue to the returned array;
``read_mb_s`` is the bytes returned over the window.
"""
from __future__ import annotations

import threading
import time

import numpy as np

import spec
from reference import mismatched_words

# YCSB's ScrambledZipfianGenerator: a zipfian over ITEM_COUNT items with
# the constant 0.99 and its precomputed zeta, each draw then hashed (FNV-1a,
# 64 bits) onto the key space, so the popular keys are spread over it
ITEM_COUNT = 10_000_000_000
ZIPFIAN_CONSTANT = 0.99
ZETAN = 26.46902820178302
FNV_OFFSET_BASIS_64 = 0xCBF29CE484222325
FNV_PRIME_64 = 1099511628211


def zipfian(u: np.ndarray, items: int = ITEM_COUNT + 1,
            theta: float = ZIPFIAN_CONSTANT, zetan: float = ZETAN) -> np.ndarray:
    """YCSB ``ZipfianGenerator.nextLong`` for uniform draws ``u`` in [0, 1)
    (Gray et al., "Quickly generating billion-record synthetic databases")."""
    zeta2 = 1.0 + 0.5 ** theta
    alpha = 1.0 / (1.0 - theta)
    eta = (1.0 - (2.0 / items) ** (1.0 - theta)) / (1.0 - zeta2 / zetan)
    uz = u * zetan
    ret = (items * (eta * u - eta + 1.0) ** alpha).astype(np.int64)
    ret = np.where(uz < 1.0 + 0.5 ** theta, 1, ret)
    return np.where(uz < 1.0, 0, ret)


def fnvhash64(v: np.ndarray) -> np.ndarray:
    """YCSB ``Utils.fnvhash64``: FNV-1a over the 8 bytes of each long, low
    byte first, then the absolute value as a signed long."""
    v = v.astype(np.uint64)
    h = np.full(v.shape, FNV_OFFSET_BASIS_64, np.uint64)
    with np.errstate(over="ignore"):
        for _ in range(8):
            h ^= v & np.uint64(0xFF)
            v >>= np.uint64(8)
            h *= np.uint64(FNV_PRIME_64)
    return np.abs(h.view(np.int64))


def scrambled_zipfian(rng: np.random.Generator, n: int, keys: int):
    """``n`` keys in ``[0, keys)``."""
    return fnvhash64(zipfian(rng.random(n))) % keys


class Driver:
    def __init__(self, cell, seed: int, work, control: bool = False):
        self.cell = cell
        self.p = cell.traffic
        self.seed = seed
        self.src = spec.source(cell, seed)
        self.store = spec.store(cell, work / "store", control)
        self.rng = np.random.default_rng([int(seed) % 2**64, 1])
        self.server = None
        self.done: list = []          # (key, reply or None, seconds)
        self.failed = 0
        self.attempted = 0
        self.served_bytes = 0

    def setup(self) -> None:
        row = int(self.p["row_elems"])
        self.items = [self.src.item(k) for k in range(self.src.files_per_pass)]
        for name, x in self.items:
            self.store.write(name, x)
        rows = np.array([x.size // row for _, x in self.items])
        self.first_row = np.concatenate([[0], np.cumsum(rows)])
        self.keys = int(self.first_row[-1])
        # every record shape the window meets: each chunk of each item,
        # through a server that is then dropped, so the window's opens cold
        warm = self.store.server(int(self.p["cache_bytes"]))
        for name, _ in self.items:
            warm.read(name)
        warm.close()
        self.server = self.store.server(int(self.p["cache_bytes"]))
        self._keys = iter(())

    def _record(self, key: int):
        i = int(np.searchsorted(self.first_row, key, side="right")) - 1
        row = int(self.p["row_elems"])
        a = (key - int(self.first_row[i])) * row
        return i, a, a + row

    def _next_key(self) -> int:
        k = next(self._keys, None)
        if k is None:
            self._keys = iter(scrambled_zipfian(self.rng, 4096,
                                                self.keys).tolist())
            k = next(self._keys)
        return k

    def window(self, seconds: float, span, limit: int | None = None) -> dict:
        lock = threading.Lock()
        first = len(self.done)
        served0 = self.served_bytes
        stats0 = self.server.stats()
        issued = [0]

        def more() -> bool:
            if limit is not None:
                return issued[0] < limit
            return time.perf_counter() - t0 < seconds

        def client() -> None:
            while True:
                with lock:
                    if not more():
                        return
                    issued[0] += 1
                    key = self._next_key()
                i, a, b = self._record(key)
                ts = time.perf_counter()
                with span("bench.request"):
                    try:
                        out = self.server.read_slice(self.items[i][0], a, b)
                    except Exception as e:  # noqa: BLE001 - counted
                        out = None
                        print(f"request {key} failed: {e!r}", flush=True)
                lat = time.perf_counter() - ts
                with lock:
                    self.failed += out is None
                    self.served_bytes += 0 if out is None else out.nbytes
                    self.done.append((key, out, lat))

        threads = [threading.Thread(target=client, name=f"bench-client-{c}")
                   for c in range(int(self.p["clients"]))]
        with span("bench.window"):
            t0 = time.perf_counter()
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            dt = time.perf_counter() - t0
        self.attempted += issued[0]
        lat_ms = np.array([d[2] for d in self.done[first:]]) * 1e3
        stats = self.server.stats()
        stored = sum(p.stat().st_size for p in self.store.files())
        raw = sum(x.nbytes for _, x in self.items)
        return {"window_s": dt, "requests": len(self.done) - first,
                "decoded_mib": (stats.get("decoded_bytes", 0)
                                - stats0.get("decoded_bytes", 0)) / 2**20,
                "server_stats": stats,
                "metrics": {
                    "read_p95_ms": float(np.percentile(lat_ms, 95)),
                    "read_mb_s": (self.served_bytes - served0) / 1e6 / dt,
                    "stored_ratio": stored / raw}}

    def free(self) -> None:
        self.server.close()

    def check(self, checks) -> None:
        """Every reply against the input's words, made again from the
        seed."""
        src = spec.source(self.cell, self.seed)
        want = [src.item(k)[1] for k in range(src.files_per_pass)]
        bad = 0
        for key, out, _ in self.done:
            i, a, b = self._record(key)
            bad += mismatched_words(out, want[i][a:b])
        checks.add("mismatched_words", bad, 0)
        checks.add("failed_requests", self.failed, 0)
        checks.add("no_request_served", int(not self.done), 0)
