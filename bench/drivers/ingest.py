"""Closed-loop ingest: one writer commits the configuration's items in
order, each through the configuration's store, as fast as each commit
returns (one client thread with no target rate, as YCSB's load phase runs
by default).

A window opens after set-up and closes when the first write that ends
after ``seconds`` completes, or after ``limit`` writes.  ``ingest_mb_s`` is
the raw bytes of every item committed in the window over the window;
``stored_ratio`` is the bytes on disk of those items over their raw bytes.
A second window goes on from the item where the first stopped.
"""
from __future__ import annotations

import shutil
import time

import numpy as np

import spec
from reference import mismatched_words


class Driver:
    def __init__(self, cell, seed: int, work, control: bool = False):
        self.cell = cell
        self.p = cell.traffic
        self.seed = seed
        self.src = spec.source(cell, seed)
        self.work = work
        self.control = control
        self.store = spec.store(cell, work / "store", control)
        self.committed: list[int] = []
        self.next = 0
        self.attempted = self.failed = 0

    def _warm_items(self) -> list[int]:
        """One item of each distinct shape, from indices the window never
        reaches, so set-up compiles every shape the window meets."""
        base, n = int(self.p["warm_base"]), int(self.p["warm_scan"])
        seen, out = set(), []
        for k in range(base, base + n):
            shape = tuple(np.shape(self.src.item(k)[1]))
            if shape not in seen:
                seen.add(shape)
                out.append(k)
        return out

    def setup(self) -> None:
        warm = spec.store(self.cell, self.work / "warm", self.control)
        for k in self._warm_items():
            warm.write(*self.src.item(k))
        shutil.rmtree(self.work / "warm", ignore_errors=True)
        self.src.free()

    def window(self, seconds: float, span, limit: int | None = None) -> dict:
        chunk = int(self.cell.config["store"]["chunk"])
        raw = chunks = 0
        first, start = len(self.committed), self.next
        with span("bench.window"):
            t0 = time.perf_counter()
            while (self.next - start < limit if limit is not None
                   else time.perf_counter() - t0 < seconds):
                k = self.next
                name, x = self.src.item(k)
                with span("bench.write_item"):
                    try:
                        self.store.write(name, x)
                    except Exception as e:  # noqa: BLE001 - counted as failed
                        self.failed += 1
                        print(f"write {name} failed: {e!r}", flush=True)
                    else:
                        self.committed.append(k)
                        raw += x.nbytes
                        chunks += -(-int(np.size(x)) // chunk)
                self.next += 1
                self.attempted += 1
            dt = time.perf_counter() - t0
        names = {self.src.name(k) for k in self.committed[first:]}
        stored = sum(p.stat().st_size for p in self.store.files()
                     if p.relative_to(self.store.root).parts[0] in names)
        return {"window_s": dt, "work_mib": raw / 2**20, "chunks": chunks,
                "items": len(self.committed) - first,
                "metrics": {"ingest_mb_s": raw / 1e6 / dt,
                            "stored_ratio": stored / max(raw, 1)}}

    def free(self) -> None:
        self.src.free()

    def check(self, checks) -> None:
        """Read back every committed item and compare every word with the
        input made again from the seed."""
        src = spec.source(self.cell, self.seed)
        bad = unreadable = 0
        for k in self.committed:
            name, want = src.item(k)
            try:
                got = self.store.read(name)
            except Exception as e:  # noqa: BLE001 - an unreadable item
                print(f"read {name} failed: {e!r}", flush=True)
                unreadable += 1
                got = None
            bad += mismatched_words(got, want)
        checks.add("mismatched_words", bad, 0)
        checks.add("unreadable_items", unreadable, 0)
        checks.add("failed_writes", self.failed, 0)
        checks.add("no_item_committed", int(not self.committed), 0)
