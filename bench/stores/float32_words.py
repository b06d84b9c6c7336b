"""The control: the plain reference in the program's place, one precision
down.  Each float64 item is kept as the raw bytes of its float32 rounding
and read back as float64, so the control run has to come out not correct."""
from __future__ import annotations

from pathlib import Path

import numpy as np


class Store:
    def __init__(self, root: Path, p: dict):
        self.root = Path(root)
        self.root.mkdir(parents=True, exist_ok=True)

    def write(self, name: str, x) -> None:
        x = np.asarray(x).reshape(-1)
        if x.dtype != np.float64:
            raise TypeError(f"float32 words stand in for float64, not {x.dtype}")
        (self.root / f"{name}.raw").write_bytes(x.astype(np.float32).tobytes())

    def read(self, name: str) -> np.ndarray:
        raw = (self.root / f"{name}.raw").read_bytes()
        return np.frombuffer(raw, np.float32).astype(np.float64)

    def files(self):
        return [p for p in self.root.rglob("*") if p.is_file()]

    def server(self, cache_bytes):
        return _Server(self)


class _Server:
    """Reads straight from the store, as the program's server would answer."""

    def __init__(self, store):
        self.store = store

    def read(self, name):
        return self.store.read(name)

    def read_slice(self, name, a, b):
        return self.store.read(name)[a:b]

    def stats(self):
        return {}

    def close(self):
        pass
