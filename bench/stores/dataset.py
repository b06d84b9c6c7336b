"""One ``DatasetWriter`` dataset per item (two-phase commit: each part
container stages, fsyncs and renames, then the manifest is rewritten
durably), read back with ``DatasetReader``."""
from __future__ import annotations

from pathlib import Path

import numpy as np


class Store:
    def __init__(self, root: Path, p: dict):
        self.root = Path(root)
        self.root.mkdir(parents=True, exist_ok=True)
        self.p = p

    def write(self, name: str, x) -> None:
        from repro.data.dataset import DatasetWriter

        DatasetWriter(self.root / name, dtype=x.dtype, chunk=self.p["chunk"],
                      backend=self.p["backend"]).write([x])

    def read(self, name: str) -> np.ndarray:
        from repro.data.dataset import DatasetReader

        with DatasetReader(self.root / name) as r:
            return r.read_all()

    def files(self):
        return [p for p in self.root.rglob("*") if p.is_file()]

    def server(self, cache_bytes):
        """The program's server over this store."""
        from repro.serving import TensorServer

        return TensorServer(self.root, cache_bytes=cache_bytes)
