"""One ``ShardStore`` container per item, written from the device array as
the program's own ``ShardStore.write`` takes it (chunks sliced on the
device, the container staged, fsynced and renamed), and read back with
``ShardStore.read``.  Each item's container lies in a directory named as
the item, so the driver counts its bytes by the item's name.

The program is imported with this module, before the source draws its
first tensor: importing it switches JAX to 64-bit mode, and a tensor drawn
on either side of that switch would compile its draw twice."""
from __future__ import annotations

from pathlib import Path

import numpy as np
from repro.data.shard_store import ShardStore

SHARD = "w"


class Store:
    def __init__(self, root: Path, p: dict):
        self.root = Path(root)
        self.root.mkdir(parents=True, exist_ok=True)
        self.p = p

    def _shards(self, name: str):
        return ShardStore(self.root / name, backend=self.p["backend"])

    def write(self, name: str, x) -> None:
        self._shards(name).write(SHARD, x, chunk=int(self.p["chunk"]))

    def read(self, name: str) -> np.ndarray:
        return self._shards(name).read(SHARD)

    def files(self):
        return [p for p in self.root.rglob("*") if p.is_file()]
