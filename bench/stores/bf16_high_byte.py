"""The control: the plain reference in the program's place, one precision
down.  Each bfloat16 word keeps only its high byte (its sign and the top
seven bits of its exponent) and reads back with a zero low byte, so the
control run has to come out not correct."""
from __future__ import annotations

from pathlib import Path

import ml_dtypes
import numpy as np


class Store:
    def __init__(self, root: Path, p: dict):
        self.root = Path(root)
        self.root.mkdir(parents=True, exist_ok=True)
        self._shapes: dict[str, tuple] = {}

    def write(self, name: str, x) -> None:
        x = np.asarray(x)
        if x.dtype != ml_dtypes.bfloat16:
            raise TypeError(f"high bytes stand in for bfloat16, not {x.dtype}")
        (self.root / name).mkdir(exist_ok=True)
        hi = (x.reshape(-1).view(np.uint16) >> 8).astype(np.uint8)
        (self.root / name / "hi.raw").write_bytes(hi.tobytes())
        self._shapes[name] = x.shape

    def read(self, name: str) -> np.ndarray:
        hi = np.frombuffer((self.root / name / "hi.raw").read_bytes(), np.uint8)
        words = hi.astype(np.uint16) << 8
        return words.view(ml_dtypes.bfloat16).reshape(self._shapes[name])

    def files(self):
        return [p for p in self.root.rglob("*") if p.is_file()]
