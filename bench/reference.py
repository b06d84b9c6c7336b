"""The comparison that decides ``correct``.

The system stores floats losslessly: what a read returns has to be the
input's bits, word for word.  The plain reference of that guarantee is the
input itself, made again from the seed by ``sources.py``; nothing here
imports the program or takes anything it made.  Every number compared is
exact, so each limit is 0.
"""
from __future__ import annotations

import numpy as np


def _words(a: np.ndarray) -> np.ndarray:
    a = np.ascontiguousarray(a).reshape(-1)
    return a.view(np.dtype(f"u{a.dtype.itemsize}"))


def mismatched_words(got, want: np.ndarray) -> int:
    """Words of ``want`` that ``got`` does not hold bit for bit; a result of
    another length or dtype counts every word of ``want`` (and any extra)."""
    want = np.asarray(want)
    if got is None:
        return int(want.size)
    got = np.asarray(got)
    if got.dtype != want.dtype or got.size != want.size:
        return int(max(want.size, got.size))
    return int(np.count_nonzero(_words(got) != _words(want)))


class Checks:
    """Named numbers, each beside its limit; correct when none exceeds it."""

    def __init__(self):
        self.items: dict[str, dict] = {}

    def add(self, name: str, value: float, limit: float) -> None:
        self.items[name] = {"value": value, "limit": limit}

    @property
    def correct(self) -> bool:
        return bool(self.items) and all(
            c["value"] <= c["limit"] for c in self.items.values())

    def lines(self) -> list[str]:
        return [f"check {k}: {c['value']} (limit {c['limit']})"
                for k, c in self.items.items()]
