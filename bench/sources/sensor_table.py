"""A sensor table laid out as its public data set is, with values drawn
from the run's seed.

The configuration's ``data`` entry gives the table's columns, in their
order, each with the range and mean that its source states and a spread
and time structure that are assumed; the files and their rows; the
significant digits the values carry.  ``item(k)`` is ``(name, array)``,
the k-th file a writer commits, row-major as the source's CSV is (one row
of every column, then the next).  One pass is every file of the table.
The first pass is one table drawn from ``(seed, 0)``.  Later passes are cut
from a pool of ``pool_passes`` such tables, each from ``(seed, t)``,
starting ``pass_stride_rows`` rows further on for each pass (modulo the
pool), so that a writer's window costs no drawing: a file is a slice.  No
two files of a run start at the same row, so no two hold the same bytes.
A second ``Source`` of the same seed makes the same values again for the
comparison after the window.  Nothing here imports the program.
"""
from __future__ import annotations

import numpy as np


def ar1(innov: np.ndarray, a: float, block: int = 512) -> np.ndarray:
    """u[i] = a * u[i-1] + innov[i], u[-1] = 0, in blocks: a lower-triangular
    matrix of powers of ``a`` inside each block, and a carry between them."""
    n = innov.size
    nb = -(-n // block)
    d = np.zeros(nb * block)
    d[:n] = innov
    d = d.reshape(nb, block)
    j = np.arange(block)
    powers = np.where(j[:, None] >= j[None, :],
                      a ** np.maximum(j[:, None] - j[None, :], 0), 0.0)
    u = d @ powers.T                       # each block started from zero
    carry_in = np.zeros(nb)
    last = u[:, -1]
    ab = a ** block
    c = 0.0
    for b in range(nb):
        carry_in[b] = c
        c = ab * c + last[b]
    u += carry_in[:, None] * (a ** (j + 1))[None, :]
    return u.reshape(-1)[:n]


def round_significant(x: np.ndarray, digits: int) -> np.ndarray:
    """Each value to ``digits`` significant decimal digits, as the double a
    parser gives for that decimal text: an integer over a power of ten, both
    exact, so the one division rounds correctly."""
    mag = np.floor(np.log10(np.where(x == 0, 1.0, np.abs(x))))
    d = (digits - 1 - mag).astype(int)
    if np.any(d < 0) or np.any(d > 22):
        raise ValueError("values out of the range this rounding is exact in")
    p = 10.0 ** d
    return np.round(x * p) / p


def column(n: int, rng: np.random.Generator, c: dict, a: float) -> np.ndarray:
    """``n`` hourly values of one column: a stationary AR(1) with lag-one
    correlation ``a``, mapped to the column's mean and spread, and clipped to
    its range.  ``dist`` is ``normal``, ``lognormal`` (skewed up from zero)
    or ``reflected`` (the column's max less a log-normal gap: skewed down
    towards a ceiling the plant runs at)."""
    u = ar1(np.sqrt(1 - a * a) * rng.standard_normal(n), a)
    u += rng.standard_normal() * a ** np.arange(1, n + 1)   # start stationary
    m, s = c["mean"], c["sd"]

    def lognormal(mean):
        v = np.log1p((s / mean) ** 2)
        return np.exp(np.log(mean) - v / 2 + np.sqrt(v) * u)

    if c["dist"] == "lognormal":
        x = lognormal(m)
    elif c["dist"] == "reflected":
        x = c["max"] - lognormal(c["max"] - m)
    elif c["dist"] == "normal":
        x = m + s * u
    else:
        raise ValueError(f"unknown dist {c['dist']!r}")
    return np.clip(x, c["min"], c["max"])


class Source:
    def __init__(self, config: dict, seed: int):
        p = config["data"]
        self.p = p
        self.rows = [int(r) for r in p["file_rows"]]
        self.first_row = np.concatenate([[0], np.cumsum(self.rows)])
        self.cols = p["columns"]
        self.seed = int(seed) % 2**64
        self.dtype = np.dtype(p["dtype"])
        total = int(self.first_row[-1])
        self.span = int(p["pool_passes"]) * total - total + 1
        self.stride = int(p["pass_stride_rows"])
        self._table0 = self._pool = None

    @property
    def files_per_pass(self) -> int:
        return len(self.rows)

    def _table(self, t: int) -> np.ndarray:
        """One table of every file's rows, ``rows x columns``."""
        rng = np.random.default_rng([self.seed, t])
        a = float(self.p["lag1"])
        table = np.stack([column(int(self.first_row[-1]), rng, c, a)
                          for c in self.cols], axis=1)
        return round_significant(table, int(self.p["significant_digits"])
                                 ).astype(self.dtype)

    def name(self, k: int) -> str:
        pas, f = divmod(k, self.files_per_pass)
        return f"p{pas:05d}.{self.p['file_names'][f]}"

    def item(self, k: int):
        pas, f = divmod(k, self.files_per_pass)
        if pas == 0 and self._pool is None:
            if self._table0 is None:
                self._table0 = self._table(0)
            rows, start = self._table0, 0
        else:
            if self._pool is None:
                self._pool = np.concatenate(
                    [self._table(t) for t in range(int(self.p["pool_passes"]))])
                self._table0 = None
            rows, start = self._pool, (pas * self.stride) % self.span
        a = start + int(self.first_row[f])
        return self.name(k), rows[a:a + self.rows[f]].reshape(-1)

    def free(self) -> None:
        self._table0 = self._pool = None
