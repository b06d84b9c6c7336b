"""Chip peaks and the least time a kernel's work can take on them.

The work is counted from the shapes of the work itself, not from how a
kernel does it, so a later kernel that replaces another is held to the
same count.  Peaks come from ``peaks.json``, keyed by ``device_kind``; a
device that is not there is an error, never a default.
"""
from __future__ import annotations

import json
from pathlib import Path

PEAKS_FILE = Path(__file__).resolve().parent / "peaks.json"


class UnknownDevice(KeyError):
    pass


def peaks(device_kind: str, path: Path = PEAKS_FILE) -> dict:
    table = json.loads(path.read_text())["devices"]
    if device_kind not in table:
        raise UnknownDevice(
            f"no peaks for device kind {device_kind!r} in {path.name}; "
            f"known: {sorted(table)}")
    return table[device_kind]


# Scoring one 32-bit word of a candidate's stream: per bit plane, the set
# bit and the flip against the previous word (extract and add each: 32 x 2
# x 2), the xor that makes the flips (1), and per byte its extraction
# (shift, mask) and its histogram count (4 x 3).
SCOREGRID_OPS_PER_WORD32 = 32 * 2 * 2 + 1 + 4 * 3
SCOREGRID_STATS_PER_ROW = 64 + 64 + 256   # ones, flips per plane; byte hist


def scoregrid_work(candidates: int, sample: int, word_bytes: int) -> dict:
    """Operations and bytes of scoring ``candidates`` transformed streams of
    ``sample`` words of ``word_bytes`` each: the word grid read once, the
    per-candidate statistics (int32) written once."""
    words32 = candidates * sample * word_bytes // 4
    return {"ops": words32 * SCOREGRID_OPS_PER_WORD32,
            "bytes": candidates * sample * word_bytes
            + candidates * SCOREGRID_STATS_PER_ROW * 4}


def least_time(work: dict, peak: dict) -> tuple[float, str]:
    """The larger of operations over the integer peak and bytes over the
    memory bandwidth, and which of the two bounds it.  The integer peak is
    the chip's published int8 rate, the only integer rate published; the
    vector unit's is lower, so the compute bound errs low."""
    t_ops = work["ops"] / peak["int8_ops_per_s"]
    t_mem = work["bytes"] / peak["hbm_bytes_per_s"]
    return (t_ops, "compute") if t_ops >= t_mem else (t_mem, "memory")
