"""The phase-1 scoring kernel's (``kernels/scoregrid``) share of its
roofline: the least time its work takes at the chip's peaks over the
kernel's device time in the traced window.

The work is read from the kernel's own operand shape: the op's name in
the trace is its HLO text, ``... custom-call(u32[rows, r, 128] ...),
custom_call_target="tpu_custom_call"``: a grid of ``rows`` rows of
``r * 128`` 32-bit words, scored once.
"""
import re

import roofline

STATS_PROGRAMS = r"^jit__grid_score$"
GRID = re.compile(r"u32\[(\d+),(\d+),128\]")


def _kernel(op: str):
    """``(rows, words per row)`` when the op is the Pallas kernel."""
    if "tpu_custom_call" not in op:
        return None
    m = GRID.search(op)
    return (int(m.group(1)), int(m.group(2)) * 128) if m else None


def read(ctx):
    t = ctx["trace"]
    if t is None:
        return None
    kernels = [(secs, _kernel(op)) for _p, op, secs, _stats in t.kernel_ops]
    kernels = [(secs, shape) for secs, shape in kernels if shape is not None]
    if not kernels:
        return None
    peak = roofline.peaks(ctx["device_kind"])
    least = busy = 0.0
    for secs, (rows, words) in kernels:
        least += roofline.least_time(roofline.scoregrid_work(rows, words, 4),
                                     peak)[0]
        busy += secs
    return 100.0 * least / busy if busy else None
