"""The program's own host spans (``repro.obs``) over the traced segment,
for the per-layer metrics that read them.

Spans record only while the profiler collects, so the table holds the
traced segment and nothing else.  A program without ``repro.obs`` has no
spans: every function here then returns ``None``, as it does when the
segment or the spans it needs are missing.
"""
from __future__ import annotations

# the suffix of every span in which the host waits on the device
DEVICE_WAIT = ".device_wait"


def totals() -> dict:
    """``{name: (count, total_s, self_s)}``, or ``{}`` for a program
    without spans."""
    try:
        from repro import obs
    except ImportError:
        return {}
    return obs.totals()


def _sum(t: dict, names, field: int):
    """Field ``field`` (0 count, 1 total, 2 self) summed over the spans
    ``names`` (a tuple of names, or a suffix of names as a string); None
    when none of them was recorded."""
    hits = [v for k, v in t.items()
            if (k.endswith(names) if isinstance(names, str) else k in names)]
    return sum(v[field] for v in hits) if hits else None


def per_mib(ctx, names, field: int = 1, scale: float = 1e3):
    """``names``' field per MiB committed in the traced segment (seconds
    read as ms by default)."""
    seg = ctx.get("segment")
    if not seg or not seg.get("work_mib"):
        return None
    s = _sum(totals(), names, field)
    return None if s is None else s * scale / seg["work_mib"]


def per_miss(ctx, names, field: int = 1, scale: float = 1e3):
    """``names``' field per decode in the traced segment (one
    ``server.decode_span`` each), in ms by default."""
    if not ctx.get("segment"):
        return None
    t = totals()
    misses = _sum(t, ("server.decode_span",), 0)
    if not misses:
        return None
    s = _sum(t, names, field)
    return None if s is None else s * scale / misses
