"""Device time of the phase-1 candidate grid (``core/scoring._grid_score``)
per MiB committed in the traced segment."""
PROGRAMS = r"^jit__grid_score$"


def read(ctx):
    t, seg = ctx["trace"], ctx["segment"]
    if t is None or t.cut or not seg or not seg.get("work_mib"):
        return None
    s = t.seconds_in(PROGRAMS)
    return s * 1e3 / seg["work_mib"] if s else None
