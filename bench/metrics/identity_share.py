"""Share of the run's chunks (window and traced segment) that phase 2
shipped as identity, from the program's count of chunks shipped by method
(``scoring.PHASE2.shipped``, reset with the other counters as the window
opens).  ``None`` where the run holds nothing to read, or the program has
no such count."""


def read(ctx):
    if not (ctx.get("window") or ctx.get("segment")):
        return None
    try:
        from repro.core import scoring
    except ImportError:
        return None
    shipped = getattr(scoring.PHASE2, "shipped", None)
    total = sum(shipped.values()) if shipped else 0
    if not total:
        return None
    return 100.0 * shipped.get("identity", 0) / total
