"""Share of the run's chunks (window and traced segment) that took the
classic host path (``scoring.PHASE2.fallbacks``) instead of the fused
device encode."""


def read(ctx):
    chunks = sum((w or {}).get("chunks", 0)
                 for w in (ctx["window"], ctx["segment"]))
    if not chunks:
        return None
    return 100.0 * ctx["counters"]["phase2_fallbacks"] / chunks
