"""Span-cache hits over lookups in the run's window and traced segment
(``TensorServer.stats()``, counted from the server's opening)."""


def read(ctx):
    last = ctx["segment"] or ctx["window"]
    cache = last.get("server_stats", {}).get("cache", {})
    looked = cache.get("hits", 0) + cache.get("misses", 0)
    if not looked:
        return None
    return 100.0 * cache["hits"] / looked
