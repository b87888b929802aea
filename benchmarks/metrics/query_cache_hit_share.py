"""Share of the window's snapshot queries answered from the per-snapshot
result cache: ``query_cache_hits`` / (hits + ``query_cache_misses``)
(query/snapshot.py:EngineSnapshot.query). Layer: query."""


def read(ctx):
    hits = ctx.counter("query_cache_hits") or 0
    misses = ctx.counter("query_cache_misses") or 0
    if hits + misses <= 0:
        return None
    return 100.0 * hits / (hits + misses)
