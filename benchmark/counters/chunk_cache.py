"""The chunk cache tier's exact counts (`ChunkCache.counters()`, `/varz` `chunk_cache`): the foreground's chunk reads, those served from the cache
with no wait and those that joined a load in flight, every join, degradations, prefetch failures, and the prefetch tasks' own delegate calls and
their chunks; noughts where the deployment has no chunk cache, nothing where the program has no such count."""

NAMES = ("reads", "hits", "read_joins", "inflight_joins", "degradations", "prefetch_failures",
         "prefetch_windows", "prefetch_rows")


def read(deployment) -> dict:
    rsm = deployment.rsm
    if not hasattr(type(rsm), "chunk_cache"):
        return {}
    cache = rsm.chunk_cache
    counts = cache.counters() if cache is not None else dict.fromkeys(NAMES, 0)
    return {f"cache_{name}": counts[name] for name in NAMES}
