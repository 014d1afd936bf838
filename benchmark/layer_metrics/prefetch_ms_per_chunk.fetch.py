"""Fetch tiers: seconds of the `cache.prefetch` spans (a prefetch task: its store reads, decrypts and waits on loads it joined) over the
chunks the tasks loaded themselves (`prefetch_rows`), in milliseconds. Tasks overlap on the cache's pool, so this is pool time, not wall time."""
from _spans import counted, span_seconds


def read(observation):
    seconds, rows = span_seconds(observation, ("cache.prefetch",)), counted(observation, "cache_prefetch_rows")
    return 1e3 * seconds / rows if seconds is not None and rows else None
