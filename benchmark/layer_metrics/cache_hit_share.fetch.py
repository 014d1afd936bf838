"""Percent of the foreground's chunk reads that the chunk cache served with no wait (`ChunkCache.counters()` `hits` over `reads`, exact):
the rest joined a load in flight or started one."""
from _spans import counted


def read(observation):
    reads = counted(observation, "cache_reads")
    return 100.0 * counted(observation, "cache_hits") / reads if reads else None
