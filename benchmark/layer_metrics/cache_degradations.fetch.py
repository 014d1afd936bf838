"""Chunk reads in the window that a cache failure (a load or a join past `get.timeout.ms`, cache-storage I/O) turned into a direct
fetch below the cache (`ChunkCache.degradations`, exact): 0 on a sound run."""
from _spans import counted


def read(observation):
    return counted(observation, "cache_degradations")
