"""Percent of the foreground's chunk reads that waited on a load already in flight (`read_joins` over `reads`, exact): the reader
caught the prefetcher."""
from _spans import counted


def read(observation):
    reads = counted(observation, "cache_reads")
    return 100.0 * counted(observation, "cache_read_joins") / reads if reads else None
