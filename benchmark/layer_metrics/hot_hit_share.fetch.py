"""Percent of the chunk reads of the window that the device hot tier served (`DeviceHotCache.hits`
over hits + misses, exact): one chunk read per chunk a reply's stream reached."""
from _shared import chunk_reads


def read(observation):
    reads = chunk_reads(observation)
    return 100.0 * observation["counters"]["hot_hits"] / reads if reads else None
