"""Percent of the chunk reads of the window on which the device hot tier admitted the chunk
(`DeviceHotCache.admissions`, exact): a second decrypt of a chunk that was decrypted before."""
from _shared import chunk_reads


def read(observation):
    reads = chunk_reads(observation)
    return 100.0 * observation["counters"]["hot_admissions"] / reads if reads else None
