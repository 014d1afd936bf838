"""Fetch tiers: mean `cache.get_chunks` span, one per chunk a reply's stream asked the chunk cache for (a hit, a join's wait or an owned load)."""
from _shared import span_mean_ms


def read(observation):
    return span_mean_ms(observation, "cache.get_chunks")
