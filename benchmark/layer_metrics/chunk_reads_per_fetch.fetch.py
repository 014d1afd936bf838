"""Chunks the fetch tiers were asked for per answered fetch (hot-tier hits + misses, exact): above
what a reply's wanted bytes span, it is what the gateway read on for a reader that had left."""
from _shared import chunk_reads


def read(observation):
    fetches = observation["window"].get("fetches")
    return chunk_reads(observation) / fetches if fetches else None
