"""Transform backend: seconds in GCM context builds (`ops.gcm.context_stats()` `context_build_seconds`, cache
misses only: a fresh key's H-power matrices in pure Python) per GiB copied."""
from _spans import counter_seconds_per_gib


def read(observation):
    return counter_seconds_per_gib(observation, "context_build_seconds")
