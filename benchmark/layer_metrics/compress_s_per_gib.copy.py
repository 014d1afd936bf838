"""Host codec: `transform.compress` busy seconds per GiB copied; nothing to read without a codec."""
from _shared import span_seconds_per_gib


def read(observation):
    return span_seconds_per_gib(observation, ("transform.compress",))
