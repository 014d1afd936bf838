"""Transform backend: mean `transform.decrypt` span, one per fetched chunk that missed the hot tier."""
from _shared import span_mean_ms


def read(observation):
    return span_mean_ms(observation, "transform.decrypt")
