"""Transform backend: window build, h2d, launch (`transform.encrypt_dispatch`) and d2h, finish
(`transform.encrypt_finish`), busy seconds per GiB copied."""
from _shared import span_seconds_per_gib


def read(observation):
    return span_seconds_per_gib(
        observation, ("transform.encrypt_dispatch", "transform.encrypt_finish")
    )
