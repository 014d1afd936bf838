"""Transform backend: the packed host window built from the chunks (`transform.pack`, a host copy of the
window's bytes); seconds per GiB copied."""
from _spans import seconds_per_gib


def read(observation):
    return seconds_per_gib(observation, ("transform.pack",))
