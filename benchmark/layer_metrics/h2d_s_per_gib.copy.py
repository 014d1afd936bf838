"""h2d: the host's time placing the packed window on the device (`transform.h2d`: `plan.shard(packed)`; the
transfer itself is the device plane's); seconds per GiB copied."""
from _spans import seconds_per_gib


def read(observation):
    return seconds_per_gib(observation, ("transform.h2d",))
