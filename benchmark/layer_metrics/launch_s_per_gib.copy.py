"""Launch: the host's time enqueueing the window program (`transform.launch`: constants' placement, the jitted
call with its trace where the shape is new, `copy_to_host_async`); seconds per GiB copied."""
from _spans import seconds_per_gib


def read(observation):
    return seconds_per_gib(observation, ("transform.launch",))
