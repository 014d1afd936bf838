"""Gateway: the request body read off the socket into a spooled file (`gateway.spool`) and decoded from it
into section files (`gateway.decode`), before the RSM is called; seconds per GiB copied."""
from _spans import seconds_per_gib


def read(observation):
    return seconds_per_gib(observation, ("gateway.spool", "gateway.decode"))
