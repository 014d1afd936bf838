"""RSM and storage: the store's own time in `upload(stream, key)`: `storage.upload`'s self time, its children
being the transform's spans, which the store pulls through the stream; seconds per GiB copied."""
from _spans import seconds_per_gib


def read(observation):
    return seconds_per_gib(observation, ("storage.upload",), "self_s")
