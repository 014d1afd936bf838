"""RSM and storage: `rsm.upload.segment` seconds per GiB copied (the transform runs inside it)."""
from _shared import span_seconds_per_gib


def read(observation):
    return span_seconds_per_gib(observation, ("rsm.upload.segment",))
