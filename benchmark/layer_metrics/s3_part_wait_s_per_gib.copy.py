"""RSM and storage, under `S3Storage`: seconds an upload's own thread stood waiting for its parts (`s3.part_wait`: a hand-over with every part in flight
already, and `close` before and after the short last part), per GiB copied. Nothing where the parts are put on the thread that fills them (no such span)."""
from _spans import seconds_per_gib


def read(observation):
    return seconds_per_gib(observation, ("s3.part_wait",))
