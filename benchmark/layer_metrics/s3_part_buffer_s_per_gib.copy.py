"""RSM and storage, under `S3Storage`: the multipart stream's own copies of every byte, into and out of its part buffer (`s3.part_buffer`:
`write`'s `bytes(data)`, the `extend`, the part sliced out and deleted from the front, `_flush_part`'s `bytes(data)`), seconds per GiB copied."""
from _spans import seconds_per_gib


def read(observation):
    return seconds_per_gib(observation, ("s3.part_buffer",))
