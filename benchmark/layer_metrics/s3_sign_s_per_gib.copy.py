"""RSM and storage, under `S3Storage`: SigV4 (`s3.sign`: the SHA-256 of each request's payload and the signature, a child of each `s3.*` call),
seconds per GiB copied."""
from _spans import seconds_per_gib


def read(observation):
    return seconds_per_gib(observation, ("s3.sign",))
