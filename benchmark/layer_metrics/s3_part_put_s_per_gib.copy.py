"""RSM and storage, under `S3Storage`: the UploadPart calls (`s3.upload_part`: the signature, the send of a 5 MiB part and the wait for its
reply; 52 a 256 MiB copy, one after another on the thread that pulls the transform's stream), seconds per GiB copied."""
from _spans import seconds_per_gib


def read(observation):
    return seconds_per_gib(observation, ("s3.upload_part",))
