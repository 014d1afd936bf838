"""RSM and storage, under `S3Storage`: attempts the store's collector counted in the window (every class, retries included) per acknowledged copy
(exact): 56 for a 256 MiB segment at 5 MiB parts (Create + 52 UploadPart + Complete, and a PutObject each for the indexes and the manifest)."""
from _spans import counter_per


def read(observation):
    return counter_per(observation, "s3_requests", "copies")
