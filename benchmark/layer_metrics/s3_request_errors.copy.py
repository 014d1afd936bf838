"""RSM and storage, under `S3Storage`: attempts answered with throttling or a server error, transport failures, and retries, in the window
(exact): 0 on a sound run."""
from _spans import counted


def read(observation):
    return counted(observation, "s3_request_errors")
