"""RSM and storage, under `S3Storage`: the signed ranged GETs of stored chunks (`s3.get_object`: request sent to the body's last byte read),
milliseconds per answered fetch."""
from _spans import ms_per_fetch


def read(observation):
    return ms_per_fetch(observation, ("s3.get_object",))
