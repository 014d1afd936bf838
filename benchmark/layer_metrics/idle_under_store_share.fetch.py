"""RSM and storage: percent of the device's idle seconds of the window held by `rsm.*`, `storage.*` and `s3.*` spans."""
from _idle import idle_share


def read(observation):
    return idle_share(observation, "store")
