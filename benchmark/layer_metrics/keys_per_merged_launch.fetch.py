"""Window program: distinct data keys per merged decrypt launch (`batcher_merged_launch_keys` over
`batcher_merged_launches`, exact): the rows of how many segments one launch decrypted under its key table."""
from _shared import per


def read(observation):
    if "batcher_merged_launches" not in (observation.get("counters") or {}):
        return None
    return per(observation, "batcher_merged_launch_keys", "batcher_merged_launches")
