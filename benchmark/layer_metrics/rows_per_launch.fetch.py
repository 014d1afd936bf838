"""Transform backend: chunk rows per decrypt launch of the batcher, inline and merged launches together
(`batcher_decrypt_launch_rows` over `batcher_decrypt_launches`, exact): 1.0 where no two windows share a launch."""
from _shared import per


def read(observation):
    if "batcher_decrypt_launches" not in (observation.get("counters") or {}):
        return None
    return per(observation, "batcher_decrypt_launch_rows", "batcher_decrypt_launches")
