"""Batcher: the flusher's own milliseconds per merged decrypt launch (`transform.batch_flush` `self_s`: the
pack, the demultiplex and the tag checks, less the launch, the collect and what else its child spans cover,
over `batcher_merged_launches`)."""
from _spans import span_seconds, counted


def read(observation):
    seconds = span_seconds(observation, ("transform.batch_flush",), "self_s")
    launches = counted(observation, "batcher_merged_launches")
    return None if seconds is None or not launches else 1e3 * seconds / launches
