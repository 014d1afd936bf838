"""Batcher: mean `transform.batch_wait`, a queued decrypt window's wait from enqueue to being woken with its
rows (the wait for batch-mates, the launch it rode and the demultiplex), milliseconds."""
from _shared import span_mean_ms


def read(observation):
    return span_mean_ms(observation, "transform.batch_wait")
