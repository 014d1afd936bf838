"""Host codec: seconds of the `transform.decompress` spans (the codec's half of a detransform, after the tags have been verified: on the
cache's pool threads under a prefetching cache, on the reader's in the foreground) over the chunks decrypted and so decompressed
(`DispatchStats.rows`; a fetch cell launches no other window), in milliseconds; nothing to read without a codec or without the span."""
from _spans import counted, span_seconds


def read(observation):
    seconds, rows = span_seconds(observation, ("transform.decompress",)), counted(observation, "rows")
    return 1e3 * seconds / rows if seconds is not None and rows else None
