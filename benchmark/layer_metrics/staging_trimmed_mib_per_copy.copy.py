"""Transform backend: MiB of free host staging buffers the backend's ring let go over its bound while windows were being
returned, per acknowledged copy (`DispatchStats.staging_trimmed_bytes`, exact, over the window's copies): 0 where the ring
holds what the encrypt streams in flight hand back, so that every later window of theirs packs into mapped memory; memory the
ring gives back once the streams end is not counted; nothing where the program has no such count."""
from _spans import counter_per


def read(observation):
    trimmed = counter_per(observation, "staging_trimmed_bytes", "copies")
    return None if trimmed is None else trimmed / (1 << 20)
