"""Transform backend: chunk rows per decrypt window of the window (`DispatchStats.rows` over `.windows`, exact; a fetch cell launches
no other window): 1.0 where every chunk is decrypted alone, `prefetch.window.chunks` where the prefetcher's sub-windows are full."""
from _spans import counted


def read(observation):
    rows, windows = counted(observation, "rows"), counted(observation, "windows")
    return rows / windows if rows is not None and windows else None
