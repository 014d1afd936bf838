"""Window program and kernels: percent of the bytes the window's rows were staged at that is padding up to the row width (1 less
`DispatchStats.bytes_in` over `.padded_bytes`, exact): what the ladder's rung costs in transfer and device work, before the device's own
row padding; 0 for fixed-shape windows."""
from _spans import counted


def read(observation):
    payload, padded = counted(observation, "bytes_in"), counted(observation, "padded_bytes")
    return 100.0 * (1.0 - payload / padded) if payload is not None and padded else None
