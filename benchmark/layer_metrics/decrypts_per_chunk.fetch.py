"""Rows decrypted in the window (`DispatchStats.rows`, exact) over the distinct chunks that the answered requests' wanted bytes span
(`chunks_reached`, counted by the generator): 1.0 is once each; above it are second decrypts and what was decrypted ahead of the reader."""
from _spans import counted


def read(observation):
    rows, reached = counted(observation, "rows"), observation["window"].get("chunks_reached")
    return rows / reached if rows is not None and reached else None
