"""Transform backend: percent of the window's decrypt windows launched in the varlen form, their row width a rung of
`bucket_max_bytes` (`DispatchStats.varlen_windows` over `.windows`, exact): 100 where every chunk is compressed, near 0 for an encrypt-only
segment, where only a full row paired with the ragged one is varlen."""
from _spans import counted


def read(observation):
    varlen, windows = counted(observation, "varlen_windows"), counted(observation, "windows")
    return 100.0 * varlen / windows if varlen is not None and windows else None
