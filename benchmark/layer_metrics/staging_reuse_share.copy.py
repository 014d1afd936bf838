"""Transform backend: percent of the window's staged windows (encrypt windows and index rows) that were packed into a host buffer of
the backend's ring, already mapped by an earlier window, and not into fresh memory (`DispatchStats.staging_reused` over
`.staging_acquired`, exact): 0 where every window allocates, near 100 once each shape's buffers circulate."""
from _spans import counted


def read(observation):
    reused, acquired = counted(observation, "staging_reused"), counted(observation, "staging_acquired")
    return 100.0 * reused / acquired if reused is not None and acquired else None
