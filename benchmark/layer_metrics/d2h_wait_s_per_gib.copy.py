"""d2h: the host blocked on the window's result (`transform.d2h_wait`: `np.asarray(out)`, the device's work
still outstanding plus the copy back); seconds per GiB copied."""
from _spans import seconds_per_gib


def read(observation):
    return seconds_per_gib(observation, ("transform.d2h_wait",))
