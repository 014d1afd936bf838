"""d2h: the part of `transform.d2h_wait` after the window's result was ready (`transform.collect`: the rest of the copy back, being woken, being given the interpreter); seconds per GiB copied."""
from _idle import wait_half_s_per_gib


def read(observation):
    return wait_half_s_per_gib(observation, "transform.collect")
