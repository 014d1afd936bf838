"""d2h: the part of `transform.d2h_wait` before the window's result was ready (`transform.ready_wait`: the program still running); seconds per GiB copied."""
from _idle import wait_half_s_per_gib


def read(observation):
    return wait_half_s_per_gib(observation, "transform.ready_wait")
