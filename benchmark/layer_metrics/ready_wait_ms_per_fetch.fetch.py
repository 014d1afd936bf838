"""d2h: the part of `transform.d2h_wait` before the decrypt window's result was ready (`transform.ready_wait`: the program still running); milliseconds per answered fetch."""
from _idle import wait_half_ms_per_fetch


def read(observation):
    return wait_half_ms_per_fetch(observation, "transform.ready_wait")
