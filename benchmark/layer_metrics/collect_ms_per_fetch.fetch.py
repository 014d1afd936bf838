"""d2h: the part of `transform.d2h_wait` after the decrypt window's result was ready (`transform.collect`: the rest of the copy back, being woken, being given the interpreter); milliseconds per answered fetch."""
from _idle import wait_half_ms_per_fetch


def read(observation):
    return wait_half_ms_per_fetch(observation, "transform.collect")
