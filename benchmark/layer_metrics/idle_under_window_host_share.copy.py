"""Transform backend: percent of the device's idle seconds of the window held by the window's host side, `transform.*` spans other than the codec's two."""
from _idle import idle_share


def read(observation):
    return idle_share(observation, "window_host")
