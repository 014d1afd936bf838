"""Host codec: percent of the device's idle seconds of the window held by `transform.compress`."""
from _idle import idle_share


def read(observation):
    return idle_share(observation, "codec")
