"""Device: percent of the device's idle seconds of the window that no span of the program held (`device.unclaimed`): the client's own time between requests."""
from _idle import idle_share


def read(observation):
    return idle_share(observation, "unclaimed")
