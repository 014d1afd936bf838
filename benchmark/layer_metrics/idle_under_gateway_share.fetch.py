"""Gateway: percent of the device's idle seconds of the window held by `gateway.*` spans (`device_idle_s` of `Tracer.summary()`)."""
from _idle import idle_share


def read(observation):
    return idle_share(observation, "gateway")
