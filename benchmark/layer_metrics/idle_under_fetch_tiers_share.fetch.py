"""Fetch tiers: percent of the device's idle seconds of the window held by `cache.*`, `hot.*`, `fetch.*` and `chunk.*` spans."""
from _idle import idle_share


def read(observation):
    return idle_share(observation, "fetch_tiers")
