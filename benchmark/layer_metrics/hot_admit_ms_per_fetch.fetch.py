"""Fetch tiers: what admitting fetches pay the hot tier (`hot.admit`: the window's host mirror, device
retention, the insert), milliseconds per answered fetch."""
from _spans import ms_per_fetch


def read(observation):
    return ms_per_fetch(observation, ("hot.admit",))
