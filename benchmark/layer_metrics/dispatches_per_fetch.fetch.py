"""GCM device launches per answered fetch (`ops.gcm.device_dispatches()`, exact)."""
from _shared import per


def read(observation):
    return per(observation, "gcm_dispatches", "fetches")
