"""Device launches per transform window over the timed copies (DispatchStats, exact)."""
from _shared import per


def read(observation):
    return per(observation, "dispatches", "windows")
