"""Launch: the host's time enqueueing decrypt windows (`transform.launch`), milliseconds per answered fetch."""
from _spans import ms_per_fetch


def read(observation):
    return ms_per_fetch(observation, ("transform.launch",))
