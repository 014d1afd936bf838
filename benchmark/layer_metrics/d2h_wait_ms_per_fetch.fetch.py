"""d2h: the host blocked on decrypt windows' results (`transform.d2h_wait`), milliseconds per answered fetch."""
from _spans import ms_per_fetch


def read(observation):
    return ms_per_fetch(observation, ("transform.d2h_wait",))
