"""RSM and storage: the store's ranged reads of stored chunks (`storage.fetch_chunks`), milliseconds per
answered fetch."""
from _spans import ms_per_fetch


def read(observation):
    return ms_per_fetch(observation, ("storage.fetch_chunks",))
