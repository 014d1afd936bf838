"""Transform backend: milliseconds in GCM context builds (`context_build_seconds`, summed over the builds, so
a pile-up of four counts its wall time four times) per answered fetch."""
from _spans import counter_ms_per_fetch


def read(observation):
    return counter_ms_per_fetch(observation, "context_build_seconds")
