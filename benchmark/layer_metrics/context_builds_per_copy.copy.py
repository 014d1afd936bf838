"""Transform backend: GCM context builds per acknowledged copy (`context_builds`, exact): one per distinct
(key, size), so window shapes plus index sizes of a segment's fresh key."""
from _spans import counter_per


def read(observation):
    return counter_per(observation, "context_builds", "copies")
