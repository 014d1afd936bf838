"""Transform backend: context builds of the window that started while another build of the same (key, aad,
size) was running (`context_builds_duplicate`, exact): what single flight would save."""
from _spans import counted


def read(observation):
    return counted(observation, "context_builds_duplicate")
