"""Programs JAX traced and lowered inside the window (`program_traces`, exact; the persistent cache saves the
compile, not these): 0 once every shape is warm."""
from _spans import counted


def read(observation):
    return counted(observation, "program_traces")
