"""RSM and storage, under `S3Storage`: connections the client's pool dialled inside the window (`created_total`, exact): 0 where the pool keeps
its connection across the reads."""
from _spans import counted


def read(observation):
    return counted(observation, "s3_connections_created")
