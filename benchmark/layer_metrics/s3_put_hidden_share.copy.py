"""RSM and storage, under `S3Storage`: percent of the parts' PUT seconds that no upload thread waited for, 100 x (1 - `part_wait_ns` / `part_put_ns`)
(exact counts of the store's part workers): 0 where every PUT is waited out, towards 100 where the PUTs run behind the pull of the next part.
Below 0 where the writers waited longer than the PUTs took (parts queued for a worker). Nothing without the counts, or in a window that put no part."""
from _spans import counted


def read(observation):
    put, wait = counted(observation, "s3_part_put_ns"), counted(observation, "s3_part_wait_ns")
    if not put or wait is None:
        return None
    return 100.0 * (1.0 - wait / put)
