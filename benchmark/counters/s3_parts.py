"""The S3 store's part workers (`S3Storage.counters()`, `/varz` `s3`; exact): nanoseconds of `upload_part` calls on the workers and nanoseconds the uploads'
threads stood in `s3.part_wait`. Nothing under another store, or on a program whose multipart stream puts its parts on the thread that fills them
(no such counts). `parts_in_flight_max` is on `/varz` too; it is a high-water mark, and a window's difference of it says nothing."""
from s3_requests import _store

NAMES = ("part_put_ns", "part_wait_ns")


def read(deployment) -> dict:
    store = _store(deployment.rsm)
    counts = store.counters() if hasattr(store, "counters") else {}
    return {"s3_" + name: counts[name] for name in NAMES if name in counts}
