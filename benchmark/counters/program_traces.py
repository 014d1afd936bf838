"""`utils.platforms.program_trace_stats()`: programs JAX traced and lowered in this process, whether or not the persistent cache then hit, and their seconds (exact); nothing where the program has no such count."""


def read(deployment) -> dict:
    from tieredstorage_tpu.utils import platforms

    stats = getattr(platforms, "program_trace_stats", None)
    return stats() if stats is not None else {}
