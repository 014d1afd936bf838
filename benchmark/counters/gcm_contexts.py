"""`ops.gcm.context_stats()`: GCM context builds (cache misses), those that ran beside another build of the same (key, aad, size), and their summed seconds (exact); nothing where the program has no such count."""


def read(deployment) -> dict:
    from tieredstorage_tpu.ops import gcm

    stats = getattr(gcm, "context_stats", None)
    return stats() if stats is not None else {}
