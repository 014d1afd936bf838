"""`DispatchStats.codec_bytes_in` and `.codec_bytes_copied`: source bytes the transform backend handed to its compress codec, and the bytes the host copied into fresh memory around the codec call (a gathered input, frames copied out) (exact); nothing where the program has no such counts."""


def read(deployment) -> dict:
    stats = deployment.backend.dispatch_stats
    handed = getattr(stats, "codec_bytes_in", None)
    copied = getattr(stats, "codec_bytes_copied", None)
    if handed is None or copied is None:
        return {}
    return {"codec_bytes_in": handed, "codec_bytes_copied": copied}
