"""`DispatchStats.encrypt_streams_max`, `.encrypt_stream_launch_sum`, `.launch_queue_sum` and `.staging_trimmed_bytes`: the most
encrypt streams the transform backend had in flight at once, the streams in flight summed over its encrypt launches, the encrypt
windows already in flight summed over all its launches, and the bytes of free staging buffers its ring let go over its bound (exact);
nothing where the program has no such counts."""

NAMES = ("encrypt_streams_max", "encrypt_stream_launch_sum", "launch_queue_sum", "staging_trimmed_bytes")


def read(deployment) -> dict:
    stats = deployment.backend.dispatch_stats
    counts = {name: getattr(stats, name, None) for name in NAMES}
    return {} if None in counts.values() else counts
