"""`DispatchStats.device_seen_ns`: nanoseconds of the `device.window` spans the transform backend's device watch recorded (0 with tracing off), read once every window launched so far has been recorded; nothing where the program has no such count."""


def read(deployment) -> dict:
    backend = deployment.backend
    if not hasattr(backend.dispatch_stats, "device_seen_ns"):
        return {}
    watch = getattr(backend, "device_watch", None)
    if watch is not None:
        watch.settle()
    return {"device_seen_ns": backend.dispatch_stats.device_seen_ns}
