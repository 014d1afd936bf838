"""`DispatchStats.staging_acquired` and `.staging_reused`: host staging buffers the transform backend's windows took, and those of them that came from its ring of reused buffers (exact); nothing where the program has no such counts."""


def read(deployment) -> dict:
    stats = deployment.backend.dispatch_stats
    acquired = getattr(stats, "staging_acquired", None)
    reused = getattr(stats, "staging_reused", None)
    if acquired is None or reused is None:
        return {}
    return {"staging_acquired": acquired, "staging_reused": reused}
