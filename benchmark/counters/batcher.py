"""The cross-request batcher's exact counts (`WindowBatcher.counters()`, on `/varz` as `batcher`): windows
submitted and taken inline, decrypt launches and their rows, merged decrypt launches and the distinct keys they
carried; nothing where the deployment has no batcher or the program no such counts."""


def read(deployment) -> dict:
    counters = getattr(getattr(deployment.backend, "batcher", None), "counters", None)
    if counters is None:
        return {}
    return {f"batcher_{name}": value for name, value in counters().items()}
