"""`DispatchStats.varlen_windows` and `.padded_bytes`: the windows the transform backend launched in the varlen form, and the bytes its windows' rows were staged at (rows x the window's row width, beside `bytes_in`) (exact); nothing where the program has no such counts."""


def read(deployment) -> dict:
    stats = deployment.backend.dispatch_stats
    varlen = getattr(stats, "varlen_windows", None)
    padded = getattr(stats, "padded_bytes", None)
    if varlen is None or padded is None:
        return {}
    return {"varlen_windows": varlen, "padded_bytes": padded}
