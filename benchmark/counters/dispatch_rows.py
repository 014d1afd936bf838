"""`DispatchStats.rows`: chunk rows of the windows the transform backend launched (exact); nothing where the program has no such count."""


def read(deployment) -> dict:
    rows = getattr(deployment.backend.dispatch_stats, "rows", None)
    return {} if rows is None else {"rows": rows}
