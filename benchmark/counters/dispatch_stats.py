"""The transform backend's `DispatchStats`: windows, launches, transfers and payload bytes (exact)."""


def read(deployment) -> dict:
    stats = deployment.backend.dispatch_stats
    return {
        "windows": stats.windows,
        "dispatches": stats.dispatches,
        "h2d_transfers": stats.h2d_transfers,
        "d2h_fetches": stats.d2h_fetches,
        "bytes_in": stats.bytes_in,
    }
