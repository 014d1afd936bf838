"""The S3 store's exact counts (`S3Storage.counters()`, `/varz` `s3`): attempts by request class and their sum, error totals and retries and their sum,
connections the pool dialled, body bytes sent as parts and read of ranged replies. Noughts under another store; on a program without `counters()` what its
collector and pool already count, and noughts for the rest."""

CLASSES = ("upload-part", "put-object", "get-object", "create-multipart-upload", "complete-multipart-upload", "abort-multipart-upload")
ERRORS = ("throttling", "server", "io")
REST = ("connections_created", "retries", "bytes_sent_as_parts", "bytes_received_ranged")


def _store(rsm):
    store = getattr(rsm, "storage_backend", None) or getattr(rsm, "_storage", None)
    while hasattr(store, "delegate"):
        store = store.delegate
    return store if type(store).__name__ == "S3Storage" else None


def _counts(store) -> dict:
    if store is None:
        return {}
    if hasattr(store, "counters"):
        return store.counters()
    # The parent of PR 35: the collector and the pool are there.
    from tieredstorage_tpu.metrics.core import MetricName

    registry, group = store.metrics.registry, store.metrics.group
    counts = {}
    for name in [f"{c}-requests" for c in CLASSES] + [f"{e}-errors" for e in ERRORS]:
        try:
            counts[name] = int(registry.value(MetricName.of(f"{name}-total", group)))
        except KeyError:
            counts[name] = 0
    counts["connections_created"] = store.client.http.pool.created_total
    return counts


def read(deployment) -> dict:
    counts = _counts(_store(deployment.rsm))
    out = {"s3_" + c.replace("-", "_") + "_requests": counts.get(f"{c}-requests", 0) for c in CLASSES}
    out["s3_requests"] = sum(out.values())
    out["s3_request_errors"] = sum(counts.get(f"{e}-errors", 0) for e in ERRORS) + counts.get("retries", 0)
    out.update({"s3_" + name: counts.get(name, 0) for name in REST})
    return out
