"""Sidecar: the broker↔accelerator process boundary (SURVEY §7 step 9).

The reference runs as an in-process JVM plugin; this framework keeps the
TPU runtime in its own process. `server` is the process entry: a configured
RemoteStorageManager behind `http_gateway`, the shim-wire HTTP/1.1 boundary
(`shimwire`) that the Java broker shim in `kafka-shim/` speaks; `client`
offers the same Python RSM surface over that wire plus timeout-based
failover to a local CPU-path RSM.
"""

from tieredstorage_tpu.sidecar.client import (  # noqa: F401
    FailoverRemoteStorageManager,
    SidecarRsmClient,
    SidecarUnavailableError,
)
