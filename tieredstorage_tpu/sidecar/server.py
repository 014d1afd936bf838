"""The sidecar process: a RemoteStorageManager behind the shim-wire gateway.

Runs the full TPU transform/storage runtime in its own process; brokers
(the Java shim in `kafka-shim/`, or the Python SidecarRsmClient) drive
copy/fetch/fetch-index/delete over the HTTP gateway
(sidecar/http_gateway.py), the process's one broker-facing listener.

Start standalone:  python -m tieredstorage_tpu.sidecar --config cfg.json
(`--port 0` picks a free port; the bound port is printed as
`SIDECAR_READY port=<n>` for supervising processes to scrape.)
"""

from __future__ import annotations

import json
import pathlib
from typing import Optional


def main(argv: Optional[list[str]] = None) -> None:
    import argparse
    import signal
    import sys
    import threading

    parser = argparse.ArgumentParser(description="tieredstorage_tpu sidecar")
    parser.add_argument("--config", required=True, help="JSON file of RSM configs")
    parser.add_argument(
        "--port", type=int, default=0,
        help="Port of the shim-wire HTTP gateway, the boundary the broker "
             "shim in kafka-shim/ and SidecarRsmClient speak; 0 picks a "
             "free port.",
    )
    parser.add_argument("--host", default="127.0.0.1")
    parser.add_argument(
        "--metrics-port", type=int, default=None,
        help="Serve Prometheus /metrics for the RSM registry on this port "
             "(the compose demo stack's scrape target).",
    )
    parser.add_argument(
        "--fleet-peers", default=None, metavar="NAME=URL,...",
        help="Fleet membership override applied after configure(): "
             "comma-separated 'name=http://host:port' entries (bare 'name' "
             "for address-less members). Replaces fleet.instances — for "
             "deployments whose gateway ports are only known at launch. "
             "Requires fleet.enabled in the config.",
    )
    parser.add_argument(
        "--virtual-cpu-devices", type=int, default=None, metavar="N",
        help="Pin JAX to the host platform with N virtual CPU devices before "
             "serving (host-only deployments / environments where the "
             "accelerator platform would be acquired implicitly).",
    )
    args = parser.parse_args(argv)

    from tieredstorage_tpu.utils.platforms import (
        enable_compile_cache,
        pin_virtual_cpu,
    )

    if args.virtual_cpu_devices is not None:
        pin_virtual_cpu(args.virtual_cpu_devices)
    # Every new window shape is a compile of tens of seconds on a TPU; a
    # restarted sidecar should find them again.
    enable_compile_cache()

    from tieredstorage_tpu.rsm import RemoteStorageManager

    rsm = RemoteStorageManager()
    rsm.configure(json.loads(pathlib.Path(args.config).read_text()))
    if args.fleet_peers:
        from tieredstorage_tpu.fleet import parse_instances

        rsm.set_fleet_peers(parse_instances(args.fleet_peers.split(",")))
    from tieredstorage_tpu.sidecar.http_gateway import SidecarHttpGateway

    gateway = SidecarHttpGateway(rsm, port=args.port, host=args.host)
    exporter = None
    if args.metrics_port is not None:
        from tieredstorage_tpu.metrics.prometheus import PrometheusExporter

        # Bind the exporter to the same interface as the gateway: a
        # loopback-only sidecar must not expose metrics network-wide.
        # The RSM's tracer rides along so /varz serves the span summary
        # (p50/p95/p99 per name) next to /metrics and /healthz; the flight
        # recorder adds the per-request `flight` section (ISSUE 14), the
        # chunk cache tier its `chunk_cache` counts, the transform backend
        # its windows' (`dispatch`), the gateway its bodies' and replies'
        # bytes (`gateway`), an S3 store its requests' (`s3`).
        exporter = PrometheusExporter(
            [rsm.metrics.registry], port=args.metrics_port, host=args.host,
            tracer=rsm.tracer, flight_recorder=rsm.flight_recorder,
            chunk_cache=rsm.chunk_cache, transform_backend=rsm.transform_backend,
            gateway=gateway, storage_backend=rsm.storage_backend,
        ).start()
    gateway.start()
    # Gossip membership starts only once the gateway can answer inbound
    # /fleet/gossip probes (a no-op unless fleet.gossip.enabled).
    rsm.start_fleet_gossip()
    print(
        f"SIDECAR_READY port={gateway.port}"
        + (f" metrics_port={exporter.port}" if exporter else ""),
        flush=True,
    )

    stop = threading.Event()
    for sig in (signal.SIGTERM, signal.SIGINT):
        signal.signal(sig, lambda *_: stop.set())
    stop.wait()
    if exporter is not None:
        exporter.stop()
    gateway.stop()
    rsm.close()
    sys.exit(0)
