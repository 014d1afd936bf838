"""The pieces every cell shares: the device gate, the compile log, the seeded
data, the deployment behind its gateway and the shim-wire client.

`refuse_kernel_switches`, `require_tpu`, `CompileLog`, `make_segment`,
`make_indexes`, `segment_metadata` and `Deployment` are copies
of `chip_smoke.py`'s (PR 21), kept here because later PRs may change the
program and the smoke and may not change the yardstick. What differs: the
client keeps one connection open per loop, as the JVM shim's pooled client does
(a stream closed before its end costs the connection, there as here), and times
a request from its first byte sent to the last byte its reader wanted.
"""

from __future__ import annotations

import http.client
import json
import os
import pathlib
import struct
import time

#: Kernel-path switches a deployment never sets (chip_smoke.KERNEL_SWITCHES).
KERNEL_SWITCHES = (
    "TIEREDSTORAGE_TPU_PALLAS",
    "TIEREDSTORAGE_TPU_PALLAS_GHASH",
    "TIEREDSTORAGE_TPU_PALLAS_GHASH_TREE",
    "TSTPU_AES_SCAN",
    "TSTPU_AES_R",
)


def emit(record: dict) -> None:
    """An earlier line of the run: what a reader needs to trust the last."""
    print(json.dumps(record), flush=True)


def refuse(message: str) -> "SystemExit":
    return SystemExit(f"benchmark: {message}")


def refuse_kernel_switches() -> None:
    present = [name for name in KERNEL_SWITCHES if name in os.environ]
    if present:
        raise refuse(
            f"unset {', '.join(present)}: a cell runs the kernel paths a "
            "deployment runs, not a forced one"
        )


def require_tpu(chips: int) -> dict:
    """The device as JAX reports it; exits before any work off the TPU."""
    import jax

    devices = jax.devices()
    first = devices[0]
    if first.platform != "tpu":
        raise refuse(
            f"JAX found no TPU (platform={first.platform!r}); there is no "
            "CPU fallback"
        )
    if len(devices) < chips:
        raise refuse(f"the cell needs {chips} chip(s), JAX reports {len(devices)}")
    return {"platform": first.platform, "kind": first.device_kind, "count": chips}


def device_peak_bytes() -> int:
    """Peak bytes in use on the fullest chip, as the runtime reports it."""
    import jax

    return max(
        int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
        for d in jax.local_devices()
    )


class CompileLog:
    """Counts what JAX compiles from `jax.monitoring` events."""

    _COMPILE = "/jax/core/compile/backend_compile_duration"
    _HIT = "/jax/compilation_cache/cache_hits"
    _MISS = "/jax/compilation_cache/cache_misses"

    def __init__(self) -> None:
        self.compiles: list[tuple[str, float]] = []
        self.cache_hits = 0
        self.cache_misses = 0

    def _on_duration(self, event: str, seconds: float, **kwargs) -> None:
        if event == self._COMPILE:
            self.compiles.append((str(kwargs.get("fun_name", "?")), seconds))

    def _on_event(self, event: str, **kwargs) -> None:
        if event == self._HIT:
            self.cache_hits += 1
        elif event == self._MISS:
            self.cache_misses += 1

    def __enter__(self) -> "CompileLog":
        import jax.monitoring

        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)
        return self

    def __exit__(self, *exc) -> None:
        import jax.monitoring

        jax.monitoring.unregister_event_duration_listener(self._on_duration)
        jax.monitoring.unregister_event_listener(self._on_event)

    def mark(self) -> dict:
        return {
            "programs": len(self.compiles),
            "compile_s": round(sum(s for _, s in self.compiles), 3),
            "cache_hits": self.cache_hits,
            "cache_misses": self.cache_misses,
        }


def cache_entries(cache_dir: str) -> int:
    path = pathlib.Path(cache_dir)
    return sum(1 for _ in path.iterdir()) if path.is_dir() else 0


# ------------------------------------------------------------------ the data
def make_segment(seed: int, n_bytes: int) -> bytes:
    """Semi-compressible bytes shaped like Kafka log batches: incompressible
    payload interleaved with repetitive record scaffolding, made in bulk from
    `seed` (chip_smoke.make_segment)."""
    import numpy as np

    rng = np.random.default_rng(seed)
    pattern = np.frombuffer(
        (b"offset=%019d key=user-%06d value=" % (0, 0)) * 64, dtype=np.uint8
    )
    out = np.empty(n_bytes, dtype=np.uint8)
    out[0::2] = rng.integers(0, 256, (n_bytes + 1) // 2, dtype=np.uint8)
    out[1::2] = np.resize(pattern, n_bytes // 2)
    return out.tobytes()


def make_indexes(seed: int, segment_bytes: int) -> dict:
    """The index sections `/v1/copy` requires, at the sizes a segment of this
    length has with Kafka's default `index.interval.bytes` = 4096: 8 B per
    offset-index entry, 12 B per time-index entry."""
    import numpy as np

    rng = np.random.default_rng([seed, 1])
    entries = max(1, segment_bytes // 4096)
    return {
        "offset_index": rng.bytes(8 * entries),
        "time_index": rng.bytes(12 * entries),
        "producer_snapshot": rng.bytes(96),
        "transaction_index": None,
        "leader_epoch_index": b"0\n1\n0 0\n",
    }


def segment_metadata(name, segment_bytes: int):
    """`name` as the metadata a broker sends with every request."""
    from tieredstorage_tpu.metadata import (
        KafkaUuid,
        RemoteLogSegmentId,
        RemoteLogSegmentMetadata,
        TopicIdPartition,
        TopicPartition,
    )

    tip = TopicIdPartition(
        KafkaUuid(name.topic_id), TopicPartition(name.topic, name.partition)
    )
    return RemoteLogSegmentMetadata(
        RemoteLogSegmentId(tip, KafkaUuid(name.segment_id)),
        start_offset=name.start_offset,
        end_offset=name.start_offset + 1_000_000 - 1,
        segment_leader_epochs={0: name.start_offset},
        segment_size_in_bytes=segment_bytes,
    )


# ------------------------------------------------------------ the deployment
KEY_ID = "bench"


def store_and_keys(root: pathlib.Path, public: pathlib.Path,
                   private: pathlib.Path) -> dict:
    """The RSM's keys for a store under `root` and the run's key pair."""
    (root / "remote").mkdir()
    return {
        "storage.root": str(root / "remote"),
        "encryption.key.pair.id": KEY_ID,
        "encryption.key.pairs": KEY_ID,
        f"encryption.key.pairs.{KEY_ID}.public.key.file": str(public),
        f"encryption.key.pairs.{KEY_ID}.private.key.file": str(private),
    }


class Failed(Exception):
    """An operation that was answered non-2xx or not at all."""


class Client:
    """One closed loop's shim-wire client: a kept-alive connection, one
    request at a time."""

    def __init__(self, port: int, timeout_s: float) -> None:
        self._port, self._timeout_s = port, timeout_s
        self._conn: http.client.HTTPConnection | None = None

    def close(self) -> None:
        if self._conn is not None:
            self._conn.close()
            self._conn = None

    def post(self, path: str, parts: list) -> tuple[bytes, float]:
        """The reply's body and the seconds from the request's first byte
        sent to the reply's last byte read."""
        if self._conn is None:
            self._conn = http.client.HTTPConnection(
                "127.0.0.1", self._port, timeout=self._timeout_s
            )
        start = time.perf_counter()
        try:
            self._conn.request(
                "POST", path, body=iter(parts),
                headers={"Content-Length": str(sum(len(p) for p in parts))},
            )
            response = self._conn.getresponse()
            body = response.read()
        except (OSError, http.client.HTTPException) as exc:
            self.close()
            raise Failed(f"{path}: {type(exc).__name__}: {exc}") from exc
        seconds = time.perf_counter() - start
        if response.status not in (200, 204):
            raise Failed(f"{path} answered {response.status}: {body[:300]!r}")
        return body, seconds

    def copy(self, md, segment: bytes, indexes: dict) -> float:
        from tieredstorage_tpu.sidecar import shimwire

        # shimwire.encode_sections' framing, as parts: the segment is sent
        # as a view instead of being copied into one body.
        parts = [shimwire.encode_metadata(md)]
        sections = {"log_segment": segment, **indexes}
        for name in shimwire.COPY_SECTIONS:
            blob = sections[name]
            if blob is None:
                parts.append(b"\x00")
            else:
                parts += [struct.pack(">BQ", 1, len(blob)), memoryview(blob)]
        return self.post("/v1/copy", parts)[1]

    def fetch_tail(self, md, start: int, read_bytes: int) -> tuple[bytes, float]:
        """The open-ended `fetchLogSegment(md, start)` as RemoteLogManager.read
        uses it: read `read_bytes` of the reply (or to its end, if that comes
        first) and close the stream, which on HTTP/1.1 closes the connection,
        so every such fetch dials anew. Timed from the request's first byte
        sent to the last byte the reader wanted."""
        from tieredstorage_tpu.sidecar import shimwire

        body = shimwire.encode_metadata(md) + shimwire.encode_fetch_tail(start, None)
        conn = http.client.HTTPConnection("127.0.0.1", self._port, timeout=self._timeout_s)
        begin = time.perf_counter()
        try:
            conn.request("POST", "/v1/fetch", body=body)
            response = conn.getresponse()
            if response.status != 200:
                raise Failed(f"/v1/fetch answered {response.status}: {response.read()[:300]!r}")
            got = response.read(read_bytes)
            seconds = time.perf_counter() - begin
        except (OSError, http.client.HTTPException) as exc:
            raise Failed(f"/v1/fetch: {type(exc).__name__}: {exc}") from exc
        finally:
            conn.close()
        return got, seconds


class Deployment:
    """An RSM behind its HTTP gateway, in this process, as
    `tieredstorage_tpu/sidecar/server.py:main` builds them."""

    def __init__(self, configs: dict) -> None:
        from tieredstorage_tpu.rsm import RemoteStorageManager
        from tieredstorage_tpu.sidecar.http_gateway import SidecarHttpGateway

        self.rsm = RemoteStorageManager()
        self.rsm.configure(configs)
        self.gateway = SidecarHttpGateway(self.rsm, port=0).start()
        self.backend = self.rsm.transform_backend

    def client(self, timeout_s: float = 300.0) -> Client:
        return Client(self.gateway.port, timeout_s)

    def close(self) -> None:
        self.gateway.stop()
        self.rsm.close()
