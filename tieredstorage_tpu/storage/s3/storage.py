"""S3 StorageBackend implementation.

Reference: storage/s3/.../S3Storage.java:40-151 — upload streams through the
multipart output stream, ranged GET via the Range header, native multi-object
delete, 404 → KeyNotFoundException and 416 → InvalidRangeException mapping.
"""

from __future__ import annotations

from typing import BinaryIO, Iterable, Mapping, Optional

from tieredstorage_tpu.storage.core import (
    BytesRange,
    InvalidRangeException,
    KeyNotFoundException,
    ObjectKey,
    StorageBackend,
    StorageBackendException,
)
from tieredstorage_tpu.storage.httpclient import HttpError, RetryPolicy
from tieredstorage_tpu.storage.proxy import ProxyConfig, socks5_socket_factory
from tieredstorage_tpu.storage.s3.client import S3ApiError, S3Client
from tieredstorage_tpu.storage.s3.config import S3StorageConfig
from tieredstorage_tpu.storage.s3.multipart import PartWorkers, S3MultiPartOutputStream
from tieredstorage_tpu.utils.tracing import NOOP_TRACER, Tracer

_COPY_BUFFER = 1024 * 1024

#: Parts of one upload that may be on the store's part workers at once, while
#: the upload's thread fills the next: 4 x `part_size` of buffers and the one
#: being filled. Not a configuration key: the depth hides a part's PUT behind
#: the pull of the next, and past that it buys nothing.
_PARTS_IN_FLIGHT = 4

#: Request classes and error kinds of `S3MetricCollector` that `counters()` reports.
_REQUEST_CLASSES = (
    "upload-part", "put-object", "get-object", "create-multipart-upload",
    "complete-multipart-upload", "abort-multipart-upload",
)
_ERROR_KINDS = ("throttling", "server", "io")


class S3Storage(StorageBackend):
    def __init__(self) -> None:
        self.client: Optional[S3Client] = None
        self.part_size = 0
        self._metric_collector = None
        self._tracer: Tracer = NOOP_TRACER
        self._part_workers = PartWorkers(_PARTS_IN_FLIGHT)

    @property
    def tracer(self) -> Tracer:
        """The tracer of the client's `s3.*` spans; the RSM hands its own
        over after `configure` (rsm.py), and until then nothing is traced."""
        return self._tracer

    @tracer.setter
    def tracer(self, tracer: Tracer) -> None:
        self._tracer = tracer
        if self.client is not None:
            self.client.tracer = tracer

    def configure(self, configs: Mapping[str, object]) -> None:
        config = S3StorageConfig(configs)
        proxy = ProxyConfig.from_configs(configs)
        from tieredstorage_tpu.storage.s3.metrics import S3MetricCollector

        self._metric_collector = S3MetricCollector()
        # Reference semantics (S3StorageConfig.java:65-68 / AWS SDK): the
        # call timeout covers the whole call INCLUDING retries, the attempt
        # timeout covers one attempt. Map the former onto the retry policy's
        # total deadline and the latter onto the per-attempt socket timeout
        # (falling back to the call timeout when only that one is set).
        call_timeout_s = (
            config.api_call_timeout_ms / 1000.0
            if config.api_call_timeout_ms is not None
            else None
        )
        attempt_timeout_s = (
            config.api_call_attempt_timeout_ms / 1000.0
            if config.api_call_attempt_timeout_ms is not None
            else call_timeout_s
        )
        retry = RetryPolicy(total_deadline_s=call_timeout_s)
        self.part_size = config.part_size
        self.client = S3Client(
            config.bucket_name,
            config.region,
            endpoint_url=config.endpoint_url,
            path_style=config.path_style_access,
            access_key=config.access_key_id,
            secret_key=config.secret_access_key,
            timeout=attempt_timeout_s,
            verify_tls=config.certificate_check_enabled,
            checksum_check=config.checksum_check_enabled,
            socket_factory=socks5_socket_factory(proxy),
            observer=self._metric_collector.observe,
            retry=retry,
            tracer=self._tracer,
        )

    def _require_client(self) -> S3Client:
        if self.client is None:
            raise StorageBackendException("S3Storage is not configured")
        return self.client

    # --------------------------------------------------------------- upload
    def upload(self, input_stream: BinaryIO, key: ObjectKey) -> int:
        client = self._require_client()
        out = S3MultiPartOutputStream(client, key.value, self.part_size, self._part_workers)
        try:
            while True:
                block = input_stream.read(_COPY_BUFFER)
                if not block:
                    break
                out.write(block)
            out.close()
        except (S3ApiError, HttpError) as e:
            out.abort()
            raise StorageBackendException(f"Failed to upload {key}") from e
        except BaseException:
            # The source failed, not the store: what was sent must not become
            # an object when the stream is collected (`IOBase.__del__` closes).
            out.abort()
            raise
        return out.processed_bytes

    # ---------------------------------------------------------------- fetch
    def fetch(self, key: ObjectKey, byte_range: Optional[BytesRange] = None) -> BinaryIO:
        client = self._require_client()
        rng = (
            (byte_range.from_position, byte_range.to_position)
            if byte_range is not None
            else None
        )
        try:
            status, headers, stream = client.get_object_stream(key.value, rng)
        except HttpError as e:
            raise StorageBackendException(f"Failed to fetch {key}") from e
        if status in (200, 206):
            return stream
        body = stream.read()
        stream.close()
        if status == 404:
            raise KeyNotFoundException(self, key)
        if status == 416:
            raise InvalidRangeException(
                f"Failed to fetch {key}: Invalid range {byte_range}"
            )
        raise StorageBackendException(
            f"Failed to fetch {key}: HTTP {status}: {body[:200]!r}"
        )

    # --------------------------------------------------------------- delete
    def delete(self, key: ObjectKey) -> None:
        client = self._require_client()
        try:
            client.delete_object(key.value)
        except (S3ApiError, HttpError) as e:
            raise StorageBackendException(f"Failed to delete {key}") from e

    def delete_all(self, keys: Iterable[ObjectKey]) -> None:
        client = self._require_client()
        key_list = [k.value for k in keys]
        if not key_list:
            return
        try:
            # S3 caps DeleteObjects at 1000 keys per call.
            for i in range(0, len(key_list), 1000):
                client.delete_objects(key_list[i : i + 1000])
        except (S3ApiError, HttpError) as e:
            raise StorageBackendException(f"Failed to delete {key_list}") from e

    # ----------------------------------------------------------------- list
    def list_objects(self, prefix: str = ""):
        """ListObjectsV2 pages (1000 keys each) chained via continuation
        tokens; S3 returns keys in lexicographic (UTF-8 binary) order."""
        client = self._require_client()
        token: Optional[str] = None
        while True:
            try:
                keys, token = client.list_objects_v2(prefix, token)
            except (S3ApiError, HttpError) as e:
                raise StorageBackendException(
                    f"Failed to list objects with prefix {prefix!r}"
                ) from e
            for key in keys:
                yield ObjectKey(key)
            if token is None:
                return

    @property
    def metrics(self):
        return self._metric_collector

    def counters(self) -> dict:
        """Exact counts of the S3 path as they stand (`/varz` `s3`): attempts
        by request class and error totals by kind (the collector the client
        feeds, one observation an attempt), connections the pool has dialled,
        retries (attempts beyond a call's first), body bytes sent as parts
        and body bytes read of ranged GetObject replies; of the part workers,
        nanoseconds of `upload_part` calls on them, nanoseconds the uploads'
        threads stood in `s3.part_wait`, and the most parts one upload has
        had in flight."""
        client, collector = self._require_client(), self._metric_collector
        out = {
            f"{name}-requests": int(collector.total(f"{name}-requests-total"))
            for name in _REQUEST_CLASSES
        }
        for kind in _ERROR_KINDS:
            out[f"{kind}-errors"] = int(collector.total(f"{kind}-errors-total"))
        out["connections_created"] = client.http.pool.created_total
        out["retries"] = client.http.retries_total
        out["bytes_sent_as_parts"] = client.bytes_sent_as_parts
        out["bytes_received_ranged"] = client.bytes_received_ranged
        out.update(self._part_workers.counters())
        return out

    def close(self) -> None:
        self._part_workers.close()
        if self.client is not None:
            self.client.close()

    def __str__(self) -> str:
        bucket = self.client.bucket if self.client else None
        return f"S3Storage{{bucket={bucket}, partSize={self.part_size}}}"
