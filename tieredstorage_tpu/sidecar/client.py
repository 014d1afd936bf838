"""Client side of the sidecar boundary: the Python twin of the Java shim.

`SidecarRsmClient` exposes the RemoteStorageManager method surface
(copy/fetch/fetch_index/delete/close) over the shim-wire HTTP gateway
(sidecar/shimwire.py, sidecar/http_gateway.py), so callers — the broker
sim, tests — are drop-in independent of whether the RSM runs in-process or
behind the wire. Statuses map back onto the RSM's exception types the way
`kafka-shim/` maps them onto KIP-405's.

`FailoverRemoteStorageManager` implements the timeout→CPU-fallback
semantics (SURVEY §7 step 9): each call goes to the sidecar with a
deadline; a 504, a refused or reset connection or a socket timeout
reroutes that call to a local in-process RSM (typically configured with
the CPU transform backend), so a wedged accelerator process degrades to
host-path service instead of failing reads/writes.
"""

from __future__ import annotations

import contextlib
import http.client
import io
import os
from typing import BinaryIO, Optional

from tieredstorage_tpu.errors import (
    RemoteResourceNotFoundException,
    RemoteStorageException,
)
from tieredstorage_tpu.manifest.segment_indexes import IndexType
from tieredstorage_tpu.metadata import LogSegmentData, RemoteLogSegmentMetadata
from tieredstorage_tpu.sidecar import shimwire
from tieredstorage_tpu.utils.deadline import remaining_s
from tieredstorage_tpu.utils.tracing import NOOP_TRACER

#: Block size in which a copy's section files are read and sent.
_SEND_BLOCK = 1 << 20


class SidecarUnavailableError(RemoteStorageException):
    """Deadline/connectivity failure — the failover wrapper's trigger."""


def _raise_mapped(status: int, body: bytes):
    """The gateway's `_fail` read backwards. 504 means "the sidecar can't
    serve in time", a failover trigger; anything else is a real answer and
    must propagate (a 429 shed carries `AdmissionRejectedException: ...`)."""
    detail = body.decode("utf-8", "replace") or f"HTTP {status}"
    if status == 504:
        raise SidecarUnavailableError(detail)
    if status == 404:
        raise RemoteResourceNotFoundException(detail)
    if status == 400:
        raise ValueError(detail)
    raise RemoteStorageException(detail)


def _part_size(part) -> int:
    return os.fstat(part.fileno()).st_size if hasattr(part, "fileno") else len(part)


class SidecarRsmClient:
    def __init__(self, target: str, *, timeout: Optional[float] = None,
                 tracer=None):
        host, _, port = target.rpartition(":")
        self._address = (host, int(port))
        self._timeout = timeout
        # Client-side spans + the traceparent header: a fetch through the
        # sidecar shows up as ONE tree (client.fetch_log_segment →
        # gateway.fetch → rsm.fetch_log_segment → storage.*) instead of two
        # disjoint traces.
        self._tracer = tracer if tracer is not None else NOOP_TRACER

    def _effective_timeout(self, timeout: Optional[float]) -> Optional[float]:
        """Per-call socket timeout clamped to the ambient Deadline's
        remaining budget, so a late call in a deadlined request can't take a
        full fresh timeout (cross-layer deadline semantics)."""
        candidates = [t for t in (timeout or self._timeout, remaining_s())
                      if t is not None]
        return max(0.001, min(candidates)) if candidates else None

    def _call(self, op: str, path: str, parts=None,
              timeout: Optional[float] = None) -> bytes:
        """One request on a connection of its own, inside a client span.
        `parts` (bytes or open binary files) go out one after the other
        under one Content-Length; None makes it a GET."""
        with self._tracer.span(f"client.{op}") as span:
            # Computed INSIDE the span so the gateway parents under it.
            headers = shimwire.request_headers(self._tracer)
            if parts is not None:
                headers["Content-Length"] = str(sum(map(_part_size, parts)))
            conn = http.client.HTTPConnection(
                *self._address, timeout=self._effective_timeout(timeout),
                blocksize=_SEND_BLOCK,
            )
            try:
                try:
                    conn.request(
                        "GET" if parts is None else "POST", path,
                        body=None if parts is None else iter(parts),
                        headers=headers,
                    )
                except (BrokenPipeError, ConnectionResetError):
                    # The gateway answers a shed (429) or oversized (413)
                    # request BEFORE reading its body, then hangs up: that
                    # answer, when there is one, is what the caller wants.
                    pass
                response = conn.getresponse()
                body = response.read()
            except OSError as exc:  # refused, reset, socket timeout
                raise SidecarUnavailableError(
                    f"{type(exc).__name__}: {exc}") from None
            except http.client.HTTPException as exc:  # truncated stream
                raise RemoteStorageException(
                    f"{type(exc).__name__}: {exc}") from None
            finally:
                conn.close()
            if response.status not in (200, 204):
                _raise_mapped(response.status, body)
            if span is not None:
                span.attributes["bytes"] = len(body)
            return body

    # ------------------------------------------------------------- surface
    def health(self, timeout: Optional[float] = None) -> None:
        self._call("health", "/v1/health", timeout=timeout)

    def copy_log_segment_data(
        self, metadata: RemoteLogSegmentMetadata, data: LogSegmentData
    ) -> bytes:
        sections = {
            "log_segment": data.log_segment,
            "offset_index": data.offset_index,
            "time_index": data.time_index,
            "producer_snapshot": data.producer_snapshot_index,
            "transaction_index": data.transaction_index,
            "leader_epoch_index": bytes(data.leader_epoch_index),
        }
        # shimwire.encode_sections' framing, as parts: the section files are
        # streamed from disk instead of being read into one body.
        parts = [shimwire.encode_metadata(metadata)]
        with contextlib.ExitStack() as stack:
            for name in shimwire.COPY_SECTIONS:
                source = sections[name]
                if source is None:
                    parts.append(shimwire.SECTION_ABSENT)
                    continue
                if not isinstance(source, bytes):
                    source = stack.enter_context(open(source, "rb"))
                parts += [shimwire.section_header(_part_size(source)), source]
            return self._call("copy_log_segment_data", "/v1/copy", parts)

    def fetch_log_segment(
        self,
        metadata: RemoteLogSegmentMetadata,
        start_position: int,
        end_position: Optional[int] = None,
    ) -> BinaryIO:
        return io.BytesIO(self._call("fetch_log_segment", "/v1/fetch", [
            shimwire.encode_metadata(metadata),
            shimwire.encode_fetch_tail(start_position, end_position),
        ]))

    def fetch_index(
        self, metadata: RemoteLogSegmentMetadata, index_type: IndexType
    ) -> BinaryIO:
        return io.BytesIO(self._call("fetch_index", "/v1/fetch-index", [
            shimwire.encode_metadata(metadata),
            shimwire.encode_index_type(index_type.name),
        ]))

    def delete_log_segment_data(self, metadata: RemoteLogSegmentMetadata) -> None:
        self._call("delete_log_segment_data", "/v1/delete",
                   [shimwire.encode_metadata(metadata)])

    def close(self) -> None:
        """Nothing is held between calls: a connection lasts one request."""


class FailoverRemoteStorageManager:
    """Sidecar-first RSM: per-call deadline, local-RSM fallback.

    `fallback` is any object with the RSM surface — typically a
    RemoteStorageManager configured with the CPU transform backend against
    the same storage, so data written by either path is readable by both
    (same wire format; SURVEY §7 step 9's degradation mode)."""

    def __init__(self, client: SidecarRsmClient, fallback, *, timeout: float):
        self._client = client
        self._fallback = fallback
        self._timeout = timeout
        client._timeout = timeout
        self.fallback_calls = 0

    def _route(self, method: str, *args):
        try:
            return getattr(self._client, method)(*args)
        except SidecarUnavailableError:
            self.fallback_calls += 1
            return getattr(self._fallback, method)(*args)

    def copy_log_segment_data(self, metadata, data):
        return self._route("copy_log_segment_data", metadata, data)

    def fetch_log_segment(self, metadata, start_position, end_position=None):
        return self._route("fetch_log_segment", metadata, start_position, end_position)

    def fetch_index(self, metadata, index_type):
        return self._route("fetch_index", metadata, index_type)

    def delete_log_segment_data(self, metadata):
        return self._route("delete_log_segment_data", metadata)

    def close(self) -> None:
        self._client.close()
        self._fallback.close()
