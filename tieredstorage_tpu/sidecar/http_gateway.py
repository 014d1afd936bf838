"""HTTP/1.1 gateway: the sidecar's broker boundary (shim wire format v1).

Serves copy, fetch, fetch-index, delete and health against a
RemoteStorageManager over the dependency-free framing in
sidecar/shimwire.py, so the Java shim (`kafka-shim/`) needs nothing but the
JDK; sidecar/client.py is its Python twin. It is the sidecar process's only
broker-facing listener: `python -m tieredstorage_tpu.sidecar --port N`
(sidecar/server.py:main) starts it.

Error mapping (the shim translates back to KIP-405 exception types):
404 RemoteResourceNotFoundException, 400 invalid argument,
429 + Retry-After admission shed, 504 deadline exceeded, 500 the rest.

Tail tolerance at this boundary (ISSUE 4): the ``x-deadline-ms`` header is
adopted as the request's end-to-end Deadline (falling back to the RSM's
``deadline.default.ms``), and every POST passes the RSM's
AdmissionController — shedding happens BEFORE the request body is read, so
an overloaded sidecar refuses cheaply instead of buffering segment uploads
it will never serve. Requests carrying an ``x-tenant`` header are
additionally subject to the controller's per-tenant fair share at
saturation (429 when a greedy tenant exceeds its split).

Fleet mode (ISSUE 6) adds two things at this boundary:

- ``GET /chunk?key=<object key>&chunks=<lo>-<hi>`` — the peer-cache route:
  a sibling instance asks the OWNER of a segment for a window of plaintext
  chunks (framed u32 count + per-chunk u32 len|bytes). Served through the
  owner's full chunk path (cache, then single-flight backend fetch), with
  the caller's ``x-deadline-ms`` and ``traceparent`` honored; deliberately
  NOT admission-gated — a client request already holds a slot while it
  forwards, so gating the peer hop could deadlock the fleet at saturation
  (the bounded worker pool is the backstop).
- a bounded worker pool (``sidecar.http.max.workers``): connections are
  handled by a fixed executor instead of one unbounded thread each, so a
  fleet instance under fan-in keeps a bounded thread count and excess
  connections queue instead of multiplying stacks.

Gossip membership (ISSUE 11) adds the SWIM exchange pair:

- ``POST /fleet/gossip`` — one membership exchange: the sender's JSON view
  is merged (fleet/gossip.py precedence rules) and this member's full view
  is the response. NOT admission-gated: gossip is the failure detector, and
  shedding it under load would make overload read as mass death.
- ``GET /fleet/ping[?witness=1]`` — liveness + status: ring generation and
  epoch, the gossip view, peer-tier counters; ``witness=1`` adds the
  runtime lock/race witness verdicts (the multi-process soak's gate).

The observability plane (ISSUE 14) adds three read-only routes:

- ``GET /slo`` — the SLO engine's verdicts (``slo.enabled``): per-spec
  compliance, error-budget remaining, and two-window burn rates computed
  from the live latency histograms; 404 while the engine is disabled.
- ``GET /debug/requests[?n=K|?slowest=K|?trace=<id>]`` — the flight
  recorder's retained evidence (``flight.enabled``): the K slowest and
  the failed requests with per-tier chunk counts, hedge/failover
  activity, GCM window accounting, and deadline budget at each stage;
  ``trace`` filters to one trace id's records (404 when none retained —
  the fleet stitcher's per-member query), ``slowest`` returns just the K
  slowest completed records; 404 while disabled, 400 on a bad count.
  Every POST request and peer-chunk serve records through the recorder,
  covering the streamed response drain.
- ``GET /debug/timeline`` (ISSUE 17) — the device-scheduler timeline ring
  (``timeline.enabled``): every merged GCM launch's scheduler context
  (work class, bucket shape, occupancy, queue depths, waiter trace ids)
  plus the clock-epoch pin the fleet stitcher uses to land peers on one
  Perfetto time axis; 404 while disabled.
- ``GET /fleet/telemetry[?aggregate=1]`` — this member's metric samples
  (fleet mode), or with ``aggregate=1`` the whole membership view merged
  into one fleet-wide scrape (sum/max/histogram-merge per stat).
"""

from __future__ import annotations

import contextlib
import io
import math
import tempfile
import threading
from concurrent.futures import ThreadPoolExecutor
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Optional
from urllib.parse import parse_qs, unquote, urlsplit

from tieredstorage_tpu.errors import RemoteResourceNotFoundException
from tieredstorage_tpu.manifest.segment_indexes import IndexType
from tieredstorage_tpu.metadata import LogSegmentData
from tieredstorage_tpu.sidecar import shimwire
from tieredstorage_tpu.utils.admission import AdmissionRejectedException
from tieredstorage_tpu.utils.deadline import (
    DeadlineExceededException,
    deadline_scope,
    ensure_deadline,
    parse_deadline_ms,
)
from tieredstorage_tpu.utils.flightrecorder import NOOP_RECORDER
from tieredstorage_tpu.utils.tracing import NOOP_TRACER

_STREAM_BLOCK = 1 << 20
#: Reject request bodies past this, so a runaway client cannot fill the
#: sidecar's scratch disk: a /v1/copy body is a whole log segment with its
#: indexes (Kafka's default log.segment.bytes is 1 GiB).
MAX_BODY_BYTES = 2 << 30
#: Bodies of every other route are held in memory (a metadata block and a
#: few integers: ~100 bytes), so they are refused past this.
MAX_INLINE_BODY_BYTES = 64 << 20


class _BodyTooLarge(Exception):
    pass


class _StreamAborted(Exception):
    """A fetch stream failed after the 200 was committed: the chunked
    framing is unrecoverable, so the connection is aborted instead of a
    second response being written into the body."""


class _BodyReader:
    """One request's body, read off the connection with its framing undone.

    A file-like (`read`, `readinto`) over the handler's `rfile` for either
    framing the gateway accepts: a `Content-Length`, or the chunked transfer
    java.net.http uses for bodies of unknown length (the shim's copy path
    wraps file streams; BaseHTTPRequestHandler does not decode it). Nothing
    is read ahead of what the caller asks for, so the shim-wire decoders can
    run straight over the socket; `cap` bounds the running total.
    """

    def __init__(self, rfile, headers, cap: int):
        self._rfile = rfile
        self._cap = cap
        #: Body bytes handed out so far.
        self.total = 0
        self._chunked = headers.get("Transfer-Encoding", "").lower() == "chunked"
        #: Bytes left of the current chunk (of the whole body under a
        #: Content-Length).
        self._left = 0
        self._ended = not self._chunked
        if not self._chunked:
            raw_len = headers.get("Content-Length", "0").strip()
            # Strict 1*DIGIT: bare int() accepts '+5'/'1_0'/'-7', all desync
            # surface, and a negative length would never be reached.
            # str.isdigit() is NOT the right gate — it accepts non-ASCII
            # digits (e.g. '٥', '５') that int() happily parses, so hold the
            # same explicit ASCII allowlist as the chunk-size arm.
            if not raw_len or not all(c in "0123456789" for c in raw_len):
                raise shimwire.ShimWireError(f"bad Content-Length {raw_len!r}")
            self._left = int(raw_len)
            if self._left > cap:
                raise _BodyTooLarge()

    @property
    def exhausted(self) -> bool:
        """The body's last byte (and a chunked body's trailer) has been read."""
        return self._left == 0 and self._ended

    def _more(self) -> bool:
        """Whether body bytes are left, after stepping into the next chunk."""
        if self._left == 0 and not self._ended:
            self._next_chunk()
        return self._left > 0

    def _next_chunk(self) -> None:
        raw_line = self._rfile.readline(1024)
        if not raw_line.endswith(b"\n"):
            # Truncation here would silently shift the remainder of the size
            # line into the chunk data.
            raise shimwire.ShimWireError("chunk size line too long")
        size_line = raw_line.strip()
        # Strict RFC 7230 chunk-size grammar (1*HEXDIG). int(_, 16) alone also
        # accepts "-5"/"+5"/"0x1f"/"1_0" — a negative size would never be
        # reached, and the non-canonical ones are request-smuggling surface
        # against stricter intermediaries. BWS before the chunk-ext ';' is
        # valid per RFC 7230 §3.2.3 (recipients MUST parse and remove) —
        # strip it before the strict 1*HEXDIG check.
        size_field = size_line.split(b";")[0].strip()
        if not size_field or not all(
            c in b"0123456789abcdefABCDEF" for c in size_field
        ):
            raise shimwire.ShimWireError(f"bad chunk size line {size_line!r}")
        size = int(size_field, 16)
        if size == 0:
            # Consume the trailer section up to the final CRLF.
            while self._rfile.readline(1024).strip():
                pass
            self._ended = True
        elif self.total + size > self._cap:
            raise _BodyTooLarge()
        self._left = size

    def _took(self, n: int) -> None:
        self.total += n
        self._left -= n
        if self._chunked and self._left == 0:
            self._rfile.read(2)  # chunk-terminating CRLF

    def read(self, n: int = -1) -> bytes:
        """Up to `n` body bytes (all that are left when negative): fewer only
        at the body's end."""
        pieces = []
        while n != 0 and self._more():
            want = min(self._left, _STREAM_BLOCK)
            block = self._rfile.read(want if n < 0 else min(n, want))
            if not block:
                raise shimwire.ShimWireError("request body truncated")
            self._took(len(block))
            pieces.append(block)
            if n > 0:
                n -= len(block)
        return pieces[0] if len(pieces) == 1 else b"".join(pieces)

    def readinto(self, view) -> int:
        """Fill `view` with body bytes; short only at the body's end."""
        view = memoryview(view)
        filled = 0
        while filled < len(view) and self._more():
            got = self._rfile.readinto(view[filled:filled + self._left])
            if not got:
                raise shimwire.ShimWireError("request body truncated")
            self._took(got)
            filled += got
        return filled

    def drain(self) -> None:
        """Read what is left of the body and drop it."""
        block = bytearray(64 << 10)
        while self.readinto(block):
            pass


class _CopyBody:
    """A /v1/copy body as `_copy_body` received it: the metadata and the
    section files in a scratch directory, which `close` removes."""

    def __init__(self, scratch, metadata, paths: dict):
        self._scratch = scratch
        self.metadata = metadata
        self.paths = paths

    def close(self) -> None:
        self._scratch.cleanup()


class _Handler(BaseHTTPRequestHandler):
    protocol_version = "HTTP/1.1"
    rsm = None  # set per-server subclass
    gateway = None  # likewise: the SidecarHttpGateway, which counts copy bodies

    def log_message(self, fmt, *args):  # quiet; the RSM has its own tracing
        pass

    # ------------------------------------------------------------- plumbing
    def _body(self):
        """The request body in memory, as a file: what every route but
        /v1/copy reads (a metadata block and a few integers). Refused past
        MAX_INLINE_BODY_BYTES."""
        tracer = getattr(self.rsm, "tracer", NOOP_TRACER)
        with tracer.span("gateway.spool") as span:
            data = _BodyReader(self.rfile, self.headers, MAX_INLINE_BODY_BYTES).read()
            if span is not None:
                span.attributes["bytes"] = len(data)
        return io.BytesIO(data)

    def _copy_body(self) -> _CopyBody:
        """A /v1/copy body, decoded as it comes off the socket: the metadata,
        then each present section straight into its file in a scratch
        directory, so a segment's bytes are written locally once (the files
        the RSM opens) and never have to fit in sidecar RAM. Returns only
        once the body is whole: six section slots decoded, every file at its
        stated length, the reader at the body's end (bytes after the sixth
        section are read and dropped). Any failure before that leaves the
        connection mid-body: the caller answers and hangs up."""
        tracer = getattr(self.rsm, "tracer", NOOP_TRACER)
        scratch = tempfile.TemporaryDirectory(prefix="sidecar-http-copy-")
        try:
            with tracer.span("gateway.spool") as span:
                reader = _BodyReader(self.rfile, self.headers, MAX_BODY_BYTES)
                metadata = shimwire.decode_metadata(reader)
                paths = shimwire.decode_sections_to_dir(reader, scratch.name)
                reader.drain()
                if span is not None:
                    span.attributes["bytes"] = reader.total
        except BaseException:
            scratch.cleanup()
            raise
        self.gateway.count_copy_body(
            received=reader.total,
            written=sum(p.stat().st_size for p in paths.values() if p is not None),
        )
        return _CopyBody(scratch, metadata, paths)

    def _reply(self, status: int, body: bytes = b"", headers=None) -> None:
        self.send_response(status)
        for name, value in (headers or {}).items():
            self.send_header(name, value)
        self.send_header("Content-Length", str(len(body)))
        self.end_headers()
        if body:
            self.wfile.write(body)

    def _reply_stream(self, stream) -> None:
        """200 + chunked transfer of a file-like's contents.

        The RSM's fetch streams are lazy: the manifest fetch (and its 404)
        happens on the first read. Pull that block BEFORE committing the
        status line so not-found maps to a clean 404 instead of a
        truncated 200. A failure later mid-stream can only abort the
        connection (the shim surfaces that as a transport error).

        A stream that offers views (`read_views`: `fetch_log_segment`'s) is
        drained through them, so a block is `memoryview`s of the plaintext
        the fetch tiers returned and the kernel's copy into the socket is
        the only one; any other stream (`fetch_index`'s) is read. Either
        way a block goes out with its chunk-size line and trailer in one
        gather write: a 2-byte trailer sent on its own behind unacknowledged
        data is what Nagle holds back. A block is `_STREAM_BLOCK` bytes
        until the stream ends, across the segment's chunks: a short block
        where a chunk ends, with the next chunk's bytes in a later write,
        stalled a reader whose bytes span the two by a TCP timer's 200 ms
        on the v5e's host (PERF.md section 6, PR 34)."""
        tracer = getattr(self.rsm, "tracer", NOOP_TRACER)
        views = hasattr(stream, "read_views")
        take = stream.read_views if views else lambda size: [stream.read(size)]
        sent = 0
        with contextlib.closing(stream), tracer.span(
                "gateway.reply_stream", bytes=0, views=views, aborted=False) as span:
            block = take(_STREAM_BLOCK)
            self.send_response(200)
            self.send_header("Transfer-Encoding", "chunked")
            self.end_headers()
            try:
                while size := sum(map(len, block)):
                    self._send_gathered([b"%x\r\n" % size, *block, b"\r\n"])
                    sent += size
                    block = take(_STREAM_BLOCK)
                self.wfile.write(b"0\r\n\r\n")
            except Exception as exc:
                if span is not None:  # the reader had left, or the stream failed
                    span.attributes["aborted"] = True
                raise _StreamAborted() from exc
            finally:
                if span is not None:
                    span.attributes["bytes"] = sent
                self.gateway.count_reply(sent=sent, as_views=sent if views else 0)

    def _send_gathered(self, parts: list) -> None:
        """`parts` to the socket as one gather write, again until the kernel
        has taken every byte. `wfile` is unbuffered, so nothing of an earlier
        `wfile.write` can be overtaken."""
        parts = [memoryview(part) for part in parts]
        while parts:
            taken = self.connection.sendmsg(parts)
            while parts and taken >= len(parts[0]):
                taken -= len(parts.pop(0))
            if taken:
                parts[0] = parts[0][taken:]

    def _fail(self, exc: Exception) -> None:
        headers = None
        if isinstance(exc, AdmissionRejectedException):
            status = 429
            headers = {"Retry-After": str(max(1, math.ceil(exc.retry_after_s)))}
        elif isinstance(exc, DeadlineExceededException):
            status = 504
        elif isinstance(exc, RemoteResourceNotFoundException):
            status = 404
        elif isinstance(exc, (ValueError, KeyError)):
            status = 400
        else:
            status = 500
        self._reply(status, f"{type(exc).__name__}: {exc}".encode("utf-8"),
                    headers=headers)

    # ------------------------------------------------------------- handlers
    def do_GET(self) -> None:
        parts = urlsplit(self.path)
        if parts.path == "/v1/health":
            self._reply(200)
        elif parts.path in ("/chunk", "/v1/chunk"):
            self._peer_chunk(parts.query)
        elif parts.path in ("/fleet/ping", "/v1/fleet/ping"):
            self._fleet_ping(parts.query)
        elif parts.path in ("/fleet/telemetry", "/v1/fleet/telemetry"):
            self._fleet_telemetry(parts.query)
        elif parts.path in ("/slo", "/v1/slo"):
            self._slo()
        elif parts.path in ("/debug/requests", "/v1/debug/requests"):
            self._debug_requests(parts.query)
        elif parts.path in ("/debug/timeline", "/v1/debug/timeline"):
            self._debug_timeline()
        elif self.path in ("/scrub", "/v1/scrub"):
            # Integrity-scrubber status: scheduler state, cumulative
            # counters, and the last pass summary ({"enabled": false} when
            # scrub.enabled is off).
            import json

            status = (
                self.rsm.scrub_status()
                if hasattr(self.rsm, "scrub_status")
                else {"enabled": False}
            )
            self._reply(200, json.dumps(status, indent=1).encode("utf-8"))
        else:
            self._reply(404, b"no such endpoint")

    def _peer_chunk(self, query: str) -> None:
        """Fleet peer-cache route: serve a window of plaintext chunks of a
        locally-owned segment to a sibling instance (fleet/peer_cache.py).
        The serving path pins the key local, so a forwarded request can
        never be re-forwarded even under transient ring disagreement."""
        serve = getattr(self.rsm, "fleet_fetch_chunks", None)
        if serve is None or getattr(self.rsm, "fleet_router", None) is None:
            self._reply(404, b"fleet mode disabled")
            return
        tracer = getattr(self.rsm, "tracer", NOOP_TRACER)
        try:
            params = parse_qs(query, keep_blank_values=False, strict_parsing=False)
            key = unquote(params["key"][0])
            window = params["chunks"][0]
            first_s, _, last_s = window.partition("-")
            first, last = int(first_s), int(last_s or first_s)
        except (KeyError, IndexError, ValueError):
            self._reply(400, b"expected ?key=<object key>&chunks=<lo>-<hi>")
            return
        wire_deadline = parse_deadline_ms(self.headers.get(shimwire.DEADLINE_HEADER))
        recorder = getattr(self.rsm, "flight_recorder", NOOP_RECORDER)
        try:
            with deadline_scope(wire_deadline), \
                    ensure_deadline(getattr(self.rsm, "default_deadline_s", None)), \
                    tracer.continue_trace(
                        self.headers.get(shimwire.TRACEPARENT_HEADER)), \
                    tracer.span(
                        "gateway.chunk", key=key, chunks=last - first + 1
                    ) as span, \
                    recorder.request(
                        "gateway.chunk",
                        trace_id=span.trace_id if span else None,
                    ):
                chunks = serve(key, first, last)
        except Exception as exc:  # noqa: BLE001 — boundary translation
            self._fail(exc)
            return
        from tieredstorage_tpu.fleet.peer_cache import encode_chunk_frames

        self._reply(200, encode_chunk_frames(chunks))

    def _fleet_ping(self, query: str) -> None:
        """Fleet liveness/status: ring + gossip view (+ witness verdicts on
        ``?witness=1`` — a full static-vs-runtime crosscheck, so only drills
        like tools/fleet_soak.py ask for it)."""
        import json

        ping = getattr(self.rsm, "fleet_ping", None)
        if ping is None or getattr(self.rsm, "fleet_router", None) is None:
            self._reply(404, b"fleet mode disabled")
            return
        params = parse_qs(query, keep_blank_values=False, strict_parsing=False)
        include_witness = params.get("witness", ["0"])[0] in ("1", "true")
        try:
            status = ping(include_witness=include_witness)
        except Exception as exc:  # noqa: BLE001 — boundary translation
            self._fail(exc)
            return
        self._reply(200, json.dumps(status, indent=1).encode("utf-8"))

    def _slo(self) -> None:
        """SLO verdicts (metrics/slo.py): compliance, error budget, and
        two-window burn rates per declared objective. 404 while
        ``slo.enabled`` is off — an absent engine must read as "not
        configured", never as "everything within budget"."""
        import json

        if getattr(self.rsm, "slo_engine", None) is None:
            self._reply(404, b"slo engine disabled")
            return
        try:
            status = self.rsm.slo_status()
        except Exception as exc:  # noqa: BLE001 — boundary translation
            self._fail(exc)
            return
        self._reply(200, json.dumps(status, indent=1).encode("utf-8"))

    def _debug_requests(self, query: str) -> None:
        """Flight-recorder evidence dump (utils/flightrecorder.py): the
        slowest and the failed requests with tier/hedge/failover/GCM
        accounting. ``?n=K`` bounds both lists, ``?slowest=K`` returns
        just the K slowest completed records, ``?trace=<id>`` filters to
        one trace's records (404 when nothing retained carries it — the
        fleet stitcher's per-member query); 400 on a malformed count, 404
        while ``flight.enabled`` is off."""
        import json

        recorder = getattr(self.rsm, "flight_recorder", None)
        if recorder is None or not recorder.enabled:
            self._reply(404, b"flight recorder disabled")
            return
        # keep_blank_values: an explicit empty ?n= is a malformed request
        # (400), not an absent parameter.
        params = parse_qs(query, keep_blank_values=True, strict_parsing=False)

        def count_of(name: str):
            if name not in params:
                return None
            raw = params[name][0]
            # Strict ASCII-digit grammar (the Content-Length precedent).
            if not raw or not all(c in "0123456789" for c in raw) or int(raw) < 1:
                raise ValueError(f"expected ?{name}=<positive integer>")
            return int(raw)

        try:
            limit = count_of("n")
            slowest = count_of("slowest")
            trace = params["trace"][0] if "trace" in params else None
            if trace is not None and not trace:
                raise ValueError("expected ?trace=<trace id>")
            status = self.rsm.flight_status(
                limit=limit, trace=trace, slowest=slowest
            )
        except Exception as exc:  # noqa: BLE001 — boundary translation
            self._fail(exc)
            return
        self._reply(200, json.dumps(status, indent=1).encode("utf-8"))

    def _debug_timeline(self) -> None:
        """Device-scheduler timeline ring (metrics/timeline.py): merged
        launches with full scheduler context, the clock-epoch pin, and
        counters. 404 while ``timeline.enabled`` is off — an absent ring
        must read as "not armed", never as "the device was idle"."""
        import json

        timeline = getattr(self.rsm, "timeline", None)
        if timeline is None or not timeline.enabled:
            self._reply(404, b"timeline recorder disabled")
            return
        try:
            status = self.rsm.timeline_status()
        except Exception as exc:  # noqa: BLE001 — boundary translation
            self._fail(exc)
            return
        self._reply(200, json.dumps(status, indent=1).encode("utf-8"))

    def _fleet_telemetry(self, query: str) -> None:
        """Fleet telemetry (fleet/telemetry.py): this member's metric
        samples, or — with ``?aggregate=1`` — the whole membership view
        merged into one fleet-wide scrape."""
        import json

        if getattr(self.rsm, "fleet_telemetry", None) is None:
            self._reply(404, b"fleet mode disabled")
            return
        params = parse_qs(query, keep_blank_values=False, strict_parsing=False)
        aggregate = params.get("aggregate", ["0"])[0] in ("1", "true")
        try:
            payload = self.rsm.fleet_telemetry_payload(aggregate=aggregate)
        except Exception as exc:  # noqa: BLE001 — boundary translation
            self._fail(exc)
            return
        self._reply(200, json.dumps(payload, indent=1).encode("utf-8"))

    def _fleet_gossip(self) -> None:
        """One SWIM membership exchange: merge the sender's JSON view,
        answer with ours. Not admission-gated (see module docstring)."""
        import json

        serve = getattr(self.rsm, "fleet_gossip", None)
        if serve is None or getattr(self.rsm, "gossip_agent", None) is None:
            self._reply(404, b"fleet gossip disabled")
            return
        try:
            body = self._body()
        except Exception as exc:  # noqa: BLE001 — body-framing failure
            self._fail(exc)
            self.close_connection = True
            return
        try:
            with contextlib.closing(body):
                payload = json.loads(body.read())
                if not isinstance(payload, dict):
                    raise ValueError("gossip payload must be a JSON object")
                view = serve(payload)
        except Exception as exc:  # noqa: BLE001 — boundary translation
            self._fail(exc)
            return
        self._reply(200, json.dumps(view).encode("utf-8"))

    def do_POST(self) -> None:
        if self.path in ("/fleet/gossip", "/v1/fleet/gossip"):
            self._fleet_gossip()
            return
        # The route decides where its body lands: a copy's sections go to
        # files as they arrive, the rest are a few bytes in memory.
        routes = {
            "/v1/copy": (self._copy_body, self._copy),
            "/v1/fetch": (self._body, self._fetch),
            "/v1/fetch-index": (self._body, self._fetch_index),
            "/v1/delete": (self._body, self._delete),
        }
        route = routes.get(self.path)
        if route is None:
            self._reply(404, b"no such endpoint")
            return
        # Admission gate FIRST — an overloaded sidecar sheds before reading
        # the request body (a copy's is a whole segment). The unread body
        # desyncs the keep-alive framing, so a shed reply also drops the
        # connection.
        admission = getattr(self.rsm, "admission", None)
        tracer = getattr(self.rsm, "tracer", NOOP_TRACER)
        # Optional tenant identity: engages the controller's per-tenant
        # fair share at saturation (absent header = legacy behavior).
        tenant = self.headers.get("x-tenant") or None
        if admission is not None:
            try:
                admission.acquire(self.path, tenant=tenant)
            except AdmissionRejectedException as exc:
                tracer.event("admission.shed", path=self.path, tenant=tenant or "")
                self._fail(exc)
                self.close_connection = True
                return
        try:
            self._handle_admitted(*route, tracer)
        finally:
            if admission is not None:
                admission.release(tenant=tenant)

    def _handle_admitted(self, receive, handler, tracer) -> None:
        # Join the caller's trace (W3C traceparent header, sent by the JVM
        # shim or a Python client) and record the gateway leg as one span:
        # the whole server-side handling, from the body's first byte read
        # (`gateway.spool` is its first child) through the streamed response,
        # so time-to-last-byte of a fetch is the gateway span's extent.
        # One function on purpose: a process's first windows are traced by
        # JAX under this frame, and on the v5e's host one more Python frame
        # here made that tracing 8-13 s slower per process (PERF.md).
        name = "gateway" + self.path.replace("/v1/", ".")
        with tracer.continue_trace(
                self.headers.get(shimwire.TRACEPARENT_HEADER)), \
                tracer.span(name) as span:
            try:
                body = receive()
            except _BodyTooLarge:
                self._reply(413, b"request body exceeds MAX_BODY_BYTES")
                self.close_connection = True  # unread body left on the socket
                return
            except Exception as exc:  # noqa: BLE001 — framing or decode failure
                # The request body was only partially consumed: the remaining
                # bytes would be parsed as the next request line, desyncing
                # the keep-alive connection. Answer, then drop the connection.
                self._fail(exc)
                self.close_connection = True
                return
            # The caller's deadline (x-deadline-ms, remaining budget) is
            # adopted once the body is in; absent one, the RSM's configured
            # default applies. The scope covers the streamed drain, so chunk
            # fetches during the response also honor it.
            wire_deadline = parse_deadline_ms(self.headers.get(shimwire.DEADLINE_HEADER))
            recorder = getattr(self.rsm, "flight_recorder", NOOP_RECORDER)
            try:
                # The flight record spans the streamed drain too (like the
                # span and the deadline scope), so chunk-tier outcomes during
                # the response land on THIS request's record.
                with contextlib.closing(body), \
                        deadline_scope(wire_deadline), \
                        ensure_deadline(getattr(self.rsm, "default_deadline_s", None)) as deadline, \
                        recorder.request(
                            name, trace_id=span.trace_id if span else None,
                        ):
                    if span is not None and deadline is not None:
                        span.attributes["deadline_ms"] = round(
                            deadline.remaining_s() * 1000.0, 1
                        )
                    handler(body)
            except _StreamAborted:
                # Response already committed; the only safe move is dropping
                # the connection so the client sees a truncated stream (the
                # shim maps that to RemoteStorageException).
                self.close_connection = True
            except Exception as exc:  # noqa: BLE001 — boundary translation
                self._fail(exc)

    def _copy(self, body: _CopyBody) -> None:
        tracer = getattr(self.rsm, "tracer", NOOP_TRACER)
        # The scratch directory goes before the reply does, so a client that
        # has its answer finds nothing of the copy left behind.
        with contextlib.closing(body):
            with tracer.span("gateway.decode"):
                paths = body.paths
                for required in ("log_segment", "offset_index", "time_index",
                                 "leader_epoch_index"):
                    if paths[required] is None:
                        raise shimwire.ShimWireError(
                            f"missing required section {required}"
                        )
                snapshot = paths["producer_snapshot"]
                if snapshot is None:
                    # KIP-405 requires the snapshot; tolerate shims for older
                    # brokers by materializing an empty one, like the
                    # reference e2e fixtures do.
                    snapshot = paths["log_segment"].with_name("producer_snapshot")
                    snapshot.write_bytes(b"")
                data = LogSegmentData(
                    log_segment=paths["log_segment"],
                    offset_index=paths["offset_index"],
                    time_index=paths["time_index"],
                    producer_snapshot_index=snapshot,
                    transaction_index=paths["transaction_index"],
                    leader_epoch_index=paths["leader_epoch_index"].read_bytes(),
                )
            custom = self.rsm.copy_log_segment_data(body.metadata, data)
        if custom:
            self._reply(200, bytes(custom))
        else:
            self._reply(204)

    def _fetch(self, body) -> None:
        md = shimwire.decode_metadata(body)
        start, end = shimwire.decode_fetch_tail(body)
        self._reply_stream(self.rsm.fetch_log_segment(md, start, end))

    def _fetch_index(self, body) -> None:
        md = shimwire.decode_metadata(body)
        name = shimwire.decode_index_type(body)
        try:
            index_type = IndexType[name]
        except KeyError:
            raise shimwire.ShimWireError(f"unknown index type {name!r}") from None
        self._reply_stream(self.rsm.fetch_index(md, index_type))

    def _delete(self, body) -> None:
        self.rsm.delete_log_segment_data(shimwire.decode_metadata(body))
        self._reply(204)


class _BoundedThreadingHTTPServer(ThreadingHTTPServer):
    """ThreadingHTTPServer handling connections on a BOUNDED worker pool.

    The stock server spawns one unbounded thread per connection, so a fleet
    instance under fan-in (brokers + peer forwards) multiplies stacks
    without limit. Here connections are accepted eagerly (cheap) and handed
    to a fixed executor (`sidecar.http.max.workers`); excess connections
    queue in the executor until a worker frees up — bounded memory, and the
    admission controller still sheds the work itself."""

    def __init__(self, server_address, handler_class, max_workers: int):
        super().__init__(server_address, handler_class)
        self.max_workers = max_workers
        self._executor = ThreadPoolExecutor(
            max_workers=max_workers, thread_name_prefix="sidecar-http"
        )

    def process_request(self, request, client_address):
        try:
            self._executor.submit(
                self.process_request_thread, request, client_address
            )
        except RuntimeError:  # executor shut down mid-accept
            self.shutdown_request(request)

    def server_close(self):
        try:
            super().server_close()
        finally:
            self._executor.shutdown(wait=False, cancel_futures=True)


class SidecarHttpGateway:
    def __init__(
        self,
        rsm,
        *,
        port: int = 0,
        host: str = "127.0.0.1",
        max_workers: Optional[int] = None,
    ):
        handler = type("BoundHandler", (_Handler,), {"rsm": rsm, "gateway": self})
        #: Exact counts over /v1/copy bodies received whole: their bytes, and
        #: the bytes the gateway wrote to local files before calling the RSM
        #: (the section files: one write per byte).
        self.copy_body_bytes = 0
        self.copy_body_bytes_written = 0
        #: Exact counts over streamed replies (/v1/fetch, /v1/fetch-index),
        #: aborted ones too: the body bytes the kernel took, and those of
        #: them handed to it as views of what the fetch tiers returned.
        self.reply_bytes_sent = 0
        self.reply_bytes_as_views = 0
        self._counts_lock = threading.Lock()
        if max_workers is None:
            max_workers = getattr(rsm, "sidecar_http_max_workers", 32)
        self._server = _BoundedThreadingHTTPServer((host, port), handler, max_workers)
        self.port = self._server.server_address[1]
        self._thread: Optional[threading.Thread] = None

    @property
    def max_workers(self) -> int:
        return self._server.max_workers

    def count_copy_body(self, *, received: int, written: int) -> None:
        with self._counts_lock:
            self.copy_body_bytes += received
            self.copy_body_bytes_written += written

    def count_reply(self, *, sent: int, as_views: int) -> None:
        with self._counts_lock:
            self.reply_bytes_sent += sent
            self.reply_bytes_as_views += as_views

    def counters(self) -> dict:
        """The `/varz` `gateway` section: the exact counts above."""
        with self._counts_lock:
            return {
                "copy_body_bytes": self.copy_body_bytes,
                "copy_body_bytes_written": self.copy_body_bytes_written,
                "reply_bytes_sent": self.reply_bytes_sent,
                "reply_bytes_as_views": self.reply_bytes_as_views,
            }

    def start(self) -> "SidecarHttpGateway":
        self._thread = threading.Thread(
            target=self._server.serve_forever, name="sidecar-http", daemon=True
        )
        self._thread.start()
        return self

    def stop(self) -> None:
        self._server.shutdown()
        self._server.server_close()
        if self._thread is not None:
            self._thread.join(timeout=5)
