"""A /v1/copy body decoded straight off the socket (ISSUE 32).

The gateway no longer holds a copy's body before it looks at it: the metadata
and the six sections are decoded as they are read, each present section into
its file. These tests drive a live gateway over raw sockets, so that they
choose where the framing cuts the body and when the client stops sending, and
pin what must hold because of it: the RSM is called only on a whole body, a
kept-alive connection stays in step (or is hung up where it cannot), the
deadline starts once the body is in, no Python frame was added above the RSM
call, and nothing is left in the scratch directory.
"""

from __future__ import annotations

import http.client
import io
import itertools
import os
import socket
import struct
import sys
import tempfile
import time
import uuid

import pytest

from tieredstorage_tpu.metadata import (
    KafkaUuid,
    RemoteLogSegmentId,
    RemoteLogSegmentMetadata,
    TopicIdPartition,
    TopicPartition,
)
from tieredstorage_tpu.rsm import RemoteStorageManager
from tieredstorage_tpu.sidecar import http_gateway, shimwire
from tieredstorage_tpu.sidecar.http_gateway import SidecarHttpGateway

TOPIC_ID = KafkaUuid(bytes(range(16)))
#: Over two of the decoder's blocks and ragged, so the log segment goes
#: through the reused buffer and ends inside it.
SEGMENT = os.urandom((5 << 19) + 17)
SECTIONS = {
    "log_segment": SEGMENT,
    "offset_index": os.urandom(800),
    "time_index": os.urandom(1200),
    "producer_snapshot": os.urandom(96),
    "transaction_index": None,
    "leader_epoch_index": b"0\n1\n0 0\n",
}
SECTION_BYTES = sum(len(blob) for blob in SECTIONS.values() if blob is not None)
INDEXES = {
    "OFFSET": "offset_index", "TIMESTAMP": "time_index",
    "PRODUCER_SNAPSHOT": "producer_snapshot", "LEADER_EPOCH": "leader_epoch_index",
}
#: Python frames between `_Handler.do_POST` and `rsm.copy_log_segment_data`
#: at the parent commit (`_handle_admitted`, `_copy`). A process's first
#: windows are traced by JAX under them, and one more cost 8-13 s of set-up
#: on the v5e's host (PERF.md, fault o).
FRAMES_ABOVE_THE_RSM_CALL = 2


def _metadata() -> RemoteLogSegmentMetadata:
    """A segment of its own for every copy."""
    tip = TopicIdPartition(TOPIC_ID, TopicPartition("copy-body", 3))
    return RemoteLogSegmentMetadata(
        remote_log_segment_id=RemoteLogSegmentId(tip, KafkaUuid(uuid.uuid4().bytes)),
        start_offset=23, end_offset=4022, segment_size_in_bytes=len(SEGMENT),
    )


def _copy_body(md, sections=SECTIONS) -> bytes:
    return shimwire.encode_metadata(md) + shimwire.encode_sections(sections)


def _header_spans(md, sections=SECTIONS) -> list:
    """(start, end) of the metadata and of every section header in the body."""
    at = len(shimwire.encode_metadata(md))
    spans = [(0, at)]
    for name in shimwire.COPY_SECTIONS:
        blob = sections[name]
        head = 1 if blob is None else 9
        spans.append((at, at + head))
        at += head + (0 if blob is None else len(blob))
    return spans


def _chunked(body: bytes, sizes) -> bytes:
    """`body` in chunked transfer, cut into chunks of the sizes `sizes` yields."""
    out, view, at = [], memoryview(body), 0
    while at < len(view):
        block = view[at:at + next(sizes)]
        out += [b"%x\r\n" % len(block), block, b"\r\n"]
        at += len(block)
    return b"".join(out + [b"0\r\n\r\n"])


def _tiny_across_headers(body: bytes, spans: list):
    """Chunks of 1-7 bytes wherever a chunk would touch the metadata or a
    section's `u8 | u64` header, 65537-byte chunks through the payloads: the
    sizes for `_chunked`, which takes each before it cuts."""
    tiny, at = itertools.cycle([1, 2, 3, 4, 5, 6, 7]), 0
    while at < len(body):
        upcoming = min((start for start, end in spans if end > at), default=len(body))
        size = next(tiny) if upcoming - at < 8 else min(65537, upcoming - at - 7)
        at += size
        yield size


class Wire:
    """One connection to the gateway, driven by hand."""

    def __init__(self, port: int):
        self.sock = socket.create_connection(("127.0.0.1", port), timeout=60)

    def close(self) -> None:
        self.sock.close()

    def head(self, path: str, headers: dict) -> None:
        lines = [f"POST {path} HTTP/1.1", "Host: 127.0.0.1"]
        lines += [f"{name}: {value}" for name, value in headers.items()]
        self.sock.sendall("\r\n".join(lines).encode("ascii") + b"\r\n\r\n")

    def post(self, path: str, body: bytes, *, chunks=None, headers=None):
        if chunks is None:
            self.head(path, {"Content-Length": len(body), **(headers or {})})
            self.sock.sendall(body)
        else:
            self.head(path, {"Transfer-Encoding": "chunked", **(headers or {})})
            self.sock.sendall(_chunked(body, chunks))
        return self.reply()

    def reply(self):
        response = http.client.HTTPResponse(self.sock, method="POST")
        response.begin()
        return response.status, response.read()

    def in_step(self) -> bool:
        """The connection takes another request and answers it."""
        self.sock.sendall(b"GET /v1/health HTTP/1.1\r\nHost: 127.0.0.1\r\n\r\n")
        return self.reply() == (200, b"")

    def hung_up(self) -> bool:
        """The gateway closed the connection after its answer."""
        self.sock.settimeout(10)
        try:
            return self.sock.recv(1) == b""
        except ConnectionResetError:
            return True


class Spy:
    """Stands in front of `rsm.copy_log_segment_data`."""

    def __init__(self, rsm, raises=None):
        self._inner, self._raises = rsm.copy_log_segment_data, raises
        self.calls = 0
        self.frames_above = None

    def __call__(self, md, data):
        self.calls += 1
        names, frame = [], sys._getframe(1)
        while frame is not None and frame.f_code.co_name != "do_POST":
            names.append(frame.f_code.co_name)
            frame = frame.f_back
        self.frames_above = names
        if self._raises is not None:
            raise self._raises
        return self._inner(md, data)


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    rsm = RemoteStorageManager()
    rsm.configure({
        "storage.backend.class": "tieredstorage_tpu.storage.filesystem:FileSystemStorage",
        "storage.root": str(tmp_path_factory.mktemp("copy-body-store")),
        "chunk.size": 16384,
        "tracing.enabled": True,
    })
    gateway = SidecarHttpGateway(rsm).start()
    yield rsm, gateway
    gateway.stop()
    rsm.close()


@pytest.fixture
def scratch(tmp_path, monkeypatch):
    """Where the gateway's scratch directories are made during this test."""
    monkeypatch.setattr(tempfile, "tempdir", str(tmp_path))
    return tmp_path


@pytest.fixture
def wire(served):
    connection = Wire(served[1].port)
    yield connection
    connection.close()


def _left_behind(scratch) -> list:
    return sorted(p.name for p in scratch.glob("sidecar-http-copy-*"))


def _fetch_body(md) -> bytes:
    return shimwire.encode_metadata(md) + shimwire.encode_fetch_tail(0, None)


def _stored(wire: Wire, md) -> dict:
    """Segment and indexes of `md` as the gateway serves them back."""
    status, segment = wire.post("/v1/fetch", _fetch_body(md))
    assert status == 200
    got = {"log_segment": segment}
    for index, section in INDEXES.items():
        status, got[section] = wire.post(
            "/v1/fetch-index", shimwire.encode_metadata(md) + shimwire.encode_index_type(index)
        )
        assert status == 200, got[section]
    return got


FRAMINGS = {
    "content-length": lambda body, spans: None,
    "chunked-65537": lambda body, spans: itertools.repeat(65537),
    "chunked-tiny-across-headers": _tiny_across_headers,
}


@pytest.mark.parametrize("framing", FRAMINGS)
def test_copy_reads_back_and_is_written_once(served, scratch, wire, framing):
    rsm, gateway = served
    md = _metadata()
    body = _copy_body(md)
    received, written = gateway.copy_body_bytes, gateway.copy_body_bytes_written
    status, reply = wire.post("/v1/copy", body, chunks=FRAMINGS[framing](body, _header_spans(md)))
    assert status in (200, 204), reply
    assert gateway.copy_body_bytes - received == len(body)
    assert gateway.copy_body_bytes_written - written == SECTION_BYTES
    assert _left_behind(scratch) == []
    want = {name: blob for name, blob in SECTIONS.items() if blob is not None}
    assert _stored(wire, md) == want


def test_tiny_chunks_do_cut_every_header():
    """The third framing is what it says: a chunk boundary inside the
    metadata and inside every present section's `u8 | u64` header."""
    md = _metadata()
    body, spans = _copy_body(md), _header_spans(md)
    cuts = set(itertools.accumulate(_tiny_across_headers(body, spans)))
    for start, end in spans:
        assert end - start == 1 or any(start < cut < end for cut in cuts), (start, end)


def test_two_copies_then_a_fetch_on_one_connection(served, scratch, wire):
    first, second = _metadata(), _metadata()
    assert wire.post("/v1/copy", _copy_body(first))[0] in (200, 204)
    assert wire.post(
        "/v1/copy", _copy_body(second), chunks=itertools.repeat(65537)
    )[0] in (200, 204)
    assert wire.post("/v1/fetch", _fetch_body(second)) == (200, SEGMENT)
    assert wire.post("/v1/fetch", _fetch_body(first)) == (200, SEGMENT)
    assert _left_behind(scratch) == []


def _send_truncated(wire, md, monkeypatch):
    body = _copy_body(md)
    wire.head("/v1/copy", {"Content-Length": len(body)})
    wire.sock.sendall(body[:len(body) // 2])  # ends inside the log segment
    wire.sock.shutdown(socket.SHUT_WR)


def _send_cut_before_last_chunk(wire, md, monkeypatch):
    """All six sections arrive, the chunked body's end never does."""
    wire.head("/v1/copy", {"Transfer-Encoding": "chunked"})
    wire.sock.sendall(_chunked(_copy_body(md), itertools.repeat(65537))[:-5])
    wire.sock.shutdown(socket.SHUT_WR)


def _send_section_over_the_cap(wire, md, monkeypatch):
    """A log segment that states a byte more than a section may hold; the
    body (of unknown length, so chunked) stops at that header."""
    body = shimwire.encode_metadata(md) + struct.pack(">BQ", 1, (2 << 30) + 1)
    wire.head("/v1/copy", {"Transfer-Encoding": "chunked"})
    wire.sock.sendall(b"%x\r\n" % len(body) + body + b"\r\n")


def _send_required_section_absent(wire, md, monkeypatch):
    body = _copy_body(md, {**SECTIONS, "offset_index": None})
    wire.head("/v1/copy", {"Content-Length": len(body)})
    wire.sock.sendall(body)


def _send_content_length_over_the_cap(wire, md, monkeypatch):
    # not one byte of a body is sent: the answer cannot have waited for one
    wire.head("/v1/copy", {"Content-Length": http_gateway.MAX_BODY_BYTES + 1})


def _send_chunked_past_the_cap(wire, md, monkeypatch):
    """The cap is passed inside the log segment. The client stops at the size
    line of the chunk that passes it, so the gateway has read all that was
    sent and its answer is not lost to a reset."""
    monkeypatch.setattr(http_gateway, "MAX_BODY_BYTES", 1 << 20)
    wire.head("/v1/copy", {"Transfer-Encoding": "chunked"})
    body = memoryview(_copy_body(md))
    for at in range(0, 15 * 65537, 65537):  # 983 055 bytes, under the cap
        wire.sock.sendall(b"%x\r\n" % 65537 + body[at:at + 65537] + b"\r\n")
    wire.sock.sendall(b"%x\r\n" % 65537)


REFUSED = {
    "truncated-in-log-segment": (_send_truncated, 400, b"truncated"),
    "cut-before-last-chunk": (_send_cut_before_last_chunk, 400, b"chunk size line"),
    "section-over-the-cap": (_send_section_over_the_cap, 400, b"over the cap"),
    "content-length-over-the-cap": (_send_content_length_over_the_cap, 413, b"MAX_BODY_BYTES"),
    "chunked-past-the-cap": (_send_chunked_past_the_cap, 413, b"MAX_BODY_BYTES"),
}


@pytest.mark.parametrize("case", REFUSED)
def test_body_not_whole_is_refused_and_hung_up(served, scratch, wire, monkeypatch, case):
    """Found before the body's end: answered, the RSM never called, nothing
    left behind, and the connection (mid-body) closed."""
    rsm, gateway = served
    send, status, message = REFUSED[case]
    spy = Spy(rsm)
    monkeypatch.setattr(rsm, "copy_log_segment_data", spy)
    received = gateway.copy_body_bytes
    send(wire, _metadata(), monkeypatch)
    got, reply = wire.reply()
    assert (got, spy.calls) == (status, 0), reply
    assert message in reply
    assert _left_behind(scratch) == []
    assert gateway.copy_body_bytes == received
    assert wire.hung_up()


def test_required_section_absent_is_refused_in_step(served, scratch, wire, monkeypatch):
    """Known only once all six slots are in, so the body is whole: refused,
    the RSM never called, and the connection usable."""
    rsm, _ = served
    spy = Spy(rsm)
    monkeypatch.setattr(rsm, "copy_log_segment_data", spy)
    _send_required_section_absent(wire, _metadata(), monkeypatch)
    status, reply = wire.reply()
    assert (status, spy.calls) == (400, 0)
    assert b"missing required section offset_index" in reply
    assert _left_behind(scratch) == []
    assert wire.in_step()


@pytest.mark.parametrize("framing", ["content-length", "chunked-65537"])
def test_bytes_after_the_sixth_section_are_drained(served, scratch, wire, monkeypatch, framing):
    rsm, gateway = served
    spy = Spy(rsm)
    monkeypatch.setattr(rsm, "copy_log_segment_data", spy)
    md = _metadata()
    body = _copy_body(md) + os.urandom(100_000)
    received, written = gateway.copy_body_bytes, gateway.copy_body_bytes_written
    status, reply = wire.post("/v1/copy", body, chunks=FRAMINGS[framing](body, None))
    assert status in (200, 204), reply
    assert spy.calls == 1
    assert gateway.copy_body_bytes - received == len(body)
    assert gateway.copy_body_bytes_written - written == SECTION_BYTES  # not the drained bytes
    assert _left_behind(scratch) == []
    assert wire.in_step()
    assert wire.post("/v1/fetch", _fetch_body(md)) == (200, SEGMENT)


def test_rsm_raising_after_a_whole_body_leaves_the_connection_in_step(
    served, scratch, wire, monkeypatch
):
    rsm, _ = served
    spy = Spy(rsm, raises=RuntimeError("the store fell over"))
    monkeypatch.setattr(rsm, "copy_log_segment_data", spy)
    status, reply = wire.post("/v1/copy", _copy_body(_metadata()))
    assert (status, spy.calls) == (500, 1)
    assert b"the store fell over" in reply
    assert _left_behind(scratch) == []
    assert wire.in_step()


def test_rsm_is_handed_whole_files_from_one_scratch_directory(served, scratch, wire, monkeypatch):
    """What the RSM opens: every section's file at its stated length, the
    empty snapshot an old shim leaves out, all in the one scratch directory
    that is gone once the copy is answered."""
    rsm, _ = served
    seen = {}

    def spy(md, data):
        files = {
            "log_segment": data.log_segment, "offset_index": data.offset_index,
            "time_index": data.time_index, "producer_snapshot": data.producer_snapshot_index,
        }
        seen["bytes"] = {name: path.read_bytes() for name, path in files.items()}
        seen["transaction_index"] = data.transaction_index
        seen["leader_epoch_index"] = data.leader_epoch_index
        seen["directories"] = {path.parent for path in files.values()}
        seen["listed"] = _left_behind(scratch)
        return None

    monkeypatch.setattr(rsm, "copy_log_segment_data", spy)
    sections = {**SECTIONS, "producer_snapshot": None}
    assert wire.post("/v1/copy", _copy_body(_metadata(), sections))[0] == 204
    assert seen["bytes"] == {
        "log_segment": SEGMENT, "offset_index": SECTIONS["offset_index"],
        "time_index": SECTIONS["time_index"], "producer_snapshot": b"",
    }
    assert seen["transaction_index"] is None
    assert seen["leader_epoch_index"] == SECTIONS["leader_epoch_index"]
    assert [d.name for d in seen["directories"]] == seen["listed"] and len(seen["listed"]) == 1
    assert _left_behind(scratch) == []


def test_deadline_starts_once_the_body_is_in(served, wire):
    """Receiving the body is not charged to `x-deadline-ms`: a client that
    holds half of it back for a while still gets its whole budget."""
    rsm, _ = served
    budget_ms, hold_s = 60_000, 0.6
    body = _copy_body(_metadata())
    rsm.tracer.clear()
    wire.head("/v1/copy", {"Content-Length": len(body), shimwire.DEADLINE_HEADER: budget_ms})
    wire.sock.sendall(body[:len(body) // 2])
    time.sleep(hold_s)
    wire.sock.sendall(body[len(body) // 2:])
    assert wire.reply()[0] in (200, 204)
    # the hold fell inside the body's read, which marks this copy's spans
    # (an earlier test's `gateway.copy` closes after its reply, so may land
    # after the clear)
    (spool,) = [
        s for s in rsm.tracer.spans("gateway.spool") if s.end_s - s.start_s >= hold_s * 0.9
    ]
    until = time.monotonic() + 30
    while not (span := [
        s for s in rsm.tracer.spans("gateway.copy") if s.span_id == spool.parent_id
    ]):  # closes after the reply is written
        assert time.monotonic() < until
        time.sleep(0.005)
    (span,) = span
    assert budget_ms - 1000 * hold_s / 2 < span.attributes["deadline_ms"] <= budget_ms


def test_no_frame_added_above_the_rsm_call(served, wire, monkeypatch):
    rsm, _ = served
    spy = Spy(rsm)
    monkeypatch.setattr(rsm, "copy_log_segment_data", spy)
    assert wire.post("/v1/copy", _copy_body(_metadata()))[0] in (200, 204)
    assert len(spy.frames_above) <= FRAMES_ABOVE_THE_RSM_CALL, spy.frames_above


def test_small_routes_refuse_a_body_they_would_have_to_hold(served, wire):
    """Every route but /v1/copy holds its body in memory, and so refuses one
    past MAX_INLINE_BODY_BYTES before reading it."""
    wire.head("/v1/delete", {"Content-Length": http_gateway.MAX_INLINE_BODY_BYTES + 1})
    assert wire.reply()[0] == 413
    assert wire.hung_up()


class TestBodyReader:
    """`_BodyReader` over a file in place of the socket."""

    BODY = bytes(range(256)) * 40

    @staticmethod
    def _reader(raw: bytes, headers: dict, cap=1 << 20):
        return http_gateway._BodyReader(io.BytesIO(raw), headers, cap)

    @pytest.mark.parametrize("sizes", [None, [1], [7, 4096, 3], [10240]])
    def test_read_and_readinto_agree_with_the_body(self, sizes):
        if sizes is None:
            raw, headers = self.BODY + b"next request", {"Content-Length": str(len(self.BODY))}
        else:
            raw = _chunked(self.BODY, itertools.cycle(sizes)) + b"next request"
            headers = {"Transfer-Encoding": "chunked"}
        reader = self._reader(raw, headers)
        assert reader.read(5) == self.BODY[:5] and not reader.exhausted
        into = bytearray(5000)
        assert reader.readinto(into) == 5000 and bytes(into) == self.BODY[5:5005]
        assert reader.read(0) == b"" and reader.total == 5005
        rest = reader.read()
        assert rest == self.BODY[5005:]
        assert reader.exhausted and reader.total == len(self.BODY)
        assert reader.read(10) == b"" and reader.readinto(bytearray(10)) == 0
        # not a byte of what follows the body was taken
        assert reader._rfile.read() == b"next request"

    def test_drain_reads_to_the_end_and_no_further(self):
        reader = self._reader(
            _chunked(self.BODY, itertools.repeat(999)) + b"next", {"Transfer-Encoding": "chunked"}
        )
        reader.drain()
        assert reader.exhausted and reader.total == len(self.BODY)
        assert reader._rfile.read() == b"next"

    def test_short_body_is_truncated_not_short(self):
        reader = self._reader(self.BODY[:100], {"Content-Length": "200"})
        with pytest.raises(shimwire.ShimWireError, match="request body truncated"):
            reader.read(200)
        reader = self._reader(self.BODY[:100], {"Content-Length": "200"})
        with pytest.raises(shimwire.ShimWireError, match="request body truncated"):
            reader.readinto(bytearray(200))

    def test_cap_holds_on_the_running_total(self):
        with pytest.raises(http_gateway._BodyTooLarge):
            self._reader(b"", {"Content-Length": "4097"}, cap=4096)
        reader = self._reader(
            _chunked(self.BODY, itertools.repeat(1000)), {"Transfer-Encoding": "chunked"}, cap=4096
        )
        assert reader.read(4000) == self.BODY[:4000]
        with pytest.raises(http_gateway._BodyTooLarge):
            reader.read(1)
