"""A reply's bytes go from the tiers to the socket as views (ISSUE 34).

`FetchChunkEnumeration` yields `memoryview`s of what the chunk manager
returned, `utils.streams.ViewConcatStream` hands them on (`read_view`, `read_views`: no
copy; `read`, `readinto`: one), and the gateway's `_reply_stream` passes each
block's views to the kernel with the chunk-size line and trailer in one gather
write. Pinned here:
the bytes for every shape of range, that a view's owner IS the tier's object,
that a chunk is asked for only once the one before is drained, and through a
live gateway the wire format, the counters, the span and the 404s.
"""

from __future__ import annotations

import http.client
import os
import time
import types

import numpy as np
import pytest

from tests.test_rsm_lifecycle import make_rsm, make_segment_metadata
from tieredstorage_tpu.errors import RemoteResourceNotFoundException
from tieredstorage_tpu.fetch.enumeration import FetchChunkEnumeration
from tieredstorage_tpu.manifest.chunk_index import FixedSizeChunkIndex
from tieredstorage_tpu.metadata import KafkaUuid
from tieredstorage_tpu.metrics.prometheus import PrometheusExporter
from tieredstorage_tpu.sidecar import http_gateway, shimwire
from tieredstorage_tpu.sidecar.http_gateway import SidecarHttpGateway
from tieredstorage_tpu.storage.core import BytesRange, KeyNotFoundException, ObjectKey
from tieredstorage_tpu.utils.streams import ViewConcatStream

CHUNK = 1000
#: Four whole chunks and a ragged fifth.
SEGMENT = os.urandom(4 * CHUNK + 333)
KEY = ObjectKey("views/segment.log")
MANIFEST = types.SimpleNamespace(
    chunk_index=FixedSizeChunkIndex(CHUNK, len(SEGMENT), CHUNK, 333)
)

RANGES = {
    "inside_one_chunk": (130, 700),
    "from_mid_chunk_across_one_boundary": (900, 1100),
    "across_two_boundaries": (700, 2300),
    "from_a_boundary_to_inside_a_chunk": (1000, 1499),
    "last_byte_of_a_chunk": (999, 999),
    "first_byte_of_a_chunk": (2000, 2000),
    "into_the_ragged_last_chunk": (3500, len(SEGMENT) - 1),
    "whole_segment": (0, len(SEGMENT) - 1),
    "open_ended_past_the_end": (2700, 10**9),
}


class Tier:
    """A chunk manager that hands out `bytes` of its own per call, or (as
    the hot tier does) `memoryview` slices of one mirror, and remembers both
    what it was asked for and what it handed out."""

    def __init__(self, as_type=bytes) -> None:
        self.as_type = as_type
        self.mirror = np.frombuffer(SEGMENT, dtype=np.uint8)
        self.asked: list[int] = []
        self.owners: dict[int, object] = {}

    def get_chunks(self, key, manifest, chunk_ids):
        assert key is KEY and manifest is MANIFEST
        out = []
        for cid in chunk_ids:
            self.asked.append(cid)
            if self.as_type is bytes:
                data = bytes(memoryview(SEGMENT)[cid * CHUNK : (cid + 1) * CHUNK])
                self.owners[cid] = data
            else:
                data = memoryview(self.mirror)[cid * CHUNK : (cid + 1) * CHUNK]
                self.owners[cid] = self.mirror
            out.append(data)
        return out


def stream_of(tier, start: int, end: int):
    return FetchChunkEnumeration(tier, KEY, MANIFEST, BytesRange.of(start, end)).to_stream()


# ---------------------------------------------------------------- the bytes
def _by_read(stream, n: int) -> bytes:
    blocks = []
    while block := stream.read(n):
        assert type(block) is bytes
        blocks.append(block)
    assert all(len(b) == n for b in blocks[:-1])  # short only at the end
    return b"".join(blocks)


def _by_readinto(stream, n: int) -> bytes:
    out, buffer, filled = bytearray(), bytearray(n), []
    while got := stream.readinto(buffer):
        out += buffer[:got]
        filled.append(got)
    assert all(got == n for got in filled[:-1])
    return bytes(out)


def _by_read_view(stream, n: int) -> bytes:
    out = bytearray()
    while view := stream.read_view(n):
        assert isinstance(view, memoryview) and view.readonly and 0 < len(view) <= n
        out += view
    return bytes(out)


def _by_read_views(stream, n: int) -> bytes:
    blocks = []
    while views := stream.read_views(n):
        assert all(isinstance(v, memoryview) and v.readonly and len(v) for v in views)
        blocks.append(b"".join(views))
    assert all(len(b) == n for b in blocks[:-1])  # a block is whole until the stream ends
    return b"".join(blocks)


def _by_read_all(stream, n: int) -> bytes:
    got = stream.read()
    assert type(got) is bytes and stream.read() == b"" and stream.read(n) == b""
    return got


WAYS = {"read": _by_read, "readinto": _by_readinto, "read_view": _by_read_view,
        "read_views": _by_read_views, "read_all": _by_read_all}


@pytest.mark.parametrize("as_type", [bytes, memoryview], ids=["bytes", "memoryview"])
@pytest.mark.parametrize("way", WAYS)
@pytest.mark.parametrize("name", RANGES)
def test_stream_is_the_segments_range(name, way, as_type):
    start, end = RANGES[name]
    expected = SEGMENT[start : end + 1]
    for n in (1 << 20, 256, 1000, 7):
        tier = Tier(as_type)
        with stream_of(tier, start, end) as stream:
            assert WAYS[way](stream, n) == expected
        first, last = start // CHUNK, min(end, len(SEGMENT) - 1) // CHUNK
        assert tier.asked == list(range(first, last + 1))  # each chunk once, in order


def test_read_of_nothing_asks_for_nothing():
    tier = Tier()
    stream = stream_of(tier, 0, len(SEGMENT) - 1)
    assert stream.read(0) == b"" and tier.asked == []
    assert stream.readinto(bytearray()) == 0 and tier.asked == []
    assert stream.read_views(0) == [] and tier.asked == []


# ------------------------------------------------------------- the identity
@pytest.mark.parametrize("as_type", [bytes, memoryview], ids=["bytes", "memoryview"])
@pytest.mark.parametrize("take", ["read_view", "read_views"])
@pytest.mark.parametrize("name", RANGES)
def test_a_view_is_of_the_object_the_tier_returned(name, take, as_type):
    """No copy can hide: the view's owner is the tier's object itself, and
    its bytes lie in that object's memory where the range says."""
    start, end = RANGES[name]
    tier = Tier(as_type)
    stream = stream_of(tier, start, end)
    at = start
    # 300 bytes a view, or 1300 a block: a block spans two chunks, a view never does
    while views := [stream.read_view(300)] if take == "read_view" else stream.read_views(1300):
        if not views[0]:
            break
        for view in views:
            cid = at // CHUNK
            assert cid in tier.asked and view.obj is tier.owners[cid]
            owner = np.frombuffer(tier.owners[cid], dtype=np.uint8)
            offset = at if as_type is memoryview else at - cid * CHUNK
            assert np.frombuffer(view, dtype=np.uint8).ctypes.data == owner.ctypes.data + offset
            assert (at + len(view) - 1) // CHUNK == cid  # never across two chunks
            at += len(view)
        assert tier.asked[-1] == (at - 1) // CHUNK  # and no chunk asked for ahead
    assert at == min(end, len(SEGMENT) - 1) + 1


# ------------------------------------------------------------- the laziness
@pytest.mark.parametrize("way", ["read", "readinto", "read_view", "read_views"])
def test_the_next_chunk_is_asked_for_only_once_this_one_is_drained(way):
    tier = Tier()
    stream = stream_of(tier, 250, len(SEGMENT) - 1)
    take = {
        "read": lambda n: len(stream.read(n)),
        "readinto": lambda n: stream.readinto(bytearray(n)),
        "read_view": lambda n: len(stream.read_view(n)),
        "read_views": lambda n: sum(map(len, stream.read_views(n))),
    }[way]
    assert tier.asked == []  # nothing before the first read
    assert take(500) == 500 and tier.asked == [0]
    assert take(250) == 250 and tier.asked == [0]  # chunk 0's last byte taken: still not chunk 1
    assert take(1) == 1 and tier.asked == [0, 1]
    if way == "read_view":  # a view ends with its chunk
        assert take(5000) == 999 and tier.asked == [0, 1]
    else:  # a read or a block is filled across chunks, and asks for no chunk it does not reach
        assert take(1999) == 1999 and tier.asked == [0, 1, 2]
    stream.close()


@pytest.mark.parametrize("way", ["read", "read_view", "read_views"])
def test_close_after_one_block_stops_the_enumeration(way):
    tier, finished = Tier(), []

    def parts():
        try:
            yield from FetchChunkEnumeration(
                tier, KEY, MANIFEST, BytesRange.of(0, len(SEGMENT) - 1)
            )._parts()
        finally:
            finished.append(True)

    stream = ViewConcatStream(parts())
    got = getattr(stream, way)(400)
    assert (b"".join(got) if way == "read_views" else bytes(got)) == SEGMENT[:400]
    stream.close()
    assert finished == [True] and stream.closed and tier.asked == [0]
    assert len(stream.read_view(400)) == 0 and tier.asked == [0]  # and stays stopped


def test_a_missing_object_is_not_found_on_the_first_read():
    class Gone:
        def get_chunks(self, key, manifest, chunk_ids):
            raise KeyNotFoundException(None, key)

    stream = stream_of(Gone(), 0, 99)
    with pytest.raises(RemoteResourceNotFoundException):
        stream.read_view(10)


# -------------------------------------------------------- the gather write
@pytest.mark.parametrize("at_most", [1, 3, 7, 1000, 1 << 20])
def test_gather_write_goes_on_until_the_kernel_has_every_byte(at_most):
    class Socket:
        def __init__(self):
            self.taken, self.calls = bytearray(), 0

        def sendmsg(self, buffers):
            self.calls += 1
            whole = b"".join(buffers)[:at_most]
            self.taken += whole
            return len(whole)

    handler = http_gateway._Handler.__new__(http_gateway._Handler)
    handler.connection = Socket()
    block = memoryview(os.urandom(2500))
    handler._send_gathered([b"9c4\r\n", block, b"\r\n"])
    assert bytes(handler.connection.taken) == b"9c4\r\n" + bytes(block) + b"\r\n"
    assert handler.connection.calls == -(-(2507) // at_most)


# ------------------------------------------------------ through the gateway
MIB = 1 << 20
WIRE_CHUNK = 3 * MIB // 2
#: Two whole 1.5 MiB chunks and a ragged third: the 1 MiB blocks of a reply
#: lie across them.
WIRE_SEGMENT = os.urandom(2 * WIRE_CHUNK + 4321)
OFFSET_INDEX = os.urandom(800)


@pytest.fixture(scope="module")
def deployment(tmp_path_factory):
    tmp_path = tmp_path_factory.mktemp("fetch-views")
    rsm, root = make_rsm(
        tmp_path, compression=False, encryption=False, chunk_size=WIRE_CHUNK,
        extra_configs={"tracing.enabled": True},
    )
    gateway = SidecarHttpGateway(rsm).start()
    md = make_segment_metadata()
    sections = {
        "log_segment": WIRE_SEGMENT, "offset_index": OFFSET_INDEX,
        "time_index": os.urandom(1200), "producer_snapshot": os.urandom(96),
        "transaction_index": None, "leader_epoch_index": b"0\n1\n0 0\n",
    }
    body = shimwire.encode_metadata(md) + shimwire.encode_sections(sections)
    status, custom = _post(gateway.port, "/v1/copy", body)
    assert status in (200, 204)
    if custom:
        md = md.with_custom_metadata(custom)
    try:
        yield types.SimpleNamespace(rsm=rsm, gateway=gateway, md=md, root=root)
    finally:
        gateway.stop()
        rsm.close()


def _post(port: int, path: str, body: bytes, read: int | None = None, raw: bool = False):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=60)
    try:
        # raw: the chunked framing as the gateway wrote it, to the connection's end
        conn.request("POST", path, body=body, headers={"Connection": "close"} if raw else {})
        response = conn.getresponse()
        if raw:
            response.chunked = False
            return response.status, response.fp.read()
        return response.status, response.read(read) if read else response.read()
    finally:
        conn.close()


def _reply_span(deployment, before: int):
    """The `gateway.reply_stream` span after the `before` already there: it
    closes after the client has its bytes."""
    tracer, deadline = deployment.rsm.tracer, time.monotonic() + 30
    while len(spans := tracer.spans("gateway.reply_stream")) <= before:
        assert time.monotonic() < deadline
        time.sleep(0.005)
    assert len(spans) == before + 1
    return spans[-1]


def _counts(deployment) -> tuple[int, int]:
    counters = deployment.gateway.counters()
    return counters["reply_bytes_sent"], counters["reply_bytes_as_views"]


def _fetch_body(deployment, start: int, end) -> bytes:
    return shimwire.encode_metadata(deployment.md) + shimwire.encode_fetch_tail(start, end)


@pytest.mark.parametrize("start,end", [
    (0, None), (WIRE_CHUNK - 5, None), (MIB // 2, 2 * WIRE_CHUNK + 9), (17, WIRE_CHUNK - 1),
])
def test_reply_decodes_to_the_exact_bytes(deployment, start, end):
    expected = WIRE_SEGMENT[start : None if end is None else end + 1]
    before, (sent, as_views) = len(deployment.rsm.tracer.spans("gateway.reply_stream")), _counts(deployment)
    status, got = _post(deployment.gateway.port, "/v1/fetch", _fetch_body(deployment, start, end))
    assert status == 200 and got == expected
    span = _reply_span(deployment, before)
    assert span.attributes == {"bytes": len(expected), "views": True, "aborted": False}
    assert _counts(deployment) == (sent + len(expected), as_views + len(expected))


def test_wire_format_is_chunked_in_whole_blocks(deployment):
    """What the shim decodes: HTTP/1.1 chunked, blocks of 1 MiB across the
    segment's chunks (the second is the tail of one chunk and the head of
    the next, two views in one write) until the stream ends, then the
    last-chunk."""
    status, wire = _post(deployment.gateway.port, "/v1/fetch", _fetch_body(deployment, 0, None), raw=True)
    assert status == 200
    sizes, body, at = [], bytearray(), 0
    while True:
        line_end = wire.index(b"\r\n", at)
        size = int(wire[at:line_end], 16)
        assert wire[at:line_end] == b"%x" % size  # canonical, no extension
        at = line_end + 2
        body += wire[at : at + size]
        assert wire[at + size : at + size + 2] == b"\r\n"
        at += size + 2
        if size == 0:
            break
        sizes.append(size)
    assert at == len(wire) and bytes(body) == WIRE_SEGMENT
    assert sizes == [MIB, MIB, MIB, 4321]


def test_a_reader_that_leaves_after_one_block_aborts_the_stream(tmp_path):
    rsm, _ = make_rsm(
        tmp_path, compression=False, encryption=False, chunk_size=MIB,
        extra_configs={"tracing.enabled": True},
    )
    gateway = SidecarHttpGateway(rsm).start()
    md = make_segment_metadata()
    segment = os.urandom(24 * MIB)  # more than the socket buffers hold
    sections = {
        "log_segment": segment, "offset_index": b"o" * 16, "time_index": b"t" * 24,
        "producer_snapshot": b"", "transaction_index": None, "leader_epoch_index": b"0\n",
    }
    try:
        body = shimwire.encode_metadata(md) + shimwire.encode_sections(sections)
        assert _post(gateway.port, "/v1/copy", body)[0] in (200, 204)
        tail = shimwire.encode_metadata(md) + shimwire.encode_fetch_tail(0, None)
        status, got = _post(gateway.port, "/v1/fetch", tail, read=MIB)
        assert status == 200 and got == segment[:MIB]
    finally:
        gateway.stop()  # joins the handler
        spans = rsm.tracer.spans("gateway.reply_stream")
        rsm.close()
    assert len(spans) == 1 and spans[0].attributes["aborted"] is True
    assert spans[0].attributes["views"] is True
    # whole blocks the kernel took before the write it refused
    sent = spans[0].attributes["bytes"]
    assert MIB <= sent < len(segment) and sent % MIB == 0
    assert gateway.counters()["reply_bytes_sent"] == sent
    assert gateway.counters()["reply_bytes_as_views"] == sent


def test_fetch_index_is_read_and_counts_no_view_bytes(deployment):
    before, (sent, as_views) = len(deployment.rsm.tracer.spans("gateway.reply_stream")), _counts(deployment)
    body = shimwire.encode_metadata(deployment.md) + shimwire.encode_index_type("OFFSET")
    status, got = _post(deployment.gateway.port, "/v1/fetch-index", body)
    assert status == 200 and got == OFFSET_INDEX
    span = _reply_span(deployment, before)
    assert span.attributes == {"bytes": len(OFFSET_INDEX), "views": False, "aborted": False}
    assert _counts(deployment) == (sent + len(OFFSET_INDEX), as_views)


def _varz(**wired) -> dict:
    exporter = PrometheusExporter([], **wired)
    try:
        return exporter.varz()
    finally:
        exporter._server.server_close()


def test_counts_are_on_varz(deployment):
    section = _varz(gateway=deployment.gateway)["gateway"]
    assert section == deployment.gateway.counters()
    assert set(section) == {
        "copy_body_bytes", "copy_body_bytes_written", "reply_bytes_sent", "reply_bytes_as_views",
    }
    assert section["reply_bytes_sent"] > 0
    assert "gateway" not in _varz()  # no section where no gateway is wired


def test_a_missing_segment_is_a_404_before_any_200(deployment):
    """No manifest: the RSM refuses before a stream exists."""
    counts = _counts(deployment)
    md = deployment.md
    other = type(md)(
        remote_log_segment_id=type(md.remote_log_segment_id)(
            md.remote_log_segment_id.topic_id_partition, KafkaUuid(b"\x09" * 16)
        ),
        start_offset=23, end_offset=2000, segment_size_in_bytes=len(WIRE_SEGMENT),
    )
    body = shimwire.encode_metadata(other) + shimwire.encode_fetch_tail(0, None)
    status, answer = _post(deployment.gateway.port, "/v1/fetch", body)
    assert status == 404 and b"RemoteResourceNotFoundException" in answer
    assert _counts(deployment) == counts


def test_a_missing_log_object_is_a_404_on_the_first_block(tmp_path):
    """The manifest is there and the log object is not: the stream's first
    read finds out, and that is still before the status line."""
    rsm, root = make_rsm(tmp_path, compression=False, encryption=False, chunk_size=4096)
    gateway = SidecarHttpGateway(rsm).start()
    md = make_segment_metadata()
    sections = {
        "log_segment": os.urandom(20000), "offset_index": b"o" * 16, "time_index": b"t" * 24,
        "producer_snapshot": b"", "transaction_index": None, "leader_epoch_index": b"0\n",
    }
    try:
        body = shimwire.encode_metadata(md) + shimwire.encode_sections(sections)
        assert _post(gateway.port, "/v1/copy", body)[0] in (200, 204)
        (log,) = list(root.rglob("*.log"))
        log.unlink()
        tail = shimwire.encode_metadata(md) + shimwire.encode_fetch_tail(0, None)
        status, answer = _post(gateway.port, "/v1/fetch", tail)
    finally:
        gateway.stop()
        rsm.close()
    assert status == 404 and b"RemoteResourceNotFoundException" in answer
    assert gateway.counters()["reply_bytes_sent"] == 0
