"""End-to-end trace propagation across the sidecar boundary (ISSUE 2).

One fetch through the HTTP gateway must produce ONE trace tree — shared
trace_id, correct parenting — spanning client → gateway → RSM → storage
backend, and the tree must export as valid Chrome trace-event JSON. The
client side uses its own Tracer instance, exactly like the JVM shim or a
remote Python client would: the only thing crossing the wire is the W3C
``traceparent`` header.
"""

from __future__ import annotations

import http.client
import json

import pytest

from tests.test_rsm_lifecycle import make_rsm, make_segment_data, make_segment_metadata
from tests.test_sidecar_http_gateway import JavaShimEncoder
from tieredstorage_tpu.sidecar import shimwire
from tieredstorage_tpu.sidecar.http_gateway import SidecarHttpGateway
from tieredstorage_tpu.utils.tracing import Tracer


@pytest.fixture
def traced_rsm(tmp_path):
    rsm, _ = make_rsm(
        tmp_path, compression=False, encryption=False,
        extra_configs={"tracing.enabled": True},
    )
    yield rsm
    rsm.close()


def _span_by_name(spans, name):
    matches = [s for s in spans if s.name == name]
    assert matches, f"no span named {name!r} in {[s.name for s in spans]}"
    return matches[0]


class TestHttpGatewayPropagation:
    def test_fetch_produces_one_trace_tree(self, tmp_path, traced_rsm):
        rsm = traced_rsm
        md = make_segment_metadata()
        rsm.copy_log_segment_data(md, make_segment_data(tmp_path, with_txn=False))
        rsm.tracer.clear()  # only the fetch's spans matter below

        client_tracer = Tracer(enabled=True)
        gateway = SidecarHttpGateway(rsm).start()
        try:
            with client_tracer.span("client.fetch_log_segment") as client_span:
                conn = http.client.HTTPConnection(
                    "127.0.0.1", gateway.port, timeout=30
                )
                body = shimwire.encode_metadata(md) + shimwire.encode_fetch_tail(0, None)
                conn.request(
                    "POST", "/v1/fetch", body=body,
                    headers=shimwire.trace_headers(client_tracer),
                )
                resp = conn.getresponse()
                assert resp.status == 200
                payload = resp.read()
                conn.close()
        finally:
            gateway.stop()
        assert len(payload) == md.segment_size_in_bytes

        spans = rsm.tracer.spans()
        gateway_span = _span_by_name(spans, "gateway.fetch")
        rsm_span = _span_by_name(spans, "rsm.fetch_log_segment")
        manifest_span = _span_by_name(spans, "rsm.fetch_manifest")
        storage_span = _span_by_name(spans, "storage.fetch_chunks")
        detransform_span = _span_by_name(spans, "chunk.detransform")
        stream_span = _span_by_name(spans, "gateway.reply_stream")

        # One shared trace across the process boundary...
        for s in (gateway_span, rsm_span, manifest_span, storage_span,
                  detransform_span, stream_span):
            assert s.trace_id == client_span.trace_id, s.name
        # ...with correct parenting: client → gateway → rsm → storage; the
        # lazy chunk transfer happens while the gateway streams the response,
        # so chunk-level spans parent under the gateway's stream span.
        assert gateway_span.parent_id == client_span.span_id
        assert rsm_span.parent_id == gateway_span.span_id
        assert manifest_span.parent_id == rsm_span.span_id
        assert stream_span.parent_id == gateway_span.span_id
        assert storage_span.parent_id == stream_span.span_id
        assert detransform_span.parent_id == stream_span.span_id
        assert stream_span.attributes == {
            "bytes": md.segment_size_in_bytes, "views": True, "aborted": False,
        }
        assert detransform_span.attributes["bytes_out"] > 0

    def test_fetch_without_traceparent_starts_fresh_trace(self, tmp_path, traced_rsm):
        rsm = traced_rsm
        md = make_segment_metadata()
        rsm.copy_log_segment_data(md, make_segment_data(tmp_path, with_txn=False))
        rsm.tracer.clear()
        gateway = SidecarHttpGateway(rsm).start()
        try:
            conn = http.client.HTTPConnection("127.0.0.1", gateway.port, timeout=30)
            conn.request(
                "POST", "/v1/fetch",
                body=shimwire.encode_metadata(md) + shimwire.encode_fetch_tail(0, None),
            )
            assert conn.getresponse().status == 200
            conn.close()
        finally:
            gateway.stop()
        gateway_span = _span_by_name(rsm.tracer.spans(), "gateway.fetch")
        assert gateway_span.parent_id is None
        assert len(gateway_span.trace_id) == 32

    def test_trace_exports_as_valid_chrome_trace(self, tmp_path, traced_rsm):
        rsm = traced_rsm
        md = make_segment_metadata()
        rsm.copy_log_segment_data(md, make_segment_data(tmp_path, with_txn=False))
        out = rsm.tracer.write_chrome_trace(tmp_path / "trace.json")
        doc = json.loads(out.read_text())
        names = {e["name"] for e in doc["traceEvents"]}
        assert {"rsm.copy_log_segment_data", "rsm.upload.segment",
                "rsm.upload.indexes", "rsm.upload.manifest"} <= names
        for event in doc["traceEvents"]:
            assert event["ph"] in ("X", "i")
            assert isinstance(event["ts"], float)


class TestClientPropagation:
    def test_client_to_sidecar_single_trace(self, tmp_path, traced_rsm):
        from tieredstorage_tpu.sidecar.client import SidecarRsmClient

        rsm = traced_rsm
        md = make_segment_metadata()
        rsm.copy_log_segment_data(md, make_segment_data(tmp_path, with_txn=False))
        rsm.tracer.clear()

        client_tracer = Tracer(enabled=True)
        gateway = SidecarHttpGateway(rsm).start()
        client = SidecarRsmClient(
            f"127.0.0.1:{gateway.port}", timeout=60, tracer=client_tracer
        )
        try:
            with client.fetch_log_segment(md, 0) as stream:
                assert len(stream.read()) == md.segment_size_in_bytes
        finally:
            client.close()
            gateway.stop()

        client_span = _span_by_name(client_tracer.spans(), "client.fetch_log_segment")
        gateway_span = _span_by_name(rsm.tracer.spans(), "gateway.fetch")
        rsm_span = _span_by_name(rsm.tracer.spans(), "rsm.fetch_log_segment")
        assert gateway_span.trace_id == client_span.trace_id
        assert gateway_span.parent_id == client_span.span_id
        assert rsm_span.trace_id == client_span.trace_id
        assert rsm_span.parent_id == gateway_span.span_id
        assert client_span.attributes["bytes"] == md.segment_size_in_bytes
