"""End-to-end deadline propagation across the sidecar boundary (ISSUE 4).

Mirror of tests/test_trace_propagation.py for the deadline context: the
caller's remaining budget crosses the HTTP gateway as the ``x-deadline-ms``
header (sent by hand and by ``SidecarRsmClient``), is adopted server-side
for the whole request (including the streamed response drain), and an
already-expired budget fails fast — before any storage work — with
``DeadlineExceededException`` mapped to 504, which the client reads as
``SidecarUnavailableError``.
"""

from __future__ import annotations

import http.client
import time

import pytest

from tests.test_rsm_lifecycle import make_rsm, make_segment_data, make_segment_metadata
from tieredstorage_tpu.errors import RemoteStorageException
from tieredstorage_tpu.sidecar import shimwire
from tieredstorage_tpu.sidecar.client import SidecarRsmClient, SidecarUnavailableError
from tieredstorage_tpu.sidecar.http_gateway import SidecarHttpGateway
from tieredstorage_tpu.utils.deadline import (
    Deadline,
    DeadlineExceededException,
    deadline_scope,
)


@pytest.fixture
def traced_rsm(tmp_path):
    rsm, _ = make_rsm(
        tmp_path, compression=False, encryption=False,
        extra_configs={"tracing.enabled": True},
    )
    yield rsm
    rsm.close()


def _fetch_via_gateway(gateway, md, headers=None):
    conn = http.client.HTTPConnection("127.0.0.1", gateway.port, timeout=30)
    body = shimwire.encode_metadata(md) + shimwire.encode_fetch_tail(0, None)
    conn.request("POST", "/v1/fetch", body=body, headers=headers or {})
    resp = conn.getresponse()
    payload = resp.read()
    conn.close()
    return resp, payload


def _span_by_name(spans, name):
    matches = [s for s in spans if s.name == name]
    assert matches, f"no span named {name!r} in {[s.name for s in spans]}"
    return matches[0]


class TestHttpGatewayPropagation:
    def test_deadline_header_adopted_for_the_request(self, tmp_path, traced_rsm):
        rsm = traced_rsm
        md = make_segment_metadata()
        rsm.copy_log_segment_data(md, make_segment_data(tmp_path, with_txn=False))
        rsm.tracer.clear()
        gateway = SidecarHttpGateway(rsm).start()
        try:
            # The client-side scope supplies the header value, exactly like
            # the Python twin of the JVM shim would send it.
            with deadline_scope(Deadline.after(30.0)):
                headers = shimwire.request_headers(rsm.tracer)
            assert shimwire.DEADLINE_HEADER in headers
            resp, payload = _fetch_via_gateway(gateway, md, headers)
        finally:
            gateway.stop()
        assert resp.status == 200
        assert len(payload) == md.segment_size_in_bytes
        # The gateway span recorded the adopted budget (proof of adoption —
        # the scope itself is thread-local server state).
        gateway_span = _span_by_name(rsm.tracer.spans(), "gateway.fetch")
        assert 0.0 < gateway_span.attributes["deadline_ms"] <= 30_000.0

    def test_expired_deadline_fails_fast_with_504(self, tmp_path, traced_rsm):
        rsm = traced_rsm
        md = make_segment_metadata()
        rsm.copy_log_segment_data(md, make_segment_data(tmp_path, with_txn=False))
        gateway = SidecarHttpGateway(rsm).start()
        try:
            start = time.monotonic()
            resp, payload = _fetch_via_gateway(
                gateway, md, {shimwire.DEADLINE_HEADER: "0"}
            )
            elapsed = time.monotonic() - start
        finally:
            gateway.stop()
        assert resp.status == 504
        assert b"DeadlineExceededException" in payload
        # Fast fail: well under one attempt timeout — no storage round trip.
        assert elapsed < 1.0

    def test_default_deadline_from_config(self, tmp_path):
        rsm, _ = make_rsm(
            tmp_path, compression=False, encryption=False,
            extra_configs={"tracing.enabled": True, "deadline.default.ms": 45_000},
        )
        md = make_segment_metadata()
        rsm.copy_log_segment_data(md, make_segment_data(tmp_path, with_txn=False))
        rsm.tracer.clear()
        gateway = SidecarHttpGateway(rsm).start()
        try:
            resp, _ = _fetch_via_gateway(gateway, md)  # no header sent
        finally:
            gateway.stop()
            rsm.close()
        assert resp.status == 200
        gateway_span = _span_by_name(rsm.tracer.spans(), "gateway.fetch")
        assert 0.0 < gateway_span.attributes["deadline_ms"] <= 45_000.0

    def test_in_process_entry_fails_fast_too(self, tmp_path, traced_rsm):
        """The _traced entry check guards the in-process surface the same
        way (no gateway involved)."""
        rsm = traced_rsm
        md = make_segment_metadata()
        rsm.copy_log_segment_data(md, make_segment_data(tmp_path, with_txn=False))
        with deadline_scope(Deadline.after(-0.001)):
            with pytest.raises(DeadlineExceededException):
                rsm.fetch_log_segment(md, 0)


class TestClientPropagation:
    """The same crossing through SidecarRsmClient, the Python twin of the
    JVM shim: it sends the ambient budget itself."""

    def _serve(self, rsm, timeout=60):
        gateway = SidecarHttpGateway(rsm).start()
        client = SidecarRsmClient(f"127.0.0.1:{gateway.port}", timeout=timeout)
        return gateway, client

    def test_deadline_header_adopted(self, tmp_path, traced_rsm):
        rsm = traced_rsm
        md = make_segment_metadata()
        rsm.copy_log_segment_data(md, make_segment_data(tmp_path, with_txn=False))
        rsm.tracer.clear()
        gateway, client = self._serve(rsm)
        try:
            with deadline_scope(Deadline.after(30.0)):
                with client.fetch_log_segment(md, 0) as stream:
                    assert len(stream.read()) == md.segment_size_in_bytes
        finally:
            client.close()
            gateway.stop()
        # The client's header carried the budget across: the gateway span
        # recorded what it adopted.
        gateway_span = _span_by_name(rsm.tracer.spans(), "gateway.fetch")
        assert 0.0 < gateway_span.attributes["deadline_ms"] <= 30_000.0

    def test_expired_deadline_fails_fast_as_unavailable(self, tmp_path, traced_rsm):
        """Server-side DeadlineExceededException maps to 504, which the
        client surfaces as its failover trigger (SidecarUnavailableError) —
        the same degradation path a wedged sidecar takes, now reached in
        milliseconds instead of a full timeout."""
        rsm = traced_rsm
        md = make_segment_metadata()
        rsm.copy_log_segment_data(md, make_segment_data(tmp_path, with_txn=False))
        gateway, client = self._serve(rsm)
        try:
            start = time.monotonic()
            with deadline_scope(Deadline.after_ms(1)):
                time.sleep(0.005)  # guarantee expiry before the call
                with pytest.raises(SidecarUnavailableError):
                    with client.fetch_log_segment(md, 0) as stream:
                        stream.read()
            assert time.monotonic() - start < 1.0
        finally:
            client.close()
            gateway.stop()

    def test_shed_request_carries_the_message_and_is_no_failover(self, tmp_path):
        rsm, _ = make_rsm(
            tmp_path, compression=False, encryption=False,
            extra_configs={
                "admission.enabled": True,
                "admission.max.concurrent": 1,
                "admission.max.queue": 0,
            },
        )
        md = make_segment_metadata()
        rsm.copy_log_segment_data(md, make_segment_data(tmp_path, with_txn=False))
        gateway, client = self._serve(rsm, timeout=10)
        try:
            rsm.admission.acquire("test-holder")
            try:
                with pytest.raises(RemoteStorageException) as exc_info:
                    with client.fetch_log_segment(md, 0) as stream:
                        stream.read()
                # A 429 is a real answer, not a failover trigger: it maps to
                # the generic RemoteStorageException carrying the shed detail.
                assert not isinstance(exc_info.value, SidecarUnavailableError)
                assert "AdmissionRejectedException" in str(exc_info.value)
            finally:
                rsm.admission.release()
            # Slot free again: served normally.
            with client.fetch_log_segment(md, 0) as stream:
                assert len(stream.read()) == md.segment_size_in_bytes
            assert rsm.admission.shed_total == 1
        finally:
            client.close()
            gateway.stop()
            rsm.close()
