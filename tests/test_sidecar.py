"""Sidecar contract tests: the RSM surface across a real process boundary.

A `python -m tieredstorage_tpu.sidecar` subprocess hosts the full RSM
(filesystem backend, compression+encryption); SidecarRsmClient drives
copy → ranged fetch → fetch-index → delete against it. Failover semantics
get their own tests: a dead endpoint with a deadline must reroute each
call to the local fallback RSM, while real answers (a 404) must
propagate untouched. The client's status mapping is pinned against a stub
gateway, and the gateway's import is checked to pull in no RPC stack.
"""

from __future__ import annotations

import io
import json
import pathlib
import re
import subprocess
import sys
import threading
from http.server import BaseHTTPRequestHandler, HTTPServer

import pytest

from tests.test_rsm_lifecycle import make_rsm, make_segment_data, make_segment_metadata
from tieredstorage_tpu.errors import (
    RemoteResourceNotFoundException,
    RemoteStorageException,
)
from tieredstorage_tpu.manifest.segment_indexes import IndexType
from tieredstorage_tpu.security.rsa import generate_key_pair_pem_files
from tieredstorage_tpu.sidecar import shimwire
from tieredstorage_tpu.sidecar.client import (
    FailoverRemoteStorageManager,
    SidecarRsmClient,
    SidecarUnavailableError,
)


def spawn_sidecar(config: dict, cfg_path, *extra_args: str):
    """Launch the real sidecar CLI subprocess and wait for its ready line.

    Returns (proc, port); on a failed boot the assertion carries the child's
    stderr so startup crashes are debuggable from CI logs."""
    cfg_path.write_text(json.dumps(config))
    proc = subprocess.Popen(
        [sys.executable, "-m", "tieredstorage_tpu.sidecar",
         "--config", str(cfg_path), *extra_args],
        stdout=subprocess.PIPE,
        stderr=subprocess.PIPE,
        text=True,
        cwd=str(pathlib.Path(__file__).resolve().parents[1]),
    )
    line = proc.stdout.readline()
    ready = re.fullmatch(r"SIDECAR_READY port=(\d+)( metrics_port=\d+)?\n", line)
    if ready is None:
        # Kill the child before reading stderr (read() would block on a
        # live process) so a failed boot neither hangs nor leaks a server.
        proc.terminate()
        try:
            _, stderr = proc.communicate(timeout=10)
        except subprocess.TimeoutExpired:
            proc.kill()
            _, stderr = proc.communicate()
        raise AssertionError(f"sidecar did not become ready: {line!r}\n{stderr}")
    return proc, int(ready.group(1))


@pytest.fixture(scope="module")
def sidecar(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("sidecar")
    storage_root = tmp / "remote"
    storage_root.mkdir()
    pub, priv = generate_key_pair_pem_files(tmp, prefix="sc")
    config = {
        "storage.backend.class": "tieredstorage_tpu.storage.filesystem.FileSystemStorage",
        "storage.root": str(storage_root),
        "chunk.size": 4096,
        "compression.enabled": True,
        "encryption.enabled": True,
        "encryption.key.pair.id": "k1",
        "encryption.key.pairs": ["k1"],
        "encryption.key.pairs.k1.public.key.file": str(pub),
        "encryption.key.pairs.k1.private.key.file": str(priv),
        "custom.metadata.fields.include": "REMOTE_SIZE,OBJECT_PREFIX,OBJECT_KEY",
    }
    proc, port = spawn_sidecar(config, tmp / "sidecar.json")
    client = SidecarRsmClient(f"127.0.0.1:{port}", timeout=60)
    yield {"client": client, "storage_root": storage_root, "tmp": tmp, "proc": proc}
    client.close()
    proc.terminate()
    proc.wait(timeout=10)


class TestContract:
    def test_copy_fetch_index_delete_across_process(self, sidecar, tmp_path):
        client = sidecar["client"]
        data = make_segment_data(tmp_path, with_txn=True)
        md = make_segment_metadata()
        custom = client.copy_log_segment_data(md, data)
        assert custom  # custom metadata round-trips the boundary
        md = md.with_custom_metadata(custom)

        stored = list(sidecar["storage_root"].rglob("*"))
        assert any(p.suffix == ".log" for p in stored if p.is_file())

        original = data.log_segment.read_bytes()
        assert client.fetch_log_segment(md, 0).read() == original
        assert (
            client.fetch_log_segment(md, 1000, 8999).read() == original[1000:9000]
        )
        assert client.fetch_index(md, IndexType.OFFSET).read() == b"OFFSETIDX" * 16
        assert (
            client.fetch_index(md, IndexType.LEADER_EPOCH).read()
            == b"leader-epoch-checkpoint-content"
        )
        client.delete_log_segment_data(md)
        left = [p for p in sidecar["storage_root"].rglob("*") if p.is_file()]
        assert not left

    def test_not_found_maps_across_boundary(self, sidecar):
        md = make_segment_metadata()
        with pytest.raises(RemoteResourceNotFoundException):
            sidecar["client"].fetch_log_segment(md, 0)

    def test_bad_range_maps_to_value_error(self, sidecar, tmp_path):
        client = sidecar["client"]
        data = make_segment_data(tmp_path, with_txn=False)
        md = make_segment_metadata()
        md = md.with_custom_metadata(client.copy_log_segment_data(md, data))
        with pytest.raises(ValueError):
            client.fetch_log_segment(md, -1)
        client.delete_log_segment_data(md)


class TestFailover:
    def test_dead_endpoint_falls_back_to_local_rsm(self, tmp_path):
        local, storage_root = make_rsm(tmp_path, compression=True, encryption=False)
        dead = SidecarRsmClient("127.0.0.1:1", timeout=0.5)
        rsm = FailoverRemoteStorageManager(dead, local, timeout=0.5)
        data = make_segment_data(tmp_path, with_txn=False)
        md = make_segment_metadata()
        custom = rsm.copy_log_segment_data(md, data)
        md = md.with_custom_metadata(custom)
        assert rsm.fallback_calls == 1
        original = data.log_segment.read_bytes()
        assert rsm.fetch_log_segment(md, 0).read() == original
        rsm.delete_log_segment_data(md)
        assert rsm.fallback_calls == 3
        rsm.close()

    def test_real_answers_propagate_not_fallback(self, sidecar, tmp_path):
        """A 404 from a healthy sidecar must NOT trigger the fallback."""
        local, _ = make_rsm(tmp_path, compression=False, encryption=False)
        rsm = FailoverRemoteStorageManager(
            sidecar["client"], local, timeout=60
        )
        with pytest.raises(RemoteResourceNotFoundException):
            rsm.fetch_log_segment(make_segment_metadata(), 0)
        assert rsm.fallback_calls == 0
        local.close()

    def test_unavailable_error_type(self):
        dead = SidecarRsmClient("127.0.0.1:1", timeout=0.3)
        with pytest.raises(SidecarUnavailableError):
            dead.fetch_log_segment(make_segment_metadata(), 0)
        dead.close()


class TestDeviceCodecAcrossBoundary:
    @pytest.mark.parametrize("codec", ["tpu-huff-v1", "tpu-lzhuff-v1"])
    def test_device_codec_segments_round_trip_the_process_boundary(
        self, tmp_path, codec
    ):
        """A sidecar configured with a device codec must write its manifest
        codec id and serve byte-exact ranged reads across the HTTP boundary
        (codec selection is config-side only; the wire protocol is
        codec-agnostic)."""
        storage_root = tmp_path / "remote"
        storage_root.mkdir()
        config = {
            "storage.backend.class":
                "tieredstorage_tpu.storage.filesystem.FileSystemStorage",
            "storage.root": str(storage_root),
            "chunk.size": 4096,
            "compression.enabled": True,
            "compression.codec": codec,
        }
        # --virtual-cpu-devices: the device codec touches JAX, and the
        # child must not take whatever accelerator the host has.
        proc, port = spawn_sidecar(
            config, tmp_path / "sidecar.json", "--virtual-cpu-devices", "1"
        )
        try:
            client = SidecarRsmClient(f"127.0.0.1:{port}", timeout=60)
            try:
                data = make_segment_data(tmp_path, with_txn=False)
                md = make_segment_metadata()
                client.copy_log_segment_data(md, data)
                manifest = json.loads(
                    next(storage_root.rglob("*.rsm-manifest")).read_text()
                )
                assert manifest["compressionCodec"] == codec
                original = data.log_segment.read_bytes()
                assert client.fetch_log_segment(md, 0).read() == original
                assert (
                    client.fetch_log_segment(md, 5000, 5999).read()
                    == original[5000:6000]
                )
                client.delete_log_segment_data(md)
            finally:
                client.close()
        finally:
            proc.terminate()
            proc.wait(timeout=10)


class _StubGateway:
    """A loopback HTTP server that answers every request with one canned
    status and body, and keeps what it was sent."""

    def __init__(self, status: int, body: bytes = b"", *, read_body: bool = True):
        stub = self
        self.requests: list = []

        class Handler(BaseHTTPRequestHandler):
            protocol_version = "HTTP/1.1"

            def log_message(self, fmt, *args):
                pass

            def _answer(self):
                sent = None
                if read_body:
                    sent = self.rfile.read(int(self.headers.get("Content-Length", 0)))
                stub.requests.append((self.command, self.path, self.headers, sent))
                self.send_response(status)
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)
                self.close_connection = True

            do_GET = do_POST = _answer

        self._server = HTTPServer(("127.0.0.1", 0), Handler)
        self.port = self._server.server_address[1]
        self._thread = threading.Thread(target=self._server.serve_forever, daemon=True)
        self._thread.start()

    def stop(self) -> None:
        self._server.shutdown()
        self._server.server_close()
        self._thread.join(timeout=5)
        assert not self._thread.is_alive()


class TestClientStatusMapping:
    """The HTTP spelling of the RSM's exception types: what the gateway's
    `_fail` writes, `SidecarRsmClient` reads back."""

    @pytest.mark.parametrize(
        "status, body, raised, failover",
        [
            (404, b"RemoteResourceNotFoundException: no such segment",
             RemoteResourceNotFoundException, False),
            (400, b"ValueError: startPosition must be non-negative", ValueError, False),
            (429, b"AdmissionRejectedException: queue full",
             RemoteStorageException, False),
            (504, b"DeadlineExceededException: budget spent",
             SidecarUnavailableError, True),
            (500, b"RuntimeError: boom", RemoteStorageException, False),
            (None, b"", SidecarUnavailableError, True),  # connection refused
        ],
        ids=["404", "400", "429", "504", "500", "refused"],
    )
    def test_status_maps_to_exception(self, status, body, raised, failover):
        stub = _StubGateway(status, body) if status is not None else None
        port = stub.port if stub is not None else 1  # nothing listens on :1
        client = SidecarRsmClient(f"127.0.0.1:{port}", timeout=5)
        try:
            with pytest.raises(raised) as exc_info:
                client.fetch_log_segment(make_segment_metadata(), 0)
        finally:
            client.close()
            if stub is not None:
                stub.stop()
        # The failover wrapper reroutes on SidecarUnavailableError alone.
        assert isinstance(exc_info.value, SidecarUnavailableError) is failover
        if body:
            assert body.decode() in str(exc_info.value)

    def test_copy_streams_sections_under_one_content_length(self, tmp_path):
        """A copy is one POST with a Content-Length (no chunked encoding)
        whose body is the shim-wire frame of the files on disk."""
        stub = _StubGateway(200, b"custom-bytes")
        client = SidecarRsmClient(f"127.0.0.1:{stub.port}", timeout=5)
        data = make_segment_data(tmp_path, with_txn=True)
        md = make_segment_metadata()
        try:
            assert client.copy_log_segment_data(md, data) == b"custom-bytes"
        finally:
            client.close()
            stub.stop()
        (method, path, headers, sent), = stub.requests
        assert (method, path) == ("POST", "/v1/copy")
        assert "Transfer-Encoding" not in headers
        frame = io.BytesIO(sent)
        assert shimwire.decode_metadata(frame) == md
        out = tmp_path / "decoded"
        out.mkdir()
        sections = shimwire.decode_sections_to_dir(frame, out)
        assert frame.read() == b""
        assert sections["log_segment"].read_bytes() == data.log_segment.read_bytes()
        assert sections["offset_index"].read_bytes() == data.offset_index.read_bytes()
        assert sections["time_index"].read_bytes() == data.time_index.read_bytes()
        assert (sections["producer_snapshot"].read_bytes()
                == data.producer_snapshot_index.read_bytes())
        assert (sections["transaction_index"].read_bytes()
                == data.transaction_index.read_bytes())
        assert sections["leader_epoch_index"].read_bytes() == data.leader_epoch_index

    def test_answer_sent_before_the_body_was_read_still_arrives(self, tmp_path):
        """The gateway sheds BEFORE reading a copy's body and hangs up; the
        client, cut off mid-send, has to hand on that answer and not a
        connection error (which would be a failover)."""
        stub = _StubGateway(
            429, b"AdmissionRejectedException: shed", read_body=False
        )
        client = SidecarRsmClient(f"127.0.0.1:{stub.port}", timeout=5)
        data = make_segment_data(tmp_path, with_txn=False)
        with open(data.log_segment, "ab") as log:
            log.truncate(64 << 20)  # far past what the socket buffers hold
        try:
            with pytest.raises(RemoteStorageException) as exc_info:
                client.copy_log_segment_data(make_segment_metadata(), data)
        finally:
            client.close()
            stub.stop()
        assert not isinstance(exc_info.value, SidecarUnavailableError)
        assert "AdmissionRejectedException" in str(exc_info.value)

    def test_timeout_is_clamped_to_the_ambient_deadline(self):
        from tieredstorage_tpu.utils.deadline import Deadline, deadline_scope

        client = SidecarRsmClient("127.0.0.1:1", timeout=60)
        assert client._effective_timeout(None) == 60
        assert client._effective_timeout(5) == 5
        with deadline_scope(Deadline.after(0.5)):
            assert 0.0 < client._effective_timeout(None) <= 0.5
        assert SidecarRsmClient("127.0.0.1:1")._effective_timeout(None) is None


class TestProcessEntry:
    def test_ready_line_names_the_gateway_port_and_health_answers(self, tmp_path):
        """`--port` is the gateway: the port on the ready line answers
        GET /v1/health, and the metrics port rides the same line."""
        storage_root = tmp_path / "remote"
        storage_root.mkdir()
        config = {
            "storage.backend.class":
                "tieredstorage_tpu.storage.filesystem.FileSystemStorage",
            "storage.root": str(storage_root),
            "chunk.size": 4096,
        }
        proc, port = spawn_sidecar(
            config, tmp_path / "sidecar.json", "--port", "0", "--metrics-port", "0"
        )
        try:
            client = SidecarRsmClient(f"127.0.0.1:{port}", timeout=30)
            client.health()
            client.close()
        finally:
            proc.terminate()
            assert proc.wait(timeout=10) == 0

    def test_gateway_import_pulls_in_no_rpc_stack(self):
        code = (
            "import sys\n"
            "import tieredstorage_tpu.sidecar.http_gateway\n"
            "import tieredstorage_tpu.sidecar.server\n"
            "loaded = [m for m in sys.modules if m.startswith(('grpc', 'google.proto'))]\n"
            "assert not loaded, loaded\n"
        )
        proc = subprocess.run(
            [sys.executable, "-c", code], capture_output=True, text=True,
            cwd=str(pathlib.Path(__file__).resolve().parents[1]),
        )
        assert proc.returncode == 0, proc.stderr
