"""Sidecar contract tests: the RSM surface across a real process boundary.

A `python -m tieredstorage_tpu.sidecar` subprocess hosts the full RSM
(filesystem backend, compression+encryption); SidecarRsmClient drives
copy → ranged fetch → fetch-index → delete against it. Failover semantics
get their own tests: a dead endpoint with a deadline must reroute each
call to the local fallback RSM, while real answers (NOT_FOUND) must
propagate untouched.
"""

from __future__ import annotations

import json
import pathlib
import subprocess
import sys
import time

import pytest

from tests.test_rsm_lifecycle import make_rsm, make_segment_data, make_segment_metadata
from tieredstorage_tpu.errors import RemoteResourceNotFoundException
from tieredstorage_tpu.manifest.segment_indexes import IndexType
from tieredstorage_tpu.security.rsa import generate_key_pair_pem_files
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
    if not line.startswith("SIDECAR_READY port="):
        # Kill the child before reading stderr (read() would block on a
        # live process) so a failed boot neither hangs nor leaks a server.
        proc.terminate()
        try:
            _, stderr = proc.communicate(timeout=10)
        except subprocess.TimeoutExpired:
            proc.kill()
            _, stderr = proc.communicate()
        raise AssertionError(f"sidecar did not become ready: {line!r}\n{stderr}")
    return proc, int(line.strip().split("port=")[1])


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
        """NOT_FOUND from a healthy sidecar must NOT trigger the fallback."""
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
        codec id and serve byte-exact ranged reads across the gRPC boundary
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
