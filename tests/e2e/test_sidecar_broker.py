"""Ordered single-broker scenario ACROSS THE SIDECAR PROCESS BOUNDARY.

Same scenario shape as test_single_broker.py (remoteCopy → remoteRead →
remoteManualDelete), but the broker sim's RSM is a SidecarRsmClient talking
shim-wire HTTP to a `python -m tieredstorage_tpu.sidecar` subprocess hosting the full
transform/storage runtime (VERDICT r2 task 3's done-criterion: the e2e
scenario green against the sidecar). Filesystem storage backend keeps the
subprocess self-contained; compression+encryption on.
"""

from __future__ import annotations

import json
import pathlib
import subprocess
import sys
import tempfile

import pytest

from tests.e2e.broker import BrokerSim
from tieredstorage_tpu.security.rsa import generate_key_pair_pem_files
from tieredstorage_tpu.sidecar.client import SidecarRsmClient

TOPIC = "sidecar-topic"
PARTITIONS = 2
N_RECORDS = 6_000
CHUNK_SIZE = 1024


@pytest.fixture(scope="module")
def env():
    tmp = pathlib.Path(tempfile.mkdtemp())
    storage_root = tmp / "remote"
    storage_root.mkdir()
    pub, priv = generate_key_pair_pem_files(tmp, prefix="e2e")
    config = {
        "storage.backend.class": "tieredstorage_tpu.storage.filesystem.FileSystemStorage",
        "storage.root": str(storage_root),
        "chunk.size": CHUNK_SIZE,
        "key.prefix": "e2e/",
        "compression.enabled": True,
        "encryption.enabled": True,
        "encryption.key.pair.id": "k1",
        "encryption.key.pairs": ["k1"],
        "encryption.key.pairs.k1.public.key.file": str(pub),
        "encryption.key.pairs.k1.private.key.file": str(priv),
    }
    cfg = tmp / "sidecar.json"
    cfg.write_text(json.dumps(config))
    proc = subprocess.Popen(
        [sys.executable, "-m", "tieredstorage_tpu.sidecar", "--config", str(cfg)],
        stdout=subprocess.PIPE,
        stderr=subprocess.PIPE,
        text=True,
        cwd=str(pathlib.Path(__file__).resolve().parents[2]),
    )
    line = proc.stdout.readline()
    assert line.startswith("SIDECAR_READY port="), line
    port = int(line.strip().split("port=")[1])
    client = SidecarRsmClient(f"127.0.0.1:{port}", timeout=120)
    broker = BrokerSim(tmp / "logs", client)
    broker.create_topic(TOPIC, PARTITIONS)
    state = {"broker": broker, "storage_root": storage_root}
    yield state
    client.close()
    proc.terminate()
    proc.wait(timeout=10)


def _produce(broker: BrokerSim) -> dict[int, list[bytes]]:
    values: dict[int, list[bytes]] = {p: [] for p in range(PARTITIONS)}
    batch: dict[int, list] = {p: [] for p in range(PARTITIONS)}
    for i in range(N_RECORDS):
        p = i % PARTITIONS
        value = (b"value-%06d-" % i) + bytes((i * 17 + j) % 256 for j in range(80))
        values[p].append(value)
        batch[p].append((1_700_000_000_000 + i, b"key-%06d" % i, value))
        if len(batch[p]) == 50:
            broker.produce(TOPIC, p, batch[p])
            batch[p] = []
    for p, records in batch.items():
        if records:
            broker.produce(TOPIC, p, records)
    return values


def test_1_remote_copy_via_sidecar(env):
    broker = env["broker"]
    env["values"] = _produce(broker)
    tiered = broker.run_tiering()
    assert tiered > 0
    env["tiered_count"] = tiered
    objects = sorted(
        str(p) for p in env["storage_root"].rglob("*") if p.is_file()
    )
    assert len(objects) == 3 * tiered
    for suffix in (".log", ".indexes", ".rsm-manifest"):
        assert sum(1 for k in objects if k.endswith(suffix)) == tiered


def test_2_remote_read_via_sidecar(env):
    broker = env["broker"]
    for p in range(PARTITIONS):
        expected = env["values"][p]
        records = broker.consume(TOPIC, p, 0, len(expected))
        assert [r.offset for r in records] == list(range(len(expected)))
        assert [r.value for r in records] == expected
    for start in (1, 49, 50, 333):
        records = broker.consume(TOPIC, 0, start, 7)
        assert [r.offset for r in records] == list(range(start, start + 7))


def test_3_remote_manual_delete_via_sidecar(env):
    broker = env["broker"]
    live = [
        m
        for m in broker.tracker.remote_segments()
        if m.remote_log_segment_id.topic_id_partition.topic_partition.partition == 0
    ]
    assert len(live) >= 2
    cut = live[0].end_offset + 1
    deleted = broker.delete_records(TOPIC, 0, cut)
    assert deleted == 1
    objects = [p for p in env["storage_root"].rglob("*") if p.is_file()]
    assert len(objects) == 3 * (env["tiered_count"] - deleted)
    records = broker.consume(TOPIC, 0, 0, 5)
    assert records and records[0].offset == cut
