"""Segment-scale streaming proof (SURVEY §7 hard part 4; round-4 verdict
next-step 6): a 1 GiB synthetic segment through the FULL production upload
path — RSM copy with the TPU backend's pipelined `transform_windows`, rate
limiter engaged, 8-way virtual mesh — asserting

- pipeline health at steady state: the copy runs twice; the second (warm)
  copy must be decisively faster (the first pays one-time jit compiles per
  varlen bucket) and its `encrypt_dispatch` spans must be a small fraction
  of wall-clock — dispatch is the async stage and blocking there would
  serialize the 3-stage pipeline. (A wall-clock "beats serial" assertion is
  wrong ON THIS HARNESS: the virtual mesh's device IS the host CPU, so
  device stages and host zstd share cores and cannot genuinely overlap —
  attribution in artifacts_r5/segment_scale_attrib_zstd.txt. The overlap
  *logic* is pinned by test_transform_tpu.py's simulated-stage test; the
  real-chip overlap shows up in the benchmark's copy cells, PERF.md §5.)
- constant host memory: peak RSS growth stays a small multiple of the
  in-flight window budget, nowhere near the 1 GiB a materialize-the-segment
  design would hold (the reference streams too —
  core/.../transform/BaseTransformChunkEnumeration.java);
- correctness: ranged fetches through the detransform path are byte-exact
  against the source file.

Runs only when TSTPU_SEGMENT_SCALE=1 (minutes on the CPU mesh); the
driver-facing artifact run is recorded in ROUNDLOG.md. Scale knob:
TSTPU_SEGMENT_SCALE_MIB (default 1024).
"""

from __future__ import annotations

import os
import resource
import time
from pathlib import Path

import numpy as np
import pytest

from tieredstorage_tpu.metadata import (
    KafkaUuid,
    LogSegmentData,
    RemoteLogSegmentId,
    RemoteLogSegmentMetadata,
    TopicIdPartition,
    TopicPartition,
)
from tieredstorage_tpu.rsm import RemoteStorageManager
from tieredstorage_tpu.security.rsa import generate_key_pair_pem_files

pytestmark = pytest.mark.skipif(
    not os.environ.get("TSTPU_SEGMENT_SCALE"),
    reason="segment-scale run is minutes long; set TSTPU_SEGMENT_SCALE=1",
)

CHUNK = 4 << 20


def _build_segment(path: Path, total: int) -> None:
    """Semi-compressible segment written in 16 MiB pieces (constant memory).

    First bytes form a valid-enough v2 batch header so the compression
    heuristic reads it (kafka_records.segment_looks_compressed)."""
    import struct

    rng = np.random.default_rng(11)
    pattern = np.frombuffer(
        (b"offset=%019d key=user-%06d value=" % (0, 0)) * 64, np.uint8
    )
    piece = 16 << 20
    # One tile covering the largest piece; per-piece slices of it (re-tiling
    # per 16 MiB piece costs ~64 redundant np.tile passes at 1 GiB).
    tiled_full = np.tile(pattern, piece // (2 * len(pattern)) + 1)
    with path.open("wb") as f:
        header = struct.pack(">qiibih", 0, total - 12, 0, 2, 0, 0x00)
        f.write(header)
        remaining = total - len(header)
        while remaining > 0:
            n = min(piece, remaining)
            half = (n + 1) // 2
            buf = np.empty(n, np.uint8)
            buf[0::2] = rng.integers(0, 256, half, dtype=np.uint8)
            buf[1::2] = tiled_full[: n - half]
            f.write(buf.tobytes())
            remaining -= n


def _peak_rss() -> int:
    return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024


def test_one_gib_segment_streams_through_the_mesh(tmp_path):
    total = int(os.environ.get("TSTPU_SEGMENT_SCALE_MIB", 1024)) << 20
    seg = tmp_path / "00000000000000000099.log"
    _build_segment(seg, total)

    for name, content in [
        ("index", b"OFFSETIDX" * 16), ("timeindex", b"TIMEIDX" * 24),
        ("snapshot", b"PRODSNAP" * 4),
    ]:
        (tmp_path / f"00000000000000000099.{name}").write_bytes(content)
    data = LogSegmentData(
        log_segment=seg,
        offset_index=tmp_path / "00000000000000000099.index",
        time_index=tmp_path / "00000000000000000099.timeindex",
        producer_snapshot_index=tmp_path / "00000000000000000099.snapshot",
        transaction_index=None,
        leader_epoch_index=b"leader-epoch-checkpoint",
    )
    tip = TopicIdPartition(KafkaUuid(b"\x03" * 16), TopicPartition("big", 0))

    def metadata(seg_id: bytes) -> RemoteLogSegmentMetadata:
        return RemoteLogSegmentMetadata(
            remote_log_segment_id=RemoteLogSegmentId(tip, KafkaUuid(seg_id)),
            start_offset=99,
            end_offset=100_000,
            segment_size_in_bytes=total,
        )

    storage_root = tmp_path / "remote"
    storage_root.mkdir()
    pub, priv = generate_key_pair_pem_files(tmp_path, prefix="scale")
    rsm = RemoteStorageManager()
    rsm.configure({
        "storage.backend.class":
            "tieredstorage_tpu.storage.filesystem.FileSystemStorage",
        "storage.root": str(storage_root),
        "chunk.size": CHUNK,
        "compression.enabled": True,
        "encryption.enabled": True,
        "encryption.key.pair.id": "key1",
        "encryption.key.pairs": "key1",
        "encryption.key.pairs.key1.public.key.file": str(pub),
        "encryption.key.pairs.key1.private.key.file": str(priv),
        "transform.backend.class":
            "tieredstorage_tpu.transform.tpu.TpuTransformBackend",
        # Rate limiter engaged but not the bottleneck (1 GiB/s floor).
        "upload.rate.limit.bytes.per.second": 1 << 30,
        "tracing.enabled": True,
    })

    meta_cold = metadata(b"\x04" * 16)
    rss_before = _peak_rss()
    t0 = time.monotonic()
    rsm.copy_log_segment_data(meta_cold, data)
    cold_s = time.monotonic() - t0
    rss_after_cold = _peak_rss()

    n0 = len(rsm.tracer._spans)
    meta = metadata(b"\x05" * 16)
    t0 = time.monotonic()
    rsm.copy_log_segment_data(meta, data)
    warm_s = time.monotonic() - t0
    rss_peak_delta = _peak_rss() - rss_before
    rss_warm_delta = _peak_rss() - rss_after_cold

    dispatch_s = sum(
        s.duration_s for s in rsm.tracer._spans[n0:]
        if s.name == "transform.encrypt_dispatch"
    )

    # Steady state reached: the warm copy must not re-pay compiles …
    assert warm_s < cold_s * 0.9, (
        f"warm copy {warm_s:.1f}s vs cold {cold_s:.1f}s — "
        "jit caches not reused across segments"
    )
    # … and the async stage must not block the pipeline thread.
    assert dispatch_s < warm_s * 0.15, (
        f"encrypt_dispatch spans sum to {dispatch_s:.1f}s of a {warm_s:.1f}s "
        "warm copy — the dispatch stage is blocking, the pipeline serialized"
    )

    # Constant memory, two invariants. (1) Absolute: on this harness the
    # virtual mesh's "device" buffers are host RSS and the XLA CPU arena
    # retains its high-water mark, so the cold-copy budget is in-flight
    # windows + arena (~1.7 GiB measured at 1 GiB), decisively below the
    # ~3 GiB a materialize-everything design needs (input + compressed +
    # encrypted copies). (2) Scaling: the warm copy must add almost
    # nothing — a per-copy materialization would add ~segment size again.
    window_bytes = rsm._transform_backend.preferred_batch_bytes
    if total >= 1 << 30:
        # Only meaningful when the segment dwarfs the XLA-CPU runtime
        # arena (~1.2 GiB baseline): at the 1 GiB default the measured
        # delta is ~1.6 GiB vs the ~3 GiB a materializing design needs,
        # while at 512 MiB the arena alone would breach 2x total.
        assert rss_peak_delta < 2 * total, (
            f"peak RSS grew {rss_peak_delta / 2**20:.0f} MiB over two copies "
            f"of a {total >> 20} MiB segment — materializing, not streaming"
        )
    # Allowance floor: the XLA-CPU arena jitters ~100 MiB run-to-run at
    # small scales regardless of streaming (measured 90 MiB at 64 MiB,
    # 42 MiB at 1 GiB); the invariant has full power at the 1 GiB default.
    assert rss_warm_delta < max(total // 4, 192 << 20), (
        f"second copy added {rss_warm_delta / 2**20:.0f} MiB of peak RSS — "
        "per-copy buffers are accumulating instead of streaming"
    )

    # Correctness: ranged fetches land byte-exact against the source.
    import random

    rng = random.Random(5)
    with seg.open("rb") as f:
        for _ in range(4):
            start = rng.randrange(0, total - (1 << 20))
            length = rng.randrange(1, 1 << 20)
            f.seek(start)
            expect = f.read(length)
            got = rsm.fetch_log_segment(
                meta, start, start + length - 1
            ).read()
            assert got == expect, f"range [{start}, +{length}) diverged"

    print(
        f"[segment-scale] total={total} cold={cold_s:.1f}s warm={warm_s:.1f}s "
        f"dispatch_warm={dispatch_s:.1f}s rss_peak_delta="
        f"{rss_peak_delta / 2**20:.0f}MiB rss_warm_delta="
        f"{rss_warm_delta / 2**20:.0f}MiB windows={total // window_bytes}",
        flush=True,
    )
