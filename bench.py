"""Headline benchmark: sustained segment-transform throughput.

Protocol (BASELINE.json config 2): one segment of 4 MiB chunks pushed through
the upload transform — per-chunk compression followed by AES-256-GCM
(IV || ct || tag per chunk) — exactly the bytes the reference's
TransformChunkEnumeration chain produces (core/.../RemoteStorageManager.java:434-453).

`value` is the PER-CHIP number BASELINE.md's north star is defined on
("≥5 GiB/s sustained per v5e chip"): sustained device AES-256-GCM throughput
over chunk windows resident in HBM. The transfer-inclusive pipeline is
reported alongside (`end_to_end_gibs`, 3-stage upload ∥ compute ∥ download),
with two host baselines: the reference's strictly sequential per-chunk loop
and a 10-worker pool matching the RLM's concurrent segment uploads
(SURVEY.md §6).

Runs on the chip or not at all: when JAX finds no TPU it exits non-zero and
prints no result line, and an exception in the run ends it the same way.
Otherwise it prints ONE JSON line on stdout that names the device it ran on
(`platform`, `device_kind`, `device_count`). Diagnostics and the
per-component breakdown go to stderr. The `benchmark` PR (ROADMAP A1)
replaces this file.
"""

from __future__ import annotations

import json
import os
import random
import sys
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

_err = lambda *a: print(*a, file=sys.stderr, flush=True)


def make_segment(n_chunks: int, chunk_bytes: int) -> list[bytes]:
    """Semi-compressible chunks shaped like Kafka log batches: repetitive
    record scaffolding interleaved with incompressible payload."""
    rng = np.random.default_rng(42)
    chunks = []
    pattern = np.frombuffer(
        (b"offset=%019d key=user-%06d value=" % (0, 0)) * 64, dtype=np.uint8
    )
    for i in range(n_chunks):
        noise = rng.integers(0, 256, (chunk_bytes + 1) // 2, dtype=np.uint8)
        tiled = np.tile(pattern, chunk_bytes // (2 * len(pattern)) + 1)[
            : chunk_bytes - len(noise)
        ]
        chunk = np.empty(chunk_bytes, dtype=np.uint8)
        chunk[0::2] = noise[: (chunk_bytes + 1) // 2]
        chunk[1::2] = tiled[: chunk_bytes // 2]
        chunks.append(chunk.tobytes())
    return chunks


def time_best(fn, *, iters: int, warmup: int) -> float:
    best = float("inf")
    for i in range(warmup + iters):
        t0 = time.perf_counter()
        fn()
        dt = time.perf_counter() - t0
        if i >= warmup:
            best = min(best, dt)
    return best


def bench_device_resident(chunks, dk, *, window: int) -> tuple[float, float]:
    """Sustained device GCM GiB/s, both directions: windows staged in HBM,
    timed loops of encrypt/decrypt dispatches, block_until_ready at the end.
    Returns (encrypt_s, decrypt_s). Outputs stay in HBM, so the number is the
    device program's alone. Decrypt is the fetch path's prefetch-window half
    (BASELINE config 4's device side)."""
    import jax

    from tieredstorage_tpu.ops.gcm import (
        gcm_decrypt_chunks,
        gcm_encrypt_chunks,
        make_context,
    )

    chunk_bytes = len(chunks[0])
    ctx = make_context(dk.data_key, dk.aad, chunk_bytes)
    rng = np.random.default_rng(1)
    windows = []
    materialize = jax.jit(lambda x: x ^ np.uint8(0))
    for i in range(0, len(chunks), window):
        w = chunks[i : i + window]
        data = np.stack([np.frombuffer(c, dtype=np.uint8) for c in w])
        ivs = rng.integers(0, 256, (len(w), 12), dtype=np.uint8)
        # Outputs of a jit are device-resident and committed.
        windows.append(
            (
                jax.block_until_ready(materialize(jax.device_put(ivs))),
                jax.block_until_ready(materialize(jax.device_put(data))),
            )
        )
    # Warm the jit cache.
    jax.block_until_ready(gcm_encrypt_chunks(ctx, *windows[0]))

    def run_encrypt():
        outs = [gcm_encrypt_chunks(ctx, ivs, data) for ivs, data in windows]
        jax.block_until_ready(outs)
        return outs

    enc_s = time_best(run_encrypt, iters=3, warmup=1)

    # Device-resident ciphertext windows for the decrypt direction. Consume
    # the plaintext windows as we go so peak HBM residency stays at one
    # dataset copy plus one window, not two full copies.
    ct_windows = []
    while windows:
        ivs, data = windows.pop(0)
        ct_windows.append(
            (ivs, jax.block_until_ready(gcm_encrypt_chunks(ctx, ivs, data)[0]))
        )
        del data
    jax.block_until_ready(gcm_decrypt_chunks(ctx, *ct_windows[0]))

    def run_decrypt():
        outs = [gcm_decrypt_chunks(ctx, ivs, ct) for ivs, ct in ct_windows]
        jax.block_until_ready(outs)
        return outs

    dec_s = time_best(run_decrypt, iters=3, warmup=1)
    return enc_s, dec_s


def multichip_devices() -> int:
    """MULTICHIP mode gate: BENCH_MULTICHIP=<n> shards the transform
    windows over an n-device mesh; "1"/"true"/"all" means
    BENCH_MULTICHIP_DEVICES (default 8) devices. Unset/0 = single-chip
    bench, exactly as before."""
    raw = os.environ.get("BENCH_MULTICHIP", "").strip().lower()
    if raw in ("", "0", "false", "no"):
        return 0
    if raw in ("1", "true", "yes", "all"):
        return int(os.environ.get("BENCH_MULTICHIP_DEVICES", 8))
    return int(raw)


def bench_multichip(chunks, dk, *, window: int, plan) -> dict:
    """Sharded device-resident GCM windows over the mesh — the PRODUCTION
    packed window program (`gcm_window_packed` under shard_map, one logical
    dispatch per window) with the packed buffers staged row-sharded in HBM,
    so the number is chip compute, transfers excluded. Reports
    aggregate and per-chip GiB/s plus the mesh shape; the first window is
    byte-checked against the unsharded program so a silent sharding bug
    can't ship a fast-but-wrong number."""
    import jax

    from tieredstorage_tpu.ops.gcm import TAG_SIZE, gcm_window_packed, make_context

    chunk_bytes = len(chunks[0])
    ctx = make_context(dk.data_key, dk.aad, chunk_bytes)
    rng = np.random.default_rng(4)
    total_bytes = sum(len(c) for c in chunks)

    materialize = jax.jit(lambda x: x ^ np.uint8(0))
    staged = []
    host_windows = []
    for i in range(0, len(chunks), window):
        w = chunks[i : i + window]
        pad = plan.pad_rows(len(w))
        packed = np.zeros((len(w) + pad, chunk_bytes + TAG_SIZE), np.uint8)
        for j, c in enumerate(w):
            packed[j, :chunk_bytes] = np.frombuffer(c, np.uint8)
        packed[:, chunk_bytes : chunk_bytes + 12] = rng.integers(
            0, 256, (len(w) + pad, 12), dtype=np.uint8
        )
        host_windows.append(packed)
        staged.append(jax.block_until_ready(materialize(plan.shard(packed))))

    def run_encrypt():
        outs = [
            gcm_window_packed(ctx, None, s, decrypt=False, mesh=plan.mesh)
            for s in staged
        ]
        jax.block_until_ready(outs)
        return outs

    # Warm the sharded jit cache, then spot-check window 0 against the
    # unsharded program before timing.
    first = np.asarray(
        jax.block_until_ready(
            gcm_window_packed(ctx, None, staged[0], decrypt=False, mesh=plan.mesh)
        )
    )
    reference = np.asarray(
        gcm_window_packed(ctx, None, host_windows[0], decrypt=False)
    )
    parity = bool(np.array_equal(first, reference))

    enc_s = time_best(run_encrypt, iters=3, warmup=1)
    aggregate = total_bytes / (1 << 30) / enc_s
    return {
        "multichip_mesh_size": plan.size,
        "multichip_mesh_shape": plan.describe(),
        "multichip_aggregate_gibs": round(aggregate, 3),
        "multichip_per_chip_gibs": round(aggregate / plan.size, 3),
        "multichip_parity": parity,
    }


def bench_hot_fetch(
    chunks: list[bytes], dk, *, window: int = 8, replays: int = 128
) -> dict:
    """Decrypt-once/serve-many (ISSUE 12): the same encrypted windows read
    cold (storage fetch + fused GCM decrypt) and then replayed with a seeded
    Zipfian draw against the `DeviceHotCache` tier. `hot_fetch_gibs` is the
    replay throughput served from the resident decrypted windows (zero GCM
    dispatches — asserted), next to `hot_cold_fetch_gibs`, the same chain's
    decrypting path. Host-path timing by construction (the hot serve never
    touches the device), so the ratio is honest on the CPU fallback too."""
    import io as _io

    from tieredstorage_tpu.fetch.cache.device_hot import DeviceHotCache
    from tieredstorage_tpu.fetch.chunk_manager import DefaultChunkManager
    from tieredstorage_tpu.manifest.chunk_index import FixedSizeChunkIndex
    from tieredstorage_tpu.manifest.encryption_metadata import (
        SegmentEncryptionMetadataV1,
    )
    from tieredstorage_tpu.manifest.segment_indexes import (
        IndexType,
        SegmentIndexesV1Builder,
    )
    from tieredstorage_tpu.manifest.segment_manifest import SegmentManifestV1
    from tieredstorage_tpu.ops import gcm as gcm_ops
    from tieredstorage_tpu.storage.core import ObjectKey
    from tieredstorage_tpu.transform.api import TransformOptions
    from tieredstorage_tpu.transform.tpu import TpuTransformBackend

    chunk_bytes = len(chunks[0])
    n_chunks = len(chunks)
    n_windows = n_chunks // window
    backend = TpuTransformBackend()
    ivs = [i.to_bytes(4, "big") * 3 for i in range(1, n_chunks + 1)]
    blob = b"".join(
        backend.transform(chunks, TransformOptions(encryption=dk, ivs=ivs))
    )

    class _Fetcher:
        def fetch(self, key, r):
            return _io.BytesIO(blob[r.from_position : r.to_position + 1])

    index = FixedSizeChunkIndex(
        original_chunk_size=chunk_bytes,
        original_file_size=chunk_bytes * n_chunks,
        transformed_chunk_size=chunk_bytes + 28,
        final_transformed_chunk_size=chunk_bytes + 28,
    )
    builder = SegmentIndexesV1Builder()
    for t in (IndexType.OFFSET, IndexType.TIMESTAMP,
              IndexType.PRODUCER_SNAPSHOT, IndexType.LEADER_EPOCH):
        builder.add(t, 0)
    manifest = SegmentManifestV1(
        chunk_index=index, segment_indexes=builder.build(), compression=False,
        encryption=SegmentEncryptionMetadataV1(dk.data_key, dk.aad),
        remote_log_segment_metadata=None,
    )
    default = DefaultChunkManager(_Fetcher(), backend)
    hot = DeviceHotCache(
        default, backend, innermost=default,
        budget_bytes=4 << 30, admission_hits=2,
    )
    key = ObjectKey("bench/topic/0/00000000000000000000-bench.log")
    windows = [list(range(w * window, (w + 1) * window)) for w in range(n_windows)]

    # Cold pass (decrypt jit already warm from the transform above), then a
    # second sweep so second-hit promotion admits every window.
    t0 = time.perf_counter()
    for ids in windows:
        hot.get_chunks(key, manifest, ids)
    cold_s = time.perf_counter() - t0
    for ids in windows:
        hot.get_chunks(key, manifest, ids)

    rng = np.random.default_rng(7)
    draws = (rng.zipf(1.2, replays) - 1) % n_windows
    before = gcm_ops.device_dispatches()
    hits_before, misses_before = hot.hits, hot.misses
    replay_bytes = 0
    t0 = time.perf_counter()
    for w in draws:
        replay_bytes += sum(
            len(c) for c in hot.get_chunks(key, manifest, windows[int(w)])
        )
    replay_s = time.perf_counter() - t0
    dispatches = gcm_ops.device_dispatches() - before
    hits = hot.hits - hits_before
    misses = hot.misses - misses_before
    cold_gibs = (chunk_bytes * n_chunks) / (1 << 30) / cold_s
    hot_gibs = replay_bytes / (1 << 30) / replay_s
    return {
        "hot_fetch_gibs": round(hot_gibs, 3),
        "hot_cold_fetch_gibs": round(cold_gibs, 3),
        "hot_vs_cold": round(hot_gibs / cold_gibs, 1) if cold_gibs else 0.0,
        "hot_hit_rate": round(hits / max(1, hits + misses), 4),
        "hot_replay_gcm_dispatches": dispatches,
        "hot_device_windows": hot.device_windows,
    }


def bench_readahead_replay(
    chunks: list[bytes], dk, *, ra_window: int = 4
) -> dict:
    """Predictive sequential readahead (ISSUE 18): the same cold sequential
    replay measured with the `ReadaheadManager` tier on vs off. The
    foreground reads chunk-at-a-time (the worst reactive shape); the
    readahead arm speculates `ra_window`-chunk windows ahead through the
    SAME chain, so the on-arm should show fewer (merged) GCM dispatches
    and a lower per-read p99 once the stream promotes. Recorded as
    trajectory keys — the `make load-demo` A/B is the hard gate."""
    import io as _io

    from tieredstorage_tpu.fetch.cache.memory import MemoryChunkCache
    from tieredstorage_tpu.fetch.chunk_manager import DefaultChunkManager
    from tieredstorage_tpu.fetch.readahead import ReadaheadManager
    from tieredstorage_tpu.manifest.chunk_index import FixedSizeChunkIndex
    from tieredstorage_tpu.manifest.encryption_metadata import (
        SegmentEncryptionMetadataV1,
    )
    from tieredstorage_tpu.manifest.segment_indexes import (
        IndexType,
        SegmentIndexesV1Builder,
    )
    from tieredstorage_tpu.manifest.segment_manifest import SegmentManifestV1
    from tieredstorage_tpu.ops import gcm as gcm_ops
    from tieredstorage_tpu.storage.core import ObjectKey
    from tieredstorage_tpu.transform.api import TransformOptions
    from tieredstorage_tpu.transform.tpu import TpuTransformBackend

    chunk_bytes = len(chunks[0])
    n_chunks = len(chunks)
    backend = TpuTransformBackend()
    ivs = [i.to_bytes(4, "big") * 3 for i in range(1, n_chunks + 1)]
    blob = b"".join(
        backend.transform(chunks, TransformOptions(encryption=dk, ivs=ivs))
    )

    class _Fetcher:
        def fetch(self, key, r):
            return _io.BytesIO(blob[r.from_position : r.to_position + 1])

    index = FixedSizeChunkIndex(
        original_chunk_size=chunk_bytes,
        original_file_size=chunk_bytes * n_chunks,
        transformed_chunk_size=chunk_bytes + 28,
        final_transformed_chunk_size=chunk_bytes + 28,
    )
    builder = SegmentIndexesV1Builder()
    for t in (IndexType.OFFSET, IndexType.TIMESTAMP,
              IndexType.PRODUCER_SNAPSHOT, IndexType.LEADER_EPOCH):
        builder.add(t, 0)
    manifest = SegmentManifestV1(
        chunk_index=index, segment_indexes=builder.build(), compression=False,
        encryption=SegmentEncryptionMetadataV1(dk.data_key, dk.aad),
        remote_log_segment_metadata=None,
    )
    key = ObjectKey("bench/topic/0/00000000000000000000-bench.log")

    def cold_replay(readahead_on: bool):
        cache = MemoryChunkCache(DefaultChunkManager(_Fetcher(), backend))
        cache.configure({
            "size": chunk_bytes * n_chunks, "prefetch.max.size": 0,
        })
        tier = (
            ReadaheadManager(cache, window_chunks=ra_window)
            if readahead_on else cache
        )
        before = gcm_ops.device_dispatches()
        lat_s: list[float] = []
        try:
            for cid in range(n_chunks):
                t0 = time.perf_counter()
                got = tier.get_chunks(key, manifest, [cid])
                lat_s.append(time.perf_counter() - t0)
                assert got[0] == chunks[cid]
            if readahead_on:
                # Drain in-flight speculation before counting dispatches.
                tier._executor.shutdown(wait=True)
            dispatches = gcm_ops.device_dispatches() - before
            manager = tier if readahead_on else None
            return lat_s, dispatches, manager
        finally:
            if readahead_on:
                tier._executor.shutdown(wait=True)
            cache.close()

    lat_off, dispatches_off, _ = cold_replay(False)
    lat_on, dispatches_on, manager = cold_replay(True)
    p99 = lambda xs: float(np.percentile(np.array(xs) * 1000.0, 99))  # noqa: E731
    return {
        "readahead_on_p99_ms": round(p99(lat_on), 3),
        "readahead_off_p99_ms": round(p99(lat_off), 3),
        "readahead_on_gcm_launches": dispatches_on,
        "readahead_off_gcm_launches": dispatches_off,
        "readahead_launches": manager.windows_launched,
        "readahead_occupancy": round(
            manager.chunks_speculated / max(1, manager.windows_launched), 3
        ),
        "readahead_hit_rate": round(manager.hit_rate, 4),
        "readahead_wasted_ratio": round(manager.misprediction_ratio, 4),
    }


def measure_compile_cost(dk, chunk_bytes: int, window: int) -> dict:
    """First-trace compile cost of the fused packed window program at the
    bench shape (ISSUE 13: the fused tree kernel collapses the traced
    graph of the full-GCM program; this records its compile cost next to
    the GiB/s keys).

    Uses the AOT lower+compile API on the PRODUCTION `_packed_jit` wrapper,
    which bypasses the in-memory executable cache — so `compile_ms` is what
    a fresh process pays at this shape. `compile_cached_ms` is an immediate
    second lower+compile: with the persistent compilation cache armed and a
    compile above its threshold, this is the cache-load cost a later process
    pays.
    """
    import jax
    import jax.numpy as jnp

    from tieredstorage_tpu.ops import gcm

    ctx = gcm.make_context(dk.data_key, dk.aad, chunk_bytes)
    rk, agg, fm, cb = gcm._device_consts(ctx)
    sm = gcm._device_step_mat(ctx)
    fn = gcm._packed_jit(False, False, None)
    shape = jax.ShapeDtypeStruct((window, chunk_bytes + 16), jnp.uint8)

    def lower_compile() -> float:
        t0 = time.perf_counter()
        fn.lower(
            rk, None, shape, agg, fm, cb, sm,
            chunk_bytes=ctx.chunk_bytes, n_blocks=ctx.n_blocks, decrypt=False,
        ).compile()
        return (time.perf_counter() - t0) * 1e3

    compile_ms = lower_compile()
    compile_cached_ms = lower_compile()

    cache_dir = jax.config.jax_compilation_cache_dir
    entries = 0
    if cache_dir and os.path.isdir(cache_dir):
        entries = len(os.listdir(cache_dir))
    return {
        "compile_ms": round(compile_ms, 1),
        "compile_cached_ms": round(compile_cached_ms, 1),
        "persistent_cache": {
            "enabled": bool(cache_dir),
            "dir": cache_dir,
            "entries": entries,
        },
    }


def bench_batched_fetch(
    dk, *, chunk_bytes: int = 8 << 10, window: int = 4,
    stream_counts: tuple = (1, 8, 64, 512),
) -> dict:
    """Cross-request GCM batching (ISSUE 15): the same decrypt workload
    fanned across 1/8/64/512 concurrent streams through one shared
    backend, batching ON (`WindowBatcher` coalescing concurrent windows
    into merged launches) vs the batching-OFF control. Reported per stream
    count: aggregate plaintext GiB/s, the measured launch count, and the
    batcher's mean occupancy — the contract under concurrency is
    `launches < windows` (dispatches_per_window < 1), with the
    single-stream row showing the fast path costs nothing. Small fixed
    windows by design: the per-launch floor this amortizes is
    size-independent, so the rows are to be read for the launch-count
    ratio, not absolute throughput."""
    import threading as _threading

    from tieredstorage_tpu.ops import gcm as gcm_ops
    from tieredstorage_tpu.transform.api import (
        DetransformOptions,
        TransformOptions,
    )
    from tieredstorage_tpu.transform.tpu import TpuTransformBackend

    n_windows_max = max(max(stream_counts), 64)
    rng = random.Random(15)
    plain = [
        [
            bytes(rng.getrandbits(8) for _ in range(chunk_bytes))
            for _ in range(window)
        ]
        for _ in range(n_windows_max)
    ]
    enc_backend = TpuTransformBackend()
    opts = TransformOptions(encryption=dk)
    wire = [enc_backend.transform(list(w), opts) for w in plain]
    enc_backend.close()
    d_opts = DetransformOptions(encryption=dk)
    out: dict = {}

    for streams in stream_counts:
        n_windows = max(64, streams)
        for batched in (True, False):
            backend = TpuTransformBackend()
            if batched:
                backend.enable_batching(wait_ms=2, max_windows=16)
            # Warm every jit shape this run can launch (fixed direct
            # windows + the merged varlen row ladder), then reset stats so
            # the measured launch counts are the steady state's.
            fixed_ctx = gcm_ops.make_context(dk.data_key, dk.aad, chunk_bytes)
            warm = np.zeros((window, chunk_bytes + 16), np.uint8)
            np.asarray(backend._launch_packed(
                fixed_ctx, backend._stage_packed(warm, False), False,
                decrypt=True,
            ))
            if batched:
                var_ctx = gcm_ops.make_varlen_context(
                    dk.data_key, dk.aad, chunk_bytes
                )
                rows = window
                while rows <= 16 * window:
                    warm = np.zeros((rows, var_ctx.max_bytes + 16), np.uint8)
                    warm[:, var_ctx.max_bytes + 12] = 16
                    np.asarray(backend._launch_packed(
                        var_ctx, backend._stage_packed(warm, True), True,
                        decrypt=True,
                    ))
                    rows *= 2
            backend.reset_dispatch_stats()

            errors: list = []

            def worker(wid: int, backend=backend, n_windows=n_windows,
                       streams=streams, errors=errors) -> None:
                for i in range(wid, n_windows, streams):
                    got = backend.detransform(list(wire[i]), d_opts)
                    if got != plain[i]:
                        errors.append(i)

            threads = [
                _threading.Thread(target=worker, args=(wid,))
                for wid in range(streams)
            ]
            t0 = time.perf_counter()
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            elapsed = time.perf_counter() - t0
            if errors:
                raise AssertionError(f"byte diffs in windows {errors[:5]}")
            stats = backend.dispatch_stats
            total_bytes = n_windows * window * chunk_bytes
            mode = "batched" if batched else "unbatched"
            out[f"{mode}_fetch_gibs_{streams}"] = round(
                total_bytes / (1 << 30) / elapsed, 4
            )
            out[f"{mode}_fetch_launches_{streams}"] = stats.dispatches
            out[f"{mode}_fetch_windows_{streams}"] = stats.windows
            if batched:
                out[f"batched_fetch_occupancy_{streams}"] = round(
                    backend.batcher.mean_occupancy, 3
                )
            backend.close()
    return out


def bench_ranged_fetch(
    chunks: list[bytes], *, chunk_bytes: int, codec: str = "zstd",
    key_prefix: str = "",
) -> dict:
    """BASELINE config 4: ranged fetches through the disk chunk cache with a
    16 MiB prefetch window over a compressed+encrypted segment on the
    filesystem backend. Reports p50/p99 latency of 64 KiB reads (seeded
    offsets, cold-start cache: the percentile mix includes miss-path
    decrypt+decompress and hit-path disk reads, like a broker serving a
    consumer catching up). With zstd on, the decompress half is host-side,
    as the reference's whole fetch path is.

    `codec` selects the manifest compression codec, so the detransform side
    of tpu-lzhuff-v1 (native C expander) is measured next to zstd — the
    round-4 verdict's missing fetch-side codec number."""
    import shutil
    import tempfile
    from pathlib import Path

    root = Path(tempfile.mkdtemp(prefix="bench-fetch-"))
    try:
        out = _ranged_fetch_measured(root, chunks, chunk_bytes, codec)
        return {f"{key_prefix}{k}": v for k, v in out.items()}
    finally:
        # ~3x the segment size of scratch (source file, remote objects,
        # disk-cache entries) — must not accumulate across bench runs.
        shutil.rmtree(root, ignore_errors=True)


def _ranged_fetch_measured(
    root, chunks: list[bytes], chunk_bytes: int, codec: str
) -> dict:
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

    (root / "remote").mkdir()
    (root / "cache").mkdir()
    segment = b"".join(chunks)
    seg_path = root / "bench.log"
    seg_path.write_bytes(segment)
    for name in ("off.idx", "time.idx", "prod.idx"):
        (root / name).write_bytes(b"\x00" * 64)
    pub, priv = generate_key_pair_pem_files(root, prefix="bench")

    rsm = RemoteStorageManager()
    rsm.configure({
        "storage.backend.class":
            "tieredstorage_tpu.storage.filesystem.FileSystemStorage",
        "storage.root": str(root / "remote"),
        "chunk.size": chunk_bytes,
        "compression.enabled": True,
        "compression.codec": codec,
        "encryption.enabled": True,
        "encryption.key.pair.id": "key1",
        "encryption.key.pairs": "key1",
        "encryption.key.pairs.key1.public.key.file": str(pub),
        "encryption.key.pairs.key1.private.key.file": str(priv),
        "fetch.chunk.cache.class":
            "tieredstorage_tpu.fetch.cache.disk.DiskChunkCache",
        "fetch.chunk.cache.path": str(root / "cache"),
        "fetch.chunk.cache.size": 1 << 30,
        "fetch.chunk.cache.prefetch.max.size": 16 << 20,
    })
    try:
        tip = TopicIdPartition(KafkaUuid.random(), TopicPartition("bench", 0))
        meta = RemoteLogSegmentMetadata(
            RemoteLogSegmentId(tip, KafkaUuid.random()), 0, 1,
            segment_size_in_bytes=len(segment),
        )
        rsm.copy_log_segment_data(
            meta,
            LogSegmentData(seg_path, root / "off.idx", root / "time.idx",
                           root / "prod.idx", None, b"bench"),
        )

        rng = np.random.default_rng(3)
        read_bytes = 64 << 10
        lat_ms = []
        for _ in range(100):
            start = int(rng.integers(0, max(1, len(segment) - read_bytes)))
            t0 = time.perf_counter()
            data = rsm.fetch_log_segment(meta, start, start + read_bytes - 1).read()
            lat_ms.append((time.perf_counter() - t0) * 1e3)
            assert data == segment[start : start + read_bytes]
    finally:
        rsm.close()
    return {
        "ranged_fetch_p50_ms": round(float(np.percentile(lat_ms, 50)), 2),
        "ranged_fetch_p99_ms": round(float(np.percentile(lat_ms, 99)), 2),
    }


def run_bench() -> dict:
    import jax

    device = jax.devices()[0]
    if device.platform != "tpu":
        raise SystemExit(
            f"bench: JAX found no TPU (platform={device.platform!r}); "
            "a device metric is measured on the chip or not at all"
        )
    mc_devices = multichip_devices()

    from tieredstorage_tpu.utils.platforms import enable_compile_cache

    _err(
        f"[bench] running on {jax.devices()} "
        f"(compile cache: {enable_compile_cache()})"
    )

    from tieredstorage_tpu.security.aes import AesEncryptionProvider
    from tieredstorage_tpu.transform.api import TransformOptions
    from tieredstorage_tpu.transform.cpu import CpuTransformBackend
    from tieredstorage_tpu.transform.tpu import TpuTransformBackend

    # The official protocol is the default: 64 chunks of 4 MiB.
    chunk_bytes = int(os.environ.get("BENCH_CHUNK_BYTES", 4 << 20))
    n_chunks = int(os.environ.get("BENCH_N_CHUNKS", 64))
    chunks = make_segment(n_chunks, chunk_bytes)
    total_bytes = n_chunks * chunk_bytes
    gib = total_bytes / (1 << 30)

    dk = AesEncryptionProvider().create_data_key_and_aad()
    opts = TransformOptions(compression=True, encryption=dk)
    opts_enc_only = TransformOptions(compression=False, encryption=dk)
    window = max(1, int(os.environ.get("BENCH_WINDOW_CHUNKS", 16)))
    extras: dict = {}

    # Record whether the Pallas kernels engage at the measured shapes.
    # `pallas_aes`/`pallas_ghash` are the SHAPE eligibility verdicts (pure
    # host logic — the production windows tile onto the kernels), probed
    # with the SAME shapes the measured windows produce so a shrunken
    # workload can't record a kernel the run never used;
    # `pallas_*_platform` records the platform/preflight half that the
    # dispatch gate additionally requires, so the artifact shows which
    # program the run measured.
    try:
        from tieredstorage_tpu.ops.aes_bitsliced import pallas_aes_available
        from tieredstorage_tpu.ops.aes_pallas import use_pallas_aes
        from tieredstorage_tpu.ops.ghash_pallas import (
            pallas_ghash_available,
            use_pallas_ghash,
        )

        from tieredstorage_tpu.ops.gcm import make_context

        # Derive the level-1 grouping from the real context rather than
        # re-implementing ghash_agg_plan's max_k math: agg_mats[0] is the
        # int8[8, k1*16, 128] operand _ghash_grouped actually contracts, so
        # the recorded verdict tracks the measured program even if the plan
        # changes.
        ctx = make_context(dk.data_key, dk.aad, chunk_bytes)
        m_blocks = ctx.n_blocks
        aes_words = window * (-(-(m_blocks + 1) // 32))
        k1 = ctx.agg_mats[0].shape[1] // 16
        ghash_rows = window * (-(-m_blocks // k1))
        extras["pallas_aes"] = bool(use_pallas_aes(aes_words))
        extras["pallas_ghash"] = bool(use_pallas_ghash(ghash_rows, k1 * 16))
        extras["pallas_aes_platform"] = bool(pallas_aes_available())
        extras["pallas_ghash_platform"] = bool(pallas_ghash_available())
        _err(
            f"[bench] pallas kernels at the measured shapes: "
            f"aes={extras['pallas_aes']} ghash={extras['pallas_ghash']} "
            f"(platform: aes={extras['pallas_aes_platform']} "
            f"ghash={extras['pallas_ghash_platform']})"
        )
    except Exception as exc:  # never cost the artifact
        extras["pallas_gate_error"] = f"{type(exc).__name__}: {exc}"

    # 1. The per-chip number (BASELINE.md north star): device-resident GCM.
    dev_s, dev_dec_s = bench_device_resident(chunks, dk, window=window)
    extras["device_encrypt_gibs"] = round(gib / dev_s, 3)
    extras["device_decrypt_gibs"] = round(gib / dev_dec_s, 3)
    _err(f"[bench] device-resident AES-GCM (per-chip): {gib / dev_s:.3f} GiB/s")
    _err(
        f"[bench] device-resident AES-GCM decrypt (fetch side): "
        f"{gib / dev_dec_s:.3f} GiB/s"
    )

    # 1b. MULTICHIP: the same windows sharded over the local mesh through
    # the production packed program (one logical dispatch fanned out across
    # every chip) — per-chip and aggregate GiB/s plus the mesh shape land in
    # the trajectory JSON next to the pallas verdicts.
    # `mesh_size` is always recorded (1 = the unsharded bench above).
    from tieredstorage_tpu.parallel.mesh import MeshPlan

    try:
        plan = MeshPlan.from_spec(mc_devices or 1)
    except Exception as exc:
        plan = MeshPlan(None)
        extras["multichip_error"] = f"{type(exc).__name__}: {exc}"
    extras["mesh_size"] = plan.size
    if plan.size > 1:
        try:
            extras.update(bench_multichip(chunks, dk, window=window, plan=plan))
            _err(
                f"[bench] MULTICHIP sharded AES-GCM over {plan.size} devices: "
                f"aggregate {extras['multichip_aggregate_gibs']} GiB/s, "
                f"per-chip {extras['multichip_per_chip_gibs']} GiB/s, "
                f"parity={extras['multichip_parity']}"
            )
        except Exception as exc:  # never cost the single-chip artifact
            extras["multichip_error"] = f"{type(exc).__name__}: {exc}"
            _err(f"[bench] MULTICHIP bench failed: {extras['multichip_error']}")

    # 1c. HOT TIER (decrypt once, serve many): Zipfian replay against the
    # device hot-window cache next to the cold (decrypting) path. Guarded:
    # a hot-tier failure must not cost the already-measured device numbers.
    try:
        extras.update(bench_hot_fetch(chunks, dk, window=min(4, len(chunks))))
        _err(
            f"[bench] hot-tier replay: hot={extras['hot_fetch_gibs']} GiB/s "
            f"vs cold={extras['hot_cold_fetch_gibs']} GiB/s "
            f"({extras['hot_vs_cold']}x), hit_rate={extras['hot_hit_rate']}, "
            f"replay GCM dispatches={extras['hot_replay_gcm_dispatches']}"
        )
    except Exception as exc:
        extras["hot_error"] = f"{type(exc).__name__}: {exc}"
        _err(f"[bench] hot-tier bench failed: {extras['hot_error']}")

    # 1c2. PREDICTIVE READAHEAD (ISSUE 18): the cold sequential replay with
    # the readahead tier on vs off — merged-launch and p99 trajectory keys
    # (BENCH_READAHEAD); the load-demo A/B is the hard gate. Guarded: a
    # readahead failure must not cost the already-measured numbers.
    try:
        extras.update(bench_readahead_replay(chunks, dk))
        _err(
            f"[bench] BENCH_READAHEAD replay: "
            f"p99 on={extras['readahead_on_p99_ms']}ms "
            f"off={extras['readahead_off_p99_ms']}ms, GCM launches "
            f"on={extras['readahead_on_gcm_launches']} "
            f"off={extras['readahead_off_gcm_launches']}, "
            f"launches={extras['readahead_launches']} "
            f"occ={extras['readahead_occupancy']}, "
            f"hit_rate={extras['readahead_hit_rate']}, "
            f"wasted_ratio={extras['readahead_wasted_ratio']}"
        )
    except Exception as exc:
        extras["readahead_error"] = f"{type(exc).__name__}: {exc}"
        _err(f"[bench] readahead bench failed: {extras['readahead_error']}")

    # 1d. CROSS-REQUEST BATCHING (ISSUE 15): concurrent-stream decrypt
    # through the WindowBatcher vs the unbatched control. Guarded the same
    # way: a batcher failure must never cost the kernel numbers.
    try:
        extras.update(bench_batched_fetch(dk))
        _err(
            "[bench] batched fetch: "
            + " ".join(
                f"s={s}:"
                f"{extras[f'batched_fetch_launches_{s}']}L"
                f"/occ={extras[f'batched_fetch_occupancy_{s}']}"
                f" vs {extras[f'unbatched_fetch_launches_{s}']}L"
                for s in (1, 8, 64, 512)
            )
        )
    except Exception as exc:
        extras["batched_fetch_error"] = f"{type(exc).__name__}: {exc}"
        _err(f"[bench] batched-fetch bench failed: {extras['batched_fetch_error']}")

    # 3. Transfer-inclusive pipelines.
    from tieredstorage_tpu.utils.tracing import Tracer

    tpu = TpuTransformBackend()
    tpu.tracer = Tracer(enabled=True)

    def windowed(o):
        def run():
            n = sum(
                len(w)
                for w in tpu.transform_windows(
                    (chunks[i : i + window] for i in range(0, len(chunks), window)), o
                )
            )
            assert n == len(chunks)

        return run

    # Guarded like the codec sections: a missing optional dependency
    # (zstandard off-CI) or a pipeline failure must not zero the already-
    # measured device-resident and MULTICHIP numbers.
    try:
        tpu.reset_dispatch_stats()
        e2e_enc_s = time_best(windowed(opts_enc_only), iters=2, warmup=1)
        extras["end_to_end_encrypt_gibs"] = round(gib / e2e_enc_s, 3)
        _err(
            f"[bench] end-to-end encrypt-only (incl transfers): "
            f"{gib / e2e_enc_s:.3f} GiB/s"
        )
        # Snapshot the accounting now so the keys survive a zstd-less
        # environment (the compressed run below re-records over them).
        wstats = tpu.dispatch_stats
        extras["dispatches_per_window"] = wstats.dispatches_per_window
        extras["hbm_roundtrips_per_window"] = wstats.hbm_roundtrips_per_window
        extras["bytes_per_dispatch"] = wstats.bytes_per_dispatch
        e2e_s = time_best(windowed(opts), iters=2, warmup=1)
        extras["end_to_end_gibs"] = round(gib / e2e_s, 3)
        _err(
            f"[bench] end-to-end zstd+encrypt pipelined x{window}-chunk windows "
            f"(incl transfers): {gib / e2e_s:.3f} GiB/s"
        )
        # Launch-count regressions must show up in the BENCH trajectory the
        # same way GiB/s does: the steady-state window path is ONE fused GCM
        # dispatch (and one h2d staging transfer + one d2h fetch) per window
        # (transform/tpu.py DispatchStats over both windowed runs above).
        wstats = tpu.reset_dispatch_stats()
        extras["dispatches_per_window"] = wstats.dispatches_per_window
        extras["hbm_roundtrips_per_window"] = wstats.hbm_roundtrips_per_window
        extras["bytes_per_dispatch"] = wstats.bytes_per_dispatch
        _err(
            f"[bench] window dispatch accounting: windows={wstats.windows} "
            f"dispatches={wstats.dispatches} h2d={wstats.h2d_transfers} "
            f"d2h={wstats.d2h_fetches} -> dispatches_per_window="
            f"{wstats.dispatches_per_window} hbm_roundtrips_per_window="
            f"{wstats.hbm_roundtrips_per_window} bytes_per_dispatch="
            f"{wstats.bytes_per_dispatch}"
        )
    except Exception as exc:
        extras["end_to_end_error"] = f"{type(exc).__name__}: {exc}"
        _err(f"[bench] end-to-end pipeline failed: {extras['end_to_end_error']}")

    # Compile-cost proof (ISSUE 13): first-trace cost of the fused window
    # program at the bench shape + the persistent-cache verdict, recorded
    # in the trajectory JSON so the 33-minute hole stays provably closed.
    # Guarded: a compile-measurement failure must not cost the artifact.
    try:
        extras.update(measure_compile_cost(dk, chunk_bytes, window))
        _err(
            f"[bench] fused window compile at ({window}, {chunk_bytes}): "
            f"first {extras['compile_ms']} ms, repeat "
            f"{extras['compile_cached_ms']} ms, persistent cache "
            f"{extras['persistent_cache']}"
        )
    except Exception as exc:
        extras["compile_error"] = f"{type(exc).__name__}: {exc}"
        _err(f"[bench] compile-cost measurement failed: {extras['compile_error']}")

    try:
        t0 = time.perf_counter()
        compressed = tpu.transform(
            chunks, TransformOptions(compression=True, encryption=None)
        )
        comp_s = time.perf_counter() - t0
        ratio = sum(len(c) for c in compressed) / total_bytes
        extras["compression_only_gibs"] = round(gib / comp_s, 3)
        extras["compression_ratio"] = round(ratio, 3)
        _err(
            f"[bench] compression-only (host): {gib / comp_s:.3f} GiB/s, "
            f"ratio {ratio:.3f}"
        )
    except Exception as exc:
        extras["compression_only_error"] = f"{type(exc).__name__}: {exc}"
        _err(f"[bench] compression-only failed: {extras['compression_only_error']}")

    # Device codec (tpu-huff-v1): batched Huffman on-chip, incl transfers.
    # Guarded: an experimental-codec failure must not zero the round's
    # already-measured primary metrics.
    try:
        from tieredstorage_tpu.transform import thuff as thuff_codec

        thuff_codec.compress_batch(chunks)  # warm jit at the timed shape
        t0 = time.perf_counter()
        tframes = thuff_codec.compress_batch(chunks)
        thuff_s = time.perf_counter() - t0
        tratio = sum(len(c) for c in tframes) / total_bytes
        extras["thuff_compress_gibs"] = round(gib / thuff_s, 3)
        extras["thuff_ratio"] = round(tratio, 3)
        _err(
            f"[bench] tpu-huff-v1 device codec (incl transfers): "
            f"{gib / thuff_s:.3f} GiB/s, ratio {tratio:.3f}"
        )
    except Exception as exc:
        extras["thuff_error"] = f"{type(exc).__name__}: {exc}"
        _err(f"[bench] tpu-huff-v1 codec failed: {extras['thuff_error']}")

    # Device LZ codec (tpu-lzhuff-v1): match-finding + Huffman on-chip,
    # sequence serialization host-side, incl transfers. Same guard.
    try:
        from tieredstorage_tpu.transform import lzhuff as lzhuff_codec

        lz_chunks = chunks
        lz_bytes = sum(len(c) for c in lz_chunks)
        lzhuff_codec.compress_batch(lz_chunks)  # warm jit at the timed shape
        t0 = time.perf_counter()
        lframes = lzhuff_codec.compress_batch(lz_chunks)
        lzhuff_s = time.perf_counter() - t0
        lratio = sum(len(c) for c in lframes) / lz_bytes
        extras["lzhuff_compress_gibs"] = round(lz_bytes / (1 << 30) / lzhuff_s, 3)
        extras["lzhuff_ratio"] = round(lratio, 3)
        # Record the measured workload next to its rate.
        extras["lzhuff_chunks"] = len(lz_chunks)
        extras["lzhuff_bytes"] = lz_bytes
        _err(
            f"[bench] tpu-lzhuff-v1 device codec (incl transfers, "
            f"{len(lz_chunks)} chunks): "
            f"{lz_bytes / (1 << 30) / lzhuff_s:.3f} GiB/s, ratio {lratio:.3f}"
        )
    except Exception as exc:
        extras["lzhuff_error"] = f"{type(exc).__name__}: {exc}"
        _err(f"[bench] tpu-lzhuff-v1 codec failed: {extras['lzhuff_error']}")
    for name, agg in sorted(tpu.tracer.summary().items()):
        _err(
            f"[bench]   span {name}: n={agg['count']} "
            f"total={agg['total_s']*1e3:.0f}ms avg={agg['avg_s']*1e3:.1f}ms"
        )
    tpu.close()

    # 4. Host baselines: the reference's strictly sequential per-chunk chain,
    # and a 10-worker pool ≈ the RLM's concurrent segment uploads. Guarded:
    # they need cryptography/zstandard (absent off-CI); the device numbers
    # above must survive without them.
    cpu_par_enc_s = None
    try:
        cpu = CpuTransformBackend()
        cpu_seq_s = time_best(lambda: cpu.transform(chunks, opts), iters=1, warmup=0)
        extras["cpu_sequential_gibs"] = round(gib / cpu_seq_s, 3)
        _err(f"[bench] CPU sequential baseline: {gib / cpu_seq_s:.3f} GiB/s")

        def cpu_parallel(o):
            def run():
                with ThreadPoolExecutor(10) as pool:
                    shards = [chunks[i::10] for i in range(10)]
                    list(pool.map(lambda s: cpu.transform(s, o), shards))

            return run

        cpu_par_s = time_best(cpu_parallel(opts), iters=1, warmup=0)
        extras["cpu_parallel10_gibs"] = round(gib / cpu_par_s, 3)
        _err(
            f"[bench] CPU 10-worker zstd+encrypt baseline: "
            f"{gib / cpu_par_s:.3f} GiB/s"
        )
        cpu_par_enc_s = time_best(cpu_parallel(opts_enc_only), iters=1, warmup=0)
        extras["cpu_parallel10_encrypt_gibs"] = round(gib / cpu_par_enc_s, 3)
        _err(
            f"[bench] CPU 10-worker encrypt-only baseline: "
            f"{gib / cpu_par_enc_s:.3f} GiB/s"
        )
    except Exception as exc:
        extras["cpu_baseline_error"] = f"{type(exc).__name__}: {exc}"
        _err(f"[bench] CPU baselines failed: {extras['cpu_baseline_error']}")

    # 5. BASELINE config 4: p50/p99 ranged fetch through the disk cache
    # (guarded: a fetch-path failure must not cost the transform metrics).
    try:
        extras.update(bench_ranged_fetch(chunks, chunk_bytes=chunk_bytes))
        _err(
            f"[bench] ranged fetch (disk cache, 16 MiB prefetch): "
            f"p50={extras['ranged_fetch_p50_ms']}ms "
            f"p99={extras['ranged_fetch_p99_ms']}ms"
        )
    except Exception as exc:
        extras["ranged_fetch_error"] = f"{type(exc).__name__}: {exc}"
        _err(f"[bench] ranged-fetch bench failed: {extras['ranged_fetch_error']}")

    # Same protocol with the LZ device codec — the fetch side detransforms
    # through the native C expander (round-4 verdict item 4).
    try:
        lz_chunks = chunks
        extras.update(bench_ranged_fetch(
            lz_chunks, chunk_bytes=chunk_bytes,
            codec="tpu-lzhuff-v1", key_prefix="lzhuff_",
        ))
        extras["lzhuff_fetch_chunks"] = len(lz_chunks)
        _err(
            f"[bench] ranged fetch with tpu-lzhuff-v1 ({len(lz_chunks)} chunks): "
            f"p50={extras['lzhuff_ranged_fetch_p50_ms']}ms "
            f"p99={extras['lzhuff_ranged_fetch_p99_ms']}ms"
        )
    except Exception as exc:
        extras["lzhuff_ranged_fetch_error"] = f"{type(exc).__name__}: {exc}"
        _err(
            f"[bench] lzhuff ranged-fetch bench failed: "
            f"{extras['lzhuff_ranged_fetch_error']}"
        )

    return {
        "metric": "device_segment_encrypt_throughput_per_chip",
        "value": round(gib / dev_s, 3),
        "unit": "GiB/s",
        # Speedup of the per-chip device encrypt over the 10-worker host pool
        # doing the same AES-GCM work (full-transform baselines also reported).
        "vs_baseline": (
            round(cpu_par_enc_s / dev_s, 2) if cpu_par_enc_s else 0.0
        ),
        **extras,
        "platform": device.platform,
        "device_kind": device.device_kind,
        "device_count": len(jax.devices()),
    }


def main() -> None:
    print(json.dumps(run_bench()), flush=True)


if __name__ == "__main__":
    main()
