"""Marginal device-resident cost of the tpu-lzhuff-v1 codec stages.

Companion to tools/profile_r3.py for the round-4 codec: times the LZ
analyze kernel (hash-table scan + match extension + pointer-doubling parse
+ dominant-distance pass, ops/lz.py) and the Huffman encode stage
(ops/huffman.py) at two sizes on device-resident inputs; the slope
separates the per-byte cost from the per-launch floor. Run on the chip:

    PYTHONPATH=. python tools/profile_lz.py [total_mib] [chunk_mib]

Host-side stages (serialization, frame assembly) are timed separately so
the device/host split of a production window is visible.
"""

from __future__ import annotations

import sys
import time

import numpy as np
import jax
import jax.numpy as jnp

from tieredstorage_tpu.ops.huffman import encode_batch
from tieredstorage_tpu.ops.lz import lz_analyze_batch, lz_shape
from tieredstorage_tpu.transform import lzhuff, thuff

err = lambda *a: print(*a, file=sys.stderr, flush=True)  # noqa: E731


def t(fn, *args, iters=3, warmup=1, **kw):
    for _ in range(warmup):
        jax.block_until_ready(fn(*args, **kw))
    best = float("inf")
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args, **kw))
        best = min(best, time.perf_counter() - t0)
    return best


def make_window(batch: int, chunk_bytes: int) -> np.ndarray:
    rng = np.random.default_rng(42)
    pattern = np.frombuffer(
        (b"offset=%019d key=user-%06d value=" % (0, 0)) * 64, dtype=np.uint8
    )
    half = (chunk_bytes + 1) // 2
    tiled = np.tile(pattern, chunk_bytes // (2 * len(pattern)) + 1)[
        : chunk_bytes - half
    ]
    chunks = np.empty((batch, chunk_bytes), np.uint8)
    for i in range(batch):
        chunks[i, 0::2] = rng.integers(0, 256, half, dtype=np.uint8)
        chunks[i, 1::2] = tiled[: chunk_bytes // 2]
    return chunks


def run_size(total_mib: int, chunk_mib: int) -> dict:
    chunk_bytes = chunk_mib << 20
    batch = max(1, (total_mib << 20) // chunk_bytes)
    chunks = make_window(batch, chunk_bytes)
    n_max = lz_shape(chunk_bytes)
    data = jax.device_put(chunks) if chunks.shape[1] == n_max else jax.device_put(
        np.pad(chunks, ((0, 0), (0, n_max - chunk_bytes)))
    )
    n_sym = jax.device_put(np.full(batch, chunk_bytes, np.int32))

    lz_s = t(lz_analyze_batch, data, n_sym, n_max=n_max)
    # Reuse one analyze result for the serialization timing below (the
    # jit cache makes this call cheap-but-not-free; no fifth device pass).
    lens_a, dists_a, sel_a = (
        np.asarray(x) for x in lz_analyze_batch(data, n_sym, n_max=n_max)
    )

    # Huffman encode stage on the raw window (table build host-side).
    lengths = np.zeros((batch, 256), np.int32)
    codes = np.zeros((batch, 256), np.int32)
    t0 = time.perf_counter()
    for row in range(batch):
        lens = thuff.limited_huffman_lengths(
            np.bincount(chunks[row], minlength=256)
        )
        lengths[row] = lens
        codes[row] = thuff.encode_tables(lens)
    tables_s = time.perf_counter() - t0
    huff_s = t(
        encode_batch,
        data[:, :chunk_bytes] if n_max != chunk_bytes else data,
        n_sym,
        jax.device_put(codes),
        jax.device_put(lengths),
        n_max=chunk_bytes,
    )

    # Host serialization (parse arrays -> field streams), one pass.
    t0 = time.perf_counter()
    for row in range(batch):
        lzhuff._serialize_row(
            chunks[row].tobytes(), sel_a[row], lens_a[row], dists_a[row]
        )
    serialize_s = time.perf_counter() - t0

    return {
        "bytes": batch * chunk_bytes,
        "lz_s": lz_s,
        "huff_s": huff_s,
        "tables_s": tables_s,
        "serialize_s": serialize_s,
    }


def main() -> None:
    total_mib = int(sys.argv[1]) if len(sys.argv) > 1 else 64
    chunk_mib = int(sys.argv[2]) if len(sys.argv) > 2 else 4
    err(f"[profile_lz] backend={jax.default_backend()} devices={jax.devices()}")
    if total_mib < 2 * chunk_mib:
        sys.exit(
            f"total_mib={total_mib} must be >= 2*chunk_mib={2 * chunk_mib}: "
            "the marginal slope needs two distinct batch sizes"
        )
    small = run_size(total_mib // 2, chunk_mib)
    big = run_size(total_mib, chunk_mib)
    d_bytes = big["bytes"] - small["bytes"]
    gib = d_bytes / (1 << 30)
    for stage in ("lz_s", "huff_s"):
        slope = big[stage] - small[stage]
        rate = gib / slope if slope > 0 else float("inf")
        err(
            f"[profile_lz] {stage[:-2]} marginal: {rate:.2f} GiB/s "
            f"({small[stage]*1e3:.0f} ms -> {big[stage]*1e3:.0f} ms)"
        )
    for stage in ("tables_s", "serialize_s"):
        rate = big["bytes"] / (1 << 30) / big[stage]
        err(f"[profile_lz] host {stage[:-2]}: {rate:.2f} GiB/s")


if __name__ == "__main__":
    main()
