"""tpu-huff-v1 under shard_map: the device codec must shard over the data
mesh the same way the GCM transform does (SURVEY.md §7 step 5 — chunk rows
sharded across chips, per-chunk transformed sizes all-gathered to build the
chunk index). Runs on the virtual 8-device CPU mesh (tests/conftest.py)."""

from __future__ import annotations

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from tieredstorage_tpu.ops.huffman import encode_batch  # noqa: E402
from tieredstorage_tpu.parallel.mesh import (  # noqa: E402
    DATA_AXIS,
    data_mesh,
)
from tieredstorage_tpu.transform.thuff import (  # noqa: E402
    assemble_frame,
    compress_batch,
    decompress_batch,
    encode_tables,
    limited_huffman_lengths,
)


def _make_rows(batch: int, n_max: int, rng) -> tuple[np.ndarray, ...]:
    """Per-row data + canonical tables, host-built as compress_batch does."""
    data = np.zeros((batch, n_max), np.uint8)
    n_sym = np.zeros(batch, np.int32)
    lengths = np.zeros((batch, 256), np.int32)
    codes_rev = np.zeros((batch, 256), np.int32)
    for row in range(batch):
        n = int(rng.integers(n_max // 2, n_max + 1))
        # Skewed symbol distribution so Huffman actually compresses.
        arr = rng.integers(0, 256, n, dtype=np.uint8) % rng.integers(3, 40)
        data[row, :n] = arr
        n_sym[row] = n
        lens = limited_huffman_lengths(np.bincount(arr, minlength=256))
        lengths[row] = lens
        codes_rev[row] = encode_tables(lens)
    return data, n_sym, codes_rev, lengths


def _mesh_encode(mesh, data, n_sym, codes_rev, lengths, *, n_max, gather_sizes):
    """Run encode_batch under shard_map over row-sharded inputs; optionally
    all_gather the per-row bit counts (the chunk-index size collective)."""

    def shard_step(d, n, c, l):
        words, total_bits, jump = encode_batch(d, n, c, l, n_max=n_max)
        if not gather_sizes:
            return words, total_bits, jump
        all_bits = jax.lax.all_gather(total_bits, DATA_AXIS, tiled=True)
        return words, total_bits, jump, all_bits

    row, row2 = P(DATA_AXIS), P(DATA_AXIS, None)
    out_specs = (row2, row, row2) + ((P(None),) if gather_sizes else ())
    step = jax.jit(
        jax.shard_map(
            shard_step,
            mesh=mesh,
            in_specs=(row2, row, row2, row2),
            out_specs=out_specs,
            check_vma=False,
        )
    )
    args = [
        jax.device_put(a, NamedSharding(mesh, s))
        for a, s in zip((data, n_sym, codes_rev, lengths), (row2, row, row2, row2))
    ]
    return step(*args)


def test_sharded_encode_matches_single_device_and_gathers_sizes():
    mesh = data_mesh(8)
    n_max = 4096
    batch = 16  # 2 rows per device
    rng = np.random.default_rng(7)
    data, n_sym, codes_rev, lengths = _make_rows(batch, n_max, rng)
    words_s, bits_s, jump_s, all_bits = _mesh_encode(
        mesh, data, n_sym, codes_rev, lengths, n_max=n_max, gather_sizes=True
    )

    words_1, bits_1, jump_1 = encode_batch(
        jnp.asarray(data), jnp.asarray(n_sym), jnp.asarray(codes_rev),
        jnp.asarray(lengths), n_max=n_max,
    )
    np.testing.assert_array_equal(np.asarray(words_s), np.asarray(words_1))
    np.testing.assert_array_equal(np.asarray(bits_s), np.asarray(bits_1))
    np.testing.assert_array_equal(np.asarray(jump_s), np.asarray(jump_1))
    # The gathered size vector is replicated and matches the per-shard bits.
    np.testing.assert_array_equal(np.asarray(all_bits), np.asarray(bits_1))


def test_sharded_frames_round_trip_through_the_codec():
    # Frames assembled from MESH-computed outputs must decode with the
    # standard (single-device) decompress path — proving chips can encode
    # independently while any host reads the result.
    mesh = data_mesh(8)
    n_max = 4096
    batch = 16
    rng = np.random.default_rng(21)
    data, n_sym, codes_rev, lengths = _make_rows(batch, n_max, rng)
    words, total_bits, jump = (
        np.asarray(x)
        for x in _mesh_encode(
            mesh, data, n_sym, codes_rev, lengths, n_max=n_max, gather_sizes=False
        )
    )

    chunks = [data[r, : n_sym[r]].tobytes() for r in range(batch)]
    frames = [
        assemble_frame(chunks[r], lengths[r], jump[r], words[r], int(total_bits[r]))
        for r in range(batch)
    ]
    assert decompress_batch(frames) == chunks
    assert sum(len(f) for f in frames) < sum(len(c) for c in chunks)
    # The reference single-device path produces byte-identical frames.
    assert frames == compress_batch(chunks)
