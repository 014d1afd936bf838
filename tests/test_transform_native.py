"""Native (C++) transform backend: wire compatibility vs the CPU oracle.

Mirrors the reference's TransformsEndToEndTest round-trip matrix (SURVEY §4)
for the native backend, plus cross-backend wire checks: bytes produced by the
native backend must detransform through the CPU backend and vice versa.
Skips when the native library can't build (no g++/zstd/libcrypto).
"""

from __future__ import annotations

import secrets

import numpy as np
import pytest

from tieredstorage_tpu import native
from tieredstorage_tpu.security.aes import AesEncryptionProvider
from tieredstorage_tpu.transform.api import (
    AuthenticationError,
    DetransformOptions,
    TransformOptions,
)
from tieredstorage_tpu.transform.cpu import CpuTransformBackend

pytestmark = pytest.mark.skipif(
    not native.available(), reason="native transform library unavailable"
)

CHUNK = 8192


@pytest.fixture(scope="module")
def backend():
    from tieredstorage_tpu.transform.native_backend import NativeTransformBackend

    return NativeTransformBackend()


@pytest.fixture(scope="module")
def keyaad():
    return AesEncryptionProvider().create_data_key_and_aad()


def chunks_of(data: bytes, size: int = CHUNK) -> list[bytes]:
    return [data[i : i + size] for i in range(0, len(data), size)]


@pytest.mark.parametrize("compression", [False, True])
@pytest.mark.parametrize("encryption", [False, True])
def test_round_trip(backend, keyaad, compression, encryption):
    rng = np.random.default_rng(7)
    # Half compressible, half noise, chunk-unaligned tail.
    data = (b"log-record " * 3000) + rng.integers(0, 256, 40961, np.uint8).tobytes()
    chunks = chunks_of(data)
    opts = TransformOptions(
        compression=compression,
        encryption=keyaad if encryption else None,
    )
    transformed = backend.transform(chunks, opts)
    dopts = DetransformOptions(
        compression=compression, encryption=keyaad if encryption else None
    )
    assert backend.detransform(transformed, dopts) == chunks


@pytest.mark.parametrize("compression", [False, True])
def test_wire_compatible_with_cpu_backend(backend, keyaad, compression):
    cpu = CpuTransformBackend()
    data = b"interchangeable bytes " * 4000
    chunks = chunks_of(data)
    ivs = [secrets.token_bytes(12) for _ in chunks]
    opts = TransformOptions(compression=compression, encryption=keyaad, ivs=ivs)
    dopts = DetransformOptions(compression=compression, encryption=keyaad)

    native_out = backend.transform(chunks, opts)
    cpu_out = cpu.transform(chunks, opts)
    # Same IVs + same zstd level ⇒ byte-identical wire output.
    assert native_out == cpu_out
    # And each detransforms through the other.
    assert cpu.detransform(native_out, dopts) == chunks
    assert backend.detransform(cpu_out, dopts) == chunks


def test_tamper_detection(backend, keyaad):
    chunks = [b"a" * CHUNK, b"b" * CHUNK]
    out = backend.transform(chunks, TransformOptions(encryption=keyaad))
    bad = [out[0], out[1][:-1] + bytes([out[1][-1] ^ 0x80])]
    with pytest.raises(AuthenticationError):
        backend.detransform(bad, DetransformOptions(encryption=keyaad))


def test_empty_and_tiny_chunks(backend, keyaad):
    chunks = [b"", b"x", b"yz"]
    opts = TransformOptions(compression=True, encryption=keyaad)
    dopts = DetransformOptions(compression=True, encryption=keyaad)
    assert backend.detransform(backend.transform(chunks, opts), dopts) == chunks


def test_large_batch_threads(backend, keyaad):
    rng = np.random.default_rng(11)
    chunks = [rng.integers(0, 256, CHUNK, np.uint8).tobytes() for _ in range(64)]
    opts = TransformOptions(compression=True, encryption=keyaad)
    dopts = DetransformOptions(compression=True, encryption=keyaad)
    assert backend.detransform(backend.transform(chunks, opts), dopts) == chunks


# --- zstd_compress_into: each chunk compressed where it lies, into the caller's buffer


def _log_like(rng, size: int) -> bytes:
    """Half record scaffolding, half noise: frames of about 3/4 the source."""
    out = np.empty(size, np.uint8)
    out[0::2] = rng.integers(0, 256, (size + 1) // 2, dtype=np.uint8)
    out[1::2] = np.resize(np.frombuffer(b"offset=%019d key=" % 7, np.uint8), size // 2)
    return out.tobytes()


def _into_case(name: str) -> list:
    rng = np.random.default_rng(36)
    if name == "sixteen_full":
        return [_log_like(rng, CHUNK) for _ in range(16)]
    if name == "ragged_last":
        return [_log_like(rng, CHUNK) for _ in range(3)] + [_log_like(rng, 517)]
    if name == "one_chunk":
        return [_log_like(rng, CHUNK)]
    if name == "empty_chunk":
        return [_log_like(rng, CHUNK), b"", _log_like(rng, 9)]
    if name == "other_buffers":
        a, b, c = (_log_like(rng, CHUNK) for _ in range(3))
        return [memoryview(a), bytearray(b), c, memoryview(bytearray(a))[100:900]]
    raise AssertionError(name)


INTO_CASES = ["sixteen_full", "ragged_last", "one_chunk", "empty_chunk", "other_buffers"]


@pytest.mark.parametrize("level", [1, 3])
@pytest.mark.parametrize("case", INTO_CASES)
def test_compress_into_frames_are_zstandards_and_views_of_the_buffer(case, level):
    zstandard = pytest.importorskip("zstandard")
    chunks = _into_case(case)
    out = np.full((len(chunks) + 1, native.zstd_bound(CHUNK) + 5), 0xAB, np.uint8)
    frames = native.zstd_compress_into(chunks, out, level=level)
    one_shot = zstandard.ZstdCompressor(level=level, write_content_size=True)
    assert len(frames) == len(chunks)
    for i, (frame, chunk) in enumerate(zip(frames, chunks)):
        assert bytes(frame) == one_shot.compress(bytes(chunk))
        assert zstandard.ZstdDecompressor().decompress(bytes(frame)) == bytes(chunk)
        # row i of the caller's array, from its first byte, and nothing else's
        assert np.shares_memory(frame, out[i])
        assert frame.ctypes.data == out[i].ctypes.data
        assert not any(np.shares_memory(frame, out[j]) for j in range(len(out)) if j != i)
    assert (out[len(chunks)] == 0xAB).all()  # the row past the last chunk is untouched
    # a second batch into the same buffer overwrites the first's frames in place
    again = native.zstd_compress_into(chunks[::-1], out, level=level)
    assert [bytes(f) for f in again] == [one_shot.compress(bytes(c)) for c in chunks[::-1]]
    assert native.zstd_decompress_batch([bytes(f) for f in again], CHUNK) == [
        bytes(c) for c in chunks[::-1]
    ]


@pytest.mark.parametrize(
    "shape_of, dtype, error",
    [
        (lambda bound: (4, bound - 1), np.uint8, "need at least"),  # a byte under the bound
        (lambda bound: (3, bound), np.uint8, "need at least"),  # a row short
        (lambda bound: (4, bound), np.uint16, "uint8"),
        (lambda bound: (4 * bound,), np.uint8, "uint8"),  # flat: the shape names the stride
    ],
    ids=["stride_under_bound", "too_few_rows", "not_bytes", "flat"],
)
def test_compress_into_refuses_a_buffer_before_any_write(shape_of, dtype, error):
    chunks = _into_case("ragged_last")
    out = np.full(shape_of(native.zstd_bound(CHUNK)), 0xAB, dtype)
    with pytest.raises(ValueError, match=error):
        native.zstd_compress_into(chunks, out)
    assert (out == 0xAB).all()


def test_compress_into_refuses_a_strided_or_read_only_buffer():
    chunks = _into_case("one_chunk")
    wide = np.zeros((2, 2 << 14), np.uint8)
    with pytest.raises(ValueError, match="contiguous"):
        native.zstd_compress_into(chunks, wide[:, ::2])
    wide.flags.writeable = False
    with pytest.raises(ValueError, match="writeable"):
        native.zstd_compress_into(chunks, wide)
    assert not wide.any()


@pytest.mark.parametrize("case", INTO_CASES)
def test_compress_batch_keeps_its_signature_and_returns_owned_bytes(case):
    zstandard = pytest.importorskip("zstandard")
    chunks = _into_case(case)
    frames = native.zstd_compress_batch(chunks, level=3, n_threads=2)
    assert all(type(f) is bytes for f in frames)
    one_shot = zstandard.ZstdCompressor(level=3, write_content_size=True)
    assert frames == [one_shot.compress(bytes(c)) for c in chunks]
    # owned: a later batch leaves them as they were
    native.zstd_compress_batch([b"\x00" * CHUNK] * len(chunks))
    assert frames == [one_shot.compress(bytes(c)) for c in chunks]
    assert native.zstd_compress_batch([]) == [] and native.zstd_compress_into(
        [], np.empty((0, 0), np.uint8)
    ) == []
