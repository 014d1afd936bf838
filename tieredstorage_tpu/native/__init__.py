"""ctypes bindings for the native host transform library.

The reference's hot per-chunk loop bottoms out in native code it links
against (zstd-jni, JDK AES-GCM intrinsics — SURVEY §2.2). This package is
the TPU build's equivalent: `native/transform_host.cpp` compiled to
libtransform_host.so (lazily, with the in-tree Makefile) and driven in
batches — one Python↔C crossing per chunk window, C++ thread-pool
parallelism inside.
"""

from __future__ import annotations

import ctypes
import os
import subprocess
from pathlib import Path
from typing import Optional

import numpy as np
from tieredstorage_tpu.utils.locks import new_lock

_REPO_ROOT = Path(__file__).resolve().parent.parent.parent
_NATIVE_DIR = _REPO_ROOT / "native"
_SO_PATH = _NATIVE_DIR / "libtransform_host.so"

_lock = new_lock("native._lock")
_lib: Optional[ctypes.CDLL] = None
_load_error: Optional[str] = None

IV_SIZE = 12
TAG_SIZE = 16

_u64p = ctypes.POINTER(ctypes.c_uint64)
_u8p = ctypes.POINTER(ctypes.c_uint8)


def _build() -> None:
    subprocess.run(
        ["make", "-s"],
        cwd=_NATIVE_DIR,
        check=True,
        capture_output=True,
        text=True,
    )


def _bind(lib: ctypes.CDLL) -> ctypes.CDLL:
    lib.ts_crypto_available.restype = ctypes.c_int
    lib.ts_zstd_bound.restype = ctypes.c_size_t
    lib.ts_zstd_bound.argtypes = [ctypes.c_size_t]
    lib.ts_zstd_compress_batch.restype = ctypes.c_int
    lib.ts_zstd_compress_batch.argtypes = [
        ctypes.POINTER(ctypes.c_char_p), _u64p, ctypes.c_int,  # in, sizes, n
        ctypes.c_int, _u8p, ctypes.c_uint64, _u64p, ctypes.c_int,
    ]
    lib.ts_zstd_decompress_batch.restype = ctypes.c_int
    lib.ts_zstd_decompress_batch.argtypes = [
        _u8p, _u64p, _u64p, ctypes.c_int,  # in, offsets, sizes, n
        _u8p, ctypes.c_uint64, _u64p, ctypes.c_int,
    ]
    aes_common = [
        _u8p, _u8p, ctypes.c_uint64,  # key, aad, aad_len
    ]
    lib.ts_aes_gcm_encrypt_batch.restype = ctypes.c_int
    lib.ts_aes_gcm_encrypt_batch.argtypes = aes_common + [
        _u8p,  # ivs
        _u8p, _u64p, _u64p, ctypes.c_int,  # in, offsets, sizes, n
        _u8p, ctypes.c_uint64, _u64p, ctypes.c_int,  # out, stride, out_sizes, threads
    ]
    lib.ts_aes_gcm_decrypt_batch.restype = ctypes.c_int
    lib.ts_aes_gcm_decrypt_batch.argtypes = aes_common + [
        _u8p, _u64p, _u64p, ctypes.c_int,
        _u8p, ctypes.c_uint64, _u64p, ctypes.c_int,
    ]
    # Optional symbol: a prebuilt .so from before the LZ layer keeps working
    # (lz_expand then returns None and callers take the numpy path).
    try:
        lib.ts_lz_expand.restype = ctypes.c_int
        lib.ts_lz_expand.argtypes = [
            ctypes.POINTER(ctypes.c_uint16), ctypes.c_int,
            _u8p, ctypes.c_uint64, _u8p, ctypes.c_uint64,
        ]
    except AttributeError:
        pass
    return lib


def load() -> Optional[ctypes.CDLL]:
    """Load (building if needed) the native library; None when unavailable."""
    global _lib, _load_error
    with _lock:
        if _lib is not None:
            return _lib
        if _load_error is not None:
            return None
        try:
            source = _NATIVE_DIR / "transform_host.cpp"
            if not _SO_PATH.exists():
                _build()
            elif source.exists() and _SO_PATH.stat().st_mtime < source.stat().st_mtime:
                # Source newer than the .so → rebuild; a prebuilt .so with no
                # source alongside (installed tree) is used as-is.
                _build()
            _lib = _bind(ctypes.CDLL(str(_SO_PATH)))
            return _lib
        except (OSError, subprocess.CalledProcessError, AttributeError) as e:
            _load_error = str(e)
            return None


def load_error() -> Optional[str]:
    """Why the last `load()` returned None (None while it has not failed)."""
    return _load_error


def available() -> bool:
    lib = load()
    return lib is not None and lib.ts_crypto_available() == 1


def _pack(chunks: list[bytes]) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    sizes = np.array([len(c) for c in chunks], dtype=np.uint64)
    offsets = np.zeros(len(chunks), dtype=np.uint64)
    if len(chunks) > 1:
        offsets[1:] = np.cumsum(sizes[:-1])
    buf = np.frombuffer(b"".join(chunks), dtype=np.uint8) if chunks else np.zeros(0, np.uint8)
    return buf, offsets, sizes


def _as_u8p(arr: np.ndarray):
    return arr.ctypes.data_as(_u8p)


def _as_u64p(arr: np.ndarray):
    return arr.ctypes.data_as(_u64p)


class NativeTransformError(RuntimeError):
    pass


class NativeAuthenticationError(NativeTransformError):
    """GCM tag verification failed for at least one chunk."""


def zstd_bound(size: int) -> int:
    """The worst-case frame size of a chunk of `size` bytes: the row width a
    `zstd_compress_into` buffer needs."""
    lib = load()
    if lib is None:
        raise NativeTransformError(f"native library unavailable: {_load_error}")
    return int(lib.ts_zstd_bound(size))


def zstd_compress_into(
    chunks: list, out: np.ndarray, level: int = 3, n_threads: int = 0
) -> list[np.ndarray]:
    """Compress each chunk where it lies into the caller's `uint8[rows, stride]`
    array: chunk i's frame goes to row i, and the frames come back as views of
    `out`, alive only as long as the caller leaves the array alone. A `bytes`
    chunk is handed to the codec as it is, any other contiguous buffer through
    `np.frombuffer`; nothing is gathered and no frame is copied out. Refused
    before any write where a row could not hold the worst frame of the
    largest chunk (`ts_zstd_bound`)."""
    lib = load()
    if lib is None:
        raise NativeTransformError(f"native library unavailable: {_load_error}")
    n = len(chunks)
    flags = out.flags
    if out.dtype != np.uint8 or out.ndim != 2 or not (flags.c_contiguous and flags.writeable):
        raise ValueError("out must be a writeable C-contiguous uint8[rows, stride] array")
    if n == 0:
        return []
    sizes = np.array([len(c) for c in chunks], dtype=np.uint64)
    rows, stride = out.shape
    bound = int(lib.ts_zstd_bound(int(sizes.max())))
    if rows < n or stride < bound:
        raise ValueError(
            f"out is uint8[{rows}, {stride}]: {n} chunks of up to "
            f"{int(sizes.max())} bytes need at least uint8[{n}, {bound}]"
        )
    pointers = (ctypes.c_char_p * n)()
    held = []  # the arrays whose addresses `pointers` carries
    for i, chunk in enumerate(chunks):
        if isinstance(chunk, bytes):
            pointers[i] = chunk
        else:
            held.append(np.frombuffer(chunk, dtype=np.uint8))
            pointers[i] = held[-1].ctypes.data
    out_sizes = np.zeros(n, dtype=np.uint64)
    rc = lib.ts_zstd_compress_batch(
        pointers, _as_u64p(sizes), n,
        level, _as_u8p(out), stride, _as_u64p(out_sizes), n_threads,
    )
    if rc != 0:
        raise NativeTransformError(f"zstd compress failed on chunk {rc - 1}")
    return [out[i, : int(out_sizes[i])] for i in range(n)]


def zstd_compress_batch(chunks: list[bytes], level: int = 3, n_threads: int = 0) -> list[bytes]:
    """`zstd_compress_into` a buffer of its own, the frames as owned `bytes`."""
    if not chunks:
        return []
    out = np.empty((len(chunks), zstd_bound(max(len(c) for c in chunks))), dtype=np.uint8)
    return [bytes(view) for view in zstd_compress_into(chunks, out, level, n_threads)]


#: Absolute sanity ceiling on a single frame's declared content size, used
#: when the caller can't supply the configured chunk-size bound. chunk.size
#: is capped at INT_MAX/2 (config guard mirroring the reference's
#: RemoteStorageManagerConfig.java:126-127), so nothing legitimate exceeds it.
MAX_FRAME_CONTENT_SIZE = (1 << 31) // 2


def checked_frame_content_sizes(chunks, max_decompressed: Optional[int]) -> int:
    """Validate each zstd frame's self-declared content size BEFORE any
    allocation sized from it: a corrupted or malicious remote frame claiming
    a huge size would otherwise force an n_chunks * stride allocation.
    Returns the largest declared size (>= 1)."""
    import zstandard

    cap = max_decompressed if max_decompressed is not None else MAX_FRAME_CONTENT_SIZE
    largest = 1
    for i, c in enumerate(chunks):
        size = zstandard.frame_content_size(c)
        if size is None or size < 0:
            raise NativeTransformError(f"zstd frame {i} missing content size")
        if size > cap:
            raise NativeTransformError(
                f"zstd frame {i} claims {size} decompressed bytes, "
                f"over the limit of {cap}"
            )
        largest = max(largest, size)
    return largest


def zstd_decompress_batch(
    chunks: list[bytes], max_decompressed: Optional[int] = None, n_threads: int = 0
) -> list[bytes]:
    lib = load()
    if lib is None:
        raise NativeTransformError(f"native library unavailable: {_load_error}")
    if not chunks:
        return []
    # Size the output stride from the largest declared frame size, bounded
    # by the caller's chunk-size cap (or the absolute ceiling).
    max_decompressed = checked_frame_content_sizes(chunks, max_decompressed)
    buf, offsets, sizes = _pack(chunks)
    stride = max_decompressed
    out = np.empty(len(chunks) * stride, dtype=np.uint8)
    out_sizes = np.zeros(len(chunks), dtype=np.uint64)
    rc = lib.ts_zstd_decompress_batch(
        _as_u8p(buf), _as_u64p(offsets), _as_u64p(sizes), len(chunks),
        _as_u8p(out), stride, _as_u64p(out_sizes), n_threads,
    )
    if rc != 0:
        raise NativeTransformError(f"zstd decompress failed on chunk {rc - 1}")
    return [
        out[i * stride : i * stride + int(out_sizes[i])].tobytes()
        for i in range(len(chunks))
    ]


_AES_MAX = 0x7FFFFFFF  # EVP int length limit (2 GiB - 1)


def _check_aad(aad: bytes) -> None:
    if len(aad) > _AES_MAX:
        raise NativeTransformError("AAD exceeds the AES length limit")


def aes_gcm_encrypt_batch(
    key: bytes, aad: bytes, ivs: np.ndarray, chunks: list[bytes], n_threads: int = 0
) -> list[bytes]:
    lib = load()
    if lib is None or lib.ts_crypto_available() != 1:
        raise NativeTransformError("native AES unavailable")
    _check_aad(aad)
    if not chunks:
        return []
    buf, offsets, sizes = _pack(chunks)
    ivs = np.ascontiguousarray(ivs, dtype=np.uint8)
    if ivs.shape != (len(chunks), IV_SIZE):
        raise ValueError(f"ivs must be ({len(chunks)}, {IV_SIZE}), got {ivs.shape}")
    key_arr = np.frombuffer(key, dtype=np.uint8)
    aad_arr = np.frombuffer(aad, dtype=np.uint8) if aad else np.zeros(0, np.uint8)
    stride = int(sizes.max()) + IV_SIZE + TAG_SIZE
    out = np.empty(len(chunks) * stride, dtype=np.uint8)
    out_sizes = np.zeros(len(chunks), dtype=np.uint64)
    rc = lib.ts_aes_gcm_encrypt_batch(
        _as_u8p(key_arr), _as_u8p(aad_arr), len(aad),
        _as_u8p(ivs), _as_u8p(buf), _as_u64p(offsets), _as_u64p(sizes), len(chunks),
        _as_u8p(out), stride, _as_u64p(out_sizes), n_threads,
    )
    if rc == -1:
        raise NativeTransformError("native AES unavailable")
    if rc < -1:
        raise NativeTransformError(f"chunk {-rc - 2} exceeds the AES length limit")
    if rc != 0:
        raise NativeTransformError(f"AES-GCM encrypt failed on chunk {rc - 1}")
    return [
        out[i * stride : i * stride + int(out_sizes[i])].tobytes()
        for i in range(len(chunks))
    ]


def lz_expand(orig_len: int, seq_stream: bytes, lit_stream: bytes) -> Optional[bytes]:
    """Expand a tpu-lzhuff-v1 sequence stream (transform/lzhuff.py format).

    Returns None when the native library (or this symbol, for a prebuilt
    older .so) is unavailable — callers fall back to the numpy expander.
    Raises NativeTransformError on a malformed stream."""
    lib = load()
    if lib is None or not hasattr(lib, "ts_lz_expand"):
        return None
    seqs = np.frombuffer(seq_stream, dtype="<u2")
    if len(seqs) % 3:
        raise NativeTransformError("sequence stream not a multiple of 6 bytes")
    lits = (
        np.frombuffer(lit_stream, dtype=np.uint8)
        if lit_stream
        else np.zeros(0, np.uint8)
    )
    out = np.empty(max(orig_len, 1), dtype=np.uint8)
    rc = lib.ts_lz_expand(
        np.ascontiguousarray(seqs).ctypes.data_as(ctypes.POINTER(ctypes.c_uint16)),
        len(seqs) // 3,
        _as_u8p(lits),
        len(lits),
        _as_u8p(out),
        orig_len,
    )
    if rc != 0:
        reasons = {1: "literal overflow", 2: "match outside decoded prefix",
                   3: "totals mismatch"}
        raise NativeTransformError(
            f"LZ expand failed: {reasons.get(rc, f'code {rc}')}"
        )
    return out[:orig_len].tobytes()


def aes_gcm_decrypt_batch(
    key: bytes, aad: bytes, chunks: list[bytes], n_threads: int = 0
) -> list[bytes]:
    lib = load()
    if lib is None or lib.ts_crypto_available() != 1:
        raise NativeTransformError("native AES unavailable")
    _check_aad(aad)
    if not chunks:
        return []
    buf, offsets, sizes = _pack(chunks)
    key_arr = np.frombuffer(key, dtype=np.uint8)
    aad_arr = np.frombuffer(aad, dtype=np.uint8) if aad else np.zeros(0, np.uint8)
    stride = max(int(sizes.max()) - IV_SIZE - TAG_SIZE, 1)
    out = np.empty(len(chunks) * stride, dtype=np.uint8)
    out_sizes = np.zeros(len(chunks), dtype=np.uint64)
    rc = lib.ts_aes_gcm_decrypt_batch(
        _as_u8p(key_arr), _as_u8p(aad_arr), len(aad),
        _as_u8p(buf), _as_u64p(offsets), _as_u64p(sizes), len(chunks),
        _as_u8p(out), stride, _as_u64p(out_sizes), n_threads,
    )
    if rc == -1:
        raise NativeTransformError("native AES unavailable")
    if rc < -1:
        raise NativeTransformError(f"chunk {-rc - 2} exceeds the AES length limit")
    if rc != 0:
        raise NativeAuthenticationError(f"GCM tag mismatch on chunks [{rc - 1}]")
    return [
        out[i * stride : i * stride + int(out_sizes[i])].tobytes()
        for i in range(len(chunks))
    ]
