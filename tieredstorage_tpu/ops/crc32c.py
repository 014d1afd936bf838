"""CRC32C (Castagnoli) as a GF(2) linear-map tree on the MXU.

CRC with init=0/xorout=0 ("crc0") is linear over GF(2) in the message bits,
so per-16-byte-block contributions are a 32x128 bit matrix, and combining a
left span with a right span of k bytes is `Z^k(left) ^ right` where Z is the
32x32 zero-byte state-evolution matrix. A log-tree with per-level matrices
(Z^(16*2^j), squared host-side) reduces a whole chunk batch with int8 matmuls
mod 2 — the same machinery as the GHASH kernel (ops/gcm.py).

The standard CRC32C (init 0xFFFFFFFF, xorout 0xFFFFFFFF) is recovered with a
length-dependent affine offset: crc(M) = crc0(M) ^ crc(0^len), the latter
computed host-side in O(log len) matrix powers. Used for integrity accounting
of transformed chunks (the reference has no integrity checksum of its own —
it relies on the object stores' checksums; this is an extension that the
manifest can carry per chunk).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

_POLY_REFLECTED = 0x82F63B78


def crc32c_reference(data: bytes, init: int = 0xFFFFFFFF, xorout: int = 0xFFFFFFFF) -> int:
    """Bitwise software CRC32C (host oracle)."""
    crc = init
    for byte in data:
        crc ^= byte
        for _ in range(8):
            crc = (crc >> 1) ^ (_POLY_REFLECTED if crc & 1 else 0)
    return crc ^ xorout


def _crc0(data: bytes) -> int:
    return crc32c_reference(data, init=0, xorout=0)


_HOST_TABLE: list | None = None


def crc32c_host(data: bytes) -> int:
    """Table-driven host CRC32C — the fast path for host-side framing (the
    e2e broker sim's v2 record batches use it; Kafka's batch CRC is CRC32C).
    The bitwise `crc32c_reference` above stays the independent oracle."""
    global _HOST_TABLE
    if _HOST_TABLE is None:
        table = []
        for i in range(256):
            crc = i
            for _ in range(8):
                crc = (crc >> 1) ^ (_POLY_REFLECTED if crc & 1 else 0)
            table.append(crc)
        _HOST_TABLE = table
    crc = 0xFFFFFFFF
    table = _HOST_TABLE
    for byte in data:
        crc = table[(crc ^ byte) & 0xFF] ^ (crc >> 8)
    return crc ^ 0xFFFFFFFF


def _bits32(v: int) -> np.ndarray:
    return np.frombuffer(v.to_bytes(4, "big"), dtype=np.uint8)[:, None] >> np.arange(
        7, -1, -1, dtype=np.uint8
    ) & 1


def _bits32_vec(v: int) -> np.ndarray:
    return _bits32(v).reshape(32).astype(np.uint8)


def _vec32_to_int(bits: np.ndarray) -> int:
    packed = np.packbits(bits.astype(np.uint8).reshape(4, 8), axis=1, bitorder="big")
    return int.from_bytes(packed.tobytes(), "big")


@functools.cache
def _leaf_matrix() -> np.ndarray:
    """uint8[32,128]: bits32(crc0(block)) = L @ bits(block), MSB-first bits."""
    m = np.zeros((32, 128), dtype=np.uint8)
    for bit in range(128):
        block = bytearray(16)
        block[bit // 8] = 0x80 >> (bit % 8)
        m[:, bit] = _bits32_vec(_crc0(bytes(block)))
    return m


@functools.cache
def _zero_byte_matrix() -> np.ndarray:
    """uint8[32,32]: state evolution over ONE zero byte."""
    m = np.zeros((32, 32), dtype=np.uint8)
    for bit in range(32):
        # Column for basis state e_bit (MSB-first indexing of the uint32),
        # evolved through one zero byte with the bitwise step.
        crc_val = 1 << (31 - bit)
        for _ in range(8):
            crc_val = (crc_val >> 1) ^ (_POLY_REFLECTED if crc_val & 1 else 0)
        m[:, bit] = _bits32_vec(crc_val)
    return m


def _mat_mod2(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    return (a.astype(np.int64) @ b.astype(np.int64) % 2).astype(np.uint8)


def _mat_pow(m: np.ndarray, e: int) -> np.ndarray:
    result = np.eye(m.shape[0], dtype=np.uint8)
    base = m
    while e:
        if e & 1:
            result = _mat_mod2(result, base)
        base = _mat_mod2(base, base)
        e >>= 1
    return result


@functools.cache
def _level_matrices(levels: int) -> np.ndarray:
    """int8[levels,32,32] transposed: level j combines spans of 16*2^j bytes."""
    z16 = _mat_pow(_zero_byte_matrix(), 16)
    mats = np.zeros((levels, 32, 32), dtype=np.int8)
    m = z16
    for j in range(levels):
        mats[j] = m.T.astype(np.int8)
        m = _mat_mod2(m, m)
    return mats


@functools.cache
def _length_offset(length: int) -> int:
    """crc32c of `length` zero bytes, via matrix powers (O(log n))."""
    state = _mat_pow(_zero_byte_matrix(), length) @ _bits32_vec(0xFFFFFFFF) % 2
    return _vec32_to_int(state) ^ 0xFFFFFFFF


# numpy, not jnp: a module-level device array would initialize the JAX
# backend at import time.
_BIT_SHIFTS = np.arange(7, -1, -1, dtype=np.uint8)


@functools.partial(jax.jit, static_argnames=("chunk_bytes", "levels"))
def _crc0_batch(data: jnp.ndarray, leaf_t: jnp.ndarray, level_mats: jnp.ndarray,
                *, chunk_bytes: int, levels: int) -> jnp.ndarray:
    batch = data.shape[0]
    n_blocks = chunk_bytes // 16
    blocks = data.reshape(batch, n_blocks, 16)
    bits = ((blocks[..., None] >> _BIT_SHIFTS) & 1).reshape(batch, n_blocks, 128)
    vals = (
        jax.lax.dot_general(
            bits.astype(jnp.int8), leaf_t, (((2,), (0,)), ((), ())),
            preferred_element_type=jnp.int32,
        )
        & 1
    ).astype(jnp.uint8)  # [batch, n_blocks, 32]
    # Left-pad to a power of two with zero states (crc0 of zero bytes = 0,
    # and prepending zero bytes to the left span is the identity here
    # because Z^k(0) = 0).
    m_pow2 = 1 << levels
    if m_pow2 > n_blocks:
        vals = jnp.concatenate(
            [jnp.zeros((batch, m_pow2 - n_blocks, 32), jnp.uint8), vals], axis=1
        )
    for j in range(levels):
        pairs = vals.reshape(batch, -1, 2, 32)
        left, right = pairs[:, :, 0, :], pairs[:, :, 1, :]
        shifted = (
            jax.lax.dot_general(
                left.astype(jnp.int8), level_mats[j], (((2,), (0,)), ((), ())),
                preferred_element_type=jnp.int32,
            )
            & 1
        ).astype(jnp.uint8)
        vals = shifted ^ right
    return vals[:, 0, :]  # [batch, 32] bit vectors


def crc32c_constants(chunk_bytes: int):
    """Host-precomputed constants for `crc32c_chunks_device` at a chunk size."""
    if chunk_bytes % 16:
        raise ValueError("chunk_bytes must be a multiple of 16")
    n_blocks = chunk_bytes // 16
    levels = max(1, (n_blocks - 1).bit_length())
    return (
        jnp.asarray(_leaf_matrix().T.astype(np.int8)),
        jnp.asarray(_level_matrices(levels)),
        chunk_bytes,
        levels,
        np.uint32(_length_offset(chunk_bytes)),
    )


def crc32c_chunks_device(data, leaf_t, level_mats, chunk_bytes, levels, length_offset):
    """Device-resident CRC32C: uint8[batch, chunk_bytes] -> uint32[batch].

    Composable under an outer jit/shard_map (unlike `crc32c_chunks`, which
    round-trips through numpy on the host).
    """
    bits = _crc0_batch(data, leaf_t, level_mats, chunk_bytes=chunk_bytes, levels=levels)
    weights = jnp.asarray((1 << np.arange(31, -1, -1)).astype(np.uint32))
    vals = jnp.sum(bits.astype(jnp.uint32) * weights, axis=1)
    return vals ^ jnp.uint32(length_offset)


#: Below this many total bytes in a same-length group, the jit dispatch costs
#: more than the table loop; the host path takes over.
_BATCH_MIN_BYTES = 1 << 16


def crc32c_batch(chunks) -> list[int]:
    """CRC32C of each chunk in a heterogeneous batch (the scrubber's verify
    primitive).

    Same-length groups are LEFT-zero-padded to a 16-byte multiple and reduced
    through the MXU log-tree in one `crc32c_chunks` call — left padding is
    free for the math (crc0(0^k || M) = crc0(M), since Z^k(0) = 0 and the
    zero prefix contributes nothing), so only the length-offset term needs
    swapping: crc(M) = kernel(0^k||M) ^ crc(0^lenP) ^ crc(0^lenM). Small
    groups fall back to the table-driven host CRC, so CPU-only deployments
    (and tiny scrub batches) never pay a device dispatch.
    """
    chunks = list(chunks)
    out: list[Optional[int]] = [None] * len(chunks)
    groups: dict[int, list[int]] = {}
    for i, c in enumerate(chunks):
        groups.setdefault(len(c), []).append(i)
    for length, idxs in groups.items():
        if length == 0:
            for i in idxs:
                out[i] = 0  # crc32c(b"") == 0
            continue
        padded = -(-length // 16) * 16
        if length * len(idxs) < _BATCH_MIN_BYTES:
            for i in idxs:
                out[i] = crc32c_host(chunks[i])
            continue
        mat = np.zeros((len(idxs), padded), dtype=np.uint8)
        for row, i in enumerate(idxs):
            mat[row, padded - length:] = np.frombuffer(chunks[i], dtype=np.uint8)
        crcs = crc32c_chunks(mat)
        fix = 0 if padded == length else (
            _length_offset(padded) ^ _length_offset(length)
        )
        for row, i in enumerate(idxs):
            out[i] = int(crcs[row]) ^ fix
    return out  # type: ignore[return-value]


def crc32c_chunks(data: np.ndarray) -> np.ndarray:
    """uint32[batch] CRC32C of each row of uint8[batch, chunk_bytes].

    chunk_bytes must currently be a multiple of 16 (transformed chunks are
    padded by the caller; arbitrary tails fold host-side if needed).
    """
    data = np.asarray(data, dtype=np.uint8)
    batch, chunk_bytes = data.shape
    if chunk_bytes % 16:
        raise ValueError("chunk_bytes must be a multiple of 16")
    n_blocks = chunk_bytes // 16
    levels = max(1, (n_blocks - 1).bit_length())
    bits = _crc0_batch(
        jnp.asarray(data),
        jnp.asarray(_leaf_matrix().T.astype(np.int8)),
        jnp.asarray(_level_matrices(levels)),
        chunk_bytes=chunk_bytes,
        levels=levels,
    )
    bits = np.asarray(bits)
    weights = (1 << np.arange(31, -1, -1, dtype=np.uint64)).astype(np.uint64)
    crc0_vals = (bits.astype(np.uint64) * weights).sum(axis=1).astype(np.uint64)
    # crc(M) = crc0(M) ^ crc(0^len); crc(0^len) already includes init+xorout.
    return (crc0_vals ^ np.uint64(_length_offset(chunk_bytes))).astype(np.uint32)
