"""AES-256: host-side key schedule + vectorized device-side cipher (JAX).

Replaces the JDK AES-GCM intrinsics the reference's EncryptionChunkEnumeration
leans on (core/.../transform/EncryptionChunkEnumeration.java): here the block
cipher is applied to ALL counter blocks of a whole chunk batch at once.

The S-box and round constants are generated programmatically from the field
definition (FIPS-197 math, not copied tables) and validated against FIPS/NIST
vectors in tests. The device cipher is the table form (SubBytes via gather,
MixColumns via GF(2^8) doubling in uint8 arithmetic); a bitsliced variant can
replace it behind the same function signature if gather throughput on the
target chip warrants it.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


# --- GF(2^8) groundwork (host) ---

def _gf8_mult(a: int, b: int) -> int:
    p = 0
    while b:
        if b & 1:
            p ^= a
        a <<= 1
        if a & 0x100:
            a ^= 0x11B  # x^8 + x^4 + x^3 + x + 1
        b >>= 1
    return p


@functools.cache
def _sbox() -> np.ndarray:
    inv = [0] * 256
    for x in range(1, 256):
        # Multiplicative inverse by exponentiation: x^254.
        y = 1
        for _ in range(254):
            y = _gf8_mult(y, x)
        inv[x] = y
    table = np.zeros(256, dtype=np.uint8)
    for x in range(256):
        v = inv[x]
        b = 0
        for i in range(8):
            bit = (
                (v >> i) ^ (v >> ((i + 4) % 8)) ^ (v >> ((i + 5) % 8))
                ^ (v >> ((i + 6) % 8)) ^ (v >> ((i + 7) % 8)) ^ (0x63 >> i)
            ) & 1
            b |= bit << i
        table[x] = b
    return table


@functools.cache
def _inv_sbox() -> np.ndarray:
    s = _sbox()
    inv = np.zeros(256, dtype=np.uint8)
    inv[s] = np.arange(256, dtype=np.uint8)
    return inv


SBOX = _sbox()
INV_SBOX = _inv_sbox()

_NR = 14  # rounds for AES-256

# ShiftRows permutation over the 16-byte state in FIPS column-major layout:
# byte index = 4*col + row; row r rotates left by r columns.
_SHIFT_ROWS = np.array(
    [4 * ((c + r) % 4) + r for c in range(4) for r in range(4)], dtype=np.int32
)
_INV_SHIFT_ROWS = np.argsort(_SHIFT_ROWS).astype(np.int32)


def key_expansion(key: bytes) -> np.ndarray:
    """AES-256 key schedule -> uint8[15, 16] round keys (host, FIPS-197 §5.2)."""
    if len(key) != 32:
        raise ValueError("AES-256 key must be 32 bytes")
    nk = 8
    words = [list(key[4 * i : 4 * i + 4]) for i in range(nk)]
    rcon = 1
    sbox = _sbox()
    for i in range(nk, 4 * (_NR + 1)):
        temp = list(words[i - 1])
        if i % nk == 0:
            temp = temp[1:] + temp[:1]
            temp = [int(sbox[t]) for t in temp]
            temp[0] ^= rcon
            rcon = _gf8_mult(rcon, 2)
        elif i % nk == 4:
            temp = [int(sbox[t]) for t in temp]
        words.append([a ^ b for a, b in zip(words[i - nk], temp)])
    flat = np.array(words, dtype=np.uint8).reshape(_NR + 1, 16)
    return flat


def aes_encrypt_block_host(round_keys: np.ndarray, block: np.ndarray) -> np.ndarray:
    """One block's FIPS-197 encryption in numpy (uint8[16] in and out), for
    the single block a key's set-up needs (GCM's H = E_K(0^128)) without a
    device program; `aes_encrypt_blocks` is the same cipher on the device."""
    state = block ^ round_keys[0]
    for rnd in range(1, _NR + 1):
        state = SBOX[state][_SHIFT_ROWS]
        if rnd < _NR:  # MixColumns: out_r = 2*s_r ^ 3*s_{r+1} ^ s_{r+2} ^ s_{r+3}
            s = state.reshape(4, 4)  # [col, row]
            rot1 = np.roll(s, -1, axis=1)
            state = (
                _xtime(s) ^ _xtime(rot1) ^ rot1 ^ np.roll(s, -2, axis=1) ^ np.roll(s, -3, axis=1)
            ).reshape(16)
        state = state ^ round_keys[rnd]
    return state


# --- device-side cipher ---

def _xtime(x):
    """GF(2^8) doubling on uint8 arrays (jax or numpy)."""
    return ((x << 1) & 0xFF) ^ ((x >> 7) * 0x1B)


def _mix_columns(state: jnp.ndarray) -> jnp.ndarray:
    """state: uint8[..., 16] in column-major layout; mix each 4-byte column."""
    s = state.reshape(state.shape[:-1] + (4, 4))  # [..., col, row]
    rot1 = jnp.roll(s, -1, axis=-1)
    rot2 = jnp.roll(s, -2, axis=-1)
    rot3 = jnp.roll(s, -3, axis=-1)
    # out_r = 2*s_r ^ 3*s_{r+1} ^ s_{r+2} ^ s_{r+3}
    out = _xtime(s) ^ (_xtime(rot1) ^ rot1) ^ rot2 ^ rot3
    return out.reshape(state.shape)


def _inv_mix_columns(state: jnp.ndarray) -> jnp.ndarray:
    s = state.reshape(state.shape[:-1] + (4, 4))
    x2 = _xtime(s)
    x4 = _xtime(x2)
    x8 = _xtime(x4)
    m9 = x8 ^ s
    m11 = x8 ^ x2 ^ s
    m13 = x8 ^ x4 ^ s
    m14 = x8 ^ x4 ^ x2
    out = (
        m14
        ^ jnp.roll(m11, -1, axis=-1)
        ^ jnp.roll(m13, -2, axis=-1)
        ^ jnp.roll(m9, -3, axis=-1)
    )
    return out.reshape(state.shape)


def aes_encrypt_blocks(round_keys: jnp.ndarray, blocks: jnp.ndarray) -> jnp.ndarray:
    """Encrypt uint8[..., 16] blocks; round_keys uint8[15,16]."""
    sbox = jnp.asarray(SBOX)
    shift = jnp.asarray(_SHIFT_ROWS)
    state = blocks ^ round_keys[0]
    for rnd in range(1, _NR):
        state = jnp.take(sbox, state.astype(jnp.int32), axis=0)
        state = jnp.take(state, shift, axis=-1)
        state = _mix_columns(state)
        state = state ^ round_keys[rnd]
    state = jnp.take(sbox, state.astype(jnp.int32), axis=0)
    state = jnp.take(state, shift, axis=-1)
    return state ^ round_keys[_NR]


def aes_decrypt_blocks(round_keys: jnp.ndarray, blocks: jnp.ndarray) -> jnp.ndarray:
    """Inverse cipher (unused by CTR mode; provided for completeness/tests)."""
    inv_sbox = jnp.asarray(INV_SBOX)
    inv_shift = jnp.asarray(_INV_SHIFT_ROWS)
    state = blocks ^ round_keys[_NR]
    for rnd in range(_NR - 1, 0, -1):
        state = jnp.take(state, inv_shift, axis=-1)
        state = jnp.take(inv_sbox, state.astype(jnp.int32), axis=0)
        state = state ^ round_keys[rnd]
        state = _inv_mix_columns(state)
    state = jnp.take(state, inv_shift, axis=-1)
    state = jnp.take(inv_sbox, state.astype(jnp.int32), axis=0)
    return state ^ round_keys[0]


def ctr_keystream(
    round_keys: jnp.ndarray, iv: jnp.ndarray, first_counter: int, n_blocks: int
) -> jnp.ndarray:
    """Keystream blocks uint8[n_blocks, 16] for a 12-byte IV.

    Counter block = IV || big-endian32(first_counter + i). GCM encrypts data
    with counters starting at 2 (J0 = IV||1 is reserved for the tag mask).
    """
    counters = jnp.arange(first_counter, first_counter + n_blocks, dtype=jnp.uint32)
    ctr_bytes = (
        counters[:, None] >> jnp.array([24, 16, 8, 0], dtype=jnp.uint32)[None, :]
    ).astype(jnp.uint8)
    iv_rep = jnp.broadcast_to(iv.astype(jnp.uint8), (n_blocks, 12))
    blocks = jnp.concatenate([iv_rep, ctr_bytes], axis=1)
    return aes_encrypt_blocks(round_keys, blocks)
