"""Pallas TPU kernel for the bitsliced AES-256 boolean circuit.

The XLA lowering of the ~2000-gate tower-field circuit (ops/aes_bitsliced.py)
round-trips every gate's uint32[16, 8, W] operand through HBM — measured at
0.66 GiB/s of keystream on a v5e (PROFILE.md). This kernel evaluates the whole
circuit per 512 KiB tile inside VMEM: the 128 bit-planes live as (R, 128)
uint32 vregs, ShiftRows is pure Python-level variable relabeling at trace
time, MixColumns is relabeling plus XORs, and only the initial/final state
touches HBM (2 bytes moved per keystream byte).

Wiring notes (replaces the reference's per-chunk JDK `AES/GCM/NoPadding`
cipher, core/.../transform/EncryptionChunkEnumeration.java:66-81):
- SubBytes reuses the derived tower-field circuit (`_sbox_planes`), applied
  once per round on all 16 byte positions stacked along sublanes (16R, 128).
- MixColumns per column: out[r] = xtime(a ^ c) ^ a ^ (a^c^d^e), with xtime a
  bit-index rotation feeding bit 7 into bits {0,1,3,4} (poly 0x11B) — all
  relabeling + XOR, no data movement.
- Round keys are uint32 full-word masks in SMEM, XORed in as scalars.
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from tieredstorage_tpu.ops.aes import _NR, _SHIFT_ROWS
from tieredstorage_tpu.ops.aes_bitsliced import _sbox_planes, _tower

#: Sublane rows per plane per grid step: one (8, 128) uint32 vreg per plane,
#: i.e. 1024 words = 32768 blocks = 512 KiB of keystream per step.
#: TSTPU_AES_R overrides for on-chip tile sweeps (tools/probe_min.py):
#: larger R = more words per vector op and fewer grid steps, at the price
#: of R/8 vregs live per plane.


def _validated_r(raw: str) -> int:
    """The ShiftRows un-stack slices the (16R, 128) sublane stack at R-row
    boundaries; an R that isn't a power-of-two multiple of 8 mis-tiles those
    slices. Fail loud at import; the TIEREDSTORAGE_TPU_PALLAS=1 forced path
    (which skips the preflight) additionally runs a behavioral output
    cross-check of the kernel body at first use
    (aes_bitsliced._forced_crosscheck_ok), so even a range-valid but
    mistiled kernel cannot corrupt keystream silently."""
    try:
        r = int(raw)
    except ValueError as e:
        raise ValueError(f"TSTPU_AES_R={raw!r} is not an integer") from e
    if r < 8 or r > 256 or r & (r - 1):
        raise ValueError(
            f"TSTPU_AES_R={raw!r} must be a power of two in [8, 256] "
            "(sublane tiling of the ShiftRows un-stack)"
        )
    return r


R = _validated_r(os.environ.get("TSTPU_AES_R", "8"))
WORDS_PER_STEP = R * 128


def use_pallas_aes(n_words: int) -> bool:
    """Shape eligibility for the fused circuit kernel — pure host logic, no
    platform probe, so benchmarks and CPU-only CI can assert that the
    production window shapes tile onto the kernel (the platform half lives
    in `aes_bitsliced.pallas_aes_available`).

    `aes_encrypt_planes_pallas` zero-pads W to the WORDS_PER_STEP grid
    internally, so eligibility is only a worth-it floor: at least 1024
    words (512 KiB of keystream — below that the XLA circuit wins on
    launch overhead) and at least half a grid step (so padding never more
    than doubles the dispatched compute under a TSTPU_AES_R override)."""
    return n_words >= 1024 and 2 * n_words >= WORDS_PER_STEP


def _xtime_planes(x: list) -> list:
    """GF(2^8) multiply-by-x on 8 bit-planes (LSB-first bit index)."""
    return [
        x[7],
        x[0] ^ x[7],
        x[1],
        x[2] ^ x[7],
        x[3] ^ x[7],
        x[4],
        x[5],
        x[6],
    ]


def _mix_columns_vars(st: list) -> list:
    """MixColumns over 16 position-vars of 8 planes each (pos = col*4 + row)."""
    out = [None] * 16
    for col in range(4):
        idx = [col * 4 + r for r in range(4)]
        all4 = [
            st[idx[0]][b] ^ st[idx[1]][b] ^ st[idx[2]][b] ^ st[idx[3]][b]
            for b in range(8)
        ]
        for r in range(4):
            a = st[idx[r]]
            c = st[idx[(r + 1) % 4]]
            xt = _xtime_planes([a[b] ^ c[b] for b in range(8)])
            out[idx[r]] = [xt[b] ^ a[b] ^ all4[b] for b in range(8)]
    return out


def _aes_kernel(rk_ref, in_ref, out_ref):
    """rk_ref: SMEM uint32[15, 128] round-key masks ([rnd, pos*8 + bit]);
    in_ref/out_ref: VMEM uint32[16, 8, R, 128] plane tiles."""
    _aes_rounds(lambda rnd, i: rk_ref[rnd, i], in_ref, out_ref)


def _aes_keyed_kernel(step_key_ref, rk_ref, in_ref, out_ref):
    """The same circuit under the grid step's own key: step_key_ref is the
    scalar-prefetched SMEM int32[steps] step -> key-slot map, rk_ref SMEM
    uint32[slots * 15, 128] the launch's stacked round-key masks."""
    base = step_key_ref[pl.program_id(0)] * (_NR + 1)
    _aes_rounds(lambda rnd, i: rk_ref[base + rnd, i], in_ref, out_ref)


def _aes_rounds(rk, in_ref, out_ref):
    """The 14 rounds over one plane tile; ``rk(rnd, i)`` is the scalar mask
    of round ``rnd``, position-bit ``i``."""
    tw = _tower()
    st = [
        [in_ref[p, b] ^ rk(0, p * 8 + b) for b in range(8)] for p in range(16)
    ]
    for rnd in range(1, _NR + 1):
        # SubBytes: all 16 positions stacked along sublanes, one circuit pass.
        big = [
            jnp.concatenate([st[p][b] for p in range(16)], axis=0) for b in range(8)
        ]
        big = _sbox_planes(tw, big)
        # Un-stack with ShiftRows fused into the slice index.
        st = [
            [
                jax.lax.slice_in_dim(
                    big[b], _SHIFT_ROWS[p] * R, (_SHIFT_ROWS[p] + 1) * R, axis=0
                )
                for b in range(8)
            ]
            for p in range(16)
        ]
        if rnd != _NR:
            st = _mix_columns_vars(st)
        st = [
            [st[p][b] ^ rk(rnd, p * 8 + b) for b in range(8)] for p in range(16)
        ]
    for p in range(16):
        for b in range(8):
            out_ref[p, b] = st[p][b]


class _ArrayRef:
    """Read-only stand-in for a Pallas ref backed by a plain array."""

    def __init__(self, arr):
        self._arr = arr

    def __getitem__(self, idx):
        return self._arr[idx]


class _CollectRef:
    """Write-only stand-in collecting kernel outputs."""

    def __init__(self):
        self.out = {}

    def __setitem__(self, idx, val):
        self.out[idx] = val


def kernel_body_reference(rk_planes: jnp.ndarray, state: jnp.ndarray) -> jnp.ndarray:
    """Evaluate `_aes_kernel` for ONE grid step with plain-array stand-ins
    for the refs — identical math (including the R-dependent ShiftRows
    un-stack slicing), no Mosaic or interpreter in the loop, ~1 s eager on
    CPU. This is what the forced-path `TSTPU_AES_R` output cross-check and
    the kernel-body tests both run: any mis-tiling of the (16R, 128)
    sublane stack shows up here exactly as it would on device.

    rk_planes: uint32[15, 16, 8] masks; state: uint32[16, 8, WORDS_PER_STEP].
    """
    out_ref = _CollectRef()
    _aes_kernel(
        _ArrayRef(rk_planes.reshape(_NR + 1, 128)),
        _ArrayRef(state.reshape(16, 8, R, 128)),
        out_ref,
    )
    rows = [
        jnp.stack([out_ref.out[(p, b)] for b in range(8)], axis=0)
        for p in range(16)
    ]
    return jnp.stack(rows, axis=0).reshape(16, 8, state.shape[2])


@functools.partial(jax.jit, static_argnames=("interpret",))
def aes_encrypt_planes_pallas(
    rk_planes: jnp.ndarray, state: jnp.ndarray, *, interpret: bool = False
) -> jnp.ndarray:
    """Encrypt a bitsliced state uint32[16, 8, W] with AES-256 in one kernel.

    Drop-in for `aes_bitsliced.aes_encrypt_planes`; W is zero-padded to the
    WORDS_PER_STEP grid INSIDE the op and the result sliced back, so callers
    dispatch production window shapes as-is. `interpret=True` runs the
    kernel op-by-op on CPU for tests."""
    w = state.shape[2]
    if w <= 0:
        raise ValueError("W must be positive")
    padded = -(-w // WORDS_PER_STEP) * WORDS_PER_STEP
    if padded != w:
        state = jnp.pad(state, ((0, 0), (0, 0), (0, padded - w)))
    steps = padded // WORDS_PER_STEP
    st4 = state.reshape(16, 8, steps * R, 128)
    rk = rk_planes.reshape(_NR + 1, 128)
    out = pl.pallas_call(
        _aes_kernel,
        grid=(steps,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((16, 8, R, 128), lambda s: (0, 0, s, 0)),
        ],
        out_specs=pl.BlockSpec((16, 8, R, 128), lambda s: (0, 0, s, 0)),
        out_shape=jax.ShapeDtypeStruct((16, 8, steps * R, 128), jnp.uint32),
        interpret=interpret,
    )(rk, st4)
    return out.reshape(16, 8, padded)[:, :, :w]


@functools.partial(jax.jit, static_argnames=("interpret",))
def aes_encrypt_planes_keyed_pallas(
    rk_table: jnp.ndarray,
    step_keys: jnp.ndarray,
    state: jnp.ndarray,
    *,
    interpret: bool = False,
) -> jnp.ndarray:
    """Encrypt a bitsliced state uint32[16, 8, steps * WORDS_PER_STEP] in one
    kernel whose grid steps may each run under a different key:
    rk_table uint32[slots, 15, 16, 8] round-key masks, step_keys int32[steps]
    the slot of each step. The caller lays every row's words out in whole
    steps (a row's span padded to WORDS_PER_STEP), so a key never changes
    inside a tile; the map is scalar-prefetched and selects the round keys
    from SMEM, so the vector work is `aes_encrypt_planes_pallas`'s."""
    w = state.shape[2]
    if w <= 0 or w % WORDS_PER_STEP:
        raise ValueError(f"W={w} is not a positive multiple of {WORDS_PER_STEP}")
    steps = w // WORDS_PER_STEP
    if step_keys.shape != (steps,):
        raise ValueError(f"step_keys {step_keys.shape} do not match {steps} steps")
    st4 = state.reshape(16, 8, steps * R, 128)
    rk = rk_table.reshape(-1, 128)
    tile = pl.BlockSpec((16, 8, R, 128), lambda s, keys: (0, 0, s, 0))
    out = pl.pallas_call(
        _aes_keyed_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(steps,),
            in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM), tile],
            out_specs=tile,
        ),
        out_shape=jax.ShapeDtypeStruct((16, 8, steps * R, 128), jnp.uint32),
        interpret=interpret,
    )(step_keys.astype(jnp.int32), rk, st4)
    return out.reshape(16, 8, w)
