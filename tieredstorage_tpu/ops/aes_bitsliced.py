"""Bitsliced AES-256-CTR keystream: boolean circuit, no gathers.

The table-form cipher in ops/aes.py spends its time in per-byte 256-entry
gathers — the worst op class for a TPU vector unit. This module replaces
SubBytes with a programmatically derived composite-field boolean circuit
(GF(2^8) inverse computed in GF((2^4)^2), Satoh/Canright-style tower): the
whole cipher becomes XOR/AND on uint32 bitplanes packed 32 blocks per lane —
pure VPU work at full vector throughput.

Every matrix/tensor in the circuit is DERIVED here from the field definitions
(FIPS-197 polynomial 0x11B, GF(16) polynomial y^4+y+1) and validated against
the generated S-box table in tests — nothing is hand-transcribed.

Layout: state is uint32[16, 8, W] — byte position (FIPS column-major), bit
index (LSB first), and W packed words, word w bit j = block 32*w + j.
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
import numpy as np

from tieredstorage_tpu.ops import _preflight
from tieredstorage_tpu.ops.aes import SBOX, _NR, _SHIFT_ROWS, _gf8_mult, key_expansion

# ---------------------------------------------------------------------------
# Host-side derivation of the tower-field S-box circuit (numpy, cached)
# ---------------------------------------------------------------------------


def _gf16_mult(a: int, b: int) -> int:
    p = 0
    while b:
        if b & 1:
            p ^= a
        a <<= 1
        if a & 0x10:
            a ^= 0x13  # y^4 + y + 1
        b >>= 1
    return p


def _gf8_pow(a: int, n: int) -> int:
    r = 1
    while n:
        if n & 1:
            r = _gf8_mult(r, a)
        a = _gf8_mult(a, a)
        n >>= 1
    return r


@functools.cache
def _tower() -> dict:
    """Derive the GF(256) ≅ GF((2^4)^2) isomorphism and circuit constants."""
    # Generator of GF(256)*.
    g = next(
        c for c in range(2, 256)
        if len({_gf8_pow(c, i) for i in range(255)}) == 255
    )
    # The subfield GF(16) inside GF(256) is {0} ∪ {g^(17k)}; find an element u
    # with u^4 + u + 1 = 0 so GF(2)[y]/(y^4+y+1) maps y ↦ u.
    u = next(
        x
        for k in range(1, 15)
        for x in [_gf8_pow(g, 17 * k)]
        if _gf8_pow(x, 4) ^ x ^ 1 == 0
    )

    def embed16(v: int) -> int:
        """GF(16) element (bits over y) → GF(256) element (bits over x)."""
        out = 0
        for i in range(4):
            if (v >> i) & 1:
                out ^= _gf8_pow(u, i)
        return out

    # λ ∈ GF(16) such that t^2 + t + λ is irreducible over GF(16) and a root
    # V exists in GF(256): V^2 + V = embed(λ). Search both.
    lam, V = next(
        (l, v)
        for l in range(1, 16)
        for v in range(1, 256)
        if _gf8_mult(v, v) ^ v == embed16(l)
        # irreducibility over GF(16): no root w in GF(16)
        and all(_gf16_mult(w, w) ^ w ^ l != 0 for w in range(16))
    )

    # Basis of GF(256) over GF(2): b ⊕ a·V with a,b ∈ GF(16) on basis u^i.
    # M maps composite coords (b0..b3, a0..a3) → AES bits.
    M = np.zeros((8, 8), dtype=np.uint8)
    for i in range(4):
        col_b = embed16(1 << i)
        col_a = _gf8_mult(embed16(1 << i), V)
        for bit in range(8):
            M[bit, i] = (col_b >> bit) & 1
            M[bit, 4 + i] = (col_a >> bit) & 1
    Minv = _gf2_inv(M)

    # AES affine layer bits: S(x) = Aff(inv(x)); Aff(v)_i = v_i ^ v_{i+4} ^
    # v_{i+5} ^ v_{i+6} ^ v_{i+7} ^ const_i (FIPS-197 §5.1.1).
    A = np.zeros((8, 8), dtype=np.uint8)
    for i in range(8):
        for j in (0, 4, 5, 6, 7):
            A[i, (i + j) % 8] ^= 1

    # Fold: input linear = Minv (AES bits → composite), output linear = A @ M
    # (composite → AES bits then affine), constant 0x63.
    lin_in = Minv % 2
    lin_out = (A @ M) % 2

    # GF(16) multiply tensor: out_k = XOR_{i,j} T[k,i,j] u_i v_j.
    T = np.zeros((4, 4, 4), dtype=np.uint8)
    for i in range(4):
        for j in range(4):
            prod = _gf16_mult(1 << i, 1 << j)
            for k in range(4):
                T[k, i, j] = (prod >> k) & 1

    # x ↦ λ·x² over GF(16): linear (Frobenius + scale), as a 4×4 bit matrix.
    SqLam = np.zeros((4, 4), dtype=np.uint8)
    for i in range(4):
        v = _gf16_mult(lam, _gf16_mult(1 << i, 1 << i))
        for k in range(4):
            SqLam[k, i] = (v >> k) & 1

    # GF(16) inverse as algebraic normal form (Möbius transform of the truth
    # table): inv_anf[k] = set of monomial masks whose XOR gives bit k.
    inv_table = [0] + [next(y for y in range(16) if _gf16_mult(x, y) == 1)
                       for x in range(1, 16)]
    inv_anf: list[list[int]] = []
    for k in range(4):
        f = [(inv_table[x] >> k) & 1 for x in range(16)]
        coeff = list(f)
        for i in range(4):  # Möbius transform over the 4-cube
            for mask in range(16):
                if mask & (1 << i):
                    coeff[mask] ^= coeff[mask ^ (1 << i)]
        inv_anf.append([m for m in range(16) if coeff[m]])

    return {
        "lin_in": lin_in,
        "lin_out": lin_out,
        "const": 0x63,
        "mult": T,
        "sq_lam": SqLam,
        "inv_anf": inv_anf,
    }


def _gf2_inv(m: np.ndarray) -> np.ndarray:
    n = m.shape[0]
    aug = np.concatenate([m.copy() % 2, np.eye(n, dtype=np.uint8)], axis=1)
    for col in range(n):
        pivot = next(r for r in range(col, n) if aug[r, col])
        if pivot != col:
            aug[[col, pivot]] = aug[[pivot, col]]
        for r in range(n):
            if r != col and aug[r, col]:
                aug[r] ^= aug[col]
    return aug[:, n:]


# ---------------------------------------------------------------------------
# Device-side circuit on uint32 bitplanes
# ---------------------------------------------------------------------------


def _linear4(mat: np.ndarray, bits: list[jnp.ndarray]) -> list[jnp.ndarray]:
    """Apply a GF(2) matrix (rows = outputs) to a list of planes via XORs."""
    out = []
    for row in mat:
        terms = [bits[i] for i in range(len(bits)) if row[i]]
        acc = terms[0]
        for t in terms[1:]:
            acc = acc ^ t
        out.append(acc)
    return out


def _gf16_mul_planes(t: np.ndarray, u: list, v: list) -> list:
    prods = {}
    out = []
    for k in range(4):
        acc = None
        for i in range(4):
            for j in range(4):
                if t[k, i, j]:
                    if (i, j) not in prods:
                        prods[(i, j)] = u[i] & v[j]
                    acc = prods[(i, j)] if acc is None else acc ^ prods[(i, j)]
        out.append(acc)
    return out


def _gf16_inv_planes(anf: list[list[int]], x: list) -> list:
    ones = jnp.full_like(x[0], 0xFFFFFFFF)
    monomials: dict[int, jnp.ndarray] = {0: ones}
    for m in range(1, 16):
        low = m & (-m)
        rest = m ^ low
        if rest == 0:
            monomials[m] = x[low.bit_length() - 1]
    for m in range(1, 16):
        if m not in monomials:
            low = m & (-m)
            monomials[m] = monomials[m ^ low] & monomials[low]
    out = []
    for k in range(4):
        acc = None
        for m in anf[k]:
            acc = monomials[m] if acc is None else acc ^ monomials[m]
        out.append(acc if acc is not None else jnp.zeros_like(x[0]))
    return out


def _sbox_planes(tw: dict, bits: list[jnp.ndarray]) -> list[jnp.ndarray]:
    """S-box over 8 bitplanes (any shape) via the tower circuit."""
    comp = _linear4(tw["lin_in"], bits)  # (b0..b3, a0..a3)
    b, a = comp[:4], comp[4:]
    # Δ = λa² ⊕ ab ⊕ b²  (b² is linear: square then no scale → use sq with λ=1)
    a_sq_lam = _linear4(tw["sq_lam"], a)
    ab = _gf16_mul_planes(tw["mult"], a, b)
    b_sq = _linear4(_sq_matrix(), b)
    delta = [a_sq_lam[i] ^ ab[i] ^ b_sq[i] for i in range(4)]
    dinv = _gf16_inv_planes(tw["inv_anf"], delta)
    a_out = _gf16_mul_planes(tw["mult"], a, dinv)
    apb = [a[i] ^ b[i] for i in range(4)]
    b_out = _gf16_mul_planes(tw["mult"], apb, dinv)
    res = _linear4(tw["lin_out"], b_out + a_out)
    const = tw["const"]
    return [
        # ~x, not x ^ jnp.uint32(-1): a scalar-const XOR materializes an
        # i32[] constant per call site, and the Pallas TPU lowering rejects
        # kernels that capture constants (~300 of them across 14 rounds —
        # seen on the real chip, round 5); bitwise NOT lowers constant-free.
        ~res[i] if (const >> i) & 1 else res[i]
        for i in range(8)
    ]


@functools.cache
def _sq_matrix() -> np.ndarray:
    m = np.zeros((4, 4), dtype=np.uint8)
    for i in range(4):
        v = _gf16_mult(1 << i, 1 << i)
        for k in range(4):
            m[k, i] = (v >> k) & 1
    return m


def _shift_rows_planes(state: jnp.ndarray) -> jnp.ndarray:
    return state[np.asarray(_SHIFT_ROWS)]


def _mix_columns_planes(state: jnp.ndarray) -> jnp.ndarray:
    """state uint32[16, 8, ...]; GF(2^8) xtime on bitplanes is a bit rotate
    with conditional feedback of bit 7 into bits {0,1,3,4} (poly 0x11B)."""
    s = state.reshape((4, 4) + state.shape[1:])  # [col, row, bit, ...]

    def xtime(x):
        top = x[:, :, 7]
        shifted = jnp.concatenate(
            [jnp.zeros_like(x[:, :, :1]), x[:, :, :-1]], axis=2
        )
        fb = jnp.zeros_like(shifted)
        for k in (0, 1, 3, 4):
            fb = fb.at[:, :, k].set(top)
        return shifted ^ fb

    rot1 = jnp.roll(s, -1, axis=1)
    rot2 = jnp.roll(s, -2, axis=1)
    rot3 = jnp.roll(s, -3, axis=1)
    out = xtime(s) ^ xtime(rot1) ^ rot1 ^ rot2 ^ rot3
    return out.reshape(state.shape)


def round_key_planes(round_keys: np.ndarray) -> np.ndarray:
    """uint8[15,16] round keys → uint32[15,16,8] full-word bit masks."""
    bits = (round_keys[..., None] >> np.arange(8)) & 1
    return (bits.astype(np.uint32) * 0xFFFFFFFF).astype(np.uint32)


def aes_encrypt_planes(rk_planes: jnp.ndarray, state: jnp.ndarray) -> jnp.ndarray:
    """Encrypt a bitsliced state uint32[16, 8, W] with AES-256.

    TSTPU_AES_SCAN=1 wraps the 13 middle rounds in a lax.scan: the traced
    graph shrinks ~14x (one round body instead of an unrolled cipher) at
    identical per-byte math, which shortens the XLA form's compile."""
    tw = _tower()
    state = state ^ rk_planes[0][..., None]

    def round_body(state, rk):
        planes = [state[:, b] for b in range(8)]
        planes = _sbox_planes(tw, planes)
        state = jnp.stack(planes, axis=1)
        state = _shift_rows_planes(state)
        state = _mix_columns_planes(state)
        return state ^ rk[..., None]

    if os.environ.get("TSTPU_AES_SCAN") == "1":
        state, _ = jax.lax.scan(
            lambda s, rk: (round_body(s, rk), None), state, rk_planes[1:_NR]
        )
    else:
        for rnd in range(1, _NR):
            state = round_body(state, rk_planes[rnd])
    planes = _sbox_planes(tw, [state[:, b] for b in range(8)])
    state = jnp.stack(planes, axis=1)
    state = _shift_rows_planes(state)
    return state ^ rk_planes[_NR][..., None]


def ctr_keystream_bitsliced(
    rk_planes: jnp.ndarray, iv: jnp.ndarray, first_counter: int, n_blocks: int
) -> jnp.ndarray:
    """Keystream uint8[n_blocks, 16] via the bitsliced cipher.

    n_blocks is rounded up to a multiple of 32 internally; callers slice.
    """
    w = (n_blocks + 31) // 32
    total = w * 32
    # Counter bytes 12..15 (big-endian); bit j of word w' ← block 32w'+j.
    n = first_counter + jnp.arange(total, dtype=jnp.uint32).reshape(w, 32)
    weights = (jnp.uint32(1) << jnp.arange(32, dtype=jnp.uint32))[None, :]
    ctr_planes = []
    for byte_i, shift in enumerate((24, 16, 8, 0)):
        byte_v = (n >> shift) & 0xFF
        planes = []
        for b in range(8):
            bit = (byte_v >> b) & 1
            planes.append(jnp.sum(bit * weights, axis=1, dtype=jnp.uint32))
        ctr_planes.append(jnp.stack(planes))  # [8, w]
    # IV bytes 0..11: constant across blocks → full-word masks.
    iv_bits = ((iv.astype(jnp.uint32)[:, None] >> jnp.arange(8)[None, :]) & 1)
    iv_planes = (iv_bits * jnp.uint32(0xFFFFFFFF)).astype(jnp.uint32)  # [12, 8]
    state = jnp.concatenate(
        [
            jnp.broadcast_to(iv_planes[:, :, None], (12, 8, w)),
            jnp.stack(ctr_planes),  # [4, 8, w]
        ],
        axis=0,
    )  # [16, 8, w]
    out = aes_encrypt_planes(rk_planes, state)
    # Unpack: byte[pos, block 32w'+j] = Σ_b ((plane[pos,b,w'] >> j) & 1) << b
    j = jnp.arange(32, dtype=jnp.uint32)[None, None, None, :]
    bits = (out[..., None] >> j) & 1  # [16, 8, w, 32]
    weights_b = (jnp.uint32(1) << jnp.arange(8, dtype=jnp.uint32))[None, :, None, None]
    bytes_ = jnp.sum(bits * weights_b, axis=1, dtype=jnp.uint32)  # [16, w, 32]
    ks = bytes_.transpose(1, 2, 0).reshape(total, 16).astype(jnp.uint8)
    return ks[:n_blocks]


def make_rk_planes(key: bytes) -> np.ndarray:
    return round_key_planes(key_expansion(key))


def rk_planes_from_round_keys(round_keys: jnp.ndarray) -> jnp.ndarray:
    """uint8[15,16] → uint32[15,16,8] masks, traceable (tiny; runs under jit)."""
    bits = (round_keys[..., None].astype(jnp.uint32) >> jnp.arange(8)) & 1
    return bits * jnp.uint32(0xFFFFFFFF)


_PALLAS_PREFLIGHT: list[bool] = []  # memoized PASS: the kernel lowers+runs here


def _preflight_attempt() -> bool:
    from tieredstorage_tpu.ops.aes_pallas import (
        WORDS_PER_STEP,
        aes_encrypt_planes_pallas,
    )

    # Runs eagerly: run_preflight gives the attempt a thread of its own,
    # outside the caller's trace (ops/_preflight.py).
    rk = rk_planes_from_round_keys(jnp.asarray(key_expansion(bytes(range(32)))))
    state = jnp.zeros((16, 8, WORDS_PER_STEP), jnp.uint32)
    out = jax.block_until_ready(aes_encrypt_planes_pallas(rk, state))
    # All input words are identical (zero), so EVERY output word must equal
    # the XLA circuit's — a lane/tile-indexing bug anywhere in the step must
    # fail the gate, not just word 0.
    ref = jax.block_until_ready(jax.jit(aes_encrypt_planes)(rk, state[:, :, :1]))
    return bool(jnp.all(out == ref))


def _pallas_preflight_ok() -> bool:
    """Compile and run the fused kernel once on a minimal tile, cross-checked
    against the XLA circuit. A Mosaic lowering or runtime failure, or a
    divergence, raises `KernelPreflightError` (ops/_preflight.py): the jit
    cache pins the first trace's program per shape, so the XLA circuit must
    never take the kernel's place in silence."""
    return _preflight.run_preflight(
        _PALLAS_PREFLIGHT, _preflight_attempt, "Pallas AES kernel"
    )


_FORCED_CROSSCHECK: list[bool] = []  # memoized forced-path verdict


def _forced_crosscheck_ok() -> bool:
    """Output cross-check for the TIEREDSTORAGE_TPU_PALLAS=1 forced path.

    The forced path bypasses the preflight gate, so the import-time range
    check on TSTPU_AES_R used to be the ONLY guard — and a range-valid but
    behaviorally mistiled kernel (or a future tiling regression) would
    corrupt keystream silently. This runs the kernel BODY once per process
    with plain-array stand-ins (aes_pallas.kernel_body_reference — the
    R-dependent ShiftRows un-stack slicing included, no Mosaic needed, so
    it is cheap even on CPU where the forced path runs interpreted) against
    the XLA circuit on a position-distinct input, and fails LOUD on
    divergence: the caller explicitly forced the kernel, so silently
    falling back would mask exactly the corruption being guarded against."""
    if _FORCED_CROSSCHECK:
        if not _FORCED_CROSSCHECK[0]:
            raise RuntimeError(
                "Pallas AES kernel output diverges from the XLA circuit for "
                f"TSTPU_AES_R; refusing the forced TIEREDSTORAGE_TPU_PALLAS=1 "
                "path (keystream would be corrupted)"
            )
        return True
    from tieredstorage_tpu.ops import aes_pallas

    with jax.ensure_compile_time_eval():
        rk = rk_planes_from_round_keys(jnp.asarray(key_expansion(bytes(range(32)))))
        w = aes_pallas.WORDS_PER_STEP
        # Position-distinct, word-distinct input: a wrong un-stack slice
        # cannot alias to the right answer the way an all-zero state could.
        state = (
            jnp.arange(16 * 8 * w, dtype=jnp.uint32).reshape(16, 8, w)
            * jnp.uint32(2654435761)
        )
        got = jax.block_until_ready(aes_pallas.kernel_body_reference(rk, state))
        ref = jax.block_until_ready(aes_encrypt_planes(rk, state))
        ok = bool(jnp.all(got == ref))
    _FORCED_CROSSCHECK.append(ok)
    return _forced_crosscheck_ok()


def pallas_aes_available() -> bool:
    """Platform half of the kernel gate: can (or must) the kernel run here?

    CPU (tests, virtual meshes) keeps the XLA path — Mosaic interpret mode
    is orders slower to compile there. TIEREDSTORAGE_TPU_PALLAS=0/1
    overrides, but is read at trace time: set it before the first call for
    a given (batch, chunk) shape, or the cached executable wins. First TPU
    use preflights the kernel on a minimal tile and raises if Mosaic can't
    lower or run it there, or if its output diverges."""
    forced = os.environ.get("TIEREDSTORAGE_TPU_PALLAS")
    if forced is not None:
        if forced in ("0", "false", "off"):
            return False
        # The forced path skips the preflight, so it must run the output
        # cross-check itself — a mistiled TSTPU_AES_R fails loud here
        # instead of corrupting keystream silently.
        return _forced_crosscheck_ok()
    return _preflight.on_tpu() and _pallas_preflight_ok()


def _use_pallas_circuit(n_words: int) -> bool:
    """Route the cipher through the fused Pallas kernel on real TPUs.

    The XLA lowering of the circuit round-trips every gate through HBM
    (0.66 GiB/s measured, PROFILE.md); the Pallas kernel keeps the planes
    in VMEM. Split gate: `aes_pallas.use_pallas_aes` is the pure-host shape
    eligibility (asserted on CPU by bench/CI), `pallas_aes_available` the
    platform/preflight half. A forced TIEREDSTORAGE_TPU_PALLAS=1 overrides
    the shape floor too — probes dispatch tiny tiles on purpose."""
    from tieredstorage_tpu.ops.aes_pallas import use_pallas_aes

    if os.environ.get("TIEREDSTORAGE_TPU_PALLAS") is not None:
        return pallas_aes_available()
    return use_pallas_aes(n_words) and pallas_aes_available()


def _ctr_state(ivs: jnp.ndarray, first_counter: int, w: int) -> jnp.ndarray:
    """Counter-block planes uint32[B, 16, 8, w]: row b's IV bytes as
    full-word masks, then the big-endian counters first_counter.. packed 32
    blocks a word, the same for every row."""
    batch = ivs.shape[0]
    total = w * 32
    # Counter planes are identical for every chunk: [4 bytes, 8 bits, w].
    n = first_counter + jnp.arange(total, dtype=jnp.uint32).reshape(w, 32)
    weights = (jnp.uint32(1) << jnp.arange(32, dtype=jnp.uint32))[None, :]
    ctr_planes = []
    for shift in (24, 16, 8, 0):
        byte_v = (n >> shift) & 0xFF
        planes = [
            jnp.sum(((byte_v >> b) & 1) * weights, axis=1, dtype=jnp.uint32)
            for b in range(8)
        ]
        ctr_planes.append(jnp.stack(planes))
    ctr = jnp.stack(ctr_planes)  # [4, 8, w]
    # IV planes per chunk: [B, 12, 8] masks broadcast over the chunk's words.
    iv_bits = (ivs.astype(jnp.uint32)[..., None] >> jnp.arange(8)) & 1
    iv_planes = iv_bits * jnp.uint32(0xFFFFFFFF)  # [B, 12, 8]
    return jnp.concatenate(
        [
            jnp.broadcast_to(iv_planes[..., None], (batch, 12, 8, w)),
            jnp.broadcast_to(ctr[None], (batch, 4, 8, w)),
        ],
        axis=1,
    )  # [B, 16, 8, w]


def _keystream_bytes(out: jnp.ndarray, n_blocks: int) -> jnp.ndarray:
    """Cipher output planes uint32[16, 8, B, w] -> keystream uint8[B, n_blocks, 16]."""
    _, _, batch, w = out.shape
    j = jnp.arange(32, dtype=jnp.uint32)
    bits = (out[..., None] >> j) & 1  # [16, 8, B, w, 32]
    weights_b = (jnp.uint32(1) << jnp.arange(8, dtype=jnp.uint32))[
        None, :, None, None, None
    ]
    bytes_ = jnp.sum(bits * weights_b, axis=1, dtype=jnp.uint32)  # [16, B, w, 32]
    ks = bytes_.transpose(1, 2, 3, 0).reshape(batch, w * 32, 16).astype(jnp.uint8)
    return ks[:, :n_blocks]


def ctr_keystream_batch(
    round_keys: jnp.ndarray, ivs: jnp.ndarray, first_counter: int, n_blocks: int
) -> jnp.ndarray:
    """Keystream uint8[B, n_blocks, 16] for a batch of per-chunk IVs.

    One bitsliced cipher evaluation covers the whole batch: each chunk's
    blocks are packed into its own span of words (n_blocks rounded up to a
    multiple of 32), with that chunk's IV planes broadcast across its span.
    Replaces the vmapped per-chunk table cipher (gather-bound) with pure
    XOR/AND on uint32 lanes. On TPU the boolean circuit itself runs as the
    fused Pallas kernel (ops/aes_pallas.py)."""
    rk_planes = rk_planes_from_round_keys(round_keys)
    batch = ivs.shape[0]
    w = (n_blocks + 31) // 32
    state = _ctr_state(ivs, first_counter, w)
    # Fold batch into the word axis: [16, 8, B*w].
    state = state.transpose(1, 2, 0, 3).reshape(16, 8, batch * w)
    n_words = batch * w
    if _use_pallas_circuit(n_words):
        from tieredstorage_tpu.ops.aes_pallas import aes_encrypt_planes_pallas

        # interpret off-TPU lets the forced path run (slowly) anywhere.
        # The op pads W to its own grid internally.
        out = aes_encrypt_planes_pallas(
            rk_planes, state, interpret=_preflight.interpret_off_device()
        )
    else:
        out = aes_encrypt_planes(rk_planes, state)
    # Unpack to bytes: [16, 8, B, w] → [B, w*32, 16].
    return _keystream_bytes(out.reshape(16, 8, batch, w), n_blocks)


def ctr_keystream_keyed(
    round_key_table: jnp.ndarray,
    row_keys: jnp.ndarray,
    ivs: jnp.ndarray,
    first_counter: int,
    n_blocks: int,
) -> jnp.ndarray:
    """`ctr_keystream_batch` for rows under different keys: round_key_table
    uint8[slots, 15, 16], row_keys int32[B] the slot of each row.

    On the kernel path every row's span of words is padded to whole grid
    steps, so each step lies inside one row and the kernel takes the
    step -> slot map (`aes_pallas.aes_encrypt_planes_keyed_pallas`); the
    pad is under one step a row (a 4 MiB row: 8193 words in 9 steps of
    1024). Off the kernel the XLA circuit runs once per row under that
    row's round keys."""
    from tieredstorage_tpu.ops import aes_pallas

    batch = ivs.shape[0]
    w = (n_blocks + 31) // 32
    table = rk_planes_from_round_keys(round_key_table)  # [slots, 15, 16, 8]
    if _use_pallas_circuit(batch * w):
        steps_per_row = -(-w // aes_pallas.WORDS_PER_STEP)
        w = steps_per_row * aes_pallas.WORDS_PER_STEP
        state = _ctr_state(ivs, first_counter, w)
        state = state.transpose(1, 2, 0, 3).reshape(16, 8, batch * w)
        out = aes_pallas.aes_encrypt_planes_keyed_pallas(
            table,
            jnp.repeat(row_keys.astype(jnp.int32), steps_per_row),
            state,
            interpret=_preflight.interpret_off_device(),
        ).reshape(16, 8, batch, w)
    else:
        state = _ctr_state(ivs, first_counter, w)
        out = jax.vmap(aes_encrypt_planes)(table[row_keys], state)  # [B, 16, 8, w]
        out = out.transpose(1, 2, 0, 3)
    return _keystream_bytes(out, n_blocks)
