"""Batched AES-256-GCM over whole chunk arrays (the TPU transform hot path).

One call encrypts/decrypts uint8[batch, chunk_bytes] with per-chunk IVs and a
shared key+AAD (the per-segment DEK+AAD of the security layer), producing the
same bytes as the host AES-GCM oracle:

- CTR keystream: the block cipher (ops/aes.py) runs over all counter blocks
  of the whole batch at once; counter 1 yields the tag mask E(J0), counters
  2.. encrypt the data (NIST SP 800-38D).
- GHASH: a grouped-power reduction where each level contracts 128 blocks at
  once via one [B*G, 128*128] x [128*128, 128] GF(2) bit-matrix matmul on the
  MXU (slot j carries H^(127-j); ops/gf128.py builds the stacked operands) —
  log128(m) big matmuls instead of log2(m) pairwise tree levels. Per-segment
  constants (AAD contribution, length block) fold into one host-computed
  128-bit vector.

Shapes are static per (chunk_bytes, batch); the TPU transform backend keys
its jit cache on them.
"""

from __future__ import annotations

import dataclasses
import functools
import threading
import time
import weakref

import jax
import jax.numpy as jnp
import numpy as np

from tieredstorage_tpu.ops import _preflight, gf128
from tieredstorage_tpu.ops.aes import aes_encrypt_block_host, key_expansion
from tieredstorage_tpu.ops.aes_bitsliced import ctr_keystream_batch, ctr_keystream_keyed
from tieredstorage_tpu.utils.caching import LoadingCache
from tieredstorage_tpu.utils.locks import new_lock

TAG_SIZE = 16


def _ceil_div(a: int, b: int) -> int:
    return -(-a // b)


@dataclasses.dataclass(frozen=True, eq=False)  # identity hash: weakly cacheable
class GcmContext:
    """Host-precomputed per-(key, aad, chunk_size) constants for the kernel."""

    round_keys: np.ndarray       # uint8[15,16]
    agg_mats: tuple              # per-level int8[k*128,128] grouped operands
    final_mat: np.ndarray        # int8[128,128] transposed mult-by-H^2 matrix
    const_bits: np.ndarray       # uint8[128] = bits(T(A)*H^(mC+2) ^ L*H)
    chunk_bytes: int
    n_blocks: int                # ceil(chunk_bytes/16)
    #: int8[128,128] transposed mult-by-H^k1 between-group fold matrix of
    #: the fused GHASH tree kernel (gf128.ghash_step_matrix).
    step_mat: np.ndarray = None


# --- context-build accounting ---
#
# A context is built only on a miss of its cache, on the host, and no device
# program runs in a build. A segment key's H-power tables are built once
# (`_KeyTable`) and every size's context is assembled from slices of them;
# concurrent first uses of one key, or of one (key, aad, size), run one build
# and the others wait for it. These counts see all of that exactly; they cost
# nothing on a hit. Guarded by `_DISPATCH_MU`, with the launch counts further
# down.
_CONTEXT_STATS = {
    "context_builds": 0, "context_builds_duplicate": 0, "context_build_seconds": 0.0,
    "key_tables_built": 0, "key_table_hits": 0,
}
_BUILDS_IN_FLIGHT: dict = {}  # identity -> builds running; under _DISPATCH_MU
_CONTEXT_TLS = threading.local()


def context_stats() -> dict:
    """`context_builds` (cache misses of `make_context` and
    `make_varlen_context`), `context_builds_duplicate` (those that started
    while another build of the same key, aad and size was running: single
    flight keeps it 0), `context_build_seconds` (summed over the builds,
    the key's table included in the build that made it), `key_tables_built`
    (builds that made their key's H-power table) and `key_table_hits`
    (builds assembled from a table that was already there)."""
    with _DISPATCH_MU:
        return dict(_CONTEXT_STATS)


def thread_context_builds() -> int:
    """Context builds run by the CALLING thread: the delta around a
    `make_context` call says whether it was a miss."""
    return getattr(_CONTEXT_TLS, "count", 0)


def _counted_build(build):
    """Count and time `build`, the body of a context cache."""

    @functools.wraps(build)
    def counted(*args):
        identity = (build.__name__, *args)
        with _DISPATCH_MU:
            running = _BUILDS_IN_FLIGHT.get(identity, 0)
            _BUILDS_IN_FLIGHT[identity] = running + 1
            _CONTEXT_STATS["context_builds"] += 1
            _CONTEXT_STATS["context_builds_duplicate"] += bool(running)
        _CONTEXT_TLS.count = getattr(_CONTEXT_TLS, "count", 0) + 1
        start = time.perf_counter()
        try:
            return build(*args)
        finally:
            seconds = time.perf_counter() - start
            with _DISPATCH_MU:
                if running := _BUILDS_IN_FLIGHT.pop(identity) - 1:
                    _BUILDS_IN_FLIGHT[identity] = running
                _CONTEXT_STATS["context_build_seconds"] += seconds

    return counted


class _CallerRuns:
    """The executor of this module's caches: a miss builds in the thread that
    missed (its thread-local build count says so), the waiters block on the
    future it fills."""

    def submit(self, fn, *args):
        fn(*args)


def _single_flight_lru(entries: int) -> LoadingCache:
    """A bounded cache whose concurrent first uses of a key run one build; a
    build that raises releases its waiters with the error and leaves no
    entry."""
    return LoadingCache(executor=_CallerRuns(), max_weight=entries)


class _KeyTable:
    """What a segment key's contexts share, whatever their size and AAD: the
    round keys, H = E_K(0^128), and per aggregation level L the k = 128
    operand for base P_L = H^(128^(L-1)) (`gf128.ghash_level_table`), built
    at the level's first use. The arrays are read-only: contexts hold views."""

    def __init__(self, key: bytes) -> None:
        self.round_keys = key_expansion(key)
        self.h = int.from_bytes(
            aes_encrypt_block_host(self.round_keys, np.zeros(16, np.uint8)).tobytes(),
            "big",
        )
        self._mu = new_lock("gcm._KeyTable._mu")
        self._next_base = self.h
        #: per level (operand, carry): level 1's operand in the byte-plane
        #: order of `gf128.ghash_agg_matrices`, int8[8, 128*16, 128], the
        #: others int8[128*128, 128]; carry int8[128,128], the transposed
        #: multiply matrix of the next level's base.
        self._levels: list[tuple[np.ndarray, np.ndarray]] = []

    def _level(self, index: int) -> tuple[np.ndarray, np.ndarray]:
        with self._mu:
            while len(self._levels) <= index:
                mats, self._next_base = gf128.ghash_level_table(self._next_base)
                carry = mats[128].copy()
                if self._levels:
                    operand = mats[:128].reshape(128 * 128, 128)
                else:  # [slot, byte, bitpos, out] -> the 8 byte-bit planes
                    operand = np.ascontiguousarray(
                        mats[:128].reshape(128, 16, 8, 128)[:, :, ::-1].transpose(2, 0, 1, 3)
                    ).reshape(8, 128 * 16, 128)
                operand.flags.writeable = carry.flags.writeable = False
                self._levels.append((operand, carry))
            return self._levels[index]

    def agg_mats(self, m: int) -> tuple:
        """`gf128.ghash_agg_matrices(h, m)`: each level's trailing k slots."""
        mats = []
        for index, (k, _padded) in enumerate(gf128.ghash_agg_plan(m)):
            operand = self._level(index)[0]
            if index == 0:
                mats.append(np.ascontiguousarray(operand[:, (128 - k) * 16:]))
            else:
                mats.append(operand[(128 - k) * 128:])
        return tuple(mats)

    def power_mat(self, exponent: int) -> np.ndarray:
        """int8[128,128] transposed multiply-by-H^exponent matrix, for an
        exponent up to 128 (`gf128.mult_matrix(gcm_pow(h, e)).T`)."""
        planes, carry = self._level(0)
        if exponent == 128:
            return carry
        rows = slice((127 - exponent) * 16, (128 - exponent) * 16)
        # planes[kbit, s*16 + p] is the matrix's row p*8 + 7 - kbit
        return np.ascontiguousarray(
            planes[::-1, rows].transpose(1, 0, 2).reshape(128, 128)
        )


_KEY_TABLES = _single_flight_lru(16)
_CONTEXTS = _single_flight_lru(64)
_VARLEN_CONTEXTS = _single_flight_lru(64)


def _key_table(key: bytes) -> _KeyTable:
    """The key's table, counted as built by the caller or as already there."""
    built = []

    def build():
        built.append(True)
        return _KeyTable(key)

    table = _KEY_TABLES.get(key, build)
    with _DISPATCH_MU:
        _CONTEXT_STATS["key_tables_built" if built else "key_table_hits"] += 1
    return table


@_counted_build
def _build_context(key: bytes, aad: bytes, chunk_bytes: int) -> GcmContext:
    table = _key_table(key)
    h = table.h

    m_c = _ceil_div(chunk_bytes, 16)
    agg_mats = table.agg_mats(m_c)

    # T(A) = sum_i A_i H^(mA-i) over the AAD blocks (zero-padded).
    aad_blocks = [aad[i : i + 16] for i in range(0, len(aad), 16)]
    t_a = 0
    for i, blk in enumerate(aad_blocks):
        power = gf128.gcm_pow(h, len(aad_blocks) - 1 - i)
        t_a ^= gf128.gcm_mult(int.from_bytes(blk.ljust(16, b"\x00"), "big"), power)

    # Length block: 64-bit bit-lengths of AAD and ciphertext.
    len_block = int.from_bytes(
        (len(aad) * 8).to_bytes(8, "big") + (chunk_bytes * 8).to_bytes(8, "big"), "big"
    )
    # GHASH(A||C||L) = T(A)*H^(mC+2) ^ T(C)*H^2 ^ L*H.
    const = gf128.gcm_mult(t_a, gf128.gcm_pow(h, m_c + 2)) ^ gf128.gcm_mult(
        len_block, h
    )

    return GcmContext(
        round_keys=table.round_keys,
        agg_mats=agg_mats,
        final_mat=table.power_mat(2),
        const_bits=gf128.int_to_bitvec(const),
        chunk_bytes=chunk_bytes,
        n_blocks=m_c,
        step_mat=table.power_mat(agg_mats[0].shape[1] // 16),
    )


def make_context(key: bytes, aad: bytes, chunk_bytes: int) -> GcmContext:
    if len(key) != 32:
        raise ValueError("AES-256 key required")
    if chunk_bytes <= 0:
        raise ValueError("chunk_bytes must be positive")
    key, aad = bytes(key), bytes(aad)
    return _CONTEXTS.get(
        (key, aad, chunk_bytes), lambda: _build_context(key, aad, chunk_bytes)
    )


# --- device-side helpers ---

# numpy, not jnp: a module-level device array would initialize the JAX
# backend at import time.
_BIT_SHIFTS = np.arange(7, -1, -1, dtype=np.uint8)


def _bits_to_bytes(bits: jnp.ndarray) -> jnp.ndarray:
    b = bits.reshape(bits.shape[:-1] + (bits.shape[-1] // 8, 8)).astype(jnp.uint8)
    weights = (jnp.uint8(1) << _BIT_SHIFTS).astype(jnp.uint8)
    return (b * weights).sum(axis=-1, dtype=jnp.uint32).astype(jnp.uint8)


def _ghash_grouped(
    data_flat: jnp.ndarray, agg_mats: tuple, step_mat=None
) -> jnp.ndarray:
    """data_flat uint8[B, m*16] -> T(C) = sum_i C_i H^(m-1-i), uint8[B, 128].

    Three strategies, chosen by shape and platform — on a TPU a kernel
    that fails its preflight raises (ops/_preflight.py), so none of these
    stands in for another's failure:

    - **Fused tree kernel** (`ghash_pallas.ghash_tree_pallas`, ISSUE 13):
      with `step_mat` and more than one aggregation level, the WHOLE
      reduction runs as one Pallas kernel — in-kernel plane extraction,
      level-1 matmuls, and the level-2+ aggregation as a sequential
      per-group fold of a VMEM accumulator (``T = (T @ M_{H^k1}) ^
      node_g``). Zero inter-stage HBM materialization: payload in, [B,128]
      node bits out.
    - **Level-1 kernel + XLA ladder**: level 1 contracts the 8 byte-bit
      planes in-kernel (bytes cross HBM once); levels >= 2 contract k
      128-bit node vectors at a time via [B*G, k*128] x [k*128, 128] XLA
      matmuls, one [B, G, 128] HBM round trip per level.
    - **Pure XLA**: the plane stack materializes in HBM (8 B/B) before the
      same ladder.

    Each ladder level left-pads to a multiple of its group width (leading
    zero blocks are the polynomial's identity). All three compute the same
    function the former pairwise tree did (gf128.ghash_agg_matrices);
    `planned_hbm_roundtrips` mirrors this branch for the per-window
    accounting, so keep them in sync."""
    batch = data_flat.shape[0]
    w1 = agg_mats[0]
    k1 = w1.shape[1] // 16
    m = data_flat.shape[1] // 16
    g = _ceil_div(m, k1)
    pad_bytes = (g * k1 - m) * 16
    if pad_bytes:
        data_flat = jnp.concatenate(
            [jnp.zeros((batch, pad_bytes), jnp.uint8), data_flat], axis=1
        )
    from tieredstorage_tpu.ops import ghash_pallas

    if (
        step_mat is not None
        and len(agg_mats) > 1
        and ghash_pallas.use_pallas_ghash_tree(batch, g, k1 * 16)
        and ghash_pallas.pallas_ghash_tree_available()
    ):
        # interpret off-TPU lets the forced path run (slowly) anywhere.
        return ghash_pallas.ghash_tree_pallas(
            data_flat, w1, step_mat,
            interpret=_preflight.interpret_off_device(),
        ).astype(jnp.uint8)
    if ghash_pallas.use_pallas_ghash(
        batch * g, k1 * 16
    ) and ghash_pallas.pallas_ghash_available():
        # In-kernel plane extraction: bytes cross HBM once instead of as
        # 8 materialized int8 planes (ghash_pallas.py, which pads the row
        # count to its own grid internally).
        rows = batch * g
        mat = data_flat.reshape(rows, k1 * 16)
        x = ghash_pallas.ghash_level1_pallas(
            mat, w1, interpret=_preflight.interpret_off_device(),
        ).reshape(batch, g, 128)
    else:
        planes = jnp.stack(
            [(data_flat >> np.uint8(kbit)) & np.uint8(1) for kbit in range(8)]
        ).astype(jnp.int8)
        x = (
            jax.lax.dot_general(
                planes.reshape(8, batch * g, k1 * 16),
                w1,
                (((0, 2), (0, 1)), ((), ())),
                preferred_element_type=jnp.int32,
            )
            & 1
        ).astype(jnp.int8).reshape(batch, g, 128)
    for w in agg_mats[1:]:
        k = w.shape[0] // 128
        m = x.shape[1]
        g = _ceil_div(m, k)
        pad = g * k - m
        if pad:
            x = jnp.concatenate([jnp.zeros((batch, pad, 128), jnp.int8), x], axis=1)
        x = (
            jax.lax.dot_general(
                x.reshape(batch * g, k * 128), w, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.int32,
            )
            & 1
        ).astype(jnp.int8).reshape(batch, g, 128)
    return x[:, 0, :].astype(jnp.uint8)


def _ghash_of_ct(
    ct_padded: jnp.ndarray,
    agg_mats: tuple, final_mat: jnp.ndarray, const_bits: jnp.ndarray,
    step_mat=None,
) -> jnp.ndarray:
    """ct_padded uint8[B, m*16] (tail already zeroed) -> GHASH bits [B,128]."""
    t_c = _ghash_grouped(ct_padded, agg_mats, step_mat)
    ghash = (
        jax.lax.dot_general(
            t_c.astype(jnp.int8), final_mat, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.int32,
        )
        & 1
    ).astype(jnp.uint8)
    return ghash ^ const_bits


@functools.partial(
    jax.jit, static_argnames=("chunk_bytes", "n_blocks", "decrypt")
)
def _gcm_process_batch(
    round_keys: jnp.ndarray,
    ivs: jnp.ndarray,
    data: jnp.ndarray,
    agg_mats: tuple,
    final_mat: jnp.ndarray,
    const_bits: jnp.ndarray,
    step_mat=None,
    *,
    chunk_bytes: int,
    n_blocks: int,
    decrypt: bool,
):
    """Shared encrypt/decrypt core. data uint8[B, chunk_bytes].

    Returns (output uint8[B, chunk_bytes], tags uint8[B, 16]); the tag is
    always computed over the CIPHERTEXT (input when decrypting, output when
    encrypting).
    """
    batch = data.shape[0]
    padded_len = n_blocks * 16

    # The named scopes put every device operation of the program down to a
    # stage in a profile (tools/profile_report.py sums device time by them).
    with jax.named_scope("gcm.ctr"):
        ks = ctr_keystream_batch(round_keys, ivs, 1, n_blocks + 1)  # [B, n_blocks+1, 16]
        tag_mask = ks[:, 0, :]
        keystream = ks[:, 1:, :].reshape(batch, padded_len)[:, :chunk_bytes]

    with jax.named_scope("gcm.xor"):
        output = data ^ keystream

    with jax.named_scope("gcm.ghash"):
        ct = data if decrypt else output
        if padded_len != chunk_bytes:
            ct_padded = (
                jnp.zeros((batch, padded_len), jnp.uint8).at[:, :chunk_bytes].set(ct)
            )
        else:
            ct_padded = ct
        ghash = _ghash_of_ct(ct_padded, agg_mats, final_mat, const_bits, step_mat)
    with jax.named_scope("gcm.tag"):
        tags = _bits_to_bytes(ghash) ^ tag_mask
    return output, tags


# --- dispatch accounting ---

#: Device-program launches issued by this module's public entry points.
#: The transform backend reads per-thread deltas around each window, which
#: makes the "one fused dispatch per window" invariant testable without a
#: TPU. The process-wide total is guarded (concurrent backends on gateway
#: worker threads would tear a bare increment — races checker); the delta
#: source is THREAD-LOCAL so one backend's window never absorbs a sibling
#: thread's launches into its own count.
_DISPATCHES = [0]
_DISPATCH_MU = new_lock("gcm._DISPATCH_MU")
_DISPATCH_TLS = threading.local()


def device_dispatches() -> int:
    """Total GCM device-program launches issued so far in this process."""
    return _DISPATCHES[0]


def thread_dispatches() -> int:
    """GCM launches issued by the CALLING thread (exact delta source for
    per-window accounting under concurrent backends)."""
    return getattr(_DISPATCH_TLS, "count", 0)


def _count_dispatch() -> None:
    with _DISPATCH_MU:
        _DISPATCHES[0] += 1
    _DISPATCH_TLS.count = getattr(_DISPATCH_TLS, "count", 0) + 1


#: Payload-scale HBM round trips between the stages of the GCM window
#: program (ISSUE 13). Same process-wide + thread-local accounting shape as
#: the launch counter above; the transform backend reads per-thread deltas
#: around each window so `make transform-demo` can gate
#: hbm_roundtrips_per_window <= 1 without a TPU. The count is STATIC (host
#: logic mirroring the branch _ghash_grouped traces) — the runtime ground
#: truth remains the GiB/s measured on the chip.
_ROUNDTRIPS = [0]
_ROUNDTRIP_TLS = threading.local()


def device_hbm_roundtrips() -> int:
    """Total inter-stage HBM round trips dispatched so far in this process."""
    return _ROUNDTRIPS[0]


def thread_hbm_roundtrips() -> int:
    """Inter-stage HBM round trips dispatched by the CALLING thread."""
    return getattr(_ROUNDTRIP_TLS, "count", 0)


def _count_roundtrips(n: int) -> None:
    with _DISPATCH_MU:
        _ROUNDTRIPS[0] += n
    _ROUNDTRIP_TLS.count = getattr(_ROUNDTRIP_TLS, "count", 0) + n


def planned_hbm_roundtrips(ctx, rows: int) -> int:
    """Stage boundaries of the GCM program that materialize a payload-scale
    intermediate in HBM, for a window of `rows` rows (PER-SHARD rows under
    a mesh — each shard traces the same program). Mirrors the strategy
    branch in `_ghash_grouped` — keep the two in sync (the fused-closure
    checker in analysis/dispatch.py pins the trace side).

    Counted:

    - 1 always — the keystream handoff: the AES kernel (or XLA circuit)
      writes its bit-plane output to HBM once; the unpack + XOR fuse into
      its consumer. This is the ONE round trip the two-kernel pipeline is
      allowed (the window's own input staging and output fetch are
      transfers, counted separately as h2d/d2h).
    - +1 per XLA grouped-power ladder level >= 2 — each level materializes
      its [B, G, 128] node tensor between matmuls.
    - +1 when GHASH level 1 runs as the XLA plane path — the 8-plane int8
      expansion (8 B of HBM traffic per payload byte).
    - +0 when the fused tree kernel engages: level 1 and every aggregation
      level run inside one kernel, nodes never leave VMEM.

    The varlen sequence assembly (mask, length-block scatter, rotation) is
    elementwise/gather work XLA fuses into the level-1 operand read, not a
    stage boundary."""
    from tieredstorage_tpu.ops import ghash_pallas

    agg_mats = ctx.agg_mats
    m = ctx.n_blocks if isinstance(ctx, GcmContext) else ctx.m_cap
    k1 = agg_mats[0].shape[1] // 16
    g = _ceil_div(m, k1)
    count = 1  # keystream planes: AES kernel -> unpack/XOR fusion
    tree = (
        getattr(ctx, "step_mat", None) is not None
        and len(agg_mats) > 1
        and ghash_pallas.use_pallas_ghash_tree(rows, g, k1 * 16)
        and ghash_pallas.pallas_ghash_tree_available()
    )
    if not tree:
        count += len(agg_mats) - 1
        if not (
            ghash_pallas.use_pallas_ghash(rows * g, k1 * 16)
            and ghash_pallas.pallas_ghash_available()
        ):
            count += 1
    return count


# Device-resident copies of each context's constant arrays, uploaded once
# per (context, mesh) instead of once per window call (the round keys, GHASH
# level matrices, and folded constants are identical for every window of a
# segment). Weak keying lets evicted contexts free their HBM.
_DEVICE_CONSTS: "weakref.WeakKeyDictionary" = weakref.WeakKeyDictionary()


def _placer(mesh):
    """How a context's constants reach the device. Without a mesh a bare
    `jnp.asarray` (the default device, uncommitted). With one they are
    committed REPLICATED over it: an uncommitted single-device array handed
    to the shard_map program would be copied from chip 0 to every other chip
    again on each launch (up to ~2 MiB of H-power matrices per window)."""
    if mesh is None:
        return jnp.asarray
    from jax.sharding import NamedSharding, PartitionSpec

    replicated = NamedSharding(mesh, PartitionSpec())
    return lambda a: jax.device_put(a, replicated)


def _on_device(cache, ctx, mesh, build):
    """`build(put)`'s device arrays for (ctx, mesh), placed once."""
    per_mesh = cache.setdefault(ctx, {})
    if mesh not in per_mesh:
        per_mesh[mesh] = build(_placer(mesh))
    return per_mesh[mesh]


def _device_consts(ctx, mesh=None) -> tuple:
    if isinstance(ctx, GcmContext):
        def build(put):
            return (
                put(ctx.round_keys),
                tuple(put(m) for m in ctx.agg_mats),
                put(ctx.final_mat),
                put(ctx.const_bits),
            )
    elif isinstance(ctx, GcmKeyedContext):
        def build(put):
            return (
                put(ctx.round_keys),
                put(ctx.aad_group),
                tuple(put(m) for m in ctx.agg_mats),
                put(ctx.h_mat),
                put(ctx.h2_mat),
                put(ctx.inv_mats),
            )
    else:
        def build(put):
            return (
                put(ctx.round_keys),
                put(ctx.aad_blocks),
                tuple(put(m) for m in ctx.agg_mats),
                put(ctx.h_mat),
            )
    return _on_device(_DEVICE_CONSTS, ctx, mesh, build)


# Device-resident fold matrices of the tree kernel, cached separately so
# `_device_consts`'s tuple arity (unpacked by the profiling tools) stays
# stable. Same keying as above.
_DEVICE_STEP_MATS: "weakref.WeakKeyDictionary" = weakref.WeakKeyDictionary()


def _device_step_mat(ctx, mesh=None):
    """Device copy of the context's tree fold matrix (None when absent)."""
    if getattr(ctx, "step_mat", None) is None:
        return None
    return _on_device(
        _DEVICE_STEP_MATS, ctx, mesh, lambda put: put(ctx.step_mat)
    )


def gcm_encrypt_chunks(ctx: GcmContext, ivs: np.ndarray, plaintext: np.ndarray):
    """plaintext uint8[B, ctx.chunk_bytes], ivs uint8[B,12] ->
    (ciphertext uint8[B, chunk_bytes], tags uint8[B,16])."""
    round_keys, agg_mats, final_mat, const_bits = _device_consts(ctx)
    _count_dispatch()
    _count_roundtrips(planned_hbm_roundtrips(ctx, len(plaintext)))
    ct, tags = _gcm_process_batch(
        round_keys,
        jnp.asarray(ivs, dtype=jnp.uint8),
        jnp.asarray(plaintext, dtype=jnp.uint8),
        agg_mats,
        final_mat,
        const_bits,
        _device_step_mat(ctx),
        chunk_bytes=ctx.chunk_bytes,
        n_blocks=ctx.n_blocks,
        decrypt=False,
    )
    return ct, tags


# --- variable-length batches (encrypt-after-compress path) ---
#
# Chunks in one batch may have different byte lengths (compressed sizes).
# The CTR keystream pads/truncates trivially; for GHASH, each row's block
# sequence [AAD blocks, C blocks, length block] is built left-aligned and
# then rotated right so it ends exactly at the tree's last slot — leading
# zero blocks don't change the polynomial, so one fixed-shape tree tags all
# rows correctly regardless of their true lengths.


@dataclasses.dataclass(frozen=True, eq=False)  # identity hash: weakly cacheable
class GcmVarlenContext:
    round_keys: np.ndarray   # uint8[15,16]
    aad_blocks: np.ndarray   # uint8[m_A,16] zero-padded AAD blocks
    agg_mats: tuple          # per-level int8[k*128,128] grouped operands
    h_mat: np.ndarray        # int8[128,128] transposed mult-by-H matrix
    aad_bit_len: int
    max_bytes: int
    m_max: int               # max data blocks
    m_cap: int               # sequence slots (AAD + data + length block)
    #: int8[128,128] transposed mult-by-H^k1 between-group fold matrix of
    #: the fused GHASH tree kernel (gf128.ghash_step_matrix).
    step_mat: np.ndarray = None


@_counted_build
def _build_varlen_context(key: bytes, aad: bytes, max_bytes: int) -> GcmVarlenContext:
    table = _key_table(key)
    m_max = _ceil_div(max_bytes, 16)
    m_a = _ceil_div(len(aad), 16)
    seq_len = m_a + m_max + 1
    aad_padded = np.frombuffer(
        aad + b"\x00" * (m_a * 16 - len(aad)), dtype=np.uint8
    ).reshape(m_a, 16) if m_a else np.zeros((0, 16), np.uint8)
    agg_mats = table.agg_mats(seq_len)
    return GcmVarlenContext(
        round_keys=table.round_keys,
        aad_blocks=aad_padded,
        agg_mats=agg_mats,
        h_mat=table.power_mat(1),
        aad_bit_len=len(aad) * 8,
        max_bytes=max_bytes,
        m_max=m_max,
        m_cap=seq_len,
        step_mat=table.power_mat(agg_mats[0].shape[1] // 16),
    )


def bucket_max_bytes(n: int) -> int:
    """Round a varlen batch's max chunk size up to a bounded ladder.

    With compression on, nearly every chunk window has a distinct max
    compressed size; using it directly as the jit-static shape would trigger
    a fresh multi-second XLA compile of the whole varlen GCM program per
    window (round-1 VERDICT weak 2). The ladder quantizes shapes to
    eighth-steps of the next power of two: at most ~4 cache entries per
    octave, ≤25% padded compute, and a steady-state hit rate of ~100% since
    real workloads cluster around one compressed-size regime."""
    if n <= 1024:
        return 1024
    step = 1 << max(4, (n - 1).bit_length() - 3)
    return step * _ceil_div(n, step)


def make_varlen_context(key: bytes, aad: bytes, max_bytes: int) -> GcmVarlenContext:
    if len(key) != 32:
        raise ValueError("AES-256 key required")
    key, aad, max_bytes = bytes(key), bytes(aad), bucket_max_bytes(max_bytes)
    return _VARLEN_CONTEXTS.get(
        (key, aad, max_bytes), lambda: _build_varlen_context(key, aad, max_bytes)
    )


@functools.partial(
    jax.jit, static_argnames=("max_bytes", "m_max", "m_a", "m_cap", "decrypt")
)
def _gcm_varlen_batch(
    round_keys, ivs, data, lengths, len_blocks, aad_blocks, agg_mats, h_mat,
    step_mat=None,
    *, max_bytes: int, m_max: int, m_a: int, m_cap: int, decrypt: bool,
):
    """data uint8[B, max_bytes] left-aligned (zero tail), lengths int32[B],
    len_blocks uint8[B,16] (host-built GCM length blocks).
    Returns (output uint8[B, max_bytes], tags uint8[B, 16])."""
    batch = data.shape[0]

    with jax.named_scope("gcm.ctr"):
        ks = ctr_keystream_batch(round_keys, ivs, 1, m_max + 1)
        tag_mask = ks[:, 0, :]
        keystream = ks[:, 1:, :].reshape(batch, m_max * 16)[:, :max_bytes]

    with jax.named_scope("gcm.xor"):
        byte_mask = (
            jnp.arange(max_bytes, dtype=jnp.int32)[None, :] < lengths[:, None]
        ).astype(jnp.uint8)
        output = (data ^ keystream) * byte_mask

    with jax.named_scope("gcm.ghash"):
        ct = data if decrypt else output  # ct is already masked in both directions
        ct_blocks = ct.reshape(batch, m_max, 16)

        n_blocks = _ceil_div_dev(lengths)  # int32[B] data blocks per row
        seq = jnp.concatenate(
            [
                jnp.broadcast_to(aad_blocks, (batch, m_a, 16)).astype(jnp.uint8),
                ct_blocks,
                jnp.zeros((batch, m_cap - m_a - m_max, 16), jnp.uint8),
            ],
            axis=1,
        )
        # Place each row's length block right after its data blocks.
        l_pos = m_a + n_blocks  # int32[B]
        onehot = (
            jnp.arange(m_cap, dtype=jnp.int32)[None, :] == l_pos[:, None]
        ).astype(jnp.uint8)
        seq = seq ^ (onehot[:, :, None] * len_blocks[:, None, :])
        # Rotate right so the sequence ends at slot m_cap-1.
        shift = m_cap - (l_pos + 1)
        idx = (jnp.arange(m_cap, dtype=jnp.int32)[None, :] - shift[:, None]) % m_cap
        seq = jnp.take_along_axis(seq, idx[:, :, None], axis=1)

        t = _ghash_grouped(seq.reshape(batch, -1), agg_mats, step_mat)
        ghash = (
            jax.lax.dot_general(
                t.astype(jnp.int8), h_mat, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.int32,
            )
            & 1
        ).astype(jnp.uint8)
    with jax.named_scope("gcm.tag"):
        tags = _bits_to_bytes(ghash) ^ tag_mask
    return output, tags


def _ceil_div_dev(lengths: jnp.ndarray) -> jnp.ndarray:
    return (lengths + 15) // 16


def _host_len_blocks(ctx: GcmVarlenContext, lengths: np.ndarray) -> np.ndarray:
    out = np.zeros((len(lengths), 16), dtype=np.uint8)
    for i, l in enumerate(lengths):
        out[i] = np.frombuffer(
            ctx.aad_bit_len.to_bytes(8, "big") + (int(l) * 8).to_bytes(8, "big"),
            dtype=np.uint8,
        )
    return out


def _run_varlen(ctx: GcmVarlenContext, ivs, data, lengths, decrypt: bool):
    lengths = np.asarray(lengths, dtype=np.int32)
    round_keys, aad_blocks, agg_mats, h_mat = _device_consts(ctx)
    _count_dispatch()
    _count_roundtrips(planned_hbm_roundtrips(ctx, len(lengths)))
    return _gcm_varlen_batch(
        round_keys,
        jnp.asarray(ivs, dtype=jnp.uint8),
        jnp.asarray(data, dtype=jnp.uint8),
        jnp.asarray(lengths),
        jnp.asarray(_host_len_blocks(ctx, lengths)),
        aad_blocks,
        agg_mats,
        h_mat,
        _device_step_mat(ctx),
        max_bytes=ctx.max_bytes,
        m_max=ctx.m_max,
        m_a=ctx.aad_blocks.shape[0],
        m_cap=ctx.m_cap,
        decrypt=decrypt,
    )


def gcm_encrypt_varlen(ctx: GcmVarlenContext, ivs, plaintext, lengths):
    """plaintext uint8[B, ctx.max_bytes] (rows zero-padded past their length)."""
    return _run_varlen(ctx, ivs, plaintext, lengths, decrypt=False)


def gcm_decrypt_varlen(ctx: GcmVarlenContext, ivs, ciphertext, lengths):
    """Returns (plaintext, expected_tags) — caller verifies tags."""
    return _run_varlen(ctx, ivs, ciphertext, lengths, decrypt=True)


def gcm_decrypt_chunks(ctx: GcmContext, ivs: np.ndarray, ciphertext: np.ndarray):
    """Returns (plaintext uint8[B, chunk_bytes], expected_tags uint8[B,16]).

    The caller compares expected_tags against the received tags (constant-time
    comparison is not required server-side here, but verification is
    mandatory — the TPU transform backend raises on mismatch)."""
    round_keys, agg_mats, final_mat, const_bits = _device_consts(ctx)
    _count_dispatch()
    _count_roundtrips(planned_hbm_roundtrips(ctx, len(ciphertext)))
    return _gcm_process_batch(
        round_keys,
        jnp.asarray(ivs, dtype=jnp.uint8),
        jnp.asarray(ciphertext, dtype=jnp.uint8),
        agg_mats,
        final_mat,
        const_bits,
        _device_step_mat(ctx),
        chunk_bytes=ctx.chunk_bytes,
        n_blocks=ctx.n_blocks,
        decrypt=True,
    )


# --- fused single-dispatch windows (the production transform path) ---
#
# One jit executable per window: CTR keystream -> XOR -> GHASH -> tag fold
# in a single device program whose ONE output buffer packs `output || tag`
# per row. Every extra launch or fetch pays a size-independent floor (not
# measured on the current code), so the window path dispatches once and
# fetches once per window. The input is staged in the same packed
# shape uint8[B, n_bytes + TAG_SIZE] (tail bytes ignored — on decrypt they
# can simply carry the received tag), which makes the output shape
# identical to the input's so XLA can DONATE the staged buffer into the
# result: steady-state windows reuse one HBM allocation instead of
# allocating input + output per window.
#
# Passing ivs=None (and for varlen lengths=None) switches the per-row
# metadata to ride IN the packed tail — [iv 12 B][length u32 LE 4 B] after
# the payload columns — so a window crosses the host→device link as ONE
# buffer: no side transfers for IVs, lengths, or length blocks (the GCM
# length block is then rebuilt in-graph, bit-identical to
# `_host_len_blocks`).


def _packed_fixed_impl(
    round_keys, ivs, data_packed, agg_mats, final_mat, const_bits,
    step_mat=None,
    *, chunk_bytes: int, n_blocks: int, decrypt: bool,
):
    with jax.named_scope("gcm.pack"):
        if ivs is None:  # trace-time branch: IVs ride the packed tail
            ivs = data_packed[:, chunk_bytes : chunk_bytes + 12]
        data = data_packed[:, :chunk_bytes]
    out, tags = _gcm_process_batch(
        round_keys, ivs, data, agg_mats, final_mat, const_bits, step_mat,
        chunk_bytes=chunk_bytes, n_blocks=n_blocks, decrypt=decrypt,
    )
    with jax.named_scope("gcm.pack"):
        return jnp.concatenate([out, tags], axis=1)


def _device_len_blocks(lengths: jnp.ndarray, aad_bit_len: int) -> jnp.ndarray:
    """uint8[B, 16] GCM length blocks built in-graph — bit-identical to
    `_host_len_blocks` (64-bit big-endian AAD and ciphertext bit lengths)
    without needing x64: big-endian byte j of (lengths * 8) is
    lengths >> (8*(7-j) - 3), and the bytes whose shift would overflow
    int32 are zero for any length below 2^37 bytes (chunks are capped two
    orders below that)."""
    batch = lengths.shape[0]
    aad_half = jnp.broadcast_to(
        jnp.asarray(
            np.frombuffer(int(aad_bit_len).to_bytes(8, "big"), dtype=np.uint8)
        ),
        (batch, 8),
    )
    cols = []
    for j in range(8):
        shift = 8 * (7 - j) - 3
        if shift >= 31:
            cols.append(jnp.zeros((batch,), jnp.uint8))
        elif shift >= 0:
            cols.append(((lengths >> shift) & 0xFF).astype(jnp.uint8))
        else:
            cols.append(((lengths & 0x1F) << 3).astype(jnp.uint8))
    return jnp.concatenate([aad_half, jnp.stack(cols, axis=1)], axis=1)


def _packed_varlen_impl(
    round_keys, ivs, data_packed, lengths, len_blocks, aad_blocks, agg_mats,
    h_mat, step_mat=None,
    *, aad_bit_len: int, max_bytes: int, m_max: int, m_a: int,
    m_cap: int, decrypt: bool,
):
    with jax.named_scope("gcm.pack"):
        if ivs is None:
            ivs = data_packed[:, max_bytes : max_bytes + 12]
        if lengths is None:
            lb = data_packed[:, max_bytes + 12 : max_bytes + 16].astype(jnp.int32)
            lengths = lb[:, 0] | (lb[:, 1] << 8) | (lb[:, 2] << 16) | (lb[:, 3] << 24)
        if len_blocks is None:
            len_blocks = _device_len_blocks(lengths, aad_bit_len)
        data = data_packed[:, :max_bytes]
    out, tags = _gcm_varlen_batch(
        round_keys, ivs, data, lengths, len_blocks,
        aad_blocks, agg_mats, h_mat, step_mat,
        max_bytes=max_bytes, m_max=m_max,
        m_a=m_a, m_cap=m_cap, decrypt=decrypt,
    )
    with jax.named_scope("gcm.pack"):
        return jnp.concatenate([out, tags], axis=1)


def _require_tail_metadata(*side_args) -> None:
    if any(a is not None for a in side_args):
        raise ValueError(
            "sharded packed windows read per-row metadata from the packed "
            "tail columns: pass ivs=None (and lengths=None) so the window "
            "crosses the host->device link as one row-sharded buffer"
        )


def _packed_fixed_sharded(mesh):
    """`_packed_fixed_impl` fanned out over a 1-D device mesh: the packed
    buffer's row axis is sharded (every row is independent — keystream,
    XOR, GHASH and tag are all row-local, so no collectives), the GCM
    constants are replicated, and in/out carry the SAME row sharding so
    jit can still donate the staged input as the output allocation."""
    from tieredstorage_tpu.parallel.mesh import DATA_AXIS
    from jax.sharding import PartitionSpec as P

    row, rep = P(DATA_AXIS, None), P()

    def run(
        round_keys, ivs, data_packed, agg_mats, final_mat, const_bits,
        step_mat=None,
        *, chunk_bytes: int, n_blocks: int, decrypt: bool,
    ):
        _require_tail_metadata(ivs)

        def body(rk, dp, am, fm, cb, sm):
            return _packed_fixed_impl(
                rk, None, dp, am, fm, cb, sm,
                chunk_bytes=chunk_bytes, n_blocks=n_blocks, decrypt=decrypt,
            )

        return jax.shard_map(
            body, mesh=mesh, in_specs=(rep, row, rep, rep, rep, rep),
            out_specs=row, check_vma=False,
        )(round_keys, data_packed, agg_mats, final_mat, const_bits, step_mat)

    return run


def _packed_varlen_sharded(mesh):
    """Varlen counterpart of `_packed_fixed_sharded`: per-row lengths ride
    the packed tail, so each shard rebuilds its own GCM length blocks
    in-graph and no cross-chip exchange is needed."""
    from tieredstorage_tpu.parallel.mesh import DATA_AXIS
    from jax.sharding import PartitionSpec as P

    row, rep = P(DATA_AXIS, None), P()

    def run(
        round_keys, ivs, data_packed, lengths, len_blocks, aad_blocks,
        agg_mats, h_mat, step_mat=None,
        *, aad_bit_len: int, max_bytes: int, m_max: int, m_a: int,
        m_cap: int, decrypt: bool,
    ):
        _require_tail_metadata(ivs, lengths, len_blocks)

        def body(rk, dp, ab, am, hm, sm):
            return _packed_varlen_impl(
                rk, None, dp, None, None, ab, am, hm, sm,
                aad_bit_len=aad_bit_len, max_bytes=max_bytes, m_max=m_max,
                m_a=m_a, m_cap=m_cap, decrypt=decrypt,
            )

        return jax.shard_map(
            body, mesh=mesh, in_specs=(rep, row, rep, rep, rep, rep),
            out_specs=row, check_vma=False,
        )(round_keys, data_packed, aad_blocks, agg_mats, h_mat, step_mat)

    return run


@functools.lru_cache(maxsize=16)
def _packed_jit(varlen: bool, donate: bool, mesh=None):
    """One jit executable per (shape family, donation, mesh) combination.

    With a mesh the impl runs under shard_map (row axis over the chips) but
    the call is still ONE logical dispatch — the launch counter and
    `DispatchStats` count it as one, which keeps the one-dispatch-per-window
    invariant meaningful across mesh sizes. `data_packed` stays argument 2
    in every spelling so donation always targets the staged window buffer.
    """
    if mesh is not None:
        fn = _packed_varlen_sharded(mesh) if varlen else _packed_fixed_sharded(mesh)
    else:
        fn = _packed_varlen_impl if varlen else _packed_fixed_impl
    static = (
        ("aad_bit_len", "max_bytes", "m_max", "m_a", "m_cap", "decrypt")
        if varlen
        else ("chunk_bytes", "n_blocks", "decrypt")
    )
    return jax.jit(
        fn, static_argnames=static, donate_argnums=(2,) if donate else ()
    )


def gcm_window_packed(
    ctx: GcmContext,
    ivs,
    data_packed,
    *,
    decrypt: bool,
    donate: bool = False,
    mesh=None,
):
    """Fused fixed-size window: data_packed uint8[B, chunk_bytes + 16] ->
    packed uint8[B, chunk_bytes + 16] where row i is `output_i || tag_i` —
    one device dispatch, one output buffer. With ivs=None the per-row IV
    is read from the packed tail (bytes [chunk_bytes, chunk_bytes+12));
    otherwise the tail columns are ignored. The tag is over the ciphertext
    in both directions (expected tag on decrypt; the caller verifies).
    `donate=True` hands the staged input buffer to XLA for reuse as the
    output — the caller must not touch data_packed afterwards. With `mesh`
    (a 1-D data mesh; batch divisible by its size, metadata in the tail)
    the one program fans out across every chip via shard_map, output rows
    sharded identically to the input's so donation still aliases."""
    round_keys, agg_mats, final_mat, const_bits = _device_consts(ctx, mesh)
    _count_dispatch()
    rows = data_packed.shape[0] // (mesh.size if mesh is not None else 1)
    _count_roundtrips(planned_hbm_roundtrips(ctx, rows))
    return _packed_jit(False, donate, mesh)(
        round_keys,
        None if ivs is None else jnp.asarray(ivs, dtype=jnp.uint8),
        jnp.asarray(data_packed, dtype=jnp.uint8),
        agg_mats,
        final_mat,
        const_bits,
        _device_step_mat(ctx, mesh),
        chunk_bytes=ctx.chunk_bytes,
        n_blocks=ctx.n_blocks,
        decrypt=decrypt,
    )


def gcm_varlen_window_packed(
    ctx: GcmVarlenContext,
    ivs,
    data_packed,
    lengths,
    *,
    decrypt: bool,
    donate: bool = False,
    mesh=None,
):
    """Fused variable-length window: data_packed uint8[B, max_bytes + 16]
    (rows left-aligned with a ZERO payload tail — GHASH requires it) ->
    packed uint8[B, max_bytes + 16] = `masked output || tag` per row. With
    ivs=None and lengths=None the per-row metadata rides the packed tail
    ([iv 12 B][length u32 LE 4 B] at columns [max_bytes, max_bytes+16))
    and the GCM length blocks are rebuilt in-graph, so the whole window is
    ONE host→device buffer. Same single-dispatch/donation/mesh contract as
    `gcm_window_packed` (sharded windows require the tail-metadata form)."""
    if lengths is not None:
        lengths = np.asarray(lengths, dtype=np.int32)
    round_keys, aad_blocks, agg_mats, h_mat = _device_consts(ctx, mesh)
    _count_dispatch()
    rows = data_packed.shape[0] // (mesh.size if mesh is not None else 1)
    _count_roundtrips(planned_hbm_roundtrips(ctx, rows))
    return _packed_jit(True, donate, mesh)(
        round_keys,
        None if ivs is None else jnp.asarray(ivs, dtype=jnp.uint8),
        jnp.asarray(data_packed, dtype=jnp.uint8),
        None if lengths is None else jnp.asarray(lengths),
        None if lengths is None else jnp.asarray(_host_len_blocks(ctx, lengths)),
        aad_blocks,
        agg_mats,
        h_mat,
        _device_step_mat(ctx, mesh),
        aad_bit_len=ctx.aad_bit_len,
        max_bytes=ctx.max_bytes,
        m_max=ctx.m_max,
        m_a=ctx.aad_blocks.shape[0],
        m_cap=ctx.m_cap,
        decrypt=decrypt,
    )


# --- merged windows of several keys (the batcher's flush) ---
#
# Concurrent fetches of different segments hold different data keys and
# AADs, so a merged launch of their rows takes a per-launch key table of
# keyed contexts (one max_bytes rung) and a row -> slot index. What depends
# on the key is read per row from that table: the round keys (the AES
# kernel's grid steps each select theirs, a row's words filling whole
# steps), the AAD, every GHASH level's operand and the powers of H.
#
# GHASH is taken over each row's blocks as they lie, with no per-row
# rotation: its AAD right-aligned in one 2 KiB group ahead of its
# ciphertext's groups, the ciphertext's zero tail after it. That gives
# T(A||C) * H^d, d = the row's trailing zero blocks, which is multiplied by
# H^-d (the key's H^(-2^k) matrices, one per bit of d); then
# Y = T(A||C) * H^2 ^ L * H. GHASH level 1 runs with a row's groups on the
# MXU's M axis under its own operand (`ghash_pallas.
# ghash_level1_keyed_pallas`), so a row costs a row and not the eight of
# the tree kernel's tile; the levels above it are per-row batched matmuls.

#: The level-1 group: 128 blocks of 16 bytes.
_GROUP_BYTES = 128 * 16


@dataclasses.dataclass(frozen=True, eq=False)  # identity hash: weakly cacheable
class GcmKeyedContext:
    """Host-precomputed constants of one (key, aad) in a keyed window of
    one `bucket_max_bytes` rung."""

    round_keys: np.ndarray   # uint8[15,16]
    aad_group: np.ndarray    # uint8[2048]: the AAD blocks ending the group
    aad_bit_len: int
    agg_mats: tuple          # per level: 1 + m_pad/128 groups of 128 blocks
    h_mat: np.ndarray        # int8[128,128] transposed mult-by-H
    h2_mat: np.ndarray       # int8[128,128] transposed mult-by-H^2
    inv_mats: np.ndarray     # int8[bits, 128, 128]: mult-by-H^(-2^k)
    max_bytes: int
    m_max: int               # max data blocks
    m_pad: int               # data blocks padded to whole groups


@_counted_build
def _build_keyed_context(key: bytes, aad: bytes, max_bytes: int) -> GcmKeyedContext:
    table = _key_table(key)
    if len(aad) > _GROUP_BYTES:
        raise ValueError(f"a keyed window takes an AAD of at most {_GROUP_BYTES} bytes")
    m_max = _ceil_div(max_bytes, 16)
    m_pad = _ceil_div(m_max, 128) * 128
    m_a = _ceil_div(len(aad), 16)
    aad_group = np.zeros(_GROUP_BYTES, np.uint8)
    if m_a:
        aad_group[_GROUP_BYTES - m_a * 16 : _GROUP_BYTES - m_a * 16 + len(aad)] = (
            np.frombuffer(aad, np.uint8)
        )
    inverse = gf128.mult_matrix(gf128.gcm_pow(table.h, (1 << 128) - 2)).T.astype(np.int64)
    inv_mats = [inverse]
    for _ in range(1, max(1, (m_pad - 1).bit_length())):
        inv_mats.append((inv_mats[-1] @ inv_mats[-1]) & 1)
    return GcmKeyedContext(
        round_keys=table.round_keys,
        aad_group=aad_group,
        aad_bit_len=len(aad) * 8,
        agg_mats=table.agg_mats(m_pad + 128),
        h_mat=table.power_mat(1),
        h2_mat=table.power_mat(2),
        inv_mats=np.stack(inv_mats).astype(np.int8),
        max_bytes=max_bytes,
        m_max=m_max,
        m_pad=m_pad,
    )


_KEYED_CONTEXTS = _single_flight_lru(64)


def make_keyed_context(key: bytes, aad: bytes, max_bytes: int) -> GcmKeyedContext:
    """The keyed-window constants of (key, aad) at `max_bytes`'s rung."""
    if len(key) != 32:
        raise ValueError("AES-256 key required")
    key, aad, max_bytes = bytes(key), bytes(aad), bucket_max_bytes(max_bytes)
    return _KEYED_CONTEXTS.get(
        (key, aad, max_bytes), lambda: _build_keyed_context(key, aad, max_bytes)
    )


def _device_aad_len_blocks(lengths: jnp.ndarray, aad_bits: jnp.ndarray) -> jnp.ndarray:
    """`_device_len_blocks` with a per-row AAD bit length int32[B]."""
    aad_half = jnp.stack(
        [
            jnp.zeros_like(aad_bits, jnp.uint8) if shift >= 32
            else ((aad_bits >> shift) & 0xFF).astype(jnp.uint8)
            for shift in range(56, -8, -8)
        ],
        axis=1,
    )
    return jnp.concatenate([aad_half, _device_len_blocks(lengths, 0)[:, 8:]], axis=1)


def _bytes_to_bits(blocks: jnp.ndarray) -> jnp.ndarray:
    """uint8[B, 16] -> int8[B, 128], most significant bit of byte 0 first
    (`_bits_to_bytes`' inverse)."""
    return ((blocks[:, :, None] >> _BIT_SHIFTS) & 1).reshape(blocks.shape[0], 128).astype(jnp.int8)


def _rows_times(bits: jnp.ndarray, mats: jnp.ndarray) -> jnp.ndarray:
    """int8[B, 128] bits times each row's own int8[B, 128, 128] matrix, mod 2."""
    return (
        jax.lax.dot_general(
            bits, mats, (((1,), (1,)), ((0,), (0,))), preferred_element_type=jnp.int32,
        )
        & 1
    ).astype(jnp.int8)


def _ghash_keyed(groups: jnp.ndarray, agg_mats: tuple, row_keys: jnp.ndarray):
    """groups uint8[B, g, 2048] -> T = sum_i S_i H_row^(m-1-i) over each
    row's g * 128 blocks, int8[B, 128], each row under its own key:
    agg_mats holds per level a tuple of the table's operands (slot order)."""
    from tieredstorage_tpu.ops import ghash_pallas

    batch, g = groups.shape[:2]
    w1 = jnp.stack(agg_mats[0])  # [slots, 8, 2048, 128]
    if ghash_pallas.pallas_ghash_available():
        tile = ghash_pallas.KEYED_ROWS_PER_STEP
        gp = _ceil_div(g, tile) * tile
        if gp != g:
            groups = jnp.concatenate(
                [jnp.zeros((batch, gp - g, _GROUP_BYTES), jnp.uint8), groups], axis=1
            )
        x = ghash_pallas.ghash_level1_keyed_pallas(
            groups.reshape(batch * gp, _GROUP_BYTES), w1,
            jnp.repeat(row_keys, gp // tile),
            interpret=_preflight.interpret_off_device(),
        ).reshape(batch, gp, 128)
        # The tile pad's leading groups are zero nodes: level 2 pads to the
        # same count (its group width is the tile's) or, where g < 128 and
        # it contracts the g groups at once, takes the trailing g.
        x = x[:, gp - (g if g < tile else gp):]
    else:
        planes = jnp.stack(
            [(groups >> np.uint8(kbit)) & np.uint8(1) for kbit in range(8)], axis=2
        ).astype(jnp.int8)  # [B, g, 8, 2048]
        x = (
            jax.lax.dot_general(
                planes, w1[row_keys], (((2, 3), (1, 2)), ((0,), (0,))),
                preferred_element_type=jnp.int32,
            )
            & 1
        ).astype(jnp.int8)  # [B, g, 128]
    for level in agg_mats[1:]:
        w = jnp.stack(level)[row_keys]  # [B, k*128, 128]
        k = w.shape[1] // 128
        m = x.shape[1]
        g = _ceil_div(m, k)
        if g * k - m:
            x = jnp.concatenate(
                [jnp.zeros((batch, g * k - m, 128), jnp.int8), x], axis=1
            )
        x = (
            jax.lax.dot_general(
                x.reshape(batch, g, k * 128), w, (((2,), (1,)), ((0,), (0,))),
                preferred_element_type=jnp.int32,
            )
            & 1
        ).astype(jnp.int8)
    return x[:, 0, :]


def _packed_keyed_impl(
    data_packed, row_keys, round_keys, aad_groups, aad_bits, agg_mats, h_mats,
    h2_mats, inv_mats,
    *, max_bytes: int, m_max: int, m_pad: int, decrypt: bool,
):
    """A packed varlen window (per-row IV and length in the tail) over rows
    of different keys: row_keys int32[B] indexes the slot tuples of the
    key table (`GcmKeyedContext` fields) and aad_bits int32[slots]."""
    batch = data_packed.shape[0]
    with jax.named_scope("gcm.pack"):
        ivs = data_packed[:, max_bytes : max_bytes + 12]
        lb = data_packed[:, max_bytes + 12 : max_bytes + 16].astype(jnp.int32)
        lengths = lb[:, 0] | (lb[:, 1] << 8) | (lb[:, 2] << 16) | (lb[:, 3] << 24)
        data = data_packed[:, :max_bytes]

    with jax.named_scope("gcm.ctr"):
        ks = ctr_keystream_keyed(jnp.stack(round_keys), row_keys, ivs, 1, m_max + 1)
        tag_mask = ks[:, 0, :]
        keystream = ks[:, 1:, :].reshape(batch, m_max * 16)[:, :max_bytes]

    with jax.named_scope("gcm.xor"):
        byte_mask = (
            jnp.arange(max_bytes, dtype=jnp.int32)[None, :] < lengths[:, None]
        ).astype(jnp.uint8)
        output = (data ^ keystream) * byte_mask

    with jax.named_scope("gcm.ghash"):
        ct = data if decrypt else output  # zero past each row's length
        if m_pad * 16 != max_bytes:
            ct = jnp.concatenate(
                [ct, jnp.zeros((batch, m_pad * 16 - max_bytes), jnp.uint8)], axis=1
            )
        groups = jnp.concatenate(
            [
                jnp.stack(aad_groups)[row_keys][:, None, :],
                ct.reshape(batch, m_pad // 128, _GROUP_BYTES),
            ],
            axis=1,
        )
        t = _ghash_keyed(groups, agg_mats, row_keys)  # T(A||C) * H^d
        trailing = m_pad - _ceil_div_dev(lengths)
        inverse = jnp.stack(inv_mats)[row_keys]  # [B, bits, 128, 128]
        for bit in range(inverse.shape[1]):
            t = jnp.where(
                ((trailing >> bit) & 1)[:, None] == 1,
                _rows_times(t, inverse[:, bit]), t,
            )
        len_bits = _bytes_to_bits(_device_aad_len_blocks(lengths, aad_bits[row_keys]))
        ghash = (
            _rows_times(t, jnp.stack(h2_mats)[row_keys])
            ^ _rows_times(len_bits, jnp.stack(h_mats)[row_keys])
        ).astype(jnp.uint8)
    with jax.named_scope("gcm.tag"):
        tags = _bits_to_bytes(ghash) ^ tag_mask
    with jax.named_scope("gcm.pack"):
        return jnp.concatenate([output, tags], axis=1)


@functools.lru_cache(maxsize=4)
def _keyed_jit(donate: bool):
    return jax.jit(
        _packed_keyed_impl,
        static_argnames=("max_bytes", "m_max", "m_pad", "decrypt"),
        donate_argnums=(0,) if donate else (),
    )


def planned_keyed_hbm_roundtrips(ctx: GcmKeyedContext) -> int:
    """`planned_hbm_roundtrips` of the keyed program: the keystream
    handoff, the level-1 operand the kernel reads (the rows' groups behind
    their AAD groups), one per GHASH level above the first, and the plane
    stack where level 1 is not the keyed kernel."""
    from tieredstorage_tpu.ops import ghash_pallas

    return 1 + len(ctx.agg_mats) + (not ghash_pallas.pallas_ghash_available())


def keyed_table_slots(rows: int) -> int:
    """Slots of a keyed launch's key table: its row count, so the table
    adds no shape of its own to the program (a launch has at most as many
    keys as rows)."""
    return rows


def gcm_keyed_window_packed(
    ctxs, row_keys, data_packed, *, decrypt: bool, donate: bool = False,
):
    """A merged varlen window whose rows carry different keys and AADs:
    data_packed uint8[B, max_bytes + 16] in the tail-metadata form of
    `gcm_varlen_window_packed` (each row's IV and length in its tail),
    ctxs the launch's key table (`GcmKeyedContext`s of one rung, at most B
    of them), row_keys int[B] each row's slot. Returns packed `masked
    output || tag` rows, byte-identical to each row's own key's varlen
    window. One device dispatch; the table is padded to
    `keyed_table_slots(B)` with its first entry, whose device constants
    every padding slot shares."""
    row_slots = np.asarray(row_keys, dtype=np.int32)
    rows = len(row_slots)
    slots = keyed_table_slots(rows)
    if data_packed.shape[0] != rows:
        raise ValueError(f"{rows} row keys for a {data_packed.shape[0]}-row window")
    if not 0 < len(ctxs) <= slots:
        raise ValueError(f"{len(ctxs)} keys for a {rows}-row window")
    first = ctxs[0]
    if any(c.max_bytes != first.max_bytes for c in ctxs):
        raise ValueError("a keyed window's contexts share one rung")
    table = list(ctxs) + [first] * (slots - len(ctxs))
    aad_bits = np.asarray([ctx.aad_bit_len for ctx in table], dtype=np.int32)
    consts = [_device_consts(ctx) for ctx in table]
    _count_dispatch()
    _count_roundtrips(planned_keyed_hbm_roundtrips(first))
    return _keyed_jit(donate)(
        jnp.asarray(data_packed, dtype=jnp.uint8),
        jnp.asarray(row_slots),
        tuple(c[0] for c in consts),
        tuple(c[1] for c in consts),
        jnp.asarray(aad_bits),
        tuple(tuple(c[2][level] for c in consts) for level in range(len(first.agg_mats))),
        tuple(c[3] for c in consts),
        tuple(c[4] for c in consts),
        tuple(c[5] for c in consts),
        max_bytes=first.max_bytes,
        m_max=first.m_max,
        m_pad=first.m_pad,
        decrypt=decrypt,
    )


@functools.partial(jax.jit, static_argnames=("n",))
def _take_rows_impl(out, first_row, *, n: int):
    return jax.lax.dynamic_slice_in_dim(out, first_row, n, axis=0)


def take_rows(out, first_row: int, n: int):
    """A device copy of rows [first_row, first_row + n) of a packed window
    output: a buffer of their own, which does not keep `out` alive. One
    program per (window shape, n); the row is an argument."""
    return _take_rows_impl(out, np.int32(first_row), n=n)


#: Public alias for composing the GCM core under an outer jit/shard_map
#: (e.g. the multichip dry-run step); same contract as `_gcm_process_batch`.
gcm_process_batch_device = _gcm_process_batch
