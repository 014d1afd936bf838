"""Pallas TPU kernels for the grouped-GHASH reduction.

Two kernels, one per reduction strategy:

- **Level-1 kernel** (`ghash_level1_pallas`): the XLA formulation
  (ops/gcm.py `_ghash_grouped`) materializes 8 int8 bit-planes of the
  ciphertext in HBM — 8 bytes of traffic per payload byte — before
  contracting them against the level-1 operand on the MXU. This kernel
  reads the raw bytes once: a [R_T, K] uint8 tile lands in VMEM, the 8
  planes are extracted as in-register shifts/masks, and 8 f32 MXU matmuls
  accumulate the 128 output bits (values bounded by K ≤ 2048 < 2^24, so
  f32 accumulation is exact; the mod-2 reduction happens once at the end).
  HBM traffic drops to read-bytes + write-nodes (~1.06 B/B). Levels >= 2
  then run as the XLA grouped-power ladder — one HBM round trip of
  [B, G, 128] node bits per level.

- **Tree kernel** (`ghash_tree_pallas`, ISSUE 13): the ENTIRE reduction —
  level 1 AND every aggregation level above it — in one kernel. The grid
  walks each row tile's groups sequentially; a VMEM scratch accumulator
  carries the running T across groups and is folded by a precomputed
  multiply-by-H^k bit matrix between steps
  (``T = (T @ M_{H^k}) ^ node_g``, gf128.ghash_step_matrix), so the node
  bits of level 2+ NEVER materialize in HBM: the payload crosses HBM
  exactly once on the way in and [B, 128] final node bits on the way out.
  The trade: group g+1 of a row depends on group g, so only the row axis
  is parallel — the level-1 matmuls run at B(+pad) sublanes instead of
  the level-1 kernel's 256-row tiles. For the production window shapes
  (B=16 rows of 4 MiB) that exchanges MXU occupancy for zero inter-stage
  HBM traffic and a single-stage program. It is the path on a TPU:
  TIEREDSTORAGE_TPU_PALLAS_GHASH_TREE=0 keeps the level-1 kernel + XLA
  ladder for an on-chip A/B only — that program's [B, G*K] -> [B*G, K]
  reshape alone costs the TPU compiler ~85 s per 16-row window shape
  (tests/test_tpu_compile.py), against ~10 s for the whole tree program.

Replaces the per-chunk GHASH of the reference's JDK GCM cipher
(core/.../transform/EncryptionChunkEnumeration.java:66-81) together with
ops/gcm.py; wired behind the same loud first-use preflight as the Pallas AES
circuit (ops/aes_bitsliced._use_pallas_circuit, ops/_preflight.py).
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from tieredstorage_tpu.ops import _preflight

#: Rows of the flattened [B*G, K] level-1 matrix per grid step. 256 rows x
#: 2048 cols keeps the widened int32 tile (2 MiB — x is upcast before the
#: bit math, see _ghash_l1_kernel) + per-plane f32 operand (2 MiB) + the
#: f32 weight slice (1 MiB) well inside VMEM.
ROWS_PER_STEP = 256


_PREFLIGHT: list[bool] = []  # memoized PASS of the level-1 preflight


def _preflight_attempt() -> bool:
    import numpy as np

    rng = np.random.default_rng(0)
    k = 256
    data = rng.integers(0, 256, (ROWS_PER_STEP, k), dtype=np.uint8)
    w1 = rng.integers(0, 2, (8, k, 128), dtype=np.int8)
    planes = np.stack([(data >> p) & 1 for p in range(8)]).astype(np.int64)
    expect = (
        np.einsum("prk,pko->ro", planes, w1.astype(np.int64)) & 1
    ).astype(np.int8)
    got = jax.block_until_ready(
        ghash_level1_pallas(jnp.asarray(data), jnp.asarray(w1))
    )
    return bool(jnp.array_equal(got, expect))


def _preflight_ok() -> bool:
    """Compile and run the kernel once on a small tile, cross-checked
    against an exact numpy mod-2 reference. Any Mosaic lowering/runtime
    failure or mismatch raises `KernelPreflightError` (same contract as
    aes_bitsliced._pallas_preflight_ok, shared machinery in
    ops/_preflight.py, which runs the attempt outside the caller's
    trace)."""
    return _preflight.run_preflight(
        _PREFLIGHT, _preflight_attempt, "Pallas GHASH level-1 kernel"
    )


def use_pallas_ghash(rows: int, k: int) -> bool:
    """Shape eligibility for the level-1 kernel — pure host logic, no
    platform probe, so benchmarks and CPU-only CI can assert that the
    production window shapes tile onto the kernel. K must tile the 128-lane
    minor dimension and the row count must fill at least one grid step
    (`ghash_level1_pallas` pads shorter remainders internally; a sub-step
    batch would waste more than half the padded compute). The dispatch
    decision is `use_pallas_ghash(...) and pallas_ghash_available()` —
    shape preconditions hold regardless of forcing: an un-tiled K would
    fail Mosaic lowering, so forcing only overrides the platform check and
    the preflight, never validity."""
    return k > 0 and k % 128 == 0 and rows >= ROWS_PER_STEP


def pallas_ghash_available() -> bool:
    """Platform half of the gate: can (or must) the kernel run here?

    TIEREDSTORAGE_TPU_PALLAS_GHASH=0/1 overrides (read at trace time, like
    the AES gate); otherwise TPU backends only, where a failing preflight
    raises."""
    forced = os.environ.get("TIEREDSTORAGE_TPU_PALLAS_GHASH")
    if forced is not None:
        return forced not in ("0", "false", "off")
    return _preflight.on_tpu() and _preflight_ok()


def _ghash_l1_kernel(x_ref, w_ref, o_ref):
    """x_ref: VMEM uint8[R, K]; w_ref: VMEM int8[8, K, 128];
    o_ref: VMEM int8[R, 128]."""
    # Widen to int32 BEFORE the bit math: Mosaic on the v5e toolchain can
    # legalize neither i8 vector shifts (arith.shrui on vector<...xi8>) nor
    # direct u8/i8->f32 casts — both failed on the real chip, round 5.
    x = x_ref[:].astype(jnp.int32)
    acc = None
    for p in range(8):
        plane = ((x >> p) & 1).astype(jnp.float32)
        w_p = w_ref[p].astype(jnp.int32).astype(jnp.float32)
        part = jnp.dot(plane, w_p, preferred_element_type=jnp.float32)
        acc = part if acc is None else acc + part
    o_ref[:] = (acc.astype(jnp.int32) & 1).astype(jnp.int8)


@functools.partial(jax.jit, static_argnames=("interpret",))
def ghash_level1_pallas(
    data: jnp.ndarray, w1: jnp.ndarray, *, interpret: bool = False
) -> jnp.ndarray:
    """data uint8[R, K] (K the level-1 group byte width),
    w1 int8[8, K, 128] -> node bits int8[R, 128].

    Bit-exact drop-in for the XLA plane-stack + dot_general level 1 in
    `gcm._ghash_grouped`. R is padded to the ROWS_PER_STEP grid INSIDE the
    op (zero rows contract to zero node bits) and the result sliced back,
    so callers dispatch production window shapes as-is."""
    rows, k = data.shape
    if rows <= 0:
        raise ValueError("rows must be positive")
    if w1.shape != (8, k, 128):
        raise ValueError(f"weights {w1.shape} do not match K={k}")
    padded = -(-rows // ROWS_PER_STEP) * ROWS_PER_STEP
    if padded != rows:
        data = jnp.pad(data, ((0, padded - rows), (0, 0)))
    steps = padded // ROWS_PER_STEP
    out = pl.pallas_call(
        _ghash_l1_kernel,
        grid=(steps,),
        in_specs=[
            pl.BlockSpec((ROWS_PER_STEP, k), lambda s: (s, 0)),
            pl.BlockSpec((8, k, 128), lambda s: (0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((ROWS_PER_STEP, 128), lambda s: (s, 0)),
        out_shape=jax.ShapeDtypeStruct((padded, 128), jnp.int8),
        interpret=interpret,
    )(data, w1)
    return out[:rows]


# --------------------------------------------------------------- tree kernel

#: Rows per grid step of the tree kernel. The row axis only carries the GCM
#: batch (each row's groups are a sequential chain), so the tile is the f32
#: sublane minimum: VMEM per step stays at the widened int32 data tile
#: (8 x K x 4 B = 64 KiB at K=2048) + the int8 level-1 operand (2 MiB) +
#: one f32 plane operand (1 MiB) + the fold matrix and [8, 128] accumulator.
TREE_ROWS_PER_STEP = 8

_TREE_PREFLIGHT: list[bool] = []  # memoized PASS of the tree preflight


def use_pallas_ghash_tree(batch: int, groups: int, k_bytes: int) -> bool:
    """Shape eligibility for the fused tree kernel — pure host logic, no
    platform probe (same split-gate contract as `use_pallas_ghash`). The
    group byte width must tile the 128-lane minor dimension and fit the
    kernel's VMEM budget (the agg plan caps k at 128 blocks = 2048 bytes),
    and at least two groups must exist: a single-group reduction is already
    one level-1 pass with nothing to aggregate, so the tree buys nothing."""
    return (
        0 < k_bytes <= 2048
        and k_bytes % 128 == 0
        and groups >= 2
        and batch >= 1
    )


def _tree_preflight_attempt() -> bool:
    import numpy as np

    rng = np.random.default_rng(0)
    k, groups = 256, 3
    data = rng.integers(0, 256, (TREE_ROWS_PER_STEP, groups * k), dtype=np.uint8)
    w1 = rng.integers(0, 2, (8, k, 128), dtype=np.int8)
    step = rng.integers(0, 2, (128, 128), dtype=np.int8)
    acc = np.zeros((TREE_ROWS_PER_STEP, 128), dtype=np.int64)
    for g in range(groups):
        tile = data[:, g * k : (g + 1) * k]
        planes = np.stack([(tile >> p) & 1 for p in range(8)]).astype(np.int64)
        node = np.einsum("prk,pko->ro", planes, w1.astype(np.int64)) & 1
        acc = ((acc @ step.astype(np.int64)) & 1) ^ node if g else node
    expect = acc.astype(np.int8)
    got = jax.block_until_ready(
        ghash_tree_pallas(jnp.asarray(data), jnp.asarray(w1), jnp.asarray(step))
    )
    return bool(jnp.array_equal(got, expect))


def _tree_preflight_ok() -> bool:
    """First-use compile-and-run of the tree kernel on a minimal shape,
    cross-checked against an exact numpy fold (same contract as
    `_preflight_ok`: a failure raises — the level-1 kernel + XLA ladder
    never takes the tree's place in silence)."""
    return _preflight.run_preflight(
        _TREE_PREFLIGHT, _tree_preflight_attempt, "Pallas GHASH tree kernel"
    )


def pallas_ghash_tree_available() -> bool:
    """Platform half of the tree gate. TIEREDSTORAGE_TPU_PALLAS_GHASH_TREE
    overrides just the tree (on-chip A/B against the ladder); unset, it
    follows TIEREDSTORAGE_TPU_PALLAS_GHASH, then TPU backend + preflight —
    all read at trace time like the sibling gates."""
    forced = os.environ.get("TIEREDSTORAGE_TPU_PALLAS_GHASH_TREE")
    if forced is None:
        forced = os.environ.get("TIEREDSTORAGE_TPU_PALLAS_GHASH")
    if forced is not None:
        return forced not in ("0", "false", "off")
    return _preflight.on_tpu() and _tree_preflight_ok()


def _ghash_tree_kernel(x_ref, w_ref, step_ref, o_ref, acc_ref):
    """x_ref: VMEM uint8[R, K] — group g's byte columns of the row tile;
    w_ref: VMEM int8[8, K, 128] level-1 operand; step_ref: VMEM
    int8[128, 128] transposed multiply-by-H^(K/16) fold matrix; o_ref:
    VMEM int8[R, 128]; acc_ref: VMEM f32[R, 128] running T accumulator
    (0/1 values), persistent across the sequential group axis."""
    g = pl.program_id(1)
    # Widen BEFORE the bit math: Mosaic on the v5e toolchain legalizes
    # neither u8 vector shifts nor direct u8->f32 casts (round 5).
    x = x_ref[:].astype(jnp.int32)
    node = None
    for p in range(8):
        plane = ((x >> p) & 1).astype(jnp.float32)
        w_p = w_ref[p].astype(jnp.int32).astype(jnp.float32)
        part = jnp.dot(plane, w_p, preferred_element_type=jnp.float32)
        node = part if node is None else node + part
    # Exact: plane sums are bounded by K <= 2048 < 2^24.
    node_bits = node.astype(jnp.int32) & 1

    @pl.when(g == 0)
    def _init():
        acc_ref[:] = node_bits.astype(jnp.float32)

    @pl.when(g != 0)
    def _fold():
        step = step_ref[:].astype(jnp.int32).astype(jnp.float32)
        folded = jnp.dot(
            acc_ref[:], step, preferred_element_type=jnp.float32
        )
        # Exact again: fold sums are bounded by 128.
        acc_ref[:] = (
            (folded.astype(jnp.int32) & 1) ^ node_bits
        ).astype(jnp.float32)

    @pl.when(g == pl.num_programs(1) - 1)
    def _emit():
        o_ref[:] = acc_ref[:].astype(jnp.int8)


@functools.partial(jax.jit, static_argnames=("interpret",))
def ghash_tree_pallas(
    data: jnp.ndarray,
    w1: jnp.ndarray,
    step_mat: jnp.ndarray,
    *,
    interpret: bool = False,
) -> jnp.ndarray:
    """data uint8[B, G*K] (G groups of the level-1 byte width K, leading
    zero-block padding already applied by the caller), w1 int8[8, K, 128],
    step_mat int8[128, 128] (gf128.ghash_step_matrix of H^(K/16)) ->
    T(C) node bits int8[B, 128].

    Bit-exact drop-in for the WHOLE `gcm._ghash_grouped` reduction — level
    1 and every grouped-power level above it — as ONE kernel: the grid
    walks (row tile, group) with the group axis sequential, a VMEM scratch
    accumulator folds ``T = (T @ M_{H^k}) ^ node_g`` between groups, and
    only the final [B, 128] node bits leave the kernel. B is padded to the
    TREE_ROWS_PER_STEP grid inside the op (zero rows reduce to zero bits)
    and sliced back."""
    rows, total = data.shape
    k = w1.shape[1]
    if rows <= 0:
        raise ValueError("rows must be positive")
    if w1.shape != (8, k, 128):
        raise ValueError(f"weights {w1.shape} are not (8, K, 128)")
    if k <= 0 or total % k:
        raise ValueError(f"data width {total} does not tile into K={k} groups")
    if step_mat.shape != (128, 128):
        raise ValueError(f"step matrix {step_mat.shape} is not (128, 128)")
    groups = total // k
    padded = -(-rows // TREE_ROWS_PER_STEP) * TREE_ROWS_PER_STEP
    if padded != rows:
        data = jnp.pad(data, ((0, padded - rows), (0, 0)))
    row_steps = padded // TREE_ROWS_PER_STEP
    out = pl.pallas_call(
        _ghash_tree_kernel,
        grid=(row_steps, groups),
        in_specs=[
            pl.BlockSpec((TREE_ROWS_PER_STEP, k), lambda r, g: (r, g)),
            pl.BlockSpec((8, k, 128), lambda r, g: (0, 0, 0)),
            pl.BlockSpec((128, 128), lambda r, g: (0, 0)),
        ],
        out_specs=pl.BlockSpec((TREE_ROWS_PER_STEP, 128), lambda r, g: (r, 0)),
        out_shape=jax.ShapeDtypeStruct((padded, 128), jnp.int8),
        scratch_shapes=[pltpu.VMEM((TREE_ROWS_PER_STEP, 128), jnp.float32)],
        interpret=interpret,
    )(data, w1, step_mat)
    return out[:rows]


# -------------------------------------------------------------- keyed level 1

#: Group rows per grid step of the keyed level-1 kernel: one row's groups
#: are padded to whole tiles, so a tile never spans two keys. 128 is also
#: the level-2 group width, so the pad is the leading zero groups level 2
#: adds anyway (gcm.gcm_keyed_window_packed). A keyed window's groups are
#: always the full 128 blocks (2 KiB) wide.
KEYED_ROWS_PER_STEP = 128


def _ghash_l1_keyed_kernel(tile_key_ref, x_ref, w_ref, o_ref):
    """`_ghash_l1_kernel` under the tile's own key: tile_key_ref is the
    scalar-prefetched tile -> key-slot map the weight block follows."""
    del tile_key_ref  # read by the index maps
    _ghash_l1_kernel(x_ref, w_ref, o_ref)


@functools.partial(jax.jit, static_argnames=("interpret",))
def ghash_level1_keyed_pallas(
    data: jnp.ndarray,
    w1: jnp.ndarray,
    tile_keys: jnp.ndarray,
    *,
    interpret: bool = False,
) -> jnp.ndarray:
    """data uint8[R, K] (R a multiple of KEYED_ROWS_PER_STEP, each tile of
    R one row's groups), w1 int8[slots, 8, K, 128] the launch's level-1
    operands, tile_keys int32[R / KEYED_ROWS_PER_STEP] -> node bits
    int8[R, 128].

    The level-1 kernel with the group axis of one GCM row on the MXU's M
    axis: a row's groups stream through its own key's operand, which is
    fetched again only where the key changes from one tile to the next.
    Where the tree kernel pays one operand pass per group for eight rows,
    this pays one per 128 groups of a row."""
    rows, k = data.shape
    t = KEYED_ROWS_PER_STEP
    if rows <= 0 or rows % t:
        raise ValueError(f"rows {rows} is not a positive multiple of {t}")
    if w1.ndim != 4 or w1.shape[1:] != (8, k, 128):
        raise ValueError(f"weights {w1.shape} do not match K={k}")
    if tile_keys.shape != (rows // t,):
        raise ValueError(f"tile_keys {tile_keys.shape} do not match {rows // t} tiles")
    return pl.pallas_call(
        _ghash_l1_keyed_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(rows // t,),
            in_specs=[
                pl.BlockSpec((t, k), lambda i, keys: (i, 0)),
                pl.BlockSpec((None, 8, k, 128), lambda i, keys: (keys[i], 0, 0, 0)),
            ],
            out_specs=pl.BlockSpec((t, 128), lambda i, keys: (i, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((rows, 128), jnp.int8),
        interpret=interpret,
    )(tile_keys.astype(jnp.int32), data, w1)
