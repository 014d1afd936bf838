"""Ask the TPU's own compiler, without a TPU: the Pallas kernels and the
window programs of the served path, at the production widths (16 rows of
4 MiB + 16), compiled for a DESCRIBED v5e 2x2 topology.

Interpret-mode tests cannot see what the chip's compiler refuses (a slice off
the tiling, too much VMEM, a program that does not fit HBM). A compile that
passes here is not a chip run and says nothing about results or times — that
is chip_smoke.py's job.

The gates ask `jax.default_backend()` and see the CPU, so each test steers
them itself (`kernels_on`). The topology is described inside a module-scoped
fixture, never at import: only one process may load the TPU's library, and
every xdist worker imports this file. All tests stay in this one file for the
same reason, and compile in the test's own process.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, SingleDeviceSharding

from tieredstorage_tpu.ops import _preflight, aes_bitsliced, aes_pallas, gcm, ghash_pallas
from tieredstorage_tpu.ops.aes_pallas import aes_encrypt_planes_pallas

CHUNK = 4 << 20   # upstream's documented chunk.size
ROWS = 16         # one 64 MiB window
KEY, AAD = bytes(range(32)), b"a" * 32
COLLECTIVES = (
    "all-reduce", "all-gather", "all-to-all", "collective-permute",
    "reduce-scatter",
)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache but
    cannot be read back without the chip: keep these out of it."""
    from jax.experimental.compilation_cache import compilation_cache

    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


@pytest.fixture
def kernels_on(monkeypatch):
    """Answer the gates as a TPU backend whose preflights passed would."""

    def steer(*, tree: bool = True):
        monkeypatch.setattr(aes_bitsliced, "pallas_aes_available", lambda: True)
        monkeypatch.setattr(ghash_pallas, "pallas_ghash_available", lambda: True)
        monkeypatch.setattr(
            ghash_pallas, "pallas_ghash_tree_available", lambda: tree
        )
        monkeypatch.setattr(_preflight, "interpret_off_device", lambda: False)

    steer()
    return steer


def shaped(array, sharding):
    return jax.ShapeDtypeStruct(array.shape, array.dtype, sharding=sharding)


def fixed_args(rows: int, n_bytes: int, *, rows_on, consts_on):
    ctx = gcm.make_context(KEY, AAD, n_bytes)
    args = (
        shaped(ctx.round_keys, consts_on), None,
        jax.ShapeDtypeStruct((rows, n_bytes + 16), jnp.uint8, sharding=rows_on),
        tuple(shaped(m, consts_on) for m in ctx.agg_mats),
        shaped(ctx.final_mat, consts_on), shaped(ctx.const_bits, consts_on),
        shaped(ctx.step_mat, consts_on),
    )
    static = dict(chunk_bytes=ctx.chunk_bytes, n_blocks=ctx.n_blocks)
    return args, static


def varlen_args(rows: int, max_bytes: int, *, rows_on, consts_on):
    ctx = gcm.make_varlen_context(KEY, AAD, max_bytes)
    args = (
        shaped(ctx.round_keys, consts_on), None,
        jax.ShapeDtypeStruct((rows, ctx.max_bytes + 16), jnp.uint8, sharding=rows_on),
        None, None, shaped(ctx.aad_blocks, consts_on),
        tuple(shaped(m, consts_on) for m in ctx.agg_mats),
        shaped(ctx.h_mat, consts_on), shaped(ctx.step_mat, consts_on),
    )
    static = dict(
        aad_bit_len=ctx.aad_bit_len, max_bytes=ctx.max_bytes, m_max=ctx.m_max,
        m_a=ctx.aad_blocks.shape[0], m_cap=ctx.m_cap,
    )
    return ctx, args, static


def kernel_calls(compiled) -> int:
    return compiled.as_text().count("tpu_custom_call")


# ------------------------------------------------------------------- kernels
def test_aes_kernel_compiles_at_the_window_width(one_chip):
    # 16 rows x (4 MiB + the tag-mask block) = 16 x 8193 packed words.
    words = ROWS * 8193
    compiled = aes_encrypt_planes_pallas.lower(
        jax.ShapeDtypeStruct((15, 16, 8), jnp.uint32, sharding=one_chip),
        jax.ShapeDtypeStruct((16, 8, words), jnp.uint32, sharding=one_chip),
    ).compile()
    assert kernel_calls(compiled) == 1


@pytest.mark.parametrize("rows", [ROWS * 128, ROWS * 2048])
def test_ghash_level1_kernel_compiles(one_chip, rows):
    # K = 2048: the level-1 group width. 16 x 2048 rows is what a 64 MiB
    # window flattens to (4 MiB / 2 KiB groups per row).
    compiled = ghash_pallas.ghash_level1_pallas.lower(
        jax.ShapeDtypeStruct((rows, 2048), jnp.uint8, sharding=one_chip),
        jax.ShapeDtypeStruct((8, 2048, 128), jnp.int8, sharding=one_chip),
    ).compile()
    assert kernel_calls(compiled) == 1


@pytest.mark.parametrize("batch", [ROWS, 1])
def test_ghash_tree_kernel_compiles(one_chip, batch):
    # batch 1: a one-chunk fetch, padded 1 -> TREE_ROWS_PER_STEP rows inside.
    compiled = ghash_pallas.ghash_tree_pallas.lower(
        jax.ShapeDtypeStruct((batch, CHUNK), jnp.uint8, sharding=one_chip),
        jax.ShapeDtypeStruct((8, 2048, 128), jnp.int8, sharding=one_chip),
        jax.ShapeDtypeStruct((128, 128), jnp.int8, sharding=one_chip),
    ).compile()
    assert kernel_calls(compiled) == 1


# ------------------------------------------------------------ window programs
def test_fixed_window_encrypt_program_compiles(one_chip, kernels_on):
    """The program `_launch_packed` runs for every full window of an
    uncompressed copy: both kernels inside one program, the staged buffer
    donated, and temporaries that fit beside a hot tier."""
    args, static = fixed_args(ROWS, CHUNK, rows_on=one_chip, consts_on=one_chip)
    compiled = gcm._packed_jit(False, True, None).lower(
        *args, **static, decrypt=False
    ).compile()
    assert kernel_calls(compiled) == 2
    memory = compiled.memory_analysis()
    window_bytes = ROWS * (CHUNK + 16)
    # Donation intact: the output aliases the (tile-padded) staged window.
    assert memory.alias_size_in_bytes >= window_bytes
    # 1536.6 MiB when this was written, ~24x the window (PERF.md). A jump
    # past 2 GiB per window would crowd four in-flight windows + a 4 GiB
    # hot tier off a 16 GiB chip.
    assert memory.temp_size_in_bytes < 2 << 30


def test_one_chunk_decrypt_program_compiles(one_chip, kernels_on):
    """What every cache-less ranged fetch launches: one row."""
    args, static = fixed_args(1, CHUNK, rows_on=one_chip, consts_on=one_chip)
    compiled = gcm._packed_jit(False, True, None).lower(
        *args, **static, decrypt=True
    ).compile()
    assert kernel_calls(compiled) == 2


@pytest.mark.parametrize("varlen", [False, True], ids=["two_full_rows", "full_and_ragged_row"])
def test_prefetch_sub_window_decrypt_programs_compile(one_chip, kernels_on, varlen):
    """What a prefetching chunk cache launches beside the one-row program
    (`prefetch.window.chunks` 2): two full rows at a segment's entry, and
    the varlen pair where a segment's last two chunks are new together."""
    if varlen:
        ctx, args, static = varlen_args(2, CHUNK, rows_on=one_chip, consts_on=one_chip)
        assert ctx.max_bytes == CHUNK
    else:
        args, static = fixed_args(2, CHUNK, rows_on=one_chip, consts_on=one_chip)
    compiled = gcm._packed_jit(varlen, True, None).lower(
        *args, **static, decrypt=True
    ).compile()
    assert kernel_calls(compiled) == 2
    # what the hot tier would retain of it, and the program's temporaries
    # beside a 4 GiB hot tier and a handful of such windows in flight
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 30


@pytest.mark.parametrize("rows,payload,rung", [
    (1, 3_268_884, 3_670_016),  # a full chunk's frame alone: the steady scan
    (1, 3_036_421, 3_145_728),  # the ragged chunk's frame alone
    (2, 3_268_884, 3_670_016),  # two full rows: a segment's entry
], ids=["one_full_row", "one_ragged_row", "two_rows"])
def test_compressed_chunk_decrypt_programs_compile(one_chip, kernels_on, rows, payload, rung):
    """What a chunk cache over a zstd + AES segment launches
    (`kip405-zstd-aes-chunkcache`): every window varlen, one or two rows of
    ~3.27 MB frames on the 3.5 MiB rung, the ragged chunk's on the 3.0 MiB
    one: a handful of programs for every chunk of every segment."""
    ctx, args, static = varlen_args(rows, payload, rows_on=one_chip, consts_on=one_chip)
    assert ctx.max_bytes == rung == gcm.bucket_max_bytes(payload)
    compiled = gcm._packed_jit(True, True, None).lower(
        *args, **static, decrypt=True
    ).compile()
    assert kernel_calls(compiled) == 2
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes >= rows * (rung + 16)  # the staged rows, donated
    assert memory.temp_size_in_bytes < 1 << 30


def test_varlen_window_program_compiles_one_bucket_down(one_chip, kernels_on):
    """A compressed window: the varlen program one ladder bucket below
    4 MiB."""
    ctx, args, static = varlen_args(
        ROWS, CHUNK - (CHUNK >> 3) - 1, rows_on=one_chip, consts_on=one_chip
    )
    assert ctx.max_bytes == CHUNK - (CHUNK >> 3)
    compiled = gcm._packed_jit(True, True, None).lower(
        *args, **static, decrypt=False
    ).compile()
    assert kernel_calls(compiled) == 2
    assert compiled.memory_analysis().alias_size_in_bytes >= ROWS * (ctx.max_bytes + 16)


@pytest.mark.parametrize("rows", [8, ROWS])
def test_keyed_window_program_compiles(one_chip, kernels_on, rows):
    """What a merged flush of several segments' keys launches
    (`gcm_keyed_window_packed`): the keyed AES and GHASH level-1 kernels in
    one program, the staged rows donated, a key table of `rows` slots. Its
    rows reach the level-1 operand as [B, groups, 2048] (a split of the
    byte axis) and not by a [B, m * 16] -> [B * groups, 2048] reshape,
    which costs the compiler ~80 s."""
    ctx = gcm.make_keyed_context(KEY, AAD, CHUNK)
    slots = gcm.keyed_table_slots(rows)

    def table(array):
        return tuple(shaped(array, one_chip) for _ in range(slots))

    args = (
        jax.ShapeDtypeStruct((rows, CHUNK + 16), jnp.uint8, sharding=one_chip),
        jax.ShapeDtypeStruct((rows,), jnp.int32, sharding=one_chip),
        table(ctx.round_keys), table(ctx.aad_group),
        jax.ShapeDtypeStruct((slots,), jnp.int32, sharding=one_chip),
        tuple(table(m) for m in ctx.agg_mats),
        table(ctx.h_mat), table(ctx.h2_mat), table(ctx.inv_mats),
    )
    compiled = gcm._keyed_jit(True).lower(
        *args, max_bytes=ctx.max_bytes, m_max=ctx.m_max, m_pad=ctx.m_pad, decrypt=True,
    ).compile()
    assert kernel_calls(compiled) == 2
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes >= rows * (CHUNK + 16)
    # 1130 MiB at 16 rows when this was written, beside the fixed 16-row
    # program's 1537
    assert memory.temp_size_in_bytes < 2 << 30


def test_keyed_kernels_compile_at_the_window_width(one_chip):
    """The two kernels alone: 16 rows of 4 MiB in 9 AES grid steps a row,
    and 16 rows of 2176 level-1 groups in 128-group tiles."""
    steps = ROWS * 9
    aes = aes_pallas.aes_encrypt_planes_keyed_pallas.lower(
        jax.ShapeDtypeStruct((ROWS, 15, 16, 8), jnp.uint32, sharding=one_chip),
        jax.ShapeDtypeStruct((steps,), jnp.int32, sharding=one_chip),
        jax.ShapeDtypeStruct((16, 8, steps * aes_pallas.WORDS_PER_STEP), jnp.uint32,
                             sharding=one_chip),
    ).compile()
    assert kernel_calls(aes) == 1
    tiles = ROWS * 17
    ghash = ghash_pallas.ghash_level1_keyed_pallas.lower(
        jax.ShapeDtypeStruct((tiles * ghash_pallas.KEYED_ROWS_PER_STEP, 2048), jnp.uint8,
                             sharding=one_chip),
        jax.ShapeDtypeStruct((ROWS, 8, 2048, 128), jnp.int8, sharding=one_chip),
        jax.ShapeDtypeStruct((tiles,), jnp.int32, sharding=one_chip),
    ).compile()
    assert kernel_calls(ghash) == 1


def test_sharded_window_program_has_no_collective(topo, kernels_on):
    """The four-chip window (`mesh.devices` = all on a 2x2 host): rows
    sharded, constants replicated, and — by construction — no collective."""
    mesh = Mesh(np.array(topo.devices), ("data",))
    rows_on = NamedSharding(mesh, P("data", None))
    replicated = NamedSharding(mesh, P())
    args, static = fixed_args(ROWS, CHUNK, rows_on=rows_on, consts_on=replicated)
    compiled = gcm._packed_jit(False, True, mesh).lower(
        *args, **static, decrypt=False
    ).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 2
    assert not [c for c in COLLECTIVES if c in text]
    # Per device: its four rows, donated, and no more than those.
    per_device = (ROWS // 4) * (CHUNK + 16)
    assert per_device <= compiled.memory_analysis().alias_size_in_bytes < 2 * per_device


@pytest.mark.slow
def test_ladder_window_program_compiles_slowly(one_chip, kernels_on):
    """The level-1 kernel + XLA ladder form, reachable on a TPU only through
    TIEREDSTORAGE_TPU_PALLAS_GHASH_TREE=0: it compiles, but its
    [16, 4 MiB] -> [32768, 2048] reshape alone costs the compiler ~85 s
    (the kernels themselves take ~1 s), ten times the tree program."""
    # Same shapes as the tree test above: drop its cached trace, in which
    # the tree gate had answered yes.
    jax.clear_caches()
    kernels_on(tree=False)
    args, static = fixed_args(ROWS, CHUNK, rows_on=one_chip, consts_on=one_chip)
    compiled = gcm._packed_jit(False, True, None).lower(
        *args, **static, decrypt=False
    ).compile()
    assert kernel_calls(compiled) == 2
