"""Pallas GHASH kernels: bit-exactness of the level-1 kernel against the
XLA plane path and a numpy mod-2 reference (interpret mode on CPU), the
fused TREE kernel (ISSUE 13: all reduction levels in one kernel) against
numpy, the serial GF(2^128) reference, the XLA ladder, and the host
`cryptography` oracle — plus the platform gates."""

from __future__ import annotations

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from tieredstorage_tpu.ops import gcm, gf128, ghash_pallas  # noqa: E402
from tieredstorage_tpu.ops.ghash_pallas import (  # noqa: E402
    ROWS_PER_STEP,
    TREE_ROWS_PER_STEP,
    ghash_level1_pallas,
    ghash_tree_pallas,
    use_pallas_ghash,
    use_pallas_ghash_tree,
)


def _numpy_level1(data: np.ndarray, w1: np.ndarray) -> np.ndarray:
    planes = np.stack([(data >> p) & 1 for p in range(8)]).astype(np.int64)
    return (np.einsum("prk,pko->ro", planes, w1.astype(np.int64)) & 1).astype(np.int8)


def test_kernel_matches_numpy_reference_single_step():
    rng = np.random.default_rng(1)
    k = 256
    data = rng.integers(0, 256, (ROWS_PER_STEP, k), dtype=np.uint8)
    w1 = rng.integers(0, 2, (8, k, 128), dtype=np.int8)
    got = np.asarray(
        ghash_level1_pallas(jnp.asarray(data), jnp.asarray(w1), interpret=True)
    )
    np.testing.assert_array_equal(got, _numpy_level1(data, w1))


def test_kernel_matches_numpy_reference_multi_step():
    rng = np.random.default_rng(2)
    k = 128
    rows = 3 * ROWS_PER_STEP
    data = rng.integers(0, 256, (rows, k), dtype=np.uint8)
    w1 = rng.integers(0, 2, (8, k, 128), dtype=np.int8)
    got = np.asarray(
        ghash_level1_pallas(jnp.asarray(data), jnp.asarray(w1), interpret=True)
    )
    np.testing.assert_array_equal(got, _numpy_level1(data, w1))


def test_kernel_pads_partial_row_steps_internally():
    """Rows that don't fill the ROWS_PER_STEP grid are padded INSIDE the op
    (zero rows contract to zero node bits) and sliced back — the shape
    coverage contract the production window shapes rely on."""
    rng = np.random.default_rng(7)
    k = 128
    rows = ROWS_PER_STEP + 17
    data = rng.integers(0, 256, (rows, k), dtype=np.uint8)
    w1 = rng.integers(0, 2, (8, k, 128), dtype=np.int8)
    got = np.asarray(
        ghash_level1_pallas(jnp.asarray(data), jnp.asarray(w1), interpret=True)
    )
    assert got.shape == (rows, 128)
    np.testing.assert_array_equal(got, _numpy_level1(data, w1))


def test_kernel_rejects_bad_shapes():
    w1 = jnp.zeros((8, 128, 128), jnp.int8)
    with pytest.raises(ValueError, match="weights"):
        ghash_level1_pallas(
            jnp.zeros((ROWS_PER_STEP, 256), jnp.uint8), w1, interpret=True
        )


def test_shape_eligibility_is_pure_host_logic(monkeypatch):
    """`use_pallas_ghash` answers only "does this shape tile onto the
    kernel" — no platform probe, so CPU-only CI can assert the production
    window shapes are eligible. The dispatch gate composes it with
    `pallas_ghash_available()` (platform/preflight/forcing)."""
    from tieredstorage_tpu.ops.ghash_pallas import pallas_ghash_available

    monkeypatch.delenv("TIEREDSTORAGE_TPU_PALLAS_GHASH", raising=False)
    assert jax.default_backend() == "cpu"
    # Well-tiled production shapes are eligible even on CPU...
    assert use_pallas_ghash(1 << 20, 2048)
    # ...but the platform half keeps the dispatch off the kernel here.
    assert not pallas_ghash_available()
    monkeypatch.setenv("TIEREDSTORAGE_TPU_PALLAS_GHASH", "1")
    assert pallas_ghash_available()
    # Forcing overrides platform/preflight, never shape validity.
    assert not use_pallas_ghash(8, 8)
    monkeypatch.setenv("TIEREDSTORAGE_TPU_PALLAS_GHASH", "0")
    assert not pallas_ghash_available()


def test_gate_requires_tiled_shapes(monkeypatch):
    monkeypatch.delenv("TIEREDSTORAGE_TPU_PALLAS_GHASH", raising=False)
    # Un-tiled K or a sub-step row count must never reach the kernel,
    # whatever the platform says.
    assert not use_pallas_ghash(1 << 20, 2048 + 64)
    assert not use_pallas_ghash(ROWS_PER_STEP - 1, 2048)


def test_preflight_failure_raises_instead_of_degrading(monkeypatch):
    from tieredstorage_tpu.ops._preflight import KernelPreflightError

    monkeypatch.setattr(ghash_pallas, "_PREFLIGHT", [])
    monkeypatch.setattr(
        ghash_pallas,
        "ghash_level1_pallas",
        lambda *a, **k: (_ for _ in ()).throw(RuntimeError("mosaic failed")),
    )
    with pytest.raises(KernelPreflightError, match="mosaic failed"):
        ghash_pallas._preflight_ok()
    assert ghash_pallas._PREFLIGHT == []  # never memoized as "unavailable"
    with pytest.raises(KernelPreflightError):
        ghash_pallas._preflight_ok()


def test_level1_preflight_attempt_crosschecks_on_cpu(monkeypatch):
    """The preflight's own numpy reference is the on-chip correctness
    oracle, so the CPU suite must execute it for real: stand the kernel in
    with `_numpy_level1` (itself kernel-validated above; the real kernel
    runs through the attempt in tests/test_preflight.py) and
    the attempt must agree. Any operator flip in the reference fails the
    cross-check loudly instead of silently blinding the TPU gate."""
    monkeypatch.setattr(
        ghash_pallas,
        "ghash_level1_pallas",
        lambda data, w1, **kw: jnp.asarray(
            _numpy_level1(np.asarray(data), np.asarray(w1))
        ),
    )
    assert ghash_pallas._preflight_attempt() is True


def test_tree_preflight_attempt_crosschecks_on_cpu(monkeypatch):
    """Same contract for the tree preflight's numpy group-fold, with the
    kernel stood in by `_numpy_tree` (kernel-validated above)."""
    monkeypatch.setattr(
        ghash_pallas,
        "ghash_tree_pallas",
        lambda data, w1, step, **kw: jnp.asarray(
            _numpy_tree(np.asarray(data), np.asarray(w1), np.asarray(step))
        ),
    )
    assert ghash_pallas._tree_preflight_attempt() is True


def test_kernels_reject_empty_batch():
    """rows == 0 must fail loud at trace time in BOTH kernels — a zero-row
    grid would otherwise return an empty result that upstream code could
    mistake for a tagged window."""
    w1 = jnp.zeros((8, 256, 128), jnp.int8)
    with pytest.raises(ValueError, match="rows"):
        ghash_level1_pallas(jnp.zeros((0, 256), jnp.uint8), w1, interpret=True)
    with pytest.raises(ValueError, match="rows"):
        ghash_tree_pallas(
            jnp.zeros((0, 512), jnp.uint8), w1,
            jnp.zeros((128, 128), jnp.int8), interpret=True,
        )


# --------------------------------------------------------- tree kernel (13)
def _numpy_tree(data: np.ndarray, w1: np.ndarray, step: np.ndarray) -> np.ndarray:
    """Exact group-sequential fold: T = (T @ M) ^ node_g, all in int64."""
    k = w1.shape[1]
    groups = data.shape[1] // k
    acc = None
    for g in range(groups):
        node = _numpy_level1(data[:, g * k : (g + 1) * k], w1).astype(np.int64)
        if acc is None:
            acc = node
        else:
            acc = ((acc @ step.astype(np.int64)) & 1) ^ node
    return acc.astype(np.int8)


class TestTreeKernel:
    def test_matches_numpy_fold_multi_group(self):
        rng = np.random.default_rng(11)
        k, groups = 256, 5
        data = rng.integers(
            0, 256, (TREE_ROWS_PER_STEP, groups * k), dtype=np.uint8
        )
        w1 = rng.integers(0, 2, (8, k, 128), dtype=np.int8)
        step = rng.integers(0, 2, (128, 128), dtype=np.int8)
        got = np.asarray(ghash_tree_pallas(
            jnp.asarray(data), jnp.asarray(w1), jnp.asarray(step),
            interpret=True,
        ))
        np.testing.assert_array_equal(got, _numpy_tree(data, w1, step))

    def test_matches_serial_ghash_reference_with_real_operands(self):
        """End-to-end math check: the REAL per-key operands
        (ghash_agg_matrices level 1 + ghash_step_matrix) composed by the
        kernel equal the serial Y_i = (Y_{i-1} ^ X_i) * H reference."""
        rng = np.random.default_rng(12)
        h = int(rng.integers(1, 1 << 62)) | 1
        k_blocks, groups, rows = 16, 4, 6  # non-divisible row count too
        m = k_blocks * groups
        w1 = gf128.ghash_agg_matrices(h, m, max_k=k_blocks)[0]
        step = gf128.ghash_step_matrix(h, k_blocks)
        data = rng.integers(0, 256, (rows, m * 16), dtype=np.uint8)
        got = np.asarray(ghash_tree_pallas(
            jnp.asarray(data), jnp.asarray(w1), jnp.asarray(step),
            interpret=True,
        ))
        for r in range(rows):
            blocks = [
                data[r, i * 16 : (i + 1) * 16].tobytes() for i in range(m)
            ]
            # ghash_reference folds one extra *H after the last block
            # (Y_i = (Y_{i-1} ^ X_i) * H = sum X_i H^(m-i)); the grouped
            # tree computes T(C) = sum C_i H^(m-1-i), so T * H must equal
            # the serial reference.
            tree_int = gf128.bitvec_to_int(got[r].astype(np.uint8))
            assert gf128.gcm_mult(tree_int, h) == gf128.ghash_reference(
                h, blocks
            ), f"row {r}"

    def test_pads_partial_row_tiles_internally(self):
        rng = np.random.default_rng(13)
        k = 128
        rows = TREE_ROWS_PER_STEP + 3
        data = rng.integers(0, 256, (rows, 4 * k), dtype=np.uint8)
        w1 = rng.integers(0, 2, (8, k, 128), dtype=np.int8)
        step = rng.integers(0, 2, (128, 128), dtype=np.int8)
        got = np.asarray(ghash_tree_pallas(
            jnp.asarray(data), jnp.asarray(w1), jnp.asarray(step),
            interpret=True,
        ))
        assert got.shape == (rows, 128)
        np.testing.assert_array_equal(got, _numpy_tree(data, w1, step))

    def test_rejects_bad_shapes(self):
        w1 = jnp.zeros((8, 256, 128), jnp.int8)
        step = jnp.zeros((128, 128), jnp.int8)
        with pytest.raises(ValueError, match="tile"):
            ghash_tree_pallas(
                jnp.zeros((4, 300), jnp.uint8), w1, step, interpret=True
            )
        with pytest.raises(ValueError, match="step"):
            ghash_tree_pallas(
                jnp.zeros((4, 512), jnp.uint8), w1,
                jnp.zeros((128, 64), jnp.int8), interpret=True,
            )

    def test_tree_eligibility_is_pure_host_logic(self):
        # Production window shapes: 16 rows, 2048 groups of 2048 bytes.
        assert use_pallas_ghash_tree(16, 2048, 2048)
        # The demo's small windows are eligible too (row padding is cheap).
        assert use_pallas_ghash_tree(4, 16, 2048)
        # Single-group shapes have nothing to aggregate.
        assert not use_pallas_ghash_tree(16, 1, 2048)
        # Un-tiled or over-VMEM group widths never reach the kernel.
        assert not use_pallas_ghash_tree(16, 8, 2048 + 64)
        assert not use_pallas_ghash_tree(16, 8, 4096)
        assert not use_pallas_ghash_tree(0, 8, 2048)

    def test_tree_availability_env_precedence(self, monkeypatch):
        from tieredstorage_tpu.ops.ghash_pallas import (
            pallas_ghash_tree_available,
        )

        monkeypatch.delenv("TIEREDSTORAGE_TPU_PALLAS_GHASH", raising=False)
        monkeypatch.delenv("TIEREDSTORAGE_TPU_PALLAS_GHASH_TREE", raising=False)
        assert jax.default_backend() == "cpu"
        assert not pallas_ghash_tree_available()
        # The shared GHASH knob arms the tree too...
        monkeypatch.setenv("TIEREDSTORAGE_TPU_PALLAS_GHASH", "1")
        assert pallas_ghash_tree_available()
        # ...but the tree-specific knob wins (on-chip A/B vs the ladder).
        monkeypatch.setenv("TIEREDSTORAGE_TPU_PALLAS_GHASH_TREE", "0")
        assert not pallas_ghash_tree_available()
        monkeypatch.setenv("TIEREDSTORAGE_TPU_PALLAS_GHASH", "0")
        monkeypatch.setenv("TIEREDSTORAGE_TPU_PALLAS_GHASH_TREE", "1")
        assert pallas_ghash_tree_available()

    def test_tree_preflight_failure_raises_instead_of_degrading(self, monkeypatch):
        from tieredstorage_tpu.ops._preflight import KernelPreflightError

        monkeypatch.setattr(ghash_pallas, "_TREE_PREFLIGHT", [])
        monkeypatch.setattr(
            ghash_pallas,
            "ghash_tree_pallas",
            lambda *a, **k: (_ for _ in ()).throw(RuntimeError("mosaic failed")),
        )
        with pytest.raises(KernelPreflightError, match="mosaic failed"):
            ghash_pallas._tree_preflight_ok()
        assert ghash_pallas._TREE_PREFLIGHT == []  # the ladder never steps in
        with pytest.raises(KernelPreflightError):
            ghash_pallas._tree_preflight_ok()


class TestTreeComposite:
    """Level-2+ Pallas parity through the PUBLIC ops: the forced tree
    kernel vs the XLA grouped-power path vs the host `cryptography`
    oracle, across tail/varlen/non-divisible shapes, encrypt AND
    decrypt (ISSUE 13 satellite)."""

    def _force_tree(self, monkeypatch, value: str):
        monkeypatch.setenv("TIEREDSTORAGE_TPU_PALLAS_GHASH_TREE", value)
        gcm._packed_jit.cache_clear()
        gcm._gcm_process_batch.clear_cache()
        gcm._gcm_varlen_batch.clear_cache()

    @pytest.mark.parametrize(
        "chunk_bytes,batch",
        [
            (8192, 5),       # two grouped levels, odd batch
            (8192 - 24, 3),  # tail block not 16-aligned (ct padding path)
            (2048 + 16, 9),  # just past one group: 2 groups at level 1
        ],
    )
    def test_fixed_tree_vs_ladder_vs_oracle(self, chunk_bytes, batch, monkeypatch):
        import secrets

        from cryptography.hazmat.primitives.ciphers.aead import AESGCM

        key = secrets.token_bytes(32)
        aad = secrets.token_bytes(24)
        ctx = gcm.make_context(key, aad, chunk_bytes)
        rng = np.random.default_rng(21)
        data = rng.integers(0, 256, (batch, chunk_bytes), dtype=np.uint8)
        ivs = rng.integers(0, 256, (batch, 12), dtype=np.uint8)
        ladder_ct, ladder_tags = (
            np.asarray(a) for a in gcm.gcm_encrypt_chunks(ctx, ivs, data)
        )
        self._force_tree(monkeypatch, "1")
        try:
            gcm._gcm_process_batch.clear_cache()
            tree_ct, tree_tags = (
                np.asarray(a) for a in gcm.gcm_encrypt_chunks(ctx, ivs, data)
            )
            # Decrypt through the tree too: plaintext + expected tags.
            back, expect_tags = (
                np.asarray(a)
                for a in gcm.gcm_decrypt_chunks(ctx, ivs, tree_ct)
            )
        finally:
            self._force_tree(monkeypatch, "0")
            gcm._gcm_process_batch.clear_cache()
        np.testing.assert_array_equal(tree_ct, ladder_ct)
        np.testing.assert_array_equal(tree_tags, ladder_tags)
        np.testing.assert_array_equal(back, data)
        np.testing.assert_array_equal(expect_tags, tree_tags)
        oracle = AESGCM(key)
        for i in (0, batch - 1):
            expected = oracle.encrypt(ivs[i].tobytes(), data[i].tobytes(), aad)
            assert tree_ct[i].tobytes() == expected[:-16]
            assert tree_tags[i].tobytes() == expected[-16:]

    def test_varlen_tree_vs_ladder_vs_oracle(self, monkeypatch):
        import secrets

        from cryptography.hazmat.primitives.ciphers.aead import AESGCM

        key = secrets.token_bytes(32)
        aad = secrets.token_bytes(16)
        ctx = gcm.make_varlen_context(key, aad, 6000)
        sizes = np.asarray([6000, 4097, 16, 1], np.int32)
        rng = np.random.default_rng(22)
        data = np.zeros((4, ctx.max_bytes), np.uint8)
        for i, s in enumerate(sizes):
            data[i, :s] = rng.integers(0, 256, int(s), dtype=np.uint8)
        ivs = rng.integers(0, 256, (4, 12), dtype=np.uint8)
        ladder_ct, ladder_tags = (
            np.asarray(a)
            for a in gcm.gcm_encrypt_varlen(ctx, ivs, data, sizes)
        )
        self._force_tree(monkeypatch, "1")
        try:
            gcm._gcm_varlen_batch.clear_cache()
            tree_ct, tree_tags = (
                np.asarray(a)
                for a in gcm.gcm_encrypt_varlen(ctx, ivs, data, sizes)
            )
            back, expect_tags = (
                np.asarray(a)
                for a in gcm.gcm_decrypt_varlen(ctx, ivs, tree_ct, sizes)
            )
        finally:
            self._force_tree(monkeypatch, "0")
            gcm._gcm_varlen_batch.clear_cache()
        np.testing.assert_array_equal(tree_ct, ladder_ct)
        np.testing.assert_array_equal(tree_tags, ladder_tags)
        np.testing.assert_array_equal(back, data)
        np.testing.assert_array_equal(expect_tags, tree_tags)
        oracle = AESGCM(key)
        for i, s in enumerate(sizes):
            expected = oracle.encrypt(
                ivs[i].tobytes(), data[i, :s].tobytes(), aad
            )
            assert tree_ct[i, :s].tobytes() == expected[:-16]
            assert tree_tags[i].tobytes() == expected[-16:]


def test_forced_integrated_path_matches_xla(monkeypatch):
    """The full grouped-GHASH with the kernel forced on (interpret mode)
    must produce the same node bits as the XLA plane path — through the
    public tag computation, over a multi-level tree."""
    import secrets

    from cryptography.hazmat.primitives.ciphers.aead import AESGCM

    key = secrets.token_bytes(32)
    aad = secrets.token_bytes(16)
    chunk_bytes = 8192  # m=512 blocks: two grouped levels
    ctx = gcm.make_context(key, aad, chunk_bytes)
    rng = np.random.default_rng(3)
    # Enough rows to clear the ROWS_PER_STEP gate floor with k1 dividing in.
    batch = 80
    data = rng.integers(0, 256, (batch, chunk_bytes), dtype=np.uint8)
    ivs = rng.integers(0, 256, (batch, 12), dtype=np.uint8)

    monkeypatch.setenv("TIEREDSTORAGE_TPU_PALLAS_GHASH", "1")
    gcm._gcm_process_batch.clear_cache()
    try:
        ct_f, tags_f = (
            np.asarray(a) for a in gcm.gcm_encrypt_chunks(ctx, ivs, data)
        )
    finally:
        monkeypatch.setenv("TIEREDSTORAGE_TPU_PALLAS_GHASH", "0")
        gcm._gcm_process_batch.clear_cache()

    oracle = AESGCM(key)
    for i in (0, batch // 2, batch - 1):
        expected = oracle.encrypt(ivs[i].tobytes(), data[i].tobytes(), aad)
        assert ct_f[i].tobytes() == expected[:-16]
        assert tags_f[i].tobytes() == expected[-16:]
