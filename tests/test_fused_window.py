"""The fused single-dispatch window path (PR 8 tentpole).

Three contracts, all CPU-runnable:

- **Shape eligibility is pure host logic**: `use_pallas_aes` /
  `use_pallas_ghash` must return True at the production window shapes
  (16-chunk x 4 MiB windows) on ANY platform — the platform/preflight half
  of the dispatch gate is separate (`pallas_*_available`).
- **Byte-for-byte parity**: the packed single-dispatch window ops
  (ops/gcm.py) and the TpuTransformBackend path built on them must produce
  exactly the wire bytes of the multi-dispatch ops (`gcm_encrypt_chunks` /
  `gcm_*_varlen`) and of the `cryptography` host oracle — and segments
  written by either path must decrypt byte-identically through the other
  (wire format unchanged).
- **One dispatch per window**: `DispatchStats` must record exactly one
  fused device dispatch, one h2d staging transfer, and one d2h fetch per
  window, for fixed-size, varlen, and decrypt windows.
"""

from __future__ import annotations

import os
import random

import numpy as np
import pytest

from tieredstorage_tpu.ops import gcm
from tieredstorage_tpu.security.aes import IV_SIZE, TAG_SIZE, AesEncryptionProvider
from tieredstorage_tpu.transform.api import DetransformOptions, TransformOptions
from tieredstorage_tpu.transform.tpu import TpuTransformBackend


@pytest.fixture(scope="module")
def key_pair():
    return AesEncryptionProvider.create_data_key_and_aad()


def det_ivs(n):
    return [bytes([i + 1]) * IV_SIZE for i in range(n)]


def _np_ivs(ivs):
    return np.stack([np.frombuffer(iv, dtype=np.uint8) for iv in ivs])


def _wire_fixed_multi_dispatch(dk, ivs, chunks):
    """IV || ct || tag via the MULTI-dispatch ops (gcm_encrypt_chunks)."""
    ctx = gcm.make_context(dk.data_key, dk.aad, len(chunks[0]))
    data = np.stack([np.frombuffer(c, dtype=np.uint8) for c in chunks])
    ct, tags = (np.asarray(a) for a in gcm.gcm_encrypt_chunks(ctx, _np_ivs(ivs), data))
    return [ivs[i] + ct[i].tobytes() + tags[i].tobytes() for i in range(len(chunks))]


def _wire_varlen_multi_dispatch(dk, ivs, chunks):
    """IV || ct || tag via the MULTI-dispatch varlen ops."""
    sizes = [len(c) for c in chunks]
    ctx = gcm.make_varlen_context(dk.data_key, dk.aad, max(sizes))
    data = np.zeros((len(chunks), ctx.max_bytes), dtype=np.uint8)
    for i, c in enumerate(chunks):
        data[i, : len(c)] = np.frombuffer(c, dtype=np.uint8)
    ct, tags = (
        np.asarray(a)
        for a in gcm.gcm_encrypt_varlen(
            ctx, _np_ivs(ivs), data, np.asarray(sizes, np.int32)
        )
    )
    return [
        ivs[i] + ct[i, : sizes[i]].tobytes() + tags[i].tobytes()
        for i in range(len(chunks))
    ]


# ------------------------------------------------------------------ shapes
class TestShapeEligibilityAtBenchShapes:
    """Eligibility is pure host logic — asserted on the CPU suite, at the
    shapes the benchmark's deployments launch (benchmark/configs/)."""

    @staticmethod
    def _bench_shapes(chunk_bytes: int, window: int):
        from tieredstorage_tpu.ops.gf128 import ghash_agg_plan

        m_blocks = -(-chunk_bytes // 16)
        aes_words = window * (-(-(m_blocks + 1) // 32))
        k1 = ghash_agg_plan(m_blocks)[0][0]
        ghash_rows = window * (-(-m_blocks // k1))
        return aes_words, ghash_rows, k1 * 16

    @pytest.mark.parametrize(
        "chunk_bytes,window",
        [
            (4 << 20, 16),  # a copy's window: 16 chunks x 4 MiB
            (4 << 20, 4),   # ranged-fetch prefetch window (16 MiB / 4 MiB)
            (1 << 20, 8),   # a smaller deployment: 8 chunks x 1 MiB
        ],
    )
    def test_production_window_shapes_are_eligible(self, chunk_bytes, window):
        from tieredstorage_tpu.ops.aes_pallas import use_pallas_aes
        from tieredstorage_tpu.ops.ghash_pallas import use_pallas_ghash

        aes_words, ghash_rows, k = self._bench_shapes(chunk_bytes, window)
        assert use_pallas_aes(aes_words), (chunk_bytes, window, aes_words)
        assert use_pallas_ghash(ghash_rows, k), (chunk_bytes, window, ghash_rows, k)

    def test_eligibility_needs_no_device(self, monkeypatch):
        """The verdicts must not consult the backend at all: poisoning the
        backend probe cannot change them."""
        import jax

        from tieredstorage_tpu.ops.aes_pallas import use_pallas_aes
        from tieredstorage_tpu.ops.ghash_pallas import use_pallas_ghash

        def boom():
            raise RuntimeError("backend probed")

        monkeypatch.setattr(jax, "default_backend", boom)
        assert use_pallas_aes(1 << 20)
        assert use_pallas_ghash(1 << 15, 2048)


# ------------------------------------------------------------------- parity
class TestFusedWindowParity:
    def test_fixed_window_matches_multi_dispatch_path(self, key_pair):
        rng = random.Random(1)
        chunks = [bytes(rng.getrandbits(8) for _ in range(4096)) for _ in range(8)]
        ivs = det_ivs(len(chunks))
        fused = TpuTransformBackend().transform(
            chunks, TransformOptions(encryption=key_pair, ivs=ivs)
        )
        assert fused == _wire_fixed_multi_dispatch(key_pair, ivs, chunks)

    def test_varlen_tail_window_matches_multi_dispatch_path(self, key_pair):
        rng = random.Random(2)
        sizes = [4096, 4096, 1000, 4096, 33]  # tail window shapes
        chunks = [bytes(rng.getrandbits(8) for _ in range(s)) for s in sizes]
        ivs = det_ivs(len(chunks))
        fused = TpuTransformBackend().transform(
            chunks, TransformOptions(encryption=key_pair, ivs=ivs)
        )
        assert fused == _wire_varlen_multi_dispatch(key_pair, ivs, chunks)

    def test_wire_format_unchanged_across_paths(self, key_pair):
        """Segments written before this PR (multi-dispatch ops) decrypt
        byte-identically through the fused path, and fused-written segments
        decrypt through the multi-dispatch ops — both directions, fixed and
        varlen."""
        rng = random.Random(3)
        tpu = TpuTransformBackend()
        d_opts = DetransformOptions(encryption=key_pair)
        for sizes in ([2048] * 6, [2048, 700, 2048, 51]):
            chunks = [bytes(rng.getrandbits(8) for _ in range(s)) for s in sizes]
            ivs = det_ivs(len(chunks))
            old_wire = (
                _wire_fixed_multi_dispatch(key_pair, ivs, chunks)
                if len(set(sizes)) == 1
                else _wire_varlen_multi_dispatch(key_pair, ivs, chunks)
            )
            # Old segments through the fused decrypt:
            assert tpu.detransform(old_wire, d_opts) == chunks
            # Fused-written segments are the same bytes, so the old decrypt
            # path (multi-dispatch expected-tag ops) accepts them trivially:
            new_wire = tpu.transform(
                chunks, TransformOptions(encryption=key_pair, ivs=ivs)
            )
            assert new_wire == old_wire

    def test_host_oracle_parity(self, key_pair):
        aead = pytest.importorskip(
            "cryptography.hazmat.primitives.ciphers.aead", reason="host oracle"
        )
        rng = random.Random(4)
        sizes = [1024, 1024, 387, 1024]
        chunks = [bytes(rng.getrandbits(8) for _ in range(s)) for s in sizes]
        ivs = det_ivs(len(chunks))
        wire = TpuTransformBackend().transform(
            chunks, TransformOptions(encryption=key_pair, ivs=ivs)
        )
        oracle = aead.AESGCM(key_pair.data_key)
        for i, c in enumerate(chunks):
            assert wire[i] == ivs[i] + oracle.encrypt(ivs[i], c, key_pair.aad)
            assert (
                oracle.decrypt(ivs[i], wire[i][IV_SIZE:], key_pair.aad) == c
            )

    def test_compressed_windowed_roundtrip(self, key_pair):
        """zstd-compressed (varlen) windows through transform_windows and
        back through the fused decrypt — the full production upload/fetch
        shape."""
        pytest.importorskip("zstandard", reason="zstd codec")
        rng = random.Random(5)
        chunks = [
            (b"payload=%06d " % rng.getrandbits(16)) * 64 for _ in range(9)
        ]
        opts = TransformOptions(compression=True, encryption=key_pair)
        tpu = TpuTransformBackend()
        windows = [chunks[i : i + 4] for i in range(0, len(chunks), 4)]
        wire = [c for out in tpu.transform_windows(iter(windows), opts) for c in out]
        back = tpu.detransform(
            wire,
            DetransformOptions(
                compression=True,
                encryption=key_pair,
                max_original_chunk_size=max(len(c) for c in chunks),
            ),
        )
        assert back == chunks


# ---------------------------------------------------------- forced kernels
class TestForcedKernelWindowParity:
    """TIEREDSTORAGE_TPU_PALLAS*=1 forces the Pallas kernels (interpret
    mode off-TPU) INSIDE the fused window program; the wire bytes must not
    move."""

    def test_forced_ghash_fused_window_matches_xla(self, key_pair, monkeypatch):
        rng = np.random.default_rng(6)
        # 80 rows x 512 blocks: clears the ROWS_PER_STEP floor through the
        # grouped level-1 (k1=128 -> 320 rows) like test_ghash_pallas.
        chunks = [rng.integers(0, 256, 8192, np.uint8).tobytes() for _ in range(80)]
        ivs = det_ivs(len(chunks))
        opts = TransformOptions(encryption=key_pair, ivs=ivs)
        plain = TpuTransformBackend().transform(chunks, opts)
        monkeypatch.setenv("TIEREDSTORAGE_TPU_PALLAS_GHASH", "1")
        gcm._packed_jit.cache_clear()  # force a fresh trace under the env
        try:
            forced = TpuTransformBackend().transform(chunks, opts)
        finally:
            monkeypatch.delenv("TIEREDSTORAGE_TPU_PALLAS_GHASH")
            gcm._packed_jit.cache_clear()  # don't leak forced executables
        assert forced == plain

    @pytest.mark.slow
    def test_forced_aes_fused_window_matches_xla(self, key_pair, monkeypatch):
        """Full forced mode (AES circuit kernel interpreted on XLA-CPU):
        minutes of compile, so slow-marked like the interpret end-to-end
        test in test_aes_pallas.py."""
        from tieredstorage_tpu.ops import aes_bitsliced

        rng = np.random.default_rng(7)
        chunks = [rng.integers(0, 256, 1024, np.uint8).tobytes() for _ in range(4)]
        ivs = det_ivs(len(chunks))
        opts = TransformOptions(encryption=key_pair, ivs=ivs)
        plain = TpuTransformBackend().transform(chunks, opts)
        monkeypatch.setenv("TIEREDSTORAGE_TPU_PALLAS", "1")
        monkeypatch.setattr(aes_bitsliced, "_FORCED_CROSSCHECK", [])
        gcm._packed_jit.cache_clear()
        try:
            forced = TpuTransformBackend().transform(chunks, opts)
        finally:
            monkeypatch.delenv("TIEREDSTORAGE_TPU_PALLAS")
            gcm._packed_jit.cache_clear()
        assert forced == plain


# -------------------------------------------------------- dispatch counting
class TestOneDispatchPerWindow:
    def _window_chunks(self, n_windows, per_window, size=2048, varlen=False):
        rng = random.Random(8)
        out = []
        for w in range(n_windows):
            sizes = [size] * per_window
            if varlen:
                sizes[-1] = size - 1 - w  # distinct short tail per window
            out.append(
                [bytes(rng.getrandbits(8) for _ in range(s)) for s in sizes]
            )
        return out

    @pytest.mark.parametrize("varlen", [False, True])
    def test_transform_windows_is_one_dispatch_per_window(self, key_pair, varlen):
        windows = self._window_chunks(4, 3, varlen=varlen)
        flat_ivs = det_ivs(sum(len(w) for w in windows))
        opts = TransformOptions(encryption=key_pair, ivs=flat_ivs)
        tpu = TpuTransformBackend()
        before = gcm.device_dispatches()
        out = list(tpu.transform_windows(iter(windows), opts))
        assert [len(o) for o in out] == [3, 3, 3, 3]
        stats = tpu.dispatch_stats
        assert stats.windows == 4
        assert stats.dispatches == 4
        assert stats.h2d_transfers == 4
        assert stats.d2h_fetches == 4
        assert stats.dispatches_per_window == 1.0
        assert stats.bytes_per_dispatch == stats.bytes_in // 4
        # The backend counters mirror the ops-level ground truth.
        assert gcm.device_dispatches() - before == 4

    def test_decrypt_window_is_one_dispatch(self, key_pair):
        chunks = self._window_chunks(1, 5)[0]
        opts = TransformOptions(encryption=key_pair, ivs=det_ivs(len(chunks)))
        tpu = TpuTransformBackend()
        wire = tpu.transform(chunks, opts)
        tpu.reset_dispatch_stats()
        assert tpu.detransform(wire, DetransformOptions(encryption=key_pair)) == chunks
        stats = tpu.dispatch_stats
        assert (stats.windows, stats.dispatches, stats.d2h_fetches) == (1, 1, 1)

    def test_reset_returns_retired_snapshot(self, key_pair):
        chunks = self._window_chunks(1, 2)[0]
        opts = TransformOptions(encryption=key_pair, ivs=det_ivs(len(chunks)))
        tpu = TpuTransformBackend()
        tpu.transform(chunks, opts)
        retired = tpu.reset_dispatch_stats()
        assert retired.windows == 1 and retired.dispatches == 1
        assert tpu.dispatch_stats.windows == 0
        assert retired.as_dict()["dispatches_per_window"] == 1.0
        assert "hbm_roundtrips_per_window" in retired.as_dict()


# ---------------------------------------------------- HBM round trips (13)
class TestHbmRoundtripAccounting:
    """ISSUE 13: `planned_hbm_roundtrips` mirrors the GHASH strategy branch
    and the backend gates windows on it. Fused tree = exactly 1 (the
    keystream handoff); XLA ladder = 1 + one per level >= 2 (+1 for the
    plane path); the counter must separate the paths."""

    def _clear(self):
        gcm._packed_jit.cache_clear()
        gcm._gcm_process_batch.clear_cache()
        gcm._gcm_varlen_batch.clear_cache()

    def test_planned_counts_fixed(self, key_pair, monkeypatch):
        # 32 KiB chunk: m=2048 -> plan [(128,2048),(16,16)] = two levels.
        ctx = gcm.make_context(key_pair.data_key, key_pair.aad, 32 << 10)
        assert len(ctx.agg_mats) == 2
        monkeypatch.delenv("TIEREDSTORAGE_TPU_PALLAS_GHASH", raising=False)
        monkeypatch.delenv("TIEREDSTORAGE_TPU_PALLAS_GHASH_TREE", raising=False)
        # CPU default: XLA plane level 1 + one inter-level trip + handoff.
        assert gcm.planned_hbm_roundtrips(ctx, 4) == 3
        # Forced tree: the one keystream handoff only.
        monkeypatch.setenv("TIEREDSTORAGE_TPU_PALLAS_GHASH_TREE", "1")
        assert gcm.planned_hbm_roundtrips(ctx, 4) == 1

    def test_planned_counts_single_level(self, key_pair, monkeypatch):
        # 1024-byte chunk: m=64 -> one grouped level, no ladder trips; the
        # tree is NOT eligible (nothing to aggregate) and not needed.
        ctx = gcm.make_context(key_pair.data_key, key_pair.aad, 1024)
        assert len(ctx.agg_mats) == 1
        monkeypatch.setenv("TIEREDSTORAGE_TPU_PALLAS_GHASH_TREE", "1")
        assert gcm.planned_hbm_roundtrips(ctx, 4) == 2  # handoff + planes
        monkeypatch.setenv("TIEREDSTORAGE_TPU_PALLAS_GHASH", "1")
        assert gcm.planned_hbm_roundtrips(ctx, 512) == 1  # L1 kernel

    def test_window_accounting_tree_vs_ladder(self, key_pair, monkeypatch):
        rng = random.Random(31)
        windows = [
            [bytes(rng.getrandbits(8) for _ in range(32 << 10)) for _ in range(2)]
            for _ in range(2)
        ]
        flat_ivs = det_ivs(4)
        opts = TransformOptions(encryption=key_pair, ivs=flat_ivs)

        monkeypatch.delenv("TIEREDSTORAGE_TPU_PALLAS_GHASH_TREE", raising=False)
        self._clear()
        ladder = TpuTransformBackend()
        ladder_out = list(ladder.transform_windows(iter(windows), opts))
        assert ladder.dispatch_stats.hbm_roundtrips_per_window > 1.0

        monkeypatch.setenv("TIEREDSTORAGE_TPU_PALLAS_GHASH_TREE", "1")
        self._clear()
        try:
            tree = TpuTransformBackend()
            tree_out = list(tree.transform_windows(iter(windows), opts))
            stats = tree.dispatch_stats
            assert stats.hbm_roundtrips_per_window == 1.0
            assert stats.hbm_roundtrips == stats.windows == 2
            assert stats.as_dict()["hbm_roundtrips_per_window"] == 1.0
        finally:
            self._clear()
        # Same wire either way: only the reduction strategy moved on-chip.
        assert tree_out == ladder_out


@pytest.mark.skipif(
    os.environ.get("TIEREDSTORAGE_TPU_PALLAS") == "1",
    reason="forced mode changes the dispatched program on purpose",
)
def test_module_counter_is_monotone(key_pair):
    before = gcm.device_dispatches()
    ctx = gcm.make_context(key_pair.data_key, key_pair.aad, 256)
    data = np.zeros((2, 256 + TAG_SIZE), np.uint8)
    gcm.gcm_window_packed(ctx, None, data, decrypt=False)
    assert gcm.device_dispatches() == before + 1


def test_two_threads_one_backend_exact_counters(key_pair):
    """ISSUE 10: the DispatchStats discipline is a GUARD (`_stats_lock`),
    not a single-thread convention — one backend instance serves concurrent
    upload/fetch windows on the gateway worker pool. Two threads driving
    the SAME backend concurrently must land exact counters: a torn
    `+=` would lose updates, and a process-global launch delta would let
    one thread's dispatch inflate the other's window (the per-thread
    `ops.gcm.thread_dispatches` delta source keeps attribution exact)."""
    import threading

    rng = random.Random(23)
    per_thread = 5
    chunk = bytes(rng.getrandbits(8) for _ in range(2048))
    tpu = TpuTransformBackend()
    opts = TransformOptions(encryption=key_pair)
    # Compile the one (shape, donation) executable before the race so both
    # threads hit the steady-state dispatch path.
    tpu.transform([chunk, chunk], opts)
    tpu.reset_dispatch_stats()

    barrier = threading.Barrier(2)
    errors: list = []

    def work():
        try:
            barrier.wait()
            for _ in range(per_thread):
                out = tpu.transform([chunk, chunk], opts)
                assert len(out) == 2
        except Exception as e:  # noqa: BLE001 - surfaced below
            errors.append(e)

    threads = [threading.Thread(target=work, daemon=True) for _ in range(2)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
    assert errors == []

    stats = tpu.dispatch_stats
    total = 2 * per_thread
    assert stats.windows == total
    assert stats.dispatches == total
    assert stats.h2d_transfers == total
    assert stats.d2h_fetches == total
    assert stats.bytes_in == total * 2 * len(chunk)
    assert stats.dispatches_per_window == 1.0
