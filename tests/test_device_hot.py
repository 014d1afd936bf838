"""Device hot-window cache tier (fetch/cache/device_hot.py, ISSUE 12).

Covers the admission/eviction state machine with a fake delegate (host-only
windows), the decrypt-capture integration with the real TpuTransformBackend
(device retention, the donation-vs-retention probe, device-side ranged
slicing), the fleet interaction (a peer forward served from the owner's hot
tier), and the factory/metrics wiring. The sketch and budget arithmetic
assertions are exact on purpose — this module is a mutation target
(tools/mutation_test.py DEFAULT_TARGETS)."""

from __future__ import annotations

import io
import random
import threading

import numpy as np
import pytest

from tieredstorage_tpu.fetch.cache.device_hot import (
    DeviceHotCache,
    FrequencySketch,
    HotWindow,
    _window_key,
    capture_scope,
    note_detransform,
    offer_decrypt_window,
)
from tieredstorage_tpu.fetch.chunk_manager import ChunkManager
from tieredstorage_tpu.storage.core import ObjectKey

CHUNK = 64
KEY = ObjectKey("pre/topic-hot/3/00000000000000000042-uuid.log")
OTHER_KEY = ObjectKey("pre/topic-hot/3/00000000000000000099-uuid.log")


class CountingManager(ChunkManager):
    """Fake delegate: chunk i is bytes([i % 251]) * CHUNK; counts calls."""

    def __init__(self):
        self.calls: list[tuple[str, tuple[int, ...]]] = []
        self._lock = threading.Lock()

    def get_chunk(self, objects_key, manifest, chunk_id):
        return io.BytesIO(self.get_chunks(objects_key, manifest, [chunk_id])[0])

    def get_chunks(self, objects_key, manifest, chunk_ids):
        with self._lock:
            self.calls.append((objects_key.value, tuple(chunk_ids)))
        return [bytes([cid % 251]) * CHUNK for cid in chunk_ids]


def expected(chunk_ids):
    return [bytes([cid % 251]) * CHUNK for cid in chunk_ids]


def make_hot(budget_windows: float = 64, *, admission_hits=2, delegate=None,
             sketch_width=64):
    """Hot tier over the fake delegate; budget in units of 4-chunk windows
    (mirror-only: 4 * CHUNK bytes per window)."""
    delegate = delegate if delegate is not None else CountingManager()
    hot = DeviceHotCache(
        delegate,
        budget_bytes=int(budget_windows * 4 * CHUNK),
        admission_hits=admission_hits,
        sketch_width=sketch_width,
    )
    return hot, delegate


# ------------------------------------------------------------------- sketch
class TestFrequencySketch:
    def test_width_rounds_up_to_power_of_two(self):
        assert FrequencySketch(100).width == 128
        assert FrequencySketch(128).width == 128
        assert FrequencySketch(1).width == 1

    def test_rejects_non_positive_width(self):
        with pytest.raises(ValueError):
            FrequencySketch(0)

    def test_touch_counts_exactly(self):
        sketch = FrequencySketch(64)
        assert sketch.estimate("k") == 0
        for i in range(1, 6):
            assert sketch.touch("k") == i
        assert sketch.estimate("k") == 5
        # Independent key unaffected (distinct CRC columns at this width).
        assert sketch.estimate("another") < 5

    def test_deterministic_across_instances(self):
        a, b = FrequencySketch(64), FrequencySketch(64)
        for _ in range(3):
            a.touch("key-x")
            b.touch("key-x")
        assert a.estimate("key-x") == b.estimate("key-x") == 3

    def test_saturates_at_max(self):
        sketch = FrequencySketch(16, decay_every=10**9)
        for _ in range(300):
            sketch.touch("k")
        assert sketch.estimate("k") == FrequencySketch.MAX_COUNT

    def test_decay_halves_counts(self):
        sketch = FrequencySketch(16, decay_every=8)
        for _ in range(7):
            sketch.touch("k")
        assert sketch.estimate("k") == 7
        # The 8th touch triggers the halving FIRST, then counts itself.
        assert sketch.touch("k") == 4
        assert sketch.estimate("k") == 4

    def test_estimate_is_min_over_rows(self):
        sketch = FrequencySketch(4, decay_every=10**9)  # tiny: collisions
        for _ in range(10):
            sketch.touch("a")
        # A colliding key can only ever over-estimate, never exceed the
        # most-touched key's count.
        assert sketch.estimate("b") <= sketch.estimate("a")


# --------------------------------------------------- admission and eviction
class TestAdmission:
    def test_first_touch_not_admitted_second_touch_is(self):
        hot, delegate = make_hot()
        ids = [0, 1, 2, 3]
        assert hot.get_chunks(KEY, None, ids) == expected(ids)
        assert (hot.resident_windows, hot.admissions, hot.rejections) == (0, 0, 1)
        assert hot.get_chunks(KEY, None, ids) == expected(ids)
        assert (hot.resident_windows, hot.admissions) == (1, 1)
        assert len(delegate.calls) == 2
        # Third read: hot hit, delegate untouched.
        assert hot.get_chunks(KEY, None, ids) == expected(ids)
        assert len(delegate.calls) == 2
        assert (hot.hits, hot.misses) == (1, 2)
        assert hot.chunks_served == 4

    def test_admission_hits_one_admits_immediately(self):
        hot, delegate = make_hot(admission_hits=1)
        ids = [4, 5]
        hot.get_chunks(KEY, None, ids)
        assert (hot.resident_windows, hot.admissions, hot.rejections) == (1, 1, 0)

    def test_disabled_budget_never_admits(self):
        hot, delegate = make_hot(0)
        for _ in range(3):
            hot.get_chunks(KEY, None, [0, 1])
        assert hot.resident_windows == 0
        assert len(delegate.calls) == 3
        # budget_bytes == 0 means the tier is OFF: no admission accounting
        # at all (no sketch touches, no rejection counts) — not merely
        # "rejected as oversize".
        assert (hot.admissions, hot.rejections, hot.evictions) == (0, 0, 0)
        assert hot._sketch.estimate(_window_key(KEY.value.rsplit("/", 1)[-1],
                                                (0, 1))) == 0

    def test_oversize_window_rejected(self):
        hot, _ = make_hot(0.5)  # budget: half a window
        for _ in range(2):
            hot.get_chunks(KEY, None, [0, 1, 2, 3])
        assert hot.resident_windows == 0
        assert hot.rejections == 2  # one below-threshold, one oversize

    def test_byte_accounting_exact(self):
        hot, _ = make_hot()
        for _ in range(2):
            hot.get_chunks(KEY, None, [0, 1, 2, 3])
            hot.get_chunks(KEY, None, [4, 5])
        assert hot.resident_windows == 2
        assert hot.resident_bytes == 4 * CHUNK + 2 * CHUNK
        assert hot.resident_device_bytes == 0  # host-only (no capture)
        assert hot.device_windows == 0

    def test_hit_rate(self):
        hot, _ = make_hot(admission_hits=1)
        hot.get_chunks(KEY, None, [0, 1])          # miss
        hot.get_chunks(KEY, None, [0, 1])          # hit
        hot.get_chunks(KEY, None, [0, 1])          # hit
        hot.get_chunks(KEY, None, [8, 9])          # miss
        assert hot.hits == 2 and hot.misses == 2
        assert hot.hit_rate == 0.5


class TestEviction:
    def test_budget_exceeded_evicts_lru_order(self):
        # Budget fits exactly 2 windows; windows admitted on first touch so
        # the sketch frequencies tie (candidate 1 >= victim 1 — no TinyLFU
        # veto) and pure LRU order decides.
        hot, _ = make_hot(2, admission_hits=1)
        hot.get_chunks(KEY, None, [0, 1, 2, 3])    # A
        hot.get_chunks(KEY, None, [4, 5, 6, 7])    # B
        assert hot.resident_windows == 2
        hot.get_chunks(KEY, None, [8, 9, 10, 11])  # C evicts A (coldest)
        assert hot.evictions == 1
        assert hot.window(KEY, 0) is None
        assert hot.window(KEY, 4) is not None
        assert hot.window(KEY, 8) is not None
        hot.get_chunks(KEY, None, [12, 13, 14, 15])  # D evicts B
        assert hot.evictions == 2
        assert hot.window(KEY, 4) is None

    def test_hit_refreshes_lru_position(self):
        hot, _ = make_hot(2, admission_hits=1)
        hot.get_chunks(KEY, None, [0, 1, 2, 3])    # A
        hot.get_chunks(KEY, None, [4, 5, 6, 7])    # B
        hot.get_chunks(KEY, None, [0, 1, 2, 3])    # hit A -> B is now LRU
        hot.get_chunks(KEY, None, [8, 9, 10, 11])  # C evicts B, not A
        assert hot.window(KEY, 0) is not None
        assert hot.window(KEY, 4) is None

    def test_tinylfu_veto_protects_hotter_victim(self):
        # Victim A is touched 4x (2 misses + 2 hits); candidate B arrives
        # with frequency 2 — A's estimate (4) > B's (2), so B is REJECTED
        # and A stays resident.
        hot, _ = make_hot(1)
        for _ in range(2):
            hot.get_chunks(KEY, None, [0, 1, 2, 3])      # admit A (freq 2)
        for _ in range(2):
            hot.get_chunks(KEY, None, [0, 1, 2, 3])      # 2 hits (freq 4)
        rejections_before = hot.rejections
        for _ in range(2):
            hot.get_chunks(KEY, None, [4, 5, 6, 7])      # B: freq 2 < 4
        assert hot.window(KEY, 0) is not None             # A survived
        assert hot.window(KEY, 4) is None                 # B refused
        assert hot.rejections == rejections_before + 2
        assert hot.evictions == 0
        # B keeps getting touched; once its frequency passes A's it wins.
        for _ in range(4):
            hot.get_chunks(KEY, None, [4, 5, 6, 7])
        assert hot.window(KEY, 4) is not None
        assert hot.window(KEY, 0) is None
        assert hot.evictions == 1

    def test_eviction_keeps_overlapping_covers(self):
        hot, _ = make_hot(3, admission_hits=1)
        hot.get_chunks(KEY, None, [0, 1, 2, 3])    # A covers 0-3
        hot.get_chunks(KEY, None, [2, 3, 4, 5])    # B re-covers 2-3
        assert hot.resident_windows == 2
        # Evicting A (LRU) must not drop chunks 2-3, which point at B now.
        hot.get_chunks(KEY, None, [8, 9, 10, 11])
        hot.get_chunks(KEY, None, [12, 13, 14, 15])
        assert hot.window(KEY, 0) is None
        assert hot.window(KEY, 2) is not None
        assert hot.get_chunks(KEY, None, [2, 3]) == expected([2, 3])
        assert hot.hits >= 1


class TestServe:
    def test_subset_and_spanning_requests_served_hot(self):
        hot, delegate = make_hot(admission_hits=1)
        hot.get_chunks(KEY, None, [0, 1, 2, 3])
        hot.get_chunks(KEY, None, [4, 5, 6, 7])
        calls = len(delegate.calls)
        # Subset of one window and a span across both windows.
        assert hot.get_chunks(KEY, None, [2, 3]) == expected([2, 3])
        assert hot.get_chunks(KEY, None, [3, 4]) == expected([3, 4])
        assert len(delegate.calls) == calls
        assert hot.hits == 2

    def test_gap_delegates_whole_window(self):
        hot, delegate = make_hot(admission_hits=1)
        hot.get_chunks(KEY, None, [0, 1])
        assert hot.get_chunks(KEY, None, [1, 2]) == expected([1, 2])
        assert delegate.calls[-1] == (KEY.value, (1, 2))
        assert hot.misses == 2

    def test_distinct_segments_do_not_collide(self):
        hot, _ = make_hot(admission_hits=1)
        hot.get_chunks(KEY, None, [0, 1])
        assert hot.window(OTHER_KEY, 0) is None
        hot.get_chunks(OTHER_KEY, None, [0, 1])
        assert hot.resident_windows == 2

    def test_empty_request(self):
        hot, delegate = make_hot()
        assert hot.get_chunks(KEY, None, []) == []
        assert delegate.calls == []

    def test_get_chunk_single(self):
        hot, _ = make_hot(admission_hits=1)
        hot.get_chunks(KEY, None, [7])
        assert hot.get_chunk(KEY, None, 7).read() == expected([7])[0]
        assert hot.hits == 1

    def test_close_releases_residency_and_chains(self):
        class ClosableManager(CountingManager):
            closed = False

            def close(self):
                self.closed = True

        delegate = ClosableManager()
        hot, _ = make_hot(admission_hits=1, delegate=delegate)
        hot.get_chunks(KEY, None, [0, 1])
        hot.close()
        assert hot.resident_windows == 0 and hot.resident_bytes == 0
        assert delegate.closed

    def test_concurrent_replay_is_consistent(self):
        hot, delegate = make_hot(admission_hits=1)
        windows = [[0, 1, 2, 3], [4, 5, 6, 7], [8, 9, 10, 11]]
        for ids in windows:
            hot.get_chunks(KEY, None, ids)
        errors: list = []

        def reader(seed):
            rng = random.Random(seed)
            for _ in range(50):
                ids = windows[rng.randrange(3)]
                if hot.get_chunks(KEY, None, ids) != expected(ids):
                    errors.append(seed)

        threads = [threading.Thread(target=reader, args=(i,)) for i in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert errors == []
        assert len(delegate.calls) == 3  # replay never re-delegated


# ------------------------------------------------------- capture primitives
class TestCapturePrimitives:
    def test_offer_outside_scope_is_dropped(self):
        offer_decrypt_window(object(), [1], 1, 1)  # must not raise or leak
        with capture_scope() as cap:
            pass
        assert cap.windows == []

    def test_scope_snapshot_survives_exit(self):
        with capture_scope() as cap:
            offer_decrypt_window("dev", [3, 3], 3, 2)
            note_detransform("opts")
        assert cap.windows == [("dev", (3, 3), 3, 2)]
        assert cap.opts == "opts"

    def test_scopes_nest_and_restore(self):
        with capture_scope() as outer:
            offer_decrypt_window("a", [1], 1, 1)
            with capture_scope() as inner:
                offer_decrypt_window("b", [2], 2, 1)
            offer_decrypt_window("c", [3], 3, 1)
        assert [w[0] for w in inner.windows] == ["b"]
        assert [w[0] for w in outer.windows] == ["a", "c"]

    def test_capture_is_thread_local(self):
        seen: list = []

        def other():
            offer_decrypt_window("other-thread", [1], 1, 1)
            seen.append(True)

        with capture_scope() as cap:
            t = threading.Thread(target=other)
            t.start()
            t.join()
        assert seen and cap.windows == []


class TestHotWindow:
    def test_ranged_slices(self):
        chunks = [b"a" * 8, b"bb" * 4, b"c" * 4]
        mirror = np.frombuffer(b"".join(chunks), dtype=np.uint8)
        w = HotWindow(
            key="f#0-2", file="f", chunk_ids=(5, 6, 7),
            mirror=mirror, offsets=(0, 8, 16), lens=(8, 8, 4),
        )
        assert w.chunk(5) == chunks[0]
        assert w.chunk(6) == chunks[1]
        assert w.chunk(7) == chunks[2]
        assert w.covers(6) and not w.covers(4)
        assert w.row_of(7) == 2
        assert w.nbytes == 20

    def test_window_key_format(self):
        assert _window_key("seg.log", (4, 5, 6)) == "seg.log#4-6"


# ---------------------------------------------- real-backend device capture
jax = pytest.importorskip("jax")

from tieredstorage_tpu.fetch.chunk_manager import DefaultChunkManager  # noqa: E402
from tieredstorage_tpu.manifest.chunk_index import FixedSizeChunkIndex  # noqa: E402
from tieredstorage_tpu.manifest.encryption_metadata import (  # noqa: E402
    SegmentEncryptionMetadataV1,
)
from tieredstorage_tpu.manifest.segment_indexes import (  # noqa: E402
    IndexType,
    SegmentIndexesV1Builder,
)
from tieredstorage_tpu.manifest.segment_manifest import SegmentManifestV1  # noqa: E402
from tieredstorage_tpu.ops import gcm  # noqa: E402
from tieredstorage_tpu.security.aes import AesEncryptionProvider  # noqa: E402
from tieredstorage_tpu.transform.api import TransformOptions  # noqa: E402
from tieredstorage_tpu.transform.tpu import TpuTransformBackend  # noqa: E402

ENC_CHUNK = 4096


class _BlobFetcher:
    def __init__(self, blob: bytes) -> None:
        self._blob = blob

    def fetch(self, key, r):
        return io.BytesIO(self._blob[r.from_position : r.to_position + 1])


def encrypted_store(n_chunks=8, chunk=ENC_CHUNK):
    rng = random.Random(11)
    chunks = [bytes(rng.getrandbits(8) for _ in range(chunk)) for _ in range(n_chunks)]
    dk = AesEncryptionProvider.create_data_key_and_aad()
    backend = TpuTransformBackend()
    ivs = [i.to_bytes(4, "big") * 3 for i in range(1, n_chunks + 1)]
    blob = b"".join(backend.transform(chunks, TransformOptions(encryption=dk, ivs=ivs)))
    index = FixedSizeChunkIndex(
        original_chunk_size=chunk, original_file_size=chunk * n_chunks,
        transformed_chunk_size=chunk + 28, final_transformed_chunk_size=chunk + 28,
    )
    builder = SegmentIndexesV1Builder()
    for t in (IndexType.OFFSET, IndexType.TIMESTAMP,
              IndexType.PRODUCER_SNAPSHOT, IndexType.LEADER_EPOCH):
        builder.add(t, 0)
    manifest = SegmentManifestV1(
        chunk_index=index, segment_indexes=builder.build(), compression=False,
        encryption=SegmentEncryptionMetadataV1(dk.data_key, dk.aad),
        remote_log_segment_metadata=None,
    )
    default = DefaultChunkManager(_BlobFetcher(blob), backend)
    return chunks, backend, default, manifest


class TestDeviceCapture:
    def test_decrypt_window_retained_and_served_without_dispatches(self):
        chunks, backend, default, manifest = encrypted_store()
        hot = DeviceHotCache(
            default, backend, innermost=default, budget_bytes=1 << 30,
        )
        ids = [0, 1, 2, 3]
        assert hot.get_chunks(KEY, manifest, ids) == chunks[:4]
        assert hot.device_windows == 0  # first touch rejected
        assert hot.get_chunks(KEY, manifest, ids) == chunks[:4]
        assert hot.device_windows == 1
        w = hot.window(KEY, 0)
        assert w.device is not None and w.n_bytes == ENC_CHUNK
        # Device accounting: B rows of (n_bytes + 16) tag columns.
        assert hot.resident_device_bytes == 4 * (ENC_CHUNK + 16)
        assert hot.resident_bytes == 4 * ENC_CHUNK + 4 * (ENC_CHUNK + 16)
        before = gcm.device_dispatches()
        assert hot.get_chunks(KEY, manifest, ids) == chunks[:4]
        assert hot.get_chunks(KEY, manifest, [1, 2]) == chunks[1:3]
        assert gcm.device_dispatches() - before == 0

    def test_retained_buffer_is_never_the_donated_operand(self):
        """Donation-vs-retention: decrypt donates the STAGED ciphertext
        input; the retained output allocation must stay live (the
        use-after-donate probe, inverted) across further donated windows."""
        chunks, backend, default, manifest = encrypted_store()
        hot = DeviceHotCache(
            default, backend, innermost=default, budget_bytes=1 << 30,
            admission_hits=1,
        )
        hot.get_chunks(KEY, manifest, [0, 1, 2, 3])
        w = hot.window(KEY, 0)
        assert w.device is not None and not w.device.is_deleted()
        # More windows through the SAME backend: each donates its own
        # staged buffer. Retention must be unaffected.
        dk2 = AesEncryptionProvider.create_data_key_and_aad()
        for _ in range(2):
            backend.transform(chunks[:4], TransformOptions(encryption=dk2))
        hot.get_chunks(KEY, manifest, [4, 5, 6, 7])
        assert not w.device.is_deleted()
        assert np.asarray(w.device)[0, :ENC_CHUNK].tobytes() == chunks[0]

    @pytest.mark.parametrize("aligned", [False, True])
    def test_retained_window_survives_reuse_of_its_staging_buffer(self, aligned):
        """The backend stages every window of a shape in one reused host
        buffer (its staging ring). A window the hot tier retained must
        still read back its plaintext after that buffer has been packed
        with another chunk's ciphertext. `aligned` seeds the ring with a
        64-byte-aligned buffer, which the CPU backend's `device_put`
        places with no copy: the staged array then IS the host buffer, and
        only the retained OUTPUT allocation keeps the tier safe."""
        chunks, backend, default, manifest = encrypted_store()
        hot = DeviceHotCache(
            default, backend, innermost=default, budget_bytes=1 << 30,
            admission_hits=1,
        )
        shape = (1, ENC_CHUNK + 16)
        if aligned:
            raw = np.empty(shape[1] + 64, np.uint8)
            offset = (-raw.ctypes.data) % 64
            backend._release_staging(raw[offset : offset + shape[1]].reshape(shape))
        backend.reset_dispatch_stats()
        real_stage, zero_copy = backend._stage_packed, []

        def spy_stage(packed, varlen):
            staged = real_stage(packed, varlen)
            zero_copy.append(staged.unsafe_buffer_pointer() == packed.ctypes.data)
            return staged

        backend._stage_packed = spy_stage
        assert hot.get_chunks(KEY, manifest, [0]) == chunks[:1]
        retained = hot.window(KEY, 0)
        assert retained.device is not None
        for cid in range(1, 6):  # the same one-row shape, the same buffer
            assert hot.get_chunks(KEY, manifest, [cid]) == [chunks[cid]]
        stats = backend.dispatch_stats
        assert stats.staging_acquired == 6
        assert stats.staging_reused == (6 if aligned else 5)
        assert len(backend._staging_free[shape]) == 1
        if aligned and jax.default_backend() == "cpu":
            assert zero_copy == [True] * 6  # the hazard was really there
        assert not retained.device.is_deleted()
        assert np.asarray(retained.device)[0, :ENC_CHUNK].tobytes() == chunks[0]
        for cid in range(6):
            (row,) = hot.device_rows(KEY, [cid])
            assert np.asarray(row)[:ENC_CHUNK].tobytes() == chunks[cid]
        assert hot.get_chunks(KEY, manifest, [0]) == chunks[:1]

    def test_device_rows_match_mirror(self):
        chunks, backend, default, manifest = encrypted_store()
        hot = DeviceHotCache(
            default, backend, innermost=default, budget_bytes=1 << 30,
            admission_hits=1,
        )
        hot.get_chunks(KEY, manifest, [0, 1, 2, 3])
        rows = hot.device_rows(KEY, [1, 3])
        assert rows is not None
        for row, cid in zip(rows, [1, 3]):
            assert np.asarray(row)[:ENC_CHUNK].tobytes() == chunks[cid]

    def test_device_rows_none_on_gap_or_hostonly(self):
        hot, _ = make_hot(admission_hits=1)
        hot.get_chunks(KEY, None, [0, 1])
        assert hot.device_rows(KEY, [0, 1]) is None  # host-only window
        assert hot.device_rows(KEY, [5]) is None     # not resident

    def test_compressed_window_keeps_mirror_only(self):
        """When a compression stage follows the decrypt, the captured rows
        are still-compressed frames — only the host mirror is kept."""
        chunks, backend, default, manifest = encrypted_store()
        compressed = SegmentManifestV1(
            chunk_index=manifest.chunk_index,
            segment_indexes=manifest.segment_indexes,
            compression=True,
            encryption=manifest.encryption,
            remote_log_segment_metadata=None,
        )
        hot = DeviceHotCache(
            default, backend, innermost=default, budget_bytes=1 << 30,
            admission_hits=1,
        )
        with capture_scope() as cap:
            got = default.get_chunks(KEY, manifest, [0, 1])
        assert len(cap.windows) == 1  # the hook fires either way
        window = hot._build_window("f#0-1", "f", (0, 1), got, cap)
        assert window.device is not None  # uncompressed: retained
        cap.opts = type(cap.opts)(
            compression=True, encryption=cap.opts.encryption,
            max_original_chunk_size=cap.opts.max_original_chunk_size,
        )
        window = hot._build_window("f#0-1", "f", (0, 1), got, cap)
        assert window.device is None  # compressed: mirror only
        assert window.nbytes == 2 * ENC_CHUNK

    def test_hookless_collaborators_left_untouched(self):
        """Constructor wiring is gated on hasattr BOTH sides: a backend or
        innermost manager without the hook attribute must not grow one."""

        class Bare:
            pass

        backend, innermost = Bare(), Bare()
        DeviceHotCache(None, backend, innermost=innermost, budget_bytes=1)
        assert not hasattr(backend, "on_decrypt_window")
        assert not hasattr(innermost, "on_detransform")

    def test_device_nbytes_prefers_buffer_attr(self):
        """HBM accounting takes the buffer's own nbytes when it has one
        (padded/sharded buffers are bigger than B rows)."""

        class StubBuf:
            nbytes = 99_999

            def is_deleted(self):
                return False

        hot, _ = make_hot()
        cap = type("C", (), {})()
        chunks = [b"x" * 8, b"y" * 8]
        cap.windows = [(StubBuf(), (8, 8), 8, 1)]
        cap.opts = type("O", (), {"compression": False})()
        window = hot._build_window("f#0-1", "f", (0, 1), chunks, cap)
        assert window.device is not None
        assert window.device_nbytes == 99_999
        assert window.nbytes == 16 + 99_999

    def test_device_nbytes_fallback_is_rows_times_padded_columns(self):
        """Without an nbytes attribute the accounting falls back to
        B * (n_bytes + 16 tag columns), exactly."""

        class NoNbytes:
            def is_deleted(self):
                return False

        hot, _ = make_hot()
        cap = type("C", (), {})()
        chunks = [b"x" * 8, b"y" * 8, b"z" * 8]
        cap.windows = [(NoNbytes(), (8, 8, 8), 8, 1)]
        cap.opts = type("O", (), {"compression": False})()
        window = hot._build_window("f#0-2", "f", (0, 1, 2), chunks, cap)
        assert window.device is not None
        assert window.device_nbytes == 3 * (8 + 16)

    def test_size_mismatch_drops_device_half(self):
        chunks, backend, default, manifest = encrypted_store()
        hot = DeviceHotCache(default, backend, budget_bytes=1 << 30)
        with capture_scope() as cap:
            got = default.get_chunks(KEY, manifest, [0, 1])
        cap.windows = [(cap.windows[0][0], (1, 2), ENC_CHUNK, 1)]
        window = hot._build_window("f#0-1", "f", (0, 1), got, cap)
        assert window.device is None


# ----------------------------------------------------------- fleet interplay
class TestFleetInteraction:
    def test_peer_forward_served_from_owner_hot_tier(self):
        """A non-owner's PeerChunkCache forward is answered by the OWNER's
        full chunk path — with the owner's hot tier warm, the forward is a
        hot serve: zero GCM dispatches on the owner, bytes identical."""
        import http.server

        from tieredstorage_tpu.fleet.peer_cache import (
            PeerChunkCache,
            encode_chunk_frames,
        )
        from tests.test_fleet import _peer_router

        chunks, backend, owner_default, manifest = encrypted_store()
        owner_hot = DeviceHotCache(
            owner_default, backend, innermost=owner_default,
            budget_bytes=1 << 30, admission_hits=1,
        )
        owner_hot.get_chunks(KEY, manifest, [0, 1, 2, 3])  # warm the owner
        assert owner_hot.resident_windows == 1

        class Handler(http.server.BaseHTTPRequestHandler):
            def log_message(self, *a):
                pass

            def do_GET(self):
                # The owner serves forwards through its full chunk path.
                window = owner_hot.get_chunks(KEY, manifest, [0, 1, 2, 3])
                body = encode_chunk_frames(window)
                self.send_response(200)
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

        server = http.server.ThreadingHTTPServer(("127.0.0.1", 0), Handler)
        threading.Thread(target=server.serve_forever, daemon=True).start()
        local_delegate = CountingManager()
        peer = PeerChunkCache(
            local_delegate,
            _peer_router(f"http://127.0.0.1:{server.server_address[1]}"),
        )
        try:
            hits_before = owner_hot.hits
            before = gcm.device_dispatches()
            got = peer.get_chunks(KEY, manifest, [0, 1, 2, 3])
            assert got == chunks[:4]
            assert owner_hot.hits == hits_before + 1
            assert gcm.device_dispatches() - before == 0
            assert local_delegate.calls == []  # served by the owner
            assert (peer.forwards, peer.peer_hits) == (1, 1)
        finally:
            server.shutdown()
            server.server_close()
            peer.close()


# --------------------------------------------------------- factory + wiring
class TestFactoryWiring:
    def test_disabled_by_default(self):
        from tieredstorage_tpu.fetch.factory import ChunkManagerFactory

        factory = ChunkManagerFactory()
        factory.configure({})
        manager = factory.init_chunk_manager(None, None)
        assert factory.device_hot_cache is None
        assert isinstance(manager, DefaultChunkManager)

    def test_hot_tier_between_cache_and_inner_wrapper(self):
        from tieredstorage_tpu.fetch.cache.memory import MemoryChunkCache
        from tieredstorage_tpu.fetch.factory import ChunkManagerFactory

        factory = ChunkManagerFactory()
        factory.configure({
            "fetch.chunk.cache.class":
                "tieredstorage_tpu.fetch.cache.memory.MemoryChunkCache",
            "fetch.chunk.cache.size": 1 << 20,
            "cache.device.bytes": 1 << 20,
            "cache.device.admission.hits": 3,
            "cache.device.sketch.width": 100,
        })
        wrapped: list = []

        def wrapper(default):
            wrapped.append(default)
            return default

        backend = TpuTransformBackend()
        manager = factory.init_chunk_manager(None, backend, wrapper)
        try:
            hot = factory.device_hot_cache
            assert isinstance(manager, MemoryChunkCache)
            assert manager._delegate is hot
            assert hot.delegate is wrapped[0]
            assert hot.budget_bytes == 1 << 20
            assert hot.admission_hits == 3
            assert hot._sketch.width == 128
            # The capture hooks were wired to the backend + innermost.
            assert backend.on_decrypt_window is offer_decrypt_window
            assert wrapped[0].on_detransform is note_detransform
        finally:
            manager.close()

    def test_budget_validation(self):
        from tieredstorage_tpu.fetch.factory import ChunkManagerFactoryConfig

        with pytest.raises(Exception):
            ChunkManagerFactoryConfig({"cache.device.bytes": -1})
        with pytest.raises(Exception):
            ChunkManagerFactoryConfig({"cache.device.admission.hits": 0})

    def test_rsm_wires_hot_tier(self, tmp_path):
        from tieredstorage_tpu.rsm import RemoteStorageManager

        rsm = RemoteStorageManager()
        rsm.configure({
            "storage.backend.class":
                "tieredstorage_tpu.storage.memory.InMemoryStorage",
            "chunk.size": 4096,
            "transform.backend.class":
                "tieredstorage_tpu.transform.tpu.TpuTransformBackend",
            "cache.device.bytes": 1 << 20,
        })
        try:
            hot = rsm.device_hot_cache
            assert hot is not None
            assert hot is rsm._chunk_manager  # no chunk cache configured
            names = {
                mn.name for mn in rsm.metrics.registry.metric_names
                if mn.group == "hot-cache-metrics"
            }
            assert "hot-cache-hits-total" in names
            assert "hot-cache-budget-bytes" in names
        finally:
            rsm.close()


class TestHotCacheMetrics:
    def test_gauges_track_counters(self):
        from tieredstorage_tpu.metrics.cache_metrics import (
            register_hot_cache_metrics,
        )
        from tieredstorage_tpu.metrics.core import MetricsRegistry

        hot, _ = make_hot(admission_hits=1)
        registry = MetricsRegistry()
        register_hot_cache_metrics(registry, hot)
        hot.get_chunks(KEY, None, [0, 1])
        hot.get_chunks(KEY, None, [0, 1])

        def value(name):
            for mn in registry.metric_names:
                if mn.name == name and mn.group == "hot-cache-metrics":
                    return registry.value(mn)
            raise AssertionError(name)

        assert value("hot-cache-hits-total") == 1.0
        assert value("hot-cache-misses-total") == 1.0
        assert value("hot-cache-hit-rate") == 0.5
        assert value("hot-cache-admissions-total") == 1.0
        assert value("hot-cache-windows-resident") == 1.0
        assert value("hot-cache-bytes-resident") == float(2 * CHUNK)
        assert value("hot-cache-budget-bytes") == float(64 * 4 * CHUNK)
