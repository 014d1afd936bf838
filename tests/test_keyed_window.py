"""Merged decrypt windows whose rows carry different keys: the keyed window
program (`ops.gcm.gcm_keyed_window_packed`) against `cryptography` AESGCM row
by row, its kernels in interpret mode, and the batcher around it — decrypt
windows of several segments' keys in one launch under a per-launch key table,
per-row error isolation across keys, the row -> key table, the hot tier's
offer of each waiter's own rows, and the spans and counts of a merged flush.
"""

from __future__ import annotations

import os
import random
import threading
import time

import numpy as np
import pytest

jax = pytest.importorskip("jax")
pytest.importorskip("cryptography")
import jax.numpy as jnp  # noqa: E402
from cryptography.hazmat.primitives.ciphers.aead import AESGCM  # noqa: E402

from tieredstorage_tpu.fetch.cache import device_hot  # noqa: E402
from tieredstorage_tpu.metrics.prometheus import PrometheusExporter  # noqa: E402
from tieredstorage_tpu.ops import aes_bitsliced, aes_pallas, gcm, ghash_pallas  # noqa: E402
from tieredstorage_tpu.security.aes import (  # noqa: E402
    IV_SIZE,
    TAG_SIZE,
    AesEncryptionProvider,
    DataKeyAndAAD,
)
from tieredstorage_tpu.transform.api import AuthenticationError  # noqa: E402
from tieredstorage_tpu.transform.batcher import WindowBatcher, bucket_rows  # noqa: E402
from tieredstorage_tpu.transform.tpu import TpuTransformBackend  # noqa: E402
from tieredstorage_tpu.utils.tracing import Tracer  # noqa: E402

MAX_BYTES = 4096


def _keys(n: int, seed: int) -> list[tuple[bytes, bytes]]:
    """n (key, aad) pairs; AADs of 32 and 20 bytes (one block count, two
    bit lengths)."""
    rng = random.Random(seed)
    return [
        (rng.randbytes(32), rng.randbytes(32 if i % 2 == 0 else 20)) for i in range(n)
    ]


def _window(rows: int, keys, sizes, decrypt: bool, seed: int):
    """(packed uint8[bucket_rows(rows), W + 16], row_keys, expected rows):
    row r under key r % len(keys); padding rows as the batcher pads them."""
    rng = np.random.default_rng(seed)
    packed = np.zeros((bucket_rows(rows), MAX_BYTES + TAG_SIZE), np.uint8)
    packed[rows:, MAX_BYTES + IV_SIZE] = 16
    row_keys = [0] * len(packed)
    expected = []
    for r in range(rows):
        row_keys[r] = slot = r % len(keys)
        key, aad = keys[slot]
        n = sizes[r]
        plain = rng.integers(0, 256, n, dtype=np.uint8).tobytes()
        iv = rng.integers(0, 256, IV_SIZE, dtype=np.uint8).tobytes()
        wire = AESGCM(key).encrypt(iv, plain, aad)
        packed[r, :n] = np.frombuffer(wire[:-TAG_SIZE] if decrypt else plain, np.uint8)
        packed[r, MAX_BYTES : MAX_BYTES + IV_SIZE] = np.frombuffer(iv, np.uint8)
        packed[r, MAX_BYTES + IV_SIZE :] = np.frombuffer(np.uint32(n).tobytes(), np.uint8)
        expected.append((plain if decrypt else wire[:-TAG_SIZE]) + wire[-TAG_SIZE:])
    return packed, row_keys, expected


def _rows_of(out: np.ndarray, sizes) -> list[bytes]:
    return [
        out[r, :n].tobytes() + out[r, MAX_BYTES:].tobytes() for r, n in enumerate(sizes)
    ]


# ------------------------------------------------------------ the program
@pytest.mark.parametrize("decrypt", [True, False], ids=["decrypt", "encrypt"])
@pytest.mark.parametrize("rows,n_keys,ragged", [
    (2, 1, False), (2, 2, True), (5, 3, True), (8, 8, False),
    (9, 4, True), (12, 7, False), (16, 2, True), (16, 16, True),
])
def test_keyed_window_matches_aesgcm_row_by_row(rows, n_keys, ragged, decrypt):
    keys = _keys(n_keys, rows * 31 + n_keys)
    rng = random.Random(rows)
    sizes = [rng.randrange(1, MAX_BYTES + 1) if ragged and r % 2 else MAX_BYTES for r in range(rows)]
    packed, row_keys, expected = _window(rows, keys, sizes, decrypt, seed=rows + n_keys)
    ctxs = [gcm.make_keyed_context(k, a, MAX_BYTES) for k, a in keys]
    out = np.asarray(gcm.gcm_keyed_window_packed(ctxs, row_keys, packed, decrypt=decrypt))
    assert out.shape == packed.shape
    assert _rows_of(out, sizes) == expected


def test_one_key_table_is_the_varlen_window_byte_for_byte():
    keys = _keys(1, 5)
    sizes = [MAX_BYTES, 100, 4000, 1, 3333]
    packed, row_keys, _ = _window(5, keys, sizes, True, seed=3)
    keyed = np.asarray(gcm.gcm_keyed_window_packed(
        [gcm.make_keyed_context(*keys[0], MAX_BYTES)], row_keys, packed, decrypt=True,
    ))
    varlen = np.asarray(gcm.gcm_varlen_window_packed(
        gcm.make_varlen_context(*keys[0], MAX_BYTES), None, packed, None, decrypt=True,
    ))
    np.testing.assert_array_equal(keyed, varlen)


def test_keyed_window_refuses_a_table_it_cannot_launch():
    keys = _keys(3, 9)
    packed, row_keys, _ = _window(2, keys, [MAX_BYTES] * 2, True, seed=1)
    ctxs = [gcm.make_keyed_context(k, a, MAX_BYTES) for k, a in keys]
    with pytest.raises(ValueError):
        gcm.gcm_keyed_window_packed(ctxs * 3, row_keys, packed, decrypt=True)
    other = gcm.make_keyed_context(keys[0][0], keys[0][1], 2 * MAX_BYTES)
    with pytest.raises(ValueError):
        gcm.gcm_keyed_window_packed([ctxs[0], other], row_keys, packed, decrypt=True)


@pytest.mark.parametrize("rows,n_keys", [(2, 2), (3, 3)])
def test_keyed_level1_kernel_path_matches_aesgcm(monkeypatch, rows, n_keys):
    """64 KiB rows reach the full 128-slot group width: the keyed level-1
    kernel (interpret mode) under each row tile's own operand."""
    global MAX_BYTES
    monkeypatch.setattr(ghash_pallas, "pallas_ghash_available", lambda: True)
    monkeypatch.setitem(globals(), "MAX_BYTES", 64 << 10)
    keys = _keys(n_keys, 77 + rows)
    sizes = [MAX_BYTES, 40_000, 1][:rows]
    packed, row_keys, expected = _window(rows, keys, sizes, True, seed=rows)
    ctxs = [gcm.make_keyed_context(k, a, MAX_BYTES) for k, a in keys]
    out = np.asarray(gcm.gcm_keyed_window_packed(ctxs, row_keys, packed, decrypt=True))
    assert _rows_of(out, sizes) == expected


def test_keyed_level1_kernel_follows_each_tiles_key():
    t = ghash_pallas.KEYED_ROWS_PER_STEP
    rng = np.random.default_rng(2)
    k = 2048
    data = rng.integers(0, 256, (3 * t, k), dtype=np.uint8)
    w1 = rng.integers(0, 2, (2, 8, k, 128), dtype=np.int8)
    tile_keys = np.array([1, 0, 1], np.int32)
    got = np.asarray(ghash_pallas.ghash_level1_keyed_pallas(
        jnp.asarray(data), jnp.asarray(w1), jnp.asarray(tile_keys), interpret=True,
    ))
    for tile, slot in enumerate(tile_keys):
        rows = data[tile * t : (tile + 1) * t]
        planes = np.stack([(rows >> p) & 1 for p in range(8)]).astype(np.int64)
        expect = np.einsum("prk,pko->ro", planes, w1[slot].astype(np.int64)) & 1
        np.testing.assert_array_equal(got[tile * t : (tile + 1) * t], expect)


@pytest.mark.parametrize("bad", ["rows", "tile_keys"])
def test_keyed_level1_kernel_refuses_misfit_shapes(bad):
    t = ghash_pallas.KEYED_ROWS_PER_STEP
    data = jnp.zeros((t + (1 if bad == "rows" else 0), 256), jnp.uint8)
    w1 = jnp.zeros((1, 8, 256, 128), jnp.int8)
    keys = jnp.zeros((2 if bad == "tile_keys" else 1,), jnp.int32)
    with pytest.raises(ValueError):
        ghash_pallas.ghash_level1_keyed_pallas(data, w1, keys, interpret=True)


def test_keyed_keystream_is_each_rows_own_keys():
    keys = _keys(3, 4)
    table = jnp.asarray(np.stack([gcm.make_context(k, a, 64).round_keys for k, a in keys]))
    ivs = jnp.asarray(np.random.default_rng(0).integers(0, 256, (5, 12), dtype=np.uint8))
    row_keys = jnp.asarray([2, 0, 1, 2, 0], jnp.int32)
    got = np.asarray(aes_bitsliced.ctr_keystream_keyed(table, row_keys, ivs, 1, 40))
    for r, slot in enumerate(np.asarray(row_keys)):
        one = aes_bitsliced.ctr_keystream_batch(table[slot], ivs[r : r + 1], 1, 40)
        np.testing.assert_array_equal(got[r], np.asarray(one)[0])


@pytest.mark.parametrize("words,steps", [(aes_pallas.WORDS_PER_STEP + 1, 1), (aes_pallas.WORDS_PER_STEP, 2)])
def test_keyed_aes_kernel_refuses_misfit_shapes(words, steps):
    table = jnp.zeros((1, 15, 16, 8), jnp.uint32)
    with pytest.raises(ValueError):
        aes_pallas.aes_encrypt_planes_keyed_pallas(
            table, jnp.zeros((steps,), jnp.int32), jnp.zeros((16, 8, words), jnp.uint32),
            interpret=True,
        )


# ------------------------------------------------------------ the batcher
def _wire(dk: DataKeyAndAAD, sizes, seed: int):
    rng = random.Random(seed)
    plain = [rng.randbytes(s) for s in sizes]
    wire = [AesEncryptionProvider.encrypt_chunk(p, dk.data_key, dk.aad) for p in plain]
    return plain, wire


def _parse(wire):
    ivs = np.stack([np.frombuffer(c[:IV_SIZE], np.uint8) for c in wire])
    return (
        [c[IV_SIZE:-TAG_SIZE] for c in wire], [len(c) - IV_SIZE - TAG_SIZE for c in wire],
        ivs, [c[-TAG_SIZE:] for c in wire],
    )


class _Held:
    """A batcher whose inline path is parked: every submit queues, and the
    test flushes them together."""

    def __init__(self, backend: TpuTransformBackend) -> None:
        self.backend = backend
        self.batcher = WindowBatcher(backend, wait_ms=50, max_windows=16)
        backend.batcher = self.batcher
        with self.batcher._cond:
            self.batcher._inflight += 1

    def submit_all(self, jobs, scope=None) -> list:
        """jobs: [(dk, wire)]; one thread each, flushed in one go; returns
        [(result or error, captured or None)]."""
        boxes: list = [[None, None] for _ in jobs]

        def one(i, dk, wire):
            context = device_hot.capture_scope() if scope else _nullscope()
            with context as captured:
                try:
                    boxes[i][0] = self.batcher.submit(dk, *_parse(wire))
                except BaseException as exc:  # noqa: BLE001 - asserted
                    boxes[i][0] = exc
            boxes[i][1] = captured

        threads = [threading.Thread(target=one, args=(i, *job)) for i, job in enumerate(jobs)]
        for t in threads:
            t.start()
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline:
            with self.batcher._cond:
                if sum(len(q) for q in self.batcher._buckets.values()) >= len(jobs):
                    self.buckets = {k: len(q) for k, q in self.batcher._buckets.items()}
                    break
            time.sleep(0.001)
        self.flushes = self.batcher.flush_now()
        for t in threads:
            t.join(timeout=60)
        return boxes

    def close(self) -> None:
        with self.batcher._cond:
            self.batcher._inflight -= 1
        self.backend.close()


class _nullscope:
    def __enter__(self):
        return None

    def __exit__(self, *exc):
        return False


@pytest.mark.parametrize("n_keys", [2, 5, 9])
def test_windows_of_distinct_keys_share_one_launch(n_keys):
    held = _Held(TpuTransformBackend())
    dks = [AesEncryptionProvider.create_data_key_and_aad() for _ in range(n_keys)]
    windows = [_wire(dk, [700, 300], seed=i) for i, dk in enumerate(dks)]
    boxes = held.submit_all([(dk, w) for dk, (_, w) in zip(dks, windows)])
    assert held.flushes == 1
    assert [b[0] for b in boxes] == [plain for plain, _ in windows]
    counts = held.batcher.counters()
    assert counts["merged_launches"] == counts["decrypt_launches"] == 1
    assert counts["merged_launch_keys"] == n_keys
    assert counts["decrypt_launch_rows"] == 2 * n_keys
    assert counts["windows_submitted"] == n_keys and counts["fast_path_windows"] == 0
    assert held.backend.dispatch_stats.dispatches == 1
    held.close()


def test_forged_row_fails_only_its_own_caller_across_keys():
    held = _Held(TpuTransformBackend())
    dks = [AesEncryptionProvider.create_data_key_and_aad() for _ in range(3)]
    windows = [_wire(dk, [600], seed=10 + i) for i, dk in enumerate(dks)]
    forged = list(windows[1][1])
    forged[0] = forged[0][:-1] + bytes([forged[0][-1] ^ 1])
    boxes = held.submit_all([(dks[0], windows[0][1]), (dks[1], forged), (dks[2], windows[2][1])])
    assert held.flushes == 1
    assert boxes[0][0] == windows[0][0] and boxes[2][0] == windows[2][0]
    assert isinstance(boxes[1][0], AuthenticationError)
    held.close()


def test_row_key_table_never_mixes_two_callers_keys(monkeypatch):
    backend = TpuTransformBackend()
    launched = []
    real = backend._launch_packed

    def spy(ctx, staged, varlen, *, decrypt, row_keys=None, **kw):
        launched.append((ctx, None if row_keys is None else list(row_keys)))
        return real(ctx, staged, varlen, decrypt=decrypt, row_keys=row_keys, **kw)

    monkeypatch.setattr(backend, "_launch_packed", spy)
    held = _Held(backend)
    dks = [AesEncryptionProvider.create_data_key_and_aad() for _ in range(3)]
    windows = [_wire(dk, [500] * (i + 1), seed=20 + i) for i, dk in enumerate(dks)]
    boxes = held.submit_all([(dk, w) for dk, (_, w) in zip(dks, windows)])
    assert [b[0] for b in boxes] == [plain for plain, _ in windows]
    [(ctxs, row_keys)] = launched
    # Rows in submit order are flushed FIFO: the table's slot of each row is
    # its caller's key, and no slot serves two callers.
    order = [i for i, (_, w) in enumerate(windows) for _ in w]
    got = {}
    for row, caller in enumerate(order):
        got.setdefault(caller, set()).add(row_keys[row])
    assert all(len(slots) == 1 for slots in got.values())
    assert len({next(iter(s)) for s in got.values()}) == 3
    for caller, slots in got.items():
        ctx = ctxs[next(iter(slots))]
        assert ctx is gcm.make_keyed_context(dks[caller].data_key, dks[caller].aad, 1024)
    held.close()


def test_decrypt_buckets_hold_every_key_of_one_shape():
    held = _Held(TpuTransformBackend())
    short_aad = DataKeyAndAAD(data_key=os.urandom(32), aad=os.urandom(48))
    dks = [AesEncryptionProvider.create_data_key_and_aad() for _ in range(3)] + [short_aad]
    jobs = [(dk, _wire(dk, [800], seed=30 + i)[1]) for i, dk in enumerate(dks)]
    jobs.append((dks[0], _wire(dks[0], [5000], seed=40)[1]))
    boxes = held.submit_all(jobs)
    # three keys of one AAD block count and rung share a bucket; another AAD
    # length and another rung each have their own
    assert sorted(held.buckets.values()) == [1, 1, 3]
    assert all(k[2] is None for k in held.buckets)
    assert held.flushes == 3
    assert all(isinstance(b[0], list) for b in boxes)
    held.close()


def test_merged_rows_reach_each_waiters_capture_as_their_own_copy():
    held = _Held(TpuTransformBackend())
    held.backend.on_decrypt_window = device_hot.offer_decrypt_window
    dks = [AesEncryptionProvider.create_data_key_and_aad() for _ in range(3)]
    windows = [_wire(dk, [900, 900], seed=50 + i) for i, dk in enumerate(dks)]
    boxes = held.submit_all([(dk, w) for dk, (_, w) in zip(dks, windows)], scope=True)
    for (plain, wire), (result, captured) in zip(windows, boxes):
        assert result == plain
        [(maker, sizes, n_bytes, mesh)] = captured.windows
        assert callable(maker) and tuple(sizes) == (900, 900) and mesh == 1
        rows = maker()
        assert rows.shape == (2, n_bytes + TAG_SIZE)  # its rows, not the launch's
        host = np.asarray(rows)
        assert [host[i, :900].tobytes() for i in range(2)] == plain
        assert [host[i, n_bytes:].tobytes() for i in range(2)] == [c[-TAG_SIZE:] for c in wire]
        cache = device_hot.DeviceHotCache(None, budget_bytes=1 << 30)
        opts = type("Opts", (), {"compression": False})()
        captured.opts = opts
        window = cache._build_window("w", "f", (0, 1), plain, captured)
        assert window.device is not None and window.device.shape[0] == 2
        assert window.device_nbytes == window.device.on_device_size_in_bytes()
    held.close()


def test_offer_outside_a_scope_copies_nothing():
    held = _Held(TpuTransformBackend())
    held.backend.on_decrypt_window = device_hot.offer_decrypt_window
    dks = [AesEncryptionProvider.create_data_key_and_aad() for _ in range(2)]
    windows = [_wire(dk, [400], seed=60 + i) for i, dk in enumerate(dks)]
    boxes = held.submit_all([(dk, w) for dk, (_, w) in zip(dks, windows)])
    assert [b[0] for b in boxes] == [plain for plain, _ in windows]
    held.close()


@pytest.mark.parametrize("traced", [True, False], ids=["tracing_on", "tracing_off"])
def test_merged_flush_spans_and_counts(traced):
    backend = TpuTransformBackend()
    backend.tracer = Tracer(enabled=traced)
    held = _Held(backend)
    dks = [AesEncryptionProvider.create_data_key_and_aad() for _ in range(3)]
    windows = [_wire(dk, [700], seed=70 + i) for i, dk in enumerate(dks)]
    threads_before = {t.name for t in threading.enumerate()}
    held.submit_all([(dk, w) for dk, (_, w) in zip(dks, windows)])
    counts = held.batcher.counters()
    assert counts["merged_launches"] == 1 and counts["merged_launch_keys"] == 3
    assert counts["waiter_collected_rows"] == 3 and counts["overlapped_launches"] == 0
    if traced:
        assert backend.device_watch.settle(60)
        [flush] = backend.tracer.spans("transform.batch_flush")
        assert flush.attributes == {"windows": 3, "keys": 3, "rows": 3, "bucket_rows": 16}
        waits = backend.tracer.spans("transform.batch_wait")
        assert len(waits) == 3
        assert all(
            w.attributes == {"rows": 1, "fast": False, "keys": 3, "occupancy": 3}
            for w in waits
        )
        [launch] = backend.tracer.spans("transform.launch")
        assert launch.parent_id == flush.span_id
        [window] = backend.tracer.spans("device.window")
        assert window.parent_id == launch.span_id
        # Each waiter's collect, on its own thread inside its batch_wait, and
        # split at the ready stamp into ready_wait and collect, which tile it.
        d2h = backend.tracer.spans("transform.d2h_wait")
        assert sorted(d.parent_id for d in d2h) == sorted(w.span_id for w in waits)
        by_wait = {w.span_id: w for w in waits}
        for d in d2h:
            assert d.thread_id == by_wait[d.parent_id].thread_id != flush.thread_id
            parts = [
                s for s in backend.tracer.spans()
                if s.parent_id == d.span_id
                and s.name in ("transform.ready_wait", "transform.collect")
            ]
            assert "transform.collect" in {p.name for p in parts}
            assert sum(p.end_s - p.start_s for p in parts) == pytest.approx(
                d.end_s - d.start_s, abs=1e-6
            )
    else:
        assert backend.tracer.spans() == []
        assert backend.device_watch is None
        assert {t.name for t in threading.enumerate()} <= threads_before
    held.close()


def test_varz_has_the_batchers_counts():
    backend = TpuTransformBackend()
    assert PrometheusExporter([], transform_backend=backend).varz()["batcher"] == {
        "enabled": False
    }
    backend.enable_batching()
    section = PrometheusExporter([], transform_backend=backend).varz()["batcher"]
    assert section == {
        "enabled": True, "windows_submitted": 0, "fast_path_windows": 0,
        "decrypt_launches": 0, "decrypt_launch_rows": 0,
        "merged_launches": 0, "merged_launch_keys": 0,
        "overlapped_launches": 0, "waiter_collected_rows": 0,
    }
    assert "batcher" not in PrometheusExporter([]).varz()
    backend.close()


def test_fast_path_window_counts_as_a_one_window_launch():
    backend = TpuTransformBackend()
    batcher = backend.enable_batching()
    dk = AesEncryptionProvider.create_data_key_and_aad()
    plain, wire = _wire(dk, [300, 300], seed=80)
    assert batcher.submit(dk, *_parse(wire)) == plain
    counts = batcher.counters()
    assert counts["fast_path_windows"] == counts["decrypt_launches"] == 1
    assert counts["decrypt_launch_rows"] == 2 and counts["merged_launches"] == 0
    # the inline window is collected by its own finish, not a waiter's
    assert counts["waiter_collected_rows"] == counts["overlapped_launches"] == 0
    backend.close()


def test_a_mesh_flushes_one_key_a_launch():
    """The keyed program has no sharded form: on a mesh a flush of several
    keys launches each key's windows on their own."""
    from tieredstorage_tpu.parallel.mesh import data_mesh

    backend = TpuTransformBackend(mesh=data_mesh())
    assert not backend.keyed_launches()
    held = _Held(backend)
    dks = [AesEncryptionProvider.create_data_key_and_aad() for _ in range(2)]
    windows = [_wire(dk, [600], seed=90 + i) for i, dk in enumerate(dks)]
    boxes = held.submit_all([(dk, w) for dk, (_, w) in zip(dks, windows)])
    assert [b[0] for b in boxes] == [plain for plain, _ in windows]
    assert held.batcher.counters()["merged_launches"] == 2
    held.close()


# ------------------------------------------------ the waiters' own collect
def _aesgcm_rows(dk: DataKeyAndAAD, wire) -> list[bytes]:
    """What `cryptography` AESGCM decrypts each wire chunk to."""
    aead = AESGCM(dk.data_key)
    return [aead.decrypt(c[:IV_SIZE], c[IV_SIZE:], dk.aad) for c in wire]


@pytest.mark.parametrize("n_keys,rows_each,ragged,bucket", [
    (1, 1, False, 16),
    (5, 2, True, 16),
    (16, 1, True, 16),
    (9, 2, False, 32),
    (2, 3, True, 8),
], ids=["1key", "5keys-ragged", "16keys-ragged", "9keys-32rows", "mesh-8rows"])
def test_each_waiter_collects_aesgcm_plaintext_row_by_row(n_keys, rows_each, ragged, bucket):
    from tieredstorage_tpu.parallel.mesh import data_mesh

    mesh = bucket == 8  # a mesh launches one key at a time, in 8-row buckets
    backend = TpuTransformBackend(mesh=data_mesh() if mesh else None)
    held = _Held(backend)
    shapes = []
    real = backend._acquire_staging

    def spy(shape):
        shapes.append(shape[0])
        return real(shape)

    backend._acquire_staging = spy
    dks = [AesEncryptionProvider.create_data_key_and_aad() for _ in range(n_keys)]
    sizes = [
        [1000 - 37 * ((k + r) % 5) if ragged else 1000 for r in range(rows_each)]
        for k in range(n_keys)
    ]
    windows = [_wire(dk, sizes[k], seed=100 + k) for k, dk in enumerate(dks)]
    boxes = held.submit_all([(dk, w) for dk, (_, w) in zip(dks, windows)])
    for dk, (plain, wire), (result, _) in zip(dks, windows, boxes):
        assert result == _aesgcm_rows(dk, wire) == plain
    assert set(shapes) == {bucket}
    counts = held.batcher.counters()
    assert counts["waiter_collected_rows"] == n_keys * rows_each
    # one launch, or on the mesh one a key back to back
    assert counts["overlapped_launches"] <= counts["merged_launches"] - 1
    assert held.backend.dispatch_stats.d2h_fetches == n_keys  # one a waiter
    held.close()


class _Gate:
    """Holds every waiter at the door of its collect until opened."""

    def __init__(self, batcher: WindowBatcher) -> None:
        self.open = threading.Event()
        self.arrived = threading.Semaphore(0)
        real = batcher._collect

        def held_collect(entry):
            self.arrived.release()
            assert self.open.wait(60)
            return real(entry)

        batcher._collect = held_collect


def _ring_holds(backend: TpuTransformBackend, rows: int) -> int:
    with backend._stats_lock:
        return sum(len(v) for k, v in backend._staging_free.items() if k[0] == rows)


@pytest.mark.parametrize("case", ["clean", "forged", "take_fails", "launch_fails"])
def test_staging_is_never_back_before_a_waiters_rows(monkeypatch, case):
    held = _Held(TpuTransformBackend())
    gate = _Gate(held.batcher)
    dks = [AesEncryptionProvider.create_data_key_and_aad() for _ in range(3)]
    windows = [_wire(dk, [800], seed=120 + i) for i, dk in enumerate(dks)]
    jobs = [(dk, w) for dk, (_, w) in zip(dks, windows)]
    if case == "forged":
        forged = list(windows[1][1])
        forged[0] = forged[0][:-1] + bytes([forged[0][-1] ^ 1])
        jobs[1] = (dks[1], forged)
    if case == "take_fails":
        def no_take(out, first_row, n):
            raise RuntimeError("take failed")

        monkeypatch.setattr(gcm, "take_rows", no_take)
    if case == "launch_fails":
        def no_launch(*args, **kwargs):
            raise RuntimeError("launch failed")

        held.batcher._launch_policy = type(held.batcher._launch_policy)(max_attempts=1)
        monkeypatch.setattr(held.backend, "_launch_packed", no_launch)
    results: list = []
    runner = threading.Thread(target=lambda: results.append(held.submit_all(jobs)))
    runner.start()
    if case != "launch_fails":
        for _ in jobs:  # every waiter is at its collect: the launch is out
            assert gate.arrived.acquire(timeout=60)
        assert held.batcher.counters()["merged_launches"] == 1
        assert _ring_holds(held.backend, 16) == 0
    gate.open.set()
    runner.join(60)
    assert not runner.is_alive()
    [boxes] = results
    back = _ring_holds(held.backend, 16)
    if case == "clean":
        assert [b[0] for b in boxes] == [p for p, _ in windows] and back == 1
    elif case == "forged":
        assert isinstance(boxes[1][0], AuthenticationError) and back == 1
        assert boxes[0][0] == windows[0][0] and boxes[2][0] == windows[2][0]
    else:  # no waiter's rows came back: the buffer is dropped, never reused
        assert all(isinstance(b[0], RuntimeError) for b in boxes) and back == 0
    with held.batcher._cond:
        assert held.batcher._uncollected == 0 and held.batcher._inflight == 1
    held.close()


def test_a_launch_being_collected_keeps_its_staging_buffer():
    """A second flush of the same shape packs while the first launch's
    second waiter is still held at its collect: the first launch's buffer is
    not back in the ring until that waiter has left, so the pack never
    writes over an output (on a zero-copy placement, the same memory) that
    a waiter has still to read."""
    held = _Held(TpuTransformBackend())
    dks = [AesEncryptionProvider.create_data_key_and_aad() for _ in range(4)]
    windows = [_wire(dk, [900], seed=160 + i) for i, dk in enumerate(dks)]
    opened, arrived = threading.Event(), threading.Semaphore(0)
    real = held.batcher._collect
    held_key = bytes(dks[1].data_key)

    def collect(entry):
        if entry.data_key == held_key:
            arrived.release()
            assert opened.wait(60)
        return real(entry)

    held.batcher._collect = collect
    first_jobs = [(dk, w) for dk, (_, w) in zip(dks[:2], windows[:2])]
    results: list = []
    runner = threading.Thread(target=lambda: results.append(held.submit_all(first_jobs)))
    runner.start()
    assert arrived.acquire(timeout=60)  # the second waiter is at its collect
    deadline = time.monotonic() + 60
    while held.batcher.counters()["waiter_collected_rows"] < 1:  # the first has its rows
        assert time.monotonic() < deadline
        time.sleep(0.001)
    assert _ring_holds(held.backend, 16) == 0
    second = held.submit_all([(dk, w) for dk, (_, w) in zip(dks[2:], windows[2:])])
    assert [b[0] for b in second] == [p for p, _ in windows[2:]]
    assert held.batcher.counters()["overlapped_launches"] == 1
    opened.set()
    runner.join(60)
    assert not runner.is_alive()
    [first] = results
    assert [b[0] for b in first] == [p for p, _ in windows[:2]]
    assert _ring_holds(held.backend, 16) == 2
    held.close()


@pytest.mark.parametrize("path", ["merged_decrypt", "merged_encrypt", "unbatched"])
def test_only_a_merged_decrypt_launch_skips_the_whole_output_copy(monkeypatch, path):
    """A merged decrypt launch starts no copy back of its whole output (each
    waiter takes only its own rows); a merged encrypt launch, whose handles
    read the whole output, and every unbatched launch do."""
    from jax._src.array import ArrayImpl

    from tieredstorage_tpu.transform.api import DetransformOptions, TransformOptions

    copied: list = []
    real = ArrayImpl.copy_to_host_async

    def spy(self):
        copied.append(self.shape)
        return real(self)

    monkeypatch.setattr(ArrayImpl, "copy_to_host_async", spy)
    dks = [AesEncryptionProvider.create_data_key_and_aad() for _ in range(2)]
    windows = [_wire(dk, [700], seed=170 + i) for i, dk in enumerate(dks)]
    backend = TpuTransformBackend()
    if path == "unbatched":
        assert backend.detransform(windows[0][1], DetransformOptions(encryption=dks[0])) == windows[0][0]
        assert len(copied) == 1
        backend.close()
        return
    held = _Held(backend)
    if path == "merged_decrypt":
        boxes = held.submit_all([(dk, w) for dk, (_, w) in zip(dks, windows)])
        assert [b[0] for b in boxes] == [p for p, _ in windows]
        assert copied == []
    else:
        handles = [
            held.batcher.submit_encrypt(plain, TransformOptions(encryption=dks[0]))
            for plain, _ in windows
        ]
        assert held.batcher.flush_now() == 1
        wires = [h.wait() for h in handles]
        assert [
            [AesEncryptionProvider.decrypt_chunk(c, dks[0].data_key, dks[0].aad) for c in w]
            for w in wires
        ] == [p for p, _ in windows]
        assert len(copied) == 1
    assert held.batcher.counters()["waiter_collected_rows"] == 2
    held.close()


def test_a_failed_take_fails_only_its_own_waiter(monkeypatch):
    held = _Held(TpuTransformBackend())
    real = gcm.take_rows

    def take(out, first_row, n):
        if first_row == 1:
            raise RuntimeError("take failed")
        return real(out, first_row, n)

    monkeypatch.setattr(gcm, "take_rows", take)
    dks = [AesEncryptionProvider.create_data_key_and_aad() for _ in range(3)]
    windows = [_wire(dk, [500], seed=130 + i) for i, dk in enumerate(dks)]
    boxes = held.submit_all([(dk, w) for dk, (_, w) in zip(dks, windows)])
    failed = [i for i, b in enumerate(boxes) if isinstance(b[0], RuntimeError)]
    assert len(failed) == 1
    assert all(boxes[i][0] == windows[i][0] for i in range(3) if i not in failed)
    held.close()


@pytest.mark.parametrize("order", ["in_order", "reversed"])
def test_merged_encrypt_handles_collect_the_unbatched_wire(order):
    """Each handle builds its own IV || ct || tag on the thread that waits;
    a handle not yet waited for holds neither the cap nor `_inflight`."""
    from tieredstorage_tpu.transform.api import TransformOptions

    dk = AesEncryptionProvider.create_data_key_and_aad()
    rng = random.Random(140)
    windows = [[rng.randbytes(s) for s in (700, 333)] for _ in range(3)]
    ivs = [bytes([i + 1]) * IV_SIZE for i in range(6)]
    opts = [TransformOptions(encryption=dk, ivs=ivs[2 * i : 2 * i + 2]) for i in range(3)]
    control = TpuTransformBackend()
    expect = [control.transform(w, o) for w, o in zip(windows, opts)]
    control.close()
    backend = TpuTransformBackend()
    held = _Held(backend)
    handles = [held.batcher.submit_encrypt(w, o) for w, o in zip(windows, opts)]
    assert held.batcher.flush_now() == 1
    with held.batcher._cond:
        assert held.batcher._uncollected == 0 and held.batcher._inflight == 1
    picks = range(3) if order == "in_order" else reversed(range(3))
    got = {i: handles[i].wait() for i in picks}
    assert [got[i] for i in range(3)] == expect
    assert [
        [AesEncryptionProvider.decrypt_chunk(c, dk.data_key, dk.aad) for c in wire]
        for wire in expect
    ] == windows
    assert held.batcher.counters()["waiter_collected_rows"] == 6
    held.close()


@pytest.mark.parametrize("started", [True, False], ids=["flusher", "flush_now"])
def test_inflight_returns_to_zero_after_every_flush(started):
    backend = TpuTransformBackend()
    dks = [AesEncryptionProvider.create_data_key_and_aad() for _ in range(6)]
    windows = [_wire(dk, [600], seed=150 + i) for i, dk in enumerate(dks)]
    if started:
        batcher = backend.enable_batching(wait_ms=20)
        results: list = [None] * 6
        barrier = threading.Barrier(6)

        def fetch(i):
            barrier.wait(timeout=30)
            results[i] = batcher.submit(dks[i], *_parse(windows[i][1]))

        threads = [threading.Thread(target=fetch, args=(i,)) for i in range(6)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        assert results == [p for p, _ in windows]
    else:
        held = _Held(backend)
        batcher = held.batcher
        for _ in range(2):  # two rounds drained by flush_now
            boxes = held.submit_all([(dk, w) for dk, (_, w) in zip(dks, windows)])
            assert held.flushes == 1
            assert [b[0] for b in boxes] == [p for p, _ in windows]
        with batcher._cond:
            batcher._inflight -= 1  # un-park
    with batcher._cond:
        assert batcher._inflight == 0 and batcher._uncollected == 0
        assert not batcher._buckets
    backend.close()
