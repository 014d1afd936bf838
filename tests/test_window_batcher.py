"""Cross-request GCM dispatch batcher (ISSUE 15, transform/batcher.py).

Covers the flush-policy matrix (windows/bytes/age/deadline-floor
triggers), the single-waiter fast path, per-row error isolation, the
bucket-ladder grouping contract (merged launches never mix buckets or
keys), deadline-expired waiters failing fast without poisoning their
batch, capped takes, the evidence seam, config wiring, and N-thread byte
parity against the unbatched path. Deterministic coalescing uses a
non-started batcher: the fast path is suppressed by parking the
``_inflight`` count, submitters queue, and the test thread drains with
``flush_now()`` — no timing races."""

from __future__ import annotations

import random
import threading
import time

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from tieredstorage_tpu.security.aes import (  # noqa: E402
    IV_SIZE,
    TAG_SIZE,
    AesEncryptionProvider,
)
from tieredstorage_tpu.transform.api import (  # noqa: E402
    AuthenticationError,
    DetransformOptions,
    TransformOptions,
)
from tieredstorage_tpu.transform.batcher import (  # noqa: E402
    BatcherStoppedError,
    WindowBatcher,
    _PendingWindow,
    bucket_rows,
)
from tieredstorage_tpu.transform.scheduler import LATENCY  # noqa: E402
from tieredstorage_tpu.transform.tpu import TpuTransformBackend  # noqa: E402
from tieredstorage_tpu.utils.deadline import (  # noqa: E402
    DeadlineExceededException,
)

DK = AesEncryptionProvider.create_data_key_and_aad()
D_OPTS = DetransformOptions(encryption=DK)
#: A synthetic latency-class decrypt bucket key (work_class, decrypt,
#: data_key, aad, bucket_bytes) for flush-policy tests on a fake clock.
KEY = (LATENCY, True, "k", "a", 1024)


def make_window(seed: int, sizes) -> tuple[list[bytes], list[bytes]]:
    """(plaintext chunks, wire chunks) for one window under DK."""
    rng = random.Random(seed)
    chunks = [bytes(rng.getrandbits(8) for _ in range(s)) for s in sizes]
    backend = TpuTransformBackend()
    ivs = [(seed * 64 + i + 1).to_bytes(4, "big") * 3 for i in range(len(sizes))]
    wire = backend.transform(chunks, TransformOptions(encryption=DK, ivs=ivs))
    backend.close()
    return chunks, wire


def parse_wire(wire: list[bytes]):
    """(payloads, sizes, ivs, tags) — what _decrypt_batch hands submit."""
    ivs = np.stack([np.frombuffer(c[:IV_SIZE], np.uint8) for c in wire])
    tags = [c[-TAG_SIZE:] for c in wire]
    sizes = [len(c) - IV_SIZE - TAG_SIZE for c in wire]
    payloads = [c[IV_SIZE:-TAG_SIZE] for c in wire]
    return payloads, sizes, ivs, tags


def park_fast_path(batcher: WindowBatcher):
    """Suppress the inline fast path so every submit queues."""
    with batcher._cond:
        batcher._inflight += 1

    def release():
        with batcher._cond:
            batcher._inflight -= 1

    return release


def queued_submit(batcher: WindowBatcher, wire: list[bytes]):
    """Background submit; returns (thread, box) with box[0] = result or
    box[1] = error once the flush completes."""
    payloads, sizes, ivs, tags = parse_wire(wire)
    box: list = [None, None]

    def run():
        try:
            box[0] = batcher.submit(DK, payloads, sizes, ivs, tags)
        except BaseException as exc:  # noqa: BLE001 - asserted by tests
            box[1] = exc

    t = threading.Thread(target=run)
    t.start()
    return t, box


def wait_queued(batcher: WindowBatcher, n: int, timeout_s: float = 5.0):
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        with batcher._cond:
            if sum(len(v) for v in batcher._buckets.values()) >= n:
                return
        time.sleep(0.001)
    raise AssertionError(f"never saw {n} queued windows")


class TestBucketRows:
    def test_exact_ladder(self):
        assert bucket_rows(1) == 8
        assert bucket_rows(8) == 8
        assert bucket_rows(9) == 16
        assert bucket_rows(16) == 16
        assert bucket_rows(17) == 32
        assert bucket_rows(64) == 64
        assert bucket_rows(65) == 128

    def test_rejects_non_positive(self):
        with pytest.raises(ValueError):
            bucket_rows(0)


class TestValidation:
    def test_ctor_bounds(self):
        backend = TpuTransformBackend()
        with pytest.raises(ValueError):
            WindowBatcher(backend, wait_ms=-1)
        with pytest.raises(ValueError):
            WindowBatcher(backend, max_windows=1)
        with pytest.raises(ValueError):
            WindowBatcher(backend, max_bytes=0)
        # Exact boundary values are legal.
        ok = WindowBatcher(backend, wait_ms=0, max_windows=2, max_bytes=1)
        assert (ok.wait_ms, ok.max_windows, ok.max_bytes) == (0.0, 2, 1)
        backend.close()

    def test_stopped_batcher_refuses_submit(self):
        backend = TpuTransformBackend()
        batcher = backend.enable_batching()
        backend.close()
        _, wire = make_window(1, [256] * 2)
        payloads, sizes, ivs, tags = parse_wire(wire)
        with pytest.raises(BatcherStoppedError):
            batcher.submit(DK, payloads, sizes, ivs, tags)
        # close() cleared the backend's reference too
        assert backend.batcher is None


def _entry(wire, now=0.0, deadline_at=None) -> _PendingWindow:
    payloads, sizes, ivs, tags = parse_wire(wire)
    return _PendingWindow(
        payloads=payloads, sizes=sizes, ivs=ivs, tags=tags,
        n_bytes=sum(sizes), enqueued_at=now, deadline_at=deadline_at,
    )


class TestFlushPolicy:
    """_due_keys_locked on a fake clock: the full trigger matrix."""

    def make(self, **kw):
        self.clock = [0.0]
        backend = TpuTransformBackend()
        kw.setdefault("wait_ms", 10.0)
        kw.setdefault("max_windows", 4)
        kw.setdefault("max_bytes", 10_000)
        batcher = WindowBatcher(
            backend, time_source=lambda: self.clock[0], **kw
        )
        return batcher

    def due(self, batcher, now):
        with batcher._cond:
            return batcher._due_keys_locked(now)

    def test_age_trigger_and_wake_time(self):
        batcher = self.make()
        _, wire = make_window(2, [512] * 2)
        with batcher._cond:
            batcher._buckets[KEY] = [_entry(wire, now=0.0)]
        due, timeout = self.due(batcher, 0.004)
        assert due == [] and timeout == pytest.approx(0.006)
        due, timeout = self.due(batcher, 0.010)
        assert due == [KEY] and timeout is None

    def test_windows_trigger_fires_before_age(self):
        batcher = self.make(max_windows=3)
        _, wire = make_window(3, [512] * 2)
        entries = [_entry(wire, now=0.0) for _ in range(3)]
        with batcher._cond:
            batcher._buckets[KEY] = entries
        due, _ = self.due(batcher, 0.0)
        assert due == [KEY]

    def test_bytes_trigger_fires_before_age(self):
        batcher = self.make(max_bytes=1500)
        _, wire = make_window(4, [900] * 1)
        with batcher._cond:
            batcher._buckets[KEY] = [
                _entry(wire, now=0.0), _entry(wire, now=0.0),
            ]
        due, _ = self.due(batcher, 0.0)
        assert due == [KEY]

    def test_deadline_floor_trigger_uses_launch_p95(self):
        batcher = self.make(wait_ms=10_000.0)  # age never fires here
        _, wire = make_window(5, [512] * 2)
        with batcher._cond:
            batcher._launch_s.extend([0.040] * 20)  # p95 = 40ms
            batcher._buckets[KEY] = [
                _entry(wire, now=0.0, deadline_at=0.100)
            ]
        # wake = deadline - p95 - floor = 100 - 40 - 5 = 55ms
        due, timeout = self.due(batcher, 0.050)
        assert due == [] and timeout == pytest.approx(0.005)
        due, _ = self.due(batcher, 0.056)
        assert due == [KEY]

    def test_launch_p95_nearest_rank(self):
        batcher = self.make()
        with batcher._cond:
            assert batcher._launch_p95_s() == 0.0
            batcher._launch_s.extend([0.001, 0.002, 0.003])
            # nearest-rank index int(0.95 * 2) = 1
            assert batcher._launch_p95_s() == pytest.approx(0.002)
            batcher._launch_s[:] = [i / 1000.0 for i in range(1, 21)]
            # 20 samples: index int(0.95 * 19) = 18 -> the 19 ms sample
            assert batcher._launch_p95_s() == pytest.approx(0.019)

    def test_wait_timeout_arithmetic_is_exact(self):
        batcher = self.make()
        batcher.WAIT_GRACE_S = 0.5
        self.clock[0] = 2.0
        assert batcher._wait_timeout_s(
            _entry(make_window(8, [256])[1], deadline_at=5.0)
        ) == pytest.approx(3.5)
        # Expired budget clamps to the grace alone; no deadline = None.
        assert batcher._wait_timeout_s(
            _entry(make_window(8, [256])[1], deadline_at=1.0)
        ) == pytest.approx(0.5)
        assert batcher._wait_timeout_s(
            _entry(make_window(8, [256])[1])
        ) is None

    def test_exactly_expired_entry_is_failed_fast(self):
        """deadline_at == now is EXPIRED (<=, not <): a budget with zero
        remaining must never launch."""
        batcher = self.make()
        plain, wire = make_window(9, [512])
        on_time = _entry(wire, now=0.0, deadline_at=4.0)
        boundary = _entry(wire, now=0.0, deadline_at=3.5)
        key = (LATENCY, True, bytes(DK.data_key), bytes(DK.aad), 1024)
        with batcher._cond:
            batcher._buckets[key] = [on_time, boundary]
        self.clock[0] = 3.5
        assert batcher.flush_now() == 1
        assert isinstance(boundary.error, DeadlineExceededException)
        assert boundary.launch is None  # nothing of the launch to collect
        assert on_time.error is None and batcher._await_entry(on_time) == plain
        assert batcher.expired_windows == 1

    def test_added_wait_is_exact_on_a_fake_clock(self):
        batcher = self.make(wait_ms=1.0)
        plain, wire = make_window(7, [512])
        entry = _entry(wire, now=1.0)
        # Real flush through the backend, timed by the fake clock: the
        # launch starts at t=3.5, so the queued window waited exactly
        # (3.5 - 1.0) s = 2500 ms.
        key = (LATENCY, True, bytes(DK.data_key), bytes(DK.aad), 1024)
        waits: list = []
        batcher.on_flush = lambda occ, added, cls, *rest: waits.extend(added)
        with batcher._cond:
            batcher._buckets[key] = [entry]
        self.clock[0] = 3.5
        assert batcher.flush_now() == 1
        assert entry.error is None and batcher._await_entry(entry) == plain
        assert entry.added_wait_ms == pytest.approx(2500.0)
        assert waits == [pytest.approx(2500.0)]

    def test_take_locked_caps_windows_and_bytes_fifo(self):
        batcher = self.make(max_windows=2, max_bytes=10_000)
        _, wire = make_window(6, [512] * 2)
        entries = [_entry(wire, now=float(i)) for i in range(5)]
        with batcher._cond:
            batcher._buckets[KEY] = list(entries)
            take = batcher._take_locked(KEY)
            assert take == entries[:2]  # FIFO, capped at max_windows
            assert batcher._buckets[KEY] == entries[2:]
        byte_capped = self.make(max_windows=16, max_bytes=1500)
        with byte_capped._cond:
            byte_capped._buckets[KEY] = list(entries)
            take = byte_capped._take_locked(KEY)
            # 1024 bytes per entry: the second pop crosses max_bytes.
            assert take == entries[:2]


class TestCoalescing:
    def test_merged_flush_demuxes_per_caller(self):
        backend = TpuTransformBackend()
        batcher = WindowBatcher(backend, wait_ms=50, max_windows=8)
        release = park_fast_path(batcher)
        plains, wires = zip(*(make_window(10 + i, [700, 700]) for i in range(3)))
        jobs = [queued_submit(batcher, list(w)) for w in wires]
        wait_queued(batcher, 3)
        assert batcher.flush_now() == 1  # one bucket, one merged launch
        release()
        for (t, box), plain in zip(jobs, plains):
            t.join(timeout=30)
            assert box[1] is None
            assert box[0] == plain
        assert batcher.launches == 1
        assert batcher.batched_windows == 3
        assert batcher.mean_occupancy == 3.0
        assert batcher.windows_submitted == 3
        assert batcher.fast_path_windows == 0
        stats = backend.dispatch_stats
        assert stats.windows == 3
        assert stats.dispatches == 1
        assert stats.d2h_fetches == 3  # each waiter fetches its own rows
        assert stats.dispatches_per_window == pytest.approx(1 / 3, abs=1e-3)
        backend.close()

    def test_bucket_ladder_never_mixes_buckets(self):
        backend = TpuTransformBackend()
        batcher = WindowBatcher(backend, wait_ms=50, max_windows=8)
        release = park_fast_path(batcher)
        # 1000 -> bucket 1024, 5000 -> bucket 5120: distinct launches.
        plain_a, wire_a = make_window(20, [1000, 900])
        plain_b, wire_b = make_window(21, [5000, 4800])
        job_a = queued_submit(batcher, wire_a)
        job_b = queued_submit(batcher, wire_b)
        wait_queued(batcher, 2)
        with batcher._cond:
            assert len(batcher._buckets) == 2
        assert batcher.flush_now() == 2
        release()
        for (t, box), plain in ((job_a, plain_a), (job_b, plain_b)):
            t.join(timeout=30)
            assert box[0] == plain
        assert batcher.launches == 2
        assert batcher.mean_occupancy == 1.0
        backend.close()

    def test_distinct_keys_never_share_a_launch(self):
        """Encrypt windows still group by key: two produces under two keys
        queue in two buckets and flush as two launches (decrypt windows of
        distinct keys share one launch: TestKeyedMerge)."""
        backend = TpuTransformBackend()
        batcher = WindowBatcher(backend, wait_ms=50, max_windows=8)
        backend.batcher = batcher
        release = park_fast_path(batcher)
        other_dk = AesEncryptionProvider.create_data_key_and_aad()
        rng = random.Random(22)
        chunks = [bytes(rng.getrandbits(8) for _ in range(800))]
        ivs = [b"\x07" * 12]
        results: dict = {}

        def produce(dk):
            results[dk.data_key] = backend.transform(
                chunks, TransformOptions(encryption=dk, ivs=ivs)
            )

        threads = [
            threading.Thread(target=produce, args=(dk,)) for dk in (DK, other_dk)
        ]
        for t in threads:
            t.start()
        wait_queued(batcher, 2)
        with batcher._cond:
            assert len(batcher._buckets) == 2
        assert batcher.flush_now() == 2  # same bucket bytes, distinct keys
        release()
        for t in threads:
            t.join(timeout=30)
        control = TpuTransformBackend()
        for dk in (DK, other_dk):
            assert results[dk.data_key] == control.transform(
                chunks, TransformOptions(encryption=dk, ivs=ivs)
            )
        control.close()
        assert batcher.launches == 2
        backend.close()

    def test_per_row_error_isolation(self):
        backend = TpuTransformBackend()
        batcher = WindowBatcher(backend, wait_ms=50, max_windows=8)
        release = park_fast_path(batcher)
        plain_ok, wire_ok = make_window(30, [600, 600])
        _, wire_bad = make_window(31, [600, 600])
        # Corrupt the SECOND row's tag of the bad window only.
        bad = list(wire_bad)
        bad[1] = bad[1][:-1] + bytes([bad[1][-1] ^ 1])
        job_ok = queued_submit(batcher, wire_ok)
        job_bad = queued_submit(batcher, bad)
        wait_queued(batcher, 2)
        assert batcher.flush_now() == 1  # ONE shared launch
        release()
        job_ok[0].join(timeout=30)
        job_bad[0].join(timeout=30)
        assert job_ok[1][1] is None
        assert job_ok[1][0] == plain_ok  # batch-mate unharmed
        assert isinstance(job_bad[1][1], AuthenticationError)
        assert "[1]" in str(job_bad[1][1])  # its own bad row index
        assert batcher.launches == 1
        assert batcher.batched_windows == 2
        backend.close()

    def test_expired_waiter_fails_fast_without_poisoning(self):
        backend = TpuTransformBackend()
        batcher = WindowBatcher(backend, wait_ms=50)
        release = park_fast_path(batcher)
        plain_ok, wire_ok = make_window(32, [640])
        _, wire_late = make_window(33, [640])
        job_ok = queued_submit(batcher, wire_ok)
        wait_queued(batcher, 1)
        # Inject an already-expired entry into the same bucket.
        late = _entry(wire_late, now=0.0, deadline_at=0.0)
        key = next(iter(batcher._buckets))
        with batcher._cond:
            batcher._buckets[key].append(late)
        assert batcher.flush_now() == 1
        release()
        job_ok[0].join(timeout=30)
        assert job_ok[1][0] == plain_ok
        assert isinstance(late.error, DeadlineExceededException)
        assert late.batch_id == 0  # never joined a launch
        assert batcher.expired_windows == 1
        assert batcher.batched_windows == 1  # the survivor alone
        assert batcher.launches == 1
        # Expired windows never count as launched windows in the stats.
        assert backend.dispatch_stats.windows == 1
        backend.close()

    def test_wait_grace_outlives_an_expired_deadline(self):
        """A waiter whose budget is tiny still outlives it by WAIT_GRACE_S:
        the flusher's deadline fail-fast (not a spurious wait timeout) is
        what reports the expiry."""
        from tieredstorage_tpu.utils.deadline import Deadline, deadline_scope

        backend = TpuTransformBackend()
        batcher = WindowBatcher(backend, wait_ms=50)
        batcher.WAIT_GRACE_S = 0.5
        release = park_fast_path(batcher)
        _, wire = make_window(35, [600])
        payloads, sizes, ivs, tags = parse_wire(wire)
        box: list = [None, None]

        def run():
            try:
                with deadline_scope(Deadline.after(0.02)):
                    box[0] = batcher.submit(DK, payloads, sizes, ivs, tags)
            except BaseException as exc:  # noqa: BLE001
                box[1] = exc

        t = threading.Thread(target=run)
        t.start()
        wait_queued(batcher, 1)
        time.sleep(0.05)  # let the 20 ms budget expire in queue
        batcher.flush_now()
        release()
        t.join(timeout=30)
        # The grace kept the waiter alive long enough to receive the
        # flusher's verdict — DeadlineExceeded, never BatcherStoppedError.
        assert isinstance(box[1], DeadlineExceededException), box
        assert batcher.expired_windows == 1
        backend.close()

    def test_launch_failure_wakes_every_waiter(self):
        backend = TpuTransformBackend()
        batcher = WindowBatcher(backend, wait_ms=50)
        release = park_fast_path(batcher)
        _, wire = make_window(34, [700])
        jobs = [queued_submit(batcher, wire) for _ in range(2)]
        wait_queued(batcher, 2)
        boom = RuntimeError("device fell over")

        def exploding_stage(packed, varlen):
            raise boom

        backend._stage_packed = exploding_stage
        assert batcher.flush_now() == 1
        release()
        for t, box in jobs:
            t.join(timeout=30)
            assert box[1] is boom
        assert batcher.launch_failures == 1
        assert batcher.launches == 0
        backend.close()


class TestWaiterCollect:
    """The flusher stops at the launch: waiters collect their own rows, so
    a second merged launch goes out while the first is being collected, up
    to ``pipeline_depth`` launches uncollected."""

    @staticmethod
    def hold_collects(batcher: WindowBatcher):
        """Every waiter stops at the door of its collect until released."""
        gate, arrived = threading.Event(), threading.Semaphore(0)
        real = batcher._collect

        def held(entry):
            arrived.release()
            assert gate.wait(30)
            return real(entry)

        batcher._collect = held
        return gate, arrived

    @pytest.mark.parametrize("depth", [1, 2, 3])
    def test_launches_overlap_up_to_the_cap(self, depth):
        backend = TpuTransformBackend()
        backend.pipeline_depth = depth
        batcher = WindowBatcher(backend, wait_ms=50, max_windows=8)
        release = park_fast_path(batcher)
        gate, arrived = self.hold_collects(batcher)
        windows = [make_window(200 + i, [640]) for i in range(depth + 1)]
        jobs = []
        for i, (_, wire) in enumerate(windows[:depth]):
            jobs.append(queued_submit(batcher, wire))
            wait_queued(batcher, 1)
            assert batcher.flush_now() == 1  # returns at the launch
            assert arrived.acquire(timeout=30)  # its waiter holds its collect
            assert batcher.counters()["overlapped_launches"] == i
        with batcher._cond:
            assert batcher._uncollected == depth
            assert batcher._inflight == depth + 1  # + the parked fast path
        # One more launch would pass the cap: the flush waits for a collect.
        jobs.append(queued_submit(batcher, windows[depth][1]))
        wait_queued(batcher, 1)
        drained = []
        flusher = threading.Thread(target=lambda: drained.append(batcher.flush_now()))
        flusher.start()
        flusher.join(0.3)
        assert flusher.is_alive() and batcher.merged_launches == depth
        gate.set()
        flusher.join(30)
        assert not flusher.is_alive() and drained == [1]
        for (t, box), (plain, _) in zip(jobs, windows):
            t.join(timeout=30)
            assert box[1] is None and box[0] == plain
        counts = batcher.counters()
        assert counts["merged_launches"] == depth + 1
        # the last went out once a collect had finished; others may not have
        assert depth - 1 <= counts["overlapped_launches"] <= depth
        assert counts["waiter_collected_rows"] == depth + 1
        release()
        with batcher._cond:
            assert batcher._uncollected == 0 and batcher._inflight == 0
        backend.close()

    def test_flusher_leaves_due_windows_queued_at_the_cap(self):
        backend = TpuTransformBackend()
        backend.pipeline_depth = 1
        batcher = WindowBatcher(backend, wait_ms=1, max_windows=8).start()
        release = park_fast_path(batcher)
        gate, arrived = self.hold_collects(batcher)
        windows = [make_window(220 + i, [512]) for i in range(3)]
        first = queued_submit(batcher, windows[0][1])
        assert arrived.acquire(timeout=30)  # launched; its collect is held
        later = [queued_submit(batcher, w) for _, w in windows[1:]]
        wait_queued(batcher, 2)
        time.sleep(0.3)  # due long since, but the cap holds them queued
        with batcher._cond:
            assert sum(len(q) for q in batcher._buckets.values()) == 2
        assert batcher.merged_launches == 1
        gate.set()
        for (t, box), (plain, _) in zip([first] + later, windows):
            t.join(timeout=30)
            assert box[1] is None and box[0] == plain
        assert batcher.merged_launches == 2  # the two queued ones merged
        release()
        backend.close()

    @pytest.mark.parametrize("held_in", ["flusher", "flush_now"])
    def test_a_deadline_passing_at_the_cap_fails_fast(self, held_in):
        """A window held queued by the cap whose deadline passes meanwhile
        fails fast with DeadlineExceeded, and is never launched: not woken
        by the liveness backstop as if the flusher were dead."""
        from tieredstorage_tpu.utils.deadline import Deadline, deadline_scope

        backend = TpuTransformBackend()
        backend.pipeline_depth = 1
        batcher = WindowBatcher(backend, wait_ms=1, max_windows=8)
        batcher.WAIT_GRACE_S = 30.0
        if held_in == "flusher":
            batcher.start()
        release = park_fast_path(batcher)
        gate, arrived = self.hold_collects(batcher)
        (plain, wire), (_, late_wire) = make_window(240, [512]), make_window(241, [512])
        first = queued_submit(batcher, wire)
        if held_in == "flush_now":
            wait_queued(batcher, 1)
            assert batcher.flush_now() == 1
        assert arrived.acquire(timeout=30)  # launched; its collect is held
        payloads, sizes, ivs, tags = parse_wire(late_wire)
        box: list = [None, None]

        def late():
            try:
                with deadline_scope(Deadline.after(0.2)):
                    box[0] = batcher.submit(DK, payloads, sizes, ivs, tags)
            except BaseException as exc:  # noqa: BLE001 - asserted
                box[1] = exc

        t = threading.Thread(target=late)
        t.start()
        wait_queued(batcher, 1)
        if held_in == "flush_now":
            flusher = threading.Thread(target=batcher.flush_now)
            flusher.start()
            time.sleep(0.4)  # the deadline passes while the cap holds it
            assert flusher.is_alive()
            gate.set()
            flusher.join(30)
        t.join(10)
        assert not t.is_alive() and isinstance(box[1], DeadlineExceededException), box
        assert batcher.merged_launches == 1 and batcher.expired_windows == 1
        gate.set()
        first[0].join(timeout=30)
        assert first[1][0] == plain
        release()
        with batcher._cond:
            assert batcher._uncollected == 0 and batcher._inflight == 0
            assert not batcher._buckets
        batcher.stop()
        backend.close()

    def test_a_waiter_that_gave_up_holds_no_launch(self):
        """A waiter whose liveness backstop fired before its flush is not
        counted among the launch's collectors: nothing waits for it."""
        clock = [0.0]
        backend = TpuTransformBackend()
        batcher = WindowBatcher(backend, wait_ms=50, time_source=lambda: clock[0])
        batcher.WAIT_GRACE_S = 0.0
        release = park_fast_path(batcher)
        plain, wire = make_window(230, [512])
        gave_up = _entry(wire, now=0.0, deadline_at=0.05)
        kept = _entry(wire, now=0.0)
        key = (LATENCY, True, bytes(DK.data_key), bytes(DK.aad), 1024)
        with batcher._cond:
            batcher._buckets[key] = [gave_up, kept]
        with pytest.raises(BatcherStoppedError):
            batcher._await_entry(gave_up)  # 50 ms on the frozen clock
        assert batcher.flush_now() == 1  # still inside its deadline: launched
        assert gave_up.left and gave_up.launch is None
        with batcher._cond:
            assert batcher._uncollected == 1 and kept.launch.waiters == 1
        assert batcher._await_entry(kept) == plain
        release()
        with batcher._cond:
            assert batcher._uncollected == 0 and batcher._inflight == 0
        backend.close()

    def test_launch_time_runs_to_the_last_waiters_rows(self):
        backend = TpuTransformBackend()
        batcher = WindowBatcher(backend, wait_ms=50, max_windows=8)
        release = park_fast_path(batcher)
        gate, arrived = self.hold_collects(batcher)
        plains, wires = zip(*(make_window(210 + i, [500]) for i in range(2)))
        jobs = [queued_submit(batcher, list(w)) for w in wires]
        wait_queued(batcher, 2)
        assert batcher.flush_now() == 1
        for _ in jobs:
            assert arrived.acquire(timeout=30)
        with batcher._cond:
            assert batcher._launch_s == []  # not sampled at the launch
        time.sleep(0.2)
        gate.set()
        for (t, box), plain in zip(jobs, plains):
            t.join(timeout=30)
            assert box[0] == plain
        with batcher._cond:
            [launch_s] = batcher._launch_s
        assert launch_s >= 0.2
        release()
        backend.close()


class TestFastPath:
    def test_single_waiter_dispatches_inline(self):
        backend = TpuTransformBackend()
        backend.enable_batching(wait_ms=200)
        batcher = backend.batcher
        plain, wire = make_window(40, [900, 900])
        got = backend.detransform(list(wire), D_OPTS)
        assert got == plain
        # Structurally zero added wait: no queue hop, no flusher launch —
        # had the window queued, it would show as a batched window and a
        # flusher launch (and pay up to wait_ms=200 before flushing).
        assert batcher.windows_submitted == 1
        assert batcher.fast_path_windows == 1
        assert batcher.batched_windows == 0
        assert batcher.launches == 0
        assert backend.dispatch_stats.dispatches == 1
        backend.close()

    def test_fast_path_serves_hot_tier_hook(self):
        backend = TpuTransformBackend()
        backend.enable_batching()
        offered = []
        backend.on_decrypt_window = (
            lambda out, sizes, n_bytes, mesh: offered.append(sizes)
        )
        plain, wire = make_window(41, [800, 800])
        assert backend.detransform(list(wire), D_OPTS) == plain
        assert offered == [[800, 800]]
        backend.close()

    def test_zero_length_rows_bypass_batcher(self):
        backend = TpuTransformBackend()
        backend.enable_batching()
        plain, wire = make_window(42, [0, 512])
        assert backend.detransform(list(wire), D_OPTS) == plain
        assert backend.batcher.windows_submitted == 0
        backend.close()


class TestParityAndEvidence:
    def test_n_thread_parity_vs_unbatched(self):
        n = 16
        windows = [make_window(50 + i, [1200 + (i % 3) * 40] * 3) for i in range(n)]
        control = TpuTransformBackend()
        expect = [control.detransform(list(w), D_OPTS) for _, w in windows]
        control.close()
        assert expect == [p for p, _ in windows]

        backend = TpuTransformBackend()
        backend.enable_batching(wait_ms=25, max_windows=8)
        results: list = [None] * n
        errors: list = []
        barrier = threading.Barrier(n)

        def fetch(i):
            try:
                barrier.wait(timeout=30)
                results[i] = backend.detransform(list(windows[i][1]), D_OPTS)
            except Exception as exc:  # noqa: BLE001
                errors.append((i, exc))

        threads = [threading.Thread(target=fetch, args=(i,)) for i in range(n)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        assert errors == []
        assert results == expect
        batcher = backend.batcher
        stats = backend.dispatch_stats
        assert stats.windows == n
        assert (
            batcher.fast_path_windows + batcher.batched_windows == n
        )
        assert stats.dispatches <= n
        assert stats.dispatches_per_window <= 1.0
        backend.close()

    def test_thread_evidence_seam(self):
        backend = TpuTransformBackend()
        batcher = WindowBatcher(backend, wait_ms=50)
        release = park_fast_path(batcher)
        assert backend.thread_batch_evidence() == (0, 0.0, 0)
        plain, wire = make_window(60, [512])
        payloads, sizes, ivs, tags = parse_wire(wire)
        box: list = [None, None, None]

        def run():
            before = batcher.thread_evidence()
            try:
                result = batcher.submit(DK, payloads, sizes, ivs, tags)
            except BaseException as exc:  # noqa: BLE001
                box[1] = exc
                return
            box[0] = result
            box[2] = (before, batcher.thread_evidence())

        t = threading.Thread(target=run)
        t.start()
        wait_queued(batcher, 1)
        batcher.flush_now()
        release()
        t.join(timeout=30)
        assert box[1] is None and box[0] == plain
        before, after = box[2]
        assert before == (0, 0.0, 0)
        assert after == (1, 1.0, 1)  # one window, occupancy 1, batch id 1
        # Evidence is thread-local: this thread still reads zero.
        assert batcher.thread_evidence() == (0, 0.0, 0)
        backend.close()

    def test_flight_record_derives_batch_occupancy(self):
        from tieredstorage_tpu.utils.flightrecorder import RequestRecord

        record = RequestRecord(name="r", trace_id="t", start_s=0.0)
        record.counters["gcm.batched_windows"] = 2.0
        record.counters["gcm.batch_occupancy"] = 7.0
        assert record.to_dict()["gcm_batch_occupancy"] == 3.5
        bare = RequestRecord(name="r", trace_id="t", start_s=0.0)
        assert "gcm_batch_occupancy" not in bare.to_dict()


class TestConfigWiring:
    def test_configure_enables_and_close_stops(self):
        backend = TpuTransformBackend()
        backend.configure({
            "batch.enabled": True, "batch.wait.ms": 7, "batch.windows": 4,
        })
        batcher = backend.batcher
        assert batcher is not None
        assert batcher.wait_ms == 7.0
        assert batcher.max_windows == 4
        assert batcher.max_bytes == backend.preferred_batch_bytes
        assert batcher._thread is not None and batcher._thread.is_alive()
        backend.close()
        assert backend.batcher is None
        with pytest.raises(BatcherStoppedError):
            batcher.submit(DK, [b"x" * 32], [32], np.zeros((1, 12), np.uint8),
                           [b"t" * 16])

    def test_configure_accepts_string_bool(self):
        backend = TpuTransformBackend()
        backend.configure({"batch.enabled": "true"})
        assert backend.batcher is not None
        backend.close()
        off = TpuTransformBackend()
        off.configure({"batch.enabled": "false"})
        assert off.batcher is None
        off.configure({})
        assert off.batcher is None
        off.close()

    def test_flush_byte_cap_follows_batch_bytes(self):
        backend = TpuTransformBackend()
        backend.configure({"batch.bytes": 1 << 20, "batch.enabled": True})
        assert backend.batcher.max_bytes == 1 << 20
        backend.close()

    def test_started_flusher_coalesces_under_concurrency(self):
        """End-to-end through the daemon: queued windows flush within
        wait_ms and share launches."""
        backend = TpuTransformBackend()
        backend.enable_batching(wait_ms=30, max_windows=8)
        n = 6
        windows = [make_window(70 + i, [768, 768]) for i in range(n)]
        results: list = [None] * n
        barrier = threading.Barrier(n)

        def fetch(i):
            barrier.wait(timeout=30)
            results[i] = backend.detransform(list(windows[i][1]), D_OPTS)

        threads = [threading.Thread(target=fetch, args=(i,)) for i in range(n)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        assert results == [p for p, _ in windows]
        batcher = backend.batcher
        assert batcher.windows_submitted == n
        assert batcher.fast_path_windows + batcher.batched_windows == n
        backend.close()


class TestBatchMetrics:
    def test_gauges_and_histograms(self):
        from tieredstorage_tpu.metrics.batch_metrics import (
            register_batch_metrics,
        )
        from tieredstorage_tpu.metrics.core import MetricsRegistry

        backend = TpuTransformBackend()
        batcher = WindowBatcher(backend, wait_ms=50)
        registry = MetricsRegistry()
        register_batch_metrics(registry, batcher)

        release = park_fast_path(batcher)
        plains, wires = zip(*(make_window(80 + i, [500]) for i in range(2)))
        jobs = [queued_submit(batcher, list(w)) for w in wires]
        wait_queued(batcher, 2)
        batcher.flush_now()
        release()
        for t, _ in jobs:
            t.join(timeout=30)

        def value(name):
            for mn in registry.metric_names:
                if mn.name == name and mn.group == "batch-metrics":
                    return registry.value(mn)
            raise AssertionError(name)

        assert value("batch-windows-submitted-total") == 2.0
        assert value("batch-coalesced-windows-total") == 2.0
        assert value("batch-launches-total") == 1.0
        assert value("batch-fast-path-windows-total") == 0.0
        assert value("batch-mean-occupancy") == 2.0
        # The flush hook filled both histograms: one occupancy sample,
        # one added-wait sample per coalesced window.
        occ = None
        wait_hist = None
        for mn in registry.metric_names:
            if mn.name == "batch-occupancy":
                occ = registry.stat(mn)
            if mn.name == "batch-added-wait-time-ms":
                wait_hist = registry.stat(mn)
        assert occ is not None and occ.count == 1
        assert occ.sum == 2.0
        assert wait_hist is not None and wait_hist.count == 2
        backend.close()

    def test_rsm_registers_batch_group(self):
        from tieredstorage_tpu.rsm import RemoteStorageManager

        rsm = RemoteStorageManager()
        rsm.configure({
            "storage.backend.class":
                "tieredstorage_tpu.storage.memory.InMemoryStorage",
            "chunk.size": 1024,
            "key.prefix": "b/",
            "transform.backend.class":
                "tieredstorage_tpu.transform.tpu.TpuTransformBackend",
            "transform.batch.enabled": True,
        })
        try:
            names = {
                mn.name for mn in rsm.metrics.registry.metric_names
                if mn.group == "batch-metrics"
            }
            assert "batch-coalesced-windows-total" in names
            assert "batch-occupancy" in names
            batcher = rsm._transform_backend.batcher
            assert batcher is not None
            assert batcher.on_flush is not None
        finally:
            rsm.close()
