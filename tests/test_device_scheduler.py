"""Work-class-aware device scheduler (ISSUE 16, transform/scheduler.py +
the class-aware half of transform/batcher.py).

Covers the pure scheduling logic exactly (thread-local scope, class age
bounds, flush-priority ordering, admission arithmetic — the mutation
target), the fake-clock policy matrix (latency out-ranks queued
background at every flush decision, the background starvation watchdog
forces a flush under sustained foreground pressure, admission paces
background launches, classes never mix in one merged launch, a background
launch failure wakes only its own class), and the encrypt-path
coalescing satellite: concurrent produces through the batched backend
yield byte-identical wire vs the unbatched path with
``dispatches_per_window < 1`` and the donation/roundtrip gates holding
through the merge. Deterministic coalescing uses the same idiom as
tests/test_window_batcher.py: park the ``_inflight`` fast path, queue,
drain with ``flush_now()``."""

from __future__ import annotations

import random
import threading
import time

import pytest

from tieredstorage_tpu.transform.scheduler import (
    BACKGROUND,
    CLASS_RANK,
    DEFAULT_BACKGROUND_MAX_AGE_MS,
    DEFAULT_SHARES,
    LATENCY,
    THROUGHPUT,
    WORK_CLASSES,
    admission_defer_s,
    admission_refill,
    class_max_age_ms,
    current_work_class,
    flush_priority,
    validate_work_class,
    work_class_scope,
)


class TestWorkClassScope:
    def test_unscoped_thread_reads_none(self):
        assert current_work_class() is None

    def test_scope_sets_and_restores(self):
        with work_class_scope(BACKGROUND) as cls:
            assert cls == BACKGROUND
            assert current_work_class() == BACKGROUND
        assert current_work_class() is None

    def test_nested_innermost_wins_and_unwinds(self):
        with work_class_scope(THROUGHPUT):
            with work_class_scope(BACKGROUND):
                assert current_work_class() == BACKGROUND
            assert current_work_class() == THROUGHPUT

    def test_scope_restores_on_exception(self):
        with pytest.raises(RuntimeError):
            with work_class_scope(LATENCY):
                raise RuntimeError("boom")
        assert current_work_class() is None

    def test_scope_is_thread_local(self):
        seen = []

        def run():
            seen.append(current_work_class())

        with work_class_scope(BACKGROUND):
            t = threading.Thread(target=run)
            t.start()
            t.join(timeout=10)
        assert seen == [None]

    def test_validate_rejects_unknown(self):
        with pytest.raises(ValueError):
            validate_work_class("interactive")
        with pytest.raises(ValueError):
            work_class_scope("gc").__enter__()
        for cls in WORK_CLASSES:
            assert validate_work_class(cls) == cls


class TestPureScheduling:
    """Exact arithmetic: the mutation-testing surface."""

    def test_rank_and_share_constants(self):
        # Strict rank order latency < throughput < background, and the
        # 8/4/1 weighted shares — the documented isolation contract.
        assert CLASS_RANK == {LATENCY: 0, THROUGHPUT: 1, BACKGROUND: 2}
        assert DEFAULT_SHARES == {LATENCY: 8, THROUGHPUT: 4, BACKGROUND: 1}
        assert DEFAULT_BACKGROUND_MAX_AGE_MS == 50.0
        assert WORK_CLASSES == (LATENCY, THROUGHPUT, BACKGROUND)

    def test_class_max_age(self):
        assert class_max_age_ms(LATENCY, 2.0, 50.0) == 2.0
        assert class_max_age_ms(THROUGHPUT, 2.0, 50.0) == 2.0
        assert class_max_age_ms(BACKGROUND, 2.0, 50.0) == 50.0

    def test_latency_outranks_any_deficit(self):
        # A latency bucket with a HUGE served deficit still sorts before a
        # starving background bucket: strict priority, not weighted.
        lat = flush_priority(LATENCY, 1 << 40, 8, oldest_enqueued_at=9.0)
        bg = flush_priority(BACKGROUND, 0, 1, oldest_enqueued_at=0.0)
        assert lat < bg

    def test_weighted_deficit_orders_non_latency(self):
        # served/share: throughput at 400/4=100 vs background at 50/1=50 —
        # background is further below its share and launches first.
        thr = flush_priority(THROUGHPUT, 400, 4, oldest_enqueued_at=0.0)
        bg = flush_priority(BACKGROUND, 50, 1, oldest_enqueued_at=0.0)
        assert bg < thr
        # Equal deficits fall back to the strict rank...
        assert flush_priority(THROUGHPUT, 40, 4, 0.0) < flush_priority(
            BACKGROUND, 10, 1, 0.0
        )
        # ...and equal ranks to FIFO age.
        assert flush_priority(BACKGROUND, 10, 1, 1.0) < flush_priority(
            BACKGROUND, 10, 1, 2.0
        )

    def test_zero_share_sorts_last(self):
        assert flush_priority(BACKGROUND, 0, 0, 0.0)[1] == float("inf")

    def test_flush_priority_validates(self):
        with pytest.raises(ValueError):
            flush_priority("bulk", 0, 1, 0.0)

    def test_admission_refill_exact(self):
        # 100 B/s over 0.25 s accrues exactly 25 B.
        assert admission_refill(0.0, 100.0, 1000.0, 0.25) == 25.0
        # Burst cap binds: 900 + 200*1 clamps at 1000, not 1100.
        assert admission_refill(900.0, 200.0, 1000.0, 1.0) == 1000.0
        # Debt pays down before budget accrues: -50 + 100*1 = 50.
        assert admission_refill(-50.0, 100.0, 1000.0, 1.0) == 50.0
        # Zero elapsed is a no-op (and legal).
        assert admission_refill(7.0, 100.0, 1000.0, 0.0) == 7.0
        with pytest.raises(ValueError):
            admission_refill(0.0, 100.0, 1000.0, -0.001)

    def test_admission_defer_exact(self):
        # 1024 B short at 512 B/s = exactly 2 s.
        assert admission_defer_s(0.0, 1024.0, 512.0) == 2.0
        # Allowance covering the need admits NOW — including exactly.
        assert admission_defer_s(1024.0, 1024.0, 512.0) == 0.0
        assert admission_defer_s(2048.0, 1024.0, 512.0) == 0.0
        # No rate configured = no admission control.
        assert admission_defer_s(0.0, 1024.0, 0.0) == 0.0
        assert admission_defer_s(0.0, 1024.0, -1.0) == 0.0
        # Debt adds to the wait: (1024 - (-512)) / 512 = 3 s.
        assert admission_defer_s(-512.0, 1024.0, 512.0) == 3.0


# --------------------------------------------------------------------------
# Policy matrix + encrypt coalescing: need the real batcher and backend.
jax = pytest.importorskip("jax")

import numpy as np  # noqa: E402

from tieredstorage_tpu.security.aes import (  # noqa: E402
    IV_SIZE,
    TAG_SIZE,
    AesEncryptionProvider,
)
from tieredstorage_tpu.transform.api import (  # noqa: E402
    DetransformOptions,
    TransformOptions,
)
from tieredstorage_tpu.transform.batcher import WindowBatcher  # noqa: E402
from tieredstorage_tpu.transform.tpu import TpuTransformBackend  # noqa: E402

DK = AesEncryptionProvider.create_data_key_and_aad()
D_OPTS = DetransformOptions(encryption=DK)


def make_window(seed: int, sizes) -> tuple[list[bytes], list[bytes]]:
    """(plaintext chunks, wire chunks) for one window under DK."""
    rng = random.Random(seed)
    chunks = [bytes(rng.getrandbits(8) for _ in range(s)) for s in sizes]
    backend = TpuTransformBackend()
    ivs = det_ivs(seed, len(sizes))
    wire = backend.transform(chunks, TransformOptions(encryption=DK, ivs=ivs))
    backend.close()
    return chunks, wire


def det_ivs(seed: int, n: int) -> list[bytes]:
    return [(seed * 64 + i + 1).to_bytes(4, "big") * 3 for i in range(n)]


def parse_wire(wire: list[bytes]):
    ivs = np.stack([np.frombuffer(c[:IV_SIZE], np.uint8) for c in wire])
    tags = [c[-TAG_SIZE:] for c in wire]
    sizes = [len(c) - IV_SIZE - TAG_SIZE for c in wire]
    payloads = [c[IV_SIZE:-TAG_SIZE] for c in wire]
    return payloads, sizes, ivs, tags


def park_fast_path(batcher: WindowBatcher):
    with batcher._cond:
        batcher._inflight += 1

    def release():
        with batcher._cond:
            batcher._inflight -= 1

    return release


def wait_queued(batcher: WindowBatcher, n: int, timeout_s: float = 5.0):
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        with batcher._cond:
            if sum(len(v) for v in batcher._buckets.values()) >= n:
                return
        time.sleep(0.001)
    raise AssertionError(f"never saw {n} queued windows")


def scoped_submit(batcher: WindowBatcher, wire: list[bytes], work_class):
    """Background-thread decrypt submit under a work-class scope."""
    payloads, sizes, ivs, tags = parse_wire(wire)
    box: list = [None, None]

    def run():
        try:
            if work_class is None:
                box[0] = batcher.submit(DK, payloads, sizes, ivs, tags)
            else:
                with work_class_scope(work_class):
                    box[0] = batcher.submit(DK, payloads, sizes, ivs, tags)
        except BaseException as exc:  # noqa: BLE001 - asserted by tests
            box[1] = exc

    t = threading.Thread(target=run)
    t.start()
    return t, box


class TestSchedulerPolicy:
    """Fake-clock policy matrix over the class-aware flush decision."""

    def make(self, **kw):
        self.clock = [0.0]
        backend = TpuTransformBackend()
        kw.setdefault("wait_ms", 10.0)
        kw.setdefault("max_windows", 4)
        kw.setdefault("max_bytes", 10_000)
        return WindowBatcher(backend, time_source=lambda: self.clock[0], **kw)

    def inject(self, batcher, work_class, wire, now=0.0):
        payloads, sizes, ivs, tags = parse_wire(wire)
        from tieredstorage_tpu.transform.batcher import _PendingWindow

        entry = _PendingWindow(
            payloads=payloads, sizes=sizes, ivs=ivs, tags=tags,
            n_bytes=sum(sizes), enqueued_at=now, deadline_at=None,
            work_class=work_class,
        )
        key = (work_class, True, bytes(DK.data_key), bytes(DK.aad), 1024)
        with batcher._cond:
            batcher._buckets.setdefault(key, []).append(entry)
        return key, entry

    def test_ctor_validates_class_knobs(self):
        backend = TpuTransformBackend()
        with pytest.raises(ValueError):
            WindowBatcher(backend, background_max_age_ms=-1)
        with pytest.raises(ValueError):
            WindowBatcher(backend, class_shares={BACKGROUND: 0})
        with pytest.raises(ValueError):
            WindowBatcher(backend, class_shares={"bulk": 2})
        ok = WindowBatcher(
            backend, background_max_age_ms=0, class_shares={BACKGROUND: 3},
        )
        assert ok.background_max_age_ms == 0.0
        assert ok.class_shares[BACKGROUND] == 3.0
        assert ok.class_shares[LATENCY] == DEFAULT_SHARES[LATENCY]
        backend.close()

    def test_latency_outranks_queued_background(self):
        """Both classes due: latency flushes FIRST at every decision."""
        batcher = self.make(background_max_age_ms=50.0)
        _, wire = make_window(101, [512] * 2)
        bg_key, _ = self.inject(batcher, BACKGROUND, wire, now=0.0)
        lat_key, _ = self.inject(batcher, LATENCY, wire, now=0.05)
        # Give background a massive age head start; latency still leads.
        self.clock[0] = 1.0
        with batcher._cond:
            due, _ = batcher._due_keys_locked(1.0)
        assert due == [lat_key, bg_key]
        # And the drain path launches in the same order.
        order: list = []
        batcher.on_flush = lambda occ, added, cls, *rest: order.append(cls)
        assert batcher.flush_now() == 2
        assert order == [LATENCY, BACKGROUND]
        batcher._backend.close()

    def test_background_watchdog_bounds_starvation(self):
        """A background bucket may wait longer than wait_ms — but NEVER
        past background_max_age_ms: bounded forward progress."""
        batcher = self.make(wait_ms=10.0, background_max_age_ms=50.0)
        _, wire = make_window(102, [512])
        bg_key, _ = self.inject(batcher, BACKGROUND, wire, now=0.0)
        # Past the foreground wait_ms bound: background is NOT yet due...
        with batcher._cond:
            due, timeout = batcher._due_keys_locked(0.020)
        assert due == [] and timeout == pytest.approx(0.030)
        # ...but the watchdog bound is hard: at 50 ms it MUST flush.
        with batcher._cond:
            due, _ = batcher._due_keys_locked(0.050)
        assert due == [bg_key]
        batcher._backend.close()

    def test_weighted_deficit_orders_throughput_vs_background(self):
        batcher = self.make()
        _, wire = make_window(103, [512])
        thr_key, _ = self.inject(batcher, THROUGHPUT, wire, now=0.0)
        bg_key, _ = self.inject(batcher, BACKGROUND, wire, now=0.0)
        self.clock[0] = 1.0
        with batcher._cond:
            # Fresh queue: equal deficits, strict rank puts throughput first.
            due, _ = batcher._due_keys_locked(1.0)
            assert due == [thr_key, bg_key]
            # Throughput far over its share, background under: bg first.
            batcher._served_bytes[THROUGHPUT] = 4000  # deficit 1000
            batcher._served_bytes[BACKGROUND] = 500   # deficit 500
            due, _ = batcher._due_keys_locked(1.0)
            assert due == [bg_key, thr_key]
        batcher._backend.close()

    def test_admission_rate_paces_background(self):
        """scrub.rate.bytes as an admission class: a drained allowance
        defers the flush until the byte budget accrues — the watchdog
        bound yields to admission (paced, not starved: the wake time IS
        the refill time)."""
        batcher = self.make(background_max_age_ms=50.0)
        batcher.set_class_rate(BACKGROUND, 1024.0)
        _, wire = make_window(104, [1024])  # n_bytes = 1024 = 1 s of rate
        bg_key, _ = self.inject(batcher, BACKGROUND, wire, now=0.0)
        with batcher._cond:
            batcher._class_allowance[BACKGROUND] = 0.0
            batcher._class_refill_at[BACKGROUND] = 0.0
        # Watchdog age reached, but the budget needs a full second.
        with batcher._cond:
            due, timeout = batcher._due_keys_locked(0.060)
        assert due == [] and timeout == pytest.approx(0.940)
        with batcher._cond:
            due, _ = batcher._due_keys_locked(1.0)
        assert due == [bg_key]
        # The take draws the allowance down (to zero here: 1 s accrued
        # 1024 B, the flush spends exactly 1024 B).
        self.clock[0] = 1.0
        with batcher._cond:
            batcher._due_keys_locked(1.0)  # refill to now
            batcher._take_locked(bg_key)
            assert batcher._class_allowance[BACKGROUND] == pytest.approx(0.0)
            assert batcher._served_bytes[BACKGROUND] == 1024
        batcher._backend.close()

    def test_unrated_class_admits_immediately(self):
        batcher = self.make()
        _, wire = make_window(105, [512])
        lat_key, _ = self.inject(batcher, LATENCY, wire, now=0.0)
        with batcher._cond:
            due, _ = batcher._due_keys_locked(0.010)
        assert due == [lat_key]
        # Clearing a configured rate restores immediate admission.
        batcher.set_class_rate(BACKGROUND, 1.0)
        batcher.set_class_rate(BACKGROUND, None)
        with batcher._cond:
            assert BACKGROUND not in batcher._class_rate
        with pytest.raises(ValueError):
            batcher.set_class_rate("bulk", 1.0)
        batcher._backend.close()

    def test_flush_now_drains_despite_admission(self):
        """stop()/tests must terminate: the sync drain ignores admission."""
        batcher = self.make()
        batcher.set_class_rate(BACKGROUND, 1.0)  # ~never admits 1 KiB
        with batcher._cond:
            batcher._class_allowance[BACKGROUND] = 0.0
        plain, wire = make_window(106, [512])
        _, entry = self.inject(batcher, BACKGROUND, wire, now=0.0)
        assert batcher.flush_now() == 1
        assert entry.error is None and batcher._await_entry(entry) == plain
        batcher._backend.close()


class TestClassIsolation:
    def test_classes_never_mix_in_one_merged_launch(self):
        """Same key, same bucket bytes, different class: structurally
        distinct buckets, distinct launches."""
        backend = TpuTransformBackend()
        batcher = WindowBatcher(backend, wait_ms=50, max_windows=8)
        release = park_fast_path(batcher)
        plain_a, wire_a = make_window(110, [700])
        plain_b, wire_b = make_window(111, [700])
        job_a = scoped_submit(batcher, wire_a, None)  # defaults to latency
        job_b = scoped_submit(batcher, wire_b, BACKGROUND)
        wait_queued(batcher, 2)
        classes: list = []
        batcher.on_flush = lambda occ, added, cls, *rest: classes.append((cls, occ))
        with batcher._cond:
            assert len(batcher._buckets) == 2
        assert batcher.flush_now() == 2
        release()
        for (t, box), plain in ((job_a, plain_a), (job_b, plain_b)):
            t.join(timeout=30)
            assert box[1] is None and box[0] == plain
        assert batcher.launches == 2
        assert classes == [(LATENCY, 1), (BACKGROUND, 1)]
        assert batcher.class_launches[LATENCY] == 1
        assert batcher.class_launches[BACKGROUND] == 1
        assert batcher.class_flushed_windows[BACKGROUND] == 1
        backend.close()

    def test_background_launch_failure_wakes_only_its_class(self):
        """The robustness core: a device failure in a background scrub
        flush delivers the exception to background waiters ALONE — the
        queued latency window still decrypts."""
        backend = TpuTransformBackend()
        batcher = WindowBatcher(backend, wait_ms=50)
        release = park_fast_path(batcher)
        plain_ok, wire_ok = make_window(112, [640])
        _, wire_bg = make_window(113, [640])
        job_lat = scoped_submit(batcher, wire_ok, None)
        job_bg = scoped_submit(batcher, wire_bg, BACKGROUND)
        wait_queued(batcher, 2)
        # Flush ONLY the background bucket against an exploding device.
        with batcher._cond:
            bg_key = next(k for k in batcher._buckets if k[0] == BACKGROUND)
            bg_entries = batcher._take_locked(bg_key)
        boom = RuntimeError("device fell over mid-scrub")
        real_stage = backend._stage_packed
        backend._stage_packed = lambda packed, varlen: (_ for _ in ()).throw(boom)
        batcher._flush_group(bg_key, bg_entries)
        backend._stage_packed = real_stage
        job_bg[0].join(timeout=30)
        assert job_bg[1][1] is boom
        # The latency waiter was NOT woken, let alone poisoned...
        assert job_lat[0].is_alive()
        assert job_lat[1] == [None, None]
        # ...and flushes cleanly on the recovered device.
        assert batcher.flush_now() == 1
        release()
        job_lat[0].join(timeout=30)
        assert job_lat[1][1] is None and job_lat[1][0] == plain_ok
        assert batcher.launch_failures == 1
        assert batcher.launches == 1
        backend.close()

    def test_background_never_takes_the_fast_path(self):
        """An IDLE batcher still queues background work: admission and
        the watchdog govern every background launch."""
        backend = TpuTransformBackend()
        batcher = WindowBatcher(backend, wait_ms=50)
        plain, wire = make_window(114, [600])
        job = scoped_submit(batcher, wire, BACKGROUND)
        wait_queued(batcher, 1)  # queued despite zero contention
        assert batcher.flush_now() == 1
        job[0].join(timeout=30)
        assert job[1][1] is None and job[1][0] == plain
        assert batcher.fast_path_windows == 0
        assert batcher.batched_windows == 1
        backend.close()

    def test_scrubber_detransform_runs_background_class(self):
        """The scrubber's verification decrypts join the background
        class: its ambient scope reaches the batcher through the full
        detransform call chain."""
        backend = TpuTransformBackend()
        backend.enable_batching(wait_ms=10)
        plain, wire = make_window(115, [800])
        with work_class_scope(BACKGROUND):
            got = backend.detransform(list(wire), D_OPTS)
        assert got == plain
        batcher = backend.batcher
        assert batcher.fast_path_windows == 0
        assert batcher.class_flushed_windows[BACKGROUND] == 1
        backend.close()


class TestEncryptCoalescing:
    """Satellite: concurrent produces coalesce with byte parity."""

    def test_concurrent_produces_merge_byte_identically(self):
        n = 4
        seeds = [120 + i for i in range(n)]
        sizes = [[600 + 40 * i, 700] for i in range(n)]
        rngs = [random.Random(s) for s in seeds]
        windows = [
            [bytes(r.getrandbits(8) for _ in range(sz)) for sz in szs]
            for r, szs in zip(rngs, sizes)
        ]
        opts = [
            TransformOptions(encryption=DK, ivs=det_ivs(s, len(szs)))
            for s, szs in zip(seeds, sizes)
        ]
        control = TpuTransformBackend()
        expect = [control.transform(w, o) for w, o in zip(windows, opts)]
        cstats = control.dispatch_stats
        # The unbatched control: one dispatch per window, every staged
        # buffer donated, roundtrips bounded.
        assert cstats.dispatches_per_window == 1.0
        assert cstats.donated_buffers == cstats.windows == n
        # Roundtrips/window depend on the GHASH kernel path (the tree
        # kernel hits 1.0, the ladder fallback pays more — see
        # test_fused_window): the control's measured value is the bound
        # the merge must stay within.
        control_rt = cstats.hbm_roundtrips_per_window
        control.close()

        backend = TpuTransformBackend()
        # Unstarted batcher wired straight onto the backend: no flusher
        # daemon racing the parked fast path, so the merge below is driven
        # deterministically by flush_now.
        batcher = WindowBatcher(backend, wait_ms=25, max_windows=8)
        backend.batcher = batcher
        release = park_fast_path(batcher)
        results: list = [None] * n
        errors: list = []

        def produce(i):
            try:
                results[i] = backend.transform(windows[i], opts[i])
            except Exception as exc:  # noqa: BLE001
                errors.append((i, exc))

        threads = [threading.Thread(target=produce, args=(i,)) for i in range(n)]
        for t in threads:
            t.start()
        wait_queued(batcher, n)
        assert batcher.flush_now() == 1  # ONE merged encrypt launch
        release()
        for t in threads:
            t.join(timeout=60)
        assert errors == []
        # Byte-identical wire segments vs the unbatched path.
        assert results == expect
        stats = backend.dispatch_stats
        assert stats.windows == n
        assert stats.dispatches == 1
        assert stats.dispatches_per_window < 1.0
        assert stats.d2h_fetches == n  # each handle fetches its own rows
        # Donation/roundtrip gates hold through the merge: the ONE merged
        # launch donated its staged buffer, and the shared program stays
        # within the per-window roundtrip budget.
        assert stats.donated_buffers == stats.dispatches == 1
        # ONE merged launch amortizes the keystream handoff over all n
        # windows: strictly under the per-window budget and never worse
        # than the unbatched control on the same kernel path.
        assert stats.hbm_roundtrips_per_window <= 1.0
        assert stats.hbm_roundtrips_per_window <= control_rt
        assert batcher.launches == 1
        assert batcher.mean_occupancy == float(n)
        assert batcher.class_flushed_windows[THROUGHPUT] == n
        backend.close()

    def test_idle_encrypt_takes_fast_path_and_pipelines(self):
        """A single produce stream never queues: submit_encrypt holds the
        in-flight count only across the async dispatch, so pipelined
        windows dispatch inline back-to-back — zero added latency, zero
        flusher launches."""
        windows = [make_window(130 + i, [512, 512])[0] for i in range(3)]
        ivs = [iv for i in range(3) for iv in det_ivs(130 + i, 2)]
        opts = TransformOptions(encryption=DK, ivs=list(ivs))
        control = TpuTransformBackend()
        expect = list(control.transform_windows(windows, opts))
        control.close()

        backend = TpuTransformBackend()
        backend.enable_batching(wait_ms=25)
        got = list(backend.transform_windows(windows, opts))
        assert got == expect
        batcher = backend.batcher
        assert batcher.windows_submitted == 3
        assert batcher.fast_path_windows == 3
        assert batcher.launches == 0
        assert backend.dispatch_stats.dispatches_per_window == 1.0
        backend.close()

    def test_encrypt_and_decrypt_never_share_a_bucket(self):
        backend = TpuTransformBackend()
        batcher = WindowBatcher(backend, wait_ms=50)
        backend.batcher = batcher  # unstarted: flush_now drives the drain
        release = park_fast_path(batcher)
        plain, wire = make_window(140, [700])
        job_dec = scoped_submit(batcher, wire, None)
        wait_queued(batcher, 1)
        enc_box: list = [None, None]

        def produce():
            try:
                rng = random.Random(141)
                chunks = [bytes(rng.getrandbits(8) for _ in range(700))]
                enc_box[0] = backend.transform(
                    chunks, TransformOptions(encryption=DK, ivs=det_ivs(141, 1))
                )
            except Exception as exc:  # noqa: BLE001
                enc_box[1] = exc

        t_enc = threading.Thread(target=produce)
        t_enc.start()
        wait_queued(batcher, 2)
        with batcher._cond:
            directions = sorted(k[1] for k in batcher._buckets)
            assert directions == [False, True]  # encrypt + decrypt buckets
        assert batcher.flush_now() == 2  # never one merged launch
        release()
        job_dec[0].join(timeout=30)
        t_enc.join(timeout=30)
        assert job_dec[1][1] is None and job_dec[1][0] == plain
        assert enc_box[1] is None and enc_box[0] is not None
        # The batched encrypt wire decrypts byte-clean.
        rt = TpuTransformBackend()
        rng_check = random.Random(141)
        assert rt.detransform(enc_box[0], D_OPTS) == [
            bytes(rng_check.getrandbits(8) for _ in range(700))
        ]
        rt.close()
        assert batcher.launches == 2
        backend.close()

    def test_zero_length_chunk_encrypt_bypasses_batcher(self):
        backend = TpuTransformBackend()
        backend.enable_batching()
        rng = random.Random(150)
        chunks = [b"", bytes(rng.getrandbits(8) for _ in range(256))]
        wire = backend.transform(
            chunks, TransformOptions(encryption=DK, ivs=det_ivs(150, 2))
        )
        assert backend.batcher.windows_submitted == 0
        assert backend.detransform(wire, D_OPTS) == chunks
        backend.close()

    def test_encrypt_launch_failure_reaches_only_its_waiters(self):
        backend = TpuTransformBackend()
        batcher = WindowBatcher(backend, wait_ms=50)
        backend.batcher = batcher  # unstarted: flush_now drives the drain
        release = park_fast_path(batcher)
        enc_box: list = [None, None]

        def produce():
            try:
                rng = random.Random(160)
                chunks = [bytes(rng.getrandbits(8) for _ in range(512))]
                enc_box[0] = backend.transform(
                    chunks, TransformOptions(encryption=DK, ivs=det_ivs(160, 1))
                )
            except Exception as exc:  # noqa: BLE001
                enc_box[1] = exc

        t = threading.Thread(target=produce)
        t.start()
        wait_queued(batcher, 1)
        boom = RuntimeError("encrypt launch failed")
        backend._stage_packed = lambda packed, varlen: (_ for _ in ()).throw(boom)
        assert batcher.flush_now() == 1
        release()
        t.join(timeout=30)
        assert enc_box[1] is boom
        assert batcher.launch_failures == 1
        backend.close()


class TestConfigWiring:
    def test_background_max_age_config_reaches_batcher(self):
        backend = TpuTransformBackend()
        backend.configure({
            "batch.enabled": True, "batch.background.max.age.ms": 75,
        })
        assert backend.batcher.background_max_age_ms == 75.0
        backend.close()
        default = TpuTransformBackend()
        default.configure({"batch.enabled": True})
        assert default.batcher.background_max_age_ms == 50.0
        default.close()

    def test_class_gauges_registered(self):
        from tieredstorage_tpu.metrics.batch_metrics import (
            register_batch_metrics,
        )
        from tieredstorage_tpu.metrics.core import MetricsRegistry

        backend = TpuTransformBackend()
        batcher = WindowBatcher(backend, wait_ms=50)
        registry = MetricsRegistry()
        register_batch_metrics(registry, batcher)

        def value(name):
            for mn in registry.metric_names:
                if mn.name == name and mn.group == "batch-metrics":
                    return registry.value(mn)
            raise AssertionError(name)

        release = park_fast_path(batcher)
        _, wire = make_window(170, [500])
        job = scoped_submit(batcher, wire, BACKGROUND)
        wait_queued(batcher, 1)
        assert value("batch-class-background-queued-windows") == 1.0
        batcher.flush_now()
        release()
        job[0].join(timeout=30)
        assert job[1][1] is None
        assert value("batch-class-background-queued-windows") == 0.0
        assert value("batch-class-background-flushed-windows-total") == 1.0
        assert value("batch-class-background-launches-total") == 1.0
        assert value("batch-class-background-added-wait-ms-total") >= 0.0
        assert value("batch-class-latency-flushed-windows-total") == 0.0
        backend.close()


class TestLaunchRetry:
    """Unified failure policy (ISSUE 19): the merged flush launches through
    the shared retry driver at the ``device.launch`` seam — a transient
    device fault is absorbed by the bounded re-dispatch (each attempt
    re-stages from the host-side packed buffer, so retries are
    replay-safe), and waiters fail only after the configured cap."""

    def test_transient_stage_fault_absorbed_by_retry(self):
        from tieredstorage_tpu.storage.core import StorageBackendException

        backend = TpuTransformBackend()
        batcher = WindowBatcher(
            backend, wait_ms=50, launch_attempts=2, launch_backoff_s=0.0
        )
        release = park_fast_path(batcher)
        plain, wire = make_window(130, [640])
        job = scoped_submit(batcher, wire, None)
        wait_queued(batcher, 1)
        real_stage = backend._stage_packed
        boom = [1]

        def flaky_stage(packed, varlen):
            if boom[0]:
                boom[0] -= 1
                raise StorageBackendException("transient device hiccup")
            return real_stage(packed, varlen)

        backend._stage_packed = flaky_stage
        try:
            assert batcher.flush_now() == 1
        finally:
            backend._stage_packed = real_stage
        release()
        job[0].join(timeout=30)
        assert job[1][1] is None and job[1][0] == plain
        assert batcher.launch_retries == 1
        assert batcher.launch_failures == 0
        assert batcher.launches == 1
        backend.close()

    def test_fault_plane_flaky_launch_recovers(self):
        """The ``device.launch`` injection point drives the same retry:
        a flaky=1 rule errors the first launch attempt, the re-dispatch
        lands, and the waiter still gets its exact plaintext."""
        from tieredstorage_tpu.utils import faults

        backend = TpuTransformBackend()
        batcher = WindowBatcher(
            backend, wait_ms=50, launch_attempts=2, launch_backoff_s=0.0
        )
        release = park_fast_path(batcher)
        plain, wire = make_window(131, [512, 300])
        job = scoped_submit(batcher, wire, None)
        wait_queued(batcher, 1)
        plane = faults.FaultPlane.parse("device.launch:flaky=1")
        prior = faults.install(plane)
        try:
            assert batcher.flush_now() == 1
        finally:
            faults.install(prior)
        release()
        job[0].join(timeout=30)
        assert job[1][1] is None and job[1][0] == plain
        assert batcher.launch_retries == 1
        assert batcher.launch_failures == 0
        assert plane.snapshot()["fired"] == {"device.launch:flaky": 1}
        backend.close()

    def test_waiters_fail_after_retry_cap_then_recover_on_heal(self):
        from tieredstorage_tpu.utils import faults
        from tieredstorage_tpu.utils.faults import FaultInjectedError

        backend = TpuTransformBackend()
        batcher = WindowBatcher(
            backend, wait_ms=50, launch_attempts=2, launch_backoff_s=0.0
        )
        release = park_fast_path(batcher)
        plain, wire = make_window(132, [640])
        job = scoped_submit(batcher, wire, None)
        wait_queued(batcher, 1)
        prior = faults.install(faults.FaultPlane.parse("device.launch:error"))
        try:
            assert batcher.flush_now() == 1  # the flush ran; its launch died
        finally:
            faults.install(prior)
        job[0].join(timeout=30)
        assert isinstance(job[1][1], FaultInjectedError)
        assert batcher.launch_retries == 1  # the cap allowed ONE re-dispatch
        assert batcher.launch_failures == 1
        # Healed device: a fresh submit round-trips cleanly.
        job2 = scoped_submit(batcher, wire, None)
        wait_queued(batcher, 1)
        assert batcher.flush_now() == 1
        release()
        job2[0].join(timeout=30)
        assert job2[1][1] is None and job2[1][0] == plain
        backend.close()
