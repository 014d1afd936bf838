"""Work-class-aware device scheduler: ONE GCM queue for fetch, encrypt, scrub.

PR 8 fused a whole window into ONE device launch — but batching stopped at
the request boundary: under massed consumer replay a hundred concurrent
fetches stage a hundred small packed windows and pay a hundred per-launch
floors. Continuous-batching inference servers (Orca, OSDI '22; vLLM)
showed the fix: coalesce *concurrent* requests into shared device
launches. ``WindowBatcher`` applies the same shape to the GCM data plane,
and (ISSUE 16) extends it with Clockwork-style (OSDI '20) work classes so
every device consumer — fetch decrypts, encrypt windows coalesced across
concurrent produces, scrub/anti-entropy verification — shares the one
queue under an explicit isolation policy (transform/scheduler.py):

- ``TpuTransformBackend._decrypt_batch`` routes eligible windows here
  (``transform.batch.enabled``); each caller blocks while its rows ride a
  SHARED packed ``uint8[B, n_bytes + 16]`` launch, then collects its own
  rows of the one output buffer on its own thread.
  ``transform_windows`` routes encrypt windows through ``submit_encrypt``
  / ``_EncryptHandle.wait`` — async, so ``pipeline.depth`` overlap is
  preserved — and concurrent produces coalesce the same way.
- Decrypt windows group by ``(work_class, direction, AAD block count,
  bucket_max_bytes(max_size))`` — the SAME jit-shape ladder the unbatched
  varlen path quantizes through (``ops/gcm.py``), so coalescing can never
  introduce a retrace; merged row counts are padded up a power-of-two
  ladder for the same reason. Concurrent fetches of different segments
  hold different data keys, so a flush packs a per-launch key table of
  its windows' distinct ``(data_key, aad)`` and a row -> key index, and
  one launch of the keyed program (``gcm.gcm_keyed_window_packed``)
  decrypts them all; a flush of one key launches the single-key varlen
  program as before. Encrypt windows still group by key as well.
  Classes (and directions) structurally NEVER share a merged launch: a
  launch failure in a background scrub flush wakes background waiters
  only, never a latency-class fetch.
- The flush policy is deadline-aware and class-aware: a bucket flushes
  when its queued windows or bytes reach the caps, when the oldest waiter
  aged past its class bound (``wait_ms`` for latency/throughput; the
  ``background_max_age_ms`` starvation watchdog for background — bounded
  forward progress under sustained foreground pressure), or when the
  oldest waiter's remaining deadline minus the observed launch p95 hits
  the floor. Due buckets launch in scheduler order: latency-class windows
  out-rank queued throughput/background work at EVERY flush decision,
  with weighted-deficit fair share among the rest.
- **Per-class admission**: a class with a configured byte rate
  (``set_class_rate``; rsm wiring maps ``scrub.rate.bytes`` onto the
  background class) accrues launch budget scheduler-side — the
  replacement for the scrubber's host token bucket on device work.
- **Single-waiter fast path**: a foreground submit that finds the batcher
  idle (no queue, no launch in flight) dispatches inline through the
  ordinary unbatched window path — light load pays ZERO added latency and
  keeps byte-identical behavior (including the hot-tier retention hook).
  Background submits always queue, so admission and the watchdog govern
  every background launch.
- **The flusher stops at the launch**: it packs a bucket's rows into a
  buffer of the backend's staging ring, launches, and wakes the waiters;
  each waiter fetches its own rows of the merged output on its own thread
  and checks their tags (decrypt: one row at a time, taken on the device
  by ``gcm.take_rows``, one program per merged shape) or builds the wire
  chunks (encrypt, ``_EncryptHandle.wait``: a window that fills most of
  its launch reads the whole output, whose copy back the launch started),
  while the flusher launches the next due bucket. The staging buffer goes
  back to the ring when the last waiter leaves, if a waiter's rows came
  back (the program that read it has run, and no waiter reads an output
  that may be that buffer). At most ``pipeline_depth`` merged decrypt
  launches wait to be collected; the flusher waits beyond that, and a
  queued window whose deadline passes meanwhile fails fast. A merged
  encrypt launch is collected when its pipeline asks (or never, by a
  stream that is abandoned), so it counts against neither that cap nor
  ``_inflight``.
- **Per-row error isolation**: decrypt tags are verified per caller on
  the caller's own rows; one forged row fails that one request with
  ``AuthenticationError``, never its batch-mates, and a failed take fails
  its own waiter only. A waiter whose deadline expired before launch
  fails fast with ``DeadlineExceededException`` and is excluded from the
  pack (it cannot poison the batch).
- **Retention**: a decrypt waiter offers its own verified rows to the hot
  tier's capture scope on its own thread, as a device copy of those rows
  made only if the tier admits them (never a view that would pin the
  whole merged buffer).
- **Tracing** (the backend's tracer; nothing with tracing off): the span
  ``transform.batch_wait`` on a queued waiter from enqueue to its rows in
  hand, with the collect's ``transform.d2h_wait`` (split into
  ``transform.ready_wait`` / ``transform.collect``) inside it, and
  ``transform.batch_flush`` on the flusher around a merged launch's pack
  and launch, the launch's ``transform.launch`` (and so its
  ``device.window``) inside it. Exact counts in ``counters()`` (``/varz``
  ``batcher``).

Accounting: the flusher's launches land in the owning backend's
``DispatchStats`` (one launch, one staging transfer, one fetch per flush),
while each coalesced window still counts as a window — so
``dispatches_per_window`` becomes ``<= 1/occupancy`` under concurrency and
the ``make transform-demo`` gates (``<= 1``) hold by construction. The
per-thread evidence seam (``thread_evidence``) lets the chunk manager
flight-record which launch a request shared (``gcm.batch:<id>`` stage +
occupancy counters on ``GET /debug/requests``); per-class counters feed
the ``batch-metrics`` group's class gauges.
"""

from __future__ import annotations

import dataclasses
import hmac
import threading
import time
from typing import Callable, Optional

import numpy as np

from tieredstorage_tpu.security.aes import IV_SIZE, TAG_SIZE
from tieredstorage_tpu.transform.scheduler import (
    BACKGROUND,
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
    is_speculative,
    validate_work_class,
)
from tieredstorage_tpu.utils import faults, flightrecorder
from tieredstorage_tpu.utils.locks import new_condition, note_mutation
from tieredstorage_tpu.utils.retry import RetryPolicy, call_with_retry


class BatcherStoppedError(RuntimeError):
    """A window was submitted to (or stranded in) a stopped batcher."""


def bucket_rows(n: int) -> int:
    """Round a merged row count up to a power of two (min 8).

    The merged launch's jit shape is ``(rows, bucket_bytes + 16)``; the
    byte axis is already quantized by ``bucket_max_bytes``, and without a
    row ladder every distinct occupancy would compile a fresh program.
    Powers of two bound the compile set to ~log2(max rows) entries at a
    worst-case 2x padded compute — padding rows are zero-filled one-block
    GCM rows, identical to the mesh padding ``_stage_packed`` adds."""
    if n < 1:
        raise ValueError(f"row count must be >= 1, got {n}")
    return 1 << max(3, (n - 1).bit_length())


#: The keyed program's smallest row bucket: a merged decrypt flush of up to
#: 16 rows is one program, so its AES kernel (whose trace and lowering are
#: ~3-5 s a shape, which the persistent cache does not save) is traced once
#: and not once for 8 rows and again for 16.
KEYED_MIN_ROWS = 16


@dataclasses.dataclass
class _PendingWindow:
    """One caller's window, queued for a shared launch. Mutated by the
    submitting thread before enqueue and by the flusher after dequeue; the
    per-entry Event is the happens-before edge between them."""

    payloads: list
    sizes: list
    ivs: np.ndarray
    tags: Optional[list]  # None on the encrypt direction (nothing to verify)
    n_bytes: int
    enqueued_at: float
    deadline_at: Optional[float]
    work_class: str = LATENCY
    decrypt: bool = True
    event: threading.Event = dataclasses.field(default_factory=threading.Event)
    error: Optional[BaseException] = None
    batch_id: int = 0
    occupancy: int = 0
    added_wait_ms: float = 0.0
    #: Flight-recorder trace id captured at enqueue ON THE REQUEST THREAD
    #: (the flusher has no ambient record) — the timeline ring and the
    #: per-class added-wait exemplars resolve a launch back to the
    #: concrete requests that rode it.
    trace_id: Optional[str] = None
    #: The window's own key and AAD (None: the bucket key's).
    data_key: Optional[bytes] = None
    aad: Optional[bytes] = None
    #: Distinct keys of the launch the window rode.
    keys: int = 0
    #: The launch the window rode and its first row there, until its waiter
    #: leaves (set under ``_cond``, and only for a waiter still waiting).
    launch: Optional["_MergedLaunch"] = None
    first_row: int = 0
    #: The waiter has left: rows collected, failed, or given up waiting.
    left: bool = False


@dataclasses.dataclass
class _MergedLaunch:
    """One merged flush from its launch until the last of its waiters has
    left: the shared output and its row width, the staging buffer the
    program read (back to the ring when the last waiter leaves, if a
    waiter's rows came back), and the waiters still to collect. Mutated
    under ``_cond``."""

    out: object
    n_bytes: int
    packed: Optional[np.ndarray]
    started_at: float
    waiters: int = 0
    #: A waiter has its rows back: the program that read ``packed`` has run.
    ran: bool = False
    #: Counted in ``_uncollected`` and ``_inflight``: a decrypt launch whose
    #: waiters are collecting.
    tracked: bool = False
    #: The timeline's record of the launch less its end (no timeline: None).
    timeline_record: Optional[dict] = None


class _EncryptHandle:
    """An in-flight encrypt window: resolve with ``wait()``. Either an
    inline dispatch (the staged tuple of ``_encrypt_dispatch``, finished
    through the ordinary ``_encrypt_finish`` fetch) or a queued entry
    riding a merged flush — callers can hold ``pipeline.depth`` of these
    without blocking, so coalescing never costs the produce pipeline its
    upload ∥ compute ∥ download overlap."""

    __slots__ = ("_batcher", "_staged", "_entry")

    def __init__(self, batcher, staged=None, entry=None) -> None:
        self._batcher = batcher
        self._staged = staged
        self._entry = entry

    def wait(self) -> list:
        """Block until this window's wire chunks (IV || ct || tag) exist: a
        queued entry's rows are collected and assembled on this thread."""
        if self._staged is not None:
            return self._batcher._backend._encrypt_finish(self._staged)
        return self._batcher._await_entry(self._entry)


class WindowBatcher:
    """Coalesces concurrent GCM windows into shared packed launches, one
    work class per launch.

    One daemon flusher thread owns the device queue; submitting threads
    block on their entry's event, then collect their own rows. All shared
    state mutates under the one ``_cond`` (guarded-by checked +
    runtime-witnessed); the flush itself runs OUTSIDE the lock so
    staging/launch never serializes submitters.
    """

    #: Flush when the oldest waiter's remaining deadline minus the observed
    #: launch p95 drops to this floor (ms): the last moment a queued window
    #: can still launch and land inside its budget.
    DEADLINE_FLOOR_MS = 5.0
    #: Launch-duration samples retained for the p95 estimate.
    LAUNCH_SAMPLES = 64
    #: Liveness-backstop slack past a waiter's own deadline: the waiter
    #: outlives its budget by this much so the flusher's fail-fast (not a
    #: spurious wait timeout) is what reports deadline expiry.
    WAIT_GRACE_S = 60.0

    #: Optional flush hook ``(occupancy, added_wait_ms_list, work_class,
    #: batch_id, trace_ids)`` — the batch-metrics group
    #: (metrics/batch_metrics.py) points it at the occupancy/added-wait
    #: histograms; the per-entry trace ids become histogram exemplars.
    on_flush: Optional[Callable] = None
    #: Optional device-scheduler timeline ring (metrics/timeline.py,
    #: ``timeline.enabled``): every merged flush and expiry drop records
    #: its full scheduler context for the Perfetto export.
    timeline = None

    def __init__(
        self,
        backend,
        *,
        wait_ms: float = 2.0,
        max_windows: int = 16,
        max_bytes: int = 64 << 20,
        background_max_age_ms: float = DEFAULT_BACKGROUND_MAX_AGE_MS,
        class_shares: Optional[dict] = None,
        launch_attempts: int = 2,
        launch_backoff_s: float = 0.005,
        time_source: Callable[[], float] = time.monotonic,
    ) -> None:
        if wait_ms < 0:
            raise ValueError(f"wait_ms must be >= 0, got {wait_ms}")
        if max_windows < 2:
            raise ValueError(f"max_windows must be >= 2, got {max_windows}")
        if max_bytes < 1:
            raise ValueError(f"max_bytes must be >= 1, got {max_bytes}")
        if background_max_age_ms < 0:
            raise ValueError(
                f"background_max_age_ms must be >= 0, got {background_max_age_ms}"
            )
        self._backend = backend
        self.wait_ms = float(wait_ms)
        self.max_windows = int(max_windows)
        self.max_bytes = int(max_bytes)
        self.background_max_age_ms = float(background_max_age_ms)
        self.class_shares = dict(DEFAULT_SHARES)
        for cls, share in (class_shares or {}).items():
            validate_work_class(cls)
            if share <= 0:
                raise ValueError(f"share for {cls!r} must be > 0, got {share}")
            self.class_shares[cls] = float(share)
        self._now = time_source
        # Unified failure policy (ISSUE 19): ONE bounded re-dispatch before
        # a merged launch fails its waiters — a transient device/runtime
        # hiccup (preempted stream, transfer glitch) should not fail a whole
        # coalesced window of requests. Classes never share a launch, so the
        # retry cannot leak a failure across classes; each attempt re-stages
        # from the host-side packed buffer (the staged device buffer is
        # donated by the launch and must never be replayed).
        self._launch_policy = RetryPolicy(
            max_attempts=max(1, int(launch_attempts)),
            base_backoff_s=max(0.0, float(launch_backoff_s)),
            max_backoff_s=max(0.0, float(launch_backoff_s)) * 4.0,
            retryable=(Exception,),
        )
        #: The ONE guard of every shared field below; doubles as the
        #: flusher's wakeup condition (the admission-controller idiom, so
        #: the lock-order checker sees wait() release the held lock).
        self._cond = new_condition("batcher.WindowBatcher._cond")
        #: bucket key (work_class, decrypt, data_key, aad, bucket_bytes)
        #: -> queued entries; a decrypt bucket's data_key is None and its
        #: aad the AAD's block count (every key of that shape shares it).
        #: One class + one direction per merged launch, structurally.
        self._buckets: dict[tuple, list[_PendingWindow]] = {}
        self._launch_s: list[float] = []
        #: Flushes not yet finished: the flusher's own, and each merged
        #: decrypt launch until its last waiter has collected.
        self._inflight = 0
        #: Merged decrypt launches whose waiters are still collecting; the
        #: flusher launches no more than ``pipeline_depth`` of them.
        self._uncollected = 0
        self._stopped = False
        self._thread: Optional[threading.Thread] = None
        self._tls = threading.local()
        self._batch_seq = 0
        #: Deficit-fair-share accounting: bytes each class launched.
        self._served_bytes = {cls: 0 for cls in WORK_CLASSES}
        #: Per-class admission (set_class_rate): bytes/s rate, burst cap,
        #: current allowance, and the last refill instant.
        self._class_rate: dict[str, float] = {}
        self._class_burst: dict[str, float] = {}
        self._class_allowance: dict[str, float] = {}
        self._class_refill_at: dict[str, float] = {}
        # Counters (exported by metrics/batch_metrics.py).
        self.windows_submitted = 0
        self.fast_path_windows = 0
        self.batched_windows = 0
        self.launches = 0
        self.expired_windows = 0
        self.launch_failures = 0
        #: Merged launches that needed the bounded re-dispatch.
        self.launch_retries = 0
        #: Per-class counters: windows that rode a merged flush, merged
        #: launches, and the summed added queue wait — the class gauges.
        self.class_flushed_windows = {cls: 0 for cls in WORK_CLASSES}
        self.class_launches = {cls: 0 for cls in WORK_CLASSES}
        self.class_added_wait_ms = {cls: 0.0 for cls in WORK_CLASSES}
        #: Speculative-rows ledger: windows/bytes submitted under a
        #: ``speculative_scope`` (readahead bets). Kept separate from the
        #: class counters so background occupancy from *prediction* is
        #: distinguishable from demanded background work (scrub).
        self.speculative_windows = 0
        self.speculative_bytes = 0
        #: Decrypt launches (fast-path windows and merged flushes) and their
        #: rows; merged decrypt launches and the distinct keys they carried.
        self.decrypt_launches = 0
        self.decrypt_launch_rows = 0
        self.merged_launches = 0
        self.merged_launch_keys = 0
        #: Merged launches enqueued while an earlier merged decrypt launch
        #: still had rows being collected, and rows collected by waiters.
        self.overlapped_launches = 0
        self.waiter_collected_rows = 0

    # --------------------------------------------------------------- lifecycle
    def start(self) -> "WindowBatcher":
        """Spawn the flusher daemon (idempotent)."""
        with self._cond:
            if self._thread is not None:
                return self
            self._stopped = False
            self._thread = threading.Thread(
                target=self._run, name="gcm-window-batcher", daemon=True
            )
        self._thread.start()
        return self

    def stop(self) -> None:
        """Stop the flusher and drain any stranded waiters."""
        with self._cond:
            self._stopped = True
            thread = self._thread
            self._thread = None
            self._cond.notify_all()
        if thread is not None:
            thread.join(timeout=30)
        self.flush_now()

    @property
    def mean_occupancy(self) -> float:
        """Coalesced windows per shared launch (fast-path dispatches are
        occupancy-1 by definition and excluded)."""
        with self._cond:
            return self.batched_windows / self.launches if self.launches else 0.0

    def set_launch_retry(self, attempts: int, backoff_s: float) -> None:
        """Rebuild the launch retry policy (`retry.launch.*`): the RSM wires
        this after the backend's configure() built the batcher, since the
        policy keys live at the RSM level, not the transform.* subtree."""
        backoff = max(0.0, float(backoff_s))
        self._launch_policy = RetryPolicy(
            max_attempts=max(1, int(attempts)),
            base_backoff_s=backoff,
            max_backoff_s=backoff * 4.0,
            retryable=(Exception,),
        )

    def set_class_rate(
        self, work_class: str, rate_bytes: Optional[float],
        burst_bytes: Optional[float] = None,
    ) -> None:
        """Admit ``work_class`` launches at ``rate_bytes``/s (burst cap
        defaults to one second of rate, like ``TokenBucket``); None clears
        the rate (unlimited). The rsm scrub wiring maps ``scrub.rate.bytes``
        here so the scrubber's device budget is a scheduler admission class
        instead of a host-side token bucket."""
        validate_work_class(work_class)
        with self._cond:
            if rate_bytes is None or rate_bytes <= 0:
                self._class_rate.pop(work_class, None)
                self._class_burst.pop(work_class, None)
                self._class_allowance.pop(work_class, None)
                self._class_refill_at.pop(work_class, None)
            else:
                self._class_rate[work_class] = float(rate_bytes)
                self._class_burst[work_class] = float(
                    rate_bytes if burst_bytes is None else burst_bytes
                )
                self._class_allowance[work_class] = self._class_burst[work_class]
                self._class_refill_at[work_class] = self._now()
            note_mutation("batcher.WindowBatcher._class_rate")
            note_mutation("batcher.WindowBatcher._class_burst")
            note_mutation("batcher.WindowBatcher._class_allowance")
            note_mutation("batcher.WindowBatcher._class_refill_at")
            self._cond.notify()

    def counters(self) -> dict:
        """Exact counts (``/varz`` ``batcher``): windows submitted and taken
        by the fast path; decrypt launches and their rows, fast path and
        merged flushes together; merged decrypt launches and the distinct
        keys they carried; merged launches enqueued while an earlier one was
        still being collected, and the rows waiters collected."""
        with self._cond:
            return {
                "windows_submitted": self.windows_submitted,
                "fast_path_windows": self.fast_path_windows,
                "decrypt_launches": self.decrypt_launches,
                "decrypt_launch_rows": self.decrypt_launch_rows,
                "merged_launches": self.merged_launches,
                "merged_launch_keys": self.merged_launch_keys,
                "overlapped_launches": self.overlapped_launches,
                "waiter_collected_rows": self.waiter_collected_rows,
            }

    def class_queued(self) -> dict[str, int]:
        """Currently queued windows per class (the queue-depth gauges)."""
        out = {cls: 0 for cls in WORK_CLASSES}
        with self._cond:
            for key, entries in self._buckets.items():
                out[key[0]] += len(entries)
        return out

    def thread_evidence(self) -> tuple[int, float, int]:
        """This THREAD's cumulative (coalesced windows, occupancy sum, last
        batch id) — the flight-recorder seam
        (``TpuTransformBackend.thread_batch_evidence``). Thread-local by
        construction: only the submitting thread writes its own cell."""
        t = self._tls
        return (
            getattr(t, "windows", 0),
            getattr(t, "occupancy_sum", 0.0),
            getattr(t, "last_batch_id", 0),
        )

    # ------------------------------------------------------------------ submit
    def submit(self, enc, payloads, sizes, ivs, tags) -> list:
        """Decrypt one window, coalescing with concurrent submitters.

        Blocks until the window's rows came back from a (possibly shared)
        launch, collected on this thread; returns the plaintext chunks or
        raises this CALLER's error only (``AuthenticationError`` on its own
        rows, ``DeadlineExceededException`` when its budget expired in
        queue).
        The work class is the thread's ambient ``work_class_scope``
        (default ``latency`` — the fetch path)."""
        work_class = current_work_class() or LATENCY
        with self._cond:
            if self._stopped:
                raise BatcherStoppedError("WindowBatcher is stopped")
            self.windows_submitted += 1
            note_mutation("batcher.WindowBatcher.windows_submitted")
            if is_speculative():
                self.speculative_windows += 1
                note_mutation("batcher.WindowBatcher.speculative_windows")
                self.speculative_bytes += sum(sizes)
                note_mutation("batcher.WindowBatcher.speculative_bytes")
            # Background work never takes the inline fast path: admission
            # and the starvation watchdog govern every background launch.
            fast = (
                work_class != BACKGROUND
                and not self._buckets
                and self._inflight == 0
            )
            if fast:
                self._inflight += 1
                note_mutation("batcher.WindowBatcher._inflight")
                self.fast_path_windows += 1
                note_mutation("batcher.WindowBatcher.fast_path_windows")
                self.decrypt_launches += 1
                note_mutation("batcher.WindowBatcher.decrypt_launches")
                self.decrypt_launch_rows += len(sizes)
                note_mutation("batcher.WindowBatcher.decrypt_launch_rows")
        if fast:
            # Idle batcher: dispatch inline through the ordinary unbatched
            # window path — light load pays zero added latency and keeps
            # the hot-tier retention hook. While this launch runs, new
            # arrivals queue behind `_inflight` and coalesce.
            try:
                return self._backend._decrypt_window(
                    enc, payloads, sizes, ivs, tags
                )
            finally:
                with self._cond:
                    self._inflight -= 1
                    note_mutation("batcher.WindowBatcher._inflight")
                    if self._buckets:
                        self._cond.notify()

        with self._backend.tracer.span(
            "transform.batch_wait", rows=len(sizes), fast=False
        ) as span:
            entry = self._enqueue(
                enc, payloads, sizes, ivs, tags, work_class, decrypt=True
            )
            try:
                return self._await_entry(entry)
            finally:
                if span is not None:
                    span.attributes.update(
                        keys=entry.keys, occupancy=entry.occupancy
                    )

    def submit_encrypt(self, chunks, opts) -> _EncryptHandle:
        """Encrypt one window, coalescing with CONCURRENT produces.

        Asynchronous: returns a handle immediately (resolve with
        ``wait()``), so ``transform_windows`` keeps ``pipeline.depth``
        windows in flight exactly as on the unbatched path. An idle
        batcher dispatches inline (``_inflight`` held only across the
        async dispatch — a single pipelined produce stream never queues);
        concurrent produces collide on the in-flight count and merge into
        one shared varlen launch with byte-identical wire output (GCM is
        deterministic per (key, aad, IV, plaintext) row). The work class
        is the thread's ambient scope (default ``throughput`` — the
        upload path)."""
        work_class = current_work_class() or THROUGHPUT
        backend = self._backend
        with self._cond:
            if self._stopped:
                raise BatcherStoppedError("WindowBatcher is stopped")
            self.windows_submitted += 1
            note_mutation("batcher.WindowBatcher.windows_submitted")
            if is_speculative():
                self.speculative_windows += 1
                note_mutation("batcher.WindowBatcher.speculative_windows")
                self.speculative_bytes += sum(len(c) for c in chunks)
                note_mutation("batcher.WindowBatcher.speculative_bytes")
            fast = (
                work_class != BACKGROUND
                and not self._buckets
                and self._inflight == 0
            )
            if fast:
                self._inflight += 1
                note_mutation("batcher.WindowBatcher._inflight")
                self.fast_path_windows += 1
                note_mutation("batcher.WindowBatcher.fast_path_windows")
        if fast:
            try:
                staged = backend._encrypt_dispatch(chunks, opts)
            finally:
                with self._cond:
                    self._inflight -= 1
                    note_mutation("batcher.WindowBatcher._inflight")
                    if self._buckets:
                        self._cond.notify()
            return _EncryptHandle(self, staged=staged)

        sizes = [len(c) for c in chunks]
        ivs = backend._make_ivs(len(chunks), opts)
        enc = opts.encryption
        entry = self._enqueue(
            enc, chunks, sizes, ivs, None, work_class, decrypt=False
        )
        return _EncryptHandle(self, entry=entry)

    def _enqueue(
        self, enc, payloads, sizes, ivs, tags, work_class: str, *, decrypt: bool
    ) -> _PendingWindow:
        """Queue one window under its class+direction bucket and wake the
        flusher; the flusher owns the entry from here."""
        from tieredstorage_tpu.ops import gcm as gcm_ops
        from tieredstorage_tpu.utils import deadline as deadline_util

        now = self._now()
        remaining = deadline_util.remaining_s()
        entry = _PendingWindow(
            payloads=list(payloads),
            sizes=list(sizes),
            ivs=ivs,
            tags=None if tags is None else list(tags),
            n_bytes=sum(sizes),
            enqueued_at=now,
            deadline_at=None if remaining is None else now + remaining,
            work_class=work_class,
            decrypt=decrypt,
            trace_id=flightrecorder.current_trace_id(),
        )
        entry.data_key, entry.aad = bytes(enc.data_key), bytes(enc.aad)
        # A decrypt bucket holds every key of its shape (the flush's key
        # table); the AAD's block count is part of the program's shape.
        key = (
            work_class,
            decrypt,
            None if decrypt else entry.data_key,
            -(-len(entry.aad) // 16) if decrypt else entry.aad,
            gcm_ops.bucket_max_bytes(max(sizes)),
        )
        with self._cond:
            if self._stopped:
                raise BatcherStoppedError("WindowBatcher is stopped")
            self._buckets.setdefault(key, []).append(entry)
            self._cond.notify()
        return entry

    def _await_entry(self, entry: _PendingWindow) -> list:
        """Wait out a queued entry's flush, then collect its rows on this
        thread (`_collect`); raises this caller's error only. The timeout
        is a liveness backstop (deadline expiry is enforced by the
        flusher's fail-fast) — clamped to the caller's remaining budget
        plus slack when one exists."""
        try:
            if not entry.event.wait(timeout=self._wait_timeout_s(entry)):
                raise BatcherStoppedError(
                    "batched window was never flushed (flusher dead?)"
                )
            if entry.batch_id:
                t = self._tls
                t.windows = getattr(t, "windows", 0) + 1
                t.occupancy_sum = getattr(t, "occupancy_sum", 0.0) + entry.occupancy
                t.last_batch_id = entry.batch_id
            if entry.error is not None:
                raise entry.error
            return self._collect(entry)
        finally:
            self._leave(entry)

    def _collect(self, entry: _PendingWindow) -> list:
        """This waiter's rows of its merged launch, fetched under
        `transform.d2h_wait`: a decrypt window's one or two rows each taken
        alone on the device (`gcm.take_rows`, one program per merged shape,
        the row an argument), an encrypt window's, which fill most of their
        launch, read from the whole output. Then the tags checked and the
        plaintext copied out (decrypt, whose verified rows are offered to
        the hot tier), or the wire chunks IV || ct || tag built (encrypt)."""
        from tieredstorage_tpu.ops import gcm as gcm_ops
        from tieredstorage_tpu.transform.api import AuthenticationError

        launch, backend, sizes = entry.launch, self._backend, entry.sizes
        n_bytes, first = launch.n_bytes, entry.first_row
        with backend.tracer.span("transform.d2h_wait") as wait:
            if entry.decrypt:
                rows = [
                    np.asarray(gcm_ops.take_rows(launch.out, first + i, 1))[0]
                    for i in range(len(sizes))
                ]
            else:
                rows = np.asarray(launch.out)[first : first + len(sizes)]
        if wait is not None:
            backend._split_wait(wait, launch.out, shared=True)
        with self._cond:
            self.waiter_collected_rows += len(rows)
            note_mutation("batcher.WindowBatcher.waiter_collected_rows")
            launch.ran = True
        backend._note_batched_fetch()
        if not entry.decrypt:
            return [
                b"".join((
                    entry.ivs[i].tobytes(),
                    memoryview(row[: sizes[i]]),
                    memoryview(row[n_bytes:]),
                ))
                for i, row in enumerate(rows)
            ]
        bad = [
            i
            for i, row in enumerate(rows)
            if not hmac.compare_digest(row[n_bytes:].tobytes(), entry.tags[i])
        ]
        if bad:
            # Per-row error isolation: one forged row fails ITS request;
            # batch-mates still get their plaintext.
            raise AuthenticationError(f"GCM tag mismatch on chunks {bad}")
        backend._offer_rows(launch.out, entry.first_row, n_bytes, sizes)
        return [row[: sizes[i]].tobytes() for i, row in enumerate(rows)]

    def _leave(self, entry: _PendingWindow) -> None:
        """The waiter is done with its launch, whatever happened; the last
        to leave finishes the launch."""
        with self._cond:
            entry.left = True
            launch, entry.launch = entry.launch, None
            if launch is None:
                return
            launch.waiters -= 1
            if launch.waiters:
                return
        self._finish_launch(launch)

    def _finish_launch(self, launch: _MergedLaunch) -> None:
        """The last waiter of a merged launch has left: its launch time (to
        rows back) is sampled, it stops counting against the cap, and the
        timeline records it. Its staging buffer goes back to the ring if a
        waiter's rows came back: the program that read it has run, and no
        waiter reads the output, which on a zero-copy placement (the CPU
        backend's, for an aligned array) is that buffer. One no waiter's
        rows came back from is dropped: the program may still be reading
        it."""
        end_s = self._now()
        with self._cond:
            self._launch_s.append(end_s - launch.started_at)
            if len(self._launch_s) > self.LAUNCH_SAMPLES:
                del self._launch_s[0]
            if launch.tracked:
                self._uncollected -= 1
                note_mutation("batcher.WindowBatcher._uncollected")
                self._inflight -= 1
                note_mutation("batcher.WindowBatcher._inflight")
                self._cond.notify_all()
            packed = launch.packed if launch.ran else None
            launch.packed = launch.out = None
        if packed is not None:
            self._backend._release_staging(packed)
        tl, record = self.timeline, launch.timeline_record
        if tl is not None and record is not None:
            tl.record_flush(end_s=end_s, **record)

    def _wait_timeout_s(self, entry: _PendingWindow) -> Optional[float]:
        """A queued waiter's liveness backstop: its remaining deadline
        budget plus ``WAIT_GRACE_S`` of slack (None = wait indefinitely for
        an unconstrained caller — the flusher's wait_ms bound is the
        pacing, not this)."""
        if entry.deadline_at is None:
            return None
        return max(0.0, entry.deadline_at - self._now()) + self.WAIT_GRACE_S

    # ----------------------------------------------------------- flush policy
    def _launch_p95_s(self) -> float:
        """p95 of recent launch wall times (0 before the first sample) —
        callers must hold ``_cond``."""
        if not self._launch_s:
            return 0.0
        ordered = sorted(self._launch_s)
        # Nearest-rank on the closed index range [0, n-1]: in range by
        # construction, no clamp needed.
        return ordered[int(0.95 * (len(ordered) - 1))]

    def _admission_ready_at_locked(
        self, work_class: str, need_bytes: int, now: float
    ) -> float:
        """When the class admission budget covers ``need_bytes`` (clamped
        at the burst/flush caps, so oversized backlogs admit in paced
        slices) — callers hold ``_cond``. Refills the allowance to
        ``now`` as a side effect."""
        rate = self._class_rate.get(work_class)
        if rate is None:
            return now
        burst = self._class_burst[work_class]
        elapsed = max(0.0, now - self._class_refill_at[work_class])
        self._class_allowance[work_class] = admission_refill(
            self._class_allowance[work_class], rate, burst, elapsed
        )
        self._class_refill_at[work_class] = now
        note_mutation("batcher.WindowBatcher._class_allowance")
        note_mutation("batcher.WindowBatcher._class_refill_at")
        need = min(need_bytes, burst, self.max_bytes)
        return now + admission_defer_s(
            self._class_allowance[work_class], need, rate
        )

    def _due_keys_locked(self, now: float) -> tuple[list, Optional[float]]:
        """(bucket keys due to flush now — scheduler order, seconds until
        the next one is).

        A bucket is due when: queued windows >= ``max_windows``; queued
        bytes >= ``max_bytes``; the oldest waiter aged past its CLASS
        bound (``wait_ms``, or the background starvation watchdog); or
        the tightest waiter's remaining deadline minus the launch p95
        estimate is at the ``DEADLINE_FLOOR_MS`` floor. A class with an
        admission rate is additionally deferred until its byte budget
        covers the flush. Due keys come back sorted by flush priority:
        latency strictly first, then weighted deficit."""
        due: list = []
        next_wake: Optional[float] = None
        p95 = self._launch_p95_s()
        floor_s = self.DEADLINE_FLOOR_MS / 1000.0
        for key, entries in self._buckets.items():
            work_class = key[0]
            queued_bytes = sum(e.n_bytes for e in entries)
            if len(entries) >= self.max_windows or queued_bytes >= self.max_bytes:
                wake = now
            else:
                age_s = class_max_age_ms(
                    work_class, self.wait_ms, self.background_max_age_ms
                ) / 1000.0
                wake = entries[0].enqueued_at + age_s
                deadlines = [
                    e.deadline_at for e in entries if e.deadline_at is not None
                ]
                if deadlines:
                    wake = min(wake, min(deadlines) - p95 - floor_s)
            wake = max(
                wake, self._admission_ready_at_locked(work_class, queued_bytes, now)
            )
            if wake <= now:
                due.append(key)
            elif next_wake is None or wake < next_wake:
                next_wake = wake
        due.sort(key=lambda k: flush_priority(
            k[0],
            self._served_bytes[k[0]],
            self.class_shares[k[0]],
            self._buckets[k][0].enqueued_at,
        ))
        timeout = None if next_wake is None else max(0.0, next_wake - now)
        return due, timeout

    def _take_locked(self, key: tuple) -> list:
        """Pop a bucket's oldest entries up to the windows/bytes caps
        (callers hold ``_cond``). A storm larger than one flush leaves the
        remainder queued — still due, so the flusher drains it in capped
        launches whose shapes stay on the warmed row ladder instead of
        compiling one giant program. Taken bytes land in the class's
        deficit account and draw down its admission allowance."""
        entries = self._buckets.get(key)
        take: list = []
        total = 0
        while entries and len(take) < self.max_windows and total < self.max_bytes:
            e = entries.pop(0)
            take.append(e)
            total += e.n_bytes
        if not entries:
            self._buckets.pop(key, None)
        if take:
            work_class = key[0]
            self._served_bytes[work_class] += total
            note_mutation("batcher.WindowBatcher._served_bytes")
            if work_class in self._class_rate:
                # Allowance may go negative (a watchdog-forced flush larger
                # than the remaining budget): the debt defers the NEXT
                # background flush, standard token-bucket pacing.
                self._class_allowance[work_class] -= total
                note_mutation("batcher.WindowBatcher._class_allowance")
        return take

    def _run(self) -> None:
        """Flusher daemon: wait for a due bucket, take a capped batch,
        flush outside the lock — the one device queue every stream
        shares. Groups flush in scheduler order (latency first)."""
        while True:
            expired: list = []
            with self._cond:
                if self._stopped:
                    return
                now = self._now()
                due, timeout = self._due_keys_locked(now)
                if due and self._collects_full_locked():
                    # Due windows stay queued, and merge with later ones,
                    # until a merged launch's waiters have collected; one
                    # whose deadline passes meanwhile fails fast.
                    expired, timeout = self._take_expired_locked(now)
                    due = []
                if not due and not expired:
                    self._cond.wait(timeout)
                    continue
                if due:
                    groups = [(key, self._take_locked(key)) for key in due]
                    self._inflight += 1
                    note_mutation("batcher.WindowBatcher._inflight")
            if expired:
                for work_class, entries in expired:
                    self._fail_expired(work_class, entries, now)
                continue
            try:
                for key, entries in groups:
                    self._flush_group(key, entries)
            finally:
                with self._cond:
                    self._inflight -= 1
                    note_mutation("batcher.WindowBatcher._inflight")

    def _take_expired_locked(self, now: float) -> tuple[list, Optional[float]]:
        """(queued windows whose deadline has passed, taken out of their
        buckets as ``(work_class, entries)`` pairs; seconds until the next
        queued deadline) — callers hold ``_cond``."""
        expired: list = []
        next_s: Optional[float] = None
        for key in list(self._buckets):
            kept, gone = [], []
            for e in self._buckets[key]:
                if e.deadline_at is None:
                    kept.append(e)
                elif e.deadline_at <= now:
                    gone.append(e)
                else:
                    kept.append(e)
                    wait_s = e.deadline_at - now
                    next_s = wait_s if next_s is None else min(next_s, wait_s)
            if gone:
                expired.append((key[0], gone))
                if kept:
                    self._buckets[key] = kept
                else:
                    del self._buckets[key]
        return expired, next_s

    def _fail_expired(self, work_class: str, entries: list, now: float) -> list:
        """The entries whose deadline has not passed at ``now``. Each other
        one fails fast WITHOUT poisoning the batch: it never joins a pack,
        and its batch-mates launch on time."""
        from tieredstorage_tpu.utils.deadline import DeadlineExceededException

        live: list[_PendingWindow] = []
        expired = 0
        for e in entries:
            if e.deadline_at is not None and e.deadline_at <= now:
                e.error = DeadlineExceededException(
                    "deadline expired while queued for a batched GCM launch"
                )
                e.event.set()
                expired += 1
            else:
                live.append(e)
        if expired:
            with self._cond:
                self.expired_windows += expired
                note_mutation("batcher.WindowBatcher.expired_windows")
            tl = self.timeline
            if tl is not None:
                tl.record_expired(work_class, expired, now)
        return live

    def _collects_full_locked(self) -> bool:
        """Whether ``pipeline_depth`` merged decrypt launches are still
        being collected: no more may launch until one is (callers hold
        ``_cond``)."""
        return self._uncollected >= max(1, self._backend.pipeline_depth)

    def flush_now(self) -> int:
        """Flush every queued window synchronously on the calling thread
        (tests and ``stop`` drain), in capped batches and scheduler order,
        ignoring admission (a drain must terminate); returns the number
        of flushes."""
        flushes = 0
        while True:
            with self._cond:
                keys = sorted(self._buckets.keys(), key=lambda k: flush_priority(
                    k[0],
                    self._served_bytes[k[0]],
                    self.class_shares[k[0]],
                    self._buckets[k][0].enqueued_at,
                ))
                groups = [(key, self._take_locked(key)) for key in keys]
            if not groups:
                return flushes
            for key, entries in groups:
                if entries:
                    self._flush_group(key, entries)
                    flushes += 1

    # ------------------------------------------------------------------ flush
    def _on_launch_retry(
        self, attempt: int, delay_s: float, exc: BaseException
    ) -> None:
        with self._cond:
            self.launch_retries += 1
            note_mutation("batcher.WindowBatcher.launch_retries")

    def _launch_once(
        self, ctx, packed, decrypt: bool, work_class: str, row_keys=None
    ):
        """One stage + launch attempt of a merged flush, replay-safe: each
        attempt re-stages from the host-side ``packed`` buffer because the
        staged device buffer is donated by the launch. ``device.launch`` is
        the fault-injection seam (keyed by work class). With ``row_keys``
        ``ctx`` is the launch's key table. Returns the device output
        buffer. An encrypt launch starts its whole-output copy back, which
        its handles read; a decrypt launch does not, since each waiter takes
        only its own rows (the copy of every launch's whole output cost the
        ten-reader fan-in 39 % of its throughput on the v5e)."""
        faults.fire("device.launch", work_class)
        staged = self._backend._stage_packed(packed, True)
        return self._backend._launch_packed(
            ctx, staged, True, decrypt=decrypt, row_keys=row_keys,
            copy_back=not decrypt,
        )

    def _flush_group(self, key: tuple, entries: list) -> None:
        """ONE shared launch for a bucket's queued windows: merge rows into
        a single packed buffer, stage + launch through the owning backend
        (donation and DispatchStats intact), then wake each waiter with
        the launch and its first row there: the waiter collects its own
        rows (`_collect`), and the flusher goes on to the next bucket. At
        most ``pipeline_depth`` merged decrypt launches wait to be
        collected; beyond that a decrypt flush waits before it packs, and
        a window whose deadline passed meanwhile fails fast then. The
        bucket key carries ONE work class and ONE direction, so a failure
        here wakes that class's waiters only."""
        from tieredstorage_tpu.ops import gcm as gcm_ops

        work_class, decrypt = key[0], key[1]
        if decrypt:
            with self._cond:
                self._cond.wait_for(lambda: not self._collects_full_locked())
        live = self._fail_expired(work_class, entries, self._now())
        if not live:
            return

        backend = self._backend
        table = list(dict.fromkeys(self._key_of(e, key) for e in live))
        keyed = decrypt and backend.keyed_launches()
        if len(table) > 1 and not keyed:
            # A mesh shards the rows, and the keyed program has no sharded
            # form: one launch per key.
            for key_aad in table:
                self._flush_group(
                    key, [e for e in live if self._key_of(e, key) == key_aad]
                )
            return
        with backend.tracer.span(
            "transform.batch_flush", windows=len(live), keys=len(table)
        ) as flush_span:
            try:
                # Decrypt rows run the keyed program under the flush's key
                # table (one key or several); encrypt rows, of one key, the
                # single-key varlen program.
                make = gcm_ops.make_keyed_context if keyed else gcm_ops.make_varlen_context
                ctxs = [make(k, a, key[4]) for k, a in table]
                slot = {key_aad: i for i, key_aad in enumerate(table)}
                n_bytes = ctxs[0].max_bytes
                rows = sum(len(e.sizes) for e in live)
                # A buffer of the backend's staging ring, already mapped (a
                # fresh 64 MiB is ~60 ms of page faults on a gVisor host):
                # every byte the program reads is written here.
                n_rows = bucket_rows(rows)
                packed = backend._acquire_staging(
                    (max(n_rows, KEYED_MIN_ROWS) if keyed else n_rows, n_bytes + TAG_SIZE)
                )
                # Padding rows run under slot 0.
                row_keys = np.zeros(len(packed), np.int32)
                r = 0
                for e in live:
                    row_keys[r : r + len(e.sizes)] = slot[self._key_of(e, key)]
                    for i, p in enumerate(e.payloads):
                        packed[r, : e.sizes[i]] = np.frombuffer(p, np.uint8)
                        packed[r, e.sizes[i] : n_bytes] = 0
                        packed[r, n_bytes : n_bytes + IV_SIZE] = e.ivs[i]
                        r += 1
                    packed[r - len(e.sizes) : r, n_bytes + IV_SIZE :] = (
                        np.asarray(e.sizes, dtype="<u4").view(np.uint8).reshape(-1, 4)
                    )
                # Row-ladder padding mirrors _stage_packed's mesh padding: one
                # 16-byte block per dummy row (zero-length rows are excluded
                # by the varlen contract).
                packed[rows:] = 0
                packed[rows:, n_bytes + IV_SIZE] = 16
                if flush_span is not None:
                    flush_span.attributes.update(rows=rows, bucket_rows=len(packed))
                ctx = ctxs if keyed else ctxs[0]
                slots = row_keys if keyed else None
                with self._cond:
                    overlapped = self._uncollected > 0
                t0 = self._now()
                out = call_with_retry(
                    lambda: self._launch_once(ctx, packed, decrypt, work_class, slots),
                    policy=self._launch_policy,
                    site="device.launch",
                    on_retry=self._on_launch_retry,
                )
            except BaseException as exc:  # noqa: BLE001 - every waiter must wake
                with self._cond:
                    self.launch_failures += 1
                    note_mutation("batcher.WindowBatcher.launch_failures")
                # Classes never share a merged launch, so this failure is
                # delivered to THIS class's waiters alone.
                for e in live:
                    e.error = exc
                    e.event.set()
                return
            for e in live:
                backend._note_window(e.n_bytes, len(e.sizes), n_bytes, True)

            occupancy = len(live)
            launch = _MergedLaunch(out, n_bytes, packed, t0)
            with self._cond:
                self._batch_seq += 1
                note_mutation("batcher.WindowBatcher._batch_seq")
                batch_id = self._batch_seq
                self.launches += 1
                note_mutation("batcher.WindowBatcher.launches")
                self.batched_windows += occupancy
                note_mutation("batcher.WindowBatcher.batched_windows")
                self.class_launches[work_class] += 1
                note_mutation("batcher.WindowBatcher.class_launches")
                self.class_flushed_windows[work_class] += occupancy
                note_mutation("batcher.WindowBatcher.class_flushed_windows")
                self.overlapped_launches += overlapped
                note_mutation("batcher.WindowBatcher.overlapped_launches")
                if decrypt:
                    self.decrypt_launches += 1
                    note_mutation("batcher.WindowBatcher.decrypt_launches")
                    self.decrypt_launch_rows += rows
                    note_mutation("batcher.WindowBatcher.decrypt_launch_rows")
                    self.merged_launches += 1
                    note_mutation("batcher.WindowBatcher.merged_launches")
                    self.merged_launch_keys += len(table)
                    note_mutation("batcher.WindowBatcher.merged_launch_keys")
                r = 0
                for e in live:
                    e.first_row = r
                    r += len(e.sizes)
                    e.batch_id = batch_id
                    e.occupancy = occupancy
                    e.keys = len(table)
                    e.added_wait_ms = max(0.0, (t0 - e.enqueued_at) * 1000.0)
                    if not e.left:  # a waiter that gave up collects nothing
                        e.launch = launch
                        launch.waiters += 1
                unwaited = not launch.waiters
                launch.tracked = decrypt and not unwaited
                if launch.tracked:
                    self._uncollected += 1
                    note_mutation("batcher.WindowBatcher._uncollected")
                    self._inflight += 1
                    note_mutation("batcher.WindowBatcher._inflight")
            tl = self.timeline
            if tl is not None:
                # Outside _cond by design: the timeline ring has its own lock
                # and class_queued() re-takes _cond for the depth snapshot.
                # The last waiter to leave records it, with the launch's end.
                launch.timeline_record = dict(
                    batch_id=batch_id,
                    work_class=work_class,
                    decrypt=decrypt,
                    bucket_bytes=key[4],
                    rows=rows,
                    n_bytes=sum(e.n_bytes for e in live),
                    occupancy=occupancy,
                    queued_age_ms=max(
                        0.0, (t0 - min(e.enqueued_at for e in live)) * 1000.0
                    ),
                    begin_s=t0,
                    queue_depths=self.class_queued(),
                    trace_ids=[e.trace_id for e in live],
                )
            added_waits = [e.added_wait_ms for e in live]
            with self._cond:
                self.class_added_wait_ms[work_class] += sum(added_waits)
                note_mutation("batcher.WindowBatcher.class_added_wait_ms")
            if unwaited:
                self._finish_launch(launch)
            for e in live:
                e.event.set()
            hook = self.on_flush
            if hook is not None:
                hook(
                    occupancy, added_waits, work_class,
                    batch_id, [e.trace_id for e in live],
                )

    @staticmethod
    def _key_of(entry: _PendingWindow, key: tuple) -> tuple:
        """(data_key, aad) of a queued window."""
        if entry.data_key is None:
            return key[2], key[3]
        return entry.data_key, entry.aad
