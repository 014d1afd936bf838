"""TPU transform backend: batched device AES-GCM (+host zstd until the
TPU-native codec lands), pluggable at `transform.backend.class`.

The point of the framework (BASELINE north star): whole windows of chunks are
shipped to the device as ONE packed uint8[batch, n_bytes + 16] buffer
(per-row IV/length metadata riding the tail columns) and encrypted/decrypted
by a SINGLE fused GCM dispatch per window — keystream, XOR, GHASH and tag
fold in one device program whose one output buffer packs `output || tag`
per row (ops/gcm.py packed window ops; the AES circuit and the GHASH tree
run as Pallas kernels on a TPU backend). One window therefore costs one
host→device transfer, one launch, one device→host fetch — whatever a
launch's fixed cost is, it is paid once per 64 MiB window — with the chunk
batch optionally sharded across a device mesh
(parallel/mesh.py). Wire format is identical to the CPU backend and the
reference: per-chunk zstd frame (content size pledged), then
IV || ciphertext || tag.
"""

from __future__ import annotations

import collections
import dataclasses
import functools
import hmac
import os
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from typing import Optional, Sequence

import numpy as np

try:  # Optional dependency: only the zstd codec path needs it (device
    # codecs and identity/encrypt-only pipelines run without it).
    import zstandard
except ImportError:  # pragma: no cover - exercised only without zstandard
    zstandard = None

from tieredstorage_tpu import native
from tieredstorage_tpu.ops import gcm as gcm_ops
from tieredstorage_tpu.ops.gcm import (
    gcm_keyed_window_packed,
    gcm_varlen_window_packed,
    gcm_window_packed,
    make_context,
    make_varlen_context,
)
from tieredstorage_tpu.parallel.mesh import MeshPlan
from tieredstorage_tpu.security.aes import IV_SIZE, TAG_SIZE
from tieredstorage_tpu.utils.locks import new_lock, note_mutation
from tieredstorage_tpu.utils.platforms import thread_program_traces
from tieredstorage_tpu.transform.device_watch import DeviceWatch
from tieredstorage_tpu.transform.api import (
    THUFF,
    TLZHUFF,
    ZSTD,
    AuthenticationError,
    DetransformOptions,
    TransformBackend,
    TransformOptions,
)


def _parse_bool(value) -> bool:
    """Config booleans arrive as real bools from dict configs and as
    strings from properties files — accept both spellings."""
    if isinstance(value, str):
        return value.strip().lower() in ("1", "true", "yes")
    return bool(value)


def _spanned(name: str, count=len, n_bytes=None):
    """Trace a backend stage; `count` maps the first positional arg to the
    span's chunks attribute (mirrors rsm._traced — one wrapper, no _inner
    twins a caller could bypass). Byte throughput per stage: `n_bytes` maps
    the first arg to bytes_in (default: summed chunk lengths when the arg is
    a chunk list), and a chunk-list result is summed into bytes_out."""

    def chunk_bytes(value):
        if isinstance(value, (list, tuple)) and value and isinstance(
            value[0], (bytes, bytearray, memoryview)
        ):
            return sum(len(c) for c in value)
        return None

    def deco(fn):
        @functools.wraps(fn)
        def wrapper(self, arg, *args, **kwargs):
            with self.tracer.span(name, chunks=count(arg)) as span:
                out = fn(self, arg, *args, **kwargs)
                if span is not None:
                    bytes_in = (n_bytes or chunk_bytes)(arg)
                    if bytes_in is not None:
                        span.attributes["bytes_in"] = bytes_in
                    bytes_out = chunk_bytes(out)
                    if bytes_out is not None:
                        span.attributes["bytes_out"] = bytes_out
                return out

        return wrapper

    return deco


@dataclasses.dataclass
class DispatchStats:
    """Per-backend device-interaction counters for the window path.

    The steady-state invariant this makes testable WITHOUT a TPU: one
    window costs exactly one host→device staging transfer, ONE fused
    device dispatch (keystream → XOR → GHASH → tag in a single program —
    `ops/gcm.py` packed window ops), and one device→host fetch. Every
    extra launch or fetch pays a size-independent floor, so launch-count
    regressions are throughput regressions; the benchmark reads
    `dispatches_per_window.copy` and `dispatches_per_fetch.fetch` from
    these counters next to its end-to-end numbers.
    Guarded by the owning backend's `_stats_lock` (one backend instance
    serves concurrent upload/fetch windows on the gateway worker pool —
    the guarded-by race checker infers and enforces the guard, and the
    RaceWitness cross-validates it under `make chaos`/`make fleet-demo`);
    launch deltas come from `ops.gcm.thread_dispatches()` so a sibling
    thread's launches never land in this window's count.

    `staging_acquired` and `staging_reused` count the host side of the
    staging transfer: every window `_build_packed` fills takes one host
    buffer of its shape, from the backend's ring of reused buffers when it
    holds one (`staging_reused`), freshly allocated otherwise. A buffer goes
    back to the ring only when its window is over: in `_encrypt_finish` once
    the wire chunks are built, at the end of `_decrypt_window` once the
    plaintext is. A window that is abandoned never returns its buffer. The
    native zstd codec's frame buffer (`_compress_batch`) is the ring's too
    and is counted with them: it goes back as soon as the window's rows
    are packed.

    `codec_bytes_in` and `codec_bytes_copied` count the host codec's side
    of a compress: the source bytes handed to it, and the bytes the host put
    into fresh memory around it: a gathered copy of the input (none: each
    chunk is compressed where it lies) and every frame that ends as an
    owned `bytes` where the native codec's, as views of its frame buffer,
    are read by the pack and copied nowhere else.

    Four counts say what concurrent encrypt streams share. A stream is a
    `transform_windows` call with encryption, from its first dispatch to
    its last finish (a segment's log, each of its indexes).
    `encrypt_streams_max` is the most streams in flight at once;
    `encrypt_stream_launch_sum` adds, at each encrypt launch, the streams
    then in flight; `launch_queue_sum` adds, at each launch, the unbatched
    encrypt windows launched before it and not yet finished: the device
    queue the window joins. `staging_trimmed_bytes` is what the ring let go
    over its bound as windows handed their buffers back (`_release_staging`);
    what it gives back once streams end is not counted."""

    windows: int = 0
    #: Chunk rows of those windows, before any mesh padding: over `windows`
    #: it is the mean window height (a prefetching chunk cache's decrypt
    #: windows read 1-2 rows where an upload's read 16).
    rows: int = 0
    dispatches: int = 0
    h2d_transfers: int = 0
    d2h_fetches: int = 0
    bytes_in: int = 0
    #: Payload-scale inter-stage HBM round trips inside the window program
    #: (ops.gcm.planned_hbm_roundtrips): the keystream handoff is the one
    #: allowed; the XLA GHASH ladder adds one per level >= 2 and one for
    #: the plane materialization — the fused tree kernel (ISSUE 13) brings
    #: the total to exactly 1, CI-gated <= 1 by `make transform-demo`.
    hbm_roundtrips: int = 0
    #: Staged window buffers XLA consumed as the output allocation —
    #: steady-state encrypt must reuse ONE HBM allocation per in-flight
    #: window (donated_buffers == windows), sharded or not.
    donated_buffers: int = 0
    #: Mesh accounting of the LAST staged window: how many chips the one
    #: logical dispatch fanned out across, and the padded per-chip row
    #: count — keeps the one-dispatch invariant testable at any mesh size.
    mesh_size: int = 1
    rows_per_device: int = 0
    #: Host staging buffers handed to `_build_packed`, and those of them
    #: that came from the ring, mapped and touched by an earlier window.
    staging_acquired: int = 0
    staging_reused: int = 0
    #: Windows launched in the varlen form (rows of differing sizes, and
    #: every window of a compressed segment, whatever its row count), and
    #: the bytes the windows' rows were staged at: rows x the window's row
    #: width, which for a varlen window is its rung of
    #: `ops.gcm.bucket_max_bytes`. Over `bytes_in` it is what the rungs
    #: cost (before any mesh or kernel row padding).
    varlen_windows: int = 0
    padded_bytes: int = 0
    #: Source bytes handed to the compress codec, and the bytes the host
    #: copied into fresh memory around it (0 on the native zstd path whose
    #: frames the window's pack reads).
    codec_bytes_in: int = 0
    codec_bytes_copied: int = 0
    #: Nanoseconds of the `device.window` spans the device watch recorded
    #: (transform/device_watch.py): the host's upper bound of the device's
    #: time on the windows launched under an enabled tracer. 0 for ever with
    #: tracing off.
    device_seen_ns: int = 0
    encrypt_streams_max: int = 0
    encrypt_stream_launch_sum: int = 0
    launch_queue_sum: int = 0
    staging_trimmed_bytes: int = 0

    @property
    def dispatches_per_window(self) -> float:
        return round(self.dispatches / self.windows, 3) if self.windows else 0.0

    @property
    def hbm_roundtrips_per_window(self) -> float:
        return round(self.hbm_roundtrips / self.windows, 3) if self.windows else 0.0

    @property
    def bytes_per_dispatch(self) -> int:
        return int(self.bytes_in / self.dispatches) if self.dispatches else 0

    def as_dict(self) -> dict:
        out = dataclasses.asdict(self)
        out["dispatches_per_window"] = self.dispatches_per_window
        out["hbm_roundtrips_per_window"] = self.hbm_roundtrips_per_window
        out["bytes_per_dispatch"] = self.bytes_per_dispatch
        return out


class TpuTransformBackend(TransformBackend):
    #: Optional decrypt-retention hook (`fetch/cache/device_hot.py`'s
    #: ``offer_decrypt_window``): called with ``(out, sizes, n_bytes,
    #: mesh_size)`` after each VERIFIED decrypt window, while the packed
    #: ``output || tags`` buffer is still device-resident (row-sharded
    #: under a mesh), so the hot tier can retain it without a second
    #: decrypt or a host→device restage. The buffer is a fresh output
    #: allocation — decrypt donates the STAGED ciphertext input, never
    #: this — so retention can never alias a donated operand.
    on_decrypt_window = None

    #: The device watch (transform/device_watch.py): None until the first
    #: window launched under an enabled tracer, and again after `close()`.
    device_watch: Optional[DeviceWatch] = None

    preferred_batch_chunks = 256
    # Window byte cap. With pipeline_depth=3 up to 4 windows are in flight
    # (compress k ∥ encrypt k-1..k-2 ∥ download k-3). What a 64 MiB window
    # costs in HBM, as reported on the v5e (PR 21, PERF.md), not estimated:
    # the compiler's memory_analysis() gives the 16-row fixed program
    # 1536.6 MiB of temporaries, ~24x the window (705.8 MiB for the varlen
    # form; 128.7 MiB per device for 4 rows under a 4-chip mesh — not linear
    # in rows). The runtime reserves them once, for whichever program is
    # executing (memory_stats peak_bytes_reserved 1536.3 MiB), beside the
    # staged and output buffers of the windows in flight
    # (peak_bytes_in_use 290 MiB after a 1 GiB uncompressed copy): about
    # 1.8 GiB of the chip's 15.75 GiB, not 4 x 1.5 GiB.
    preferred_batch_bytes = 64 << 20

    def __init__(self, mesh=None):
        # `mesh` accepts a prebuilt jax Mesh or MeshPlan (tests/bench);
        # direct construction without one stays single-device. The config
        # path (`configure`) instead records a `transform.mesh.devices`
        # spec — DEFAULT "all local chips" — resolved lazily at the first
        # staged window so configuring an RSM does not initialize the jax
        # backend (the transform path does, the moment a window is staged).
        self._plan: Optional[MeshPlan] = (
            MeshPlan.wrap(mesh) if mesh is not None else MeshPlan(None)
        )
        self._mesh_spec = None
        self._pool: Optional[ThreadPoolExecutor] = None
        self._stats_lock = new_lock("tpu.TpuTransformBackend._stats_lock")
        self.dispatch_stats = DispatchStats()
        #: The ring of host staging buffers: free `uint8[rows, n_bytes + 16]`
        #: arrays by shape, the shape returned longest ago first. Guarded by
        #: `_stats_lock`, bounded by `_staging_bound()` bytes.
        self._staging_free: "collections.OrderedDict[tuple, list]" = (
            collections.OrderedDict()
        )
        self._staging_free_bytes = 0
        #: Encrypt streams in flight (`transform_windows` with encryption),
        #: the most of them in flight at once since the ring last gave its
        #: buffers back, and the unbatched encrypt windows launched and not
        #: yet finished; when the ring gives the buffers of concurrent
        #: streams back (a `time.monotonic()`, or None), and the thread that
        #: does it (`_end_stream`); guarded by `_stats_lock`.
        self._encrypt_streams = 0
        self._encrypt_streams_peak = 0
        self._encrypt_windows_out = 0
        self._give_back_at: Optional[float] = None
        self._give_back_thread: Optional[threading.Thread] = None
        self._give_back_wake = threading.Event()
        self._closed = False
        #: Cross-request decrypt batcher (transform/batcher.py), built by
        #: `configure()` from `transform.batch.enabled` or explicitly via
        #: `enable_batching()`; None = every window dispatches unbatched.
        self.batcher = None

    def reset_dispatch_stats(self) -> DispatchStats:
        """Swap in fresh counters; returns the retired snapshot."""
        with self._stats_lock:
            retired = self.dispatch_stats
            self.dispatch_stats = DispatchStats()
        return retired

    def dispatch_counts(self) -> dict:
        """`DispatchStats.as_dict()` as it stands, for `/varz`'s `dispatch`."""
        with self._stats_lock:
            return self.dispatch_stats.as_dict()

    @staticmethod
    def thread_dispatch_counters() -> tuple[int, int]:
        """This THREAD's cumulative (GCM dispatches, planned HBM round
        trips) — the flight recorder's per-request window accounting seam
        (fetch/chunk_manager.py differences it around one detransform).
        Thread-local by construction (`ops.gcm` keeps per-thread counters),
        so a sibling window's launches never inflate another request's
        record. Duck-typed: CPU backends simply lack the method."""
        return gcm_ops.thread_dispatches(), gcm_ops.thread_hbm_roundtrips()

    def configure(self, configs: dict) -> None:
        if "batch.chunks" in configs:
            self.preferred_batch_chunks = int(configs["batch.chunks"])
        if "batch.bytes" in configs:
            self.preferred_batch_bytes = int(configs["batch.bytes"])
        if "pipeline.depth" in configs:
            self.pipeline_depth = max(1, int(configs["pipeline.depth"]))
        # Configured backends default to the full local mesh: per-broker
        # transform throughput scales ~linearly with local chip count, and
        # on single-chip hosts "all" IS the unsharded path (MeshPlan
        # normalizes a 1-device mesh to the fallback plan).
        self._mesh_spec = configs.get("mesh.devices", "all")
        self._plan = None  # resolve lazily at the first staged window
        if _parse_bool(configs.get("batch.enabled", False)):
            self.enable_batching(
                wait_ms=float(configs.get("batch.wait.ms", 2)),
                max_windows=int(configs.get("batch.windows", 16)),
                background_max_age_ms=float(
                    configs.get("batch.background.max.age.ms", 50)
                ),
            )

    def enable_batching(
        self, *, wait_ms: float = 2.0, max_windows: int = 16,
        max_bytes: Optional[int] = None,
        background_max_age_ms: Optional[float] = None,
    ):
        """Build + start the cross-request window batcher / device
        scheduler (idempotent). The flush byte cap defaults to the window
        byte cap (`transform.batch.bytes`): a merged launch never exceeds
        the HBM budget one pipelined window was already sized for."""
        if self.batcher is None:
            from tieredstorage_tpu.transform.batcher import WindowBatcher

            kwargs = {}
            if background_max_age_ms is not None:
                kwargs["background_max_age_ms"] = background_max_age_ms
            self.batcher = WindowBatcher(
                self,
                wait_ms=wait_ms,
                max_windows=max_windows,
                max_bytes=(
                    self.preferred_batch_bytes if max_bytes is None else max_bytes
                ),
                **kwargs,
            ).start()
        return self.batcher

    def thread_batch_evidence(self) -> tuple[int, float, int]:
        """This THREAD's cumulative (coalesced windows, occupancy sum,
        last shared batch id) — the flight recorder's batch-evidence seam
        (fetch/chunk_manager.py differences it around one detransform so
        `GET /debug/requests` shows which requests shared a launch).
        Duck-typed like `thread_dispatch_counters`."""
        batcher = self.batcher
        return (0, 0.0, 0) if batcher is None else batcher.thread_evidence()

    def _note_window(self, n_bytes: int, rows: int, row_bytes: int, varlen: bool) -> None:
        """One window's payload, rows, staged row width and form. The
        batcher notes every window a merged launch coalesced (varlen, its
        rows as wide as the launch's), so `dispatches_per_window` reads
        `launches/windows <= 1/occupancy`."""
        with self._stats_lock:
            self.dispatch_stats.windows += 1
            self.dispatch_stats.rows += rows
            self.dispatch_stats.bytes_in += n_bytes
            self.dispatch_stats.padded_bytes += rows * row_bytes
            self.dispatch_stats.varlen_windows += varlen
            note_mutation("tpu.TpuTransformBackend.dispatch_stats")

    def _note_batched_fetch(self) -> None:
        """One device→host fetch of a window that rode a merged flush: its
        waiter's own rows (transform/batcher.py `_collect`)."""
        with self._stats_lock:
            self.dispatch_stats.d2h_fetches += 1
            note_mutation("tpu.TpuTransformBackend.dispatch_stats")

    def mesh_plan(self) -> MeshPlan:
        """The resolved sharding plan (builds the mesh on first use)."""
        if self._plan is None:
            self._plan = MeshPlan.from_spec(self._mesh_spec)
        return self._plan

    def _zstd_pool(self) -> ThreadPoolExecutor:
        if self._pool is None:
            self._pool = ThreadPoolExecutor(max_workers=min(32, os.cpu_count() or 4))
        return self._pool

    def close(self) -> None:
        if self.batcher is not None:
            self.batcher.stop()
            self.batcher = None
        with self._stats_lock:
            watch, self.device_watch = self.device_watch, None
            self._closed = True
            give_back = self._give_back_thread
        if give_back is not None:
            self._give_back_wake.set()
            give_back.join()
        if watch is not None:
            watch.stop()
        if self._pool is not None:
            self._pool.shutdown(wait=False)
            self._pool = None

    # ------------------------------------------------------------- transform
    def transform(self, chunks: Sequence[bytes], opts: TransformOptions) -> list[bytes]:
        out = list(chunks)
        if not out:
            return []
        frame_buffer = None
        if opts.compression:
            out, frame_buffer = self._compress_batch(out, opts)
        if opts.encryption is not None:
            return self._finish_or_empty(
                self._dispatch_encrypt_window(out, opts, frame_buffer)
            )
        return self._own_frames(out, frame_buffer)

    #: Staged windows kept in flight before blocking on the oldest: at depth
    #: N the host compresses window k while the device encrypts k-1..k-N+1
    #: and k-N's ciphertext streams back — a 3-stage pipeline
    #: (upload ∥ compute ∥ download) whose steady-state cost is meant to be
    #: max(stage times), not their sum (overlap not measured on the chip).
    pipeline_depth = 3

    def transform_windows(self, windows, opts: TransformOptions):
        """Double-buffered pipelined staging (SURVEY §7 step 5): JAX
        dispatch is async, so `_encrypt_dispatch` only ENQUEUES window k's
        work — one `device_put` of the packed host buffer, one fused GCM
        program (donating that buffer as its output allocation), and the
        `copy_to_host_async` of the result — and returns un-materialized.
        With `pipeline_depth` staged windows in flight, window k+1's
        host→device transfer overlaps window k's compute and window
        k−1's device→host materialization; only `_encrypt_finish`
        (pipeline_depth windows later) blocks, on the oldest window's
        single packed buffer. Steady-state cost is max(stage times), not
        their sum, and each window pays the per-launch floor exactly once
        (DispatchStats counts launches/transfers per window to keep the
        invariant testable without a TPU)."""
        if opts.encryption is None:
            # Compression-only is host-bound: nothing to overlap against.
            for window in windows:
                yield self.transform(window, opts)
            return
        pending: "collections.deque" = collections.deque()
        iv_offset = 0
        self._begin_stream()
        try:
            for window in windows:
                chunks = list(window)
                # Deterministic IVs (tests) are a flat per-chunk sequence: slice
                # the window's share so windowed == monolithic byte-for-byte.
                w_opts = opts
                if opts.ivs is not None:
                    w_opts = dataclasses.replace(
                        opts, ivs=opts.ivs[iv_offset : iv_offset + len(chunks)]
                    )
                    iv_offset += len(chunks)
                staged = None
                if chunks:
                    frame_buffer = None
                    if opts.compression:
                        chunks, frame_buffer = self._compress_batch(chunks, w_opts)
                    staged = self._dispatch_encrypt_window(chunks, w_opts, frame_buffer)
                pending.append(staged)
                while len(pending) > max(1, self.pipeline_depth):
                    yield self._finish_or_empty(pending.popleft())
            while pending:
                yield self._finish_or_empty(pending.popleft())
        finally:
            # Windows the caller stopped before never come back: they leave
            # the queue with the stream, and keep their buffers.
            for staged in pending:
                if isinstance(staged, tuple):
                    self._drop_window(staged[4])
            self._end_stream()

    def _begin_stream(self) -> None:
        with self._stats_lock:
            self._encrypt_streams += 1
            self._give_back_at = None
            self._encrypt_streams_peak = max(self._encrypt_streams_peak, self._encrypt_streams)
            stats = self.dispatch_stats
            stats.encrypt_streams_max = max(stats.encrypt_streams_max, self._encrypt_streams)
            note_mutation("tpu.TpuTransformBackend.dispatch_stats")

    #: Seconds the ring keeps the buffers of concurrent streams once the last
    #: of them has ended: copy threads working through a backlog start their
    #: next streams together, each once its segment's body has arrived (a
    #: 256 MiB body beside nine other copies: 0.64-0.68 s p50, 0.86-2.9 s p95
    #: on a v5e host; PERF.md §5).
    staging_linger_s = 5.0

    def _end_stream(self) -> None:
        """One stream fewer. Where the last one ends after streams have run
        concurrently, the ring keeps their bound for `staging_linger_s` and
        then, unless a stream has begun meanwhile, gives back what is free
        over one stream's bound: on the backend's one `staging-give-back`
        thread, started by the first such end. A lone stream leaves nothing
        over one stream's bound, and no thread."""
        with self._stats_lock:
            self._encrypt_streams -= 1
            if self._encrypt_streams:
                return
            if self._encrypt_streams_peak <= 1:
                self._encrypt_streams_peak = 0
                return
            self._give_back_at = time.monotonic() + self.staging_linger_s
            thread = self._give_back_thread
            if thread is None and not self._closed:
                thread = self._give_back_thread = threading.Thread(
                    target=self._give_back_loop, name="staging-give-back", daemon=True,
                )
                thread.start()
        self._give_back_wake.set()

    def _give_back_loop(self) -> None:
        while True:
            with self._stats_lock:
                if self._closed:
                    return
                due = self._give_back_at
                now = time.monotonic()
                if due is not None and now >= due:
                    self._give_back_at = None
                    self._encrypt_streams_peak = 0
                    self._trim_staging(self._staging_bound())
                    note_mutation("tpu.TpuTransformBackend._staging_free")
                    due = None
            self._give_back_wake.wait(None if due is None else due - now)
            self._give_back_wake.clear()

    def _drop_window(self, staging: list) -> None:
        """An encrypt window that will not be finished leaves the device
        queue's count; its buffer is never handed back (the device may
        still read it)."""
        with self._stats_lock:
            if staging:
                staging.clear()
                self._encrypt_windows_out -= 1

    def _dispatch_encrypt_window(
        self, chunks: list, opts: TransformOptions,
        frame_buffer: Optional[np.ndarray] = None,
    ):
        """Dispatch one encrypt window asynchronously. With the batcher
        enabled the window joins the shared work-class-aware device queue
        (`submit_encrypt` — idle batchers dispatch inline, CONCURRENT
        produces coalesce into one merged varlen launch); otherwise, or
        for windows with zero-length chunks (excluded by the merged
        launch's varlen contract), it stages directly. Either way the
        return is un-materialized: `_finish_or_empty` blocks pipeline_depth
        windows later.

        `frame_buffer` is the ring buffer that `chunks` are views of, where
        the native codec made them (`_compress_batch`). A frame view is dead
        once `_build_packed` has copied its row into the staged window, so
        the buffer goes back to the ring when `_encrypt_dispatch` returns;
        the batcher may queue its chunks, so it is given owned `bytes`. No
        view leaves the backend."""
        batcher = self.batcher
        if batcher is not None and min(len(c) for c in chunks) > 0:
            return batcher.submit_encrypt(self._own_frames(chunks, frame_buffer), opts)
        try:
            return self._encrypt_dispatch(chunks, opts)
        finally:
            if frame_buffer is not None:
                self._release_staging(frame_buffer)

    def _finish_or_empty(self, staged) -> list[bytes]:
        if staged is None:
            return []
        if hasattr(staged, "wait"):  # batched: an _EncryptHandle
            return staged.wait()
        return self._encrypt_finish(staged)

    def _compress_batch(
        self, chunks: list[bytes], opts: TransformOptions
    ) -> tuple[list, Optional[np.ndarray]]:
        """One frame a chunk, and the ring buffer the frames are views of
        where the native zstd codec wrote them: each chunk is compressed
        where it lies, into a row of a `uint8[rows, zstd_bound(largest)]`
        buffer that an earlier window has mapped. Whoever takes the frames
        hands that buffer back (`_dispatch_encrypt_window`, `_own_frames`).
        Every other codec returns owned `bytes` and no buffer."""
        with self.tracer.span("transform.compress", chunks=len(chunks)) as span:
            frames, frame_buffer = self._compress_frames(chunks, opts)
            bytes_in = sum(len(c) for c in chunks)
            bytes_out = sum(len(f) for f in frames)
            with self._stats_lock:
                self.dispatch_stats.codec_bytes_in += bytes_in
                if frame_buffer is None:
                    self.dispatch_stats.codec_bytes_copied += bytes_out
                note_mutation("tpu.TpuTransformBackend.dispatch_stats")
            if span is not None:
                span.attributes["bytes_in"] = bytes_in
                span.attributes["bytes_out"] = bytes_out
        return frames, frame_buffer

    def _compress_frames(self, chunks: list[bytes], opts: TransformOptions):
        if opts.compression_codec == THUFF:
            from tieredstorage_tpu.transform import thuff

            return thuff.compress_batch(chunks), None
        if opts.compression_codec == TLZHUFF:
            from tieredstorage_tpu.transform import lzhuff

            return lzhuff.compress_batch(chunks), None
        if opts.compression_codec != ZSTD:
            raise ValueError(f"Codec {opts.compression_codec!r} not implemented")
        level = opts.compression_level
        if self._use_native():
            frame_buffer = self._acquire_staging(
                (len(chunks), native.zstd_bound(max(len(c) for c in chunks)))
            )
            return native.zstd_compress_into(chunks, frame_buffer, level=level), frame_buffer
        if zstandard is None:
            raise ModuleNotFoundError(
                "The 'zstandard' package is required for the 'zstd' codec "
                "but is not installed"
            )
        return list(
            self._zstd_pool().map(
                lambda c: zstandard.ZstdCompressor(
                    level=level, write_content_size=True
                ).compress(c),
                chunks,
            )
        ), None

    def _own_frames(self, frames: list, frame_buffer: Optional[np.ndarray]) -> list[bytes]:
        """The frames as `bytes` of their own, for whoever keeps them past
        this window (a compression-only transform's caller, the batcher's
        queue), and their buffer back to the ring."""
        if frame_buffer is None:
            return frames
        owned = [frame.tobytes() for frame in frames]
        self._release_staging(frame_buffer)
        with self._stats_lock:
            self.dispatch_stats.codec_bytes_copied += sum(len(f) for f in owned)
            note_mutation("tpu.TpuTransformBackend.dispatch_stats")
        return owned

    @staticmethod
    def _use_native() -> bool:
        """Host zstd stays on the CPU (SURVEY §7 hard part 1); prefer the C++
        batch library over the Python thread pool when it's buildable. Only
        the zstd half is needed here, so libcrypto availability is not
        required (native.load, not native.available)."""
        return native.load() is not None

    @classmethod
    def zstd_engine(cls) -> str:
        """Which host zstd implementation the `zstd` codec runs on — the
        choice `_use_native` makes from whether the lazy `make` succeeded:
        ``"native"`` (native/transform_host.cpp) or ``"python-pool"``."""
        return "native" if cls._use_native() else "python-pool"

    def _make_ivs(self, n: int, opts: TransformOptions) -> np.ndarray:
        if opts.ivs is not None:
            if len(opts.ivs) < n:
                raise ValueError("Not enough IVs for the chunk batch")
            return np.stack(
                [np.frombuffer(iv, dtype=np.uint8) for iv in opts.ivs[:n]]
            )
        return np.frombuffer(os.urandom(IV_SIZE * n), dtype=np.uint8).reshape(n, IV_SIZE)

    def _window_context(self, enc, sizes: list[int], compressed: bool = False):
        """The GCM context of a window of these row sizes, its row width and
        whether it is the varlen form. A hit is a dictionary lookup; a miss
        builds the (key, aad, size)'s constants on the host (`built`).

        Rows of a compressed segment each have a size of their own, so their
        windows take the varlen form whatever their row count: the row width
        is then a rung of `bucket_max_bytes`, and a one-row window shares its
        program with every chunk on that rung where the fixed form would
        trace and compile one per distinct size. Encrypt-only rows of one
        size keep the fixed-shape program."""
        with self.tracer.span("transform.context") as span:
            builds = gcm_ops.thread_context_builds()
            varlen = compressed or len(set(sizes)) != 1
            if varlen:
                ctx = make_varlen_context(enc.data_key, enc.aad, max(sizes))
                n_bytes = ctx.max_bytes
            else:
                ctx = make_context(enc.data_key, enc.aad, sizes[0])
                n_bytes = ctx.chunk_bytes
            if span is not None:
                span.attributes["built"] = gcm_ops.thread_context_builds() > builds
        return ctx, n_bytes, varlen

    def _staging_bound(self) -> int:
        """Bytes the ring may hold free: for each of the most encrypt
        streams in flight at once since it last gave buffers back, and for
        one where none was, the `pipeline_depth + 1` windows a stream keeps
        in flight, at the window byte cap, twice over for the other buffers
        a stream takes beside its full windows (the codec's frame buffer, a
        ragged or one-row window, the index rows). Ten concurrent copies
        hand back ten streams' buffers; bounded at one stream's, the ring
        would trim most of them and every later window would pack into
        fresh memory."""
        streams = max(1, self._encrypt_streams_peak)
        return 2 * (self.pipeline_depth + 1) * self.preferred_batch_bytes * streams

    def _acquire_staging(self, shape: tuple) -> np.ndarray:
        """A host buffer for one packed window: the ring's, already mapped
        and dirty with an earlier window of this shape, or a new one that
        the first pass over it has to fault in."""
        packed = None
        with self._stats_lock:
            self.dispatch_stats.staging_acquired += 1
            free = self._staging_free.get(shape)
            if free:
                packed = free.pop()
                if not free:
                    del self._staging_free[shape]
                self._staging_free_bytes -= packed.nbytes
                self.dispatch_stats.staging_reused += 1
            note_mutation("tpu.TpuTransformBackend.dispatch_stats")
            note_mutation("tpu.TpuTransformBackend._staging_free")
        return np.empty(shape, dtype=np.uint8) if packed is None else packed

    def _release_staging(self, packed: np.ndarray) -> None:
        """Hand a finished window's buffer back to the ring. Only once the
        program that read the staged window has run and the host has what
        it needs of the output: until then the host->device copy may still
        be reading the buffer (and where a placement is zero-copy, as the
        CPU backend's is for an aligned array, the staged array IS the
        buffer). Over the bound the shapes returned longest ago give way
        first."""
        with self._stats_lock:
            self._staging_free.setdefault(packed.shape, []).append(packed)
            self._staging_free.move_to_end(packed.shape)
            self._staging_free_bytes += packed.nbytes
            self.dispatch_stats.staging_trimmed_bytes += self._trim_staging(
                self._staging_bound()
            )
            note_mutation("tpu.TpuTransformBackend._staging_free")
            note_mutation("tpu.TpuTransformBackend.dispatch_stats")

    def _trim_staging(self, bound: int) -> int:
        """Let the free buffers returned longest ago go until the ring holds
        at most `bound` bytes; the bytes let go. Under `_stats_lock`."""
        trimmed = 0
        while self._staging_free_bytes > bound:
            shape, oldest = next(iter(self._staging_free.items()))
            n_bytes = oldest.pop(0).nbytes
            self._staging_free_bytes -= n_bytes
            trimmed += n_bytes
            if not oldest:
                del self._staging_free[shape]
        return trimmed

    def _build_packed(
        self, payloads: list, sizes: list[int], ivs: np.ndarray, n_bytes: int,
        varlen: bool,
    ) -> np.ndarray:
        """One packed host window uint8[B, n_bytes + 16]: left-aligned
        payload rows (zero tail — varlen GHASH requires it) with the
        per-row metadata the fused kernel reads from the tail columns
        ([iv 12 B][length u32 LE 4 B]), so the whole window crosses the
        host→device link as a single buffer. The buffer is the ring's
        (`_acquire_staging`) and may be dirty: every byte the program reads
        is written here, a short row's tail included; whoever ends the
        window hands it back with `_release_staging`."""
        with self.tracer.span("transform.pack"):
            packed = self._acquire_staging((len(payloads), n_bytes + TAG_SIZE))
            for i, p in enumerate(payloads):
                packed[i, : sizes[i]] = np.frombuffer(p, dtype=np.uint8)
                packed[i, sizes[i] : n_bytes] = 0
            packed[:, n_bytes : n_bytes + IV_SIZE] = ivs
            packed[:, n_bytes + IV_SIZE :] = (
                np.asarray(sizes, dtype="<u4").view(np.uint8).reshape(-1, 4)
                if varlen else 0
            )
        return packed

    def _stage_packed(self, packed: np.ndarray, varlen: bool):
        """Mesh-pad and ship one packed window to the device — the single
        host→device transfer of the window path (h2d counter). The row
        axis lands sharded over the plan's mesh (replication-free: each
        chip holds only its rows), or on the one device on the fallback
        plan. The `transform.h2d` span is the host's time in the placement,
        not the transfer's (the device plane of a profile has that)."""
        plan = self.mesh_plan()
        n_bytes = packed.shape[1] - TAG_SIZE
        pad = plan.pad_rows(packed.shape[0])
        if pad:
            pad_rows = np.zeros((pad, packed.shape[1]), np.uint8)
            if varlen:
                # Degenerate zero-length rows are excluded by the varlen
                # contract; padding rows carry one block like real callers.
                pad_rows[:, n_bytes + IV_SIZE] = 16
            packed = np.concatenate([packed, pad_rows])
        with self.tracer.span("transform.h2d", bytes=packed.nbytes):
            staged = plan.shard(packed)
        with self._stats_lock:
            self.dispatch_stats.h2d_transfers += 1
            self.dispatch_stats.mesh_size = plan.size
            self.dispatch_stats.rows_per_device = packed.shape[0] // plan.size
            note_mutation("tpu.TpuTransformBackend.dispatch_stats")
        return staged

    def keyed_launches(self) -> bool:
        """Whether a merged window may carry rows of several keys in one
        launch (`ops.gcm.gcm_keyed_window_packed`): on one device. The
        keyed program has no sharded form."""
        return self.mesh_plan().mesh is None

    def _launch_packed(
        self, ctx, staged, varlen: bool, *, decrypt: bool, row_keys=None,
        copy_back: bool = True,
    ):
        """ONE fused device dispatch for a staged window (keystream → XOR →
        GHASH → tag in a single program, `output || tag` packed into a
        single buffer), with the staged buffer donated back to XLA as the
        output allocation. Input and output carry the identical shape AND
        row sharding on both the fallback and the mesh path (shard_map
        out_specs mirror the staged rows), so donation aliases in the
        steady state regardless of mesh size; a genuinely mismatched
        sharding would be the only reason to skip, and no such case exists
        on this path. Starts the device→host copy immediately so the
        result streams back while later windows compute, unless
        `copy_back` is False: the batcher's merged decrypt launch, whose
        waiters each fetch only their own rows (a whole-output copy of every
        such launch made the fan-in cell 39 % slower on the v5e; PERF.md §6).
        The
        `transform.launch` span is the host's enqueue time: placing the
        context's constants on their first use, the jitted call (`traced`
        when it traced a new program: seconds, where a launch is
        milliseconds) and starting the copy back. With `row_keys` the
        window's rows carry different keys: `ctx` is the launch's key table
        and the keyed varlen program runs (`keyed_launches`). The span's
        `streams` and `queued` are the encrypt streams in flight and the
        unbatched encrypt windows launched before this one and not yet
        finished (`DispatchStats.launch_queue_sum`)."""
        mesh = self.mesh_plan().mesh
        with self.tracer.span("transform.launch") as span:
            traces = thread_program_traces()
            before = gcm_ops.thread_dispatches()
            rt_before = gcm_ops.thread_hbm_roundtrips()
            if row_keys is not None:
                out = gcm_keyed_window_packed(
                    ctx, row_keys, staged, decrypt=decrypt, donate=True,
                )
            elif varlen:
                out = gcm_varlen_window_packed(
                    ctx, None, staged, None, decrypt=decrypt, donate=True,
                    mesh=mesh,
                )
            else:
                out = gcm_window_packed(
                    ctx, None, staged, decrypt=decrypt, donate=True, mesh=mesh,
                )
            delta = gcm_ops.thread_dispatches() - before
            rt_delta = gcm_ops.thread_hbm_roundtrips() - rt_before
            donated = staged.is_deleted()  # XLA consumed the staged allocation
            with self._stats_lock:
                stats = self.dispatch_stats
                stats.dispatches += delta
                stats.hbm_roundtrips += rt_delta
                if donated:
                    stats.donated_buffers += 1
                streams, queued = self._encrypt_streams, self._encrypt_windows_out
                if not decrypt:
                    stats.encrypt_stream_launch_sum += streams
                stats.launch_queue_sum += queued
                note_mutation("tpu.TpuTransformBackend.dispatch_stats")
            if copy_back:
                out.copy_to_host_async()
            if span is not None:
                span.attributes["traced"] = thread_program_traces() > traces
                span.attributes["streams"] = streams
                span.attributes["queued"] = queued
                self._watch_window(out, span, varlen, decrypt)
        return out

    def _offer_rows(self, out, first_row: int, n_bytes: int, sizes) -> None:
        """Offer a verified decrypt window that rode a merged launch to the
        retention hook, on the waiter's own thread (whose capture scope it
        fills): its rows of the merged output `out`, from `first_row`. The
        hook is handed a maker of a device copy of just those rows, so a
        retained window holds its own device bytes and never pins the
        merged buffer, and nothing is copied unless the tier admits."""
        hook = self.on_decrypt_window
        if hook is not None:
            hook(
                functools.partial(gcm_ops.take_rows, out, first_row, len(sizes)),
                sizes, n_bytes, self.mesh_plan().size,
            )

    def _watch_window(self, out, launch, varlen: bool, decrypt: bool) -> None:
        """Hand a window launched under an enabled tracer to the device
        watch, which the first such window starts: `rows` and `bytes` are
        the staged window's, mesh and rung padding included (what the
        program was given, not the payload)."""
        watch = self.device_watch
        if watch is None:
            with self._stats_lock:
                watch = self.device_watch
                if watch is None:
                    watch = self.device_watch = DeviceWatch(
                        self.tracer, self._note_device_seen
                    )
        watch.watch(
            out, launch, rows=out.shape[0], bytes=out.nbytes, decrypt=decrypt,
            varlen=varlen,
        )

    def _note_device_seen(self, nanoseconds: int) -> None:
        with self._stats_lock:
            self.dispatch_stats.device_seen_ns += nanoseconds
            note_mutation("tpu.TpuTransformBackend.dispatch_stats")

    def _split_wait(self, wait, out, *, shared: bool = False) -> None:
        """A finished `transform.d2h_wait`, split at the moment the window's
        result was ready (the device watch's stamp, or the wait's own end
        where that came first): `transform.ready_wait` up to it, the program
        still running, and `transform.collect` after it, what the thread
        still paid once the result existed (the rest of the copy back, being
        woken, being given the interpreter, `np.asarray`). The two tile the
        wait; a result that was ready before the wait began leaves no
        `ready_wait`. `shared`: one of several waiters of a merged launch
        collecting its own rows of `out`."""
        watch = self.device_watch
        ready_s = (
            wait.end_s if watch is None
            else watch.ready_by(out, wait.end_s, claim=not shared)
        )
        if ready_s > wait.start_s:
            self.tracer.record(
                "transform.ready_wait", wait.start_s, ready_s, parent=wait
            )
        self.tracer.record(
            "transform.collect", max(ready_s, wait.start_s), wait.end_s, parent=wait
        )

    @_spanned("transform.encrypt_dispatch")
    def _encrypt_dispatch(self, chunks: list[bytes], opts: TransformOptions):
        """Stage and launch a window: build ONE packed host array, ship it
        with one device_put, issue ONE fused GCM dispatch, start the
        device→host copy; returns the un-materialized staged window."""
        enc = opts.encryption
        sizes = [len(c) for c in chunks]
        ivs = self._make_ivs(len(chunks), opts)

        ctx, n_bytes, varlen = self._window_context(enc, sizes, opts.compression)
        packed = self._build_packed(chunks, sizes, ivs, n_bytes, varlen)
        staged = self._stage_packed(packed, varlen)
        out = self._launch_packed(ctx, staged, varlen, decrypt=False)
        self._note_window(sum(sizes), len(sizes), n_bytes, varlen)
        with self._stats_lock:
            self._encrypt_windows_out += 1
        return ivs, sizes, n_bytes, out, [packed]

    @_spanned("transform.encrypt_finish", count=lambda staged: len(staged[1]),
              n_bytes=lambda staged: sum(staged[1]))
    def _encrypt_finish(self, staged) -> list[bytes]:
        """Block on a staged window's single packed device buffer (one
        device→host fetch) and materialize the wire format
        (IV || ct || tag per chunk): one allocation and one copy of the
        payload per chunk. The window is over then, and its host staging
        buffer goes back to the ring."""
        ivs, sizes, n_bytes, out, staging = staged
        try:
            with self.tracer.span("transform.d2h_wait") as wait:
                host = np.asarray(out)
        except BaseException:
            self._drop_window(staging)
            raise
        if wait is not None:
            self._split_wait(wait, out)
        with self._stats_lock:
            self.dispatch_stats.d2h_fetches += 1
            note_mutation("tpu.TpuTransformBackend.dispatch_stats")
        wire = [
            b"".join((
                ivs[i].tobytes(),
                memoryview(host[i, : sizes[i]]),
                memoryview(host[i, n_bytes:]),
            ))
            for i in range(len(sizes))
        ]
        if staging:  # a window finished twice returns its buffer once
            with self._stats_lock:
                self._encrypt_windows_out -= 1
            self._release_staging(staging.pop())
        return wire

    # ----------------------------------------------------------- detransform
    def detransform(self, chunks: Sequence[bytes], opts: DetransformOptions) -> list[bytes]:
        out = list(chunks)
        if not out:
            return []
        if opts.encryption is not None:
            out = self._decrypt_batch(out, opts)
        if opts.compression:
            out = self._decompress_batch(out, opts)
        return out

    @_spanned("transform.decompress")
    def _decompress_batch(self, chunks: list[bytes], opts: DetransformOptions) -> list[bytes]:
        """The codec's half of a fetch, after every row's tag has been
        verified: `transform.compress`'s twin."""
        if opts.compression_codec == THUFF:
            from tieredstorage_tpu.transform import thuff

            return thuff.decompress_batch(chunks, opts.max_original_chunk_size)
        if opts.compression_codec == TLZHUFF:
            from tieredstorage_tpu.transform import lzhuff

            return lzhuff.decompress_batch(chunks, opts.max_original_chunk_size)
        if opts.compression_codec != ZSTD:
            raise ValueError(f"Codec {opts.compression_codec!r} not implemented")
        if self._use_native():
            return native.zstd_decompress_batch(
                chunks, max_decompressed=opts.max_original_chunk_size
            )
        if zstandard is None:
            raise ModuleNotFoundError(
                "The 'zstandard' package is required for the 'zstd' "
                "codec but is not installed"
            )
        native.checked_frame_content_sizes(chunks, opts.max_original_chunk_size)
        # One DCtx per chunk: zstandard (de)compressor objects are not
        # thread-safe across the pool's workers.
        return list(
            self._zstd_pool().map(
                lambda c: zstandard.ZstdDecompressor().decompress(c), chunks
            )
        )

    @_spanned("transform.decrypt")
    def _decrypt_batch(self, chunks: list[bytes], opts: DetransformOptions) -> list[bytes]:
        """Fetch-direction window through the same fused single-dispatch
        path as encrypt: one packed staging transfer, one device program
        computing plaintext + EXPECTED tags, one fetch; tags verified
        host-side against the received ones. With cross-request batching
        enabled (`transform.batch.enabled`, transform/batcher.py) the
        window instead joins the shared device queue and may ride ONE
        merged launch with windows from concurrent requests — the
        single-waiter fast path falls straight back to `_decrypt_window`,
        so light load is byte- and latency-identical to the unbatched
        path."""
        enc = opts.encryption
        for i, c in enumerate(chunks):
            if len(c) < IV_SIZE + TAG_SIZE:
                raise ValueError(f"Encrypted chunk {i} shorter than IV+tag")
        ivs = np.stack(
            [np.frombuffer(c[:IV_SIZE], dtype=np.uint8) for c in chunks]
        )
        received_tags = [c[-TAG_SIZE:] for c in chunks]
        sizes = [len(c) - IV_SIZE - TAG_SIZE for c in chunks]
        # Views, not slices: the one copy of a stored chunk's payload is the
        # one `_build_packed` makes into the staged window.
        payloads = [memoryview(c)[IV_SIZE:-TAG_SIZE] for c in chunks]
        batcher = self.batcher
        if batcher is not None and min(sizes) > 0:
            # Zero-length rows are excluded by the varlen window contract
            # the merged launch uses; such windows take the direct path.
            return batcher.submit(enc, payloads, sizes, ivs, received_tags)
        return self._decrypt_window(
            enc, payloads, sizes, ivs, received_tags, opts.compression
        )

    def _decrypt_window(
        self, enc, payloads: list, sizes: list[int], ivs: np.ndarray,
        received_tags: list, compressed: bool = False,
    ) -> list[bytes]:
        """The unbatched decrypt window: ONE staging transfer, ONE fused
        launch, ONE fetch for this caller's rows alone. Also the
        batcher's single-waiter fast path (zero added latency at light
        load, the hot-tier retention hook included; a merged window's
        rows are offered by `_offer_rows` instead). `compressed` is the
        manifest's word that the rows are compressed chunks
        (`_window_context`)."""
        ctx, n_bytes, varlen = self._window_context(enc, sizes, compressed)
        packed = self._build_packed(payloads, sizes, ivs, n_bytes, varlen)
        staged = self._stage_packed(packed, varlen)
        out = self._launch_packed(ctx, staged, varlen, decrypt=True)
        self._note_window(sum(sizes), len(sizes), n_bytes, varlen)

        with self.tracer.span("transform.d2h_wait") as wait:
            host = np.asarray(out)
        if wait is not None:
            self._split_wait(wait, out)
        with self._stats_lock:
            self.dispatch_stats.d2h_fetches += 1
            note_mutation("tpu.TpuTransformBackend.dispatch_stats")
        bad = [
            i
            for i in range(len(sizes))
            if not hmac.compare_digest(
                host[i, n_bytes:].tobytes(), received_tags[i]
            )
        ]
        if bad:
            raise AuthenticationError(f"GCM tag mismatch on chunks {bad}")
        hook = self.on_decrypt_window
        if hook is not None:
            hook(out, sizes, n_bytes, self.mesh_plan().size)
        plain = [host[i, : sizes[i]].tobytes() for i in range(len(sizes))]
        self._release_staging(packed)
        return plain


def _definition():
    """ConfigDef of the `transform.`-prefixed keys `configure()` reads —
    rendered into docs/configs.rst (the generated-docs drift gate in
    `make analyze` keeps it in sync with the committed file)."""
    from tieredstorage_tpu.config.configdef import ConfigDef, ConfigKey, in_range

    d = ConfigDef()
    d.define(ConfigKey(
        "batch.chunks", "int", default=256, validator=in_range(1, None),
        importance="medium",
        doc="Preferred chunks per device transform window.",
    ))
    d.define(ConfigKey(
        "batch.bytes", "long", default=64 << 20, validator=in_range(1, None),
        importance="medium",
        doc="Window byte cap. On a v5e the program of a 64 MiB window (16 "
            "rows of 4 MiB) reserves about 1.5 GiB of HBM temporaries while "
            "it executes — reserved once, not per window in flight — beside "
            "about 0.3 GiB of staged and output buffers at pipeline.depth 3. "
            "Also the flush byte cap of a merged cross-request decrypt "
            "launch (batch.enabled).",
    ))
    d.define(ConfigKey(
        "pipeline.depth", "int", default=3, validator=in_range(1, None),
        importance="medium",
        doc="Double-buffer depth of transform_windows: staged windows kept "
            "in flight before blocking on the oldest (host compress || "
            "device encrypt || device->host copy).",
    ))
    d.define(ConfigKey(
        "batch.enabled", "bool", default=False, importance="medium",
        doc="Coalesce GCM windows from CONCURRENT requests into shared "
            "fused launches (transform/batcher.py): one work-class-aware "
            "device queue (latency fetch decrypts / throughput produce "
            "encrypts / background scrub verification — classes never "
            "share a merged launch) whose flush policy is deadline- and "
            "class-aware, grouped by the bucket_max_bytes jit-shape ladder "
            "so coalescing never retraces. Decrypt windows of different "
            "segments merge whatever their data keys: a flush carries a "
            "per-launch key table and each row is decrypted and verified "
            "under its own key and AAD (on one device; under a mesh each "
            "key launches apart); encrypt windows merge within one key. A "
            "merged window's verified rows are offered to the device hot "
            "tier as a copy of their own. A foreground submit that finds "
            "the batcher idle dispatches inline (the single-waiter fast "
            "path), so light load pays zero added latency. Default off: "
            "every window dispatches unbatched, exactly the pre-batch "
            "path.",
    ))
    d.define(ConfigKey(
        "batch.wait.ms", "long", default=2, validator=in_range(0, None),
        importance="medium",
        doc="Max added wait (ms) a queued foreground (latency/throughput "
            "class) window tolerates before its bucket flushes regardless "
            "of occupancy. Flushes also fire when batch.windows or "
            "batch.bytes is reached, or when the oldest waiter's remaining "
            "deadline minus the observed launch p95 hits the floor.",
    ))
    d.define(ConfigKey(
        "batch.background.max.age.ms", "long", default=50,
        validator=in_range(0, None), importance="low",
        doc="Starvation-watchdog bound (ms) for background-class (scrub / "
            "anti-entropy verification) windows on the shared device "
            "queue: the max age a background bucket may sit queued under "
            "sustained foreground pressure before it must flush (admission "
            "budget permitting) — bounded forward progress without letting "
            "background work bite foreground latency.",
    ))
    d.define(ConfigKey(
        "batch.windows", "int", default=16, validator=in_range(2, None),
        importance="medium",
        doc="Max windows coalesced into one shared decrypt launch (the "
            "occupancy cap per flush); batch.bytes (the window byte cap) "
            "bounds the merged launch's bytes.",
    ))
    d.define(ConfigKey(
        "mesh.devices", "int", default=0, validator=in_range(0, None),
        importance="medium",
        doc="Shard every packed transform window's row axis over a 1-D data "
            "mesh of this many local devices: 0 (default) = all local "
            "chips, 1 = single-chip (exactly the unsharded path), n = the "
            "first n local devices (configuration fails at first use when "
            "fewer are attached). One window stays ONE logical fused "
            "dispatch at any mesh size; single-chip hosts never trace the "
            "shard_map layer.",
    ))
    return d
