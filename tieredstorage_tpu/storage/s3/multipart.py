"""Multipart upload output stream.

Reference: storage/s3/.../S3MultiPartOutputStream.java:40-211 — buffer up to
`part_size` bytes, lazily create the multipart upload on the first flushed
part, upload each full buffer as a part, complete on close, abort on any
error; `processed_bytes()` is the upload-size accounting surfaced through
ObjectUploader.upload.

Where the reference sends each part on the writer's thread and waits for its
reply, this stream fills a part where it will be sent from and hands the full
buffer to the store's part workers (`PartWorkers`), so that the writer goes on
pulling its source while up to `parts_in_flight` parts are hashed, sent and
acknowledged. The stored object, the part boundaries, the requests and the
commit point (Complete, sent only once every part has its ETag) are the serial
stream's.
"""

from __future__ import annotations

import io
import logging
import time
from concurrent.futures import ThreadPoolExecutor
from typing import Callable, Optional

from tieredstorage_tpu.storage.s3.client import S3Client
from tieredstorage_tpu.utils.deadline import Deadline, current_deadline, deadline_scope
from tieredstorage_tpu.utils.locks import new_condition, new_lock
from tieredstorage_tpu.utils.tracing import format_traceparent

log = logging.getLogger(__name__)

#: A slice of a writer's wait for its parts, not a limit: the loop waits on.
#: What bounds the wait is the PUT it waits for (the client's timeouts and
#: retry policy, under the writer's own deadline).
_WAIT_SLICE_TIMEOUT_S = 1.0


class PartWorkers:
    """What the multipart streams of one `S3Storage` share, as they share its
    connection pool: the threads that put parts (two streams' worth of parts
    in flight, under the RLM's 10 task threads and the client's 32 pooled
    connections; started with the first part any stream hands over, joined
    by `close`), the part buffers between one upload and the next (a fresh
    5 MiB is a millisecond of page faults; a stream's worth is kept), and the
    exact counts of both."""

    def __init__(self, parts_in_flight: int) -> None:
        #: Parts of one stream that may be with the workers at once; a stream
        #: holds one buffer more, the one being filled.
        self.parts_in_flight = parts_in_flight
        self._lock = new_lock("multipart.PartWorkers._lock")
        self._executor: Optional[ThreadPoolExecutor] = None
        self._spare: list[bytearray] = []
        #: Nanoseconds of `upload_part` calls on the workers, nanoseconds the
        #: writers stood in `s3.part_wait`, and the most parts any one stream
        #: has had in flight.
        self.part_put_ns = 0
        self.part_wait_ns = 0
        self.parts_in_flight_max = 0

    def submit(self, put: Callable[[], None]) -> None:
        with self._lock:
            if self._executor is None:
                self._executor = ThreadPoolExecutor(
                    max_workers=2 * self.parts_in_flight, thread_name_prefix="s3-part"
                )
            executor = self._executor
        executor.submit(put)

    def take_buffer(self, part_size: int) -> bytearray:
        with self._lock:
            while self._spare:
                buffer = self._spare.pop()
                if len(buffer) == part_size:
                    return buffer
        return bytearray(part_size)

    def give_buffers(self, buffers: list[bytearray]) -> None:
        with self._lock:
            room = self.parts_in_flight + 1 - len(self._spare)
            self._spare.extend(buffers[: max(0, room)])

    def count(self, *, put_ns: int = 0, wait_ns: int = 0, in_flight: int = 0) -> None:
        with self._lock:
            self.part_put_ns += put_ns
            self.part_wait_ns += wait_ns
            self.parts_in_flight_max = max(self.parts_in_flight_max, in_flight)

    def counters(self) -> dict:
        with self._lock:
            return {
                "part_put_ns": self.part_put_ns,
                "part_wait_ns": self.part_wait_ns,
                "parts_in_flight_max": self.parts_in_flight_max,
            }

    def close(self) -> None:
        """Joins the workers: every part handed over has been answered."""
        with self._lock:
            executor, self._executor = self._executor, None
            self._spare.clear()
        if executor is not None:
            executor.shutdown(wait=True)


class S3MultiPartOutputStream(io.RawIOBase):
    """One writer's thread fills parts and hands them over (`write`,
    `_flush_part`, `close`, `abort` are that thread's); the workers put them
    and file the ETags. An object that never fills a part is one PutObject on
    the writer's thread and meets no worker."""

    def __init__(self, client: S3Client, key: str, part_size: int, workers: PartWorkers):
        self.client = client
        self.key = key
        self.part_size = part_size
        self._workers = workers
        #: Guards what the workers touch: the parts in flight, their ETags by
        #: number, the first failure, the buffers that have come back.
        self._parts = new_condition("multipart.S3MultiPartOutputStream._parts")
        self._in_flight = 0
        self._etags: dict[int, str] = {}
        self._failure: Optional[BaseException] = None
        self._free: list[bytearray] = []
        # The writer's own: the buffer being filled and how far.
        self._filling: Optional[bytearray] = None
        self._filled = 0
        self._upload_id: str | None = None
        self._part_number = 0
        self._processed = 0
        self._aborted = False

    def writable(self) -> bool:
        return True

    @property
    def processed_bytes(self) -> int:
        return self._processed

    def write(self, data) -> int:
        if self.closed or self._aborted:
            raise ValueError("Stream is closed")
        view = memoryview(data).cast("B")
        try:
            taken = 0
            while taken < len(view):
                if self._filling is None:
                    self._filling = self._next_buffer()
                n = min(len(view) - taken, self.part_size - self._filled)
                # `s3.part_buffer`: the stream's one copy of every byte, into
                # the part where it will be sent from (two spans where a block
                # straddles a part's end).
                with self.client.tracer.span("s3.part_buffer"):
                    self._filling[self._filled : self._filled + n] = view[taken : taken + n]
                taken += n
                self._filled += n  # tsa: single-thread
                if self._filled == self.part_size:
                    self._flush_filled()
        except Exception:
            self.abort()
            raise
        self._processed += len(view)  # tsa: single-thread
        return len(view)

    def _next_buffer(self) -> bytearray:
        """A buffer to fill: one that has come back from a worker, else one of
        the workers' spares or a new one. No wait: with at most
        `parts_in_flight` parts out, a stream holds one buffer more."""
        with self._parts:
            if self._free:
                return self._free.pop()
        return self._workers.take_buffer(self.part_size)

    def _wait(self, ready: Callable[[], object]) -> None:
        """The writer stands until `ready()` holds of what the workers touch:
        the span `s3.part_wait` and the count `part_wait_ns`."""
        start = time.perf_counter_ns()
        with self.client.tracer.span("s3.part_wait"), self._parts:
            while not ready():
                self._parts.wait(_WAIT_SLICE_TIMEOUT_S)
        self._workers.count(wait_ns=time.perf_counter_ns() - start)

    def _wait_for_parts(self) -> None:
        """Until nothing is in flight; then the first part's failure, if any."""
        self._wait(lambda: not self._in_flight)
        self._raise_failure()

    def _raise_failure(self) -> None:
        with self._parts:
            failure = self._failure
        if failure is not None:
            raise failure

    def _flush_filled(self) -> None:
        self._flush_part(memoryview(self._filling)[: self._filled])
        # Handed over, or (a `_flush_part` that sent nothing) filled again.
        self._filled = 0

    def _flush_part(self, data: memoryview) -> None:
        """Hand the filled buffer, of which `data` is a view, to a worker as
        the next part: the only place that takes a part number and the only
        way a part reaches the client. Where `parts_in_flight` are out
        already the writer waits for one to come back first; once a part has
        failed no other is handed over."""
        depth = self._workers.parts_in_flight
        with self._parts:
            all_out = self._in_flight >= depth
        if all_out:
            self._wait(lambda: self._in_flight < depth)
        self._raise_failure()
        if self._upload_id is None:
            self._upload_id = self.client.create_multipart_upload(self.key)
        self._part_number += 1  # tsa: single-thread
        number, buffer, self._filling = self._part_number, self._filling, None
        with self._parts:
            self._in_flight += 1
            in_flight = self._in_flight
        self._workers.count(in_flight=in_flight)
        # The worker's spans join the copy's trace under this event, which has
        # no extent: `storage.upload`'s own time stays the writer's.
        handover = self.client.tracer.event("s3.part_handover", part=number)
        traceparent = handover and format_traceparent(handover.trace_id, handover.span_id)
        deadline = current_deadline()
        try:
            self._workers.submit(
                lambda: self._put_part(number, data, buffer, traceparent, deadline)
            )
        except BaseException:
            self._part_returned(buffer)
            raise

    def _put_part(
        self, number: int, data: memoryview, buffer: bytearray,
        traceparent: Optional[str], deadline: Optional[Deadline],
    ) -> None:
        """On a worker: one `upload_part` call, retries included, of a view
        that `HttpClient` may send more than once; the buffer goes back to
        the writer only when the call has returned."""
        etag, failure = None, None
        start = time.perf_counter_ns()
        try:
            with self.client.tracer.continue_trace(traceparent), deadline_scope(deadline):
                etag = self.client.upload_part(self.key, self._upload_id, number, data)
        except BaseException as e:  # noqa: BLE001 — raised again on the writer's thread
            failure = e
        self._workers.count(put_ns=time.perf_counter_ns() - start)
        self._part_returned(buffer, number, etag, failure)

    def _part_returned(
        self, buffer: bytearray, number: int = 0, etag: Optional[str] = None,
        failure: Optional[BaseException] = None,
    ) -> None:
        with self._parts:
            if etag is not None:
                self._etags[number] = etag
            elif self._failure is None:
                self._failure = failure
            self._in_flight -= 1
            self._free.append(buffer)
            self._parts.notify_all()

    def _give_buffers_back(self) -> None:
        """To the workers' spares, once nothing is in flight."""
        with self._parts:
            buffers, self._free = self._free, []
        if self._filling is not None:
            buffers.append(self._filling)
        self._filling = None
        self._workers.give_buffers(buffers)

    def abort(self) -> None:
        """Best-effort abort; safe to call repeatedly
        (reference: S3MultiPartOutputStream.java:124-146). Every part in
        flight is waited for first: no Abort is sent while a PUT of the same
        upload may still land."""
        if self._aborted:
            return
        self._aborted = True
        if self._upload_id is not None:
            self._wait(lambda: not self._in_flight)
            try:
                self.client.abort_multipart_upload(self.key, self._upload_id)
            except Exception:  # noqa: BLE001 — abort is best-effort by contract
                # Logged, not raised: the caller is already unwinding an
                # upload failure, but a leaked multipart upload accrues
                # storage until lifecycle cleanup, so leave a trace.
                log.warning(
                    "Failed to abort multipart upload %s for %s",
                    self._upload_id, self.key, exc_info=True,
                )
        self._give_buffers_back()

    def close(self) -> None:
        if self.closed:
            return
        try:
            if not self._aborted:
                if self._upload_id is None:
                    # Whole object fit in one buffer: plain PutObject
                    # (cheaper than a 1-part multipart round trip).
                    filled = memoryview(self._filling or b"")[: self._filled]
                    self.client.put_object(self.key, filled)
                else:
                    # Every part has to have its ETag before the last, short
                    # one goes out, and that one too before Complete.
                    self._wait_for_parts()
                    if self._filled:
                        self._flush_filled()
                        self._wait_for_parts()
                    self.client.complete_multipart_upload(
                        self.key, self._upload_id, sorted(self._etags.items())
                    )
        except Exception:
            self.abort()
            raise
        finally:
            self._give_buffers_back()
            super().close()
