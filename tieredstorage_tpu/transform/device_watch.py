"""The device watch: when did the chip finish each window?

JAX returns from a launch before the device has run it, and the window path
never synchronises: the one blocking call of a window is the finish's
`np.asarray(out)`, which ends when the program has run, the bytes have come
back and the thread has been given the interpreter again. So no host span
knows how long the device took, and none can say what the device was waiting
for while it ran nothing.

Under an enabled tracer `TpuTransformBackend._launch_packed` hands every
launched window here. One daemon thread takes them first in, first out, calls
`block_until_ready()` on each (the one place where that call is right: the
thread is on no request's path, and the call releases the interpreter lock
while it waits), and stamps `time.perf_counter()` when it returns. For each
window it

- emits the event `device.ready` at that moment: inside a profiler session
  the event is a `TraceAnnotation` on the profiler's clock, beside the device
  plane, so `tools/profile_report.py` can say how late the stamps are;
- records the span `device.window`, child of the window's `transform.launch`,
  from the later of the launch's start and the previous window's ready stamp
  (the chip runs one program at a time) to this window's ready stamp: the
  host's upper bound of the device's time on the window. `Tracer.summary()`
  reads the device's idle time, and who held it, from these spans;
- adds the span's nanoseconds to `DispatchStats.device_seen_ns`.

A window has one ready stamp, set by whoever sees readiness first: this
thread, or the finishing thread whose `np.asarray` returned before this one
was woken (`ready_by`, which is how `transform.d2h_wait` is split into
`transform.ready_wait` and `transform.collect`). A finisher's stamp also
stamps every window launched before its own that has none yet: the device ran
them first. So the stamps never decrease in launch order, no two
`device.window` spans overlap, and a late watch never stretches a window past
its own finish.

With tracing off none of this exists: no thread, no object, no call.
"""

from __future__ import annotations

import collections
import queue
import threading
import time
import weakref
from typing import Callable, Optional

from tieredstorage_tpu.utils.locks import new_condition
from tieredstorage_tpu.utils.tracing import DEVICE_READY, DEVICE_WINDOW, Span, Tracer

#: How long `stop()` waits for the windows in flight: a daemon thread that a
#: hung program holds is left behind, not waited for.
STOP_TIMEOUT_S = 60.0


class _Flight:
    """One launched window until it is ready and its finisher has asked."""

    __slots__ = ("out", "out_ref", "launch", "attributes", "ready_s")

    def __init__(self, out, launch: Span, attributes: dict) -> None:
        self.out = out  # held until the window is ready, no longer
        self.out_ref = weakref.ref(out)
        self.launch = launch
        self.attributes = attributes
        self.ready_s: Optional[float] = None


class DeviceWatch:
    """Owned by one transform backend; `note_seen(ns)` is the backend's exact
    count of what the spans add up to."""

    def __init__(self, tracer: Tracer, note_seen: Callable[[int], None]) -> None:
        self._tracer = tracer
        self._note_seen = note_seen
        self._cond = new_condition("device_watch.DeviceWatch._cond")
        self._queue: "queue.SimpleQueue[Optional[_Flight]]" = queue.SimpleQueue()
        #: Launched and not yet recorded, in launch order; guarded by `_cond`.
        self._pending: "collections.deque[_Flight]" = collections.deque()
        #: Flights no finisher has claimed (a merged launch of the batcher,
        #: whose waiters each ask, never is): dropped once their output is
        #: gone; guarded by `_cond`.
        self._unclaimed: list = []
        self._last_ready_s = 0.0  # the watch thread's own
        self._thread = threading.Thread(target=self._run, name="device-watch", daemon=True)
        self._thread.start()

    # ------------------------------------------------------------ the launcher
    def watch(self, out, launch: Span, **attributes) -> None:
        """Called inside the window's `transform.launch` span, right after
        the launch: `out` is kept alive only until it is ready."""
        flight = _Flight(out, launch, attributes)
        with self._cond:
            self._pending.append(flight)
            self._unclaimed = [f for f in self._unclaimed if f.out_ref() is not None]
            self._unclaimed.append(flight)
        self._queue.put(flight)

    # ------------------------------------------------------------ the finisher
    def ready_by(self, out, wait_end_s: float, *, claim: bool = True) -> float:
        """The ready stamp of the window whose output is `out`, for the thread
        whose blocking read of it ended at `wait_end_s`: that end is itself
        proof of readiness, so it is the stamp where the watch has set none
        yet, and the answer is never later. No call here blocks. `claim`
        False: one of several waiters of a merged launch asks, and the flight
        stays for the others until its output is gone."""
        with self._cond:
            for at, flight in enumerate(self._unclaimed):
                if flight.out_ref() is out:
                    if claim:
                        del self._unclaimed[at]
                    return min(self._stamp(flight, wait_end_s), wait_end_s)
        return wait_end_s  # launched before this watch existed

    def _stamp(self, flight: _Flight, now_s: float) -> float:
        """`flight`'s stamp, set to `now_s` if it has none, together with
        every window launched before it that has none. Under `_cond`."""
        if flight.ready_s is None:
            for earlier in self._pending:
                if earlier.ready_s is None:
                    earlier.ready_s = now_s
                if earlier is flight:
                    break
        return flight.ready_s

    # ----------------------------------------------------------- the one thread
    def _run(self) -> None:
        while True:
            flight = self._queue.get()
            if flight is None:
                return
            failed = False
            try:
                flight.out.block_until_ready()
            except Exception:  # noqa: BLE001 — a failed program has ended too; its finisher raises
                failed = True
            now_s = time.perf_counter()
            self._tracer.event(DEVICE_READY)
            with self._cond:
                ready_s = self._stamp(flight, now_s)
                flight.out = None
            start_s = min(max(flight.launch.start_s, self._last_ready_s), ready_s)
            self._last_ready_s = ready_s
            self._tracer.record(
                DEVICE_WINDOW, start_s, ready_s, parent=flight.launch, failed=failed,
                **flight.attributes,
            )
            self._note_seen(round((ready_s - start_s) * 1e9))
            with self._cond:
                self._pending.popleft()
                self._cond.notify_all()

    def settle(self, timeout_s: float = 10.0) -> bool:
        """Wait until every window launched so far is recorded and counted
        (a reader of the spans or of `device_seen_ns` calls this first, never
        a request); False if the device was still busy at the timeout."""
        with self._cond:
            return self._cond.wait_for(lambda: not self._pending, timeout_s)

    def stop(self) -> None:
        """Record what is in flight, then end the thread."""
        self._queue.put(None)
        self._thread.join(STOP_TIMEOUT_S)

    def is_alive(self) -> bool:
        return self._thread.is_alive()
