"""Distributed tracing: Dapper-style spans across the RSM, fetch, and sidecar tiers.

The reference has no tracing (SURVEY §5 — only SLF4J boundary logs,
RemoteStorageManager.java:218,549,598); this build adds a real span system:

- nested spans with wall-time accounting and `trace_id`/`span_id`/`parent_id`
  identity, propagated through a thread-local context stack;
- W3C ``traceparent`` propagation (`current_traceparent` / `continue_trace`)
  so one request shows up as a single tree spanning
  client → sidecar gateway → RSM → storage backend;
- every span and event of an enabled tracer is also a
  ``jax.profiler.TraceAnnotation``, in a process that has imported ``jax``
  already: a profiler session that happens to be open gets the program's
  spans in the same trace as the device plane, on the profiler's clock
  (``tools/profile_report.py`` checks one against the other); outside a
  session an annotation costs nothing measurable, and a process without
  ``jax`` (a client-side tracer) never imports it for this;
- spans with given times (`Tracer.record`) for what was seen from outside:
  the device's time on each launched window (`device.window`, from the TPU
  backend's device watch), from which `summary()` puts every second the
  device idled down to the host span that held it (`device_idle_s`);
- a bounded ring-buffer recorder (newest spans win; evictions are counted in
  `dropped_spans`) with per-name p50/p95/p99 summaries and a Chrome
  trace-event JSON exporter (loadable in Perfetto / ``chrome://tracing``,
  interleavable with `jax.profiler` device timelines).

Usage:
    tracer = Tracer(enabled=True)
    with tracer.span("copy_log_segment_data", topic="t", partition=3):
        with tracer.span("transform"):
            ...
    tracer.write_chrome_trace("artifacts/trace.json")
"""

from __future__ import annotations

import collections
import contextlib
import dataclasses
import itertools
import json
import math
import os
import pathlib
import sys
import threading
import time
from typing import Iterator, Optional
from tieredstorage_tpu.utils.locks import new_lock

#: Header/metadata key carrying W3C trace context across process boundaries.
TRACEPARENT_HEADER = "traceparent"

_TRACEPARENT_VERSION = "00"
_HEX = set("0123456789abcdef")


#: Odd, so that `seed + n * STRIDE` visits every value of its width once
#: (2**64 and 2**128 over the golden ratio).
_SPAN_ID_STRIDE = 0x9E3779B97F4A7C15
_TRACE_ID_STRIDE = 0x9E3779B97F4A7C15F39CC0605CEDC835

#: The device's side of the ring (transform/device_watch.py): one span per
#: launched window, and the row of `summary()` for idle time nobody held.
DEVICE_WINDOW = "device.window"
DEVICE_UNCLAIMED = "device.unclaimed"
#: The watch's event at the moment it saw a window ready: a stamp, not a span
#: that can hold idle time.
DEVICE_READY = "device.ready"


class _Ids:
    """Span and trace ids of one tracer: one `os.urandom` seed, then a
    counter, so that a span costs no system call. Unique within the tracer
    (a stride that is odd walks the whole ring of values before it repeats),
    never all zero, and as hard to guess across tracers as their seeds."""

    def __init__(self) -> None:
        seed = int.from_bytes(os.urandom(24), "big")
        self._span_seed, self._trace_seed = seed >> 128, seed & ((1 << 128) - 1)
        self._next = itertools.count(1).__next__  # atomic under the interpreter lock

    def span_id(self) -> str:
        value = 0
        while not value:
            value = (self._span_seed + self._next() * _SPAN_ID_STRIDE) & ((1 << 64) - 1)
        return f"{value:016x}"

    def trace_id(self) -> str:
        value = 0
        while not value:
            value = (self._trace_seed + self._next() * _TRACE_ID_STRIDE) & ((1 << 128) - 1)
        return f"{value:032x}"


def format_traceparent(trace_id: str, span_id: str) -> str:
    """W3C trace-context header value (always sampled: this tracer records
    everything it is enabled for)."""
    return f"{_TRACEPARENT_VERSION}-{trace_id}-{span_id}-01"


def parse_traceparent(header: Optional[str]) -> Optional[tuple[str, str]]:
    """(trace_id, parent_span_id) from a ``traceparent`` value, or None.

    Lenient per the W3C spec: unknown versions are accepted as long as the
    00-version prefix fields parse; malformed values are ignored (tracing
    must never fail a request)."""
    if not header:
        return None
    parts = header.strip().lower().split("-")
    if len(parts) < 4:
        return None
    version, trace_id, span_id = parts[0], parts[1], parts[2]
    if len(version) != 2 or not set(version) <= _HEX or version == "ff":
        return None
    if len(trace_id) != 32 or not set(trace_id) <= _HEX or trace_id == "0" * 32:
        return None
    if len(span_id) != 16 or not set(span_id) <= _HEX or span_id == "0" * 16:
        return None
    return trace_id, span_id


def _annotation(name: str):
    """A ``jax.profiler.TraceAnnotation`` for ``name``, or None in a process
    that has not imported ``jax`` (tracing never imports it, and never fails
    a request over it)."""
    jax = sys.modules.get("jax")
    if jax is None:
        return None
    try:
        return jax.profiler.TraceAnnotation(name)
    except Exception:  # noqa: BLE001 — e.g. jax still half-imported
        return None


def _self_seconds(span: "Span", children: list) -> float:
    """``span``'s duration less the part its children cover. Children of
    pipelined windows overlap, and one adopted across threads may outlive
    its parent: they are clipped to the span and merged before they are
    subtracted, so the result is never negative."""
    covered, reach = 0.0, span.start_s
    for start, end in sorted(children):
        start, end = max(start, reach), min(end, span.end_s)
        if end > start:
            covered += end - start
            reach = end
    return max(0.0, span.duration_s - covered)


@dataclasses.dataclass
class Span:
    name: str
    start_s: float
    end_s: float = 0.0
    depth: int = 0
    attributes: dict = dataclasses.field(default_factory=dict)
    trace_id: str = ""
    span_id: str = ""
    parent_id: Optional[str] = None
    thread_id: int = 0

    @property
    def duration_s(self) -> float:
        return max(0.0, self.end_s - self.start_s)


def _percentile(sorted_durations: list[float], q: float) -> float:
    """Nearest-rank percentile over an ascending-sorted NON-EMPTY list.

    Part of the degenerate-case contract (ISSUE 14): an empty sample set
    has NO percentile — callers must not see a fabricated 0.0 — so the
    empty list is a programming error here (``summary()`` never builds an
    entry without at least one span). A single sample is every percentile
    of itself (nearest rank: rank 1 of 1)."""
    if not sorted_durations:
        raise ValueError("percentile of an empty sample set is undefined")
    rank = max(1, math.ceil(q * len(sorted_durations)))
    return sorted_durations[min(rank, len(sorted_durations)) - 1]


def merge(intervals: list, bridge=0) -> list:
    """Sorted, disjoint intervals covering the same points, and the gaps no
    longer than `bridge` between them."""
    merged: list = []
    for start, end in sorted(intervals):
        if merged and start <= merged[-1][1] + bridge:
            merged[-1][1] = max(merged[-1][1], end)
        else:
            merged.append([start, end])
    return merged


def label_gaps(gaps: list, spans: list) -> dict:
    """Why the device waited: each gap of its timeline put down, piece by
    piece, to the span that held it. `gaps` are disjoint `(start, end)` or
    `(start, end, thread)`, the thread being the one that launched the window
    which ended the gap; `spans` are `(start, end, name)` or `(start, end,
    name, thread)` of every host thread; any one unit of time throughout.

    A gap is cut at every span edge inside it, and each piece goes to (i) the
    innermost (shortest) span that covers it on the gap's thread: the thread
    the device was waiting for, and what it was doing; else (ii) the innermost
    span that covers it on any thread: a second between two copies is then the
    next copy's spool, decode and first context build, not one `gateway.copy`,
    and the tail of a copy lies with its handler before the next copy's
    thread exists; else (iii) nobody in the program wanted the device.
    Returns `by_span` (time by span name) and `uncovered` (the pieces of
    iii, each one's length)."""
    ordered = sorted((s for s in spans if s[1] > s[0]), key=lambda s: s[0])
    by_span: dict = collections.defaultdict(int)
    uncovered: list = []
    live: list = []
    following = 0
    for gap in sorted(gaps, key=lambda g: g[0]):
        gap_start, gap_end = gap[0], gap[1]
        thread = gap[2] if len(gap) > 2 else None
        while following < len(ordered) and ordered[following][0] < gap_end:
            live.append(ordered[following])
            following += 1
        live = [s for s in live if s[1] > gap_start]  # gaps come in order: gone for good
        cuts = sorted({
            gap_start, gap_end,
            *(t for s in live for t in s[:2] if gap_start < t < gap_end),
        })
        for piece_start, piece_end in zip(cuts, cuts[1:]):
            covering = [s for s in live if s[0] <= piece_start and s[1] >= piece_end]
            launcher = [
                s for s in covering if thread is not None and len(s) > 3 and s[3] == thread
            ]
            if covering:
                held = min((s[1] - s[0], s[2]) for s in launcher or covering)
                by_span[held[1]] += piece_end - piece_start
            else:
                uncovered.append(piece_end - piece_start)
    return {"by_span": by_span, "uncovered": uncovered}


def device_idle(spans: list, since_s: float = float("-inf")) -> Optional[dict]:
    """`label_gaps` over a ring that holds the device's windows: the gaps are
    what the merged `device.window` spans leave of the stretch from the first
    span's start to the last span's end; the one before a window is the
    launching thread's (the thread of the window's `transform.launch`, its
    parent), the one after the last window nobody's. The stretch begins no
    earlier than `since_s`, the moment the ring was last cleared: a span that
    was open then lands in the ring later with its whole length, and what
    the device did before the clear is not in the ring to hold against it.
    None where the ring has no `device.window`."""
    windows = sorted(
        (s for s in spans if s.name == DEVICE_WINDOW), key=lambda s: s.start_s
    )
    if not windows:
        return None
    first = max(min(s.start_s for s in spans), since_s)
    last = max(s.end_s for s in spans)
    by_id = {s.span_id: s for s in spans}
    launched_by: dict = {}
    for window in reversed(windows):  # the earliest window of a start wins
        launch = by_id.get(window.parent_id)
        launched_by[window.start_s] = None if launch is None else launch.thread_id
    busy = [
        interval for interval in merge([(w.start_s, w.end_s) for w in windows])
        if interval[1] > first
    ]
    edges = [first, *(t for interval in busy for t in interval), last]
    threads = [*(launched_by[interval[0]] for interval in busy), None]
    gaps = [
        (start, end, thread)
        for start, end, thread in zip(edges[0::2], edges[1::2], threads)
        if end > start
    ]
    return label_gaps(
        gaps, [(s.start_s, s.end_s, s.name, s.thread_id) for s in spans]
    )


class Tracer:
    """Nested span recorder; thread-safe, cheap when disabled.

    Spans recorded while another span is active on the same thread (or while
    a remote context installed by `continue_trace` is active) are parented
    under it and share its `trace_id`; otherwise a span starts a new trace.
    The recorder is a ring buffer: once `max_spans` is reached the OLDEST
    span is evicted (and counted in `dropped_spans`), so long soak runs keep
    the newest spans instead of silently freezing the recorder."""

    def __init__(self, enabled: bool = False, *, max_spans: int = 10_000):
        self.enabled = enabled
        self.max_spans = max_spans
        self._spans: collections.deque[Span] = collections.deque(maxlen=max_spans)
        #: Spans evicted from the ring buffer (exported as a counter metric).
        self.dropped_spans = 0
        self._lock = new_lock("tracing.Tracer._lock")
        self._local = threading.local()
        self._ids = _Ids()
        # Pinned once so Chrome-trace timestamps from several tracers in one
        # process (client + sidecar in tests/demos) land on one shared
        # timeline. Monotonic, not wall clock: Perfetto only needs a
        # consistent epoch, and an NTP step mid-run would skew span starts
        # against their perf_counter-measured durations.
        self._epoch_perf = time.perf_counter()
        self._epoch_mono = time.monotonic()
        #: When the ring was last cleared: idle time is attributed from here.
        self._cleared_s = float("-inf")

    # ---------------------------------------------------------------- context
    def _stack(self) -> list[Span]:
        stack = getattr(self._local, "stack", None)
        if stack is None:
            stack = self._local.stack = []
        return stack

    def _parent_context(self) -> tuple[str, Optional[str]]:
        """(trace_id, parent_span_id) for a new span on this thread."""
        stack = self._stack()
        if stack:
            return stack[-1].trace_id, stack[-1].span_id
        remote = getattr(self._local, "remote", None)
        if remote is not None:
            return remote
        return self._ids.trace_id(), None

    def current_traceparent(self) -> Optional[str]:
        """``traceparent`` value for the active context, for injection into
        outgoing HTTP headers; None when there is nothing to
        propagate (tracing disabled or no active span)."""
        if not self.enabled:
            return None
        stack = self._stack()
        if stack:
            return format_traceparent(stack[-1].trace_id, stack[-1].span_id)
        remote = getattr(self._local, "remote", None)
        if remote is not None:
            return format_traceparent(remote[0], remote[1])
        return None

    @contextlib.contextmanager
    def continue_trace(self, traceparent: Optional[str]) -> Iterator[None]:
        """Adopt a remote parent context for the duration of the block: spans
        opened inside join the caller's trace instead of starting a new one.
        Malformed/absent headers degrade to a no-op (new root trace)."""
        parsed = parse_traceparent(traceparent) if self.enabled else None
        if parsed is None:
            yield
            return
        prior = getattr(self._local, "remote", None)
        self._local.remote = parsed
        try:
            yield
        finally:
            self._local.remote = prior

    # ---------------------------------------------------------------- record
    def _record(self, span: Span) -> None:
        with self._lock:
            if len(self._spans) == self._spans.maxlen:
                self.dropped_spans += 1
            self._spans.append(span)

    @contextlib.contextmanager
    def span(self, name: str, **attributes) -> Iterator[Optional[Span]]:
        if not self.enabled:
            yield None
            return
        stack = self._stack()
        trace_id, parent_id = self._parent_context()
        s = Span(
            name=name, start_s=time.perf_counter(), depth=len(stack),
            attributes=attributes, trace_id=trace_id, span_id=self._ids.span_id(),
            parent_id=parent_id, thread_id=threading.get_ident(),
        )
        stack.append(s)
        ctx = _annotation(name)
        if ctx is not None:
            ctx.__enter__()
        try:
            yield s
        finally:
            if ctx is not None:
                ctx.__exit__(None, None, None)
            s.end_s = time.perf_counter()
            stack.pop()
            self._record(s)

    def event(self, name: str, **attributes) -> Optional[Span]:
        """Record an instantaneous (zero-duration) span — state transitions
        like circuit-breaker trips or upload rollbacks that have no useful
        extent but must show up on the timeline."""
        if not self.enabled:
            return None
        now = time.perf_counter()
        trace_id, parent_id = self._parent_context()
        s = Span(
            name=name, start_s=now, end_s=now, depth=len(self._stack()),
            attributes=attributes, trace_id=trace_id, span_id=self._ids.span_id(),
            parent_id=parent_id, thread_id=threading.get_ident(),
        )
        # Zero-duration annotation: timeline parity with span(), so events
        # land in a profile next to the kernels they interleave with.
        ctx = _annotation(name)
        if ctx is not None:
            with ctx:
                pass
        self._record(s)
        return s

    def record(
        self, name: str, start_s: float, end_s: float, *,
        parent: Optional[Span] = None, **attributes,
    ) -> Optional[Span]:
        """Record a span whose times are given (`time.perf_counter()`
        readings): something that was seen from outside and not lived
        through, such as the device's time on a window, or the two halves of
        a wait that only its end can tell apart. Child of `parent` (a span of
        any thread, open or recorded), else of the calling thread's context
        as `event` is. It was on nobody's stack, has no annotation in a
        profiler session (that has no given times), and meets the ring and
        `dropped_spans` as any span does. None with tracing off."""
        if not self.enabled:
            return None
        if parent is not None:
            trace_id, parent_id, depth = parent.trace_id, parent.span_id, parent.depth + 1
        else:
            (trace_id, parent_id), depth = self._parent_context(), len(self._stack())
        s = Span(
            name=name, start_s=start_s, end_s=end_s, depth=depth,
            attributes=attributes, trace_id=trace_id, span_id=self._ids.span_id(),
            parent_id=parent_id, thread_id=threading.get_ident(),
        )
        self._record(s)
        return s

    def extend(self, span: Optional[Span], **attributes) -> None:
        """Move a recorded span's end to now (and add `attributes`): for work
        that outlives the block that opened the span, such as a streamed
        body read after the call that returned it. The span keeps its place
        in the tree and is off every thread's stack, so it may be extended
        from any thread and any number of times; None (tracing off) is a
        no-op."""
        if span is None:
            return
        span.attributes.update(attributes)
        span.end_s = time.perf_counter()

    # --------------------------------------------------------------- readers
    def spans(self, name: Optional[str] = None) -> list[Span]:
        with self._lock:
            out = list(self._spans)
        if name is not None:
            out = [s for s in out if s.name == name]
        return out

    @property
    def recorded_spans(self) -> int:
        with self._lock:
            return len(self._spans)

    def clear(self) -> None:
        with self._lock:
            self._spans.clear()
            self.dropped_spans = 0
            self._cleared_s = time.perf_counter()

    def summary(self) -> dict[str, dict[str, float]]:
        """Per-name count/total/avg/max plus p50/p95/p99 durations (seconds),
        and ``self_s``: the name's total less what its spans' children
        (found by ``parent_id`` among the recorded spans) cover.

        Degenerate-case contract (ISSUE 14): no recorded spans means an
        EMPTY dict — a name never appears with fabricated zero percentiles,
        so consumers (``/varz``, the SLO engine's evidence path) can treat
        "absent" as "no data" without a sentinel check. A name with exactly
        one span reports that span's duration as count=1, avg, max, and
        every percentile (nearest-rank: one sample is every quantile of
        itself).

        Where the ring holds `device.window` spans (a TPU backend's device
        watch, transform/device_watch.py) every row also has
        ``device_idle_s``: the seconds the device ran no window while that
        name's spans held it (`device_idle`, `label_gaps`), and the pieces
        that no span held are the row ``device.unclaimed`` (``count`` of
        them, ``total_s`` = ``self_s`` = ``device_idle_s``, the percentiles
        over the pieces; absent where there is none). The rows'
        ``device_idle_s`` and the merged windows add up to the ring's
        stretch: first start, or the last ``clear()`` where a span that was
        open then reaches back before it, to last end. Without such a span
        no row has the field."""
        spans = self.spans()
        children: dict[str, list[tuple[float, float]]] = {}
        for s in spans:
            if s.parent_id is not None:
                children.setdefault(s.parent_id, []).append((s.start_s, s.end_s))
        agg: dict[str, list[float]] = {}
        self_s: dict[str, float] = collections.defaultdict(float)
        for s in spans:
            agg.setdefault(s.name, []).append(s.duration_s)
            self_s[s.name] += _self_seconds(s, children.get(s.span_id, ()))
        idle = device_idle(spans, self._cleared_s)
        if idle is not None and idle["uncovered"]:
            agg[DEVICE_UNCLAIMED] = idle["uncovered"]
            self_s[DEVICE_UNCLAIMED] = idle["by_span"][DEVICE_UNCLAIMED] = sum(idle["uncovered"])
        out: dict[str, dict[str, float]] = {}
        for name, ds in agg.items():
            ds.sort()
            out[name] = {
                "count": len(ds),
                "total_s": sum(ds),
                "self_s": self_s[name],
                "avg_s": sum(ds) / len(ds),
                "max_s": ds[-1],
                "p50_s": _percentile(ds, 0.50),
                "p95_s": _percentile(ds, 0.95),
                "p99_s": _percentile(ds, 0.99),
            }
            if idle is not None:
                out[name]["device_idle_s"] = float(idle["by_span"].get(name, 0.0))
        return out

    # ---------------------------------------------------------------- export
    def _ts_us(self, perf_s: float) -> float:
        return (self._epoch_mono + (perf_s - self._epoch_perf)) * 1e6

    def chrome_trace_events(self) -> list[dict]:
        """Spans as Chrome trace-event dicts: complete events (``ph: "X"``)
        for timed spans, instant events (``ph: "i"``) for zero-duration
        events; `args` carries the span identity so trees survive the export."""
        events: list[dict] = []
        pid = os.getpid()
        for s in self.spans():
            args = {
                "trace_id": s.trace_id,
                "span_id": s.span_id,
                "parent_id": s.parent_id,
                **{k: str(v) for k, v in s.attributes.items()},
            }
            base = {
                "name": s.name,
                "cat": "tieredstorage",
                "ts": self._ts_us(s.start_s),
                "pid": pid,
                "tid": s.thread_id,
                "args": args,
            }
            if s.duration_s > 0.0:
                events.append({**base, "ph": "X", "dur": s.duration_s * 1e6})
            else:
                events.append({**base, "ph": "i", "s": "t"})
        return events

    def export_chrome_trace(self) -> dict:
        """JSON-object-format Chrome trace (Perfetto / ``chrome://tracing``)."""
        return {
            "traceEvents": self.chrome_trace_events(),
            "displayTimeUnit": "ms",
            "otherData": {"dropped_spans": self.dropped_spans},
        }

    def write_chrome_trace(self, path) -> pathlib.Path:
        out = pathlib.Path(path)
        out.parent.mkdir(parents=True, exist_ok=True)
        out.write_text(json.dumps(self.export_chrome_trace(), indent=1))
        return out


#: Process-wide default tracer; RSM wires it from `tracing.enabled` config.
NOOP_TRACER = Tracer(enabled=False)
