"""Why the chip waits (ISSUE 37): the device watch's span per launched window,
`transform.d2h_wait` split at the moment the result was ready, and every idle
second of the device put down to the host span that held it.

One copy and a lagging reader's fetches go through the HTTP gateway of an RSM
on the TPU backend (CPU forms of the kernels), as in `test_window_spans.py`;
the attribution itself is held to synthetic spans whose answer is known.
"""

from __future__ import annotations

import os
import pathlib
import sys
import threading
import time

import pytest

from tests.test_rsm_lifecycle import make_rsm, make_segment_metadata
from tests.test_window_spans import (
    CHUNK, COPY_WINDOWS, SEGMENT, WINDOW_CHUNKS, _closed_spans, _named, _parent_names, _post,
)
from tieredstorage_tpu.sidecar import shimwire
from tieredstorage_tpu.sidecar.http_gateway import SidecarHttpGateway
from tieredstorage_tpu.transform.device_watch import DeviceWatch
from tieredstorage_tpu.utils import tracing
from tieredstorage_tpu.utils.tracing import Span, Tracer, device_idle, label_gaps, parse_traceparent

pytest.importorskip("cryptography")

FETCH_WINDOWS = 2  # the first read decrypts, the second decrypts again and admits


def _watch_threads() -> list:
    return [t for t in threading.enumerate() if t.name == "device-watch"]


def _serve(tmp_path, traced: bool) -> dict:
    """One copy, then three reads of its first chunk; what the tracer, the
    backend's counts and the process's threads looked like at each step."""
    rsm, _ = make_rsm(
        tmp_path, compression=False, encryption=True, chunk_size=CHUNK,
        extra_configs={
            "tracing.enabled": traced,
            "transform.backend.class":
                "tieredstorage_tpu.transform.tpu.TpuTransformBackend",
            "transform.batch.chunks": WINDOW_CHUNKS,
            "cache.device.bytes": 64 << 20,
        },
    )
    backend = rsm.transform_backend
    gateway = SidecarHttpGateway(rsm).start()
    md = make_segment_metadata()
    segment = os.urandom(SEGMENT)
    sections = {
        "log_segment": segment, "offset_index": os.urandom(800),
        "time_index": os.urandom(1200), "producer_snapshot": os.urandom(96),
        "transaction_index": None, "leader_epoch_index": b"0\n1\n0 0\n",
    }
    body = shimwire.encode_metadata(md) + shimwire.encode_sections(sections)
    out: dict = {}
    try:
        status, custom = _post(gateway.port, "/v1/copy", body)
        assert status in (200, 204)
        if custom:
            md = md.with_custom_metadata(custom)
        if traced:
            _closed_spans(rsm.tracer, "gateway.copy", 1)
            assert backend.device_watch.settle(60)
            out["copy"] = rsm.tracer.spans()
            out["copy_summary"] = rsm.tracer.summary()
            out["copy_seen_ns"] = backend.dispatch_stats.device_seen_ns
            rsm.tracer.clear()
        fetch_body = shimwire.encode_metadata(md) + shimwire.encode_fetch_tail(0, CHUNK - 1)
        replies = [_post(gateway.port, "/v1/fetch", fetch_body) for _ in range(3)]
        assert [r[1] for r in replies] == [segment[:CHUNK]] * 3
        if traced:
            _closed_spans(rsm.tracer, "gateway.fetch", 3)
            assert backend.device_watch.settle(60)
            out["fetch"] = rsm.tracer.spans()
        out["dropped"] = rsm.tracer.dropped_spans
        out["watch_threads_while_serving"] = len(_watch_threads())
        out["watch"] = backend.device_watch
        out["varz_dispatch"] = backend.dispatch_counts()
    finally:
        gateway.stop()
        rsm.close()
    out["watch_after_close"] = backend.device_watch
    out["watch_threads_after_close"] = len(_watch_threads())
    return out


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    return _serve(tmp_path_factory.mktemp("device-flight"), traced=True)


@pytest.fixture(scope="module")
def untraced(tmp_path_factory):
    return _serve(tmp_path_factory.mktemp("device-flight-off"), traced=False)


# ------------------------------------------------- the flight record: device.window
@pytest.mark.parametrize("side,windows,decrypt", [
    ("copy", COPY_WINDOWS, False), ("fetch", FETCH_WINDOWS, True),
])
def test_one_device_window_per_launch_child_of_it(served, side, windows, decrypt):
    spans = served[side]
    assert len(_named(spans, "transform.launch")) == windows
    assert _parent_names(spans, "device.window") == {"transform.launch": windows}
    launches = {s.span_id: s for s in _named(spans, "transform.launch")}
    seen = _named(spans, "device.window")
    assert {w.parent_id for w in seen} == set(launches)  # each launch its own
    for window in seen:
        launch = launches[window.parent_id]
        assert window.trace_id == launch.trace_id and window.depth == launch.depth + 1
        assert window.end_s >= window.start_s >= launch.start_s
        assert window.attributes["decrypt"] is decrypt
        assert window.attributes["rows"] >= 1 and window.attributes["bytes"] > 0
        assert isinstance(window.attributes["varlen"], bool)
    assert len(_named(spans, "device.ready")) == windows  # the stamp, as an event


@pytest.mark.parametrize("side", ["copy", "fetch"])
def test_no_two_device_windows_overlap(served, side):
    seen = sorted(_named(served[side], "device.window"), key=lambda s: s.start_s)
    for earlier, later in zip(seen, seen[1:]):
        assert later.start_s >= earlier.end_s
    assert served["dropped"] == 0


def test_device_seen_ns_is_what_the_spans_add_up_to(served):
    spans_ns = sum(
        round(w.duration_s * 1e9) for w in _named(served["copy"], "device.window")
    )
    assert served["copy_seen_ns"] == spans_ns > 0
    assert served["varz_dispatch"]["device_seen_ns"] >= served["copy_seen_ns"]  # on /varz


# ---------------------------------------------------- transform.d2h_wait, split
@pytest.mark.parametrize("side,windows", [("copy", COPY_WINDOWS), ("fetch", FETCH_WINDOWS)])
def test_ready_wait_and_collect_tile_their_wait(served, side, windows):
    spans = served[side]
    waits = _named(spans, "transform.d2h_wait")
    assert len(waits) == windows
    assert _parent_names(spans, "transform.collect") == {"transform.d2h_wait": windows}
    for wait in waits:
        halves = sorted(
            (s for s in spans if s.parent_id == wait.span_id), key=lambda s: s.start_s
        )
        assert [s.name for s in halves] in (
            ["transform.ready_wait", "transform.collect"], ["transform.collect"],
        )
        assert halves[0].start_s == wait.start_s and halves[-1].end_s == wait.end_s
        if len(halves) == 2:
            assert halves[0].end_s == halves[1].start_s > wait.start_s
        assert all(s.thread_id == wait.thread_id for s in halves)


def test_a_window_ends_no_later_than_its_own_finish(served):
    """One ready stamp a window, set by whoever saw readiness first: the
    finisher's `collect` starts at it, and the device's span ends at it."""
    spans = served["copy"]
    collects = sorted(_named(spans, "transform.collect"), key=lambda s: s.end_s)
    seen = sorted(_named(spans, "device.window"), key=lambda s: s.end_s)
    assert len(collects) == len(seen) == COPY_WINDOWS
    for window, collect in zip(seen, collects):
        assert window.end_s <= collect.end_s


# --------------------------------------------------------------- tracing off
def test_with_tracing_off_there_is_no_watch_and_nothing_is_seen(untraced):
    assert untraced["watch"] is None and untraced["watch_threads_while_serving"] == 0
    assert untraced["varz_dispatch"]["device_seen_ns"] == 0
    assert untraced["varz_dispatch"]["windows"] == COPY_WINDOWS + FETCH_WINDOWS


def test_close_joins_the_watch(served):
    assert served["watch_threads_while_serving"] == 1
    assert served["watch"].is_alive() is False
    assert served["watch_after_close"] is None and served["watch_threads_after_close"] == 0


def test_a_late_watch_takes_the_finishers_stamp_for_every_earlier_window():
    """The finisher of the second window saw its result before the watch was
    woken for the first: both were ready by then, the device ran them in
    order, so neither span may end after that finish."""
    import jax.numpy as jnp

    tracer = Tracer(enabled=True)
    seen_ns: list = []
    watch = DeviceWatch(tracer, seen_ns.append)
    gate = threading.Event()
    real_event = tracer.event
    # hold the watch thread after its first window was ready, before it stamps
    tracer.event = lambda name, **kw: (gate.wait(30), real_event(name, **kw))[1]
    try:
        first, second = jnp.zeros(8), jnp.ones(8)
        with tracer.span("transform.launch") as launch_1:
            watch.watch(first, launch_1, rows=1)
        with tracer.span("transform.launch") as launch_2:
            watch.watch(second, launch_2, rows=1)
        finished_s = time.perf_counter()
        assert watch.ready_by(second, finished_s) == finished_s
        time.sleep(0.02)
        gate.set()
        assert watch.settle(30)
        # the first window's finisher comes last and is given the stamp too
        assert watch.ready_by(first, time.perf_counter()) == finished_s
        assert watch.ready_by(first, time.perf_counter()) > finished_s  # asked twice: its own end
    finally:
        gate.set()
        watch.stop()
    one, two = sorted(tracer.spans("device.window"), key=lambda s: s.start_s)
    assert (one.parent_id, two.parent_id) == (launch_1.span_id, launch_2.span_id)
    assert one.end_s == two.start_s == two.end_s == finished_s
    assert sum(seen_ns) == round(one.duration_s * 1e9)
    assert not watch.is_alive()


# ------------------------------------------------------------- Tracer.record
class TestRecord:
    def test_given_times_and_parent_from_any_thread(self):
        tracer = Tracer(enabled=True)
        with tracer.span("transform.launch") as launch:
            pass
        made: list = []
        thread = threading.Thread(
            target=lambda: made.append(
                tracer.record("device.window", 1.5, 4.0, parent=launch, rows=16)
            )
        )
        thread.start()
        thread.join(30)
        (span,) = made
        assert (span.start_s, span.end_s, span.duration_s) == (1.5, 4.0, 2.5)
        assert (span.trace_id, span.parent_id) == (launch.trace_id, launch.span_id)
        assert span.depth == launch.depth + 1 and span.attributes == {"rows": 16}
        assert span.thread_id == thread.ident != launch.thread_id
        assert tracer.spans("device.window") == [span]

    def test_without_a_parent_it_joins_the_threads_context(self):
        tracer = Tracer(enabled=True)
        with tracer.span("outer") as outer:
            inner = tracer.record("inner", 0.0, 1.0)
        alone = tracer.record("alone", 0.0, 1.0)
        assert (inner.trace_id, inner.parent_id, inner.depth) == (outer.trace_id, outer.span_id, 1)
        assert alone.parent_id is None and alone.trace_id != outer.trace_id
        assert tracer._stack() == []  # never on anyone's stack

    def test_off_and_the_ring(self):
        assert Tracer(enabled=False).record("x", 0.0, 1.0) is None
        tracer = Tracer(enabled=True, max_spans=2)
        for i in range(3):
            tracer.record("x", float(i), i + 1.0)
        assert [s.start_s for s in tracer.spans()] == [1.0, 2.0]
        assert tracer.dropped_spans == 1


# -------------------------------------------------- ids without a system call
def test_ids_are_unique_well_formed_and_cost_no_urandom(monkeypatch):
    tracer = Tracer(enabled=True)  # its one seed is drawn here
    monkeypatch.setattr(
        tracing.os, "urandom", lambda n: pytest.fail("a span made a system call for its id")
    )
    for _ in range(2000):
        with tracer.span("root"):
            tracer.event("leaf")
    spans = tracer.spans()
    assert len({s.span_id for s in spans}) == len(spans) == 4000
    assert len({s.trace_id for s in spans}) == 2000
    for s in spans[:50]:
        assert parse_traceparent(tracing.format_traceparent(s.trace_id, s.span_id)) == (
            s.trace_id, s.span_id
        )


def test_two_tracers_do_not_share_ids():
    one, two = Tracer(enabled=True), Tracer(enabled=True)
    ids = {t.event("e").span_id for t in (one, two) for _ in range(100)}
    assert len(ids) == 200


# -------------------------------------------------- the attribution, synthetic
def _span(name, start, end, thread=1, span_id=None, parent_id=None) -> Span:
    return Span(
        name=name, start_s=start, end_s=end, thread_id=thread,
        span_id=span_id or f"{name}@{start}", parent_id=parent_id, trace_id="t",
    )


def _launched(at, until, thread, ready) -> list:
    """A launch on `thread` and the device's window under it."""
    launch = _span("transform.launch", at, until, thread, span_id=f"launch@{at}")
    return [launch, _span("device.window", at, ready, 99, parent_id=launch.span_id)]


def _ring(tracer: Tracer, spans: list) -> Tracer:
    for span in spans:
        tracer._record(span)
    return tracer


class TestIdleAttribution:
    def test_the_launchers_thread_wins_over_a_shorter_span_elsewhere(self):
        # the device idles 10-20 until thread 1 launches; thread 1 was packing
        # (a long span), thread 2 did something short at the same time
        spans = [
            *_launched(0, 1, 1, 10), *_launched(20, 21, 1, 30),
            _span("gateway.copy", 0, 30, 1), _span("transform.pack", 10, 20, 1),
            _span("hot.admit", 12, 14, 2),
        ]
        idle = device_idle(spans)
        assert dict(idle["by_span"]) == {"transform.pack": 10}
        assert idle["uncovered"] == []

    def test_rule_two_the_innermost_span_of_any_thread(self):
        # thread 3 launches at 20 but has no span open before 18: the tail of
        # the earlier request, on its handler's thread, holds the idle time
        spans = [
            *_launched(0, 1, 1, 10), *_launched(20, 21, 3, 30),
            _span("gateway.copy", 0, 18, 1), _span("storage.upload", 10, 16, 1),
            _span("gateway.copy", 18, 30, 3),
        ]
        idle = device_idle(spans)
        assert dict(idle["by_span"]) == {"storage.upload": 6, "gateway.copy": 4}

    def test_what_no_span_covers_is_unclaimed(self):
        spans = [
            *_launched(0, 1, 1, 10), *_launched(20, 21, 1, 30),
            _span("gateway.fetch", 0, 12, 1), _span("gateway.fetch", 19, 30, 1),
        ]
        idle = device_idle(spans)
        assert dict(idle["by_span"]) == {"gateway.fetch": 3}
        assert idle["uncovered"] == [7]  # 12-19: the client's own time

    def test_the_gap_after_the_last_window_has_no_launcher(self):
        spans = [
            *_launched(0, 1, 1, 10),
            _span("gateway.copy", 0, 25, 1), _span("storage.upload", 10, 22, 2),
            _span("rsm.upload.manifest", 22, 24, 2),
        ]
        idle = device_idle(spans)
        # innermost of any thread: thread 1's gateway.copy only where nothing is shorter
        assert dict(idle["by_span"]) == {
            "storage.upload": 12, "rsm.upload.manifest": 2, "gateway.copy": 1,
        }

    def test_the_gap_before_the_first_window_is_its_launchers(self):
        spans = [
            _span("gateway.copy", 0, 30, 1), _span("gateway.spool", 0, 4, 1),
            _span("transform.context", 4, 5, 1), _span("hot.evict", 1, 2, 2),
            *_launched(5, 6, 1, 30),
        ]
        assert dict(device_idle(spans)["by_span"]) == {
            "gateway.spool": 4, "transform.context": 1,
        }

    def test_overlapping_windows_merge_and_events_hold_nothing(self):
        spans = [
            *_launched(0, 1, 1, 10), *_launched(8, 9, 1, 14), *_launched(20, 21, 1, 22),
            _span("gateway.copy", 0, 22, 1), _span("device.ready", 15, 15, 99),
        ]
        idle = device_idle(spans)
        assert dict(idle["by_span"]) == {"gateway.copy": 6} and idle["uncovered"] == []

    @pytest.mark.parametrize("seed", [1, 2, 3])
    def test_idle_and_busy_add_up_to_the_rings_stretch(self, seed):
        import random

        rng = random.Random(seed)
        spans, at = [], 0.0
        for _ in range(40):
            at += rng.uniform(0.0, 3.0)
            thread = rng.randrange(1, 4)
            spans += _launched(at, at + 0.1, thread, at + rng.uniform(0.2, 2.5))
            if rng.random() < 0.7:
                spans.append(_span("gateway.fetch", at - rng.uniform(0, 2), at + 3, thread))
            if rng.random() < 0.5:
                spans.append(_span("hot.admit", at + 1, at + rng.uniform(1.1, 4), 7))
        summary = _ring(Tracer(enabled=True), spans).summary()
        busy = sum(
            end - start
            for start, end in tracing.merge(
                [(s.start_s, s.end_s) for s in spans if s.name == "device.window"]
            )
        )
        stretch = max(s.end_s for s in spans) - min(s.start_s for s in spans)
        idle = sum(row["device_idle_s"] for row in summary.values())
        assert idle + busy == pytest.approx(stretch, abs=1e-9)
        assert summary["device.window"]["device_idle_s"] == 0.0

    def test_the_summary_rows_and_the_unclaimed_row(self):
        spans = [
            *_launched(0, 1, 1, 10), *_launched(20, 21, 1, 30), *_launched(40, 41, 1, 42),
            _span("gateway.fetch", 0, 12, 1), _span("gateway.fetch", 19, 31, 1),
            _span("gateway.fetch", 39, 42, 1),
        ]
        summary = _ring(Tracer(enabled=True), spans).summary()
        assert all("device_idle_s" in row for row in summary.values())
        assert summary["gateway.fetch"]["device_idle_s"] == pytest.approx(5.0)
        assert summary["transform.launch"]["device_idle_s"] == 0.0
        unclaimed = summary["device.unclaimed"]  # 12-19 and 31-39
        assert unclaimed == {
            "count": 2, "total_s": 15.0, "self_s": 15.0, "avg_s": 7.5, "max_s": 8.0,
            "p50_s": 7.0, "p95_s": 8.0, "p99_s": 8.0, "device_idle_s": 15.0,
        }
        assert all(isinstance(v, (int, float)) for row in summary.values() for v in row.values())

    def test_a_span_open_at_the_clear_holds_nothing_from_before_it(self):
        """A warm-up's `gateway.copy` closes after its reply is written: it
        lands in a ring that was cleared meanwhile, and reaches back over a
        set-up whose windows are gone."""
        tracer = Tracer(enabled=True)
        tracer.clear()
        cleared = tracer._cleared_s
        spans = [
            _span("gateway.copy", cleared - 40, cleared + 1, 1),  # the warm-up's
            *_launched(cleared + 2, cleared + 3, 2, cleared + 5),
            _span("gateway.copy", cleared + 1.5, cleared + 6, 2),
            _span("gateway.spool", cleared + 1.5, cleared + 2, 2),
        ]
        summary = _ring(tracer, spans).summary()
        idle = {n: row["device_idle_s"] for n, row in summary.items() if row["device_idle_s"]}
        assert idle == pytest.approx({
            "gateway.copy": 2.0, "gateway.spool": 0.5, "device.unclaimed": 0.5,
        })
        # held against the whole ring it would be 40 s more
        assert dict(device_idle(spans)["by_span"])["gateway.copy"] == pytest.approx(42.0)

    def test_with_every_gap_claimed_there_is_no_unclaimed_row(self):
        spans = [*_launched(0, 1, 1, 10), _span("gateway.copy", 0, 12, 1)]
        summary = _ring(Tracer(enabled=True), spans).summary()
        assert "device.unclaimed" not in summary
        assert summary["gateway.copy"]["device_idle_s"] == pytest.approx(2.0)

    def test_without_a_device_window_the_summary_is_what_it_was(self):
        assert device_idle([_span("gateway.copy", 0, 5, 1)]) is None
        tracer = Tracer(enabled=True)
        with tracer.span("outer"):
            with tracer.span("inner"):
                pass
            tracer.event("mark")
        summary = tracer.summary()
        fields = {"count", "total_s", "self_s", "avg_s", "max_s", "p50_s", "p95_s", "p99_s"}
        assert set(summary) == {"outer", "inner", "mark"}
        assert all(set(row) == fields for row in summary.values())


def test_the_served_copys_summary_adds_up(served):
    summary = served["copy_summary"]
    spans = served["copy"]
    busy = sum(
        end - start for start, end in tracing.merge(
            [(s.start_s, s.end_s) for s in _named(spans, "device.window")]
        )
    )
    stretch = max(s.end_s for s in spans) - min(s.start_s for s in spans)
    assert sum(row["device_idle_s"] for row in summary.values()) + busy == pytest.approx(
        stretch, abs=1e-9
    )
    assert summary["device.window"]["count"] == COPY_WINDOWS
    # one request in the ring, and its `gateway.copy` covers the whole of it
    assert "device.unclaimed" not in summary


# ------------------------------ tools/profile_report.py runs on the moved code
def test_profile_report_imports_the_tracers_labelling():
    tools = str(pathlib.Path(__file__).resolve().parents[1] / "tools")
    if tools not in sys.path:
        sys.path.insert(0, tools)
    import profile_report

    assert profile_report.label_gaps is label_gaps
    assert profile_report.merge is tracing.merge


@pytest.mark.parametrize("gaps,expected,uncovered", [
    # label_gaps' own rule, on a trace like test_profile_report.py's made-up one
    ([(0, 15)],
     {"gateway.fetch": 8, "transform.decrypt": 1, "transform.launch": 2, "transform.d2h_wait": 3},
     [1]),
    ([(35, 115)],
     {"transform.d2h_wait": 5, "transform.decrypt": 1, "hot.admit": 1, "gateway.fetch": 6,
      "gateway.reply_stream": 57},
     [5, 5]),
    # named as the gap's launcher, thread 1 keeps 41-42 from thread 2's shorter span;
    # 105-110, where it has nothing open, still goes to thread 2's request
    ([(35, 115, 1)],
     {"transform.d2h_wait": 5, "transform.decrypt": 1, "gateway.fetch": 7,
      "gateway.reply_stream": 57},
     [5, 5]),
])
def test_label_gaps_cases(gaps, expected, uncovered):
    spans = [
        (1, 100, "gateway.fetch", 1), (9, 41, "transform.decrypt", 1),
        (10, 12, "transform.launch", 1), (12, 40, "transform.d2h_wait", 1),
        (42, 99, "gateway.reply_stream", 1), (43, 43, "hot.hit", 1),
        (41, 42, "hot.admit", 2), (105, 110, "gateway.fetch", 2),
    ]
    labelled = label_gaps(gaps, spans)
    assert dict(labelled["by_span"]) == expected
    assert labelled["uncovered"] == uncovered
