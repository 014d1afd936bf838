"""Tracing wired through the RSM hot path (SURVEY §5).

Spans must appear around copy/fetch/delete and around the TPU backend's
compress/dispatch/finish/decrypt stages, nested, with attributes; disabled
tracing must record nothing and inject the no-op everywhere.
"""

from __future__ import annotations

import pytest

from tests.test_rsm_lifecycle import make_rsm, make_segment_data, make_segment_metadata
from tieredstorage_tpu.utils.tracing import Tracer


def _lifecycle(rsm, tmp_path):
    data = make_segment_data(tmp_path, with_txn=False)
    md = make_segment_metadata()
    custom = rsm.copy_log_segment_data(md, data)
    if custom:
        md = md.with_custom_metadata(custom)
    assert rsm.fetch_log_segment(md, 0).read() == data.log_segment.read_bytes()
    rsm.delete_log_segment_data(md)


def test_spans_cover_rsm_and_transform_stages(tmp_path):
    rsm, _ = make_rsm(
        tmp_path, compression=True, encryption=True,
        extra_configs={
            "tracing.enabled": True,
            "transform.backend.class": "tieredstorage_tpu.transform.tpu.TpuTransformBackend",
        },
    )
    _lifecycle(rsm, tmp_path)
    names = {s.name for s in rsm.tracer.spans()}
    assert {
        "rsm.copy_log_segment_data",
        "rsm.fetch_log_segment",
        "rsm.delete_log_segment_data",
        "transform.compress",
        "transform.encrypt_dispatch",
        "transform.encrypt_finish",
        "transform.decrypt",
    } <= names
    copy_span = rsm.tracer.spans("rsm.copy_log_segment_data")[0]
    assert copy_span.attributes["topic"] == "topic"
    assert copy_span.attributes["partition"] == 7
    assert copy_span.duration_s > 0
    # Backend spans are nested under the RSM operation (depth > 0).
    dispatch = rsm.tracer.spans("transform.encrypt_dispatch")
    assert dispatch and all(s.depth > 0 for s in dispatch)
    # Summary aggregates per name.
    summary = rsm.tracer.summary()
    assert summary["rsm.copy_log_segment_data"]["count"] == 1
    rsm.close()


def test_tracing_disabled_records_nothing(tmp_path):
    rsm, _ = make_rsm(tmp_path, compression=True, encryption=False)
    _lifecycle(rsm, tmp_path)
    assert rsm.tracer.spans() == []
    assert rsm.tracer.enabled is False


class _Annotations:
    """Stands in for `jax.profiler`: every TraceAnnotation opened and closed."""

    def __init__(self):
        self.opened, self.closed = [], []
        outer = self

        class TraceAnnotation:
            def __init__(self, name):
                self.name = name

            def __enter__(self):
                outer.opened.append(self.name)
                return self

            def __exit__(self, *exc):
                outer.closed.append(self.name)

        self.TraceAnnotation = TraceAnnotation


@pytest.fixture
def annotations(monkeypatch):
    import jax

    seen = _Annotations()
    monkeypatch.setattr(jax, "profiler", seen)
    return seen


def test_jax_profiler_forwarding_smoke(annotations):
    """An enabled tracer opens a TraceAnnotation around every span, with no
    switch: outside a profiler session they are no-ops, inside one they put
    the spans into the profiler's own trace."""
    tracer = Tracer(enabled=True)
    with tracer.span("outer"):
        with tracer.span("inner"):
            pass
    assert [s.name for s in tracer.spans()] == ["inner", "outer"]
    assert tracer.spans("inner")[0].depth == 1
    assert annotations.opened == ["outer", "inner"]
    assert annotations.closed == ["inner", "outer"]


def test_event_forwards_to_jax_profiler(annotations):
    """tracer.event() is annotated like span() is: zero-duration
    annotations keep timeline parity with spans."""
    tracer = Tracer(enabled=True)
    s = tracer.event("breaker.trip", reason="threshold")
    assert s is not None and s.duration_s == 0.0
    assert tracer.spans("breaker.trip")[0].attributes["reason"] == "threshold"
    assert annotations.opened == annotations.closed == ["breaker.trip"]


def test_disabled_tracer_records_nothing_and_opens_no_annotation(annotations, monkeypatch):
    import types

    from tieredstorage_tpu.utils import tracing

    def no_clock():
        raise AssertionError("a disabled tracer read the clock")

    tracer = Tracer(enabled=False)
    # the module's own reference to `time`, not the interpreter's clock
    monkeypatch.setattr(tracing, "time", types.SimpleNamespace(perf_counter=no_clock))
    with tracer.span("outer") as span:
        assert span is None
        assert tracer.event("leaf") is None
    assert tracer.spans() == [] and tracer.summary() == {}
    assert annotations.opened == []


def test_a_process_without_jax_is_never_made_to_import_it():
    """A client-side tracer (sidecar/client.py) records spans and leaves
    `jax` alone."""
    import subprocess
    import sys

    code = (
        "import sys\n"
        "from tieredstorage_tpu.utils.tracing import Tracer\n"
        "t = Tracer(enabled=True)\n"
        "with t.span('client.fetch_log_segment'):\n"
        "    t.event('leaf')\n"
        "assert [s.name for s in t.spans()] == ['leaf', 'client.fetch_log_segment']\n"
        "assert 'jax' not in sys.modules, 'tracing imported jax'\n"
    )
    proc = subprocess.run([sys.executable, "-c", code], capture_output=True, text=True)
    assert proc.returncode == 0, proc.stderr


def _span(tracer, name, start, end, parent=None):
    from tieredstorage_tpu.utils.tracing import Span

    s = Span(name=name, start_s=start, end_s=end, span_id=f"{name}@{start}",
             parent_id=parent.span_id if parent else None, trace_id="t")
    tracer._record(s)
    return s


class TestSelfTime:
    def test_nested_tree(self):
        tracer = Tracer(enabled=True)
        root = _span(tracer, "root", 0.0, 10.0)
        mid = _span(tracer, "mid", 1.0, 7.0, root)
        _span(tracer, "leaf", 2.0, 4.0, mid)
        _span(tracer, "leaf", 5.0, 6.0, mid)
        _span(tracer, "other", 8.0, 9.5, root)
        summary = tracer.summary()
        assert summary["root"]["self_s"] == pytest.approx(10.0 - 6.0 - 1.5)
        assert summary["mid"]["self_s"] == pytest.approx(6.0 - 3.0)
        assert summary["leaf"]["self_s"] == pytest.approx(3.0)  # no children: all its own
        assert summary["leaf"]["total_s"] == pytest.approx(3.0)
        assert summary["other"]["self_s"] == pytest.approx(1.5)

    def test_overlapping_children_are_merged_and_clipped(self):
        """Pipelined windows: a parent's children overlap each other, and
        one adopted across threads outlives it; self time never goes
        negative and the overlap counts once."""
        tracer = Tracer(enabled=True)
        upload = _span(tracer, "storage.upload", 0.0, 10.0)
        _span(tracer, "window", 1.0, 6.0, upload)
        _span(tracer, "window", 4.0, 9.0, upload)   # overlaps the first
        _span(tracer, "window", 5.0, 5.5, upload)   # inside both
        _span(tracer, "late", 9.5, 14.0, upload)    # outlives the parent
        _span(tracer, "early", -3.0, 0.5, upload)   # began before it
        summary = tracer.summary()
        # covered: [0, 0.5] + [1, 9] + [9.5, 10] = 9.0
        assert summary["storage.upload"]["self_s"] == pytest.approx(1.0)
        assert summary["window"]["total_s"] == pytest.approx(10.5)
        assert all(row["self_s"] >= 0.0 for row in summary.values())

    def test_children_that_cover_everything_leave_zero(self):
        tracer = Tracer(enabled=True)
        root = _span(tracer, "root", 0.0, 2.0)
        _span(tracer, "child", 0.0, 1.5, root)
        _span(tracer, "child", 1.0, 2.0, root)
        assert tracer.summary()["root"]["self_s"] == 0.0

    def test_live_spans_report_self_time(self):
        tracer = Tracer(enabled=True)
        with tracer.span("outer"):
            with tracer.span("inner"):
                pass
        summary = tracer.summary()
        outer, inner = summary["outer"], summary["inner"]
        assert outer["self_s"] == pytest.approx(outer["total_s"] - inner["total_s"])
        assert inner["self_s"] == inner["total_s"]


class TestTraceIdentity:
    def test_nested_spans_share_trace_and_parent_correctly(self):
        tracer = Tracer(enabled=True)
        with tracer.span("root"):
            with tracer.span("child"):
                tracer.event("leaf")
        root = tracer.spans("root")[0]
        child = tracer.spans("child")[0]
        leaf = tracer.spans("leaf")[0]
        assert root.trace_id and len(root.trace_id) == 32
        assert root.parent_id is None
        assert child.trace_id == root.trace_id
        assert child.parent_id == root.span_id
        assert leaf.trace_id == root.trace_id
        assert leaf.parent_id == child.span_id

    def test_sibling_roots_get_distinct_traces(self):
        tracer = Tracer(enabled=True)
        with tracer.span("a"):
            pass
        with tracer.span("b"):
            pass
        a, b = tracer.spans("a")[0], tracer.spans("b")[0]
        assert a.trace_id != b.trace_id

    def test_traceparent_format_parse_round_trip(self):
        from tieredstorage_tpu.utils.tracing import (
            format_traceparent,
            parse_traceparent,
        )

        header = format_traceparent("ab" * 16, "cd" * 8)
        assert header == f"00-{'ab' * 16}-{'cd' * 8}-01"
        assert parse_traceparent(header) == ("ab" * 16, "cd" * 8)
        for bad in (None, "", "00-short-id-01", f"00-{'0' * 32}-{'cd' * 8}-01",
                    f"00-{'ab' * 16}-{'0' * 16}-01", f"ff-{'ab' * 16}-{'cd' * 8}-01",
                    "zz-not-hex-at-all"):
            assert parse_traceparent(bad) is None, bad

    def test_continue_trace_adopts_remote_parent(self):
        from tieredstorage_tpu.utils.tracing import format_traceparent

        tracer = Tracer(enabled=True)
        remote_trace, remote_span = "12" * 16, "34" * 8
        with tracer.continue_trace(format_traceparent(remote_trace, remote_span)):
            assert tracer.current_traceparent() == format_traceparent(
                remote_trace, remote_span
            )
            with tracer.span("server.op"):
                pass
        server = tracer.spans("server.op")[0]
        assert server.trace_id == remote_trace
        assert server.parent_id == remote_span
        # Context is restored: the next root starts a fresh trace.
        with tracer.span("later"):
            pass
        assert tracer.spans("later")[0].trace_id != remote_trace

    def test_continue_trace_with_garbage_is_noop(self):
        tracer = Tracer(enabled=True)
        with tracer.continue_trace("totally-not-a-traceparent"):
            with tracer.span("op"):
                pass
        assert tracer.spans("op")[0].parent_id is None

    def test_current_traceparent_reflects_active_span(self):
        tracer = Tracer(enabled=True)
        assert tracer.current_traceparent() is None
        with tracer.span("op") as s:
            from tieredstorage_tpu.utils.tracing import format_traceparent

            assert tracer.current_traceparent() == format_traceparent(
                s.trace_id, s.span_id
            )
        assert tracer.current_traceparent() is None
        disabled = Tracer(enabled=False)
        assert disabled.current_traceparent() is None


class TestRingBuffer:
    def test_ring_buffer_keeps_newest_and_counts_drops(self):
        tracer = Tracer(enabled=True, max_spans=5)
        for i in range(8):
            tracer.event(f"e{i}")
        assert tracer.recorded_spans == 5
        assert tracer.dropped_spans == 3
        assert [s.name for s in tracer.spans()] == [f"e{i}" for i in range(3, 8)]

    def test_clear_resets_drop_counter(self):
        tracer = Tracer(enabled=True, max_spans=2)
        for i in range(4):
            tracer.event(f"e{i}")
        tracer.clear()
        assert tracer.recorded_spans == 0 and tracer.dropped_spans == 0


class TestSummaryAndExport:
    def test_summary_percentiles(self):
        tracer = Tracer(enabled=True)
        for i in range(100):
            s = tracer.event("op")
            s.end_s = s.start_s + (i + 1) / 1000.0  # 1ms..100ms
        summary = tracer.summary()["op"]
        assert summary["count"] == 100
        assert abs(summary["p50_s"] - 0.050) < 1e-9
        assert abs(summary["p95_s"] - 0.095) < 1e-9
        assert abs(summary["p99_s"] - 0.099) < 1e-9
        assert abs(summary["max_s"] - 0.100) < 1e-9

    def test_chrome_trace_export_is_valid_and_loadable(self, tmp_path):
        import json

        tracer = Tracer(enabled=True)
        with tracer.span("fetch", topic="t"):
            tracer.event("breaker.trip")
        out = tracer.write_chrome_trace(tmp_path / "artifacts" / "trace.json")
        doc = json.loads(out.read_text())
        events = doc["traceEvents"]
        assert len(events) == 2
        by_name = {e["name"]: e for e in events}
        fetch, trip = by_name["fetch"], by_name["breaker.trip"]
        assert fetch["ph"] == "X" and fetch["dur"] > 0
        assert trip["ph"] == "i" and trip["s"] == "t"
        for e in events:
            assert {"name", "ph", "ts", "pid", "tid", "args"} <= set(e)
        assert fetch["args"]["topic"] == "t"
        assert trip["args"]["trace_id"] == fetch["args"]["trace_id"]
        assert trip["args"]["parent_id"] == fetch["args"]["span_id"]
        assert doc["otherData"]["dropped_spans"] == 0


class TestSummaryDegenerateContract:
    """ISSUE 14: the empty/single-sample contract, pinned."""

    def test_empty_tracer_summary_is_empty_dict(self):
        assert Tracer(enabled=True).summary() == {}
        assert Tracer(enabled=False).summary() == {}

    def test_single_span_is_every_percentile_of_itself(self):
        tracer = Tracer(enabled=True)
        s = tracer.event("solo")
        s.end_s = s.start_s + 0.042
        summary = tracer.summary()["solo"]
        assert summary["count"] == 1
        for key in ("avg_s", "max_s", "p50_s", "p95_s", "p99_s"):
            assert summary[key] == pytest.approx(0.042)

    def test_percentile_of_empty_set_is_a_programming_error(self):
        from tieredstorage_tpu.utils.tracing import _percentile

        with pytest.raises(ValueError, match="empty"):
            _percentile([], 0.5)
