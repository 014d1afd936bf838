"""Spans and exact counters inside the window path and the gateway (ISSUE 27).

One copy and a lagging reader's fetches go through the HTTP gateway of an RSM
on the TPU backend (CPU forms of the kernels), and every span of PERF.md
section 3's table has to appear once per window or request, under the right
parent. The counters of `ops/gcm.py` (context builds, duplicates among them)
and of `utils/platforms.py` (programs traced) are held to what they count.
"""

from __future__ import annotations

import collections
import http.client
import os
import threading
import time

import pytest

from tests.test_rsm_lifecycle import make_rsm, make_segment_metadata
from tieredstorage_tpu.ops import gcm
from tieredstorage_tpu.sidecar import shimwire
from tieredstorage_tpu.sidecar.http_gateway import SidecarHttpGateway
from tieredstorage_tpu.utils import platforms

pytest.importorskip("cryptography")

CHUNK = 64 << 10
WINDOW_CHUNKS = 4
SEGMENT = 6 * CHUNK - 300  # two windows: four chunks, then one whole and one ragged


def _post(port: int, path: str, body: bytes, read: int | None = None):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=120)
    try:
        conn.request("POST", path, body=body)
        response = conn.getresponse()
        return response.status, response.read(read) if read else response.read()
    finally:
        conn.close()


def _closed_spans(tracer, name: str, count: int) -> list:
    """All spans, once `count` of `name` are in the ring. A `gateway.<op>`
    span closes after its reply is written, so the client has its bytes
    before the request's outermost span is recorded."""
    deadline = time.monotonic() + 60
    while len(tracer.spans(name)) < count:
        assert time.monotonic() < deadline, f"{name}: {len(tracer.spans(name))} of {count}"
        time.sleep(0.005)
    return tracer.spans()


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """The spans of one copy, then of reads that decrypt, admit and hit."""
    tmp_path = tmp_path_factory.mktemp("window-spans")
    rsm, _ = make_rsm(
        tmp_path, compression=False, encryption=True, chunk_size=CHUNK,
        extra_configs={
            "tracing.enabled": True,
            "transform.backend.class":
                "tieredstorage_tpu.transform.tpu.TpuTransformBackend",
            "transform.batch.chunks": WINDOW_CHUNKS,
            "cache.device.bytes": 64 << 20,
        },
    )
    gateway = SidecarHttpGateway(rsm).start()
    md = make_segment_metadata()
    segment = os.urandom(SEGMENT)
    sections = {
        "log_segment": segment, "offset_index": os.urandom(800),
        "time_index": os.urandom(1200), "producer_snapshot": os.urandom(96),
        "transaction_index": None, "leader_epoch_index": b"0\n1\n0 0\n",
    }
    body = shimwire.encode_metadata(md) + shimwire.encode_sections(sections)
    try:
        status, custom = _post(gateway.port, "/v1/copy", body)
        assert status in (200, 204)
        if custom:
            md = md.with_custom_metadata(custom)
        copy_spans = _closed_spans(rsm.tracer, "gateway.copy", 1)
        rsm.tracer.clear()
        fetch_body = shimwire.encode_metadata(md) + shimwire.encode_fetch_tail(0, CHUNK - 1)
        replies = [_post(gateway.port, "/v1/fetch", fetch_body) for _ in range(3)]
        assert [r[1] for r in replies] == [segment[:CHUNK]] * 3
        fetch_spans = _closed_spans(rsm.tracer, "gateway.fetch", 3)
    finally:
        gateway.stop()
        rsm.close()
    return {"copy": copy_spans, "fetch": fetch_spans, "segment_bytes": SEGMENT}


def _named(spans, name):
    return [s for s in spans if s.name == name]


def _parent_names(spans, name):
    by_id = {s.span_id: s for s in spans}
    return collections.Counter(
        by_id[s.parent_id].name if s.parent_id in by_id else None
        for s in _named(spans, name)
    )


# Six windows in a copy: two of the segment, four one-row index windows
# (offset, time, snapshot, leader epoch).
COPY_WINDOWS = 6


@pytest.mark.parametrize("name,count,parent", [
    ("gateway.copy", 1, None),
    ("gateway.spool", 1, "gateway.copy"),
    ("gateway.decode", 1, "gateway.copy"),
    ("rsm.copy_log_segment_data", 1, "gateway.copy"),
    ("storage.upload", 3, None),  # .log, .indexes, .rsm-manifest: three parents
    ("transform.encrypt_dispatch", COPY_WINDOWS, None),
    ("transform.context", COPY_WINDOWS, "transform.encrypt_dispatch"),
    ("transform.pack", COPY_WINDOWS, "transform.encrypt_dispatch"),
    ("transform.h2d", COPY_WINDOWS, "transform.encrypt_dispatch"),
    ("transform.launch", COPY_WINDOWS, "transform.encrypt_dispatch"),
    ("transform.encrypt_finish", COPY_WINDOWS, None),
    ("transform.d2h_wait", COPY_WINDOWS, "transform.encrypt_finish"),
])
def test_copy_span_once_per_window_or_request(served, name, count, parent):
    spans = served["copy"]
    assert len(_named(spans, name)) == count
    if parent is not None:
        assert _parent_names(spans, name) == {parent: count}


def test_copy_span_tree_details(served):
    spans = served["copy"]
    assert _parent_names(spans, "storage.upload") == {
        "rsm.upload.segment": 1, "rsm.upload.indexes": 1, "rsm.upload.manifest": 1,
    }
    # The store pulls the transform's stream: the segment's windows are the
    # children of its upload, so the upload's self time is the write.
    segment_upload = next(
        s for s in _named(spans, "storage.upload") if s.attributes["key"].endswith(".log")
    )
    children = [s for s in spans if s.parent_id == segment_upload.span_id]
    assert collections.Counter(s.name for s in children) == {
        "transform.encrypt_dispatch": 2, "transform.encrypt_finish": 2,
    }
    assert segment_upload.attributes["bytes"] > served["segment_bytes"]
    # spool first, then decode, then the RSM, all inside the gateway span
    gateway, spool, decode, rsm_copy = (
        _named(spans, n)[0] for n in
        ("gateway.copy", "gateway.spool", "gateway.decode", "rsm.copy_log_segment_data")
    )
    assert gateway.start_s <= spool.start_s <= spool.end_s <= decode.start_s
    assert decode.end_s <= rsm_copy.start_s <= rsm_copy.end_s <= gateway.end_s
    assert spool.attributes["bytes"] > served["segment_bytes"]
    # a fresh key: every distinct window size builds its context once, and
    # each launch of the first copy in a process may trace
    built = [s.attributes["built"] for s in _named(spans, "transform.context")]
    assert built.count(True) >= 2 and all(isinstance(b, bool) for b in built)
    assert all(
        isinstance(s.attributes["traced"], bool) for s in _named(spans, "transform.launch")
    )


@pytest.mark.parametrize("name,count,parent", [
    ("gateway.fetch", 3, None),
    ("gateway.spool", 3, "gateway.fetch"),
    ("gateway.reply_stream", 3, "gateway.fetch"),
    ("rsm.fetch_log_segment", 3, "gateway.fetch"),
    # the first read decrypts, the second decrypts again and admits, the third hits
    ("storage.fetch_chunks", 2, "gateway.reply_stream"),
    ("transform.decrypt", 2, None),
    ("transform.context", 2, "transform.decrypt"),
    ("transform.pack", 2, "transform.decrypt"),
    ("transform.h2d", 2, "transform.decrypt"),
    ("transform.launch", 2, "transform.decrypt"),
    ("transform.d2h_wait", 2, "transform.decrypt"),
    ("hot.admit", 1, "gateway.reply_stream"),
    ("hot.hit", 1, "gateway.reply_stream"),
])
def test_fetch_span_once_per_window_or_request(served, name, count, parent):
    spans = served["fetch"]
    assert len(_named(spans, name)) == count
    if parent is not None:
        assert _parent_names(spans, name) == {parent: count}


def test_fetch_span_attributes(served):
    spans = served["fetch"]
    assert [s.attributes for s in _named(spans, "gateway.reply_stream")] == [
        {"bytes": CHUNK, "views": True, "aborted": False}
    ] * 3
    admit = _named(spans, "hot.admit")[0]
    assert admit.attributes["admitted"] is True and admit.attributes["bytes"] >= CHUNK
    assert admit.duration_s > 0.0
    # the copy built this (key, size)'s context: both decrypts hit the cache
    assert [s.attributes["built"] for s in _named(spans, "transform.context")] == [False] * 2
    # the second decrypt launched the program the first had traced
    assert _named(spans, "transform.launch")[-1].attributes["traced"] is False


def test_a_reader_that_leaves_early_shows_as_aborted(tmp_path):
    """The lagging reader's open-ended fetch: it reads what it wants and
    closes, and the gateway finds out when a write fails."""
    rsm, _ = make_rsm(
        tmp_path, compression=False, encryption=False, chunk_size=CHUNK,
        extra_configs={"tracing.enabled": True},
    )
    gateway = SidecarHttpGateway(rsm).start()
    md = make_segment_metadata()
    segment = os.urandom(64 * CHUNK)  # more than the socket buffers hold
    sections = {
        "log_segment": segment, "offset_index": b"o" * 16, "time_index": b"t" * 24,
        "producer_snapshot": b"", "transaction_index": None,
        "leader_epoch_index": b"0\n",
    }
    try:
        body = shimwire.encode_metadata(md) + shimwire.encode_sections(sections)
        assert _post(gateway.port, "/v1/copy", body)[0] in (200, 204)
        tail = shimwire.encode_metadata(md) + shimwire.encode_fetch_tail(0, None)
        status, got = _post(gateway.port, "/v1/fetch", tail, read=1024)
        assert status == 200 and got == segment[:1024]
    finally:
        gateway.stop()  # joins the handler
        stream = rsm.tracer.spans("gateway.reply_stream")
        rsm.close()
    assert len(stream) == 1 and stream[0].attributes["aborted"] is True
    # `bytes` counts the blocks written whole before the write that failed
    assert 0 <= stream[0].attributes["bytes"] < len(segment)


class TestContextBuildCounters:
    def test_a_miss_counts_and_a_hit_does_not(self):
        key, before = os.urandom(32), gcm.context_stats()
        mine = gcm.thread_context_builds()
        gcm.make_context(key, b"aad", 4096)
        once = gcm.context_stats()
        assert once["context_builds"] == before["context_builds"] + 1
        assert once["context_build_seconds"] > before["context_build_seconds"]
        assert gcm.thread_context_builds() == mine + 1
        gcm.make_context(key, b"aad", 4096)
        assert gcm.context_stats() == once and gcm.thread_context_builds() == mine + 1
        # another size of the same key is another build, as is the varlen form
        gcm.make_context(key, b"aad", 8192)
        gcm.make_varlen_context(key, b"aad", 4096)
        gcm.make_varlen_context(key, b"aad", 4000)  # same bucket: a hit
        assert gcm.context_stats()["context_builds"] == once["context_builds"] + 2
        assert gcm.context_stats()["context_builds_duplicate"] == (
            before["context_builds_duplicate"]
        )

    @pytest.mark.parametrize("make", [gcm.make_context, gcm.make_varlen_context])
    def test_two_threads_building_one_key_and_size_run_one_build(self, make, monkeypatch):
        """Single flight (ISSUE 28): the second thread finds the first one's
        build running and waits for it, so nothing is built twice and the
        duplicate count, which counts builds that start beside another of the
        same key, aad and size, stays where it was."""
        first_inside, second_called = threading.Event(), threading.Event()
        real = gcm.gf128.ghash_level_table

        def slow(p):
            first_inside.set()
            second_called.wait(60)  # the build outlasts the second thread's arrival
            return real(p)

        monkeypatch.setattr(gcm.gf128, "ghash_level_table", slow)
        key, before, got = os.urandom(32), gcm.context_stats(), []
        threads = [
            threading.Thread(target=lambda: got.append(make(key, b"aad", 4096)))
            for _ in range(2)
        ]
        threads[0].start()
        assert first_inside.wait(60)
        threads[1].start()
        time.sleep(0.05)  # the second is at the cache, or soon will be: a hit either way
        second_called.set()
        for t in threads:
            t.join(60)
        after = gcm.context_stats()
        assert len(got) == 2 and got[0] is got[1]
        assert after["context_builds"] == before["context_builds"] + 1
        assert after["context_builds_duplicate"] == before["context_builds_duplicate"]
        assert not gcm._BUILDS_IN_FLIGHT

    def test_a_build_that_raises_leaves_nothing_in_flight(self, monkeypatch):
        def broken(p):
            raise RuntimeError("no matrices")

        monkeypatch.setattr(gcm.gf128, "ghash_level_table", broken)
        with pytest.raises(RuntimeError):
            gcm.make_context(os.urandom(32), b"aad", 4096)
        assert not gcm._BUILDS_IN_FLIGHT


class TestProgramTraces:
    def test_rises_on_a_new_shape_and_not_on_a_repeat(self):
        import jax
        import jax.numpy as jnp

        platforms.watch_program_traces()
        platforms.watch_program_traces()  # idempotent: one listener

        @jax.jit
        def double(x):
            return x * 2

        a, b = jnp.ones(7), jnp.ones(9)  # made before the count is read
        before, mine = platforms.program_trace_stats(), platforms.thread_program_traces()
        double(a)
        once = platforms.program_trace_stats()
        assert once["program_traces"] == before["program_traces"] + 1
        assert once["program_trace_seconds"] > before["program_trace_seconds"]
        assert platforms.thread_program_traces() == mine + 1
        double(a)
        assert platforms.program_trace_stats() == once
        double(b)
        assert platforms.program_trace_stats()["program_traces"] == once["program_traces"] + 1

    def test_a_nested_jit_is_one_program(self):
        import jax
        import jax.numpy as jnp

        platforms.watch_program_traces()

        @jax.jit
        def inner(x):
            return x + 1

        @jax.jit
        def outer(x):
            return inner(x) * 3

        x = jnp.ones(11)
        before = platforms.program_trace_stats()["program_traces"]
        outer(x)
        assert platforms.program_trace_stats()["program_traces"] == before + 1

    def test_enable_compile_cache_starts_the_count(self, monkeypatch):
        import jax

        calls = []
        monkeypatch.setattr(platforms, "watch_program_traces", lambda: calls.append(1))
        before = jax.config.jax_compilation_cache_dir
        try:
            platforms.enable_compile_cache()
        finally:
            jax.config.update("jax_compilation_cache_dir", before)
        assert calls == [1]


def test_varz_carries_the_counters():
    from tieredstorage_tpu.metrics.prometheus import PrometheusExporter

    varz = PrometheusExporter([]).varz()
    assert set(varz["programs"]) == {"program_traces", "program_trace_seconds"}
    assert set(varz["gcm"]) == {
        "context_builds", "context_builds_duplicate", "context_build_seconds",
        "key_tables_built", "key_table_hits",
    }
