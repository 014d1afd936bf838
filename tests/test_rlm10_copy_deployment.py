"""The `kip405-zstd-aes-rlm10` deployment at 64 KiB chunks on the CPU: the
configuration file's `rsm` keys through `RemoteStorageManager` behind the
gateway, ten copies of ten seeded segments sent at once, as the broker's ten
RLM copy threads send them in the cell `zstd-aes-rlm10.copy`.

What the configuration states and the cell's per-layer metrics count is held
here at a small size: every copy reads back to its source bytes and indexes
through the plain reference (`benchmark/reference.py`: `cryptography` AESGCM +
`zstandard`), each under a data key of its own; the encrypt streams overlap
(`encrypt_streams_max`), and the staging ring holds what they hand back
(`staging_trimmed_bytes` 0) and gives it back once they end. At the backend,
ten `transform_windows` streams on threads give the CPU backend's bytes, reuse
every buffer of their second round, and count their launches exactly; one
stream keeps the parent's bound.
"""

from __future__ import annotations

import pathlib
import random
import sys
import threading
import time

import pytest

pytest.importorskip("cryptography")
pytest.importorskip("zstandard")

from tests.test_chunk_cache_deployment import BENCHMARK, harness, reference  # noqa: E402
from tieredstorage_tpu.security.aes import IV_SIZE, AesEncryptionProvider  # noqa: E402
from tieredstorage_tpu.transform import CpuTransformBackend, TransformOptions  # noqa: E402
from tieredstorage_tpu.transform.tpu import TpuTransformBackend  # noqa: E402
from tieredstorage_tpu.utils.tracing import Tracer  # noqa: E402

CHUNK = 64 << 10
WINDOW_CHUNKS = 16
SEGMENT_BYTES = 2 * WINDOW_CHUNKS * CHUNK - 300  # two 16-row windows, a ragged last chunk
COPIES = 10  # remote.log.manager.thread.pool.size
SEED = 2**31 + 43


def one_stream_bound(backend: TpuTransformBackend) -> int:
    return 2 * (backend.pipeline_depth + 1) * backend.preferred_batch_bytes


def given_back(backend: TpuTransformBackend, timeout_s: float = 30.0) -> bool:
    """Whether the ring is back under one stream's bound within `timeout_s`:
    it gives the buffers of concurrent streams back `staging_linger_s` after
    the last has ended."""
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        with backend._stats_lock:
            if backend._give_back_at is None and not backend._encrypt_streams_peak:
                return backend._staging_free_bytes <= one_stream_bound(backend)
        time.sleep(0.01)
    return False


class Deployed:
    """The configuration's RSM behind its gateway, windows of 16 rows as the
    cell's (`transform.batch.bytes` 16 chunks), the program's spans on."""

    def __init__(self, tmp_path) -> None:
        import json

        config = json.loads((BENCHMARK / "configs" / "kip405-zstd-aes-rlm10.json").read_text())
        self.key, public, private = reference.new_key_pair(tmp_path, harness.KEY_ID)
        store = harness.store_and_keys(tmp_path, public, private)
        self.root = pathlib.Path(store["storage.root"])
        self.deployment = harness.Deployment({
            **config["rsm"], **store,
            "chunk.size": CHUNK, "cache.device.bytes": 64 << 20,
            "transform.batch.bytes": WINDOW_CHUNKS * CHUNK,
            "tracing.enabled": True, "tracing.max.spans": 100_000,
        })
        self.rsm = self.deployment.rsm
        self.backend = self.deployment.backend
        self.backend.staging_linger_s = 0.5
        self.indexes = harness.make_indexes(SEED, SEGMENT_BYTES)

    def copy(self, ordinal: int, segment: bytes) -> None:
        name = reference.SegmentName.seeded(SEED, ordinal)
        client = self.deployment.client()
        try:
            client.copy(harness.segment_metadata(name, len(segment)), segment, self.indexes)
        finally:
            client.close()

    def close(self) -> None:
        self.deployment.close()


@pytest.fixture(scope="module")
def ten_copies(tmp_path_factory):
    """One warm-up copy, then ten concurrent copies of ten seeded segments
    started together on a barrier; the deployment, the segments, the errors."""
    d = Deployed(tmp_path_factory.mktemp("rlm10"))
    try:
        segments = [harness.make_segment(SEED + i, SEGMENT_BYTES) for i in range(COPIES + 1)]
        d.copy(COPIES, segments[COPIES])  # every shape compiled before the ten
        start, errors = threading.Barrier(COPIES), []

        def rlm_task(i: int) -> None:
            try:
                start.wait()
                d.copy(i, segments[i])
            except Exception as exc:  # noqa: BLE001 - reported by the tests
                errors.append(exc)

        threads = [
            threading.Thread(target=rlm_task, args=(i,), name=f"rlm-task-{i}")
            for i in range(COPIES)
        ]
        for thread in threads:
            thread.start()
        for thread in threads:
            thread.join(timeout=600)
        assert not any(thread.is_alive() for thread in threads)
        yield d, segments, errors
    finally:
        d.close()


def test_every_copy_reads_back_under_a_key_of_its_own(ten_copies):
    d, segments, errors = ten_copies
    assert errors == []
    keys = []
    for i in range(COPIES):
        stored = reference.read_segment(d.root, reference.SegmentName.seeded(SEED, i), d.key)
        assert stored.segment == segments[i]
        assert all(stored.indexes[name] == blob for name, blob in d.indexes.items())
        keys.append(stored.data_key)
    assert len(set(keys)) == COPIES


def test_the_streams_overlapped_and_the_ring_held_what_they_handed_back(ten_copies):
    d, _, _ = ten_copies
    stats = d.backend.dispatch_stats
    assert stats.encrypt_streams_max >= 2
    # one stream a segment's log and one an index: every launch was inside one
    launches = d.rsm.tracer.spans("transform.launch")
    assert stats.dispatches == stats.windows == len(launches) > 0
    assert all(span.attributes["streams"] >= 1 for span in launches)
    assert stats.encrypt_stream_launch_sum == sum(s.attributes["streams"] for s in launches)
    assert stats.launch_queue_sum == sum(s.attributes["queued"] for s in launches)
    assert max(s.attributes["streams"] for s in launches) >= 2
    # the streams are over: the ring goes back under one stream's bound
    assert (d.backend._encrypt_streams, d.backend._encrypt_windows_out) == (0, 0)
    assert given_back(d.backend)


def test_varz_carries_the_four_counts(ten_copies):
    from tieredstorage_tpu.metrics.prometheus import PrometheusExporter

    d, _, _ = ten_copies
    dispatch = PrometheusExporter([], transform_backend=d.backend).varz()["dispatch"]
    assert dispatch == d.backend.dispatch_stats.as_dict()
    assert dispatch["encrypt_streams_max"] >= 2
    assert dispatch["encrypt_stream_launch_sum"] >= dispatch["windows"]
    assert dispatch["launch_queue_sum"] > 0
    assert "staging_trimmed_bytes" in dispatch


# ------------------------------------------------------ the backend, directly
@pytest.fixture(scope="module")
def key_pair():
    return AesEncryptionProvider.create_data_key_and_aad()


def ivs(n: int, stream: int) -> list[bytes]:
    return [bytes([stream + 1, i + 1]) * (IV_SIZE // 2) for i in range(n)]


def windows_of(seed: int, n_windows: int, rows: int = 4, chunk: int = 4096) -> list:
    rng = random.Random(seed)
    return [
        [rng.randbytes(chunk // 2) + bytes(chunk // 2) for _ in range(rows)]
        for _ in range(n_windows)
    ]


@pytest.mark.parametrize("compression", [False, True])
def test_ten_streams_on_threads_give_the_cpu_backends_bytes(key_pair, compression):
    """Round 1: each stream's first `pipeline_depth + 1` windows, all
    launched before any is finished; then all ten wait at a barrier with
    three windows each in flight; round 2: the rest. The second round packs
    every window into a buffer of the ring and lets nothing go; once the
    streams end, the ring is back under one stream's bound."""
    cpu, tpu = CpuTransformBackend(), TpuTransformBackend()
    tpu.preferred_batch_bytes = 4 * 4096  # one stream's bound: 8 windows
    tpu.staging_linger_s = 0.05
    depth = tpu.pipeline_depth
    streams, n_windows = 10, 2 * (depth + 1)
    inputs = [windows_of(100 + s, n_windows) for s in range(streams)]
    opts = [
        TransformOptions(compression=compression, encryption=key_pair,
                         ivs=ivs(4 * n_windows, s))
        for s in range(streams)
    ]
    at_barrier: dict = {}

    def snapshot() -> None:
        stats = tpu.dispatch_stats
        at_barrier.update(acquired=stats.staging_acquired, reused=stats.staging_reused,
                          trimmed=stats.staging_trimmed_bytes, streams=tpu._encrypt_streams)

    start, between = threading.Barrier(streams), threading.Barrier(streams, action=snapshot)
    full, finished = threading.Barrier(streams), threading.local()
    finish = tpu._encrypt_finish

    def first_finish_waits_for_all(staged):
        if not getattr(finished, "once", False):
            finished.once = True
            full.wait()  # every stream has its first depth + 1 windows out
        return finish(staged)

    tpu._encrypt_finish = first_finish_waits_for_all
    outputs: dict = {}
    errors: list = []

    def windows(s: int):
        for k, window in enumerate(inputs[s]):
            if k == depth + 1:
                between.wait()
            yield window

    def stream(s: int) -> None:
        try:
            start.wait()
            outputs[s] = list(tpu.transform_windows(windows(s), opts[s]))
        except Exception as exc:  # noqa: BLE001 - reported below
            errors.append(exc)

    threads = [threading.Thread(target=stream, args=(s,)) for s in range(streams)]
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)  # hand over inside acquire and release too
    try:
        for thread in threads:
            thread.start()
        for thread in threads:
            thread.join(timeout=300)
    finally:
        sys.setswitchinterval(interval)
    assert not any(thread.is_alive() for thread in threads)
    assert errors == []
    for s in range(streams):
        assert outputs[s] == list(cpu.transform_windows(iter(inputs[s]), opts[s]))
    stats = tpu.dispatch_stats
    assert at_barrier["streams"] == stats.encrypt_streams_max == streams
    assert stats.staging_trimmed_bytes == at_barrier["trimmed"] == 0
    if not compression:  # one shape, and a stream hands one back before it takes one
        assert (at_barrier["acquired"], at_barrier["reused"]) == (streams * (depth + 1), 0)
        assert stats.staging_acquired - at_barrier["acquired"] == streams * (n_windows - depth - 1)
        assert stats.staging_reused - at_barrier["reused"] == streams * (n_windows - depth - 1)
    assert stats.encrypt_stream_launch_sum >= stats.windows == streams * n_windows
    assert (tpu._encrypt_streams, tpu._encrypt_windows_out) == (0, 0)
    assert given_back(tpu)
    tpu.close()


def test_the_bound_follows_the_streams_in_flight(key_pair):
    tpu = TpuTransformBackend()
    tpu.preferred_batch_bytes = 4 * 4096
    tpu.staging_linger_s = 0.05
    one = one_stream_bound(tpu)
    assert tpu._staging_bound() == one  # no stream: the parent's bound
    gens = [
        tpu.transform_windows(iter(windows_of(s, 6)), TransformOptions(encryption=key_pair))
        for s in range(3)
    ]
    firsts = [next(gen) for gen in gens]  # each has dispatched 4 and finished 1
    assert all(len(first) == 4 for first in firsts)
    assert tpu._encrypt_streams == 3 and tpu._staging_bound() == 3 * one
    assert len(list(gens[0])) == 5  # one stream ends: the others' bound stays
    assert tpu._encrypt_streams == 2 and tpu._staging_bound() == 3 * one
    for gen in gens[1:]:
        assert len(list(gen)) == 5
    assert tpu._encrypt_streams == 0
    assert given_back(tpu) and tpu._staging_bound() == one


def test_a_stream_begun_within_the_linger_keeps_the_ring(key_pair):
    """Copy threads of a backlog start their next streams together once
    their bodies have arrived: a stream that begins before the linger is
    over finds the concurrent streams' bound and buffers; a lone stream
    never starts the timer."""
    tpu = TpuTransformBackend()
    tpu.preferred_batch_bytes = 4 * 4096
    tpu.staging_linger_s = 60.0
    opts = TransformOptions(encryption=key_pair)
    gens = [tpu.transform_windows(iter(windows_of(s, 4)), opts) for s in range(2)]
    for gen in gens:
        next(gen)
    for gen in gens:
        list(gen)
    held, due = tpu._staging_free_bytes, tpu._give_back_at
    assert due is not None and tpu._staging_bound() == 2 * one_stream_bound(tpu)
    gen = tpu.transform_windows(iter(windows_of(9, 4)), opts)
    next(gen)
    assert tpu._give_back_at is None  # a stream has begun: nothing is given back
    list(gen)
    assert tpu._staging_free_bytes == held  # the lone stream reused, took nothing away
    assert tpu._staging_bound() == 2 * one_stream_bound(tpu)
    assert tpu._give_back_at > due  # the linger starts again from its end
    thread = tpu._give_back_thread
    assert thread.name == "staging-give-back" and thread.daemon and thread.is_alive()
    tpu.close()
    assert not thread.is_alive()
    lone = TpuTransformBackend()
    list(lone.transform_windows(iter(windows_of(10, 6)), opts))
    assert lone._give_back_thread is None and lone._encrypt_streams_peak == 0


def test_one_stream_counts_its_queue_exactly(key_pair):
    """One stream of five windows at depth 3: the launches find 0, 1, 2, 3
    and 3 windows of it ahead (the fifth is launched once the first is
    finished); each launch finds one stream; the span says the same."""
    tpu = TpuTransformBackend()
    tpu.tracer = Tracer(enabled=True)
    out = list(tpu.transform_windows(iter(windows_of(7, 5)), TransformOptions(encryption=key_pair)))
    assert len(out) == 5
    stats = tpu.dispatch_stats
    assert (stats.encrypt_streams_max, stats.encrypt_stream_launch_sum) == (1, 5)
    assert stats.launch_queue_sum == 0 + 1 + 2 + 3 + 3
    launches = tpu.tracer.spans("transform.launch")
    assert [s.attributes["queued"] for s in launches] == [0, 1, 2, 3, 3]
    assert [s.attributes["streams"] for s in launches] == [1] * 5
    assert tpu._encrypt_windows_out == 0
    # a window outside any stream counts no stream, and finds none ahead
    tpu.transform(windows_of(8, 1)[0], TransformOptions(encryption=key_pair))
    assert (stats.encrypt_stream_launch_sum, stats.launch_queue_sum) == (5, 9)


def test_a_stream_stopped_early_leaves_the_queue_and_the_count(key_pair):
    tpu = TpuTransformBackend()
    gen = tpu.transform_windows(iter(windows_of(9, 6)), TransformOptions(encryption=key_pair))
    next(gen)  # four dispatched, one finished
    assert (tpu._encrypt_streams, tpu._encrypt_windows_out) == (1, 3)
    gen.close()
    assert (tpu._encrypt_streams, tpu._encrypt_windows_out) == (0, 0)
    # the three abandoned windows never gave their buffers back
    assert sum(len(v) for v in tpu._staging_free.values()) == 1
