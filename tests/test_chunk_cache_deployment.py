"""The `kip405-aes-chunkcache` deployment at 64 KiB chunks on the CPU: the
configuration file's `rsm` keys through `RemoteStorageManager` behind the
gateway, segments stored by the plain reference (`benchmark/reference.py`),
read as the cell `aes-cache.fetch_scan` reads them.

What the configuration states and the cell's per-layer metrics count is held
here at a small size: replies equal the source; every chunk below the cache is
fetched and decrypted once, however many readers and prefetch tasks want it; a
segment's entry decrypts 2-row windows and the steady scan one-row ones; the
ragged last chunk arrives through a prefetch; an altered chunk is refused and
never cached; a load slower than `get.timeout.ms` fails the read that owns it
and degrades the read that joined it, and lands in the cache all the same.
"""

from __future__ import annotations

import importlib.util
import json
import pathlib
import sys
import threading
import time

import pytest

pytest.importorskip("cryptography")

REPO_ROOT = pathlib.Path(__file__).resolve().parents[1]
BENCHMARK = REPO_ROOT / "benchmark"
CHUNK = 64 << 10
FULL_CHUNKS = 11
SEGMENT_BYTES = (FULL_CHUNKS + 1) * CHUNK - 300  # 11 full chunks and a ragged one
CHUNKS = FULL_CHUNKS + 1
PREFETCH_CHUNKS = 4  # 16 MiB over 4 MiB chunks, at this size
READ, STEP = 16 << 10, 15 << 10  # the cell's 1 MiB read and 254 x 4 KiB step, cut alike
SEED = 2**31 + 29


def _load(name: str):
    """A module of the benchmark, under a name no other test file's import
    of a `harness` or a `reference` can meet."""
    spec = importlib.util.spec_from_file_location(f"chunkcache_deployment_{name}",
                                                  BENCHMARK / f"{name}.py")
    module = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = module  # dataclasses look their module up there
    spec.loader.exec_module(module)
    return module


harness, reference = _load("harness"), _load("reference")


class Deployed:
    """The configuration's RSM behind its gateway over a store that the plain
    reference fills, with the program's spans on."""

    #: The deployment's file and the plain writer that fills its store.
    config_file = "kip405-aes-chunkcache.json"
    writer = reference

    def __init__(self, tmp_path: pathlib.Path, **overrides) -> None:
        config = json.loads((BENCHMARK / "configs" / self.config_file).read_text())
        self.key, public, private = reference.new_key_pair(tmp_path, harness.KEY_ID)
        store = harness.store_and_keys(tmp_path, public, private)
        self.root = pathlib.Path(store["storage.root"])
        self.source = harness.make_segment(SEED, SEGMENT_BYTES)
        self.indexes = harness.make_indexes(SEED, SEGMENT_BYTES)
        self.deployment = harness.Deployment({
            **config["rsm"], **store,
            "chunk.size": CHUNK, "cache.device.bytes": 64 << 20,
            "fetch.chunk.cache.prefetch.max.size": PREFETCH_CHUNKS * CHUNK,
            "fetch.chunk.cache.size": 64 * CHUNK,
            "tracing.enabled": True, "tracing.max.spans": 100_000,
            **overrides,
        })
        self.rsm = self.deployment.rsm
        self.cache = self.rsm.chunk_cache
        self.stats = self.deployment.backend.dispatch_stats
        self._ordinal = 0

    def close(self) -> None:
        self.deployment.close()

    def store(self, n_bytes: int = SEGMENT_BYTES):
        """One more segment of the source's first `n_bytes`, under its own
        data key; returns its name and the metadata a broker would send."""
        name = reference.SegmentName.seeded(SEED, self._ordinal)
        self._ordinal += 1
        self.writer.write_segment(self.root, name, self.key, harness.KEY_ID,
                                  self.source[:n_bytes], self.indexes, CHUNK)
        return name, harness.segment_metadata(name, n_bytes)

    def warm(self) -> None:
        """Every decrypt window a scan can make, compiled on another segment
        (one row, two rows, the ragged row, the varlen pair), so that no test
        waits on a compile."""
        for full_chunks in (5, 2):
            n_bytes = full_chunks * CHUNK + SEGMENT_BYTES % CHUNK
            _, md = self.store(n_bytes)
            for start in range(0, n_bytes, CHUNK):
                assert self.read(md, start, 64) == self.source[start : start + 64]
        self.settle()

    def read(self, md, start: int, n_bytes: int) -> bytes:
        """A bounded fetch through the gateway: the stream ends where the
        range does, so nothing is read on for a reader that has left."""
        from tieredstorage_tpu.sidecar import shimwire

        body, _ = self.deployment.client().post("/v1/fetch", [
            shimwire.encode_metadata(md),
            shimwire.encode_fetch_tail(start, start + n_bytes - 1),
        ])
        return body

    def settle(self, timeout_s: float = 60.0) -> None:
        """Until the cache's pool has nothing queued or running."""
        pool = self.cache.executor
        deadline = time.monotonic() + timeout_s
        while time.monotonic() < deadline:
            if pool._work_queue.empty() and not self.cache._inflight:
                time.sleep(0.05)
                if pool._work_queue.empty() and not self.cache._inflight:
                    return
            time.sleep(0.01)
        raise AssertionError("the chunk cache's pool did not settle")

    def decrypt_rows(self, since: int = 0) -> list[int]:
        """Rows of each decrypt window since the `since`-th, oldest first."""
        spans = self.rsm.tracer.spans("transform.decrypt")
        return [span.attributes["chunks"] for span in spans][since:]


@pytest.fixture
def deployed(tmp_path):
    d = Deployed(tmp_path)
    try:
        d.warm()
        yield d
    finally:
        d.close()


def scan(d: Deployed, md, failures: list) -> None:
    """The cell's reader: open-ended fetches of READ bytes, STEP apart, front
    to back, each on a new connection, each reply held to the source."""
    client = d.deployment.client()
    for start in range(0, SEGMENT_BYTES, STEP):
        due = min(READ, SEGMENT_BYTES - start)
        got, _ = client.fetch_tail(md, start, READ)
        if got != d.source[start : start + due]:
            failures.append(start)


@pytest.mark.parametrize("readers", [1, 2])
def test_scan_equals_source_and_decrypts_each_chunk_once(deployed, readers):
    d = deployed
    _, md = d.store()
    rows, windows = d.stats.rows, d.stats.windows
    fetched = len(d.rsm.tracer.spans("storage.fetch_chunks"))
    failures: list = []
    threads = [threading.Thread(target=scan, args=(d, md, failures)) for _ in range(readers)]
    for thread in threads:
        thread.start()
    for thread in threads:
        thread.join()
    d.settle()
    assert failures == []
    # decrypted once each, whoever wanted it: the foreground, a prefetch task,
    # the gateway reading on, the other reader
    assert d.stats.rows - rows == CHUNKS
    assert d.stats.windows - windows <= CHUNKS
    reads = d.rsm.tracer.spans("storage.fetch_chunks")[fetched:]
    assert sum(span.attributes["chunks"] for span in reads) == CHUNKS
    counts = d.cache.counters()
    assert counts["degradations"] == 0 and counts["prefetch_failures"] == 0
    assert counts["hits"] + counts["misses"] == counts["reads"]
    # each chunk reached the hot tier once: nothing was admitted or served there
    hot = d.rsm.device_hot_cache
    assert (hot.hits, hot.admissions) == (0, 0)


def test_entry_decrypts_pairs_then_the_scan_one_row_and_the_ragged_chunk_is_prefetched(deployed):
    d = deployed
    _, md = d.store()
    seen = len(d.decrypt_rows())
    counts = d.cache.counters()
    # the segment's entry: chunk 0 in the foreground, 1-2 and 3-4 by a prefetch task
    assert d.read(md, 0, READ) == d.source[:READ]
    d.settle()
    assert sorted(d.decrypt_rows(seen)) == [1, 2, 2]
    after = d.cache.counters()
    assert after["prefetch_windows"] - counts["prefetch_windows"] == 2
    assert after["prefetch_rows"] - counts["prefetch_rows"] == 4
    assert after["reads"] - counts["reads"] == 1 and after["hits"] == counts["hits"]
    # the steady scan: each chunk the reader reaches is a hit, and only the
    # chunk four ahead is new: one-row windows, the ragged last chunk among them
    seen, rows, windows = len(d.decrypt_rows()), d.stats.rows, d.stats.windows
    for chunk in range(1, CHUNKS):
        start = chunk * CHUNK
        due = min(READ, SEGMENT_BYTES - start)
        assert d.read(md, start, due) == d.source[start : start + due]
        d.settle()
    steady = d.decrypt_rows(seen)
    assert steady == [1] * (CHUNKS - 1 - PREFETCH_CHUNKS)
    assert d.stats.rows - rows == d.stats.windows - windows == len(steady)
    ragged = d.rsm.tracer.spans("transform.decrypt")[-1]
    assert ragged.attributes["bytes_out"] == SEGMENT_BYTES % CHUNK
    last = d.cache.counters()
    assert last["hits"] - after["hits"] == last["reads"] - after["reads"] == CHUNKS - 1
    assert last["prefetch_rows"] - after["prefetch_rows"] == len(steady)  # the ragged one too
    joins = [s.attributes for s in d.rsm.tracer.spans("cache.get_chunks")][-(CHUNKS - 1):]
    assert all((a["hits"], a["joined"], a["owned"]) == (1, 0, 0) for a in joins)


def test_an_altered_chunk_is_refused_not_cached_and_refused_again(deployed):
    d = deployed
    name, md = d.store(CHUNK)  # one chunk: no prefetch beside it
    with open(name.path(d.root, "log"), "r+b") as log:
        log.seek(reference.IV + CHUNK // 2)
        byte = log.read(1)
        log.seek(-1, 1)
        log.write(bytes([byte[0] ^ 0x01]))
    size = d.cache.size
    for _ in range(2):
        with pytest.raises(harness.Failed):
            d.read(md, 0, READ)
        d.settle()
        assert d.cache.size == size and not d.cache._inflight
    assert d.cache.counters()["degradations"] == 0


@pytest.mark.parametrize("slow_rows,outcome", [(1, "fails"), (2, "degrades")])
def test_a_load_slower_than_the_timeout_and_its_late_arrival(tmp_path, monkeypatch,
                                                               slow_rows, outcome):
    """`get.timeout.ms` as upstream has it: the read that owns a slow load
    fails (the broker retries); the read that joined another's slow load
    fetches below the cache instead, counted as a degradation. Either way the
    load runs on and its chunks are in the cache afterwards."""
    from tieredstorage_tpu.transform.tpu import TpuTransformBackend

    # A compile outlasts this timeout (the fault in miniature), so the
    # process's programs are compiled under a deployment that can wait.
    for directory in ("warm", "timed"):
        (tmp_path / directory).mkdir()
    patient = Deployed(tmp_path / "warm")
    try:
        patient.warm()
    finally:
        patient.close()
    d = Deployed(tmp_path / "timed", **{"fetch.chunk.cache.get.timeout.ms": 1500})
    try:
        d.warm()
        _, md = d.store()
        decrypt, slowed = TpuTransformBackend._decrypt_window, []

        def decrypt_slowly(self, enc, payloads, sizes, *rest):
            if len(sizes) == slow_rows and not slowed:
                slowed.append(len(sizes))
                time.sleep(4.0)
            return decrypt(self, enc, payloads, sizes, *rest)

        monkeypatch.setattr(TpuTransformBackend, "_decrypt_window", decrypt_slowly)
        size, rows = d.cache.size, d.stats.rows
        waits = len(d.rsm.tracer.spans("cache.join_wait"))  # the warm-up's
        if outcome == "fails":  # chunk 0's own load is the slow one
            with pytest.raises(harness.Failed, match="timed out"):
                d.read(md, 0, READ)
            assert d.cache.counters()["degradations"] == 0
        else:  # chunk 1 joins the prefetch task's slow window of chunks 1-2
            assert d.read(md, 0, READ) == d.source[:READ]
            assert d.read(md, CHUNK, READ) == d.source[CHUNK : CHUNK + READ]
            assert d.cache.counters()["degradations"] == 1
            assert [e.attributes["cause"] for e in d.rsm.tracer.spans("cache.degradation")] == [
                "join_timeout"
            ]
            assert len(d.rsm.tracer.spans("cache.join_wait")) == waits + 1
        d.settle()
        # the late load landed: chunks 0-4 (and 5, behind chunk 1's read) are cached
        assert d.cache.size - size >= 1 + PREFETCH_CHUNKS
        before = d.cache.counters()
        assert d.read(md, 0, READ) == d.source[:READ]
        assert d.cache.counters()["hits"] == before["hits"] + 1
        # the degraded read decrypted its chunk a second time, below the cache
        assert d.stats.rows - rows == d.cache.size - size + (outcome == "degrades")
    finally:
        d.close()


def test_varz_carries_the_chunk_cache_section(deployed):
    from tieredstorage_tpu.metrics.prometheus import PrometheusExporter

    d = deployed
    section = PrometheusExporter([], chunk_cache=d.rsm.chunk_cache).varz()["chunk_cache"]
    assert section == {"enabled": True, **d.cache.counters()}
    assert section["reads"] > 0 and section["prefetch_rows"] > 0
    assert PrometheusExporter([]).varz()["chunk_cache"] == {"enabled": False}
