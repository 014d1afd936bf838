"""The `kip405-zstd-aes-chunkcache` deployment at 64 KiB chunks on the CPU:
the configuration file's `rsm` keys through `RemoteStorageManager` behind the
gateway, segments stored by the compressing plain writer
(`benchmark/reference_zstd.py`), read as the cell `zstd-aes-cache.fetch_scan`
reads them.

What the configuration states and the cell's per-layer metrics count is held
here at a small size: replies equal the source; every chunk below the cache is
fetched, decrypted and decompressed once; one-row windows of compressed chunks
of N distinct sizes share the programs of their `bucket_max_bytes` rungs,
while an encrypt-only one-row window keeps its fixed-shape context; a chunk
whose tag alone is altered is refused and never cached; `transform.decompress`
and the counts `varlen_windows` and `padded_bytes` appear, on `/varz` too. And
the writer itself: the plain reference reads its objects back to the source,
the program parses its manifest, and its chunk-size encoding is upstream's.
"""

from __future__ import annotations

import base64
import json
import random
import sys
import threading

import pytest

pytest.importorskip("cryptography")
pytest.importorskip("zstandard")

from tests import test_chunk_cache_deployment as sibling  # noqa: E402
from tests.test_chunk_cache_deployment import (  # noqa: E402
    CHUNK,
    CHUNKS,
    READ,
    SEED,
    SEGMENT_BYTES,
    harness,
    reference,
    scan,
)
from tieredstorage_tpu.manifest import codec  # noqa: E402
from tieredstorage_tpu.manifest.segment_manifest import manifest_from_json  # noqa: E402
from tieredstorage_tpu.ops import gcm  # noqa: E402
from tieredstorage_tpu.security.aes import DataKeyAndAAD  # noqa: E402
from tieredstorage_tpu.transform.api import DetransformOptions  # noqa: E402
from tieredstorage_tpu.transform.tpu import TpuTransformBackend  # noqa: E402
from tieredstorage_tpu.utils import platforms  # noqa: E402

# `reference_zstd` takes everything else from `reference`, by that name: the
# module the sibling loaded, so that both writers share one `SegmentName`.
sys.modules.setdefault("reference", reference)
reference_zstd = sibling._load("reference_zstd")

RAGGED = SEGMENT_BYTES % CHUNK


class Deployed(sibling.Deployed):
    config_file = "kip405-zstd-aes-chunkcache.json"
    writer = reference_zstd

    def spans_of(self, name: str, since: int = 0) -> list:
        return self.rsm.tracer.spans(name)[since:]


@pytest.fixture
def deployed(tmp_path):
    d = Deployed(tmp_path)
    try:
        d.warm()
        yield d
    finally:
        d.close()


def stored_chunks(root, name, key):
    """A stored segment's chunks as the store holds them, and what decrypts
    them: from the three objects alone, as the plain reference reads them."""
    manifest = json.loads(name.path(root, "rsm-manifest").read_text())
    sizes = reference.transformed_sizes(manifest["chunkIndex"])
    log = name.path(root, "log").read_bytes()
    chunks, at = [], 0
    for size in sizes:
        chunks.append(log[at : at + size])
        at += size
    enc = DataKeyAndAAD(
        reference.read_segment(root, name, key).data_key,
        base64.b64decode(manifest["encryption"]["aad"]),
    )
    return chunks, enc


# ------------------------------------------------------------- the served path
@pytest.mark.parametrize("readers", [1, 2])
def test_scan_equals_source_and_each_chunk_is_decrypted_and_decompressed_once(deployed, readers):
    d = deployed
    _, md = d.store()
    rows, windows = d.stats.rows, d.stats.windows
    varlen, traces = d.stats.varlen_windows, platforms.program_trace_stats()["program_traces"]
    fetched, decompressed = len(d.spans_of("storage.fetch_chunks")), len(d.spans_of("transform.decompress"))
    failures: list = []
    threads = [threading.Thread(target=scan, args=(d, md, failures)) for _ in range(readers)]
    for thread in threads:
        thread.start()
    for thread in threads:
        thread.join()
    d.settle()
    assert failures == []
    assert d.stats.rows - rows == CHUNKS
    reads = d.spans_of("storage.fetch_chunks", fetched)
    assert sum(span.attributes["chunks"] for span in reads) == CHUNKS
    codec_spans = d.spans_of("transform.decompress", decompressed)
    assert sum(span.attributes["chunks"] for span in codec_spans) == CHUNKS
    assert sum(span.attributes["bytes_out"] for span in codec_spans) == SEGMENT_BYTES
    assert all(s.attributes["bytes_in"] < s.attributes["bytes_out"] for s in codec_spans)
    # every window of a compressed segment is varlen, and a warm scan traces nothing
    assert d.stats.varlen_windows - varlen == d.stats.windows - windows
    assert platforms.program_trace_stats()["program_traces"] == traces
    counts = d.cache.counters()
    assert counts["degradations"] == 0 and counts["prefetch_failures"] == 0
    hot = d.rsm.device_hot_cache
    assert (hot.hits, hot.admissions) == (0, 0)


def test_the_steady_scan_decrypts_one_row_windows_on_their_rungs(deployed):
    d = deployed
    name, md = d.store()
    assert d.read(md, 0, READ) == d.source[:READ]
    d.settle()
    seen, padded, payload = len(d.decrypt_rows()), d.stats.padded_bytes, d.stats.bytes_in
    for chunk in range(1, CHUNKS):
        start = chunk * CHUNK
        due = min(READ, SEGMENT_BYTES - start)
        assert d.read(md, start, due) == d.source[start : start + due]
        d.settle()
    steady = d.decrypt_rows(seen)
    assert steady and set(steady) == {1}
    # the rows were staged at their rungs: the ragged chunk's among them
    sizes = [size - 28 for size in reference_zstd.stored_sizes(d.root, name)]
    decrypted = sizes[-len(steady):]
    assert d.stats.bytes_in - payload == sum(decrypted)
    assert d.stats.padded_bytes - padded == sum(gcm.bucket_max_bytes(s) for s in decrypted)
    assert len({gcm.bucket_max_bytes(s) for s in sizes}) < len(set(sizes))
    ragged = d.spans_of("transform.decompress")[-1]
    assert ragged.attributes["bytes_out"] == RAGGED


def test_a_chunk_whose_tag_alone_is_altered_is_refused_and_never_cached(deployed):
    d = deployed
    name, md = d.store(CHUNK)  # one chunk: no prefetch beside it
    (stored,) = reference_zstd.stored_sizes(d.root, name)
    with open(name.path(d.root, "log"), "r+b") as log:
        log.seek(stored - reference.TAG // 2)
        byte = log.read(1)
        log.seek(-1, 1)
        log.write(bytes([byte[0] ^ 0x01]))
    size, decompressed = d.cache.size, len(d.spans_of("transform.decompress"))
    for _ in range(2):
        with pytest.raises(harness.Failed, match="500"):
            d.read(md, 0, READ)
        d.settle()
        assert d.cache.size == size and not d.cache._inflight
    # refused by the tag, before the codec saw the frame
    assert len(d.spans_of("transform.decompress")) == decompressed
    assert d.cache.counters()["degradations"] == 0


def test_varz_carries_the_span_and_the_two_counts(deployed):
    from tieredstorage_tpu.metrics.prometheus import PrometheusExporter

    d = deployed
    varz = PrometheusExporter(
        [], tracer=d.rsm.tracer, chunk_cache=d.rsm.chunk_cache,
        transform_backend=d.rsm.transform_backend,
    ).varz()
    assert varz["spans"]["transform.decompress"]["count"] > 0
    dispatch = varz["dispatch"]
    assert dispatch == d.stats.as_dict()
    assert dispatch["varlen_windows"] == dispatch["windows"] > 0
    assert dispatch["padded_bytes"] > dispatch["bytes_in"] > 0
    assert "dispatch" not in PrometheusExporter([]).varz()


# ------------------------------------------------ the window's form, directly
@pytest.fixture(scope="module")
def compressed_rows(tmp_path_factory):
    """Twelve stored chunks of one compressed segment (eleven full, and a
    ragged one short enough to fall on a lower rung), the source, the key."""
    tmp = tmp_path_factory.mktemp("rows")
    key, _, _ = reference.new_key_pair(tmp, harness.KEY_ID)
    n_bytes = (CHUNKS - 1) * CHUNK + 30_000
    source = harness.make_segment(SEED, n_bytes)
    name = reference.SegmentName.seeded(SEED, 0)
    reference_zstd.write_segment(tmp, name, key, harness.KEY_ID, source,
                                 harness.make_indexes(SEED, n_bytes), CHUNK)
    chunks, enc = stored_chunks(tmp, name, key)
    return source, chunks, enc


def test_one_row_windows_of_n_sizes_trace_no_more_programs_than_their_rungs(compressed_rows):
    source, chunks, enc = compressed_rows
    platforms.watch_program_traces()
    sizes = [len(c) - 28 for c in chunks]
    rungs = {gcm.bucket_max_bytes(size) for size in sizes}
    assert len(set(sizes)) > len(rungs) == 2
    backend = TpuTransformBackend()
    opts = DetransformOptions(compression=True, encryption=enc, max_original_chunk_size=CHUNK)
    before = platforms.program_trace_stats()["program_traces"]
    for i, chunk in enumerate(chunks):
        (plain,) = backend.detransform([chunk], opts)
        assert plain == source[i * CHUNK : (i + 1) * CHUNK]
    traced = platforms.program_trace_stats()["program_traces"] - before
    assert traced <= len(rungs)
    stats = backend.dispatch_stats
    assert stats.windows == stats.varlen_windows == stats.rows == len(chunks)
    assert stats.bytes_in == sum(sizes)
    assert stats.padded_bytes == sum(gcm.bucket_max_bytes(size) for size in sizes)
    # and again: not one more
    again = platforms.program_trace_stats()["program_traces"]
    backend.detransform([chunks[3]], opts)
    assert platforms.program_trace_stats()["program_traces"] == again


@pytest.mark.parametrize("compressed,form", [(False, "fixed"), (True, "varlen")])
def test_the_manifests_word_chooses_the_form_of_a_uniform_window(compressed, form):
    """What `_window_context` observes: rows of one size keep the fixed-shape
    context unless the manifest says the segment is compressed."""
    enc = DataKeyAndAAD(bytes(range(32)), b"aad" * 4)
    backend = TpuTransformBackend()
    for sizes in ([50_000], [50_000, 50_000]):
        ctx, n_bytes, varlen = backend._window_context(enc, sizes, compressed)
        assert varlen is compressed
        if form == "fixed":
            assert isinstance(ctx, gcm.GcmContext) and n_bytes == 50_000
        else:
            assert isinstance(ctx, gcm.GcmVarlenContext)
            assert n_bytes == gcm.bucket_max_bytes(50_000) == 57_344
    # rows of differing sizes are varlen either way
    assert backend._window_context(enc, [50_000, 40_000], compressed)[2] is True


def test_a_one_chunk_compressed_upload_is_staged_on_its_rung(compressed_rows):
    """The copy direction observes the same word (`TransformOptions.
    compression`): a small segment's single compressed chunk does not get a
    program of its own size either, and the plain reference reads it back."""
    import zstandard
    from cryptography.hazmat.primitives.ciphers.aead import AESGCM

    from tieredstorage_tpu.transform.api import TransformOptions

    source, _, enc = compressed_rows
    backend = TpuTransformBackend()
    opts = TransformOptions(compression=True, encryption=enc)
    for n_bytes in (CHUNK, CHUNK - 1234):
        (wire,) = backend.transform([source[:n_bytes]], opts)
        frame = AESGCM(enc.data_key).decrypt(wire[:12], wire[12:], enc.aad)
        assert zstandard.ZstdDecompressor().decompress(frame, max_output_size=CHUNK) == source[:n_bytes]
    stats = backend.dispatch_stats
    assert stats.windows == stats.varlen_windows == 2
    assert stats.padded_bytes > stats.bytes_in
    assert stats.padded_bytes % gcm.bucket_max_bytes(stats.bytes_in // 2) == 0


def test_an_encrypt_only_one_row_window_is_fixed_shape_and_unpadded(tmp_path):
    key, _, _ = reference.new_key_pair(tmp_path, harness.KEY_ID)
    source = harness.make_segment(SEED, 2 * CHUNK)
    name = reference.SegmentName.seeded(SEED, 0)
    reference.write_segment(tmp_path, name, key, harness.KEY_ID, source,
                            harness.make_indexes(SEED, 2 * CHUNK), CHUNK)
    chunks, enc = stored_chunks(tmp_path, name, key)
    backend = TpuTransformBackend()
    opts = DetransformOptions(encryption=enc, max_original_chunk_size=CHUNK)
    assert backend.detransform([chunks[0]], opts) == [source[:CHUNK]]
    assert backend.detransform(chunks, opts) == [source[:CHUNK], source[CHUNK:]]
    stats = backend.dispatch_stats
    assert (stats.windows, stats.varlen_windows) == (2, 0)
    assert stats.padded_bytes == stats.bytes_in == 3 * CHUNK
    assert stats.as_dict()["varlen_windows"] == 0


def test_a_compressed_window_is_refused_on_its_tag_before_the_codec(compressed_rows):
    _, chunks, enc = compressed_rows
    from tieredstorage_tpu.transform.api import AuthenticationError

    altered = bytearray(chunks[2])
    altered[-5] ^= 0x10
    backend = TpuTransformBackend()
    opts = DetransformOptions(compression=True, encryption=enc, max_original_chunk_size=CHUNK)
    with pytest.raises(AuthenticationError):
        backend.detransform([bytes(altered)], opts)


# ------------------------------------------------------------------ the writer
@pytest.mark.parametrize("n_bytes", [SEGMENT_BYTES, 3 * CHUNK, CHUNK, 300])
def test_the_plain_reference_reads_the_writers_objects_back(tmp_path, n_bytes):
    key, _, _ = reference.new_key_pair(tmp_path, harness.KEY_ID)
    source = harness.make_segment(SEED, n_bytes)
    indexes = harness.make_indexes(SEED, n_bytes)
    name = reference.SegmentName.seeded(SEED, 7)
    reference_zstd.write_segment(tmp_path, name, key, harness.KEY_ID, source, indexes, CHUNK)
    stored = reference.read_segment(tmp_path, name, key)
    assert stored.segment == source
    assert stored.indexes == indexes
    sizes = reference_zstd.stored_sizes(tmp_path, name)
    assert len(sizes) == -(-n_bytes // CHUNK)
    assert sum(sizes) == name.path(tmp_path, "log").stat().st_size


def test_the_program_parses_the_writers_manifest(tmp_path):
    key, _, _ = reference.new_key_pair(tmp_path, harness.KEY_ID)
    source = harness.make_segment(SEED, SEGMENT_BYTES)
    name = reference.SegmentName.seeded(SEED, 1)
    reference_zstd.write_segment(tmp_path, name, key, harness.KEY_ID, source,
                                 harness.make_indexes(SEED, SEGMENT_BYTES), CHUNK)
    manifest = manifest_from_json(
        name.path(tmp_path, "rsm-manifest").read_text(),
        data_key_decoder=lambda text: reference.unwrap_key(
            key, base64.b64decode(text.partition(":")[2])
        ),
    )
    assert manifest.compression is True and manifest.compression_codec in (None, "zstd")
    index = manifest.chunk_index
    assert type(index).__name__ == "VariableSizeChunkIndex"
    assert index.original_chunk_size == CHUNK and index.original_file_size == SEGMENT_BYTES
    assert list(index.transformed_chunks) == reference_zstd.stored_sizes(tmp_path, name)
    assert manifest.encryption.data_key == reference.read_segment(tmp_path, name, key).data_key
    opts = DetransformOptions.from_manifest(manifest)
    assert opts.compression is True and opts.max_original_chunk_size == CHUNK


def _seeded_sizes(seed: int) -> list[int]:
    rng = random.Random(seed)
    count = rng.choice([0, 1, 2, 3, 12, 64, 65])
    base = rng.choice([0, 28, 3_267_984, 2**31 - 70_000])
    spread = rng.choice([0, 1, 255, 256, 65_535, 65_536, 69_999])
    sizes = [base + rng.randrange(spread + 1) for _ in range(count)]
    if sizes:
        sizes[-1] = rng.randrange(2**31 - 1)  # the ragged last chunk is not de-based
    return sizes


@pytest.mark.parametrize("seed", range(24))
def test_encode_chunk_sizes_is_upstreams_codec(seed):
    sizes = _seeded_sizes(seed)
    encoded = reference_zstd.encode_chunk_sizes(sizes)
    assert encoded == codec.encode_chunk_sizes(sizes)
    assert reference.decode_chunk_sizes(encoded) == sizes
    assert codec.decode_chunk_sizes(encoded) == sizes
    assert int.from_bytes(encoded[:4], "big") == len(sizes)
