"""The `kip405-aes-s3` deployment at 64 KiB chunks on the CPU: the
configuration file's `rsm` keys through `RemoteStorageManager` behind the
gateway, against `benchmark/s3_endpoint.py` in a process of its own, copied to
and read as the cells `aes-s3.copy` and `aes-s3.fetch_scan` do.

One scenario is run once (a traced deployment copies a segment of 11 MiB and a
little, so that two full 5 MiB parts and a short last one go out, reads it
back through the gateway, is asked for an altered chunk; an untraced one does
the same), the endpoint is stopped, and each test holds one of the
configuration's statements or one of the PR's to what was recorded: the plain
reference reads the three objects from the endpoint's directory, the replies
equal the source, the request counts of a copy are exact, no multipart upload
is left open, the manifest's Put is last, the altered chunk is refused, every
`s3.*` span lies under its parent, `/varz` has `s3`, an untraced deployment
records nothing. Then the part pipeline against the same endpoint at 5 MiB
parts (PR 38): objects of two parts, three with a short last one, an exact
multiple and under a part are stored as the serial stream stored them, a part
that fails leaves no upload open and is aborted after the others returned, a
skipped `_flush_part` leaves the object one part short. Then the endpoint
alone: a wrong secret or an altered body is 403, a short middle part
`EntityTooSmall`, Range gives 206 and 416, an object is absent until Complete.
"""

from __future__ import annotations

import http.client
import importlib.util
import json
import pathlib
import subprocess
import io
import sys
import threading
import time
import types

import pytest

pytest.importorskip("cryptography")

from tieredstorage_tpu.metrics.prometheus import PrometheusExporter  # noqa: E402
from tieredstorage_tpu.storage.s3.client import S3ApiError, S3Client  # noqa: E402
from tieredstorage_tpu.storage.s3.signer import SigV4Signer  # noqa: E402

REPO_ROOT = pathlib.Path(__file__).resolve().parents[1]
BENCHMARK = REPO_ROOT / "benchmark"
CHUNK = 64 << 10
PART = 5 << 20
SEGMENT_BYTES = 176 * CHUNK + 4321  # 11 MiB and a ragged chunk
SEED = 2**31 + 35
ACCESS, SECRET = "minioadmin", "minioadmin"


def _load(name: str):
    """A module of the benchmark, under a name no other test file's import
    of a `harness` or a `reference` can meet."""
    spec = importlib.util.spec_from_file_location(f"s3_deployment_{name}",
                                                  BENCHMARK / f"{name}.py")
    module = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = module  # dataclasses look their module up there
    spec.loader.exec_module(module)
    return module


harness, reference, s3_endpoint = _load("harness"), _load("reference"), _load("s3_endpoint")
CONFIG = json.loads((BENCHMARK / "configs" / "kip405-aes-s3.json").read_text())
BUCKET = CONFIG["rsm"]["storage.s3.bucket.name"]


class Endpoint:
    """`benchmark/s3_endpoint.py` in a process of its own."""

    def __init__(self, tmp: pathlib.Path, secret: str = SECRET) -> None:
        self.root = tmp / "s3"
        self.bucket_dir = self.root / BUCKET
        self.bucket_dir.mkdir(parents=True)
        self.journal_path = tmp / "journal.jsonl"
        self.process = subprocess.Popen(
            [sys.executable, str(BENCHMARK / "s3_endpoint.py"), "--root", str(self.root),
             "--journal", str(self.journal_path), "--access-key", ACCESS, "--secret-key", secret],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True,
        )
        ready = self.process.stdout.readline()
        assert ready.startswith(s3_endpoint.READY), ready
        self.port = int(ready.rsplit("port=", 1)[1])
        self.url = f"http://127.0.0.1:{self.port}"

    def client(self, secret: str = SECRET) -> S3Client:
        return S3Client(BUCKET, "us-east-1", endpoint_url=self.url, path_style=True,
                        access_key=ACCESS, secret_key=secret)

    def stop(self) -> list[dict]:
        if self.process.poll() is None:
            self.process.stdin.close()
            self.process.wait(timeout=30)
        self.process.stdout.close()
        return s3_endpoint.read_journal(self.journal_path)


def _deploy(tmp: pathlib.Path, endpoint: Endpoint, key_files, *, traced: bool):
    _, public, private = key_files
    store = harness.store_and_keys(tmp, public, private)  # storage.root: S3Storage ignores it
    return harness.Deployment({
        **CONFIG["rsm"], **store,
        "storage.s3.endpoint.url": endpoint.url,
        "chunk.size": CHUNK, "cache.device.bytes": 64 << 20,
        **({"tracing.enabled": True, "tracing.max.spans": 100_000} if traced else {}),
    })


def _bounded_read(deployment, md, start: int, n_bytes: int) -> bytes:
    from tieredstorage_tpu.sidecar import shimwire

    body, _ = deployment.client().post("/v1/fetch", [
        shimwire.encode_metadata(md), shimwire.encode_fetch_tail(start, start + n_bytes - 1),
    ])
    return body


def _settled(store) -> dict:
    """The store's counts once they stand still: the gateway reads on for a
    while for a reader that has left (`fetch_tail`)."""
    counts = store.counters()
    for _ in range(100):
        time.sleep(0.2)
        if counts == (counts := store.counters()):
            break
    return counts


def _run(tmp: pathlib.Path, endpoint: Endpoint, key_files, source, indexes, *, traced, ordinal):
    """Copy one segment, read it back, ask for an altered chunk; what a test
    may want of it afterwards."""
    run_tmp = tmp / ("traced" if traced else "untraced")
    run_tmp.mkdir()
    deployment = _deploy(run_tmp, endpoint, key_files, traced=traced)
    rsm = deployment.rsm
    try:
        store = rsm.storage_backend
        name = reference.SegmentName.seeded(SEED, ordinal)
        md = harness.segment_metadata(name, SEGMENT_BYTES)
        before = store.counters()
        deployment.client().copy(md, source, indexes)
        after_copy = store.counters()
        replies = {
            start: _bounded_read(deployment, md, start, n)
            for start, n in ((0, 100), (3 * CHUNK - 7, CHUNK), (SEGMENT_BYTES - 5000, 5000))
        }
        tail, _ = deployment.client().fetch_tail(md, 40 * CHUNK + 11, 16 << 10)
        # The canary: a short segment stored as files by the plain reference,
        # one bit of its first chunk's ciphertext altered.
        canary = reference.SegmentName.seeded(SEED, ordinal + 100)
        reference.write_segment(endpoint.bucket_dir, canary, key_files[0], harness.KEY_ID,
                                source[: 2 * CHUNK], indexes, CHUNK)
        with open(canary.path(endpoint.bucket_dir, "log"), "r+b") as log:
            log.seek(reference.IV + CHUNK // 2)
            byte = log.read(1)
            log.seek(-1, 1)
            log.write(bytes([byte[0] ^ 0x01]))
        canary_md = harness.segment_metadata(canary, 2 * CHUNK)
        clean = _bounded_read(deployment, canary_md, CHUNK, 64)
        try:
            _bounded_read(deployment, canary_md, 0, 64)
            altered_refused = None
        except harness.Failed as exc:
            altered_refused = str(exc)
        return types.SimpleNamespace(
            name=name, md=md, before=before, after_copy=after_copy, at_end=_settled(store),
            replies=replies, tail=tail, clean=clean, altered_refused=altered_refused,
            spans=rsm.tracer.spans(), recorded_spans=rsm.tracer.recorded_spans,
            varz=PrometheusExporter([], storage_backend=store).varz(),
            pool=store.client.http.pool,
        )
    finally:
        deployment.close()


@pytest.fixture(scope="module")
def scenario(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("s3-deployment")
    key_files = reference.new_key_pair(tmp, harness.KEY_ID)
    source = harness.make_segment(SEED, SEGMENT_BYTES)
    indexes = harness.make_indexes(SEED, SEGMENT_BYTES)
    endpoint = Endpoint(tmp)
    try:
        traced = _run(tmp, endpoint, key_files, source, indexes, traced=True, ordinal=0)
        untraced = _run(tmp, endpoint, key_files, source, indexes, traced=False, ordinal=1)
    finally:
        journal = endpoint.stop()
    return types.SimpleNamespace(
        traced=traced, untraced=untraced, journal=journal, source=source, indexes=indexes,
        key=key_files[0], bucket_dir=endpoint.bucket_dir,
    )


def _of(journal, name, ops=None):
    prefix = str(name.path(pathlib.PurePosixPath(""), ""))
    return [r for r in journal if r["key"].startswith(prefix) and (ops is None or r["op"] in ops)]


def _delta(run, name: str) -> int:
    return run.after_copy[name] - run.before[name]


# ------------------------------------------------------------ the deployment
def test_two_full_parts_and_a_short_last_one_go_out(scenario):
    parts = sorted(_of(scenario.journal, scenario.traced.name, {"UploadPart"}),
                   key=lambda r: r["part"])  # the journal's order is the replies'
    stored = (scenario.traced.name.path(scenario.bucket_dir, "log")).stat().st_size
    assert [(r["part"], r["status"]) for r in parts] == [(1, 200), (2, 200), (3, 200)]
    assert [r["bytes"] for r in parts] == [PART, PART, stored - 2 * PART]
    assert 0 < stored - 2 * PART < PART
    assert s3_endpoint.journal_parts_under_minimum(scenario.journal) == 0


def test_plain_reference_reads_the_three_objects_from_the_endpoints_directory(scenario):
    for run in (scenario.traced, scenario.untraced):
        stored = reference.read_segment(scenario.bucket_dir, run.name, scenario.key)
        assert stored.segment == scenario.source
        assert all(stored.indexes[n] == blob for n, blob in scenario.indexes.items())
    assert reference.read_segment(
        scenario.bucket_dir, scenario.traced.name, scenario.key
    ).data_key != reference.read_segment(
        scenario.bucket_dir, scenario.untraced.name, scenario.key
    ).data_key


@pytest.mark.parametrize("which", ["traced", "untraced"])
def test_a_scans_replies_equal_the_source(scenario, which):
    run, source = getattr(scenario, which), scenario.source
    for start, body in run.replies.items():
        assert body and body == source[start : start + len(body)]
    assert len(run.replies[SEGMENT_BYTES - 5000]) == 5000  # the ragged last chunk
    assert run.tail == source[40 * CHUNK + 11 : 40 * CHUNK + 11 + (16 << 10)]
    assert run.clean == source[CHUNK : CHUNK + 64]


@pytest.mark.parametrize("which", ["traced", "untraced"])
def test_request_counts_of_a_copy_are_exact(scenario, which):
    run = getattr(scenario, which)
    assert {n: _delta(run, f"{n}-requests") for n in (
        "create-multipart-upload", "upload-part", "complete-multipart-upload", "put-object",
        "get-object", "abort-multipart-upload",
    )} == {
        "create-multipart-upload": 1, "upload-part": 3, "complete-multipart-upload": 1,
        "put-object": 2, "get-object": 0, "abort-multipart-upload": 0,
    }
    stored = run.name.path(scenario.bucket_dir, "log").stat().st_size
    assert _delta(run, "bytes_sent_as_parts") == stored
    assert _delta(run, "bytes_received_ranged") == 0
    # and the endpoint's record says the same
    answered = sorted((r["op"], r["status"]) for r in _of(scenario.journal, run.name)
                      if r["op"] != "GetObject")
    assert answered == sorted(
        [("CreateMultipartUpload", 200), ("CompleteMultipartUpload", 200)]
        + [("UploadPart", 200)] * 3 + [("PutObject", 200)] * 2
    )


def test_reads_are_counted_as_ranged_bytes_on_few_connections(scenario):
    run = scenario.traced
    gets = run.at_end["get-object-requests"] - run.after_copy["get-object-requests"]
    received = run.at_end["bytes_received_ranged"] - run.after_copy["bytes_received_ranged"]
    ranged = [r for r in scenario.journal if r["op"] == "GetObject" and r["status"] == 206]
    assert gets > 0 and received > 0
    # bodies are read to their end, so what was counted is what the endpoint
    # sent (which, once the counts were taken, went on for a reader that had left)
    assert received <= sum(r["bytes"] for r in ranged)
    assert received == sum(
        s.attributes["bytes"] for s in run.spans
        if s.name == "s3.get_object" and s.attributes["status"] == 206
    )
    assert run.pool.created_total == run.at_end["connections_created"] < gets
    assert run.at_end["retries"] == 0
    assert all(run.at_end[f"{kind}-errors"] == 0 for kind in ("throttling", "server", "io"))


def test_no_multipart_upload_is_left_open_and_nothing_was_refused(scenario):
    assert s3_endpoint.journal_uploads_left_open(scenario.journal) == 0
    assert s3_endpoint.journal_requests_refused(scenario.journal) == 0
    assert not [p for p in (scenario.bucket_dir.parent / s3_endpoint.INCOMING).iterdir()]


@pytest.mark.parametrize("which", ["traced", "untraced"])
def test_the_manifests_put_is_the_last_change_of_a_copy(scenario, which):
    name = getattr(scenario, which).name
    prefix = str(name.path(pathlib.PurePosixPath(""), ""))
    last = s3_endpoint.journal_last_change(scenario.journal, prefix)
    assert (last["op"], last["status"], last["key"]) == ("PutObject", 200, prefix + "rsm-manifest")
    changes = _of(scenario.journal, name, set(s3_endpoint._CHANGES_THE_STORE))
    assert [r["key"].rsplit(".", 1)[1] for r in changes] == ["log"] * 5 + ["indexes", "rsm-manifest"]


@pytest.mark.parametrize("which", ["traced", "untraced"])
def test_the_altered_chunk_is_refused(scenario, which):
    refused = getattr(scenario, which).altered_refused
    assert refused is not None and "/v1/fetch answered" in refused


def test_every_s3_span_lies_under_its_parent(scenario):
    spans = scenario.traced.spans
    by_id = {s.span_id: s for s in spans}
    names = {s.name for s in spans}
    assert {"s3.upload_part", "s3.put_object", "s3.create_multipart_upload",
            "s3.complete_multipart_upload", "s3.sign", "s3.part_buffer", "s3.get_object",
            "s3.part_wait", "s3.part_handover"} <= names
    assert "s3.abort_multipart_upload" not in names
    calls = {"s3.upload_part", "s3.put_object", "s3.create_multipart_upload",
             "s3.complete_multipart_upload", "s3.get_object"}
    for span in spans:
        if not span.name.startswith("s3."):
            continue
        parent = by_id[span.parent_id]
        if span.name == "s3.sign":
            assert parent.name in calls
        elif span.name == "s3.get_object":
            assert parent.name in ("storage.fetch_chunks", "storage.fetch_manifest", "rsm.fetch_index")
        elif span.name == "s3.upload_part":
            # on a worker, in the copy's trace, under the writer's event of no
            # extent: not a child that `storage.upload`'s own time loses
            upload = by_id[parent.parent_id]
            assert (parent.name, upload.name) == ("s3.part_handover", "storage.upload")
            assert parent.duration_s == 0 and parent.attributes["part"] == span.attributes["part"]
            assert span.trace_id == upload.trace_id
            assert span.thread_id != upload.thread_id == parent.thread_id
        else:
            assert parent.name == "storage.upload" and span.thread_id == parent.thread_id
    # one signature a request
    assert sum(s.name == "s3.sign" for s in spans) == sum(s.name in calls for s in spans)
    parts = sorted((s.attributes["part"], s.attributes["bytes"]) for s in spans
                   if s.name == "s3.upload_part")
    assert parts[:2] == [(1, PART), (2, PART)] and len(parts) == 3
    # a multipart copy's close records its wait, short or long: the row is always there
    log_upload = by_id[next(s for s in spans if s.name == "s3.part_handover").parent_id]
    assert [s.name for s in spans if s.parent_id == log_upload.span_id].count("s3.part_wait") >= 2


def test_store_write_stays_the_upload_threads_own_time(scenario):
    """`store_write_s_per_gib.copy` reads `storage.upload`'s `self_s`: the
    total less what the spans on the upload's own thread cover, whatever the
    workers' PUTs overlapped."""
    spans = scenario.traced.spans
    uploads = [s for s in spans if s.name == "storage.upload"]
    own = 0.0
    for upload in uploads:
        children = [s for s in spans if s.parent_id == upload.span_id]
        assert all(s.thread_id == upload.thread_id for s in children)
        covered = sum(s.duration_s for s in children)  # one thread: they do not overlap
        assert covered <= upload.duration_s
        own += upload.duration_s - covered
    assert own > 0
    assert sum(s.duration_s for s in spans if s.name == "s3.upload_part") > 0


def test_the_part_counts_are_in_the_stores_counters(scenario):
    for run in (scenario.traced, scenario.untraced):
        assert _delta(run, "part_put_ns") > 0 and _delta(run, "part_wait_ns") > 0
        assert _delta(run, "part_wait_ns") < _delta(run, "part_put_ns") * 3
        assert 1 <= run.at_end["parts_in_flight_max"] <= 4
    waits = sum(s.duration_s for s in scenario.traced.spans if s.name == "s3.part_wait")
    assert _delta(scenario.traced, "part_wait_ns") == pytest.approx(waits * 1e9, rel=0.25)


def test_a_get_objects_span_ends_where_its_body_does(scenario):
    spans = scenario.traced.spans
    by_id = {s.span_id: s for s in spans}
    chunk_reads = [s for s in spans if s.name == "s3.get_object"
                   and by_id[s.parent_id].name == "storage.fetch_chunks"]
    assert chunk_reads
    for span in chunk_reads:
        parent = by_id[span.parent_id]
        assert span.attributes["status"] == 206 and span.attributes["ranged"] is True
        assert span.attributes["bytes"] == parent.attributes["bytes"] > 0
        sign = next(s for s in spans if s.parent_id == span.span_id)
        assert sign.end_s <= span.end_s <= parent.end_s and span.duration_s > sign.duration_s


def test_varz_has_s3_and_only_under_that_store(scenario):
    section = scenario.traced.varz["s3"]
    assert section == scenario.traced.at_end
    assert {"upload-part-requests", "get-object-requests", "io-errors", "connections_created",
            "retries", "bytes_sent_as_parts", "bytes_received_ranged",
            "part_put_ns", "part_wait_ns", "parts_in_flight_max"} <= set(section)
    assert "s3" not in PrometheusExporter([]).varz()

    class AnotherStore:
        pass

    assert "s3" not in PrometheusExporter([], storage_backend=AnotherStore()).varz()


def test_an_untraced_deployment_records_no_span(scenario):
    assert scenario.untraced.recorded_spans == 0 and scenario.untraced.spans == []
    assert scenario.traced.recorded_spans > 0


def test_the_tracer_reaches_the_store_only_through_the_rsm():
    from tieredstorage_tpu.storage.s3 import S3Storage
    from tieredstorage_tpu.utils.tracing import NOOP_TRACER, Tracer

    store = S3Storage()
    assert store.tracer is NOOP_TRACER
    store.configure({"s3.bucket.name": "b", "s3.endpoint.url": "http://127.0.0.1:9",
                     "root": "/ignored", "backend.class": "ignored"})
    assert store.client.tracer is NOOP_TRACER and store.part_size == PART
    tracer = Tracer(enabled=True)
    store.tracer = tracer
    assert store.client.tracer is tracer
    assert store.counters()["upload-part-requests"] == 0


# ------------------------------------------- the part pipeline, 5 MiB parts
SIZES = {"two-parts": 2 * PART, "three-parts-short-last": 2 * PART + 4321,
         "exact-multiple": 3 * PART, "under-a-part": PART - 1}


def _blob(n: int, salt: int) -> bytes:
    return (bytes((i * 7 + salt) % 251 for i in range(4099)) * (n // 4099 + 1))[:n]


@pytest.fixture(scope="module")
def pipeline(tmp_path_factory):
    """`S3Storage.upload` against the endpoint, once: the four sizes, two
    uploads at once, one whose second part fails while the third is still on
    its way, one whose second `_flush_part` is skipped as
    `benchmark/controls/part_dropped.py` skips it; then the journal."""
    from tieredstorage_tpu.storage.core import ObjectKey, StorageBackendException
    from tieredstorage_tpu.storage.s3 import S3Storage
    from tieredstorage_tpu.storage.s3.multipart import S3MultiPartOutputStream

    e = Endpoint(tmp_path_factory.mktemp("s3-pipeline"))
    store = S3Storage()
    store.configure({
        "s3.bucket.name": BUCKET, "s3.region": "us-east-1", "s3.endpoint.url": e.url,
        "s3.path.style.access.enabled": True,
        "aws.access.key.id": ACCESS, "aws.secret.access.key": SECRET,
    })
    out = types.SimpleNamespace(sources={}, uploaded={}, bucket_dir=e.bucket_dir)
    try:
        for salt, (case, size) in enumerate(SIZES.items()):
            out.sources[case] = _blob(size, salt)
            out.uploaded[case] = store.upload(io.BytesIO(out.sources[case]), ObjectKey(f"pipe/{case}"))

        twins = {f"twin-{i}": _blob(4 * PART + i, 40 + i) for i in range(2)}
        out.sources.update(twins)
        threads = [threading.Thread(target=store.upload, args=(io.BytesIO(data), ObjectKey(f"pipe/{case}")))
                   for case, data in twins.items()]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
        out.twins_done = not any(t.is_alive() for t in threads)

        put = store.client.upload_part

        def failing(key, upload_id, number, data):
            if number == 2:
                raise S3ApiError(500, "InternalError", "injected")
            if number == 3:
                time.sleep(0.5)  # still on its way when part 2 has failed
            return put(key, upload_id, number, data)

        store.client.upload_part = failing
        try:
            with pytest.raises(StorageBackendException) as raised:
                store.upload(io.BytesIO(_blob(6 * PART, 50)), ObjectKey("pipe/failed"))
            out.failure = raised.value
        finally:
            store.client.upload_part = put

        flush = S3MultiPartOutputStream._flush_part
        _load("controls/part_dropped").apply()
        try:
            out.sources["dropped"] = _blob(7 * PART + 99, 60)
            out.uploaded["dropped"] = store.upload(io.BytesIO(out.sources["dropped"]), ObjectKey("pipe/dropped"))
        finally:
            S3MultiPartOutputStream._flush_part = flush
        out.counters = store.counters()
        started = list(store._part_workers._executor._threads)
        store.close()
        out.workers_joined = bool(started) and not any(t.is_alive() for t in started)
    finally:
        store.close()
        out.journal = e.stop()
    return out


def _pipe(pipeline, case, ops=None):
    return [r for r in pipeline.journal if r["key"] == f"pipe/{case}" and (ops is None or r["op"] in ops)]


@pytest.mark.parametrize("case", list(SIZES))
def test_an_object_is_stored_as_the_serial_stream_stored_it(pipeline, case):
    source = pipeline.sources[case]
    assert pipeline.uploaded[case] == len(source)
    assert (pipeline.bucket_dir / "pipe" / case).read_bytes() == source
    full, last = divmod(len(source), PART)
    answered = sorted((r["op"], r["part"] or 0, r["bytes"], r["status"]) for r in _pipe(pipeline, case))
    if len(source) < PART:
        assert answered == [("PutObject", 0, len(source), 200)]
        return
    parts = [("UploadPart", n + 1, PART, 200) for n in range(full)]
    if last:
        parts.append(("UploadPart", full + 1, last, 200))
    assert [a for a in answered if a[0] == "UploadPart"] == parts
    assert sorted(a[0] for a in answered if a[0] != "UploadPart") == [
        "CompleteMultipartUpload", "CreateMultipartUpload"]
    # Complete is the last, and the short last part goes out after every full one
    assert _pipe(pipeline, case)[-1]["op"] == "CompleteMultipartUpload"
    if last:
        assert _pipe(pipeline, case, {"UploadPart"})[-1]["part"] == full + 1


def test_two_uploads_at_once_keep_their_parts_apart(pipeline):
    assert pipeline.twins_done
    for case in ("twin-0", "twin-1"):
        assert (pipeline.bucket_dir / "pipe" / case).read_bytes() == pipeline.sources[case]
        assert len({r["upload_id"] for r in _pipe(pipeline, case, {"UploadPart"})}) == 1


def test_a_failed_part_is_aborted_after_the_others_returned(pipeline):
    assert type(pipeline.failure.__cause__) is S3ApiError and pipeline.failure.__cause__.status == 500
    answered = _pipe(pipeline, "failed")
    ops = [r["op"] for r in answered]
    assert ops.count("AbortMultipartUpload") == 1 and ops[-1] == "AbortMultipartUpload"
    assert "CompleteMultipartUpload" not in ops
    assert 3 in {r["part"] for r in answered if r["op"] == "UploadPart"}  # waited for, then aborted
    assert 2 not in {r["part"] for r in answered}
    assert not (pipeline.bucket_dir / "pipe" / "failed").exists()


def test_a_skipped_flush_leaves_the_object_one_part_short(pipeline):
    source = pipeline.sources["dropped"]
    assert pipeline.uploaded["dropped"] == len(source)
    assert sorted(r["part"] for r in _pipe(pipeline, "dropped", {"UploadPart"})) == [1, 3, 4, 5, 6, 7, 8]
    assert (pipeline.bucket_dir / "pipe" / "dropped").read_bytes() == source[:PART] + source[2 * PART:]


def test_the_pipelines_journal_is_clean_and_its_workers_joined(pipeline):
    assert s3_endpoint.journal_uploads_left_open(pipeline.journal) == 0
    assert s3_endpoint.journal_requests_refused(pipeline.journal) == 0
    assert s3_endpoint.journal_parts_under_minimum(pipeline.journal) == 0
    assert not [p for p in (pipeline.bucket_dir.parent / s3_endpoint.INCOMING).iterdir()]
    assert pipeline.workers_joined
    assert pipeline.counters["parts_in_flight_max"] == 4
    assert pipeline.counters["part_put_ns"] > 0
    assert pipeline.counters["retries"] == 0


# --------------------------------------------------------- the endpoint alone
@pytest.fixture(scope="module")
def endpoint(tmp_path_factory):
    e = Endpoint(tmp_path_factory.mktemp("s3-endpoint"))
    yield e
    e.stop()


def _signed(endpoint: Endpoint, method: str, key: str, signed_body: bytes, sent_body: bytes,
            secret: str = SECRET, query: str = ""):
    """One request by hand: signed over `signed_body`, sent with `sent_body`."""
    path = f"/{BUCKET}/{key}"
    headers = SigV4Signer(ACCESS, secret, "us-east-1").sign(
        method, path, {}, {"Host": f"127.0.0.1:{endpoint.port}"}, signed_body,
    )
    conn = http.client.HTTPConnection("127.0.0.1", endpoint.port, timeout=30)
    try:
        conn.request(method, path + query, body=sent_body, headers=headers)
        response = conn.getresponse()
        return response.status, response.read(), dict(response.getheaders())
    finally:
        conn.close()


def test_a_wrong_secret_is_403_and_stores_nothing(endpoint):
    status, body, _ = _signed(endpoint, "PUT", "auth/wrong-secret", b"payload", b"payload",
                              secret="not-the-secret")
    assert status == 403 and b"SignatureDoesNotMatch" in body
    assert not (endpoint.bucket_dir / "auth" / "wrong-secret").exists()
    with pytest.raises(S3ApiError) as refused:
        endpoint.client(secret="not-the-secret").put_object("auth/wrong-secret", b"payload")
    assert refused.value.status == 403


def test_an_altered_body_is_403_and_stores_nothing(endpoint):
    status, body, _ = _signed(endpoint, "PUT", "auth/altered", b"payload", b"pAyload")
    assert status == 403 and b"XAmzContentSHA256Mismatch" in body
    assert not (endpoint.bucket_dir / "auth" / "altered").exists()
    status, _, _ = _signed(endpoint, "PUT", "auth/whole", b"payload", b"payload")
    assert status == 200 and (endpoint.bucket_dir / "auth" / "whole").read_bytes() == b"payload"


def test_an_unsigned_request_is_403(endpoint):
    conn = http.client.HTTPConnection("127.0.0.1", endpoint.port, timeout=30)
    try:
        conn.request("GET", f"/{BUCKET}/auth/whole")
        response = conn.getresponse()
        assert response.status == 403 and b"AccessDenied" in response.read()
    finally:
        conn.close()


def test_a_short_middle_part_is_entity_too_small(endpoint):
    client = endpoint.client()
    upload_id = client.create_multipart_upload("parts/short-middle")
    etags = [(1, client.upload_part("parts/short-middle", upload_id, 1, bytes(PART))),
             (2, client.upload_part("parts/short-middle", upload_id, 2, bytes(PART - 1))),
             (3, client.upload_part("parts/short-middle", upload_id, 3, b"tail"))]
    with pytest.raises(S3ApiError) as refused:
        client.complete_multipart_upload("parts/short-middle", upload_id, etags)
    assert (refused.value.status, refused.value.code) == (400, "EntityTooSmall")
    assert not (endpoint.bucket_dir / "parts" / "short-middle").exists()
    client.abort_multipart_upload("parts/short-middle", upload_id)
    client.close()


def test_complete_checks_each_parts_etag(endpoint):
    client = endpoint.client()
    upload_id = client.create_multipart_upload("parts/etag")
    etag = client.upload_part("parts/etag", upload_id, 1, b"one part")
    with pytest.raises(S3ApiError) as refused:
        client.complete_multipart_upload("parts/etag", upload_id, [(1, '"another"')])
    assert (refused.value.status, refused.value.code) == (400, "InvalidPart")
    client.complete_multipart_upload("parts/etag", upload_id, [(1, etag)])
    assert (endpoint.bucket_dir / "parts" / "etag").read_bytes() == b"one part"
    client.close()


def test_range_gives_206_and_416_and_a_missing_key_404(endpoint):
    client = endpoint.client()
    blob = bytes(range(256)) * 40
    client.put_object("ranges/blob", blob)
    status, headers, body = client.get_object_stream("ranges/blob", (100, 1099))
    with body:
        assert status == 206 and body.read() == blob[100:1100]
    assert headers["content-range"] == f"bytes 100-1099/{len(blob)}"
    status, headers, body = client.get_object_stream("ranges/blob", (len(blob) - 10, len(blob) + 500))
    with body:
        assert status == 206 and body.read() == blob[-10:]
    status, _, body = client.get_object_stream("ranges/blob")
    with body:
        assert status == 200 and body.read() == blob
    status, _, body = client.get_object_stream("ranges/blob", (len(blob), len(blob) + 5))
    with body:
        assert status == 416 and b"InvalidRange" in body.read()
    status, _, body = client.get_object_stream("ranges/none", (0, 5))
    with body:
        assert status == 404 and b"NoSuchKey" in body.read()
    assert client.bytes_received_ranged == 1000 + 10
    client.close()


def test_an_object_is_absent_until_complete(endpoint):
    client = endpoint.client()
    key, target = "whole/or-absent", endpoint.bucket_dir / "whole" / "or-absent"
    upload_id = client.create_multipart_upload(key)
    etags = [(1, client.upload_part(key, upload_id, 1, b"a" * PART)),
             (2, client.upload_part(key, upload_id, 2, b"b" * 1000))]
    assert not target.exists()
    assert not [p for p in endpoint.bucket_dir.rglob("*") if p.is_file() and "or-absent" in p.name]
    status, _, body = client.get_object_stream(key)
    with body:
        assert status == 404
    client.complete_multipart_upload(key, upload_id, etags)
    assert target.read_bytes() == b"a" * PART + b"b" * 1000
    client.close()


def test_an_aborted_upload_leaves_nothing(endpoint):
    client = endpoint.client()
    before = set((endpoint.root / s3_endpoint.INCOMING).iterdir())
    upload_id = client.create_multipart_upload("whole/aborted")
    client.upload_part("whole/aborted", upload_id, 1, b"c" * 4096)
    assert set((endpoint.root / s3_endpoint.INCOMING).iterdir()) != before
    client.abort_multipart_upload("whole/aborted", upload_id)
    assert set((endpoint.root / s3_endpoint.INCOMING).iterdir()) == before
    with pytest.raises(S3ApiError) as refused:
        client.upload_part("whole/aborted", upload_id, 2, b"late")
    assert (refused.value.status, refused.value.code) == (404, "NoSuchUpload")
    client.close()


def test_delete_object_and_delete_objects(endpoint):
    client = endpoint.client()
    for name in ("del/a", "del/b", "del/c"):
        client.put_object(name, b"x")
    client.delete_object("del/a")
    client.delete_objects(["del/b", "del/c", "del/never-there"])
    assert not [p for p in (endpoint.bucket_dir / "del").iterdir()]
    client.close()


def test_a_key_that_climbs_out_is_refused(endpoint):
    status, body, _ = _signed(endpoint, "PUT", "a/../../outside", b"x", b"x")
    assert status == 400 and b"InvalidArgument" in body


def test_the_journal_has_one_line_a_request_with_its_fields(tmp_path):
    e = Endpoint(tmp_path)
    client = e.client()
    client.put_object("j/one", b"12345")
    upload_id = client.create_multipart_upload("j/two")
    client.upload_part("j/two", upload_id, 1, b"123")
    status, _, body = client.get_object_stream("j/one", (1, 3))
    with body:
        body.read()
    client.close()
    journal = e.stop()
    assert [(r["op"], r["key"], r["status"], r["bytes"], r["part"]) for r in journal] == [
        ("PutObject", "j/one", 200, 5, None), ("CreateMultipartUpload", "j/two", 200, 0, None),
        ("UploadPart", "j/two", 200, 3, 1), ("GetObject", "j/one", 206, 3, None),
    ]
    assert journal[1]["upload_id"] == journal[2]["upload_id"] == upload_id
    assert [r["t"] for r in journal] == sorted(r["t"] for r in journal)
    assert all(r["bucket"] == BUCKET for r in journal)
    assert s3_endpoint.journal_uploads_left_open(journal) == 1
    assert e.process.returncode == 0
