"""The two S3 cells rehearsed off the chip, by hand, beside `test_rehearsal.py`:

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests/test_rehearsal_s3.py -q

`aes-s3.copy` and `aes-s3.fetch_scan` end to end through `run.py`'s own path at
64 KiB chunks, against `benchmark/s3_endpoint.py` in a process of its own, the
journal's four numbers among the compared ones. The copy cell's segment is
11 MiB and a little, so that two full 5 MiB parts and a short last one go out
(the other configurations' 1.5 MiB would be one PutObject). `part_dropped` and
`verify_skipped` come out as not correct; the readers of the new per-layer
metrics return nothing, and do not raise, on what a program without the new
spans and counts gives them. Not part of tier-1: no number here is a device's.
"""

from __future__ import annotations

import json

import pytest
from test_rehearsal import BENCHMARK, last_line, run, run_cell, tiny  # noqa: F401

COPY, FETCH = "aes-s3.copy", "aes-s3.fetch_scan"
JOURNAL_NUMBERS = [
    "multipart_uploads_left_open", "requests_refused_by_store", "manifest_put_not_last",
    "parts_under_minimum",
]
NEW_METRICS = {
    COPY: ["s3_part_put_s_per_gib.copy", "s3_part_buffer_s_per_gib.copy", "s3_sign_s_per_gib.copy",
           "s3_requests_per_copy.copy", "s3_request_errors.copy"],
    FETCH: ["s3_get_ms_per_fetch.fetch", "s3_connections_dialled.fetch", "s3_request_errors.fetch"],
}
CHUNK = 64 << 10
COPY_SEGMENT = 176 * CHUNK + 4321  # 11 MiB and a ragged chunk: parts of 5, 5 and ~1 MiB


@pytest.fixture
def tiny_s3(tiny):
    """`tiny` with the copy cell's segment long enough for a multipart upload
    and the S3 scan's reads cut like `catchup_scan`'s."""
    path = tiny / "traffic" / "catchup_scan_s3.json"
    scan = json.loads(path.read_text())
    scan["parameters"].update(
        segments=3, read_bytes=16 << 10, step_bytes=15 << 10, first_request=90,
        stretch_after=3, stretch_seconds=0.5,
    )
    path.write_text(json.dumps(scan))
    path = tiny / "traffic" / "copy_backlog_s3.json"
    backlog = json.loads(path.read_text())
    backlog["parameters"].update(max_copies=3, check_copies=3)
    path.write_text(json.dumps(backlog))
    return tiny


def long_segments(here) -> None:
    path = here / "configs" / "kip405-aes-s3.json"
    config = json.loads(path.read_text())
    config["sizes"] = {**config["sizes"], "segment_bytes": COPY_SEGMENT}
    path.write_text(json.dumps(config))


def window_line(out: list) -> dict:
    return next(json.loads(line) for line in out if '"phase": "window", "seconds"' in line)


def test_copy_cell_untraced(tiny_s3, capsys):
    long_segments(tiny_s3)
    assert run_cell(tiny_s3, COPY, seconds="4") == 0
    result = last_line(capsys)
    assert result["correct"] is True and result["failed"] == 0 and result["attempted"] > 0
    assert sorted(result["metrics"]) == ["copy_gib_s", "setup_s"]
    assert set(JOURNAL_NUMBERS) <= set(result["compared"])
    assert all(c["value"] == 0 for c in result["compared"].values())


def test_copy_cell_traced(tiny_s3, capsys):
    long_segments(tiny_s3)
    assert run_cell(tiny_s3, COPY, "--trace", "1", seconds="4") == 0
    out = capsys.readouterr().out.splitlines()
    result = json.loads(out[-1])
    assert result["correct"] is True
    bench = json.loads((tiny_s3.parent / "BENCHMARK.json").read_text())
    expected = {m["name"] for m in run.of_cell(bench["per_layer"], COPY)}
    assert set(NEW_METRICS[COPY]) <= expected
    assert {n for n in expected if not n.startswith(("gcm_roofline", "device_idle"))} <= set(
        result["metrics"]
    )
    values = {name: m["value"] for name, m in result["metrics"].items()}
    # Create + 3 UploadPart + Complete, and a PutObject each for indexes and manifest
    assert values["s3_requests_per_copy.copy"] == 7
    assert values["s3_request_errors.copy"] == 0
    assert values["s3_part_put_s_per_gib.copy"] > values["s3_sign_s_per_gib.copy"] > 0
    assert values["s3_part_buffer_s_per_gib.copy"] > 0
    counters = window_line(out)["counters"]
    copies = window_line(out)["copies"]
    assert counters["s3_upload_part_requests"] == 3 * copies
    assert counters["s3_put_object_requests"] == 2 * copies
    assert counters["s3_get_object_requests"] == 0
    assert counters["s3_connections_created"] == 0
    spans = {json.loads(line)["span"] for line in out if line.startswith('{"span"')}
    assert {"s3.upload_part", "s3.put_object", "s3.create_multipart_upload",
            "s3.complete_multipart_upload", "s3.sign", "s3.part_buffer"} <= spans


def test_fetch_cell_untraced(tiny_s3, capsys):
    assert run_cell(tiny_s3, FETCH) == 0
    result = last_line(capsys)
    assert result["correct"] is True and result["failed"] == 0 and result["attempted"] > 0
    assert sorted(result["metrics"]) == ["fetch_mib_s", "fetch_p50_ms", "fetch_p95_ms", "setup_s"]
    assert set(JOURNAL_NUMBERS) <= set(result["compared"])
    assert all(c["value"] == 0 for c in result["compared"].values())


def test_fetch_cell_traced(tiny_s3, capsys):
    assert run_cell(tiny_s3, FETCH, "--trace", "1", seconds="2.5") == 0
    out = capsys.readouterr().out.splitlines()
    result = json.loads(out[-1])
    assert result["correct"] is True
    bench = json.loads((tiny_s3.parent / "BENCHMARK.json").read_text())
    expected = {m["name"] for m in run.of_cell(bench["per_layer"], FETCH)}
    assert set(NEW_METRICS[FETCH]) <= expected
    assert {n for n in expected if not n.startswith(("gcm_roofline", "device_idle"))} <= set(
        result["metrics"]
    )
    values = {name: m["value"] for name, m in result["metrics"].items()}
    assert values["s3_request_errors.fetch"] == 0
    # One a handler thread that reads at once: the gateway reads on for a
    # reader that has left while the next request's handler starts; never one a read.
    assert values["s3_connections_dialled.fetch"] <= 8 < window_line(out)["counters"]["s3_get_object_requests"]
    assert 0 < values["s3_get_ms_per_fetch.fetch"] <= values["store_read_ms_per_fetch.fetch"]
    window = window_line(out)
    assert window["counters"]["s3_get_object_requests"] > 0
    assert window["counters"]["s3_bytes_received_ranged"] > 0
    assert window["counters"]["s3_upload_part_requests"] == 0


def test_another_store_reads_noughts(tiny_s3, capsys):
    """`counters/s3_requests.py` is read in every cell."""
    assert run_cell(tiny_s3, "aes.copy") == 0
    out = capsys.readouterr().out.splitlines()
    counters = window_line(out)["counters"]
    assert {k: v for k, v in counters.items() if k.startswith("s3_")} and not any(
        v for k, v in counters.items() if k.startswith("s3_")
    )


def test_part_dropped_comes_out_not_correct(tiny_s3, capsys):
    from tieredstorage_tpu.storage.s3.multipart import S3MultiPartOutputStream

    long_segments(tiny_s3)
    saved = S3MultiPartOutputStream._flush_part
    try:
        run_cell(tiny_s3, COPY, "--control", "part_dropped", seconds="4")
        result = last_line(capsys)
    finally:
        S3MultiPartOutputStream._flush_part = saved
    assert result["correct"] is False
    assert result["compared"]["copies_unreadable"]["value"] > 0
    # the store completed what it was given: the journal has nothing to say
    assert all(result["compared"][n]["value"] == 0 for n in JOURNAL_NUMBERS)


def test_verify_skipped_comes_out_not_correct(tiny_s3, capsys):
    from tieredstorage_tpu.transform import tpu

    saved = tpu.hmac
    try:
        run_cell(tiny_s3, FETCH, "--control", "verify_skipped")
        result = last_line(capsys)
    finally:
        tpu.hmac = saved
    assert result["correct"] is False
    assert result["compared"]["altered_chunk_served"]["value"] == 1


@pytest.mark.parametrize("metric", NEW_METRICS[COPY] + NEW_METRICS[FETCH])
def test_new_readers_return_nothing_without_the_new_counts(metric):
    """What a program without the spans and the counter file gives them."""
    reader = run.load(BENCHMARK / "layer_metrics" / f"{metric}.py", "per-layer metric")
    observation = {
        "window": {"seconds": 20.0, "fetches": 1500, "copies": 9, "bytes": 9 << 28},
        "counters": {"windows": 1400, "bytes_in": 1400 << 22, "hot_hits": 0},
        "spans": {"storage.upload": {"total_s": 9.0, "avg_s": 1.0, "self_s": 2.5},
                  "storage.fetch_chunks": {"total_s": 9.0, "avg_s": 0.006, "self_s": 9.0}},
    }
    assert reader.read(observation) is None
