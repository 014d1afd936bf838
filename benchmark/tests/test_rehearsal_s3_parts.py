"""The part pipeline's two metrics rehearsed off the chip, by hand, beside
`test_rehearsal_s3.py`:

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests/test_rehearsal_s3_parts.py -q

`aes-s3.copy`'s traced rehearsal (an 11 MiB segment: two full 5 MiB parts and a
short last one) prints `s3_part_wait_s_per_gib.copy` and
`s3_put_hidden_share.copy`, the share within 0-100, the request count and the
journal's numbers as before; `part_dropped` still comes out not correct, and
the run ends. On what a program without the span and the two counts gives them
(the parent of PR 38) the readers and `counters/s3_parts.py` return nothing and
do not raise, so the line leaves the metrics out. Not part of tier-1: no number
here is a device's.
"""

from __future__ import annotations

import json
import types

from test_rehearsal import BENCHMARK, last_line, run, run_cell, tiny  # noqa: F401
from test_rehearsal_s3 import (  # noqa: F401
    COPY, JOURNAL_NUMBERS, long_segments, tiny_s3, window_line,
)

WAIT, HIDDEN = "s3_part_wait_s_per_gib.copy", "s3_put_hidden_share.copy"


def test_copy_cell_traced_prints_both(tiny_s3, capsys):
    long_segments(tiny_s3)
    assert run_cell(tiny_s3, COPY, "--trace", "1", seconds="4") == 0
    out = capsys.readouterr().out.splitlines()
    result = json.loads(out[-1])
    assert result["correct"] is True
    assert all(result["compared"][n]["value"] == 0 for n in JOURNAL_NUMBERS)
    values = {name: m["value"] for name, m in result["metrics"].items()}
    assert result["metrics"][WAIT]["unit"] == "s/GiB" and result["metrics"][HIDDEN]["unit"] == "%"
    assert values[WAIT] > 0  # a multipart copy's close always records its wait
    assert 0 <= values[HIDDEN] <= 100
    # the mechanism engaged, and what it must not move did not
    assert values["s3_requests_per_copy.copy"] == 7 and values["s3_request_errors.copy"] == 0
    assert values["s3_part_put_s_per_gib.copy"] > 0 and values["s3_part_buffer_s_per_gib.copy"] > 0
    assert values["store_write_s_per_gib.copy"] > 0  # the upload thread's own time, not ~0
    counters = window_line(out)["counters"]
    assert counters["s3_part_put_ns"] > 0 and counters["s3_part_wait_ns"] > 0
    spans = {json.loads(line)["span"] for line in out if line.startswith('{"span"')}
    assert {"s3.part_wait", "s3.part_handover", "s3.part_buffer", "s3.upload_part"} <= spans


def test_part_dropped_still_comes_out_not_correct_and_ends(tiny_s3, capsys):
    from tieredstorage_tpu.storage.s3.multipart import S3MultiPartOutputStream

    long_segments(tiny_s3)
    saved = S3MultiPartOutputStream._flush_part
    try:
        run_cell(tiny_s3, COPY, "--control", "part_dropped", seconds="4")
        result = last_line(capsys)
    finally:
        S3MultiPartOutputStream._flush_part = saved
    assert result["correct"] is False and result["failed"] == 0
    assert result["compared"]["copies_unreadable"]["value"] > 0
    assert all(result["compared"][n]["value"] == 0 for n in JOURNAL_NUMBERS)


def test_entries_name_the_s3_copy_cell_only():
    bench = json.loads((BENCHMARK.parent / "BENCHMARK.json").read_text())
    entries = {m["name"]: m for m in bench["per_layer"] if m["name"] in (WAIT, HIDDEN)}
    common = {"layer": "RSM and storage", "moves": "copy_gib_s", "workloads": [COPY]}
    assert entries == {
        WAIT: {"name": WAIT, "unit": "s/GiB", "better": "lower", "source": "program_span", **common},
        HIDDEN: {"name": HIDDEN, "unit": "%", "better": "higher", "source": "program_counter", **common},
    }
    assert [m["name"] for m in bench["per_layer"][-2:]] == [WAIT, HIDDEN]
    for cell in ("aes.copy", "zstd-aes.copy", "aes-s3.fetch_scan"):
        assert not {WAIT, HIDDEN} & {m["name"] for m in run.of_cell(bench["per_layer"], cell)}


def test_counter_and_readers_return_nothing_without_the_new_counts():
    """What the parent commit gives them: `counters()` without the part
    workers' counts, no `s3.part_wait` span; and another store."""
    counter = run.load(BENCHMARK / "counters" / "s3_parts.py", "counter")
    S3Storage = type("S3Storage", (), {"counters": lambda self: self.counts})

    def deployment(store):
        return types.SimpleNamespace(rsm=types.SimpleNamespace(storage_backend=store))

    parent = S3Storage()
    parent.counts = {"upload-part-requests": 52, "retries": 0, "bytes_sent_as_parts": 1 << 28}
    assert counter.read(deployment(parent)) == {}
    change = S3Storage()
    change.counts = {**parent.counts, "part_put_ns": 600, "part_wait_ns": 60, "parts_in_flight_max": 4}
    assert counter.read(deployment(change)) == {"s3_part_put_ns": 600, "s3_part_wait_ns": 60}
    wrapped = types.SimpleNamespace(delegate=change)
    assert counter.read(deployment(wrapped)) == {"s3_part_put_ns": 600, "s3_part_wait_ns": 60}
    assert counter.read(deployment(type("FileSystemStorage", (), {})())) == {}

    wait = run.load(BENCHMARK / "layer_metrics" / f"{WAIT}.py", "per-layer metric")
    hidden = run.load(BENCHMARK / "layer_metrics" / f"{HIDDEN}.py", "per-layer metric")
    observation = {
        "window": {"seconds": 20.0, "copies": 12, "bytes": 3 << 30},
        "counters": {"s3_requests": 672, "s3_upload_part_requests": 624},
        "spans": {"storage.upload": {"total_s": 17.0, "avg_s": 1.4, "self_s": 1.9},
                  "s3.upload_part": {"total_s": 7.7, "avg_s": 0.012, "self_s": 5.5}},
    }
    assert wait.read(observation) is None and hidden.read(observation) is None
    observation["spans"]["s3.part_wait"] = {"total_s": 0.6, "avg_s": 0.02, "self_s": 0.6}
    observation["counters"].update(s3_part_put_ns=8_000_000_000, s3_part_wait_ns=600_000_000)
    assert wait.read(observation) == 0.6 / 3
    assert hidden.read(observation) == 92.5
    # a window that put no part (another store's counter file reads nothing; a fetch cell): nothing
    observation["counters"].update(s3_part_put_ns=0, s3_part_wait_ns=0)
    assert hidden.read(observation) is None
