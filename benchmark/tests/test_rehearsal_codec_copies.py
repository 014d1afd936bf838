"""The host codec's copy counter and metric rehearsed off the chip, by hand,
beside `test_rehearsal.py`:

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q

`counters/codec_copies.py` and `codec_copied_bytes_per_byte.copy` print 0.0 on
`zstd-aes.copy`'s traced rehearsal (each chunk compressed where it lies, the
frames read by the pack as views); on what a program without the two counts
gives them they return nothing and do not raise, so the line leaves the metric
out. Not part of tier-1: no number here is a device's.
"""

from __future__ import annotations

import json
import types

from test_rehearsal import BENCHMARK, run, run_cell, tiny  # noqa: F401
from test_rehearsal_staging import pristine_backend  # noqa: F401

METRIC = "codec_copied_bytes_per_byte.copy"


def test_zstd_copy_cell_traced_prints_no_copied_byte(tiny, capsys):
    assert run_cell(tiny, "zstd-aes.copy", "--trace", "1", seconds="2.5") == 0
    out = capsys.readouterr().out.splitlines()
    result = json.loads(out[-1])
    assert result["correct"] is True
    assert result["metrics"][METRIC] == {"value": 0.0, "unit": "count"}
    # the codec still runs under its span, and the ring holds its frame buffer
    assert result["metrics"]["compress_s_per_gib.copy"]["value"] > 0
    assert result["metrics"]["staging_reuse_share.copy"]["value"] >= 90
    window = next(json.loads(line) for line in out if '"phase": "window", "seconds"' in line)
    counters = window["counters"]
    assert counters["codec_bytes_copied"] == 0
    assert counters["codec_bytes_in"] == window["bytes"]


def test_entry_names_the_compressing_copy_cell_only():
    bench = json.loads((BENCHMARK.parent / "BENCHMARK.json").read_text())
    (entry,) = [m for m in bench["per_layer"] if m["name"] == METRIC]
    assert entry == {
        "name": METRIC, "unit": "count", "better": "lower", "source": "program_counter",
        "layer": "host codec", "moves": "copy_gib_s", "workloads": ["zstd-aes.copy"],
    }
    for cell in ("aes.copy", "aes-s3.copy", "zstd-aes-cache.fetch_scan"):
        assert METRIC not in {m["name"] for m in run.of_cell(bench["per_layer"], cell)}


def test_counter_and_reader_return_nothing_without_the_new_counts():
    """What the parent commit gives them: a `DispatchStats` with neither field."""
    counter = run.load(BENCHMARK / "counters" / "codec_copies.py", "counter")
    parent = types.SimpleNamespace(backend=types.SimpleNamespace(
        dispatch_stats=types.SimpleNamespace(windows=8, staging_acquired=8)
    ))
    assert counter.read(parent) == {}
    change = types.SimpleNamespace(backend=types.SimpleNamespace(
        dispatch_stats=types.SimpleNamespace(codec_bytes_in=1000, codec_bytes_copied=0)
    ))
    assert counter.read(change) == {"codec_bytes_in": 1000, "codec_bytes_copied": 0}

    reader = run.load(BENCHMARK / "layer_metrics" / f"{METRIC}.py", "per-layer metric")
    observation = {
        "window": {"seconds": 20.0, "copies": 12, "bytes": 12 << 28},
        "counters": {"windows": 96, "dispatches": 96, "bytes_in": 9 << 28},
        "spans": {"transform.compress": {"total_s": 8.1, "avg_s": 0.17, "self_s": 8.1}},
    }
    assert reader.read(observation) is None
    observation["counters"].update(codec_bytes_in=4000, codec_bytes_copied=0)
    assert reader.read(observation) == 0.0
    observation["counters"].update(codec_bytes_copied=7120)
    assert reader.read(observation) == 1.78
    # an encrypt-only copy hands the codec nothing: nothing to read
    observation["counters"].update(codec_bytes_in=0, codec_bytes_copied=0)
    assert reader.read(observation) is None
