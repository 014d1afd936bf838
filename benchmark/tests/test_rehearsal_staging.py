"""The staging ring's counter and metric rehearsed off the chip, by hand, beside
`test_rehearsal.py`:

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q

`counters/staging_buffers.py` and `staging_reuse_share.copy` print a number on
a copy cell's traced rehearsal; on what a program without the two counts gives
them they return nothing and do not raise, so the line leaves the metric out.
Not part of tier-1: no number here is a device's.
"""

from __future__ import annotations

import json
import types

import pytest
from test_rehearsal import BENCHMARK, run, run_cell, tiny  # noqa: F401

from tieredstorage_tpu.transform.tpu import TpuTransformBackend

METRIC = "staging_reuse_share.copy"
#: As they are when the files are collected, before any test has run.
PRISTINE = (TpuTransformBackend._encrypt_finish, TpuTransformBackend._decrypt_window)


@pytest.fixture(autouse=True)
def pristine_backend():
    """`controls/chunk_altered.py` patches both methods for good, and
    `test_rehearsal_chunk_cache.py`, which runs before this file, puts back
    only `_decrypt_window`: a copy cell run after it would store altered
    chunks."""
    TpuTransformBackend._encrypt_finish, TpuTransformBackend._decrypt_window = PRISTINE


@pytest.mark.parametrize("cell", ["aes.copy", "zstd-aes.copy"])
def test_copy_cell_traced_prints_the_reuse_share(tiny, capsys, cell):
    assert run_cell(tiny, cell, "--trace", "1", seconds="2.5") == 0
    out = capsys.readouterr().out.splitlines()
    result = json.loads(out[-1])
    assert result["correct"] is True
    # the warm-up copy has filled the ring: the timed copies allocate nothing
    assert 90 <= result["metrics"][METRIC]["value"] <= 100
    assert result["metrics"][METRIC]["unit"] == "%"
    window = next(json.loads(line) for line in out if '"phase": "window", "seconds"' in line)
    counters = window["counters"]
    assert 0 < counters["staging_reused"] <= counters["staging_acquired"]
    # two windows and four index rows a copy at these sizes, a buffer each
    assert counters["staging_acquired"] == counters["windows"]


def test_fetch_cell_is_not_asked_for_the_metric():
    bench = json.loads((BENCHMARK.parent / "BENCHMARK.json").read_text())
    entry = bench["per_layer"][-1]
    assert entry["name"] == METRIC and entry["workloads"] == ["aes.copy", "zstd-aes.copy"]
    assert METRIC not in {m["name"] for m in run.of_cell(bench["per_layer"], "aes.fetch_scan")}


def test_counter_and_reader_return_nothing_without_the_new_counts():
    """What the parent commit gives them: a `DispatchStats` with neither field."""
    counter = run.load(BENCHMARK / "counters" / "staging_buffers.py", "counter")
    parent = types.SimpleNamespace(backend=types.SimpleNamespace(
        dispatch_stats=types.SimpleNamespace(windows=8, rows=68, dispatches=8)
    ))
    assert counter.read(parent) == {}
    change = types.SimpleNamespace(backend=types.SimpleNamespace(
        dispatch_stats=types.SimpleNamespace(staging_acquired=8, staging_reused=6)
    ))
    assert counter.read(change) == {"staging_acquired": 8, "staging_reused": 6}

    reader = run.load(BENCHMARK / "layer_metrics" / f"{METRIC}.py", "per-layer metric")
    observation = {
        "window": {"seconds": 20.0, "copies": 12, "bytes": 12 << 28},
        "counters": {"windows": 96, "dispatches": 96, "bytes_in": 12 << 28},
        "spans": {"transform.pack": {"total_s": 3.9, "avg_s": 0.04, "self_s": 3.9}},
    }
    assert reader.read(observation) is None
    observation["counters"].update(staging_acquired=96, staging_reused=0)
    assert reader.read(observation) == 0.0
    observation["counters"].update(staging_reused=90)
    assert reader.read(observation) == 93.75
    observation["counters"].update(staging_acquired=0, staging_reused=0)
    assert reader.read(observation) is None
