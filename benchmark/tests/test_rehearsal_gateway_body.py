"""The gateway's body counter and metric rehearsed off the chip, by hand, beside
`test_rehearsal.py`:

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q

`counters/gateway_body.py` and `gateway_body_writes_per_byte.copy` print a
number on a copy cell's traced rehearsal (1.0: a copy's body is written locally
once); on what a gateway without the two counts gives them they return nothing
and do not raise, so the line leaves the metric out. Not part of tier-1: no
number here is a device's.
"""

from __future__ import annotations

import json
import types

import pytest
from test_rehearsal import BENCHMARK, run, run_cell, tiny  # noqa: F401
from test_rehearsal_staging import pristine_backend  # noqa: F401

METRIC = "gateway_body_writes_per_byte.copy"
SPANS = "gateway_spool_decode_s_per_gib.copy"


@pytest.mark.parametrize("cell", ["aes.copy", "zstd-aes.copy"])
def test_copy_cell_traced_prints_one_write_per_byte(tiny, capsys, cell):
    assert run_cell(tiny, cell, "--trace", "1", seconds="2.5") == 0
    out = capsys.readouterr().out.splitlines()
    result = json.loads(out[-1])
    assert result["correct"] is True
    # short of 1.0 by the metadata and section headers, which reach no file
    assert result["metrics"][METRIC]["value"] == pytest.approx(1.0, abs=1e-3)
    assert result["metrics"][METRIC]["value"] < 1.0 and result["metrics"][METRIC]["unit"] == "count"
    # both of the gateway's spans are still there for the accepted metric
    assert result["metrics"][SPANS]["value"] > 0
    window = next(json.loads(line) for line in out if '"phase": "window", "seconds"' in line)
    counters = window["counters"]
    # a body is the sections and a few hundred bytes of metadata and headers
    assert 0 < counters["copy_body_bytes"] - counters["copy_body_bytes_written"] < 1024 * window["copies"]
    assert counters["copy_body_bytes_written"] > window["bytes"]


def test_entry_names_the_copy_cells_only():
    bench = json.loads((BENCHMARK.parent / "BENCHMARK.json").read_text())
    (entry,) = [m for m in bench["per_layer"] if m["name"] == METRIC]
    assert entry == {
        "name": METRIC, "unit": "count", "better": "lower", "source": "program_counter",
        "layer": "gateway", "moves": "copy_gib_s", "workloads": ["aes.copy", "zstd-aes.copy"],
    }
    for cell in ("aes.fetch_scan", "aes-cache.fetch_scan"):
        assert METRIC not in {m["name"] for m in run.of_cell(bench["per_layer"], cell)}


def test_counter_and_reader_return_nothing_without_the_new_counts():
    """What the parent commit gives them: a gateway with neither count."""
    counter = run.load(BENCHMARK / "counters" / "gateway_body.py", "counter")
    parent = types.SimpleNamespace(gateway=types.SimpleNamespace(port=8080))
    assert counter.read(parent) == {}
    change = types.SimpleNamespace(gateway=types.SimpleNamespace(
        port=8080, copy_body_bytes=1000, copy_body_bytes_written=900
    ))
    assert counter.read(change) == {"copy_body_bytes": 1000, "copy_body_bytes_written": 900}

    reader = run.load(BENCHMARK / "layer_metrics" / f"{METRIC}.py", "per-layer metric")
    observation = {
        "window": {"seconds": 20.0, "copies": 12, "bytes": 12 << 28},
        "counters": {"windows": 96, "dispatches": 96, "bytes_in": 12 << 28},
        "spans": {"gateway.spool": {"total_s": 3.1, "avg_s": 0.26, "self_s": 3.1}},
    }
    assert reader.read(observation) is None
    observation["counters"].update(copy_body_bytes=4000, copy_body_bytes_written=4000)
    assert reader.read(observation) == 1.0
    observation["counters"].update(copy_body_bytes_written=8000)
    assert reader.read(observation) == 2.0
    observation["counters"].update(copy_body_bytes=0, copy_body_bytes_written=0)
    assert reader.read(observation) is None
