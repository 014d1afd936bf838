"""The gateway's reply counter and its two metrics rehearsed off the chip, by
hand, beside `test_rehearsal.py`:

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q

`counters/gateway_reply.py`, `reply_view_share.fetch` and
`gateway_reply_ms_per_fetch.fetch` print a number on a fetch cell's traced
rehearsal (100: every block of a fetch's reply reaches the socket as a view;
the reply loop's own milliseconds a fetch); on what a gateway without the two
counts gives them the counter and the share return nothing and do not raise,
so the line leaves the share out, and the milliseconds still read the span the
parent has. Not part of tier-1: no number here is a device's.
"""

from __future__ import annotations

import json
import types

import pytest
from test_rehearsal import BENCHMARK, run, run_cell, tiny  # noqa: F401
from test_rehearsal_chunk_cache import tiny_cache  # noqa: F401

SHARE = "reply_view_share.fetch"
OWN_MS = "gateway_reply_ms_per_fetch.fetch"
FETCH_CELLS = ["aes.fetch_scan", "aes-cache.fetch_scan", "zstd-aes-cache.fetch_scan"]


@pytest.mark.parametrize("cell", ["aes.fetch_scan", "aes-cache.fetch_scan"])
def test_fetch_cell_traced_prints_every_reply_byte_a_view(tiny_cache, capsys, cell):
    assert run_cell(tiny_cache, cell, "--trace", "1", seconds="2.5") == 0
    out = capsys.readouterr().out.splitlines()
    result = json.loads(out[-1])
    assert result["correct"] is True
    assert result["metrics"][SHARE] == {"value": 100.0, "unit": "%"}
    assert result["metrics"][OWN_MS]["value"] > 0 and result["metrics"][OWN_MS]["unit"] == "ms"
    window = next(json.loads(line) for line in out if '"phase": "window", "seconds"' in line)
    counters = window["counters"]
    # what the reader took, and what the handler wrote on after it had left
    assert counters["reply_bytes_as_views"] == counters["reply_bytes_sent"] >= window["bytes"]
    span = next(json.loads(line) for line in out if '"span": "gateway.reply_stream"' in line)
    assert span["self_s"] <= span["total_s"]
    assert result["metrics"][OWN_MS]["value"] == pytest.approx(
        1e3 * span["self_s"] / window["fetches"], rel=1e-3
    )


@pytest.mark.parametrize("name,unit,better,source", [
    (SHARE, "%", "higher", "program_counter"),
    (OWN_MS, "ms", "lower", "program_span"),
])
def test_entries_name_the_fetch_cells_only(name, unit, better, source):
    bench = json.loads((BENCHMARK.parent / "BENCHMARK.json").read_text())
    (entry,) = [m for m in bench["per_layer"] if m["name"] == name]
    assert entry == {
        "name": name, "unit": unit, "better": better, "source": source,
        "layer": "gateway", "moves": "fetch_p50_ms", "workloads": FETCH_CELLS,
    }
    for cell in ("aes.copy", "zstd-aes.copy"):
        assert name not in {m["name"] for m in run.of_cell(bench["per_layer"], cell)}
    for cell in FETCH_CELLS:
        assert "fetch_p50_ms" in {m["name"] for m in run.of_cell(bench["end_to_end"], cell)}


def test_counter_and_share_return_nothing_without_the_new_counts():
    """What the parent commit gives them: a gateway with neither count."""
    counter = run.load(BENCHMARK / "counters" / "gateway_reply.py", "counter")
    parent = types.SimpleNamespace(gateway=types.SimpleNamespace(
        port=8080, copy_body_bytes=7, copy_body_bytes_written=7
    ))
    assert counter.read(parent) == {}
    change = types.SimpleNamespace(gateway=types.SimpleNamespace(
        port=8080, reply_bytes_sent=3000, reply_bytes_as_views=2000
    ))
    assert counter.read(change) == {"reply_bytes_sent": 3000, "reply_bytes_as_views": 2000}

    reader = run.load(BENCHMARK / "layer_metrics" / f"{SHARE}.py", "per-layer metric")
    observation = {
        "window": {"seconds": 20.0, "fetches": 2500, "bytes": 2500 << 20},
        "counters": {"windows": 600, "dispatches": 600},
        "spans": {"gateway.reply_stream": {"total_s": 16.0, "avg_s": 0.0064, "self_s": 14.5}},
    }
    assert reader.read(observation) is None
    observation["counters"].update(reply_bytes_sent=4000, reply_bytes_as_views=4000)
    assert reader.read(observation) == 100.0
    observation["counters"].update(reply_bytes_as_views=1000)
    assert reader.read(observation) == 25.0
    observation["counters"].update(reply_bytes_as_views=0)
    assert reader.read(observation) == 0.0
    observation["counters"].update(reply_bytes_sent=0)
    assert reader.read(observation) is None


def test_own_milliseconds_read_the_span_both_sides_have():
    reader = run.load(BENCHMARK / "layer_metrics" / f"{OWN_MS}.py", "per-layer metric")
    observation = {
        "window": {"seconds": 20.0, "fetches": 2500, "bytes": 2500 << 20},
        "counters": {},
        "spans": {"gateway.reply_stream": {"total_s": 16.0, "avg_s": 0.0064, "self_s": 14.5}},
    }
    assert reader.read(observation) == pytest.approx(5.8)
    # a program with no such span, a summary with no `self_s`, an untraced run, no fetches
    observation["spans"] = {"gateway.fetch": {"total_s": 17.0, "avg_s": 0.0068, "self_s": 0.5}}
    assert reader.read(observation) is None
    observation["spans"] = {"gateway.reply_stream": {"total_s": 16.0, "avg_s": 0.0064}}
    assert reader.read(observation) is None
    del observation["spans"]
    assert reader.read(observation) is None
    observation["spans"] = {"gateway.reply_stream": {"total_s": 16.0, "avg_s": 0.0064, "self_s": 14.5}}
    observation["window"]["fetches"] = 0
    assert reader.read(observation) is None
