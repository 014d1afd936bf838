"""The chunk-cache cell rehearsed off the chip, by hand, beside `test_rehearsal.py`
(whose `CELLS` are the cells PR 26 made):

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q

`aes-cache.fetch_scan` end to end at 64 KiB chunks with the prefetch cut to the
same four chunks ahead and the cache to a third of what is stored; its controls
come out as not correct; the readers of the new per-layer metrics return
nothing, and do not raise, on what a program without the new counts gives
them. Not part of tier-1: no number here is a device's.
"""

from __future__ import annotations

import json

import pytest
from test_rehearsal import BENCHMARK, last_line, run, run_cell, tiny  # noqa: F401

CELL = "aes-cache.fetch_scan"
NEW_METRICS = [
    "cache_hit_share.fetch", "inflight_join_share.fetch", "cache_get_ms_per_fetch.fetch",
    "prefetch_ms_per_chunk.fetch", "rows_per_decrypt_window.fetch", "decrypts_per_chunk.fetch",
    "cache_degradations.fetch",
]


@pytest.fixture
def tiny_cache(tiny):
    """`tiny` with the cell's own files cut likewise: 4 chunks of prefetch, a
    4 MiB cache under 12 MiB stored, the scan's reads at 16 KiB. (At 64 KiB
    chunks the gateway streams a dozen chunks on into the socket's buffers
    for a reader that has left, where at 4 MiB it streams one: a cache of 16
    chunks would lose them before the reader came.)"""
    path = tiny / "configs" / "kip405-aes-chunkcache.json"
    config = json.loads(path.read_text())
    chunk = config["sizes"]["chunk_bytes"]
    config["rsm"].update({
        "fetch.chunk.cache.prefetch.max.size": 4 * chunk,
        "fetch.chunk.cache.size": 64 * chunk,
    })
    path.write_text(json.dumps(config))
    path = tiny / "traffic" / "catchup_scan_prefetch.json"
    scan = json.loads(path.read_text())
    scan["parameters"].update(
        segments=8, read_bytes=16 << 10, step_bytes=15 << 10, first_request=90,
        stretch_after=3, stretch_seconds=0.5,
    )
    path.write_text(json.dumps(scan))
    return tiny


def test_cell_end_to_end_untraced(tiny_cache, capsys):
    assert run_cell(tiny_cache, CELL) == 0
    result = last_line(capsys)
    assert result["correct"] is True and result["failed"] == 0 and result["attempted"] > 0
    assert sorted(result["metrics"]) == ["fetch_mib_s", "fetch_p50_ms", "fetch_p95_ms", "setup_s"]
    assert all(c["value"] <= c["limit"] for c in result["compared"].values())


def test_cell_end_to_end_traced(tiny_cache, capsys):
    assert run_cell(tiny_cache, CELL, "--trace", "1", seconds="2.5") == 0
    out = capsys.readouterr().out.splitlines()
    result = json.loads(out[-1])
    assert result["correct"] is True
    bench = json.loads((tiny_cache.parent / "BENCHMARK.json").read_text())
    expected = {m["name"] for m in run.of_cell(bench["per_layer"], CELL)}
    assert set(NEW_METRICS) <= expected
    assert {n for n in expected if not n.startswith(("gcm_roofline", "device_idle"))} <= set(
        result["metrics"]
    )
    values = {name: m["value"] for name, m in result["metrics"].items()}
    assert values["cache_degradations.fetch"] == 0
    assert 1.0 <= values["rows_per_decrypt_window.fetch"] <= 2.0
    # once each, plus what was decrypted ahead of the reader
    assert 1.0 <= values["decrypts_per_chunk.fetch"] < 2.0
    assert 0 < values["cache_hit_share.fetch"] <= 100
    window = next(json.loads(line) for line in out if '"phase": "window", "seconds"' in line)
    assert window["wrapped"] is False
    # under a chunk cache no window reaches the hot tier twice
    assert window["counters"]["hot_hits"] == 0 and window["counters"]["hot_admissions"] == 0
    assert window["counters"]["cache_prefetch_failures"] == 0


@pytest.mark.parametrize("control,number", [
    ("verify_skipped", "altered_chunk_served"),
    ("chunk_altered", "replies_differ"),
])
def test_control_or_fault_comes_out_not_correct(tiny_cache, capsys, control, number):
    from tieredstorage_tpu.transform import tpu

    saved = (tpu.TpuTransformBackend._decrypt_window, tpu.hmac)
    try:
        run_cell(tiny_cache, CELL, "--control", control)
        result = last_line(capsys)
    finally:
        tpu.TpuTransformBackend._decrypt_window, tpu.hmac = saved
    assert result["correct"] is False
    assert result["compared"][number]["value"] > result["compared"][number]["limit"]


@pytest.mark.parametrize("metric", NEW_METRICS)
def test_new_readers_return_nothing_without_the_new_counts(metric):
    """What the parent commit gives them: the counters and spans it had."""
    reader = run.load(BENCHMARK / "layer_metrics" / f"{metric}.py", "per-layer metric")
    observation = {
        "window": {"seconds": 20.0, "fetches": 1500, "bytes": 1500 << 20},
        "counters": {"windows": 1400, "bytes_in": 1400 << 22, "hot_hits": 0},
        "spans": {"transform.decrypt": {"total_s": 19.0, "avg_s": 0.0165, "self_s": 2.5}},
    }
    assert reader.read(observation) is None
