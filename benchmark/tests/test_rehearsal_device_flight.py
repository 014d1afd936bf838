"""The device watch's counter and the seventeen metrics of PR 37 rehearsed off
the chip, by hand, beside `test_rehearsal.py`:

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q

A traced cell prints every new metric that lists it, its idle shares add up to
100 and the two halves of `transform.d2h_wait` to the whole; on what a program
without the field, the spans or the count gives them (the parent commit) the
readers return nothing and do not raise, so the line leaves the metrics out; a
span name that holds idle seconds and is in no family refuses the whole family.
Not part of tier-1: no number here is a device's.
"""

from __future__ import annotations

import json
import types

import pytest

from test_rehearsal import BENCHMARK, run, run_cell, tiny  # noqa: F401

COPY = ["aes.copy", "zstd-aes.copy", "aes-s3.copy"]
FETCH = ["aes.fetch_scan", "aes-cache.fetch_scan", "zstd-aes-cache.fetch_scan", "aes-s3.fetch_scan"]
#: metric -> (family of `_idle.py`, or None; the cells that list it)
NEW = {
    "device_busy_seen_ratio.copy": COPY, "device_busy_seen_ratio.fetch": FETCH,
    "ready_wait_s_per_gib.copy": COPY, "ready_wait_ms_per_fetch.fetch": FETCH,
    "collect_s_per_gib.copy": COPY, "collect_ms_per_fetch.fetch": FETCH,
    "idle_under_gateway_share.copy": COPY, "idle_under_gateway_share.fetch": FETCH,
    "idle_under_store_share.copy": COPY, "idle_under_store_share.fetch": FETCH,
    "idle_under_window_host_share.copy": COPY, "idle_under_window_host_share.fetch": FETCH,
    "idle_under_codec_share.copy": ["zstd-aes.copy"],
    "idle_under_codec_share.fetch": ["zstd-aes-cache.fetch_scan"],
    "idle_under_fetch_tiers_share.fetch": FETCH,
    "idle_unclaimed_share.copy": COPY, "idle_unclaimed_share.fetch": FETCH,
}


def reader(name: str):
    return run.load(BENCHMARK / "layer_metrics" / f"{name}.py", "per-layer metric")


def test_entries_are_appended_and_name_their_cells():
    bench = json.loads((BENCHMARK.parent / "BENCHMARK.json").read_text())
    tail = bench["per_layer"][-len(NEW):]
    assert [m["name"] for m in tail] == list(NEW)
    for entry in tail:
        assert entry["workloads"] == NEW[entry["name"]]
        assert set(entry) == {"name", "unit", "better", "source", "layer", "moves", "workloads"}
        assert entry["source"] == (
            "device_trace" if entry["name"].startswith("device_busy_seen") else "program_span"
        )
        assert (BENCHMARK / "layer_metrics" / f"{entry['name']}.py").is_file()


@pytest.mark.parametrize("cell", ["aes.copy", "zstd-aes.copy", "aes.fetch_scan"])
def test_traced_cell_prints_every_new_metric_that_lists_it(tiny, capsys, cell):
    assert run_cell(tiny, cell, "--trace", "1", seconds="2.5") == 0
    out = capsys.readouterr().out.splitlines()
    result = json.loads(out[-1])
    assert result["correct"] is True
    metrics = {name: m["value"] for name, m in result["metrics"].items()}
    listed = [name for name, cells in NEW.items() if cell in cells]
    assert set(listed) <= set(metrics)
    shares = [v for name, v in metrics.items() if name.startswith(("idle_under", "idle_unclaimed"))]
    assert sum(shares) == pytest.approx(100.0, abs=0.1) and min(shares) >= 0.0
    side = "copy" if cell.endswith("copy") else "fetch"
    unit = "s_per_gib.copy" if side == "copy" else "ms_per_fetch.fetch"
    assert metrics[f"ready_wait_{unit}"] + metrics[f"collect_{unit}"] == pytest.approx(
        metrics[f"d2h_wait_{unit}"], rel=0.02
    )
    assert metrics[f"device_busy_seen_ratio.{side}"] > 0
    spans = {
        row["span"]: row for row in map(json.loads, (l for l in out if l.startswith('{"span"')))
    }
    assert spans["device.window"]["count"] == spans["transform.launch"]["count"]
    assert all("device_idle_s" in row for row in spans.values())
    window = next(json.loads(line) for line in out if '"phase": "window", "seconds"' in line)
    assert window["counters"]["device_seen_ns"] > 0


def test_untraced_cell_sees_nothing_and_starts_no_watch(tiny, capsys):
    import threading

    assert run_cell(tiny, "aes.copy") == 0
    out = capsys.readouterr().out.splitlines()
    window = next(json.loads(line) for line in out if '"phase": "window", "seconds"' in line)
    assert window["counters"]["device_seen_ns"] == 0
    assert not [t for t in threading.enumerate() if t.name == "device-watch"]


def observation(**rows) -> dict:
    """A traced fetch window's observation with these `device_idle_s` by span."""
    return {
        "window": {"seconds": 20.0, "fetches": 2000, "bytes": 2000 << 20},
        "counters": {"windows": 1800},
        "stretch": {"busy_s": 0.5, "window_s": 5.0, "counters": {"device_seen_ns": 900_000_000}},
        "spans": {
            name: {"count": 3, "total_s": 4.0, "self_s": 1.0, "max_s": 0.9, "device_idle_s": idle}
            for name, idle in rows.items()
        },
    }


def test_shares_of_one_table_add_up_to_100():
    seen = observation(**{
        "gateway.reply_stream": 4.0, "gateway.fetch": 1.0, "storage.fetch_chunks": 3.0,
        "s3.get_object": 1.0, "rsm.fetch_log_segment": 0.5, "transform.decompress": 2.0,
        "transform.d2h_wait": 0.0, "transform.collect": 2.5, "hot.admit": 1.0,
        "cache.get_chunks": 0.5, "chunk.detransform": 0.5, "device.unclaimed": 4.0,
        "device.window": 0.0,
    })
    read = {name: reader(name).read(seen) for name in NEW if name.endswith(".fetch")}
    assert read["idle_under_gateway_share.fetch"] == pytest.approx(25.0)
    assert read["idle_under_store_share.fetch"] == pytest.approx(22.5)
    assert read["idle_under_codec_share.fetch"] == pytest.approx(10.0)
    assert read["idle_under_window_host_share.fetch"] == pytest.approx(12.5)
    assert read["idle_under_fetch_tiers_share.fetch"] == pytest.approx(10.0)
    assert read["idle_unclaimed_share.fetch"] == pytest.approx(20.0)
    assert sum(v for name, v in read.items() if name.startswith("idle_")) == pytest.approx(100.0)
    assert read["device_busy_seen_ratio.fetch"] == pytest.approx(1.8)
    # 4.0 s of `transform.collect` over 2000 fetches; every window was ready
    # before its wait began, so there is no `transform.ready_wait` row: 0
    assert read["collect_ms_per_fetch.fetch"] == pytest.approx(2.0)
    assert read["ready_wait_ms_per_fetch.fetch"] == 0.0
    # the traced run's own pause: the longest piece nobody held, a second or
    # more, is the profiler's stop and is left out of every share
    seen["spans"]["device.unclaimed"].update(device_idle_s=10.5, total_s=10.5, max_s=6.5)
    assert reader("idle_unclaimed_share.fetch").read(seen) == pytest.approx(20.0)
    assert reader("idle_under_gateway_share.fetch").read(seen) == pytest.approx(25.0)
    # a cell where every gap was claimed has no `device.unclaimed` row
    del seen["spans"]["device.unclaimed"]
    assert reader("idle_unclaimed_share.fetch").read(seen) == 0.0


def test_an_unmatched_span_name_refuses_the_whole_family(capsys):
    seen = observation(**{"gateway.fetch": 1.0, "scrub.pass": 0.25, "device.unclaimed": 1.0})
    for name in NEW:
        if name.startswith("idle_"):
            assert reader(name).read(seen) is None
    assert "scrub.pass" in capsys.readouterr().err
    # with no idle second under it the name is no defect
    seen["spans"]["scrub.pass"]["device_idle_s"] = 0.0
    assert reader("idle_under_gateway_share.copy").read(seen) == pytest.approx(50.0)


def test_every_reader_returns_nothing_on_the_parents_observation():
    """What the parent commit gives them: span rows without `device_idle_s`,
    no `transform.collect`, no `device_seen_ns`."""
    parent = {
        "window": {"seconds": 20.0, "fetches": 2000, "copies": 12, "bytes": 12 << 28},
        "counters": {"windows": 96, "bytes_in": 9 << 28},
        "stretch": {"busy_s": 0.15, "window_s": 0.8, "counters": {"windows": 8}},
        "spans": {
            "gateway.copy": {"count": 12, "total_s": 9.0, "self_s": 0.5, "avg_s": 0.75},
            "transform.d2h_wait": {"count": 96, "total_s": 0.7, "self_s": 0.7, "avg_s": 0.007},
        },
    }
    for name in NEW:
        assert reader(name).read(parent) is None, name
    assert reader("idle_under_store_share.copy").read({"window": {}, "counters": {}}) is None

    counter = run.load(BENCHMARK / "counters" / "device_flight.py", "counter")
    stats = types.SimpleNamespace(windows=8, codec_bytes_in=0)
    assert counter.read(types.SimpleNamespace(backend=types.SimpleNamespace(dispatch_stats=stats))) == {}
    settled = []
    change = types.SimpleNamespace(backend=types.SimpleNamespace(
        dispatch_stats=types.SimpleNamespace(device_seen_ns=1234),
        device_watch=types.SimpleNamespace(settle=lambda: settled.append(True)),
    ))
    assert counter.read(change) == {"device_seen_ns": 1234} and settled == [True]
    change.backend.device_watch = None  # tracing off: no watch to wait for
    assert counter.read(change) == {"device_seen_ns": 1234}
