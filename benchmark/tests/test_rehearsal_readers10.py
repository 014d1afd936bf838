"""The fan-in and hot-segment cells rehearsed off the chip, by hand, beside
`test_rehearsal.py`:

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q

`aes-readers10.fetch_fanin` (ten closed loops through the batcher) and
`aes.fetch_hot` (one client over a Zipf-drawn hot segment) run end to end at
64 KiB chunks, traced and untraced, correct; the fan-in cell's merged warm-up
launches keyed windows, its traced line prints the four batcher metrics, and
on a program without the batcher's counts and spans (the parent commit) their
readers return nothing; the canary's batch-mates are served. Not part of
tier-1: no number here is a device's.
"""

from __future__ import annotations

import json

import pytest

from test_rehearsal import BENCHMARK, run, run_cell, tiny  # noqa: F401

NEW = {
    "rows_per_launch.fetch", "keys_per_merged_launch.fetch",
    "batch_wait_ms_per_window.fetch", "batch_flush_ms_per_launch.fetch",
}


@pytest.fixture
def small(tiny):
    """The fan-in mix at the tiny sizes: 15 KiB steps over 1.5 MiB segments."""
    for mix in ("catchup_fanin", "hot_segment_zipf"):
        path = tiny / "traffic" / f"{mix}.json"
        traffic = json.loads(path.read_text())
        traffic["parameters"].update(
            read_bytes=16 << 10, step_bytes=15 << 10, stretch_after=3, stretch_seconds=0.5,
        )
        path.write_text(json.dumps(traffic))
    return tiny


def reader(name: str):
    return run.load(BENCHMARK / "layer_metrics" / f"{name}.py", "per-layer metric")


def lines(capsys) -> tuple[dict, list]:
    out = capsys.readouterr().out.splitlines()
    return json.loads(out[-1]), [json.loads(line) for line in out[:-1] if line.startswith("{")]


@pytest.mark.parametrize("cell", ["aes-readers10.fetch_fanin", "aes.fetch_hot"])
@pytest.mark.parametrize("trace", ["0", "1"])
def test_cell_runs_correct(small, capsys, cell, trace):
    assert run_cell(small, cell, "--trace", trace, seconds="2.5") == 0
    result, earlier = lines(capsys)
    assert result["correct"] is True and result["failed"] == 0
    compared = result["compared"]
    if cell.endswith("fanin"):
        assert compared["batch_mates_failed"]["value"] == 0
        window = next(r for r in earlier if "segments_entered_by_reader" in r)
        assert len(window["segments_entered_by_reader"]) == 10
    bench = json.loads((small.parent / "BENCHMARK.json").read_text())
    listed = {m["name"] for m in run.of_cell(bench["per_layer"], cell)}
    if trace == "1":
        assert set(result["metrics"]) <= listed
        if cell.endswith("fanin"):
            assert NEW <= set(result["metrics"])
            assert result["metrics"]["keys_per_merged_launch.fetch"]["value"] >= 1.0
    else:
        assert set(result["metrics"]) == {"fetch_p50_ms", "fetch_p95_ms", "fetch_mib_s", "setup_s"}


def test_warm_up_merges_keys(small, capsys):
    """Set-up's held rounds: a merged launch of nine keys, one of two and
    one of one, each twice, and the canary's round of four."""
    assert run_cell(small, "aes-readers10.fetch_fanin", seconds="1.5") == 0
    _, earlier = lines(capsys)
    assert any(r.get("phase") == "merged_warm_up" for r in earlier)
    window = next(r for r in earlier if r.get("phase") == "window" and "counters" in r)
    assert window["programs_compiled_in_window"] == 0


@pytest.mark.parametrize("name", sorted(NEW))
def test_reader_returns_nothing_on_a_program_without_the_batcher(name):
    observation = {"counters": {"rows": 3}, "spans": {}, "window": {"fetches": 5}}
    assert reader(name).read(observation) is None


def test_entries_are_appended_and_name_the_fanin_cell():
    bench = json.loads((BENCHMARK.parent / "BENCHMARK.json").read_text())
    tail = bench["per_layer"][-len(NEW):]
    assert {m["name"] for m in tail} == NEW
    assert all(m["workloads"] == ["aes-readers10.fetch_fanin"] for m in tail)
    assert [w["name"] for w in bench["workloads"][-2:]] == [
        "aes-readers10.fetch_fanin", "aes.fetch_hot",
    ]
