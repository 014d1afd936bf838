"""The ten-copy cell rehearsed off the chip, by hand, beside `test_rehearsal.py`:

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q

`zstd-aes-rlm10.copy` (ten closed-loop copies of compressed, encrypted segments
at once) runs end to end at 64 KiB chunks, traced and untraced, correct, with
its files found by name and no edit to `run.py`; its traced line prints the
three metrics of the encrypt streams, and on a program without their counts
(the parent commit) the counter reads nothing and the readers return nothing.
Not part of tier-1: no number here is a device's.
"""

from __future__ import annotations

import json
import types

import pytest

from test_rehearsal import BENCHMARK, run, run_cell, tiny  # noqa: F401

CELL = "zstd-aes-rlm10.copy"
NEW = {
    "streams_per_launch.copy", "windows_queued_per_launch.copy",
    "staging_trimmed_mib_per_copy.copy",
}


def load(kind: str, name: str):
    return run.load(BENCHMARK / kind / f"{name}.py", kind)


def lines(capsys) -> tuple[dict, list]:
    out = capsys.readouterr().out.splitlines()
    return json.loads(out[-1]), [json.loads(line) for line in out[:-1] if line.startswith("{")]


@pytest.mark.parametrize("trace", ["0", "1"])
def test_cell_runs_correct(tiny, capsys, trace):
    assert run_cell(tiny, CELL, "--trace", trace, seconds="3") == 0
    result, earlier = lines(capsys)
    assert result["correct"] is True and result["failed"] == 0
    assert result["compared"]["data_keys_reused"]["value"] == 0
    window = next(r for r in earlier if r.get("phase") == "window")
    assert window["copies"] >= 10 and window["programs_compiled_in_window"] == 0
    if trace == "1":
        bench = json.loads((tiny.parent / "BENCHMARK.json").read_text())
        listed = {m["name"] for m in run.of_cell(bench["per_layer"], CELL)}
        assert NEW <= set(result["metrics"]) <= listed
        assert result["metrics"]["streams_per_launch.copy"]["value"] >= 1.0
        assert result["metrics"]["staging_trimmed_mib_per_copy.copy"]["value"] == 0
    else:
        assert set(result["metrics"]) == {"copy_gib_s", "setup_s"}


def test_a_single_copy_cell_reads_one_stream_a_launch(tiny, capsys):
    assert run_cell(tiny, "zstd-aes.copy", "--trace", "1", seconds="2.5") == 0
    result, _ = lines(capsys)
    assert result["correct"] is True
    assert result["metrics"]["streams_per_launch.copy"]["value"] == 1.0
    assert result["metrics"]["staging_trimmed_mib_per_copy.copy"]["value"] == 0


def test_counter_reads_nothing_on_a_program_without_the_counts():
    stats = types.SimpleNamespace(windows=3, staging_acquired=3, staging_reused=2)
    deployment = types.SimpleNamespace(backend=types.SimpleNamespace(dispatch_stats=stats))
    assert load("counters", "encrypt_streams").read(deployment) == {}


@pytest.mark.parametrize("name", sorted(NEW))
def test_reader_returns_nothing_on_a_program_without_the_counts(name):
    observation = {"counters": {"dispatches": 8, "windows": 8}, "spans": {}, "window": {"copies": 2}}
    assert load("layer_metrics", name).read(observation) is None


def test_entries_are_appended_and_name_the_four_copy_cells():
    bench = json.loads((BENCHMARK.parent / "BENCHMARK.json").read_text())
    tail = bench["per_layer"][-len(NEW):]
    assert {m["name"] for m in tail} == NEW
    assert all(
        m["workloads"] == ["aes.copy", "zstd-aes.copy", "aes-s3.copy", CELL] for m in tail
    )
    assert bench["workloads"][-1]["name"] == CELL and bench["workloads"][-1]["chips"] == 4
    assert bench["configs"][-1]["name"] == "kip405-zstd-aes-rlm10"
    for metric in bench["end_to_end"] + bench["per_layer"][:-len(NEW)]:
        cells = metric.get("workloads", [])
        assert (CELL in cells) == ("zstd-aes.copy" in cells and metric["name"] != "setup_s")
        if CELL in cells:
            assert cells[-1] == CELL
