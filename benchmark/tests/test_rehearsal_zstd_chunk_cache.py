"""The compressed chunk-cache cell rehearsed off the chip, by hand, beside
`test_rehearsal_chunk_cache.py`:

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q

`zstd-aes-cache.fetch_scan` end to end at 64 KiB chunks with the prefetch cut
to the same four chunks ahead; with the tag check skipped the tag-only canary
is served and the run reads not correct, as it does with the served bytes
altered after the codec; `chunk_altered`'s fault is refused by the codec in
the warm-up; on a program that gives a one-row window a
program per byte size the generator refuses at once; the new counter file and
the three new readers return nothing, and do not raise, on what a program
without the new counts and span gives them. Whatever a control patched is put
back. Not part of tier-1: no number here is a device's.
"""

from __future__ import annotations

import json
import types

import pytest
from test_rehearsal import BENCHMARK, harness, last_line, run, run_cell, tiny  # noqa: F401

from tieredstorage_tpu.transform import tpu

CELL = "zstd-aes-cache.fetch_scan"
NEW_METRICS = [
    "decompress_ms_per_chunk.fetch", "varlen_window_share.fetch", "window_pad_share.fetch",
]
#: As they are when the files are collected, before any test has run.
PRISTINE = (
    tpu.TpuTransformBackend._encrypt_finish, tpu.TpuTransformBackend._decrypt_window,
    tpu.TpuTransformBackend.detransform, tpu.hmac,
)


@pytest.fixture(autouse=True)
def pristine_backend():
    """Every control patches the backend's class or module for good: each test
    starts and ends with what was there before any ran."""
    def restore():
        (tpu.TpuTransformBackend._encrypt_finish, tpu.TpuTransformBackend._decrypt_window,
         tpu.TpuTransformBackend.detransform, tpu.hmac) = PRISTINE

    restore()
    yield
    restore()


@pytest.fixture
def tiny_zstd(tiny):
    """`tiny` with the cell's own files cut likewise: 4 chunks of prefetch, a
    4 MiB cache, the scan's reads at 16 KiB."""
    path = tiny / "configs" / "kip405-zstd-aes-chunkcache.json"
    config = json.loads(path.read_text())
    chunk = config["sizes"]["chunk_bytes"]
    config["rsm"].update({
        "fetch.chunk.cache.prefetch.max.size": 4 * chunk,
        "fetch.chunk.cache.size": 64 * chunk,
    })
    path.write_text(json.dumps(config))
    path = tiny / "traffic" / "catchup_scan_prefetch_zstd.json"
    scan = json.loads(path.read_text())
    scan["parameters"].update(
        segments=8, read_bytes=16 << 10, step_bytes=15 << 10, first_request=90,
        stretch_after=3, stretch_seconds=0.5,
    )
    path.write_text(json.dumps(scan))
    return tiny


def test_cell_end_to_end_untraced(tiny_zstd, capsys):
    assert run_cell(tiny_zstd, CELL) == 0
    out = capsys.readouterr()
    result = json.loads(out.out.splitlines()[-1])
    assert result["correct"] is True and result["failed"] == 0 and result["attempted"] > 0
    assert sorted(result["metrics"]) == ["fetch_mib_s", "fetch_p50_ms", "fetch_p95_ms", "setup_s"]
    assert all(c["value"] == 0 for c in result["compared"].values())
    probe = next(json.loads(line) for line in out.out.splitlines() if '"phase": "one_row_probe"' in line)
    assert probe["programs_traced"] == 0
    assert probe["stored_bytes"][0] != probe["stored_bytes"][1]
    assert '"check": "altered chunk refused"' in out.out


def test_cell_end_to_end_traced(tiny_zstd, capsys):
    assert run_cell(tiny_zstd, CELL, "--trace", "1", seconds="2.5") == 0
    out = capsys.readouterr().out.splitlines()
    result = json.loads(out[-1])
    assert result["correct"] is True
    bench = json.loads((tiny_zstd.parent / "BENCHMARK.json").read_text())
    expected = {m["name"] for m in run.of_cell(bench["per_layer"], CELL)}
    assert set(NEW_METRICS) <= expected and len(expected) == 20
    assert {n for n in expected if not n.startswith(("gcm_roofline", "device_idle"))} <= set(
        result["metrics"]
    )
    values = {name: m["value"] for name, m in result["metrics"].items()}
    assert values["varlen_window_share.fetch"] == 100.0
    assert 0 < values["window_pad_share.fetch"] < 25  # a rung is an eighth-step
    assert values["decompress_ms_per_chunk.fetch"] > 0
    assert values["program_traces.fetch"] == 0
    assert values["cache_degradations.fetch"] == 0
    assert 1.0 <= values["rows_per_decrypt_window.fetch"] <= 2.0
    assert 1.0 <= values["decrypts_per_chunk.fetch"] < 2.0
    window = next(json.loads(line) for line in out if '"phase": "window", "seconds"' in line)
    assert window["wrapped"] is False
    counters = window["counters"]
    assert counters["varlen_windows"] == counters["windows"] > 0
    assert counters["padded_bytes"] > counters["bytes_in"] > 0
    assert counters["cache_prefetch_failures"] == 0
    spans = [json.loads(line)["span"] for line in out if line.startswith('{"span"')]
    assert "transform.decompress" in spans


def test_tag_check_skipped_comes_out_not_correct(tiny_zstd, capsys):
    """The canary's ciphertext is whole, so the codec has nothing to refuse:
    only the tag check stands between the altered chunk and the reader."""
    run_cell(tiny_zstd, CELL, "--control", "verify_skipped")
    result = last_line(capsys)
    assert result["correct"] is False
    over = [name for name, c in result["compared"].items() if c["value"] > c["limit"]]
    assert over == ["altered_chunk_served"]


def test_bytes_altered_after_the_codec_come_out_not_correct(tiny_zstd, capsys):
    """`controls/served_bytes_altered.py`, the planted fault of a deployment
    with a codec: what `detransform` hands on differs by one bit."""
    run_cell(tiny_zstd, CELL, "--control", "served_bytes_altered")
    result = last_line(capsys)
    assert result["correct"] is False and result["failed"] == 0
    assert result["compared"]["replies_differ"]["value"] > 0
    assert result["compared"]["altered_chunk_served"]["value"] == 0


def test_planted_fault_is_refused_by_the_codec_before_a_byte_is_served(tiny_zstd, capsys):
    """`controls/chunk_altered.py` flips byte 5 of what a decrypt window hands
    on: under compression a byte of the zstd frame's header. At 64 KiB that is
    the content size, which the program's codec holds to `chunk.size` and to
    the frame's content, so the warm-up's first read is answered 500 and the
    run ends there: no result line, and so nothing that reads as correct. (At
    4 MiB it is the window descriptor, and the decoder gives back the same
    bytes: PERF.md section 6, PR 33.)"""
    with pytest.raises(harness.Failed, match="Detransform failed"):
        run_cell(tiny_zstd, CELL, "--control", "chunk_altered")
    assert '"correct"' not in capsys.readouterr().out


def test_a_program_per_chunk_size_is_refused_at_once(tiny_zstd, monkeypatch, capsys):
    """The parent commit's `_window_context`: a window of one row is uniform,
    whatever the manifest says, and takes a fixed-shape program of its size."""
    context = tpu.TpuTransformBackend._window_context

    def per_size(self, enc, sizes, compressed=False):
        return context(self, enc, sizes)

    monkeypatch.setattr(tpu.TpuTransformBackend, "_window_context", per_size)
    with pytest.raises(SystemExit, match="compiles one-row windows per chunk size"):
        run_cell(tiny_zstd, CELL)
    out = capsys.readouterr().out
    probe = next(json.loads(line) for line in out.splitlines() if '"phase": "one_row_probe"' in line)
    assert probe["programs_traced"] >= 1
    assert '"phase": "window"' not in out and '"correct"' not in out


def test_counter_file_returns_nothing_without_the_new_counts():
    reader = run.load(BENCHMARK / "counters" / "dispatch_forms.py", "counter")
    parent = types.SimpleNamespace(backend=types.SimpleNamespace(
        dispatch_stats=types.SimpleNamespace(windows=3, rows=3, bytes_in=1 << 20)
    ))
    assert reader.read(parent) == {}
    stats = tpu.DispatchStats(windows=3, varlen_windows=2, padded_bytes=1 << 21)
    here = types.SimpleNamespace(backend=types.SimpleNamespace(dispatch_stats=stats))
    assert reader.read(here) == {"varlen_windows": 2, "padded_bytes": 1 << 21}


@pytest.mark.parametrize("metric", NEW_METRICS)
def test_new_readers_return_nothing_without_the_new_counts(metric):
    """What the parent commit gives them: the counters and spans it had."""
    reader = run.load(BENCHMARK / "layer_metrics" / f"{metric}.py", "per-layer metric")
    observation = {
        "window": {"seconds": 20.0, "fetches": 1500, "bytes": 1500 << 20},
        "counters": {"windows": 1400, "rows": 1440, "bytes_in": 1400 << 22, "hot_hits": 0},
        "spans": {"transform.decrypt": {"total_s": 19.0, "avg_s": 0.0165, "self_s": 2.5}},
    }
    assert reader.read(observation) is None
    assert reader.read({"window": {}, "counters": {}}) is None


def test_new_readers_on_hand_made_numbers():
    def reader(name):
        return run.load(BENCHMARK / "layer_metrics" / f"{name}.py", "per-layer metric")

    observation = {
        "window": {"seconds": 20.0, "fetches": 2000},
        "counters": {"windows": 500, "varlen_windows": 500, "rows": 520,
                     "bytes_in": 7 << 20, "padded_bytes": 8 << 20},
        "spans": {"transform.decompress": {"total_s": 2.6, "avg_s": 0.0052, "self_s": 2.6}},
    }
    assert reader("varlen_window_share.fetch").read(observation) == 100.0
    assert reader("window_pad_share.fetch").read(observation) == pytest.approx(12.5)
    assert reader("decompress_ms_per_chunk.fetch").read(observation) == pytest.approx(5.0)
    observation["counters"].update(varlen_windows=0, padded_bytes=7 << 20)
    assert reader("varlen_window_share.fetch").read(observation) == 0.0
    assert reader("window_pad_share.fetch").read(observation) == 0.0
