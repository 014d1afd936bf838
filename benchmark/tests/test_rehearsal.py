"""The benchmark rehearsed off the chip, by hand:

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q

Each cell runs end to end at the smoke's tiny sizes (64 KiB chunks) with the
device check answered by the test, and its last line is held to the contract's
keys; each control and each planted fault has to come out as not correct; the
arithmetic of the trace reduction and of the copy rate is checked on hand-made
numbers; a configuration, mix, per-layer metric and cell added as new files are
picked up with no edit to `run.py`. Not part of tier-1: no number here is a
device's.
"""

from __future__ import annotations

import json
import pathlib
import shutil
import subprocess
import sys

import pytest

pytest.importorskip("cryptography")
pytest.importorskip("zstandard")

BENCHMARK = pathlib.Path(__file__).resolve().parents[1]
REPO_ROOT = BENCHMARK.parent
for path in (REPO_ROOT, BENCHMARK):
    if str(path) not in sys.path:
        sys.path.insert(0, str(path))

import harness  # noqa: E402
import run  # noqa: E402
import trace_reduce  # noqa: E402

FAKE_DEVICE = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
TINY = {"chunk_bytes": 64 << 10, "segment_bytes": 24 * (64 << 10) - 300, "window_chunks": 16}
CELLS = ["aes.copy", "aes.fetch_scan", "zstd-aes.copy"]


@pytest.fixture
def tiny(tmp_path, monkeypatch):
    """A copy of the benchmark whose configurations are cut to 64 KiB chunks
    and whose catch-up reads 16 KiB of 3 stored segments; the device check answered; the CPU
    client's threads taken for the device's line in the trace."""
    here = tmp_path / "benchmark"
    shutil.copytree(BENCHMARK, here, ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(REPO_ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    for path in (here / "configs").glob("*.json"):
        config = json.loads(path.read_text())
        config["sizes"] = TINY
        config["rsm"].update({"chunk.size": TINY["chunk_bytes"], "cache.device.bytes": 64 << 20})
        path.write_text(json.dumps(config))
    scan = json.loads((here / "traffic" / "catchup_scan.json").read_text())
    scan["parameters"].update(
        segments=3, read_bytes=16 << 10, step_bytes=15 << 10, first_request=90,
        stretch_after=3,
        stretch_seconds=0.5,
    )
    (here / "traffic" / "catchup_scan.json").write_text(json.dumps(scan))
    monkeypatch.setattr(harness, "require_tpu", lambda chips: FAKE_DEVICE)
    monkeypatch.setattr(
        trace_reduce, "is_device_line",
        lambda plane, line: plane == "/host:CPU" and line.startswith("tf_XLAPjRtCpuClient"),
    )
    return here


def last_line(capsys) -> dict:
    out = capsys.readouterr()
    result = json.loads(out.out.splitlines()[-1])
    # the compared numbers are the last lines of standard error too
    tail = out.err.splitlines()[-len(result["compared"]):]
    assert [line.split()[1] for line in tail] == list(result["compared"])
    return result


def run_cell(here, cell, *extra, seconds="1.5"):
    import jax

    before = jax.config.jax_compilation_cache_dir
    try:
        return run.main(
            ["--workload", cell, "--seed", str(2**31 + 11), "--seconds", seconds, *extra],
            here=here,
        )
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


@pytest.mark.parametrize("cell", CELLS)
def test_cell_end_to_end_untraced(tiny, capsys, cell):
    assert run_cell(tiny, cell) == 0
    result = last_line(capsys)
    assert list(result) == ["correct", "attempted", "failed", "metrics", "device", "compared"]
    assert result["correct"] is True and result["failed"] == 0 and result["attempted"] > 0
    bench = json.loads((tiny.parent / "BENCHMARK.json").read_text())
    expected = [m["name"] for m in run.of_cell(bench["end_to_end"], cell)]
    assert sorted(result["metrics"]) == sorted(expected) and "setup_s" in expected
    assert all(m["value"] > 0 for m in result["metrics"].values())
    assert set(result["device"]) == {"platform", "kind", "count", "memory_peak_bytes"}
    assert all(c["value"] <= c["limit"] for c in result["compared"].values())


@pytest.mark.parametrize("cell", CELLS)
def test_cell_end_to_end_traced(tiny, capsys, cell):
    assert run_cell(tiny, cell, "--trace", "1", seconds="2.5") == 0
    result = last_line(capsys)
    assert result["correct"] is True
    bench = json.loads((tiny.parent / "BENCHMARK.json").read_text())
    expected = {m["name"] for m in run.of_cell(bench["per_layer"], cell)}
    assert set(result["metrics"]) <= expected
    assert {n for n in expected if not n.startswith(("gcm_roofline", "device_idle"))} <= set(
        result["metrics"]
    )
    assert 0 < result["device"]["busy_s"] and result["device"]["window_s"] > 0
    assert set(result["breakdown"]) == {"device_ops", "idle_gaps"}
    assert len(result["breakdown"]["device_ops"]) <= 10


@pytest.mark.parametrize("cell,control,number", [
    ("aes.copy", "tag_skipped", "copies_unreadable"),
    ("zstd-aes.copy", "tag_skipped", "copies_unreadable"),
    ("aes.fetch_scan", "verify_skipped", "altered_chunk_served"),
    ("aes.copy", "chunk_altered", "copies_unreadable"),
    ("zstd-aes.copy", "chunk_altered", "copies_unreadable"),
    ("aes.fetch_scan", "chunk_altered", "replies_differ"),
])
def test_control_or_fault_comes_out_not_correct(tiny, capsys, cell, control, number):
    """The rest of a run with the timed path broken underneath."""
    from tieredstorage_tpu.transform import tpu

    saved = (tpu.TpuTransformBackend._encrypt_finish,
             tpu.TpuTransformBackend._decrypt_window, tpu.hmac)
    try:
        run_cell(tiny, cell, "--control", control)
        result = last_line(capsys)
    finally:
        (tpu.TpuTransformBackend._encrypt_finish,
         tpu.TpuTransformBackend._decrypt_window, tpu.hmac) = saved
    assert result["correct"] is False
    assert result["compared"][number]["value"] > result["compared"][number]["limit"]


def test_trace_reduction_on_hand_made_intervals():
    # overlapping operations count once
    assert trace_reduce.busy_seconds([(0.0, 1.0), (0.5, 1.5), (3.0, 4.0)]) == 2.5
    assert trace_reduce.merge([(3, 4), (0, 1), (1, 2)]) == [[0, 2], [3, 4]]
    # an empty trace is 100 % idle
    assert trace_reduce.busy_seconds([]) == 0.0
    idle = run.load(BENCHMARK / "layer_metrics" / "device_idle_share.copy.py", "per-layer metric")
    assert idle.read({"stretch": {"window_s": 5.0, "busy_s": 0.0}}) == 100.0
    assert idle.read({"stretch": {"window_s": 5.0, "busy_s": 2.5}}) == 50.0


def test_copy_rate_ends_at_the_last_acknowledgement():
    copy = run.load(BENCHMARK / "generators" / "copy_closed_loop.py", "generator")
    # 5 copies of 256 MiB acknowledged, the last 37.5 s after the window opened
    assert copy.copy_rate_gib_s(5 * (256 << 20), 100.0, 137.5) == pytest.approx(1.25 / 37.5)


def test_roofline_and_idle_readers_return_nothing_without_a_trace():
    reader = run.load(BENCHMARK / "layer_metrics" / "gcm_roofline.copy.py", "per-layer metric")
    idle = run.load(BENCHMARK / "layer_metrics" / "device_idle_share.copy.py", "per-layer metric")
    peaks = run.peaks_for(BENCHMARK, "TPU v5 lite")
    assert reader.read({"peaks": peaks}) is None and idle.read({"peaks": peaks}) is None
    stretch = {"window_s": 8.0, "busy_s": 0.0, "counters": {"bytes_in": 1 << 28}}
    assert reader.read({"peaks": peaks, "stretch": stretch}) is None  # never 0
    stretch["busy_s"] = 0.4
    share = reader.read({"peaks": peaks, "stretch": stretch})
    assert share == pytest.approx(100 * (2 * (1 << 28) / 819e9) / 0.4)
    assert idle.read({"peaks": peaks, "stretch": stretch}) == pytest.approx(95.0)


def test_new_files_are_found_by_name_with_no_edit_to_run_py(tiny, capsys):
    """A configuration, a mix, a per-layer metric and a cell, as new files and
    one BENCHMARK.json entry each."""
    root = tiny.parent
    bench = json.loads((root / "BENCHMARK.json").read_text())
    config = json.loads((tiny / "configs" / "kip405-aes.json").read_text())
    config["rsm"]["cache.device.bytes"] = 32 << 20
    (tiny / "configs" / "added-config.json").write_text(json.dumps(config))
    mix = json.loads((tiny / "traffic" / "copy_backlog.json").read_text())
    mix["parameters"].update(max_copies=2, clients=2)  # two RLM task threads
    (tiny / "traffic" / "added_mix.json").write_text(json.dumps(mix))
    (tiny / "layer_metrics" / "added_metric.copy.py").write_text(
        "def read(observation):\n    return float(observation['window']['copies'])\n"
    )
    bench["configs"].append({
        "name": "added-config", "source": "test", "reduced": [], "why": "test",
        "file": "benchmark/configs/added-config.json",
    })
    bench["workloads"].append({
        "name": "added.cell", "config": "added-config", "traffic": "added_mix",
        "chips": 1, "why": "test",
    })
    bench["per_layer"].append({
        "name": "added_metric.copy", "unit": "count", "better": "higher",
        "source": "program_counter", "layer": "test", "moves": "copy_gib_s",
        "workloads": ["added.cell"],
    })
    for metric in bench["end_to_end"]:
        if metric["name"] == "copy_gib_s":
            metric["workloads"].append("added.cell")
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    assert run_cell(tiny, "added.cell", "--trace", "1", seconds="30") == 0
    result = last_line(capsys)
    assert result["correct"] is True and result["attempted"] == 2  # the mix's cap
    assert result["metrics"] == {"added_metric.copy": {"value": 2.0, "unit": "count"}}


@pytest.mark.parametrize("argv,message", [
    (["--workload", "no.such.cell"], "unknown workload"),
])
def test_unknown_names_are_refused(tiny, argv, message):
    with pytest.raises(SystemExit, match=message):
        run.main([*argv, "--seed", "1", "--seconds", "1"], here=tiny)


@pytest.mark.parametrize("missing,message", [
    ("configs/kip405-aes.json", "unknown configuration"),
    ("traffic/copy_backlog.json", "unknown traffic mix"),
    ("generators/copy_closed_loop.py", "unknown generator"),
    ("layer_metrics/device_idle_share.copy.py", "unknown per-layer metric"),
])
def test_a_name_without_its_file_is_refused(tiny, missing, message):
    (tiny / missing).unlink()
    with pytest.raises(SystemExit, match=message):
        run.main(["--workload", "aes.copy", "--seed", "1", "--seconds", "1"], here=tiny)


def test_a_device_kind_without_peaks_is_refused(tiny, monkeypatch):
    monkeypatch.setattr(harness, "require_tpu", lambda chips: {**FAKE_DEVICE, "kind": "TPU v9"})
    with pytest.raises(SystemExit, match="not in peaks.json"):
        run.main(["--workload", "aes.copy", "--seed", "1", "--seconds", "1"], here=tiny)


def test_the_zstd_cell_refuses_another_engine(tiny, monkeypatch):
    from tieredstorage_tpu.transform.tpu import TpuTransformBackend

    monkeypatch.setattr(TpuTransformBackend, "zstd_engine", classmethod(lambda cls: "python-pool"))
    with pytest.raises(SystemExit, match="python-pool"):
        run.main(["--workload", "zstd-aes.copy", "--seed", "1", "--seconds", "1"], here=tiny)


@pytest.mark.parametrize("switch", harness.KERNEL_SWITCHES)
def test_a_kernel_switch_is_refused(tiny, monkeypatch, switch):
    monkeypatch.setenv(switch, "1")
    with pytest.raises(SystemExit, match=switch):
        run.main(["--workload", "aes.copy", "--seed", "1", "--seconds", "1"], here=tiny)


def test_off_a_tpu_the_script_fails_and_prints_no_result(tmp_path):
    proc = subprocess.run(
        [sys.executable, str(BENCHMARK / "run.py"), "--workload", "aes.copy",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=REPO_ROOT, env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin"},
        capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode != 0 and "no TPU" in proc.stderr
    assert '"correct"' not in proc.stdout


def test_without_the_program_the_script_fails_and_prints_no_result(tmp_path):
    """In a directory that holds only BENCHMARK.json and the files under `paths`."""
    shutil.copytree(BENCHMARK, tmp_path / "benchmark", ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(REPO_ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    proc = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", "aes.copy",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin"},
        capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode != 0 and '"correct"' not in proc.stdout
