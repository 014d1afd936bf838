"""chip_smoke.py rehearsed off the chip: its phase functions run here on the
CPU backend at 8 x 64 KiB with the device assertion answered by the test,
and the script itself — unpatched — must exit non-zero without an `ok` line
wherever JAX finds no TPU."""

from __future__ import annotations

import json
import os
import pathlib
import shutil
import subprocess
import sys

import pytest

pytest.importorskip("cryptography")
pytest.importorskip("zstandard")

REPO_ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(REPO_ROOT))

import chip_smoke  # noqa: E402

TINY = chip_smoke.Sizes(
    chunk_bytes=64 << 10,
    segment_bytes=8 * (64 << 10) - 300,
    device_cache_bytes=64 << 20,
    window_chunks=8,
)
FAKE_DEVICE = {"platform": "tpu", "kind": "rehearsal", "count": 1}


def phase_lines(capsys) -> dict:
    lines = [json.loads(line) for line in capsys.readouterr().out.splitlines()]
    return {line["phase"]: line for line in lines if "phase" in line}


def test_one_chip_phases_run_at_tiny_size(capsys):
    with chip_smoke.CompileLog() as log:
        # On the CPU the gates answer "no": the window program holds no kernel.
        chip_smoke.run_one_chip(7, TINY, log, expect_kernels=0)
    phases = phase_lines(capsys)
    for name in (
        "gates", "copy_plain", "window_program", "reference_reads_tpu_upload",
        "fetch_whole_segment", "fetch_aligned_chunk", "fetch_across_boundary",
        "fetch_ragged_last_chunk", "hot_tier", "tpu_reads_reference_upload",
        "fetch_index", "delete", "copy_zstd", "reference_reads_tpu_zstd_upload",
        "fetch_zstd_ragged_last_chunk", "delete_zstd",
    ):
        assert name in phases, name
    assert phases["gates"]["pallas_ghash_tree"] is False
    assert phases["copy_plain"]["bytes"] == TINY.segment_bytes
    copy = phases["copy_plain"]["dispatch"]
    assert copy["dispatches_per_window"] == 1.0
    assert copy["donated_buffers"] == copy["windows"]
    assert phases["hot_tier"]["dispatches_on_hit"] == 0
    assert phases["hot_tier"]["device_buffer_deleted"] is False
    assert phases["copy_plain"]["programs_compiled"] > 0  # the log listens
    assert phases["delete"]["objects_left"] == 0


def test_wrong_kernel_count_fails_the_run(capsys):
    """A phase that finds the wrong program is an exception, not a line."""
    from tieredstorage_tpu.transform.tpu import TpuTransformBackend

    backend = TpuTransformBackend()
    with chip_smoke.CompileLog() as log, pytest.raises(
        AssertionError, match="expected 2"
    ):
        chip_smoke.phase_window_program(log, backend, TINY, expect_kernels=2)
    assert phase_lines(capsys)["window_program"]["tpu_custom_calls"] == 0


def test_across_chips_path_runs_on_the_virtual_mesh(capsys):
    with chip_smoke.CompileLog() as log:
        chip_smoke.run_across_chips(7, TINY, log, mesh_devices=4)
    phases = phase_lines(capsys)
    for shape in ("fixed", "varlen"):
        for direction in ("encrypt", "decrypt"):
            assert phases[f"mesh_{shape}_{direction}"]["dispatch"]["mesh_size"] == 4
            assert phases[f"single_{shape}_{direction}"]["dispatch"]["mesh_size"] == 1
    placed = phases["placement"]
    assert [rows for _, (rows, _) in placed["staged_window"]["shards"]] == [2] * 4
    assert placed["bare_asarray"] == {"devices": [0], "committed": False}
    assert all(
        c["fully_replicated"] and len(c["shards"]) == 4
        for c in placed["gcm_constants"]
    )
    # Nothing but the mesh path and what it is compared with.
    assert not [name for name in phases if name.startswith(("copy", "fetch"))]


def test_main_prints_ok_last_when_the_device_check_is_answered(monkeypatch, capsys):
    monkeypatch.setattr(chip_smoke, "require_tpu", lambda chips=None: FAKE_DEVICE)
    monkeypatch.setattr(chip_smoke, "Sizes", lambda: TINY)
    monkeypatch.setattr(
        chip_smoke, "run_across_chips",
        lambda seed, sizes, log: chip_smoke.emit({"phase": "stub", "seed": seed}),
    )
    import jax

    before = jax.config.jax_compilation_cache_dir
    try:
        assert chip_smoke.main(["--chips", "4", "--seed", "3"]) == 0
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    lines = [json.loads(line) for line in capsys.readouterr().out.splitlines()]
    assert lines[-1] == {"ok": True, "device": FAKE_DEVICE}  # and nothing else
    assert {"phase": "stub", "seed": 3} in lines


@pytest.mark.parametrize("switch", chip_smoke.KERNEL_SWITCHES)
def test_refuses_to_start_with_a_kernel_switch_set(monkeypatch, switch):
    monkeypatch.setenv(switch, "1")
    with pytest.raises(SystemExit, match=switch):
        chip_smoke.main([])


def run_script(cwd: pathlib.Path, script: pathlib.Path, *args: str):
    env = {k: v for k, v in os.environ.items() if k not in chip_smoke.KERNEL_SWITCHES}
    env["JAX_PLATFORMS"] = "cpu"
    return subprocess.run(
        [sys.executable, str(script), *args], cwd=cwd, env=env,
        capture_output=True, text=True, timeout=300,
    )


@pytest.mark.parametrize("args", [(), ("--chips", "4")])
def test_script_fails_without_a_tpu_and_prints_no_ok(tmp_path, args):
    proc = run_script(tmp_path, REPO_ROOT / "chip_smoke.py", *args)
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
    assert "no TPU" in proc.stderr


def test_script_alone_fails_without_the_program(tmp_path):
    """In a directory that holds chip_smoke.py and nothing else of the repo."""
    alone = shutil.copy(REPO_ROOT / "chip_smoke.py", tmp_path / "chip_smoke.py")
    proc = run_script(tmp_path, pathlib.Path(alone))
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
