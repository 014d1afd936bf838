"""utils/platforms.enable_compile_cache: a compile cache that can be placed
from outside (JAX_COMPILATION_CACHE_DIR), and otherwise sits at a fixed path
derived from the checkout, never from the working directory."""

from __future__ import annotations

import pathlib
import subprocess
import sys

import jax
import pytest

from tieredstorage_tpu.utils import platforms

REPO_ROOT = pathlib.Path(__file__).resolve().parents[1]


@pytest.fixture
def restored_cache_dir():
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


def test_placed_from_outside_is_left_alone(monkeypatch, tmp_path, restored_cache_dir):
    placed = tmp_path / "placed-from-outside"
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(placed))
    monkeypatch.chdir(tmp_path)
    default_cache = REPO_ROOT / ".jax_cache"
    default_existed = default_cache.exists()

    assert platforms.enable_compile_cache() == str(placed)
    assert jax.config.jax_compilation_cache_dir == str(placed)
    # The helper named no other directory and wrote nothing: not the placed
    # one (JAX makes it on first write), not a default beside it.
    assert list(tmp_path.iterdir()) == []
    assert default_cache.exists() == default_existed


def test_unset_falls_back_to_the_checkout(monkeypatch, tmp_path, restored_cache_dir):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.chdir(tmp_path)
    assert platforms.enable_compile_cache() == str(REPO_ROOT / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == str(REPO_ROOT / ".jax_cache")


def test_default_is_the_same_from_two_working_directories(tmp_path):
    """Derived from the file's own location: the path is part of the cache's
    key, so a directory that moves with the cwd would never hit."""
    script = (
        "import os; os.environ.pop('JAX_COMPILATION_CACHE_DIR', None);"
        f"import sys; sys.path.insert(0, {str(REPO_ROOT)!r});"
        "from tieredstorage_tpu.utils.platforms import enable_compile_cache;"
        "print(enable_compile_cache())"
    )
    seen = set()
    for cwd in (tmp_path, REPO_ROOT / "tests"):
        out = subprocess.run(
            [sys.executable, "-c", script], cwd=cwd, check=True,
            capture_output=True, text=True, timeout=120,
            env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin"},
        )
        seen.add(out.stdout.strip().splitlines()[-1])
    assert seen == {str(REPO_ROOT / ".jax_cache")}
