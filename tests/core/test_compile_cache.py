"""One rule for where the persistent compile cache lives
(scaling_tpu/compile_cache.py): the environment places it, else one fixed
directory inside the checkout; no other file sets a directory."""

import subprocess
import sys
from pathlib import Path

import jax
import pytest

from scaling_tpu import compile_cache

REPO_ROOT = Path(__file__).resolve().parents[2]
OPTION = "jax_compilation_" + "cache_dir"  # spelled so this file is not a hit


@pytest.fixture
def updates(monkeypatch):
    """Record what the helper would set instead of changing this process's
    cache under the suite's feet."""
    seen = {}
    monkeypatch.setattr(jax.config, "update", seen.__setitem__)
    monkeypatch.delenv("SCALING_TPU_TEST_CACHE", raising=False)
    return seen


def test_environment_places_the_cache(updates, monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/dir")
    compile_cache.enable_compile_cache()
    assert OPTION not in updates
    assert updates["jax_persistent_cache_min_compile_time_secs"] == 0.0


def test_default_is_one_directory_inside_the_checkout(updates, monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    compile_cache.enable_compile_cache()
    assert updates[OPTION] == str(REPO_ROOT / ".jax_cache")


def test_off_switch_disables_the_cache(updates, monkeypatch):
    monkeypatch.setenv("SCALING_TPU_TEST_CACHE", "off")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/dir")
    assert compile_cache.enable_compile_cache() is None
    assert updates == {"jax_enable_compilation_cache": False}


def test_two_processes_agree_on_the_directory(tmp_path):
    """The path is part of the cache key: it may not depend on the working
    directory, the pid or the time."""
    code = (f"import sys; sys.path.insert(0, {str(REPO_ROOT)!r}); "
            "from scaling_tpu.compile_cache import CACHE_DIR; print(CACHE_DIR)")
    seen = {
        subprocess.run(
            [sys.executable, "-c", code], cwd=cwd, capture_output=True,
            text=True, timeout=60, check=True,
        ).stdout.strip()
        for cwd in (REPO_ROOT, tmp_path)
    }
    assert seen == {str(REPO_ROOT / ".jax_cache")}


def test_only_the_helper_sets_a_cache_directory():
    hits = [
        str(path.relative_to(REPO_ROOT))
        for path in REPO_ROOT.rglob("*.py")
        if not any(part.startswith(".") for part in
                   path.relative_to(REPO_ROOT).parts)
        and OPTION in path.read_text()
    ]
    assert hits == ["scaling_tpu/compile_cache.py"]
