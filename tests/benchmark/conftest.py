"""What the benchmark's tests share: the harness's entry module, and a copy
of ``benchmark/`` into which a later PR's files are ADDED."""

import filecmp
import shutil
import sys
from pathlib import Path

import pytest

from benchmark import cells

TOY = Path(__file__).parent / "data" / "toy"
# what a later PR may bring, each a directory of files found by name
TOY_PARTS = ("configs", "traffic", "metrics", "readers", "reference", "views")


@pytest.fixture(scope="module")
def run():
    sys.path.insert(0, str(cells.REPO))
    from benchmark import run as run_module

    return run_module


@pytest.fixture(scope="module")
def grown(tmp_path_factory):
    """A module's own copy of ``benchmark/`` plus the toy files; nothing the
    benchmark already had is touched or shadowed by them."""
    root = tmp_path_factory.mktemp("checkout") / "benchmark"
    shutil.copytree(cells.ROOT, root, ignore=shutil.ignore_patterns("__pycache__"))
    before = {p.relative_to(root) for p in root.rglob("*") if p.is_file()}
    for part in TOY_PARTS:
        for f in (TOY / part).iterdir():
            assert not (root / part / f.name).exists(), "a toy file shadows a real one"
            shutil.copy(f, root / part / f.name)
    for rel in before:
        assert filecmp.cmp(root / rel, cells.ROOT / rel, shallow=False)
    return root
