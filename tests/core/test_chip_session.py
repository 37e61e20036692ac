"""The on-chip measurement session's plumbing, rehearsed off-chip.

chip_session.py spends chip time, so its plumbing is debugged here: the
section registry, the per-section subprocess entry, and a section end to
end at the smoke shapes on the CPU backend."""

import os
import re
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
SCRIPT = os.path.join(REPO, "benchmarks", "chip_session.py")


def _smoke_env():
    env = dict(os.environ)
    env["CHIP_SESSION_SMOKE"] = "1"
    env["JAX_PLATFORMS"] = "cpu"
    return env


def test_section_registry_names_are_unique_and_bounded():
    sys.path.insert(0, REPO)
    import importlib

    import benchmarks.chip_session as cs

    importlib.reload(cs)
    secs = cs._sections()
    names = [n for n, _, _ in secs]
    assert len(names) == len(set(names))
    assert all(t > 0 for _, _, t in secs)
    # the parent dispatches sections without touching jax: importing the
    # module must stay device-free
    assert sum(t for _, _, t in secs) > 0


def test_unknown_section_exits_with_error():
    p = subprocess.run(
        [sys.executable, SCRIPT, "no-such-section"],
        capture_output=True, text=True, env=_smoke_env(), timeout=120,
    )
    assert p.returncode != 0
    assert "unknown section" in p.stderr


@pytest.mark.slow
def test_single_section_runs_on_cpu_and_prints_measurement():
    """One real section end to end in a subprocess on the CPU backend."""
    p = subprocess.run(
        [sys.executable, SCRIPT, "mbs-2"],
        capture_output=True, text=True, env=_smoke_env(), timeout=600,
    )
    assert p.returncode == 0, p.stderr[-2000:]
    m = re.search(r"6\. step mbs=2:\s+[0-9.]+ ms", p.stdout)
    assert m, p.stdout


@pytest.mark.slow
def test_decode_section_runs_on_cpu():
    """The decode section is capture day's top-priority measurement
    (VERDICT r4 #3) and rides the fused while-loop generate path that
    changed this round (sampler cache key) — its plumbing must survive a
    CPU rehearsal, not be debugged on chip time."""
    p = subprocess.run(
        [sys.executable, SCRIPT, "decode"],
        capture_output=True, text=True, env=_smoke_env(), timeout=600,
    )
    assert p.returncode == 0, p.stderr[-2000:]
    m = re.search(r"9\. decode:\s+[0-9]+ tok/s", p.stdout)
    assert m, p.stdout
