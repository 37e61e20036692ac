"""The bring-up gate fails where there is nothing to measure.

``chip_smoke.py`` needs a TPU: on the CPU it exits non-zero and prints no
result line, so that no record can carry a number the chip did not produce.
``dryrun_multichip`` refuses a device count it does not have unless the
virtual CPU mesh was asked for by name.
"""

import os
import subprocess
import sys
from pathlib import Path

import pytest

REPO_ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(REPO_ROOT))


def _run_on_cpu(script, **env):
    return subprocess.run(
        [sys.executable, str(REPO_ROOT / script)],
        capture_output=True, text=True, timeout=120, cwd=REPO_ROOT,
        env={**os.environ, "JAX_PLATFORMS": "cpu",
             "SCALING_TPU_TEST_CACHE": "off", **env},
    )


def test_chip_smoke_fails_without_a_tpu():
    proc = _run_on_cpu("chip_smoke.py")
    assert proc.returncode != 0
    assert "no TPU" in proc.stderr
    assert '"ok"' not in proc.stdout


def test_dryrun_multichip_refuses_too_few_devices(devices, monkeypatch):
    """Fewer devices than asked raises; only the explicit virtual CPU mesh
    (a child process, stubbed here) stands in for them."""
    import __graft_entry__ as entry

    with pytest.raises(RuntimeError, match="virtual_cpu_mesh=True"):
        entry.dryrun_multichip(len(devices) + 8)
    asked = []
    monkeypatch.setattr(entry, "_run_on_virtual_cpu_mesh", asked.append)
    entry.dryrun_multichip(len(devices) + 8, virtual_cpu_mesh=True)
    assert asked == [len(devices) + 8]
