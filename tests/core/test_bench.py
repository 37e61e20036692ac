"""The measurement entry points fail where there is nothing to measure.

``bench.py`` and ``chip_smoke.py`` need a TPU: on the CPU they exit non-zero
and print no result line, so that no record can carry a number the chip did
not produce. ``dryrun_multichip`` refuses a device count it does not have
unless the virtual CPU mesh was asked for by name.
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


def test_bench_fails_without_a_tpu():
    proc = _run_on_cpu("bench.py")
    assert proc.returncode != 0
    assert "JAX found cpu" in proc.stderr
    # no throughput line, no JSON at all
    assert "tokens_per_sec" not in proc.stdout and "{" not in proc.stdout


def test_bench_rejects_unknown_model():
    proc = _run_on_cpu("bench.py", BENCH_MODEL="7b")
    assert proc.returncode != 0
    assert "unknown BENCH_MODEL" in proc.stderr
    assert "{" not in proc.stdout


def test_chip_smoke_fails_without_a_tpu():
    proc = _run_on_cpu("chip_smoke.py")
    assert proc.returncode != 0
    assert "no TPU" in proc.stderr
    assert '"ok"' not in proc.stdout


def _fake_measure(times):
    def measure(mbs):
        t = times[mbs]
        if isinstance(t, Exception):
            raise t
        return f"arch{mbs}", t
    return measure


def test_mbs_ladder_logic():
    """The self-tune ladder (pure logic, faked measurements): climbs while
    per-token speed improves, stops on the first non-winner, and an arm that
    does not fit keeps the recorded winner instead of killing the bench."""
    import bench

    # 8 wins per token (8/1.5 > 4/1), 16 loses (16/4 < 8/1.5) -> keep 8
    times = {4: 1.0, 8: 1.5, 16: 4.0, 32: 0.1}
    arch, dt, mbs = bench.climb_mbs_ladder(
        _fake_measure(times), [4, 8, 16, 32], "arch4", times[4]
    )
    assert (arch, dt, mbs) == ("arch8", 1.5, 8)  # 32 never measured

    # 8 does not fit -> stay at 4
    oom = RuntimeError("RESOURCE_EXHAUSTED: out of memory allocating 9.1G")
    arch, dt, mbs = bench.climb_mbs_ladder(
        _fake_measure({4: 1.0, 8: oom}), [4, 8, 16], "arch4", 1.0
    )
    assert (arch, dt, mbs) == ("arch4", 1.0, 4)

    # monotone winner climbs to the top rung
    times = {4: 1.0, 8: 1.9, 16: 3.7}
    arch, dt, mbs = bench.climb_mbs_ladder(
        _fake_measure(times), [4, 8, 16], "arch4", 1.0
    )
    assert mbs == 16


def test_mbs_ladder_swallows_only_out_of_memory():
    """Any other failure of a rung is a failure of the bench: a kernel that
    breaks at a larger batch must not read as 'the smaller batch won'."""
    import bench

    broken = ValueError("Mosaic failed to compile the kernel")
    with pytest.raises(ValueError, match="Mosaic"):
        bench.climb_mbs_ladder(
            _fake_measure({4: 1.0, 8: broken}), [4, 8], "arch4", 1.0
        )


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
