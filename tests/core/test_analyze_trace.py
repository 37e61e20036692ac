"""The trace analyzer must parse a REAL xplane dump: it walks the protobuf
wire format by hand (the installed tensorboard plugin's generated protos
are broken against the installed protobuf), so a jax upgrade that shifts
the xplane schema has to fail HERE, on the CPU, not on chip time."""

import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp

REPO_ROOT = Path(__file__).resolve().parents[2]


def test_analyze_trace_parses_real_xplane_dump(tmp_path):
    @jax.jit
    def work(x):
        return (x @ x).sum()

    x = jnp.ones((256, 256))
    jax.block_until_ready(work(x))  # compile outside the trace
    jax.profiler.start_trace(str(tmp_path))
    jax.block_until_ready(work(x))
    jax.profiler.stop_trace()
    assert list(tmp_path.glob("**/*.xplane.pb")), "jax wrote no xplane file"

    proc = subprocess.run(
        [sys.executable, str(REPO_ROOT / "benchmarks" / "analyze_trace.py"),
         str(tmp_path)],
        capture_output=True,
        text=True,
        timeout=120,
        cwd=REPO_ROOT,
    )
    assert proc.returncode == 0, proc.stderr
    # parsed real content: at least one line section with per-op rows
    assert "==" in proc.stdout, proc.stdout
    assert "ms total" in proc.stdout
    assert "%" in proc.stdout
