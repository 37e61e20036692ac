"""What the multi-host supervision e2es share (``test_multihost.py``: a host
lost; ``test_multihost_elastic.py``: coordinated preemption and elastic
capacity): the supervised run of a fake pod in subprocesses, and the readers
of what it leaves behind. The golden run is ``conftest.py``'s ``baseline``.

CI hygiene (ISSUE 4 satellite): every scenario runs inside
subprocesses with an explicit wall-clock timeout far under the tier-1
budget and the per-case limit (tests/conftest.py), and every training
process runs with ``SCALING_TPU_TEST_CACHE=off`` + no persistent jax compile
cache (the known cache read-back corruption on this container — see
tests/conftest.py). The supervisor itself is also a subprocess, so a
supervision bug can hang/kill only its own process, never the suite.
"""

import json
import os
import signal
import socket
import subprocess
import sys
import threading
from pathlib import Path

REPO = Path(__file__).resolve().parents[3]
DRIVER = Path(__file__).resolve().parent / "multihost_driver.py"

# per-save ckpt.write hits for this arch: 4 model npz + 4 optimizer npz
WRITES_PER_SAVE = 8
# hard per-scenario wall clock (each epoch cold-compiles ~10s; the
# worst tier-1 scenario runs three epochs plus two teardowns: 36 s under six
# loaded workers; the slow drills say their own)
SCENARIO_TIMEOUT = 120


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def run_supervised(tmp_dir: Path, name: str, faults: str = "",
                   timeout: float = SCENARIO_TIMEOUT, *, num_hosts: int = 2,
                   steps: int = 8, save_interval: int = 3, actor=None,
                   **spec_extra):
    """``actor``, when given, runs in a daemon thread alongside the
    supervised run — ``actor(workdir, proc)`` — playing the out-of-pod
    participant an elastic scenario needs (a restored host announcing on
    the capacity channel, a serving fleet heartbeating demand). It must
    poll ``proc.poll() is None`` and return when the run exits."""
    workdir = tmp_dir / name
    spec = {
        "master_port": free_port(),
        "num_hosts": num_hosts,
        "control_dir": str(workdir / "control"),
        "payload": {
            "workdir": str(workdir),
            "steps": steps,
            "save_interval": save_interval,
            "barrier_timeout": spec_extra.pop("barrier_timeout", 30.0),
        },
        **spec_extra,
    }
    spec_file = tmp_dir / f"{name}_spec.json"
    spec_file.write_text(json.dumps(spec))
    # one telemetry dir per scenario: supervisor + every worker (all
    # epochs) append events here, and each worker's log_metrics appends
    # step records — exactly the run dir `python -m scaling_tpu.obs
    # report` is pointed at after a real incident (ISSUE 5)
    telemetry_dir = tmp_dir / f"{name}_telemetry"
    telemetry_dir.mkdir(exist_ok=True)
    env = {
        **os.environ,
        "JAX_PLATFORMS": "cpu",
        "SCALING_TPU_EVENTS_PATH": str(telemetry_dir / "events.jsonl"),
        "SCALING_TPU_METRICS_PATH": str(telemetry_dir / "metrics.jsonl"),
        "SCALING_TPU_TEST_CACHE": "off",
    }
    env.pop("XLA_FLAGS", None)  # fake hosts are single-device by design
    for k in ("SCALING_TPU_HOST_ID", "SCALING_TPU_NUM_HOSTS",
              "SCALING_TPU_CONTROL_DIR", "SCALING_TPU_COORD_EPOCH"):
        env.pop(k, None)
    if faults:
        env["SCALING_TPU_FAULTS"] = faults
    else:
        env.pop("SCALING_TPU_FAULTS", None)
    # own session: on a scenario timeout the driver IS the supervisor, so
    # SIGKILLing it alone would skip _teardown and orphan the fake-host
    # jax workers (the host.hang one sleeps forever) past the pytest run
    p = subprocess.Popen(
        [sys.executable, str(DRIVER), str(spec_file)],
        cwd=REPO, env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
        text=True, start_new_session=True,
    )
    actor_thread = None
    if actor is not None:
        actor_thread = threading.Thread(
            target=actor, args=(workdir, p), daemon=True)
        actor_thread.start()
    try:
        stdout, stderr = p.communicate(timeout=timeout)
    except subprocess.TimeoutExpired:
        os.killpg(p.pid, signal.SIGKILL)
        p.wait(timeout=30)
        raise
    if actor_thread is not None:
        actor_thread.join(timeout=10)
    return subprocess.CompletedProcess(p.args, p.returncode, stdout, stderr), workdir


def read_losses(workdir: Path, host: int) -> dict:
    """step -> loss; later lines win (a resumed epoch rewrites its steps,
    and the rewrites must match — that IS the loss-exactness check)."""
    f = workdir / f"host{host}_losses.jsonl"
    out = {}
    if f.is_file():
        for line in f.read_text().splitlines():
            rec = json.loads(line)
            out[rec["step"]] = rec["loss"]
    return out


def read_result(workdir: Path, host: int) -> dict:
    return json.loads((workdir / f"host{host}_result.json").read_text())


def read_events(tmp_dir: Path, name: str) -> list:
    f = tmp_dir / f"{name}_telemetry" / "events.jsonl"
    if not f.is_file():
        return []
    return [json.loads(l) for l in f.read_text().splitlines()]
