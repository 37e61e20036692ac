"""Multi-host supervision e2e (ISSUE 4 acceptance): a fake 2-host pod
under the heartbeat supervisor survives a SIGKILLed host (teardown,
relaunch with a new coordinator epoch, loss-exact resume from the
newest valid checkpoint, no manual cleanup), never advances ``latest``
past a save interrupted between shard commit and the cross-host commit
barrier, and drains coordinated preemption — SIGTERM on ONE host makes
every host save at the same step boundary and exit resume-ready.

CI hygiene (ISSUE 4 satellite): every scenario runs inside
subprocesses with an explicit wall-clock timeout far under the tier-1
``timeout -k 10 870`` budget, and every training process runs with
``SCALING_TPU_TEST_CACHE=off`` + no persistent jax compile cache (the
known cache read-back corruption on this container — see
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
import time
from pathlib import Path

import numpy as np
import pytest

from scaling_tpu.resilience import verify_checkpoint

REPO = Path(__file__).resolve().parents[3]
DRIVER = Path(__file__).resolve().parent / "multihost_driver.py"

# per-save ckpt.write hits for this arch: 4 model npz + 4 optimizer npz
WRITES_PER_SAVE = 8
# hard per-scenario wall clock (each epoch cold-compiles ~10s; the
# worst scenario runs three epochs plus two teardowns)
SCENARIO_TIMEOUT = 240


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


@pytest.fixture(scope="module")
def baseline(tmp_path_factory):
    """One uninterrupted single-host supervised run: the golden loss
    trajectory every fake host (same seed, same program) must replay."""
    tmp = tmp_path_factory.mktemp("multihost_e2e")
    p, workdir = run_supervised(tmp, "baseline", num_hosts=1)
    assert p.returncode == 0, p.stdout[-3000:] + p.stderr[-3000:]
    gold = read_losses(workdir, 0)
    assert sorted(gold) == list(range(1, 9))
    return tmp, gold


def test_kill_one_host_supervisor_relaunches_loss_exact(baseline):
    """host.kill on host 1 (of 2) at iteration boundaries: the supervisor
    must tear down the survivor (no indefinite barrier hang), relaunch
    the pod as a fresh coordinator epoch, and the relaunched hosts must
    resume from the newest VALID checkpoint and replay the golden losses
    exactly — with no manual cleanup in between. The armed hit count
    re-fires in each epoch's fresh process, so the run takes two
    relaunches before the kill window falls off the end of training."""
    tmp, gold = baseline
    p, workdir = run_supervised(
        tmp, "kill", faults="host.kill=kill@5@host=1", restart_budget=2,
    )
    assert p.returncode == 0, p.stdout[-3000:] + p.stderr[-3000:]
    for host in (0, 1):
        result = read_result(workdir, host)
        assert result["iterations"] == 8
        # the LAST epoch resumed from the newest valid checkpoint
        assert result["resumed_from"] == 6
        assert result["epoch"] == 2  # two relaunches happened
        losses = read_losses(workdir, host)
        assert sorted(losses) == list(range(1, 9))
        np.testing.assert_array_equal(
            np.asarray([losses[s] for s in range(1, 9)]),
            np.asarray([gold[s] for s in range(1, 9)]),
        )
        ckpt = workdir / f"host{host}" / "ckpt"
        assert (ckpt / "latest").read_text() == "global_step6"
        assert verify_checkpoint(ckpt / "global_step6") == []
    events = read_events(tmp, "kill")
    dead = [e for e in events if e["event"] == "host-dead"]
    assert len(dead) == 2 and all(e["hosts"] == [1] for e in dead)
    assert all(e["reason"] == "exit" for e in dead)
    relaunches = [e for e in events if e["event"] == "relaunch"]
    assert [e["epoch"] for e in relaunches] == [1, 2]
    assert any(e["event"] == "epoch-clean-exit" for e in events)

    # ISSUE 5 acceptance: the run's telemetry dir (events + metrics
    # JSONL from the supervisor and every worker across all 3 epochs)
    # parses cleanly through the run-dir analyzer
    from scaling_tpu.obs.cli import main as obs_main
    from scaling_tpu.obs.report import load_run_dir, render_report

    telemetry = tmp / "kill_telemetry"
    data = load_run_dir(telemetry)
    assert data.bad_lines == 0, f"unparseable telemetry: {data.bad_lines}"
    assert {r["host"] for r in data.steps} == {0, 1}
    report = render_report(data, telemetry)
    assert "restarts=2" in report
    assert "step 3:" in report and "step 6:" in report  # ckpt breakdown
    assert obs_main(["report", str(telemetry)]) == 0


def test_kill_between_commit_and_barrier_latest_never_advances(baseline):
    """The commit-barrier guarantee: host 0 is SIGKILLed AFTER its step-6
    shard commit but BEFORE the ``commit:step-6`` barrier, while host 1
    dies mid-write of the same save (leaving staging debris). ``latest``
    must still point at step 3 on BOTH hosts — no torn multi-step
    checkpoint can ever be assembled — and a later supervised run must
    restore from step 3, sweep the debris, and re-commit step 6."""
    tmp, gold = baseline
    p, workdir = run_supervised(
        tmp, "midsave",
        faults=(
            "ckpt.commit_barrier=kill@2@host=0,"
            f"ckpt.write=kill@{WRITES_PER_SAVE + 4}@host=1"
        ),
        restart_budget=0,
    )
    assert p.returncode != 0  # budget 0: the supervisor gave up
    for host in (0, 1):
        ckpt = workdir / f"host{host}" / "ckpt"
        # the one invariant that makes mixed-step checkpoints impossible
        assert (ckpt / "latest").read_text() == "global_step3"
        assert verify_checkpoint(ckpt / "global_step3") == []
    # host 0 committed its shard (rename done) but never advanced latest
    assert (workdir / "host0" / "ckpt" / "global_step6").is_dir()
    # host 1 died mid-write: only staging debris, never a committed dir
    assert not (workdir / "host1" / "ckpt" / "global_step6").exists()
    assert (workdir / "host1" / "ckpt" / ".tmp-global_step6").is_dir()
    events = read_events(tmp, "midsave")
    assert any(e["event"] == "host-dead" for e in events)
    assert any(e["event"] == "give-up" for e in events)

    # ---- recovery: same directories, NO manual cleanup
    p2, workdir = run_supervised(tmp, "midsave", restart_budget=0)
    assert p2.returncode == 0, p2.stdout[-3000:] + p2.stderr[-3000:]
    for host in (0, 1):
        result = read_result(workdir, host)
        assert result["resumed_from"] == 3  # latest honored, step 6 torn
        assert result["iterations"] == 8
        losses = read_losses(workdir, host)
        np.testing.assert_array_equal(
            np.asarray([losses[s] for s in range(4, 9)]),
            np.asarray([gold[s] for s in range(4, 9)]),
        )
        ckpt = workdir / f"host{host}" / "ckpt"
        # debris swept by the re-reached save; step 6 re-committed whole
        assert not (ckpt / ".tmp-global_step6").exists()
        assert verify_checkpoint(ckpt / "global_step6") == []
        assert (ckpt / "latest").read_text() == "global_step6"


def test_sigterm_one_host_preempts_all_at_same_boundary(baseline):
    """Coordinated preemption: SIGTERM delivered to exactly ONE fake
    host becomes a broadcast flag; every host observes it at the same
    lockstep boundary, saves at the same step, and exits resume-ready —
    the supervisor treats the drained epoch as clean (no relaunch)."""
    tmp, gold = baseline
    p, workdir = run_supervised(
        tmp, "sigterm", faults="signal.sigterm=sigterm@4@host=1",
        restart_budget=1,
    )
    assert p.returncode == 0, p.stdout[-3000:] + p.stderr[-3000:]
    for host in (0, 1):
        result = read_result(workdir, host)
        assert result["iterations"] == 3  # both stopped at the SAME step
        assert result["preempted"] is True
        losses = read_losses(workdir, host)
        assert sorted(losses) == [1, 2, 3]
        np.testing.assert_array_equal(
            np.asarray([losses[s] for s in (1, 2, 3)]),
            np.asarray([gold[s] for s in (1, 2, 3)]),
        )
        ckpt = workdir / f"host{host}" / "ckpt"
        assert (ckpt / "latest").read_text() == "global_step3"
        assert verify_checkpoint(ckpt / "global_step3") == []
    events = read_events(tmp, "sigterm")
    bcast = [e for e in events if e["event"] == "preempt-broadcast"]
    assert bcast and bcast[0]["host"] == 1  # the signaled host spoke first
    assert not any(e["event"] == "relaunch" for e in events)
    clean = [e for e in events if e["event"] == "epoch-clean-exit"]
    assert clean and clean[0]["preempted"] is True


def test_sigterm_to_supervisor_drains_all_hosts_same_boundary(baseline):
    """Operator-initiated drain: SIGTERM to the SUPERVISOR is relayed
    as SIGTERM to every worker (never a raw flag write, which two
    lockstep hosts could observe on opposite sides of a barrier
    release and split their exit boundaries). Both hosts must save at
    the same step and exit 0; the epoch is clean, no relaunch.

    The 8 steps take ~4 ms each, less than any poll from outside: each
    host sleeps a second after every step (``step_delay``), so the
    signal sent at the first sight of both loss files lands with seven
    seconds of training ahead. A run that finished by itself fails as
    that, by ``stop < steps``, before anything is said of the drain."""
    steps = 8
    tmp, gold = baseline
    workdir = tmp / "supterm"
    spec = {
        "master_port": free_port(),
        "num_hosts": 2,
        "control_dir": str(workdir / "control"),
        "payload": {
            "workdir": str(workdir), "steps": steps, "save_interval": 3,
            "barrier_timeout": 30.0, "step_delay": 1.0,
        },
        "restart_budget": 1,
    }
    spec_file = tmp / "supterm_spec.json"
    spec_file.write_text(json.dumps(spec))
    telemetry_dir = tmp / "supterm_telemetry"
    telemetry_dir.mkdir(exist_ok=True)
    env = {
        **os.environ,
        "JAX_PLATFORMS": "cpu",
        "SCALING_TPU_EVENTS_PATH": str(telemetry_dir / "events.jsonl"),
        "SCALING_TPU_METRICS_PATH": str(telemetry_dir / "metrics.jsonl"),
        "SCALING_TPU_TEST_CACHE": "off",
    }
    env.pop("XLA_FLAGS", None)
    for k in ("SCALING_TPU_HOST_ID", "SCALING_TPU_NUM_HOSTS",
              "SCALING_TPU_CONTROL_DIR", "SCALING_TPU_COORD_EPOCH",
              "SCALING_TPU_FAULTS"):
        env.pop(k, None)
    p = subprocess.Popen(
        [sys.executable, str(DRIVER), str(spec_file)], cwd=REPO, env=env,
        start_new_session=True,
    )
    try:
        deadline = time.monotonic() + SCENARIO_TIMEOUT
        while time.monotonic() < deadline:
            # signal once both hosts are demonstrably mid-training
            if ((workdir / "host0_losses.jsonl").is_file()
                    and (workdir / "host1_losses.jsonl").is_file()):
                break
            time.sleep(0.05)
        else:
            pytest.fail("workers never started training")
        p.send_signal(signal.SIGTERM)
        assert p.wait(timeout=SCENARIO_TIMEOUT) == 0
    finally:
        if p.poll() is None:
            os.killpg(p.pid, signal.SIGKILL)
            p.wait(timeout=30)
    r0, r1 = read_result(workdir, 0), read_result(workdir, 1)
    stop = r0["iterations"]
    assert stop < steps, "the run was over before the signal: no drill"
    assert r0["preempted"] is True and r1["preempted"] is True
    assert r0["iterations"] == r1["iterations"]  # SAME boundary
    for host in (0, 1):
        losses = read_losses(workdir, host)
        assert sorted(losses) == list(range(1, stop + 1))
        np.testing.assert_array_equal(
            np.asarray([losses[s] for s in range(1, stop + 1)]),
            np.asarray([gold[s] for s in range(1, stop + 1)]),
        )
    events = read_events(tmp, "supterm")
    assert any(e["event"] == "preempt-relay" for e in events)
    assert not any(e["event"] == "relaunch" for e in events)


def test_downsize_two_hosts_to_one_continues_loss_exact(baseline):
    """Elastic downsizing e2e (ISSUE 12): host 1 dies at its 5th loop
    entry in EVERY epoch (``x*`` re-arms per relaunch) — the capacity is
    never coming back. With ``downsize_after=2`` the supervisor retries
    the full size twice, then drops host 1 from the plan and relaunches
    the survivor alone: the downsized epoch resumes from the newest
    checkpoint (written under the 2-host world — the restoring 1-host
    topology differs, so the trainer's reshard path engages and logs the
    ``ckpt-reshard`` transition), completes loss-exact, and the
    supervisor exits 0 instead of burning its budget and giving up.
    The run dir must parse through ``obs report`` with the downsize in
    the restart timeline and pass/fail ``--assert-max-downsizes``."""
    tmp, gold = baseline
    p, workdir = run_supervised(
        tmp, "downsize", faults="host.kill=kill@5x*@host=1",
        restart_budget=2, downsize_after=2,
    )
    assert p.returncode == 0, p.stdout[-3000:] + p.stderr[-3000:]
    # the survivor finished the run in the downsized epoch, resuming
    # from the last checkpoint the 2-host world committed
    result = read_result(workdir, 0)
    assert result["iterations"] == 8
    assert result["resumed_from"] == 6
    assert result["epoch"] == 2  # epochs 0,1 at world 2; epoch 2 at world 1
    losses = read_losses(workdir, 0)
    assert sorted(losses) == list(range(1, 9))
    np.testing.assert_array_equal(
        np.asarray([losses[s] for s in range(1, 9)]),
        np.asarray([gold[s] for s in range(1, 9)]),
    )
    ckpt = workdir / "host0" / "ckpt"
    assert (ckpt / "latest").read_text() == "global_step6"
    assert verify_checkpoint(ckpt / "global_step6") == []
    # host 1 never finished: SIGKILLed in both full-size epochs
    assert not (workdir / "host1_result.json").exists()

    events = read_events(tmp, "downsize")
    downs = [e for e in events if e["event"] == "downsize"]
    assert len(downs) == 1
    assert downs[0]["old_world"] == 2 and downs[0]["new_world"] == 1
    assert downs[0]["removed_hosts"] == [1]
    dead = [e for e in events if e["event"] == "host-dead"]
    assert len(dead) == 2 and all(e["hosts"] == [1] for e in dead)
    # the downsized epoch's restore crossed mesh shapes: 2 hosts -> 1
    reshards = [e for e in events if e["event"] == "ckpt-reshard"]
    assert reshards and reshards[-1]["saved_hosts"] == 2
    assert reshards[-1]["restoring_hosts"] == 1
    assert any(e["event"] == "epoch-clean-exit" for e in events)

    # obs report: the incident run dir parses; the restart timeline
    # carries the world-size transition; the gate counts downsizes and
    # fails at a too-low ceiling
    from scaling_tpu.obs.cli import main as obs_main
    from scaling_tpu.obs.report import load_run_dir, render_report

    telemetry = tmp / "downsize_telemetry"
    data = load_run_dir(telemetry)
    assert data.bad_lines == 0, f"unparseable telemetry: {data.bad_lines}"
    report = render_report(data, telemetry)
    assert "downsizes=1" in report
    assert "world-size transitions:" in report and "2->1" in report
    assert obs_main([
        "report", str(telemetry), "--assert-max-downsizes", "1",
    ]) == 0
    assert obs_main([
        "report", str(telemetry), "--assert-max-downsizes", "0",
    ]) == 1


@pytest.mark.slow
def test_chaos_downsize_drill_three_to_two_to_one_loss_exact(baseline):
    """Chaos downsize drill (ISSUE 13 satellite, ROADMAP elastic
    follow-on): a 3-host pod downsize-LOOPS to 1 under continuous
    ``SCALING_TPU_FAULTS`` injection. Host 2 dies at its 5th loop entry
    in every epoch (its capacity never returns); after ``downsize_after
    = 2`` consecutive losses the supervisor drops it and relaunches at
    world 2 — where host 1 starts dying (``@epoch=`` scoped rules: the
    same ``host.kill`` point armed per-epoch), forcing the second
    downsize. A transient ``data.read`` fault also fires in every
    worker process throughout (absorbed by the bounded-retry layer).
    The surviving host completes all 12 steps LOSS-EXACT vs a golden
    12-step run — capacity loss degraded service, never correctness
    (ATP, arxiv 2301.08658) — and the run dir parses through ``obs
    report`` with the full 3->2->1 transition timeline and
    passes/fails ``--assert-max-downsizes`` at 2/1.

    12 steps (not the module baseline's 8) so the world-2 epochs live
    long enough to COMMIT a checkpoint of their own: the final epoch
    then restores a world-2 save onto the 1-host mesh — both downsizes
    exercise reshard-on-restore, not just the first.

    Kill-window arithmetic (save_interval 3): epoch 0 kills host 2 at
    entry 5 (latest=3), epoch 1 resumes from 3 and re-kills at entry 5
    = step 8 (latest=6) -> downsize. Epoch 2 (world 2) resumes from 6
    (reshard 3->2), saves step 9, host 1 dies at entry 4 (latest=9);
    epoch 3 resumes from 9 and dies at entry 2 -> downsize. Epoch 4
    (world 1) resumes from 9 (reshard 2->1) and completes.

    Slow tier: six supervised epochs incl. the golden run at ~12s cold
    compile each."""
    tmp, _ = baseline
    p0, golddir = run_supervised(
        tmp, "chaos3_gold", num_hosts=1, steps=12,
    )
    assert p0.returncode == 0, p0.stdout[-3000:] + p0.stderr[-3000:]
    gold = read_losses(golddir, 0)
    assert sorted(gold) == list(range(1, 13))

    p, workdir = run_supervised(
        tmp, "chaos3", num_hosts=3, steps=12,
        faults=(
            "host.kill=kill@5x*@host=2,"
            "host.kill=kill@4x*@host=1@epoch=2,"
            "host.kill=kill@2x*@host=1@epoch=3,"
            "data.read=fail@2"
        ),
        restart_budget=2, downsize_after=2, timeout=420,
    )
    assert p.returncode == 0, p.stdout[-3000:] + p.stderr[-3000:]
    # the last survivor finished the run in the twice-downsized epoch
    result = read_result(workdir, 0)
    assert result["iterations"] == 12
    assert result["epoch"] == 4  # 0,1 @ world 3; 2,3 @ world 2; 4 @ world 1
    assert result["resumed_from"] == 9  # a checkpoint the WORLD-2 pod wrote
    losses = read_losses(workdir, 0)
    assert sorted(losses) == list(range(1, 13))
    np.testing.assert_array_equal(
        np.asarray([losses[s] for s in range(1, 13)]),
        np.asarray([gold[s] for s in range(1, 13)]),
    )
    ckpt = workdir / "host0" / "ckpt"
    assert (ckpt / "latest").read_text() == "global_step12"
    assert verify_checkpoint(ckpt / "global_step12") == []

    events = read_events(tmp, "chaos3")
    downs = [e for e in events if e["event"] == "downsize"]
    assert [(e["old_world"], e["new_world"]) for e in downs] == [
        (3, 2), (2, 1),
    ]
    assert downs[0]["removed_hosts"] == [2]
    assert downs[1]["removed_hosts"] == [1]
    # each downsized epoch's restore crossed mesh shapes
    reshards = [e for e in events if e["event"] == "ckpt-reshard"]
    assert [(e["saved_hosts"], e["restoring_hosts"]) for e in reshards][-1] \
        == (2, 1)
    assert any(
        (e["saved_hosts"], e["restoring_hosts"]) == (3, 2) for e in reshards
    )
    assert any(e["event"] == "epoch-clean-exit" for e in events)

    # the full transition timeline through the real analyzer + gates
    from scaling_tpu.obs.cli import main as obs_main
    from scaling_tpu.obs.report import load_run_dir, render_report

    telemetry = tmp / "chaos3_telemetry"
    data = load_run_dir(telemetry)
    assert data.bad_lines == 0, f"unparseable telemetry: {data.bad_lines}"
    report = render_report(data, telemetry)
    assert "downsizes=2" in report
    assert "world-size transitions:" in report
    assert "3->2" in report and "2->1" in report
    assert obs_main(
        ["report", str(telemetry), "--assert-max-downsizes", "2"]
    ) == 0
    assert obs_main(
        ["report", str(telemetry), "--assert-max-downsizes", "1"]
    ) == 1


@pytest.mark.slow
def test_hung_host_detected_by_stale_heartbeat_and_relaunched(baseline):
    """host.hang wedges host 0's loop without exiting — only the missing
    heartbeats give it away. The supervisor must declare it hung, SIGKILL
    it after the SIGTERM grace (a wedged host ignores SIGTERM), tear down
    the (still-heartbeating, barrier-parked) survivor, and relaunch to
    completion. Like the kill scenario, the armed hit re-fires per epoch,
    so completion takes two relaunches.

    Slow tier: ~1 min of deliberate stale-heartbeat waiting; the
    detection policy itself rides the fast tier in
    tests/core/test_runner/test_supervisor.py (classify_workers units)
    and the teardown escalation in its SIGTERM→SIGKILL unit."""
    tmp, gold = baseline
    p, workdir = run_supervised(
        tmp, "hang", faults="host.hang=hang@5@host=0", restart_budget=2,
        heartbeat_timeout=6.0, worker_grace=3.0, barrier_timeout=120.0,
        # the driver's 240s default equals SCENARIO_TIMEOUT, and the
        # grace suppresses ALL staleness verdicts — detection could
        # never fire in time. The fake hosts cold-compile in ~12s, so
        # 60s still shields startup while leaving three epochs' worth
        # of detect+relaunch inside the scenario budget
        startup_grace=60.0,
    )
    assert p.returncode == 0, p.stdout[-3000:] + p.stderr[-3000:]
    for host in (0, 1):
        result = read_result(workdir, host)
        assert result["iterations"] == 8
        losses = read_losses(workdir, host)
        np.testing.assert_array_equal(
            np.asarray([losses[s] for s in range(1, 9)]),
            np.asarray([gold[s] for s in range(1, 9)]),
        )
    events = read_events(tmp, "hang")
    dead = [e for e in events if e["event"] == "host-dead"]
    # the hung host was identified by heartbeat staleness, not exit code
    assert dead and all(e["reason"] == "heartbeat-stale" for e in dead)
    assert all(0 in e["hosts"] for e in dead)
    assert any(e["event"] == "epoch-clean-exit" for e in events)


@pytest.fixture(scope="module")
def baseline12(baseline):
    """Uninterrupted 12-step golden run for the elastic-capacity e2es
    (their world-2 epochs need enough steps to commit checkpoints of
    their own before the resize dance starts)."""
    tmp, _ = baseline
    p, workdir = run_supervised(tmp, "gold12", num_hosts=1, steps=12)
    assert p.returncode == 0, p.stdout[-3000:] + p.stderr[-3000:]
    gold = read_losses(workdir, 0)
    assert sorted(gold) == list(range(1, 13))
    return tmp, gold


def _event_seen(tmp: Path, name: str, event: str) -> bool:
    f = tmp / f"{name}_telemetry" / "events.jsonl"
    try:
        lines = f.read_text().splitlines()
    except OSError:
        return False
    for line in lines:
        try:
            if json.loads(line).get("event") == event:
                return True
        except ValueError:
            continue  # torn tail line mid-write
    return False


@pytest.mark.slow
def test_upsize_restored_host_sizes_pod_back_up_loss_exact(baseline12):
    """Elastic size-back-up e2e (ISSUE 19 tentpole): host 1 dies at its
    5th loop entry in epochs 0 and 1 (``@epoch=`` scoped — the restored
    capacity must NOT be re-killed later), the supervisor downsizes to 1
    after ``downsize_after=2`` losses — and THEN the capacity comes
    back: an out-of-pod actor announces the restored host on the
    capacity channel with a stable incarnation. After ``upsize_after=3``
    consecutive healthy observations the supervisor drains the
    downsized epoch at a step boundary (coordinated-preemption save),
    replans over the larger pool, and relaunches at world 2:
    reshard-on-restore GROWS the mesh (1 -> 2), consumed samples carry
    over skip/repeat-free, and the final losses are EXACT vs the
    uninterrupted golden run. The run dir renders both world-size
    transitions through ``obs report`` and passes/fails the generalized
    ``--assert-max-resizes`` gate at 2/1.

    Slow tier: five supervised epochs incl. the 12-step golden run."""
    tmp, gold = baseline12

    def restored_host(workdir, proc):
        # the restored host: silent until after the downsize (a host
        # that shrank the job must re-prove itself from OUTSIDE the
        # pod), then a steady heartbeat with a FIXED incarnation until
        # the supervisor acts on it
        from scaling_tpu.resilience.capacity import CapacityChannel

        while proc.poll() is None and not _event_seen(
                tmp, "upsize", "downsize"):
            time.sleep(0.1)
        ch = CapacityChannel(workdir / "control" / "capacity")
        # heartbeat until the upsize EXECUTES (not merely drains): a
        # drained decision that could not be applied must find the
        # announcement still there on the retry
        while proc.poll() is None and not _event_seen(
                tmp, "upsize", "upsize"):
            ch.announce("standby-1", "localhost", 1, incarnation=1)
            time.sleep(0.1)
        ch.withdraw("standby-1")

    p, workdir = run_supervised(
        tmp, "upsize", steps=12,
        faults=(
            "host.kill=kill@5x*@host=1@epoch=0,"
            "host.kill=kill@5x*@host=1@epoch=1"
        ),
        restart_budget=2, downsize_after=2, upsize_after=3,
        actor=restored_host, timeout=420,
    )
    assert p.returncode == 0, p.stdout[-3000:] + p.stderr[-3000:]
    # BOTH hosts finished the final full-size epoch — the restored
    # capacity rejoined and ran to completion
    for host in (0, 1):
        result = read_result(workdir, host)
        assert result["iterations"] == 12
        assert result["epoch"] == 3  # 0,1 @ 2; 2 @ 1 (drained); 3 @ 2
    # epoch 2 resumed from a checkpoint the 2-host world wrote
    assert read_result(workdir, 0)["resumed_from"] >= 6
    losses = read_losses(workdir, 0)
    assert sorted(losses) == list(range(1, 13))
    np.testing.assert_array_equal(
        np.asarray([losses[s] for s in range(1, 13)]),
        np.asarray([gold[s] for s in range(1, 13)]),
    )
    # the restored host's replayed steps are exact too (it missed the
    # middle of the run, so only compare the steps it logged)
    losses1 = read_losses(workdir, 1)
    assert losses1
    for s, v in losses1.items():
        assert v == gold[s], f"host1 step {s}: {v} != {gold[s]}"

    events = read_events(tmp, "upsize")
    downs = [e for e in events if e["event"] == "downsize"]
    assert len(downs) == 1
    assert downs[0]["old_world"] == 2 and downs[0]["new_world"] == 1
    ups = [e for e in events if e["event"] == "upsize"]
    assert len(ups) == 1
    assert ups[0]["old_world"] == 1 and ups[0]["new_world"] == 2
    assert ups[0]["source"] == "announce"
    assert ups[0]["added_hosts"] == ["localhost"]
    drains = [e for e in events if e["event"] == "capacity-drain"]
    assert [e["action"] for e in drains] == ["upsize"]
    # reshard-on-restore engaged in BOTH directions
    reshards = [
        (e["saved_hosts"], e["restoring_hosts"])
        for e in events if e["event"] == "ckpt-reshard"
    ]
    assert (2, 1) in reshards and (1, 2) in reshards
    assert any(e["event"] == "epoch-clean-exit" for e in events)

    from scaling_tpu.obs.cli import main as obs_main
    from scaling_tpu.obs.report import load_run_dir, render_report

    telemetry = tmp / "upsize_telemetry"
    data = load_run_dir(telemetry)
    assert data.bad_lines == 0, f"unparseable telemetry: {data.bad_lines}"
    report = render_report(data, telemetry)
    assert "world-size transitions:" in report
    assert "2->1" in report and "1->2" in report
    assert "downsizes=1" in report and "upsizes=1" in report
    assert obs_main(
        ["report", str(telemetry), "--assert-max-resizes", "2"]
    ) == 0
    assert obs_main(
        ["report", str(telemetry), "--assert-max-resizes", "1"]
    ) == 1
    # the legacy flag is an alias counting BOTH directions
    assert obs_main(
        ["report", str(telemetry), "--assert-max-downsizes", "2"]
    ) == 0
    assert obs_main(
        ["report", str(telemetry), "--assert-max-downsizes", "1"]
    ) == 1


@pytest.mark.slow
def test_arbitration_serving_burst_borrows_and_returns_a_host(baseline12):
    """Train<->serve arbitration e2e (ISSUE 19 tentpole): a fake serving
    fleet rides the same capacity channel. Sustained fleet pressure
    makes the arbiter lend a training host — drain at a step boundary,
    journaled lease GRANT (grant-before-shrink: the no-orphan
    guarantee), downsize with ``source="lease"`` — and sustained fleet
    idle returns it: journal-only reclaim, fleet releases, training
    upsizes with ``source="lease-return"``. A ``capacity.lease`` fault
    kills the FIRST handoff mid-grant: no lease may exist afterwards
    (training keeps the host, relaunches at full size) and the arbiter
    retries after its cooldown — kill-mid-handoff leaves no orphaned
    host on either side. Final losses EXACT vs the uninterrupted
    golden; the lease journal is empty at exit.

    Slow tier: five supervised epochs (the injected grant failure adds
    a full-size relaunch before the real handoff)."""
    tmp, gold = baseline12
    handoff = {"activated": 0, "released": 0}

    def fleet(workdir, proc):
        from scaling_tpu.resilience.capacity import (
            CapacityChannel,
            FleetCapacityClient,
        )

        ch = CapacityChannel(workdir / "control" / "capacity")
        client = FleetCapacityClient(ch, publish_interval_s=0.0)
        # let training make real progress before the burst
        losses = workdir / "host0_losses.jsonl"
        while proc.poll() is None and not losses.is_file():
            time.sleep(0.1)
        lease = None
        while proc.poll() is None and lease is None:
            client.publish(pressure=0.9, queue=8, replicas=1)
            granted = client.granted()
            lease = granted[0] if granted else None
            time.sleep(0.1)
        if lease is None:
            return
        lease = client.activate(lease)
        handoff["activated"] += 1
        # burst over: sustained idle until the arbiter reclaims
        back = None
        while proc.poll() is None and back is None:
            client.publish(pressure=0.0, queue=0, replicas=1)
            reclaiming = client.reclaiming()
            back = reclaiming[0] if reclaiming else None
            time.sleep(0.1)
        if back is not None:
            client.release(back)
            handoff["released"] += 1

    p, workdir = run_supervised(
        tmp, "arb", steps=16, arbitrate=True, min_train_hosts=1,
        sustain=0.3, idle=0.3, cooldown=0.5,
        faults="capacity.lease=fail@1",
        restart_budget=2, actor=fleet, timeout=420,
    )
    assert p.returncode == 0, p.stdout[-3000:] + p.stderr[-3000:]
    assert handoff == {"activated": 1, "released": 1}
    for host in (0, 1):
        result = read_result(workdir, host)
        assert result["iterations"] == 16
    losses = read_losses(workdir, 0)
    assert sorted(losses) == list(range(1, 17))
    gold16 = {}
    p0, golddir = run_supervised(tmp, "arb_gold", num_hosts=1, steps=16)
    assert p0.returncode == 0, p0.stdout[-3000:] + p0.stderr[-3000:]
    gold16 = read_losses(golddir, 0)
    np.testing.assert_array_equal(
        np.asarray([losses[s] for s in range(1, 17)]),
        np.asarray([gold16[s] for s in range(1, 17)]),
    )

    events = read_events(tmp, "arb")
    downs = [e for e in events if e["event"] == "downsize"]
    assert len(downs) == 1
    assert downs[0]["source"] == "lease"
    assert downs[0]["old_world"] == 2 and downs[0]["new_world"] == 1
    assert downs[0]["removed_hosts"] == ["localhost"]
    ups = [e for e in events if e["event"] == "upsize"]
    assert len(ups) == 1
    assert ups[0]["source"] == "lease-return"
    assert ups[0]["old_world"] == 1 and ups[0]["new_world"] == 2
    # the killed first handoff: TWO lease drains, ONE downsize — the
    # failed grant left no lease, training kept the host
    drains = [e["action"] for e in events
              if e["event"] == "capacity-drain"]
    assert drains.count("lease") == 2
    assert drains.count("upsize-release") == 1
    grants = [e for e in events if e["event"] == "capacity-lease"]
    assert [e["state"] for e in grants] == ["granted"]
    reclaims = [e for e in events if e["event"] == "capacity-reclaim"]
    assert len(reclaims) == 1 and reclaims[0]["reason"] == "idle"

    # no orphaned lease survives the round trip
    from scaling_tpu.resilience.capacity import CapacityChannel

    assert CapacityChannel(workdir / "control" / "capacity") \
        .read_leases() == {}

    from scaling_tpu.obs.cli import main as obs_main
    from scaling_tpu.obs.report import load_run_dir, render_report

    telemetry = tmp / "arb_telemetry"
    data = load_run_dir(telemetry)
    assert data.bad_lines == 0, f"unparseable telemetry: {data.bad_lines}"
    report = render_report(data, telemetry)
    assert "2->1" in report and "1->2" in report
    assert obs_main(
        ["report", str(telemetry), "--assert-max-resizes", "2"]
    ) == 0
    assert obs_main(
        ["report", str(telemetry), "--assert-max-resizes", "1"]
    ) == 1


def test_flapping_host_never_churns_the_pod(baseline):
    """Flap drill (ISSUE 19 tentpole): a host that oscillates faster
    than the hysteresis window — every announcement carries a BUMPED
    incarnation, i.e. the unit restarted between observations — must
    produce ZERO resizes. The streak resets on every incarnation
    change, so the announcement can never mature no matter how long it
    flaps. The run completes undisturbed at full size, loss-exact, and
    the zero-churn gate ``--assert-max-resizes 0`` passes."""
    tmp, gold = baseline

    def flapper(workdir, proc):
        from scaling_tpu.resilience.capacity import CapacityChannel

        ch = CapacityChannel(workdir / "control" / "capacity")
        incarnation = 0
        while proc.poll() is None:
            incarnation += 1
            ch.announce("flappy", "localhost", 1, incarnation=incarnation)
            time.sleep(0.05)

    p, workdir = run_supervised(
        tmp, "flap", upsize_after=3, actor=flapper,
    )
    assert p.returncode == 0, p.stdout[-3000:] + p.stderr[-3000:]
    for host in (0, 1):
        result = read_result(workdir, host)
        assert result["iterations"] == 8
        losses = read_losses(workdir, host)
        np.testing.assert_array_equal(
            np.asarray([losses[s] for s in range(1, 9)]),
            np.asarray([gold[s] for s in range(1, 9)]),
        )
    events = read_events(tmp, "flap")
    assert not [e for e in events if e["event"] in
                ("downsize", "upsize", "capacity-drain")]
    assert any(e["event"] == "epoch-clean-exit" for e in events)

    from scaling_tpu.obs.cli import main as obs_main

    assert obs_main([
        "report", str(tmp / "flap_telemetry"),
        "--assert-max-resizes", "0",
    ]) == 0
