"""Multi-host supervision e2e (ISSUE 4 acceptance), a host LOST: a fake 2-host
pod under the heartbeat supervisor survives a SIGKILLed host (teardown,
relaunch with a new coordinator epoch, loss-exact resume from the
newest valid checkpoint, no manual cleanup), never advances ``latest``
past a save interrupted between shard commit and the cross-host commit
barrier, and relaunches a host whose heartbeat went stale. Coordinated
preemption and elastic capacity: ``test_multihost_elastic.py``; the supervised
run and its hygiene: ``multihost_tools.py``; the golden run: ``conftest.py``.
"""

import numpy as np
import pytest

from scaling_tpu.resilience import verify_checkpoint

from .multihost_tools import (
    WRITES_PER_SAVE, read_events, read_losses, read_result, run_supervised,
)


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
        # the driver's 240s default equals this scenario's budget, and
        # the grace suppresses ALL staleness verdicts — detection could
        # never fire in time. The fake hosts cold-compile in ~12s, so
        # 60s still shields startup while leaving three epochs' worth
        # of detect+relaunch inside the scenario budget
        startup_grace=60.0, timeout=240,
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
