"""Multi-host supervision e2e, the pod DRAINED and RESIZED: coordinated
preemption — SIGTERM on ONE host, or on the supervisor, makes every host save
at the same step boundary and exit resume-ready — and elastic capacity: a pod
that loses a host for good continues loss-exact at the smaller world, sizes
back up when the host is restored, lends a host to a serving burst and takes
it back, and is not churned by a host that flaps. A host lost:
``test_multihost.py``; the supervised run and its hygiene:
``multihost_tools.py``; the golden run: ``conftest.py``.
"""

import json
import os
import signal
import subprocess
import sys
import time
from pathlib import Path

import numpy as np
import pytest

from scaling_tpu.resilience import verify_checkpoint

from .multihost_tools import (
    DRIVER, REPO, SCENARIO_TIMEOUT, free_port, read_events, read_losses,
    read_result, run_supervised,
)


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
