"""Process-fleet chaos e2e (ISSUE 16 acceptance, tier-1).

- ``serve bench --replicas-proc 2`` runs each replica as a SUBPROCESS
  (own interpreter, own engine, line-JSON RPC) behind the same router
  policy as the in-process fleet;
- SIGKILL one replica mid-tick (``serve.replica.kill`` fault point):
  the supervisor detects the death, re-dispatches its in-flight
  requests to the survivor via journal replay, relaunches the worker on
  the shared backoff curve — and the bench completes with tokens
  IDENTICAL to a fault-free run (the (request, position) sampler keys
  survive the crash);
- ``obs report`` renders the fleet timeline and the
  ``--assert-max-replica-restarts`` gate passes on the chaos run,
  fails loudly both over the ceiling and on a run dir with no fleet
  supervision telemetry;
- SIGTERM mid-bench drains the WHOLE fleet of subprocesses to exit 0;
- ``--autoscale`` grows the fleet under sustained pressure and drains
  it back at idle (slow-marked: the policy itself is unit-tested in
  test_replica_proc_units.py).
"""

import json
import os
import signal
import subprocess
import sys
import time
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[3]

# the verified chaos shape: small toy model (worker cold-start is two
# subprocess jit warmups), seed 7, 8 requests — replica 1's 3rd armed
# tick lands mid-run with requests still in flight on it
SHAPE = [
    "--requests", "8", "--rate", "50", "--seed", "7", "--warmup", "1",
    "--num-slots", "2", "--block-size", "4", "--num-blocks", "64",
    "--max-blocks-per-seq", "8", "--token-budget", "64",
    "--prefill-chunk", "4",
    "--hidden", "32", "--layers", "2", "--vocab", "64", "--heads", "4",
    "--prompt-len", "3", "8", "--output-len", "4", "8",
]


def _env(**extra):
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "SCALING_TPU_TEST_CACHE": "off", **extra}
    for k in ("SCALING_TPU_EVENTS_PATH", "SCALING_TPU_FAULTS",
              "SCALING_TPU_HOST_ID", "XLA_FLAGS"):
        env.pop(k, None)
    env.update(extra)
    return env


def run_bench(run_dir, *extra, env=None, timeout=120):
    run_dir.mkdir(parents=True, exist_ok=True)
    cmd = [sys.executable, "-m", "scaling_tpu.serve", "bench", *SHAPE,
           "--run-dir", str(run_dir), "--json", str(run_dir / "stats.json"),
           *extra]
    return subprocess.run(cmd, cwd=REPO, env=env or _env(),
                          capture_output=True, text=True, timeout=timeout)


def obs_report(run_dir, *extra):
    return subprocess.run(
        [sys.executable, "-m", "scaling_tpu.obs", "report", str(run_dir),
         *extra],
        cwd=REPO, env=_env(), capture_output=True, text=True, timeout=120,
    )


def stats_of(run_dir):
    return json.loads((run_dir / "stats.json").read_text())


@pytest.fixture(scope="module")
def chaos_pair(tmp_path_factory):
    """The acceptance pair: the SAME seeded workload on a 2-subprocess
    fleet, fault-free vs one replica SIGKILLed mid-tick."""
    tmp = tmp_path_factory.mktemp("proc_fleet")
    a = run_bench(tmp / "clean", "--replicas-proc", "2")
    assert a.returncode == 0, a.stdout[-2000:] + a.stderr[-2000:]
    b = run_bench(
        tmp / "chaos", "--replicas-proc", "2",
        env=_env(SCALING_TPU_FAULTS="serve.replica.kill=kill@3@host=1"),
    )
    assert b.returncode == 0, b.stdout[-2000:] + b.stderr[-2000:]
    return tmp, stats_of(tmp / "clean"), stats_of(tmp / "chaos"), b.stdout


def test_sigkill_failover_is_token_exact(chaos_pair):
    tmp, clean, chaos, _ = chaos_pair
    # the fault fired: a real subprocess died and was supervised back
    assert chaos["replica_restarts"] >= 1
    # the dead replica had work: journal-harvested outputs and/or
    # re-dispatched in-flight requests
    assert chaos["redispatched_requests"] + chaos["recovered_requests"] >= 1
    assert chaos["replicas_gave_up"] == 0
    assert clean["replica_restarts"] == 0
    # every request completed in both runs...
    assert clean["requests"] == chaos["requests"] == 8
    assert clean["requests_timeout"] == chaos["requests_timeout"] == 0
    # ...and the chaos run's tokens are IDENTICAL: journal replay kept
    # the original req_ids, so the (request, position) sampler keys
    # regenerate the same stream on whichever replica picks them up
    assert clean["outputs"] == chaos["outputs"]


def test_supervision_surfaces_in_summary_and_stdout(chaos_pair):
    _, _, chaos, stdout = chaos_pair
    assert chaos["proc_fleet"] is True
    assert chaos["replicas"] == 2
    assert "supervision:" in stdout
    assert f"restarts={chaos['replica_restarts']}" in stdout


def test_obs_fleet_timeline_and_restart_gate(chaos_pair):
    tmp, _, chaos, _ = chaos_pair
    ceiling = chaos["replica_restarts"]
    p = obs_report(tmp / "chaos", "--assert-max-replica-restarts",
                   str(ceiling))
    assert p.returncode == 0, p.stdout[-2000:] + p.stderr[-2000:]
    assert "fleet timeline:" in p.stdout
    for what in ("dead", "restart", "failover", "restored"):
        assert what in p.stdout
    # over the ceiling: crash-looping fleets fail the gate
    p = obs_report(tmp / "chaos", "--assert-max-replica-restarts", "0")
    assert p.returncode == 1
    assert "crash-looping" in p.stdout


def test_restart_gate_demands_fleet_telemetry(tmp_path):
    """A run dir with NO serve-replica-* lifecycle events fails the
    gate outright — silently green on missing telemetry is how fleet
    regressions hide."""
    (tmp_path / "events.jsonl").write_text(json.dumps(
        {"event": "serve-summary", "ts": 1.0, "requests": 1}) + "\n")
    p = obs_report(tmp_path, "--assert-max-replica-restarts", "3")
    assert p.returncode == 1
    assert "no fleet supervision telemetry" in p.stdout


def test_sigterm_drains_the_whole_fleet(tmp_path):
    """SIGTERM to the bench → every subprocess replica drains (finish
    in-flight, refuse new) and the bench exits 0 with a summary."""
    run_dir = tmp_path / "drain"
    run_dir.mkdir()
    cmd = [sys.executable, "-m", "scaling_tpu.serve", "bench", *SHAPE,
           "--replicas-proc", "2", "--requests", "500", "--rate", "2",
           "--run-dir", str(run_dir), "--json", str(run_dir / "stats.json")]
    proc = subprocess.Popen(cmd, cwd=REPO, env=_env(),
                            stdout=subprocess.PIPE, stderr=subprocess.PIPE,
                            text=True)
    proc.args = cmd
    try:
        # wait for both replicas' ready events (cold jit in the workers)
        events = run_dir / "events.jsonl"
        deadline = time.monotonic() + 300
        while time.monotonic() < deadline:
            if events.is_file() and events.read_text().count(
                    "serve-replica-ready") >= 2:
                break
            if proc.poll() is not None:
                break
            time.sleep(0.1)
        else:
            pytest.fail("fleet never became ready")
        assert proc.poll() is None, proc.communicate()[1][-2000:]
        proc.send_signal(signal.SIGTERM)
        out, err = proc.communicate(timeout=120)
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.communicate()
    assert proc.returncode == 0, out[-2000:] + err[-2000:]
    stats = stats_of(run_dir)
    assert stats["drained"] is True
    assert stats["unsubmitted"] > 0  # it really stopped early
    assert stats["replicas_gave_up"] == 0


@pytest.mark.slow
def test_autoscale_grows_and_shrinks_the_fleet(tmp_path):
    """Sustained high-watermark pressure spawns replica 1; the idle
    tail drains it back to min_replicas. (The policy's hysteresis /
    budget / floor branches are unit-tested; this drives the full
    subprocess spawn + drain machinery once.)"""
    p = run_bench(
        tmp_path / "autos", "--replicas-proc", "1", "--autoscale",
        "--min-replicas", "1", "--max-replicas", "2",
        "--autoscale-sustain-s", "0.3", "--autoscale-idle-s", "0.5",
        "--requests", "150", "--rate", "500", "--output-len", "8", "16",
    )
    assert p.returncode == 0, p.stdout[-2000:] + p.stderr[-2000:]
    stats = stats_of(tmp_path / "autos")
    assert stats["replica_spawns"] == 1
    assert stats["replica_drains"] == 1
    assert stats["requests"] == 150


# ---------------------------------------------- distributed tracing (ISSUE 20)
def obs_trace(run_dir, *extra):
    return subprocess.run(
        [sys.executable, "-m", "scaling_tpu.obs", "trace", str(run_dir),
         *extra],
        cwd=REPO, env=_env(), capture_output=True, text=True, timeout=120,
    )


def test_obs_trace_reconstructs_cross_host_failover_trace(chaos_pair):
    """ISSUE 20 acceptance: the killed replica's in-flight request
    reconstructs as ONE trace spanning both hosts — the dead replica's
    spans and the survivor's re-dispatch spans share the trace id the
    journal carried across the crash — with finite, ordered timestamps
    after clock alignment."""
    tmp, _, chaos, _ = chaos_pair
    p = obs_trace(tmp / "chaos", "--slowest", "8", "--json",
                  str(tmp / "chaos" / "trace.json"))
    assert p.returncode == 0, p.stdout[-2000:] + p.stderr[-2000:]
    payload = json.loads((tmp / "chaos" / "trace.json").read_text())
    assert payload["schema_version"] == 1
    assert payload["traces"] == 8  # warmup stayed off the books
    cross = {tid: t for tid, t in payload["per_trace"].items()
             if set(t["hosts"]) >= {0, 1}}
    assert cross, payload["per_trace"]  # at least one failover trace
    for t in cross.values():
        assert t["status"] == "completed"
        for phase, v in t["phases"].items():
            assert v >= 0.0 and v == v  # finite, non-negative
        assert t["phases"]["e2e"] > 0.0
    # the reassembled records really are ordered on the aligned clock
    from scaling_tpu.obs.report import load_run_dir
    from scaling_tpu.obs.trace import assemble_traces

    traces = assemble_traces(load_run_dir(tmp / "chaos"))
    for tid in cross:
        starts = [r["_start"] for r in traces[tid]]
        assert starts == sorted(starts)
        assert all(s == s and abs(s) != float("inf") for s in starts)
    # the renderer names the cross-host trace's hosts
    assert "hosts=[0,1]" in p.stdout or "hosts=[1,0]" in p.stdout


def test_obs_trace_coverage_gate_passes_healthy_fails_withheld(
        chaos_pair, tmp_path):
    """--assert-trace-coverage 0.95 passes over the real (healthy AND
    chaos) run dirs; a run dir with its span records withheld — the
    serve-request events survive, their work spans do not — FAILS with
    exit 1. Missing data never passes by silence."""
    tmp, _, _, _ = chaos_pair
    for arm in ("clean", "chaos"):
        p = obs_trace(tmp / arm, "--assert-trace-coverage", "0.95")
        assert p.returncode == 0, arm + p.stdout[-2000:]
        assert "PASS" in p.stdout
    # withhold the span records (a producer that stopped stamping):
    # serve-request completions survive, the work spans backing them
    # do not — coverage collapses to 0
    broken = tmp_path / "withheld"
    broken.mkdir()
    kept = []
    for src in sorted((tmp / "clean").rglob("*.jsonl")):
        for line in src.read_text().splitlines():
            if line.strip() and '"span"' not in line:
                kept.append(line)
    assert any('"serve-request"' in line for line in kept)
    (broken / "events.jsonl").write_text("\n".join(kept) + "\n")
    p = obs_trace(broken, "--assert-trace-coverage", "0.95")
    assert p.returncode == 1, p.stdout[-2000:]
    assert "FAIL assert-trace-coverage" in p.stdout


def test_obs_trace_coverage_gate_demands_completions(tmp_path):
    """No completed serve-request events at all -> the coverage gate
    fails outright (exit 1), mirroring every other gate's
    missing-data-fails contract."""
    (tmp_path / "events.jsonl").write_text(json.dumps(
        {"event": "serve-shed", "ts": 1.0, "reason": "pressure"}) + "\n")
    p = obs_trace(tmp_path, "--assert-trace-coverage", "0.5")
    assert p.returncode == 1
    assert "no completed serve-request" in p.stdout


def test_obs_trace_critical_path_gate(chaos_pair):
    """Sane per-phase ceilings pass; absurd ones fail with the
    offending trace named."""
    tmp, _, _, _ = chaos_pair
    p = obs_trace(tmp / "chaos",
                  "--assert-critical-path", "decode:300",
                  "--assert-critical-path", "failover:300",
                  "--assert-critical-path", "queue_wait:300")
    assert p.returncode == 0, p.stdout[-2000:]
    assert "PASS" in p.stdout
    p = obs_trace(tmp / "chaos", "--assert-critical-path", "decode:1e-6")
    assert p.returncode == 1
    assert "FAIL assert-critical-path: decode" in p.stdout
    # malformed / unknown phase specs fail loudly, not silently
    p = obs_trace(tmp / "chaos", "--assert-critical-path", "warp:1.0")
    assert p.returncode == 1
    assert "unknown phase" in p.stdout


def test_obs_report_one_line_trace_summary(chaos_pair):
    """The report grows ONE trace line over traced run dirs (coverage +
    top critical-path phase) and stays silent over untraced ones."""
    tmp, _, _, _ = chaos_pair
    p = obs_report(tmp / "chaos")
    assert p.returncode == 0
    (line,) = [l for l in p.stdout.splitlines()
               if l.strip().startswith("traces:")]
    assert "coverage" in line and "top critical-path phase" in line
