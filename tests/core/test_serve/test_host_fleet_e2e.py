"""Multi-host serving-fleet chaos e2e (ISSUE 18 acceptance, tier-1).

A fake 2-host fleet on one box: the hostsfile names ``localhost`` and
``127.0.0.1`` — two distinct resource-pool entries, both spawned as
real local subprocesses with distinct ``SCALING_TPU_HOST_ID``s, so the
whole host-mode path (placement plan, rendezvous file, per-host fault
selectors, cross-host failover) runs without ssh.

- ``serve bench --replicas-proc 2 --hostsfile`` places one replica per
  fake host; workers publish ``host:port`` into ``rendezvous.jsonl``
  and the router dials what they published;
- SIGKILL every replica on fake host 1 mid-tick
  (``serve.replica.kill=kill@3@host=1``): the survivor on host 0 picks
  up the dead host's in-flight requests via journal replay and the run
  completes with tokens IDENTICAL to a fault-free run;
- a forced RPC partition against host 1 (pre-dispatch connection drops
  plus admitted-but-reply-lost drops) produces client retries and
  in-doubt parks but ZERO duplicate admissions — every req_id has
  exactly one journal submit record across the whole fleet — and zero
  lost requests (token-exact vs the same clean run);
- ``obs report`` attributes the fleet timeline per host and the
  ``--assert-max-replica-restarts`` gate fails loudly when a planned
  host never rendezvoused;
- SIGTERM mid-bench drains the whole multi-host fleet to exit 0.

Policy units (placement feasibility, in-doubt park/resolve, rendezvous
records, clock-skew liveness) live in test_replica_proc_units.py and
test_tune/test_serving.py; this module owns the subprocess truth.
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

HOSTSFILE = "localhost slots=2\n127.0.0.1 slots=2\n"

# the proc-fleet chaos shape (test_proc_fleet_e2e.py) — same seed, same
# 8 requests, so token-exactness here proves the HOST layer added no
# nondeterminism on top of the already-pinned fleet behavior
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
           "SCALING_TPU_TEST_CACHE": "off"}
    for k in ("SCALING_TPU_EVENTS_PATH", "SCALING_TPU_FAULTS",
              "SCALING_TPU_HOST_ID", "XLA_FLAGS"):
        env.pop(k, None)
    env.update(extra)
    return env


def run_bench(run_dir, *extra, env=None, timeout=120):
    run_dir.mkdir(parents=True, exist_ok=True)
    hosts = run_dir / "hosts.txt"
    hosts.write_text(HOSTSFILE)
    cmd = [sys.executable, "-m", "scaling_tpu.serve", "bench", *SHAPE,
           "--replicas-proc", "2", "--hostsfile", str(hosts),
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


def journal_submit_counts(run_dir):
    """req_id -> number of journal SUBMIT records across every replica
    journal in the run dir — the duplicate-admission detector."""
    counts = {}
    for j in sorted(Path(run_dir).glob("journal*.jsonl")):
        for line in j.read_text().splitlines():
            try:
                rec = json.loads(line)
            except ValueError:
                continue  # torn tail
            if rec.get("kind") == "serve-submit":
                counts[rec["req"]] = counts.get(rec["req"], 0) + 1
    return counts


# two fake hosts, host 1 under fire: every replica on it SIGKILLed at
# its 3rd armed tick (whole-host death), vs a burst partition that
# first refuses host 1's RPCs pre-dispatch (connection dies after
# send -> in-doubt) and later drops replies AFTER dispatch (admitted
# with the reply lost -> worker-side dedup on the re-offer)
KILL_FAULTS = "serve.replica.kill=kill@3@host=1"
PARTITION_FAULTS = ("serve.replica.net_partition=partition@1x6@host=1,"
                    "serve.replica.rpc=drop@8x4@host=1")


@pytest.fixture(scope="module")
def host_runs(tmp_path_factory):
    """One clean baseline + two chaos arms over the SAME seeded
    workload on the fake 2-host fleet."""
    tmp = tmp_path_factory.mktemp("host_fleet")
    runs = {}
    for name, faults in (("clean", None), ("hostkill", KILL_FAULTS),
                         ("partition", PARTITION_FAULTS)):
        env = _env(SCALING_TPU_FAULTS=faults) if faults else _env()
        p = run_bench(tmp / name, env=env)
        assert p.returncode == 0, (
            f"{name}: " + p.stdout[-2000:] + p.stderr[-2000:])
        runs[name] = stats_of(tmp / name)
    return tmp, runs


def test_clean_host_run_places_and_rendezvouses_both_hosts(host_runs):
    tmp, runs = host_runs
    clean = runs["clean"]
    assert clean["fleet_hosts"] == [0, 1]
    assert clean["hosts_reported"] == [0, 1]
    # one replica per host, per the placement plan's least-loaded spread
    assert sorted(r["host"] for r in clean["replica_stats"]) == [0, 1]
    # the workers really published routable addresses (not loopback
    # assumptions): the router served the whole run through them
    rendezvous = {
        json.loads(line)["replica"]: json.loads(line)
        for line in (tmp / "clean" / "rendezvous.jsonl").read_text()
        .splitlines() if line.strip()
    }
    assert sorted(rendezvous) == [0, 1]
    assert all(":" in rec["addr"] for rec in rendezvous.values())
    assert clean["replica_restarts"] == 0
    assert clean["requests"] == 8 and clean["requests_timeout"] == 0


def test_host_death_failover_is_token_exact_across_hosts(host_runs):
    tmp, runs = host_runs
    clean, chaos = runs["clean"], runs["hostkill"]
    # host 1's replica really died and was supervised back
    assert chaos["replica_restarts"] >= 1
    assert chaos["redispatched_requests"] + chaos["recovered_requests"] >= 1
    assert chaos["replicas_gave_up"] == 0
    # every request completed, and the tokens are IDENTICAL: journal
    # replay carried host 1's in-flight requests to the survivor on
    # host 0 with their original req_ids, so the (request, position)
    # sampler keys regenerate the same streams machine-to-machine
    assert clean["requests"] == chaos["requests"] == 8
    assert chaos["requests_timeout"] == 0
    assert clean["outputs"] == chaos["outputs"]
    # the relaunch stayed on its recorded host (placement pin)
    assert chaos["hosts_reported"] == [0, 1]


def test_partition_retries_but_never_duplicates_or_loses(host_runs):
    tmp, runs = host_runs
    clean, part = runs["clean"], runs["partition"]
    # the partition was real: clients retried across it
    assert part["rpc_retries"] >= 1
    # ...but no request was lost (token-exact) and none double-admitted
    assert part["requests"] == 8 and part["requests_timeout"] == 0
    assert clean["outputs"] == part["outputs"]
    counts = journal_submit_counts(tmp / "partition")
    dup = {req: n for req, n in counts.items() if n != 1}
    assert dup == {}, f"duplicate journal admissions: {dup}"
    assert len(counts) >= 8  # every bench request was admitted once
    # nothing left parked: every in-doubt submit resolved exactly once
    assert part["router"]["in_doubt_pending"] == 0


def test_obs_report_attributes_fleet_per_host(host_runs):
    tmp, runs = host_runs
    ceiling = runs["hostkill"]["replica_restarts"]
    p = obs_report(tmp / "hostkill", "--assert-max-replica-restarts",
                   str(ceiling))
    assert p.returncode == 0, p.stdout[-2000:] + p.stderr[-2000:]
    assert "fleet timeline by host:" in p.stdout
    assert "hosts: planned=[0, 1] reported=[0, 1]" in p.stdout
    assert "host=1" in p.stdout  # per-replica host marks


def test_restart_gate_fails_when_a_planned_host_never_reported(tmp_path):
    """A host in the placement plan with no rendezvous record is silent
    capacity loss — the fleet 'ran green' at half strength. The gate
    must say so, not pass on a clean restart count."""
    events = [
        {"event": "serve-replica-ready", "replica": 0, "host": 0,
         "ts": 1.0},
        {"event": "serve-summary", "ts": 2.0, "requests": 1,
         "fleet_hosts": [0, 1], "hosts_reported": [0],
         "submit_dups": 0, "rpc_retries": 0},
    ]
    (tmp_path / "events.jsonl").write_text(
        "".join(json.dumps(e) + "\n" for e in events))
    p = obs_report(tmp_path, "--assert-max-replica-restarts", "3")
    assert p.returncode == 1
    assert "never rendezvoused" in p.stdout
    assert "MISSING=[1]" in p.stdout


def test_sigterm_drains_the_whole_host_fleet(tmp_path):
    """SIGTERM to the bench → the drain flag is raised on the control
    plane, the drain fans out over the network RPCs, and every worker
    on every fake host finishes in-flight work; exit 0 with a summary."""
    run_dir = tmp_path / "drain"
    run_dir.mkdir()
    hosts = run_dir / "hosts.txt"
    hosts.write_text(HOSTSFILE)
    cmd = [sys.executable, "-m", "scaling_tpu.serve", "bench", *SHAPE,
           "--replicas-proc", "2", "--hostsfile", str(hosts),
           "--requests", "500", "--rate", "2",
           "--run-dir", str(run_dir), "--json", str(run_dir / "stats.json")]
    proc = subprocess.Popen(cmd, cwd=REPO, env=_env(),
                            stdout=subprocess.PIPE, stderr=subprocess.PIPE,
                            text=True)
    try:
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
            pytest.fail("host fleet never became ready")
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
    assert stats["unsubmitted"] > 0
    assert stats["replicas_gave_up"] == 0
    assert stats["hosts_reported"] == [0, 1]


def test_hostsfile_without_proc_replicas_is_a_loud_arg_error(tmp_path):
    hosts = tmp_path / "hosts.txt"
    hosts.write_text(HOSTSFILE)
    p = subprocess.run(
        [sys.executable, "-m", "scaling_tpu.serve", "bench", *SHAPE,
         "--hostsfile", str(hosts), "--run-dir", str(tmp_path / "r")],
        cwd=REPO, env=_env(), capture_output=True, text=True, timeout=120,
    )
    assert p.returncode == 2
    assert "--replicas-proc" in p.stderr
