"""Fleet bench e2e (ISSUE 14 acceptance, tier-1).

- the tuner's serving mode emits a runnable config whose top pick (2
  devices -> 2 data-parallel replicas) runs straight through
  ``serve bench --config``;
- the SAME Poisson workload is served whole at 1 and at 2 replicas, both
  replicas taking their share of it, with the SAME ``--assert-ttft`` gate
  passing both runs (how much faster two replicas are is a chip's
  question: PERF.md section 7, cell ``serve-router-4replica``);
- ``obs report`` renders the fleet rows + router stats and the
  ``--assert-max-replica-skew`` gate passes on balanced dispatch, fails
  loudly on a run dir with no replica telemetry;
- SIGTERM mid-bench drains the WHOLE fleet to exit 0 with per-replica
  journal namespaces on disk.
"""

import json
import os
import signal
import subprocess
import sys
import time
from pathlib import Path
from types import SimpleNamespace

import pytest

REPO = Path(__file__).resolve().parents[3]

# the toy fleet shape
MODEL_ARGS = ["--hidden", "128", "--layers", "2", "--vocab", "64",
              "--heads", "4"]
WORK_ARGS = [
    "--requests", "48", "--rate", "100000", "--seed", "3", "--warmup", "1",
    "--prompt-len", "4", "10", "--output-len", "12", "16",
    "--max-blocks-per-seq", "8", "--prefill-chunk", "4",
]


def _env():
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "SCALING_TPU_TEST_CACHE": "off"}
    env.pop("SCALING_TPU_EVENTS_PATH", None)
    env.pop("XLA_FLAGS", None)  # the bench sets its own device count
    return env


def run_bench_cli(run_dir, *extra, timeout=120):
    cmd = [sys.executable, "-m", "scaling_tpu.serve", "bench",
           *WORK_ARGS, *MODEL_ARGS,
           "--run-dir", str(run_dir), "--json", str(run_dir / "stats.json"),
           *extra]
    return subprocess.run(cmd, cwd=REPO, env=_env(), capture_output=True,
                          text=True, timeout=timeout)


@pytest.fixture(scope="module")
def fleet_pair(tmp_path_factory):
    """tune --serve emits the 2-chip top pick; the SAME workload runs at
    1 replica (explicit flags) and through the emitted config."""
    tmp = tmp_path_factory.mktemp("fleet_e2e")
    cfg = tmp / "serving_config.json"
    report = tmp / "tune.json"
    p = subprocess.run(
        [sys.executable, "-m", "scaling_tpu.tune", "--serve",
         "--devices", "2", "--model", "128,2,4,4,256,64,2.0",
         "--serve-block-sizes", "4", "--serve-token-budgets", "48",
         "--serve-num-slots", "12",
         "--emit-config", str(cfg), "--json", str(report)],
        cwd=REPO, env=_env(), capture_output=True, text=True, timeout=120,
    )
    assert p.returncode == 0, p.stdout[-2000:] + p.stderr[-2000:]
    emitted = json.loads(cfg.read_text())

    r1_dir, r2_dir = tmp / "r1", tmp / "r2"
    r1_dir.mkdir()
    p1 = run_bench_cli(
        r1_dir, "--replicas", "1",
        "--num-slots", str(emitted["num_slots"]),
        "--block-size", str(emitted["block_size"]),
        "--token-budget", str(emitted["token_budget"]),
        "--num-blocks", str(emitted["num_blocks"]),
        "--assert-ttft", "120",
    )
    assert p1.returncode == 0, p1.stdout[-3000:] + p1.stderr[-3000:]
    r2_dir.mkdir()
    p2 = run_bench_cli(
        r2_dir, "--config", str(cfg), "--assert-ttft", "120",
    )
    assert p2.returncode == 0, p2.stdout[-3000:] + p2.stderr[-3000:]
    return {
        "emitted": emitted,
        "report": json.loads(report.read_text()),
        "r1_dir": r1_dir, "r2_dir": r2_dir,
        "r1": json.loads((r1_dir / "stats.json").read_text()),
        "r2": json.loads((r2_dir / "stats.json").read_text()),
        "stdout1": p1.stdout, "stdout2": p2.stdout,
    }


def test_tuner_top_pick_is_runnable_replicated_config(fleet_pair):
    """The acceptance wiring: the serving tuner's top pick for 2 chips
    is a 2-replica config (replication beats mp for a model that fits
    one chip), and `serve bench --config` ran it verbatim."""
    emitted = fleet_pair["emitted"]
    assert emitted["replicas"] == 2 and emitted["mp"] == 1
    ranked = fleet_pair["report"]["ranked"]
    assert ranked[0]["label"].startswith("mp1·r2")
    # the mp=2 point was enumerated and scored too (the sharded arm is
    # in the search space, just not the winner at this size)
    assert any(r["mp"] == 2 for r in ranked)
    eng = fleet_pair["r2"]["engine"]
    assert eng["replicas"] == 2
    assert eng["block_size"] == emitted["block_size"]
    assert eng["token_budget"] == emitted["token_budget"]


def test_two_replicas_share_the_stream_one_replica_served_alone(fleet_pair):
    """What a CPU mesh can show exactly of scale-out: the same 48
    requests complete at 1 and at 2 replicas with the same tokens out,
    the router spread them over both replicas within the skew gate's
    ceiling, and the same --assert-ttft gate passed both runs."""
    r1, r2 = fleet_pair["r1"], fleet_pair["r2"]
    assert r1["requests"] == 48 and r2["requests"] == 48
    assert r1["output_tokens"] == r2["output_tokens"] > 0
    reps = {row["replica"]: row["requests"] for row in r2["replica_stats"]}
    assert set(reps) == {0, 1} and sum(reps.values()) == 48
    assert min(reps.values()) > 0
    assert max(reps.values()) / min(reps.values()) <= 3  # the skew gate's
    for out in (fleet_pair["stdout1"], fleet_pair["stdout2"]):
        assert "PASS" in out and "FAIL" not in out


def test_obs_report_fleet_rows_and_skew_gate(fleet_pair, capsys):
    from scaling_tpu.obs.cli import main

    rc = main(["report", str(fleet_pair["r2_dir"]),
               "--assert-max-replica-skew", "3"])
    out = capsys.readouterr().out
    assert rc == 0, out
    assert "fleet: replicas=2" in out
    assert "replica 0:" in out and "replica 1:" in out
    assert "affinity_hits=" in out and "retries_elsewhere=" in out
    assert "PASS" in out


def test_skew_gate_fails_on_missing_replica_telemetry(fleet_pair, capsys):
    """Missing data FAILS a requested gate: the single-replica run dir
    carries no replica_stats, so the skew gate must fire."""
    from scaling_tpu.obs.cli import main

    rc = main(["report", str(fleet_pair["r1_dir"]),
               "--assert-max-replica-skew", "10"])
    out = capsys.readouterr().out
    assert rc == 1
    assert "FAIL assert-max-replica-skew: no fleet telemetry" in out


def test_sigterm_drains_whole_fleet_to_exit_zero(tmp_path):
    """The fleet drain acceptance: SIGTERM mid-bench -> every replica
    stops admitting, in-flight work finishes, the bench exits 0 with a
    parseable run dir and per-replica journal namespaces on disk."""
    run_dir = tmp_path / "drain"
    run_dir.mkdir()
    cmd = [sys.executable, "-m", "scaling_tpu.serve", "bench",
           "--requests", "30", "--rate", "1", "--seed", "3",
           "--prompt-len", "4", "8", "--output-len", "3", "5",
           "--num-slots", "4", "--block-size", "4", "--num-blocks", "64",
           "--max-blocks-per-seq", "8", "--token-budget", "64",
           "--prefill-chunk", "4", "--replicas", "2",
           "--hidden", "32", "--layers", "2", "--vocab", "64",
           "--heads", "4",
           "--run-dir", str(run_dir), "--json", str(run_dir / "stats.json")]
    p = subprocess.Popen(cmd, cwd=REPO, env=_env(), stdout=subprocess.PIPE,
                         stderr=subprocess.PIPE, text=True)
    try:
        deadline = time.monotonic() + 360
        events = run_dir / "events.jsonl"
        while time.monotonic() < deadline:
            if events.is_file() and "serve-request" in events.read_text():
                break
            time.sleep(0.2)
        else:
            pytest.fail("fleet bench never served a request")
        p.send_signal(signal.SIGTERM)
        assert p.wait(timeout=120) == 0, p.stderr.read()[-3000:]
    finally:
        if p.poll() is None:
            p.kill()
            p.wait(timeout=30)
    stats = json.loads((run_dir / "stats.json").read_text())
    assert stats["drained"] is True
    assert stats["unsubmitted"] > 0  # it really was mid-workload
    assert stats["replicas"] == 2
    # per-replica journal namespaces, no shared stream
    assert (run_dir / "journal_r0.jsonl").is_file()
    assert (run_dir / "journal_r1.jsonl").is_file()
    evs = [json.loads(l)
           for l in (run_dir / "events.jsonl").read_text().splitlines()]
    assert any(e["event"] == "serve-drain" for e in evs)
    assert any(e["event"] == "serve-summary" for e in evs)


@pytest.mark.parametrize("ends", ["returns", "raises"])
def test_bench_main_puts_sigterms_handler_back_as_it_found_it(monkeypatch, ends):
    """``main`` in process: the drain handler its engine chains onto SIGTERM
    is gone when it returns or raises, and the handler that was there is
    there again."""
    from scaling_tpu.serve import bench
    from scaling_tpu.serve.engine import install_drain_handler

    drained = []

    def mine(signum, frame):
        drained.append("mine")

    def arms(argv):
        for arm in ("arm 0", "arm 3"):
            install_drain_handler(SimpleNamespace(
                begin_drain=lambda arm=arm: drained.append(arm)))
        assert signal.getsignal(signal.SIGTERM) is not mine
        if ends == "raises":
            raise RuntimeError("scheduler livelock?")
        return 0

    monkeypatch.setattr(bench, "_main", arms)
    found = signal.signal(signal.SIGTERM, mine)
    try:
        if ends == "raises":
            with pytest.raises(RuntimeError, match="livelock"):
                bench.main([])
        else:
            assert bench.main([]) == 0
        assert signal.getsignal(signal.SIGTERM) is mine
        signal.raise_signal(signal.SIGTERM)
        # the engines of a bench long finished are not drained
        assert drained == ["mine"]
    finally:
        signal.signal(signal.SIGTERM, found)
