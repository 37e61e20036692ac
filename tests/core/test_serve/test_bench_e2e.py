"""Serving smoke e2e (ISSUE 9, hot path rebuilt in ISSUE 10): a
subprocess run of the real benchmark entrypoint serving ~8 concurrent
toy requests on the CPU mesh — through the Pallas paged-decode kernel
(interpreted) with prompts streaming in chunks, so the tier-1 smoke
exercises the production hot path — then the real ``obs report``
analyzer over its run dir: the serving section parses (including the
tick-time attribution), the gates pass at sane thresholds and fail at
absurd ones."""

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[3]

BENCH_ARGS = [
    "--requests", "8", "--rate", "50", "--seed", "3",
    "--prompt-len", "4", "12", "--output-len", "3", "6",
    "--num-slots", "4", "--block-size", "4", "--num-blocks", "64",
    "--max-blocks-per-seq", "8", "--token-budget", "64",
    # 4-token prefill chunks (prompts of 4-12 tokens span 1-3 chunks,
    # so several prompts are mid-prefill at once — asserted below)
    "--prefill-chunk", "4",
    "--hidden", "32", "--layers", "2", "--vocab", "64", "--heads", "4",
]


@pytest.fixture(scope="module")
def bench_run(tmp_path_factory):
    run_dir = tmp_path_factory.mktemp("serve_bench")
    stats_json = run_dir / "stats.json"
    cmd = [
        sys.executable, "-m", "scaling_tpu.serve", "bench",
        *BENCH_ARGS, "--run-dir", str(run_dir), "--json", str(stats_json),
        "--assert-serve-throughput", "0.5", "--assert-ttft", "120",
    ]
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "SCALING_TPU_TEST_CACHE": "off"}
    env.pop("SCALING_TPU_EVENTS_PATH", None)
    p = subprocess.run(cmd, cwd=REPO, env=env, capture_output=True,
                       text=True, timeout=120)
    assert p.returncode == 0, p.stdout[-3000:] + p.stderr[-3000:]
    return run_dir, stats_json, p.stdout


def test_bench_serves_all_requests_with_finite_stats(bench_run):
    run_dir, stats_json, stdout = bench_run
    stats = json.loads(stats_json.read_text())
    assert stats["requests"] == 8
    assert stats["output_tokens"] > 0
    assert stats["tokens_per_s"] > 0
    assert 0 < stats["ttft_p99_s"] < 120
    assert "== gates ==" in stdout and "PASS" in stdout
    # telemetry artifacts landed on the standard rails
    assert (run_dir / "events.jsonl").is_file()
    assert (run_dir / "metrics.jsonl").is_file()


def test_bench_exercised_concurrent_chunked_prefill(bench_run):
    """The ISSUE 10 acceptance shape, now through the ISSUE 11 fused
    tick: at least 2 prompts prefilled in the same tick (chunked
    admission shares the budget) through exactly ONE compiled mixed
    program — a tick with N prefilling prompts dispatches 1 executable,
    not N+1."""
    _, stats_json, stdout = bench_run
    stats = json.loads(stats_json.read_text())
    assert stats["max_concurrent_prefills"] >= 2, stats
    assert stats["prefill_compiles"] == 1, stats
    assert "hot path: prefill_chunk=4 max_concurrent_prefills=" in stdout


def test_obs_report_grows_serving_section_over_bench_run_dir(bench_run,
                                                             capsys):
    """The REAL analyzer over the real run dir: parses cleanly (exit 0),
    renders the serving section with finite numbers, and the gates
    mirror the training MFU gates' exit-code contract."""
    from scaling_tpu.obs.cli import main

    run_dir, _, _ = bench_run
    rc = main(["report", str(run_dir),
               "--assert-serve-throughput", "0.5", "--assert-ttft", "120"])
    out = capsys.readouterr().out
    assert rc == 0, out
    assert "== serving ==" in out
    assert "output tokens/s" in out
    assert "ttft: p50=" in out
    # tick-time attribution: the fused run lands in the mixed phase
    assert "tick time:" in out
    assert "mixed" in out
    assert "PASS" in out


def test_obs_report_serving_gates_fail_at_absurd_thresholds(bench_run,
                                                            capsys):
    from scaling_tpu.obs.cli import main

    run_dir, _, _ = bench_run
    rc = main(["report", str(run_dir),
               "--assert-serve-throughput", "1e9", "--assert-ttft", "1e-9"])
    out = capsys.readouterr().out
    assert rc == 1
    assert "FAIL assert-serve-throughput" in out
    assert "FAIL assert-ttft" in out


@pytest.fixture(scope="module")
def prefix_bench_run(tmp_path_factory):
    """The ISSUE 11 acceptance arm: 8 requests per prompt family sharing
    a 48-token system prompt, arriving slowly enough that followers hit
    the warm trie, under the SAME --assert-ttft gate as the general run."""
    run_dir = tmp_path_factory.mktemp("serve_bench_prefix")
    stats_json = run_dir / "stats.json"
    cmd = [
        sys.executable, "-m", "scaling_tpu.serve", "bench",
        "--requests", "8", "--rate", "3", "--seed", "5", "--warmup", "1",
        "--shared-prefix-len", "48", "--prefix-families", "1",
        "--prompt-len", "2", "6", "--output-len", "3", "6",
        "--num-slots", "4", "--block-size", "4", "--num-blocks", "64",
        "--max-blocks-per-seq", "16", "--token-budget", "64",
        "--prefill-chunk", "8",
        "--hidden", "32", "--layers", "2", "--vocab", "64", "--heads", "4",
        "--run-dir", str(run_dir), "--json", str(stats_json),
        "--assert-serve-throughput", "0.5", "--assert-ttft", "120",
    ]
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "SCALING_TPU_TEST_CACHE": "off"}
    env.pop("SCALING_TPU_EVENTS_PATH", None)
    p = subprocess.run(cmd, cwd=REPO, env=env, capture_output=True,
                       text=True, timeout=120)
    assert p.returncode == 0, p.stdout[-3000:] + p.stderr[-3000:]
    return run_dir, stats_json, p.stdout


def test_prefix_arm_cuts_prefill_work_4x_under_same_gates(prefix_bench_run):
    """8 requests/prompt-family pay the shared prefix once: prefill
    token work (prompt tokens actually prefilled) drops >= 4x vs the
    no-cache total, while the standard TTFT/throughput gates still
    PASS."""
    _, stats_json, stdout = prefix_bench_run
    stats = json.loads(stats_json.read_text())
    assert stats["requests"] == 8
    assert stats["prefix_hit_tokens"] > 0, stats
    assert stats["prefilled_tokens"] * 4 <= stats["prompt_tokens"], stats
    assert "prefix cache:" in stdout and "tokens hit" in stdout
    assert "PASS" in stdout


def test_prefix_arm_report_renders_the_prefix_hit_line(prefix_bench_run,
                                                       capsys):
    """obs report over the prefix arm's run dir renders the prefix-hit
    line, under the gates the bench itself passed."""
    from scaling_tpu.obs.cli import main

    run_dir, _, _ = prefix_bench_run
    rc = main(["report", str(run_dir), "--assert-ttft", "120"])
    out = capsys.readouterr().out
    assert rc == 0, out
    assert "prefix cache:" in out and "tokens hit" in out


@pytest.mark.parametrize("entry,flag", [
    ("bench", "--spec-k"), ("bench", "--spec-k-sweep"),
    ("report", "--assert-spec-accept-rate")])
def test_a_flag_of_the_deleted_drafting_is_an_error_that_names_it(
        bench_run, capsys, entry, flag):
    """The engine drafts nothing: a stale command line that still asks for
    drafts, or gates on their accept rate, stops at the parser and does not
    run (or pass a run dir) without them."""
    from scaling_tpu.obs.cli import main as obs_main
    from scaling_tpu.serve.bench import main as bench_main

    run_dir, _, _ = bench_run
    with pytest.raises(SystemExit) as refused:
        if entry == "bench":
            bench_main([*BENCH_ARGS, flag, "2"])
        else:
            obs_main(["report", str(run_dir), flag, "0"])
    assert refused.value.code == 2
    assert f"unrecognized arguments: {flag}" in capsys.readouterr().err


CHAOS_ARGS = [
    "--requests", "6", "--rate", "50", "--seed", "3",
    "--prompt-len", "4", "10", "--output-len", "3", "5",
    "--num-slots", "4", "--block-size", "4", "--num-blocks", "64",
    "--max-blocks-per-seq", "8", "--token-budget", "64",
    "--prefill-chunk", "4",
    "--hidden", "32", "--layers", "2", "--vocab", "64", "--heads", "4",
]


def _bench_cmd_env(run_dir, faults=None, extra=(), args=CHAOS_ARGS):
    cmd = [
        sys.executable, "-m", "scaling_tpu.serve", "bench",
        *args, "--run-dir", str(run_dir),
        "--json", str(run_dir / "stats.json"), *extra,
    ]
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "SCALING_TPU_TEST_CACHE": "off"}
    env.pop("SCALING_TPU_EVENTS_PATH", None)
    env.pop("SCALING_TPU_FAULTS", None)
    if faults:
        env["SCALING_TPU_FAULTS"] = faults
    return cmd, env


def _run_chaos_bench(run_dir, faults=None, extra=()):
    cmd, env = _bench_cmd_env(run_dir, faults=faults, extra=extra)
    return subprocess.run(cmd, cwd=REPO, env=env, capture_output=True,
                          text=True, timeout=120)


# a slow open-loop tail (20 requests at 1/s) keeps the bench busy long
# enough for an external SIGTERM to land demonstrably mid-workload
DRAIN_ARGS = [
    "--requests", "20", "--rate", "1", *CHAOS_ARGS[4:],
]


def _sigterm_mid_bench(run_dir, extra=()):
    """Start the bench, wait for its first served request, SIGTERM it,
    and return the exit code (killing the tree on timeout)."""
    import signal as _signal
    import time as _time

    cmd, env = _bench_cmd_env(run_dir, extra=extra, args=DRAIN_ARGS)
    p = subprocess.Popen(cmd, cwd=REPO, env=env, stdout=subprocess.PIPE,
                         stderr=subprocess.PIPE, text=True)
    try:
        deadline = _time.monotonic() + 360
        events = run_dir / "events.jsonl"
        while _time.monotonic() < deadline:
            if events.is_file() and "serve-request" in events.read_text():
                break
            _time.sleep(0.2)
        else:
            pytest.fail("bench never served a request")
        p.send_signal(_signal.SIGTERM)
        return p.wait(timeout=120)
    finally:
        if p.poll() is None:
            p.kill()
            p.wait(timeout=30)


@pytest.fixture(scope="module")
def chaos_runs(tmp_path_factory):
    """The ISSUE 13 acceptance pair: a fault-free reference run, and a
    chaos run killed mid-tick (``serve.tick=kill@6`` — SIGKILL, no
    cleanup) under the supervised relaunch wrapper (``--restarts 2``),
    which replays the request journal and serves the rest."""
    tmp = tmp_path_factory.mktemp("serve_chaos")
    clean_dir = tmp / "clean"
    clean_dir.mkdir()
    p_clean = _run_chaos_bench(clean_dir)
    assert p_clean.returncode == 0, \
        p_clean.stdout[-3000:] + p_clean.stderr[-3000:]
    chaos_dir = tmp / "chaos"
    chaos_dir.mkdir()
    p_chaos = _run_chaos_bench(
        chaos_dir, faults="serve.tick=kill@6", extra=("--restarts", "2"),
    )
    return clean_dir, chaos_dir, p_chaos


def test_chaos_bench_supervised_restart_is_token_exact(chaos_runs):
    """Kill-mid-tick via the ``serve.tick`` fault point, supervised
    restart, journal replay: the wrapper exits 0, at least one restart
    actually happened (the crashed child really died mid-run), and
    EVERY request's final output is token-for-token identical to the
    fault-free run — the deadline/shed-free chaos arm loses no request
    and corrupts no output."""
    from scaling_tpu.serve.journal import replay_journal

    clean_dir, chaos_dir, p_chaos = chaos_runs
    assert p_chaos.returncode == 0, \
        p_chaos.stdout[-3000:] + p_chaos.stderr[-3000:]
    events = [
        json.loads(l)
        for l in (chaos_dir / "events.jsonl").read_text().splitlines()
    ]
    restarts = [e for e in events if e["event"] == "serve-restart"]
    resumes = [e for e in events if e["event"] == "serve-resume"]
    assert restarts and resumes, events
    clean = replay_journal(clean_dir / "journal.jsonl")
    chaos = replay_journal(chaos_dir / "journal.jsonl")
    assert len(clean.completed) == 6
    assert chaos.completed == clean.completed  # token-for-token


def test_chaos_run_dir_passes_shed_and_timeout_gates(chaos_runs, capsys):
    """The resumed run dir parses through the real analyzer: restart
    line rendered, shed/timeout gates PASS at 0 (nothing shed, nothing
    timed out) and fail at impossible ceilings via missing-data-fails
    elsewhere."""
    from scaling_tpu.obs.cli import main

    _, chaos_dir, _ = chaos_runs
    rc = main(["report", str(chaos_dir),
               "--assert-max-shed-rate", "0",
               "--assert-max-serve-timeouts", "0"])
    out = capsys.readouterr().out
    assert rc == 0, out
    assert "resilience: shed=0" in out
    assert "restarts=1" in out
    assert "PASS" in out


def test_shed_timeout_gates_fail_on_missing_data(tmp_path, capsys):
    """Missing data FAILS a requested gate: a run dir whose
    serve-summary predates the resilience fields (or has none at all)
    must not pass by silence."""
    from scaling_tpu.obs.cli import main

    (tmp_path / "events.jsonl").write_text(json.dumps({
        "event": "serve-summary", "ts": 1.0, "requests": 2,
        "tokens_per_s": 5.0, "output_tokens": 10, "wall_s": 2.0,
    }) + "\n")
    rc = main(["report", str(tmp_path),
               "--assert-max-shed-rate", "1.0",
               "--assert-max-serve-timeouts", "100"])
    out = capsys.readouterr().out
    assert rc == 1
    assert "FAIL assert-max-shed-rate: no shed telemetry" in out
    assert "FAIL assert-max-serve-timeouts: no timeout telemetry" in out


def test_wedged_tick_watchdog_kills_and_supervisor_recovers(tmp_path):
    """``serve.tick=hang`` wedges the engine mid-run; the tick-stall
    watchdog must dump stacks, log serve-stall, and SIGKILL the child
    so the ``--restarts`` supervisor actually recovers (relaunch +
    journal replay) instead of hanging forever behind a silent
    child. ``--warmup`` compiles the program before the watchdog is
    armed (both launches), so the deadline is held against a warm tick's
    milliseconds and not against a compile that five busy neighbours
    stretch past it; the 2 warm-up ticks count towards ``hang@4``."""
    run_dir = tmp_path / "hang"
    run_dir.mkdir()
    p = _run_chaos_bench(
        run_dir, faults="serve.tick=hang@4",
        extra=("--restarts", "1", "--tick-timeout-s", "5", "--warmup", "1"),
    )
    assert p.returncode == 0, p.stdout[-3000:] + p.stderr[-3000:]
    events = [
        json.loads(l)
        for l in (run_dir / "events.jsonl").read_text().splitlines()
    ]
    assert any(e["event"] == "serve-stall" for e in events)
    restarts = [e for e in events if e["event"] == "serve-restart"]
    assert restarts and restarts[0]["rc"] == -9  # the watchdog's SIGKILL
    from scaling_tpu.serve.journal import replay_journal

    final = replay_journal(run_dir / "journal.jsonl")
    assert len(final.completed) == 6 and not final.incomplete


def test_sigterm_to_supervisor_relays_drain_to_child(tmp_path):
    """The graceful-drain contract in SUPERVISED mode: SIGTERM to the
    --restarts supervisor is relayed to the running child, the child
    drains and exits 0, the supervisor exits 0, and no orphan keeps
    writing to the run dir."""
    run_dir = tmp_path / "supdrain"
    run_dir.mkdir()
    assert _sigterm_mid_bench(run_dir, extra=("--restarts", "2")) == 0
    stats = json.loads((run_dir / "stats.json").read_text())
    assert stats["drained"] is True and stats["unsubmitted"] > 0
    evs = [
        json.loads(l)
        for l in (run_dir / "events.jsonl").read_text().splitlines()
    ]
    assert any(e["event"] == "serve-drain" for e in evs)
    assert not any(e["event"] == "serve-restart" for e in evs)


def test_sigterm_mid_bench_drains_and_exits_zero(tmp_path):
    """The graceful-drain acceptance: SIGTERM mid-bench -> no new
    admissions, in-flight requests finish, telemetry flushes, exit 0 —
    and the run dir passes the shed/timeout gates with the drain noted
    in the serving section."""
    run_dir = tmp_path / "drain"
    run_dir.mkdir()
    assert _sigterm_mid_bench(run_dir) == 0
    stats = json.loads((run_dir / "stats.json").read_text())
    assert stats["drained"] is True
    assert stats["unsubmitted"] > 0  # it really was mid-bench
    assert stats["requests_timeout"] == 0
    evs = [
        json.loads(l)
        for l in (run_dir / "events.jsonl").read_text().splitlines()
    ]
    assert any(e["event"] == "serve-drain" for e in evs)
    assert any(e["event"] == "serve-summary" for e in evs)

    from scaling_tpu.obs.cli import main

    assert main(["report", str(run_dir),
                 "--assert-max-shed-rate", "0",
                 "--assert-max-serve-timeouts", "0"]) == 0


def test_bench_registry_metrics_flushed(bench_run):
    """The engine's counters/gauges land in the metrics JSONL through
    obs.get_registry() — the same registry training flushes through."""
    run_dir, _, _ = bench_run
    recs = [
        json.loads(line)
        for line in (run_dir / "metrics.jsonl").read_text().splitlines()
        if line.strip()
    ]
    regs = [r for r in recs if r.get("kind") == "registry"]
    assert regs
    counters = regs[-1]["counters"]
    assert counters["serve_requests_completed_total"] == 8.0
    assert counters["serve_tokens_generated_total"] > 0
    gauges = regs[-1]["gauges"]
    assert gauges["serve_running_seqs"] == 0.0
    assert gauges["serve_free_blocks"] == 63.0  # all recycled at drain
