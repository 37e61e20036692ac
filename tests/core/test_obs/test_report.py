"""Run-dir analyzer: golden health reports over canned run dirs (single
host and a 2-host pod exercising straggler attribution), gate exit
codes, the --json payload, and the real ``python -m scaling_tpu.obs``
entrypoint (ISSUE 5 acceptance criterion).

The goldens pin the EXACT rendering — formatting changes are deliberate:
regenerate with
``python -c "from scaling_tpu.obs.report import *; ..."`` (see
docs/OBSERVABILITY.md) and re-review the diff by eye."""

import json
import subprocess
import sys
from pathlib import Path

import pytest

from scaling_tpu.obs.cli import main
from scaling_tpu.obs.report import check_gates, load_run_dir, render_report

FIXTURES = Path(__file__).resolve().parent / "fixtures"


def _golden(name: str) -> str:
    return (FIXTURES / f"golden_{name}.txt").read_text()


# ---------------------------------------------------------------- golden
def test_single_host_golden_report():
    data = load_run_dir(FIXTURES / "rundir_single")
    # the torn tail line (SIGKILLed writer) is counted, never fatal
    assert data.bad_lines == 1
    assert render_report(data, "RUNDIR") == _golden("single")


def test_pod_golden_report_with_straggler_attribution():
    data = load_run_dir(FIXTURES / "rundir_pod")
    report = render_report(data, "RUNDIR")
    assert report == _golden("pod")
    # the load-bearing verdicts, asserted independently of formatting:
    # host 1 is slow (0.75s vs 0.5s p50), so host 0 waits at every
    # barrier and host 1 "arrived last" — the offline echo of the live
    # _on_step_stall straggler table
    assert "straggler: host 1 (p50 1.50x the fastest host)" in report
    assert "blame: host 1 kept peers waiting 2.530s across 4 barrier(s)" in report
    assert "[FAILED: BarrierTimeout]" in report
    assert "totals: restarts=1 preemptions=1 stalls=0" in report
    assert "commit_barrier=0.320s" in report


def test_epoch_keyed_attribution_separates_relaunch_incidents():
    """A relaunched pod re-waits the same barrier name and re-saves the
    same step; attribution must keep the epochs apart — host 0 straggles
    in epoch 0, host 1 in epoch 1, and neither verdict may blend."""
    from scaling_tpu.obs.report import (
        RunData, barrier_section, checkpoint_section,
    )

    def bw(epoch, host, dur):
        return {"event": "span", "span": "barrier.wait", "ts": 1.0,
                "barrier": "commit:step-3", "epoch": epoch, "host": host,
                "dur_s": dur, "ok": True}

    def stage(epoch, dur):
        return {"event": "span", "span": "ckpt.stage", "ts": 1.0,
                "step": 3, "epoch": epoch, "dur_s": dur, "ok": True}

    data = RunData(
        events=[bw(0, 0, 0.01), bw(0, 1, 5.0),   # epoch 0: host 0 last
                bw(1, 0, 5.0), bw(1, 1, 0.01),   # epoch 1: host 1 last
                stage(0, 1.0), stage(1, 2.0)],
        steps=[], registry=[], files=1, bad_lines=0,
    )
    barriers = "\n".join(barrier_section(data))
    assert "epoch 0 commit:step-3" in barriers
    assert "epoch 1 commit:step-3" in barriers
    assert "-> host 0 arrived last" in barriers
    assert "-> host 1 arrived last" in barriers
    assert "blame: host 0 kept peers waiting 5.000s across 1 barrier(s)" in barriers
    assert "blame: host 1 kept peers waiting 5.000s across 1 barrier(s)" in barriers
    ckpt = "\n".join(checkpoint_section(data))
    assert "epoch 0 step 3: stage=1.000s" in ckpt
    assert "epoch 1 step 3: stage=2.000s" in ckpt


def test_failed_barrier_excluded_from_blame():
    """Host 2 dies before the barrier: the survivors both time out with
    ok=false. The arrived-last/blame accounting must not pick whichever
    survivor's timeout was marginally shorter — the culprit never wrote
    a span at all."""
    from scaling_tpu.obs.report import RunData, barrier_section

    data = RunData(
        events=[
            {"event": "span", "span": "barrier.wait", "ts": 1.0,
             "barrier": "commit:step-9", "host": 0, "dur_s": 30.0,
             "ok": False, "error": "BarrierTimeout"},
            {"event": "span", "span": "barrier.wait", "ts": 1.0,
             "barrier": "commit:step-9", "host": 1, "dur_s": 29.8,
             "ok": False, "error": "BarrierTimeout"},
        ],
        steps=[], registry=[], files=1, bad_lines=0,
    )
    section = "\n".join(barrier_section(data))
    assert "[FAILED: BarrierTimeout]" in section
    assert "arrived last" not in section
    assert "blame:" not in section


# ----------------------------------------------------------------- gates
def test_gates_pass_and_fail_thresholds():
    data = load_run_dir(FIXTURES / "rundir_single")
    assert check_gates(data, assert_mfu=0.30, assert_step_time=0.6) == []
    failures = check_gates(data, assert_mfu=0.5, assert_step_time=0.1)
    assert len(failures) == 2
    assert "mean MFU 0.3300 < floor 0.5000" in failures[0]
    assert "p50 step time 0.500s > ceiling 0.100s" in failures[1]


def test_gates_fail_on_missing_data():
    """A run that recorded no MFU must not pass an MFU floor by silence."""
    data = load_run_dir(FIXTURES / "rundir_single")
    data = type(data)(events=data.events, steps=[], registry=data.registry,
                      files=data.files, bad_lines=data.bad_lines)
    failures = check_gates(data, assert_mfu=0.1, assert_step_time=1.0)
    assert any("no MFU samples" in f for f in failures)
    assert any("no step_duration samples" in f for f in failures)


# ------------------------------------------------------------------- CLI
def test_cli_exit_codes_and_json(tmp_path, capsys):
    rc = main(["report", str(FIXTURES / "rundir_single")])
    assert rc == 0
    assert "== run summary ==" in capsys.readouterr().out

    out_json = tmp_path / "report.json"
    rc = main([
        "report", str(FIXTURES / "rundir_single"),
        "--assert-mfu", "0.5", "--json", str(out_json),
    ])
    assert rc == 1
    out = capsys.readouterr().out
    assert "== gates ==" in out and "FAIL assert-mfu" in out
    payload = json.loads(out_json.read_text())
    assert payload["step_records"] == 5 and payload["bad_lines"] == 1
    assert payload["stats"]["mfu_mean"] == pytest.approx(0.33)
    assert len(payload["gate_failures"]) == 1


def test_cli_gates_pass_prints_pass(capsys):
    rc = main([
        "report", str(FIXTURES / "rundir_pod"),
        "--assert-mfu", "0.2", "--assert-step-time", "1.0",
    ])
    assert rc == 0
    assert "  PASS" in capsys.readouterr().out


def test_cli_empty_and_missing_dir_exit_2(tmp_path, capsys):
    assert main(["report", str(tmp_path)]) == 2
    assert "no telemetry records" in capsys.readouterr().err
    assert main(["report", str(tmp_path / "nope")]) == 2
    assert "not a directory" in capsys.readouterr().err


def test_module_entrypoint_subprocess():
    """The documented invocation, end to end — and it must stay fast:
    the obs package imports no jax at module level, so the analyzer
    never pays backend init."""
    proc = subprocess.run(
        [sys.executable, "-m", "scaling_tpu.obs", "report",
         str(FIXTURES / "rundir_pod")],
        capture_output=True, text=True, timeout=60,
    )
    assert proc.returncode == 0, proc.stderr
    assert proc.stdout == _golden("pod").replace(
        "RUNDIR", str(FIXTURES / "rundir_pod")
    )


def test_obs_package_imports_without_jax():
    """Contract pinned: importing scaling_tpu.obs must not import jax
    (the supervisor's relaunch path and the CLI both rely on this)."""
    code = (
        "import sys; import scaling_tpu.obs; import scaling_tpu.obs.report; "
        "import scaling_tpu.obs.cli; sys.exit(1 if 'jax' in sys.modules else 0)"
    )
    proc = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        timeout=60,
    )
    assert proc.returncode == 0, "scaling_tpu.obs pulled in jax at import time"


def _pipeline_run_dir(tmp_path, virtual=1, token_slices=1, steps=4,
                      fwdbwd=0.01, sync=0.99):
    lines = [json.dumps({"event": "pipeline-config", "ts": 0.0, "pp": 2,
                         "virtual": virtual, "token_slices": token_slices,
                         "gas": 8})]
    for s in range(10, 10 + steps):
        # first step is the compile outlier the section must drop
        scale = 30.0 if s == 10 else 1.0
        lines.append(json.dumps({"event": "span", "span": "step.fwdbwd",
                                 "step": s, "dur_s": fwdbwd * scale,
                                 "ts": float(s)}))
        lines.append(json.dumps({"event": "span", "span": "step.sync",
                                 "step": s, "dur_s": sync * scale,
                                 "ts": float(s) + 0.5}))
    (tmp_path / "events.jsonl").write_text("\n".join(lines) + "\n")
    return tmp_path


def test_pipeline_section_attributes_measured_idle(tmp_path):
    """Deterministic spans -> exact attribution: interleaved pp=2 v=2
    gas=8 is 16 work / 17 total ticks (5.9% bubble vs fill-drain's
    11.1%), and the measured p50 (1.0s, compile step dropped) attributes
    0.059s/step of fill/drain idle."""
    from scaling_tpu.obs.report import load_run_dir, pipeline_section

    data = load_run_dir(_pipeline_run_dir(tmp_path, virtual=2))
    lines = pipeline_section(data)
    text = "\n".join(lines)
    assert "schedule: interleaved(v=2) pp=2 gas=8 (16 work ticks / 17 total" in text
    assert "predicted bubble: 5.9% (fill-drain on this shape: 11.1%)" in text
    assert "fwdbwd+sync amortized over 3 steps): 1.000s" in text
    assert "idle 0.059s/step (5.9% of compute)" in text


def test_pipeline_section_token_slice_and_fill_drain(tmp_path):
    from scaling_tpu.obs.report import load_run_dir, pipeline_section

    d1 = tmp_path / "ts"; d1.mkdir()
    text = "\n".join(pipeline_section(load_run_dir(
        _pipeline_run_dir(d1, token_slices=4))))
    assert "token-slice(S=4)" in text and "predicted bubble: 3.0%" in text
    d2 = tmp_path / "fd"; d2.mkdir()
    text = "\n".join(pipeline_section(load_run_dir(_pipeline_run_dir(d2))))
    assert "fill-drain" in text and "predicted bubble: 11.1%" in text


def test_pipeline_section_absent_without_config_event(tmp_path):
    """Non-pipelined run dirs keep their exact report layout — the
    committed golden reports must not grow an empty pipeline section."""
    from scaling_tpu.obs.report import load_run_dir, pipeline_section

    (tmp_path / "events.jsonl").write_text(
        json.dumps({"event": "span", "span": "step.fwdbwd", "step": 1,
                    "dur_s": 0.5, "ts": 1.0}) + "\n")
    assert pipeline_section(load_run_dir(tmp_path)) == []
    assert "== pipeline ==" not in render_report(load_run_dir(tmp_path))


# ---------------------------------------------------------- tuner section
def _tuner_run_dir(tmp_path, predicted=1.1, steps=4, fwdbwd=0.01, sync=0.99,
                   with_spans=True, with_steps=False):
    lines = [json.dumps({
        "event": "tuner-prediction", "ts": 0.0, "label": "pp1·dp8·mp1·z1",
        "predicted_step_s": predicted, "world_size": 8,
        "source": "run_dir:test",
    })]
    for s in range(10, 10 + steps):
        scale = 30.0 if s == 10 else 1.0  # compile outlier, dropped
        if with_spans:
            lines.append(json.dumps({"event": "span", "span": "step.fwdbwd",
                                     "step": s, "dur_s": fwdbwd * scale,
                                     "ts": float(s)}))
            lines.append(json.dumps({"event": "span", "span": "step.sync",
                                     "step": s, "dur_s": sync * scale,
                                     "ts": float(s) + 0.5}))
    (tmp_path / "events.jsonl").write_text("\n".join(lines) + "\n")
    if with_steps:
        (tmp_path / "metrics.jsonl").write_text(json.dumps({
            "kind": "step", "step": 11, "host": 0,
            "metrics": {"step_duration": 2.0},
        }) + "\n")
    return tmp_path


def test_tuner_section_scores_prediction_vs_span_measured(tmp_path):
    """ISSUE 8 acceptance: the tuner section compares the predicted step
    time against the SPAN-measured compute (fwdbwd+sync p50, compile
    step dropped — here exactly 1.0s) and reports a finite calibration
    error (+10% for a 1.1s prediction)."""
    from scaling_tpu.obs.report import load_run_dir, tuner_section

    data = load_run_dir(_tuner_run_dir(tmp_path, predicted=1.1))
    lines, stats = tuner_section(data)
    text = "\n".join(lines)
    assert "== tuner ==" in text
    assert "layout pp1·dp8·mp1·z1: predicted 1.100s/step" in text
    assert "measured: 1.000s/step [span-measured compute" in text
    assert "calibration error: +10.0%" in text
    assert stats["tuner_calibration_error"] == pytest.approx(0.10)
    assert stats["tuner_measured_step_s"] == pytest.approx(1.0)


def test_tuner_section_falls_back_to_step_duration(tmp_path):
    from scaling_tpu.obs.report import load_run_dir, tuner_section

    data = load_run_dir(_tuner_run_dir(
        tmp_path, predicted=1.0, with_spans=False, with_steps=True
    ))
    lines, stats = tuner_section(data)
    text = "\n".join(lines)
    assert "step_duration p50 (no spans" in text
    assert stats["tuner_calibration_error"] == pytest.approx(-0.5)


def test_tuner_section_absent_without_prediction_event(tmp_path):
    """Untuned run dirs keep their exact report layout — the committed
    golden reports must not grow an empty tuner section."""
    from scaling_tpu.obs.report import load_run_dir, tuner_section

    (tmp_path / "events.jsonl").write_text(
        json.dumps({"event": "span", "span": "step.fwdbwd", "step": 1,
                    "dur_s": 0.5, "ts": 1.0}) + "\n")
    lines, stats = tuner_section(load_run_dir(tmp_path))
    assert lines == [] and stats == {}
    assert "== tuner ==" not in render_report(load_run_dir(tmp_path))


def test_tuner_calibration_gate(tmp_path):
    """The gate fails on a too-large calibration error AND on missing
    data (a run with no prediction must not pass by silence), and the
    CLI wires --assert-tuner-calibration through."""
    from scaling_tpu.obs.cli import main
    from scaling_tpu.obs.report import load_run_dir

    run = _tuner_run_dir(tmp_path, predicted=1.5)  # 50% off
    data = load_run_dir(run)
    assert check_gates(data, assert_tuner_calibration=0.6) == []
    failures = check_gates(data, assert_tuner_calibration=0.25)
    assert failures and "assert-tuner-calibration" in failures[0]
    # missing data fails
    empty = tmp_path / "untuned"
    empty.mkdir()
    (empty / "events.jsonl").write_text(
        json.dumps({"event": "relaunch", "ts": 1.0}) + "\n")
    assert check_gates(
        load_run_dir(empty), assert_tuner_calibration=0.5
    )
    # CLI: pass and fail exit codes
    assert main([
        "report", str(run), "--assert-tuner-calibration", "0.6"
    ]) == 0
    assert main([
        "report", str(run), "--assert-tuner-calibration", "0.25"
    ]) == 1


# ---------------------------------------------------------------- serving
def _serve_run_dir(tmp_path, with_summary=True, n_requests=4):
    """Canned serving run dir (ISSUE 9): serve-request events with known
    TTFTs + a serve-summary with known throughput."""
    run = tmp_path / "serve_run"
    run.mkdir(exist_ok=True)
    lines = []
    for i in range(n_requests):
        lines.append(json.dumps({
            "event": "serve-request", "ts": 10.0 + i, "req": i,
            "prompt_tokens": 8, "output_tokens": 4,
            "ttft_s": 0.1 * (i + 1), "e2e_s": 0.5 + 0.1 * i,
            "itl_mean_s": 0.01 * (i + 1),
            "preemptions": 1 if i == 2 else 0,
        }))
    if with_summary:
        lines.append(json.dumps({
            "event": "serve-summary", "ts": 20.0, "requests": n_requests,
            "wall_s": 2.0, "output_tokens": 4 * n_requests,
            "tokens_per_s": 2 * n_requests, "ticks": 12, "preemptions": 1,
            "prefill_compiles": 2,
        }))
    (run / "events.jsonl").write_text("\n".join(lines) + "\n")
    return run


def test_serving_section_renders_percentiles_and_throughput(tmp_path):
    """ISSUE 9 acceptance: the serving section reports tokens/s from the
    summary event and exact TTFT percentiles over the per-request
    events, plus the preempted-and-resumed count."""
    from scaling_tpu.obs.report import load_run_dir, serving_section

    data = load_run_dir(_serve_run_dir(tmp_path))
    lines, stats = serving_section(data)
    text = "\n".join(lines)
    assert "== serving ==" in text
    assert "throughput: 8.0 output tokens/s" in text
    assert "ticks=12 preemptions=1 prefill_compiles=2" in text
    assert "preempted-and-resumed: 1 of 4" in text
    assert stats["serve_tokens_per_s"] == pytest.approx(8.0)
    assert stats["serve_ttft_p50_s"] == pytest.approx(0.2)
    assert stats["serve_ttft_p99_s"] == pytest.approx(0.4)


def test_serving_section_derives_throughput_without_summary(tmp_path):
    """A crashed run (no serve-summary) still reports: throughput is
    derived from the request events' tokens and timestamps."""
    from scaling_tpu.obs.report import load_run_dir, serving_section

    data = load_run_dir(_serve_run_dir(tmp_path, with_summary=False))
    lines, stats = serving_section(data)
    text = "\n".join(lines)
    assert "no serve-summary" in text
    # 16 tokens over ts spread 3.0s
    assert stats["serve_tokens_per_s"] == pytest.approx(16 / 3.0)
    assert stats["serve_ttft_p99_s"] == pytest.approx(0.4)


def test_serving_section_absent_for_training_runs(tmp_path):
    """Training run dirs keep their exact report layout — the committed
    golden reports must not grow an empty serving section."""
    from scaling_tpu.obs.report import load_run_dir, serving_section

    (tmp_path / "events.jsonl").write_text(
        json.dumps({"event": "span", "span": "step.fwdbwd", "step": 1,
                    "dur_s": 0.5, "ts": 1.0}) + "\n")
    lines, stats = serving_section(load_run_dir(tmp_path))
    assert lines == [] and stats == {}
    assert "== serving ==" not in render_report(load_run_dir(tmp_path))


def test_serving_gates_thresholds_and_missing_data(tmp_path):
    """--assert-serve-throughput / --assert-ttft: pass at sane
    thresholds, fail at absurd ones, fail on run dirs with no serving
    telemetry at all (silence must not pass a gate)."""
    data = load_run_dir(_serve_run_dir(tmp_path))
    assert check_gates(data, assert_serve_throughput=1.0,
                       assert_ttft=1.0) == []
    failures = check_gates(data, assert_serve_throughput=1e9,
                           assert_ttft=1e-9)
    assert len(failures) == 2
    assert "assert-serve-throughput" in failures[0]
    assert "assert-ttft" in failures[1]
    empty = tmp_path / "training_only"
    empty.mkdir()
    (empty / "events.jsonl").write_text(
        json.dumps({"event": "span", "span": "step.fwdbwd", "step": 1,
                    "dur_s": 0.5, "ts": 1.0}) + "\n")
    failures = check_gates(load_run_dir(empty),
                           assert_serve_throughput=1.0, assert_ttft=1.0)
    assert len(failures) == 2
    assert all("no " in f for f in failures)


# ----------------------------------------------------- elastic downsizing
def _elastic_run_dir(tmp_path, downsizes=1, supervised=True):
    """Canned supervised run dir with a downsize + reshard transition
    (ISSUE 12): the restart timeline must render the world-size
    transition and the --assert-max-downsizes gate must count it."""
    run = tmp_path / "elastic_run"
    run.mkdir(parents=True, exist_ok=True)
    lines = []
    if supervised:
        lines.append(json.dumps({
            "event": "epoch-start", "ts": 1.0, "epoch": 0, "num_hosts": 2,
        }))
    for i in range(downsizes):
        lines.append(json.dumps({
            "event": "downsize", "ts": 5.0 + i, "epoch": i,
            "old_world": 2 - i, "new_world": 1 - i, "removed_hosts": [1],
            "layout": None, "predicted_step_s": None, "source": "shrink",
        }))
    if downsizes:
        lines.append(json.dumps({
            "event": "ckpt-reshard", "ts": 8.0, "step": 3,
            "saved": "world2·pp1·dp2·cp1·mp1·hosts2",
            "restoring": "world1·pp1·dp1·cp1·mp1·hosts1",
            "saved_world": 2, "restoring_world": 1,
            "saved_hosts": 2, "restoring_hosts": 1,
        }))
    (run / "events.jsonl").write_text("\n".join(lines) + "\n")
    return run


def test_timeline_renders_world_size_transitions(tmp_path):
    from scaling_tpu.obs.report import load_run_dir, timeline_section

    run = _elastic_run_dir(tmp_path)
    lines = timeline_section(load_run_dir(run))
    joined = "\n".join(lines)
    assert "downsizes=1" in joined
    assert "world-size transitions:" in joined
    assert "2->1 (downsize/shrink)" in joined
    assert "2->1 (reshard" in joined
    # non-elastic runs render neither suffix nor transition line (the
    # committed golden reports stay byte-identical)
    plain = tmp_path / "plain"
    plain.mkdir()
    (plain / "events.jsonl").write_text(
        json.dumps({"event": "relaunch", "ts": 1.0}) + "\n")
    plain_lines = "\n".join(timeline_section(load_run_dir(plain)))
    assert "downsizes" not in plain_lines
    assert "world-size transitions" not in plain_lines


def test_max_downsizes_gate_counts_and_fails_on_missing_data(tmp_path):
    from scaling_tpu.obs.cli import main
    from scaling_tpu.obs.report import load_run_dir

    run = _elastic_run_dir(tmp_path)
    data = load_run_dir(run)
    assert check_gates(data, assert_max_downsizes=1) == []
    failures = check_gates(data, assert_max_downsizes=0)
    assert failures and "assert-max-downsizes" in failures[0]
    assert "2->1" in failures[0]  # the transition rides the message
    # missing data fails: no supervisor telemetry at all
    unsupervised = tmp_path / "unsup"
    unsupervised.mkdir()
    (unsupervised / "events.jsonl").write_text(
        json.dumps({"event": "relaunch", "ts": 1.0}) + "\n")
    failures = check_gates(
        load_run_dir(unsupervised), assert_max_downsizes=3
    )
    assert failures and "no supervisor telemetry" in failures[0]
    # a supervised run with zero downsizes passes any ceiling
    healthy = _elastic_run_dir(tmp_path / "h", downsizes=0)
    assert check_gates(load_run_dir(healthy), assert_max_downsizes=0) == []
    # CLI wiring: pass and fail exit codes
    assert main(["report", str(run), "--assert-max-downsizes", "1"]) == 0
    assert main(["report", str(run), "--assert-max-downsizes", "0"]) == 1
