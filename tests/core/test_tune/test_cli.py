"""Tuner CLI: the ranked --json report, the pinned golden, the
prediction event hand-off, the run-dir calibration sources, and the
tier-1 smoke — the emitted TopologyConfig round-trips validation and the
dryrun entrypoint really runs it (ISSUE 8 satellite: CI/tooling)."""

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

from scaling_tpu.tune import cli

REPO = Path(__file__).resolve().parents[3]


def run_cli(*args, timeout=120):
    return subprocess.run(
        [sys.executable, "-m", "scaling_tpu.tune", *args],
        cwd=REPO,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
        capture_output=True,
        text=True,
        timeout=timeout,
    )


@pytest.fixture(scope="module")
def report(tmp_path_factory):
    out = tmp_path_factory.mktemp("tune") / "report.json"
    p = run_cli("--devices", "8", "--model", "0.5b", "--json", str(out))
    assert p.returncode == 0, p.stdout[-2000:] + p.stderr[-2000:]
    return json.loads(out.read_text())


def test_cli_ranks_the_8dev_space(report):
    """ISSUE 8 acceptance: `python -m scaling_tpu.tune --json` ranks the
    8-device layout space and the top pick matches-or-beats the
    hand-picked MULTICHIP arm by the simulator+FLOPs score."""
    ranked = report["ranked"]
    assert len(ranked) > 10
    scores = [r["predicted_step_s"] for r in ranked]
    assert scores == sorted(scores)
    hand_picked = [
        r for r in ranked if r["label"] == "pp2·dp2·mp2·sp·z1"
    ]
    assert hand_picked, [r["label"] for r in ranked]
    assert ranked[0]["predicted_step_s"] <= hand_picked[0]["predicted_step_s"]
    # every row prices its comm against a link class
    assert all(
        rec["link"] in ("ici", "dcn")
        for r in ranked for rec in r["comm_by_axis"].values()
    )
    assert report["prediction"]["label"] == ranked[0]["label"]


def test_emitted_config_roundtrips_validation(report):
    from scaling_tpu.topology.config import TopologyConfig

    cfg = TopologyConfig.from_dict(report["topology_config"])
    assert cfg.world_size == 8


def test_check_golden_clean_and_drift_detection(report):
    p = run_cli("--devices", "8", "--model", "0.5b", "--check-golden")
    assert p.returncode == 0, p.stdout[-2000:]
    assert "golden: OK" in p.stdout
    # a doctored ranking must read as drift
    doctored = {
        "ranked": [
            dict(r, predicted_step_s=r["predicted_step_s"] * 2)
            for r in report["ranked"]
        ]
    }
    drift = cli.check_golden(
        doctored, cli.golden_path(8, "0.5b")
    )
    assert drift, "doubled scores must drift"
    reordered = {"ranked": list(reversed(report["ranked"]))}
    assert cli.check_golden(reordered, cli.golden_path(8, "0.5b"))


def test_record_events_appends_prediction(tmp_path):
    events = tmp_path / "events.jsonl"
    p = run_cli("--devices", "8", "--model", "0.5b",
                "--record-events", str(events))
    assert p.returncode == 0
    recs = [json.loads(l) for l in events.read_text().splitlines()]
    assert len(recs) == 1
    assert recs[0]["event"] == "tuner-prediction"
    assert recs[0]["predicted_step_s"] > 0
    assert "SCALING_TPU_TUNER_PREDICTION" in p.stdout


def test_obs_root_calibrates_from_newest_run_dir(tmp_path, capsys):
    """--obs-root: the newest obs run dir under it supplies the measured
    MFU the cost model is scaled by, and the report names that source."""
    obs_root = tmp_path / "telemetry"
    run = obs_root / "run_a"
    run.mkdir(parents=True)
    (run / "metrics_rank_0.jsonl").write_text(
        '{"kind": "step", "step": 1, "host": 0, "metrics": {"mfu": 0.4}}\n'
    )
    rc = cli.main([
        "--devices", "8", "--model", "0.5b", "--obs-root", str(obs_root),
        "--top", "1",
    ])
    assert rc == 0
    out = capsys.readouterr().out
    assert "efficiency=0.400" in out and str(run) in out


def test_no_measurement_means_the_labelled_default(tmp_path, capsys):
    """Without a run dir the tuner scores with the default efficiency and
    says so; it never borrows a number from a committed record."""
    rc = cli.main([
        "--devices", "8", "--model", "0.5b", "--obs-root", str(tmp_path),
        "--top", "1",
    ])
    assert rc == 0
    assert "uncalibrated" in capsys.readouterr().out


@pytest.mark.slow
def test_lower_crosscheck_agrees_with_analytic_volumes(tmp_path):
    """--lower lowers the REAL train step for the top layout (tiny audit
    shapes) and reports its per-axis inventory next to the analytic
    estimate; the dominant axis's analytic bytes must land within 2x of
    the lowered truth — the cost model's volumes are grounded, not
    invented."""
    out = tmp_path / "report.json"
    p = run_cli("--devices", "8", "--model", "0.5b", "--lower", "1",
                "--json", str(out), timeout=600)
    assert p.returncode == 0, p.stdout[-2000:] + p.stderr[-2000:]
    cross = json.loads(out.read_text())["lowered_crosscheck"]
    assert cross and "lowered_per_axis" in cross[0]
    lowered = cross[0]["lowered_per_axis"]
    analytic = cross[0]["analytic_per_axis"]
    dominant = max(lowered, key=lambda a: lowered[a]["bytes"])
    assert dominant in analytic, (lowered, analytic)
    ratio = analytic[dominant] / lowered[dominant]["bytes"]
    assert 0.5 <= ratio <= 2.0, (dominant, ratio)


def test_prediction_from_env_sanitizes(monkeypatch):
    """The trainer-side half of the hand-off: well-formed payloads pass
    through typed; malformed ones (bad JSON, missing the number) return
    None instead of killing a run."""
    from scaling_tpu import tune

    monkeypatch.setenv(tune.PREDICTION_ENV, json.dumps({
        "label": "pp1·dp8·mp1·z1", "predicted_step_s": "0.5",
        "world_size": 8, "source": "bench", "junk": object is None,
    }))
    pred = tune.prediction_from_env()
    assert pred == {"label": "pp1·dp8·mp1·z1", "predicted_step_s": 0.5,
                    "world_size": 8, "source": "bench"}
    for bad in ("not json", json.dumps({"label": "x"}), json.dumps([1])):
        monkeypatch.setenv(tune.PREDICTION_ENV, bad)
        assert tune.prediction_from_env() is None
    monkeypatch.delenv(tune.PREDICTION_ENV)
    assert tune.prediction_from_env() is None


def test_best_layout_runs_through_dryrun_entrypoint(report):
    """The tuner's pick is not advice — the dryrun entrypoint accepts it
    and executes one real sharded train step on the 8-device virtual
    mesh (the same path every dryrun layout takes), with the tuner-rank
    annotation riding the ok line."""
    topo = report["topology_config"]
    code = (
        "import __graft_entry__ as g\n"
        f"g._dryrun_one(8, pp={topo['pipe_parallel_size']}, "
        f"dp={topo['data_parallel_size']}, "
        f"cp={topo['context_parallel_size']}, "
        f"mp={topo['model_parallel_size']})\n"
    )
    env = {
        **os.environ,
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": (os.environ.get("XLA_FLAGS", "")
                      + " --xla_force_host_platform_device_count=8").strip(),
        "SCALING_TPU_TEST_CACHE": "off",
    }
    p = subprocess.run(
        [sys.executable, "-c", code], cwd=REPO, env=env,
        capture_output=True, text=True, timeout=120,
    )
    assert p.returncode == 0, p.stdout[-2000:] + p.stderr[-2000:]
    assert "dryrun ok" in p.stdout
    assert "tuner_rank=" in p.stdout
