"""End-to-end crash consistency (ISSUE 3 acceptance): a training
subprocess SIGKILLed mid-checkpoint-write restarts and auto-resumes from
the newest VALID checkpoint with no manual cleanup, and the resumed loss
trajectory matches the uninterrupted run exactly. Plus the fallback
(corrupt + legacy-torn checkpoints skipped through the real trainer
load path), the NaN-burst save-and-abort policy, and the SIGTERM
preemption window (no extra step burned, previous handler chained).

Training runs in single-device subprocesses (``resilience_script.py``)
so the parent pytest process never touches the fragile full-trainer
restore path, and so ``SIGKILL``/``SIGTERM``/env-driven fault plans hit
a real standalone process exactly as they would on a pod.
"""

import json
import os
import shutil
import signal
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from scaling_tpu.resilience import verify_checkpoint

REPO = Path(__file__).resolve().parents[3]
SCRIPT = Path(__file__).resolve().parent / "resilience_script.py"

# per-save ckpt.write hits for this arch: 4 model npz + 4 optimizer npz
WRITES_PER_SAVE = 8


def run_script(tmp_dir: Path, name: str, faults: str = "", timeout: float = 120,
               **spec_extra):
    workdir = tmp_dir / name
    spec = {
        "workdir": str(workdir),
        "steps": 10,
        "save_interval": 3,
        "losses_path": str(tmp_dir / f"{name}_losses.jsonl"),
        "result_path": str(tmp_dir / f"{name}_result.json"),
        **spec_extra,
    }
    spec_file = tmp_dir / f"{name}_spec.json"
    spec_file.write_text(json.dumps(spec))
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    env.pop("XLA_FLAGS", None)  # the script is single-device by design
    if faults:
        env["SCALING_TPU_FAULTS"] = faults
    else:
        env.pop("SCALING_TPU_FAULTS", None)
    p = subprocess.run(
        [sys.executable, str(SCRIPT), str(spec_file)],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=timeout,
    )
    return p, workdir, spec


def read_losses(tmp_dir: Path, name: str) -> dict:
    f = tmp_dir / f"{name}_losses.jsonl"
    out = {}
    if f.is_file():
        for line in f.read_text().splitlines():
            rec = json.loads(line)
            out[rec["step"]] = rec["loss"]
    return out


def read_result(tmp_dir: Path, name: str) -> dict:
    return json.loads((tmp_dir / f"{name}_result.json").read_text())


@pytest.fixture(scope="module")
def baseline(tmp_path_factory):
    """One uninterrupted 10-step run: the golden loss trajectory."""
    tmp = tmp_path_factory.mktemp("resilience_e2e")
    p, workdir, _ = run_script(tmp, "baseline")
    assert p.returncode == 0, p.stdout[-3000:] + p.stderr[-3000:]
    losses = read_losses(tmp, "baseline")
    assert sorted(losses) == list(range(1, 11))
    return tmp, workdir, losses


def test_sigkill_mid_save_then_autoresume_matches_uninterrupted(baseline):
    tmp, _, gold = baseline
    # ---- crash arm: SIGKILL during the 5th file write of the step-6 save
    p, workdir, _ = run_script(
        tmp, "crash", faults=f"ckpt.write=kill@{WRITES_PER_SAVE + 5}"
    )
    assert p.returncode == -signal.SIGKILL, p.stdout[-2000:] + p.stderr[-2000:]
    ckpt = workdir / "ckpt"
    # the interrupted save never became visible: only committed step 3,
    # staging debris for step 6, and `latest` still pointing at step 3
    assert verify_checkpoint(ckpt / "global_step3") == []
    assert not (ckpt / "global_step6").exists()
    assert (ckpt / ".tmp-global_step6").is_dir()  # torn staging dir
    assert (ckpt / "latest").read_text() == "global_step3"
    # crash-arm losses up to the kill match the golden run (determinism)
    crash_losses = read_losses(tmp, "crash")
    for step, loss in crash_losses.items():
        assert loss == gold[step]

    # ---- restart arm: same directory, NO manual cleanup
    p2, workdir2, _ = run_script(
        tmp, "crash", resume=True, restart_budget=1,
    )
    assert p2.returncode == 0, p2.stdout[-3000:] + p2.stderr[-3000:]
    result = read_result(tmp, "crash")
    assert result["resumed_from"] == 3  # newest VALID checkpoint
    assert result["iterations"] == 10
    resumed = read_losses(tmp, "crash")  # same jsonl: crash run + resumed
    np.testing.assert_array_equal(
        np.asarray([resumed[s] for s in range(4, 11)]),
        np.asarray([gold[s] for s in range(4, 11)]),
    )
    # the restart's own saves swept the torn staging dir and re-committed
    assert not (ckpt / ".tmp-global_step6").exists()
    assert verify_checkpoint(ckpt / "global_step6") == []
    assert verify_checkpoint(ckpt / "global_step9") == []
    assert (ckpt / "latest").read_text() == "global_step9"


def test_fallback_skips_corrupt_and_legacy_torn_checkpoints(baseline):
    """Through the REAL trainer load path: a bad-digest manifest (step 9)
    and a manifest-less truncated npz (step 6, the pre-manifest torn-save
    shape) are both skipped; the run resumes from step 3 and reproduces
    the golden trajectory."""
    tmp, golden_workdir, gold = baseline
    workdir = tmp / "fallback"
    shutil.copytree(golden_workdir / "ckpt", workdir / "ckpt")
    ckpt = workdir / "ckpt"
    # step 9: flip bytes under an intact manifest -> bad digest
    f9 = ckpt / "global_step9" / "model_state_layer_0_InputLayer.npz"
    f9.write_bytes(b"\x00" * f9.stat().st_size)
    # step 6: legacy (no manifest) + truncated npz -> load-time BadZipFile
    (ckpt / "global_step6" / "MANIFEST.json").unlink()
    f6 = ckpt / "global_step6" / "model_state_layer_0_InputLayer.npz"
    f6.write_bytes(f6.read_bytes()[: f6.stat().st_size // 3])

    p, _, _ = run_script(tmp, "fallback", resume=True)
    assert p.returncode == 0, p.stdout[-3000:] + p.stderr[-3000:]
    result = read_result(tmp, "fallback")
    assert result["resumed_from"] == 3
    assert result["iterations"] == 10
    resumed = read_losses(tmp, "fallback")
    np.testing.assert_array_equal(
        np.asarray([resumed[s] for s in range(4, 11)]),
        np.asarray([gold[s] for s in range(4, 11)]),
    )
    # the skip reasons were logged, and the rerun healed both steps
    assert "skipping" in (p.stdout + p.stderr)
    assert verify_checkpoint(ckpt / "global_step9") == []


def test_nan_burst_policy_skips_then_saves_and_aborts(tmp_path):
    """step.nan_grads poisons every observed loss from step 5 on; with a
    budget of 2 the trainer tolerates steps 5-6, then saves a resumable
    checkpoint and aborts with the diagnosis at step 7."""
    p, workdir, _ = run_script(
        tmp_path, "nan", faults="step.nan_grads=nan@5x*", nonfinite_budget=2,
    )
    assert p.returncode == 42, p.stdout[-3000:] + p.stderr[-3000:]
    assert "NONFINITE_ABORT" in p.stdout
    assert "consecutive non-finite" in p.stdout + p.stderr
    losses = read_losses(tmp_path, "nan")
    assert sorted(losses) == list(range(1, 8))  # aborted after step 7
    assert all(np.isfinite(losses[s]) for s in range(1, 5))
    assert all(np.isnan(losses[s]) for s in range(5, 8))
    # the save-and-abort left a valid checkpoint at the abort step
    assert verify_checkpoint(workdir / "ckpt" / "global_step7") == []
    assert (workdir / "ckpt" / "latest").read_text() == "global_step7"


def test_sigterm_in_checkpoint_window_exits_without_extra_step(tmp_path):
    """SIGTERM delivered at the top of iteration 4 (the post-save window):
    the pre-step preemption check must save-and-exit WITHOUT burning step
    4, and the previously installed SIGTERM handler must still run."""
    p, workdir, _ = run_script(
        tmp_path, "sigterm", faults="signal.sigterm=sigterm@4",
    )
    assert p.returncode == 0, p.stdout[-3000:] + p.stderr[-3000:]
    result = read_result(tmp_path, "sigterm")
    assert result["iterations"] == 3  # no extra step after the signal
    losses = read_losses(tmp_path, "sigterm")
    assert sorted(losses) == [1, 2, 3]
    assert verify_checkpoint(workdir / "ckpt" / "global_step3") == []
    assert (workdir / "CHAINED").is_file()  # previous handler chained
