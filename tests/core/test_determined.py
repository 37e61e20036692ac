"""Determined glue (reference: core/determined/, core/trainer/trainer.py:
317-553): detection must be a no-op off-cluster, and on-cluster the glue
must poll preemption each step, report metrics, hand finished checkpoints
to Determined storage, and prefer the experiment's latest checkpoint on
restart. The SDK is not installed here, so an injected fake stands in —
the adapter's contract with the trainer hooks is what's under test."""

import contextlib
import sys
import types
from pathlib import Path

import numpy as np
import pytest

from scaling_tpu.determined import DeterminedGlue


class FakeCore:
    """The slice of det.core.Context the glue touches."""

    def __init__(self, preempt_at=None, restore_dir=None):
        self.preempt_calls = 0
        self.preempt_at = preempt_at
        self.reported = []
        self.uploaded = []
        self.restore_dir = restore_dir
        self.exited = False

        core = self

        class _Preempt:
            def should_preempt(self):
                core.preempt_calls += 1
                return (
                    core.preempt_at is not None
                    and core.preempt_calls >= core.preempt_at
                )

        class _Train:
            def report_training_metrics(self, steps_completed, metrics):
                core.reported.append((steps_completed, metrics))

        class _Checkpoint:
            def upload(self, path, metadata):
                core.uploaded.append((Path(path), metadata))

            @contextlib.contextmanager
            def restore_path(self, storage_id):
                yield core.restore_dir

        self.preempt = _Preempt()
        self.train = _Train()
        self.checkpoint = _Checkpoint()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.exited = True
        return False


def fake_sdk(monkeypatch, core, latest_checkpoint=None, on_cluster=True):
    det = types.ModuleType("determined")
    info = (
        types.SimpleNamespace(latest_checkpoint=latest_checkpoint)
        if on_cluster
        else None
    )
    det.get_cluster_info = lambda: info
    det.core = types.SimpleNamespace(init=lambda *a, **k: core)
    monkeypatch.setitem(sys.modules, "determined", det)
    return det


def test_detect_returns_none_without_sdk():
    assert "determined" not in sys.modules  # not installed in this image
    assert DeterminedGlue.detect() is None


def test_detect_returns_none_off_cluster(monkeypatch):
    fake_sdk(monkeypatch, FakeCore(), on_cluster=False)
    assert DeterminedGlue.detect() is None


def test_glue_adapters(monkeypatch, tmp_path):
    core = FakeCore(restore_dir=str(tmp_path / "dl"))
    fake_sdk(monkeypatch, core, latest_checkpoint="uuid-1")
    glue = DeterminedGlue.detect()
    assert glue is not None

    assert glue.should_preempt() is False
    glue.report_metrics({"loss": np.float32(1.5), "note": "skip-me"}, step=3)
    assert core.reported == [(3, {"loss": 1.5})]

    glue.upload_checkpoint(tmp_path / "ckpt", step=7)
    assert core.uploaded == [(tmp_path / "ckpt", {"steps_completed": 7})]

    with glue.latest_checkpoint() as p:
        assert p == tmp_path / "dl"

    glue.close()
    assert core.exited


def test_glue_drives_training_preemption(monkeypatch, tmp_path):
    """Attached to a real trainer on the CPU mesh: preemption polled every
    step stops training early with a durable checkpoint that is handed to
    Determined, and metrics flow to the cluster."""
    from tests.core.test_training.test_training import build_trainer, make_config

    trainer = build_trainer(
        make_config(tmp_path, train_iterations=50, save_interval=None),
        dataset_size=128,
    )
    core = FakeCore(preempt_at=3)
    fake_sdk(monkeypatch, core, latest_checkpoint=None)
    glue = DeterminedGlue.detect()
    glue.attach(trainer)

    trainer.run_training()
    glue.close()

    assert trainer.context.iterations == 3  # stopped at the preempt poll
    assert len(core.uploaded) == 1  # the preemption checkpoint was handed off
    uploaded_dir, meta = core.uploaded[0]
    assert meta == {"steps_completed": 3}
    assert uploaded_dir.is_dir() and list(uploaded_dir.iterdir())
    # step 3 ran to its end and is the step the preemption checkpoint holds:
    # its metrics reach the cluster before the exit (the trainer's contract,
    # _run_training_loop: "the step that just completed is about to be saved
    # ... its metrics must reach the sinks too")
    assert [s for s, _ in core.reported] == [1, 2, 3]
