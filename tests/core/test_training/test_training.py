"""Full-lifecycle training tests on the MLP example (the reference's
``tests/core/test_training/test_training.py`` pattern): train N steps saving
mid-run, relaunch from the checkpoint, and assert the losses of the
remaining steps match EXACTLY.

Every test here that LOADS a checkpoint runs subprocess-isolated
(``run_in_subprocess``): on constrained hosts the 8-virtual-device XLA
CPU restore path can hard-abort the whole pytest process (known
container abort, ISSUE 3 satellite) — isolation turns that into an
ordinary failure so the remaining suite still reports. Prefetch and ZeRO-3
through the same lifecycle: ``test_training_stream_and_zero3.py``."""


import numpy as np
import pytest

from tests.core.subproc import run_in_subprocess

from examples.mlp_example.config import MLPConfig
from examples.mlp_example.context import MLPContext
from examples.mlp_example.data import MNISTDataset
from examples.mlp_example.model import init_model, init_optimizer, loss_function
from examples.mlp_example.train import batch_to_model_input
from scaling_tpu.topology import Topology
from scaling_tpu.trainer import BaseTrainer


def make_config(tmp_path, dp=1, mbs=32, gas=1, train_iterations=10, save_interval=6,
                load_dir=None, zero=False, loss_scaler=False):
    return MLPConfig.from_dict(
        {
            "topology": {
                "model_parallel_size": 1,
                "pipe_parallel_size": 1,
                "data_parallel_size": dp,
                "micro_batch_size": mbs,
                "gradient_accumulation_steps": gas,
            },
            "optimizer": {
                "gradient_clipping": 1.0,
                "zero": zero,
                "loss_scaler": {"enable": loss_scaler},
            },
            "learning_rate_scheduler": {
                "learning_rate": 0.01,
                "learning_rate_decay_iters": 100,
            },
            "architecture": {"n_hidden_layers": 2, "hidden_dim": 64},
            "trainer": {
                "train_iterations": train_iterations,
                "seed": 42,
                "save_dir": str(tmp_path / "ckpt"),
                "save_interval": save_interval,
                "load_dir": str(load_dir) if load_dir else None,
                "assert_checkpoint_loaded": load_dir is not None,
                "delete_past_optimizer_states": False,
            },
            "logger": {"log_dir": None},
        }
    )


def build_trainer(config, dataset_size=512):
    topology = Topology(config.topology)
    context = MLPContext(config=config, topology=topology)
    module = init_model(config, topology)
    optimizer = init_optimizer(config, module, topology)
    dataset = MNISTDataset(train=True, seed=config.trainer.seed)
    # shrink dataset for test speed
    dataset.xs = dataset.xs[:dataset_size]
    dataset.ys = dataset.ys[:dataset_size]
    dataset.set_seed(config.trainer.seed)
    trainer = BaseTrainer(
        config=config.trainer,
        context=context,
        parallel_module=module,
        optimizer=optimizer,
        loss_function=loss_function,
        dataset=dataset,
        batch_to_model_input=batch_to_model_input,
    )
    trainer.initialize(load_checkpoint=config.trainer.load_dir is not None)
    return trainer


def run_steps(trainer, n):
    """Run n steps through the trainer loop (saves at intervals), collect losses."""
    losses = []
    target = trainer.context.iterations + n
    saved_iters = trainer.config.train_iterations
    object.__setattr__(trainer.config, "train_iterations", target)

    def collect(_trainer, output, metrics):
        losses.append(output.loss)
        return metrics

    trainer.run_training(log_metrics_fn=collect)
    object.__setattr__(trainer.config, "train_iterations", saved_iters)
    return losses


@pytest.mark.parametrize("dp,gas,zero,loss_scaler", [
    (1, 1, False, False),
    pytest.param(2, 2, False, False, marks=pytest.mark.slow),
    (2, 1, True, False),
    (1, 1, False, True),
])
@run_in_subprocess()
def test_checkpoint_resume_loss_exactness(request, tmp_path, devices, dp, gas, zero, loss_scaler):
    cfg = make_config(tmp_path, dp=dp, gas=gas, zero=zero, loss_scaler=loss_scaler)
    trainer = build_trainer(cfg)
    losses = run_steps(trainer, 10)
    # a checkpoint was written at step 6
    resume_cfg = make_config(
        tmp_path, dp=dp, gas=gas, zero=zero, loss_scaler=loss_scaler,
        load_dir=tmp_path / "ckpt",
    )
    resumed = build_trainer(resume_cfg)
    assert resumed.context.iterations == 6
    resumed_losses = run_steps(resumed, 4)
    np.testing.assert_array_equal(np.asarray(losses[6:]), np.asarray(resumed_losses))


@pytest.mark.slow
def test_training_descends_across_dp_layouts(tmp_path, devices):
    """Both dp=1 and dp=2 layouts train successfully (data order differs
    between layouts by design — DP striding — so curves aren't comparable
    point-wise; exact-parity coverage is test_zero_matches_nonzero_losses and
    the TP mesh-parity tests)."""
    cfg1 = make_config(tmp_path / "a", dp=1, mbs=64, train_iterations=5)
    cfg2 = make_config(tmp_path / "b", dp=2, mbs=32, train_iterations=5)
    l1 = run_steps(build_trainer(cfg1), 5)
    l2 = run_steps(build_trainer(cfg2), 5)
    assert l1[0] > l1[-1]
    assert l2[0] > l2[-1]


@pytest.mark.slow
def test_zero_matches_nonzero_losses(tmp_path, devices):
    cfg_a = make_config(tmp_path / "a", dp=2, zero=False, train_iterations=5)
    cfg_b = make_config(tmp_path / "b", dp=2, zero=True, train_iterations=5)
    la = run_steps(build_trainer(cfg_a), 5)
    lb = run_steps(build_trainer(cfg_b), 5)
    np.testing.assert_allclose(np.asarray(la), np.asarray(lb), rtol=1e-5)


def test_checkpoint_layout(tmp_path, devices):
    cfg = make_config(tmp_path, train_iterations=6, save_interval=6)
    trainer = build_trainer(cfg)
    run_steps(trainer, 6)
    step_dir = tmp_path / "ckpt" / "global_step6"
    assert (tmp_path / "ckpt" / "latest").read_text() == "global_step6"
    model_files = sorted(p.name for p in step_dir.glob("model_state_layer_*.npz"))
    assert model_files == [
        "model_state_layer_0_InputLayer.npz",
        "model_state_layer_1_HiddenLayer.npz",
        "model_state_layer_2_HiddenLayer.npz",
        "model_state_layer_3_HeadLayer.npz",
    ]
    assert (step_dir / "context.json").is_file()
    assert (step_dir / "optimizer_state.json").is_file()
    assert list(step_dir.glob("optimizer_state_layer_*.npz"))


@pytest.mark.slow
@run_in_subprocess(timeout=420)
def test_async_checkpoint_resume_matches_sync(request, tmp_path, devices):
    """save_checkpoint_async produces byte-equivalent checkpoints: resume
    from an async save reproduces the sync-save training trajectory."""
    cfg_sync = make_config(tmp_path / "sync", train_iterations=6, save_interval=3)
    cfg_async = make_config(tmp_path / "async", train_iterations=6, save_interval=3)
    d = cfg_async.model_dump(mode="json")
    d["trainer"]["save_checkpoint_async"] = True
    cfg_async = type(cfg_async).from_dict(d)

    l_sync = run_steps(build_trainer(cfg_sync), 6)
    t_async = build_trainer(cfg_async)
    l_async = run_steps(t_async, 6)
    np.testing.assert_allclose(np.asarray(l_sync), np.asarray(l_async), rtol=1e-6)
    # run_training waited for the writer: all files of the last save exist
    step_dir = tmp_path / "async" / "ckpt" / "global_step6"
    assert (tmp_path / "async" / "ckpt" / "latest").read_text() == "global_step6"
    assert list(step_dir.glob("model_state_layer_*.npz"))
    assert list(step_dir.glob("optimizer_state_layer_*.npz"))

    # resume each and confirm identical continued losses
    r_sync = build_trainer(make_config(
        tmp_path / "rs", train_iterations=9, load_dir=tmp_path / "sync" / "ckpt"))
    r_async = build_trainer(make_config(
        tmp_path / "ra", train_iterations=9, load_dir=tmp_path / "async" / "ckpt"))
    np.testing.assert_allclose(
        np.asarray(run_steps(r_sync, 3)), np.asarray(run_steps(r_async, 3)), rtol=1e-6
    )
