"""Fake pod host for the multi-host supervision e2e tests.

NOT collected by pytest. Launched by the supervised runner as
``python -u -m tests.core.test_resilience.multihost_script
--payload=<b64>`` — one process per fake host, each a realistic
standalone single-device trainer (the same MLP as
``resilience_script.py``) that joins the control plane the supervisor
described in the environment (``SCALING_TPU_CONTROL_DIR`` /
``SCALING_TPU_HOST_ID`` / ``SCALING_TPU_NUM_HOSTS``).

Every fake host runs the SAME seed-42 single-device program, so the pod
is N replicas of one deterministic trajectory: "loss-exact resume" is
checkable per host against one uninterrupted golden run, and the
per-step control-plane barrier emulates the lockstep a real SPMD
collective would enforce. Checkpoints are per-host shard dirs
(``<workdir>/host<K>/ckpt``) — the commit barrier is what keeps their
``latest`` pointers moving in unison.

Deliberately NO persistent compile cache (cache read-back mis-executes
on the known-bad container — see tests/conftest.py) and NO
``initialize_distributed`` (the fake hosts share no jax world; the
control plane is the only cross-host channel, which is exactly what the
supervision layer must survive on when collectives are hung).

Payload keys: ``workdir``, ``steps``, ``save_interval``,
``barrier_timeout`` (seconds), and optionally ``step_delay`` (seconds
slept after each step's loss is on disk: a drill that signals from
outside needs the run to outlast its own poll; the losses are the same).

Exit codes: 0 clean (finished or coordinated preemption), 75 aborted by
the supervisor / barrier timeout, 42 NonFiniteLossError. SIGKILL shows
as -9 to the supervisor.
"""

import json
import os
import sys
import time
from pathlib import Path

REPO = Path(__file__).resolve().parents[3]


def main() -> int:
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    # single-device even when launched from an 8-virtual-device parent
    import re as _re

    os.environ["XLA_FLAGS"] = _re.sub(
        r"--xla_force_host_platform_device_count=\d+", "",
        os.environ.get("XLA_FLAGS", ""),
    ).strip()
    sys.path.insert(0, str(REPO))

    import jax

    jax.config.update("jax_platforms", "cpu")

    from examples.mlp_example.config import MLPConfig
    from examples.mlp_example.context import MLPContext
    from examples.mlp_example.data import MNISTDataset
    from examples.mlp_example.model import init_model, init_optimizer, loss_function
    from examples.mlp_example.train import batch_to_model_input
    from scaling_tpu.resilience import (
        BarrierTimeout,
        JobAborted,
        NonFiniteLossError,
        controlplane_from_env,
    )
    from scaling_tpu.runner import LaunchConfig
    from scaling_tpu.topology import Topology
    from scaling_tpu.trainer import BaseTrainer

    spec = LaunchConfig.from_launcher_args().payload
    host_id = int(os.environ.get("SCALING_TPU_HOST_ID", "0"))
    epoch = int(os.environ.get("SCALING_TPU_COORD_EPOCH", "-1"))
    base = Path(spec["workdir"])
    # the workdir need not pre-exist (and need not contain the control
    # dir): first run on a fresh machine creates it
    base.mkdir(parents=True, exist_ok=True)
    ckpt_dir = base / f"host{host_id}" / "ckpt"
    losses_path = base / f"host{host_id}_losses.jsonl"
    result_path = base / f"host{host_id}_result.json"

    # heartbeat implies drain-safe: the supervisor gates capacity drains
    # on heartbeat coverage, so a SIGTERM may arrive any time after the
    # first heartbeat — arm a handler BEFORE announcing liveness (the
    # trainer's own handler, installed after the slow build, chains to
    # this one and adopts anything it caught)
    import signal as _signal

    early_term = {"hit": False}
    _signal.signal(
        _signal.SIGTERM,
        lambda signum, frame: early_term.__setitem__("hit", True),
    )

    cp = controlplane_from_env()
    if cp is not None:
        # visible to the supervisor before the slow part (trainer build +
        # cold jit compile) starts
        cp.heartbeat(0, status="starting")

    config = MLPConfig.from_dict({
        "topology": {
            "model_parallel_size": 1,
            "pipe_parallel_size": 1,
            "data_parallel_size": 1,
            "micro_batch_size": 32,
            "gradient_accumulation_steps": 1,
        },
        "optimizer": {"gradient_clipping": 1.0},
        "learning_rate_scheduler": {
            "learning_rate": 0.01,
            "learning_rate_decay_iters": 100,
        },
        "architecture": {"n_hidden_layers": 2, "hidden_dim": 64},
        "trainer": {
            "train_iterations": spec["steps"],
            "seed": 42,
            "save_dir": str(ckpt_dir),
            "save_interval": spec["save_interval"],
            # always point load at save: a relaunched epoch resumes from
            # the newest valid checkpoint, a first launch starts fresh
            "load_dir": str(ckpt_dir),
            "assert_checkpoint_loaded": False,
            "delete_past_optimizer_states": False,
        },
        "logger": {"log_dir": None},
    })
    topology = Topology(config.topology)
    context = MLPContext(config=config, topology=topology)
    module = init_model(config, topology)
    optimizer = init_optimizer(config, module, topology)
    dataset = MNISTDataset(train=True, seed=config.trainer.seed)
    dataset.xs = dataset.xs[:512]
    dataset.ys = dataset.ys[:512]
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
    trainer.install_preemption_handler()
    if early_term["hit"]:
        # a drain landed during the build window: exit at the first
        # boundary exactly as if it arrived one instant later
        trainer._preempted = True
    if cp is not None:
        trainer.attach_control_plane(
            cp, barrier_timeout_s=float(spec.get("barrier_timeout", 30.0))
        )
    trainer.initialize(load_checkpoint=True)
    resumed_from = trainer.context.iterations
    step_delay = float(spec.get("step_delay", 0.0))

    def record_loss(_trainer, output, metrics):
        with open(losses_path, "a") as f:
            f.write(json.dumps({
                "step": _trainer.context.iterations, "loss": output.loss,
            }) + "\n")
            f.flush()
            os.fsync(f.fileno())
        time.sleep(step_delay)
        return metrics

    try:
        trainer.run_training(log_metrics_fn=record_loss)
    except (JobAborted, BarrierTimeout) as e:
        print(f"HOST_ABORTED host={host_id}: {type(e).__name__}: {e}")
        return 75
    except NonFiniteLossError as e:
        print(f"NONFINITE_ABORT host={host_id}: {e}")
        return 42
    result_path.write_text(json.dumps({
        "host": host_id,
        "epoch": epoch,
        "iterations": trainer.context.iterations,
        "resumed_from": resumed_from,
        "preempted": trainer._preempted,
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
