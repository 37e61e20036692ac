"""The MLP example's lifecycle (``test_training.py``, whose builders these
use) under two more features: a prefetching dataloader gives the synchronous
one's stream and resumes exactly; ZeRO-3 (FSDP) gives ZeRO-1's losses and
resumes exactly; a ZeRO stage that is not built is refused. The tests that
LOAD a checkpoint run subprocess-isolated, as there."""

import numpy as np

from tests.core.subproc import run_in_subprocess

from .test_training import build_trainer, make_config, run_steps


@run_in_subprocess()
def test_prefetch_matches_synchronous(request, tmp_path, devices):
    """dataloader_prefetch_factor overlaps batch assembly with the device
    step without changing the stream: identical losses, and resume from a
    mid-run checkpoint stays exact (prefetched-but-unconsumed batches are
    rebuilt from consumed_samples)."""
    def with_prefetch(cfg, depth):
        d = cfg.model_dump(mode="json")
        d["trainer"]["dataloader_prefetch_factor"] = depth
        return type(cfg).from_dict(d)

    cfg_sync = make_config(tmp_path / "sync", train_iterations=6, save_interval=3)
    cfg_pre = with_prefetch(
        make_config(tmp_path / "pre", train_iterations=6, save_interval=3), 3
    )
    l_sync = run_steps(build_trainer(cfg_sync), 6)
    t_pre = build_trainer(cfg_pre)
    l_pre = run_steps(t_pre, 6)
    np.testing.assert_allclose(np.asarray(l_sync), np.asarray(l_pre), rtol=1e-6)

    cfg_resume = with_prefetch(
        make_config(tmp_path / "resume", train_iterations=6,
                    load_dir=tmp_path / "pre" / "ckpt"), 3
    )
    # the latest checkpoint is step 6; point at step 3 to replay 4-6
    (tmp_path / "pre" / "ckpt" / "latest").write_text("global_step3")
    t_resume = build_trainer(cfg_resume)
    assert t_resume.context.iterations == 3
    l_resumed = run_steps(t_resume, 3)
    np.testing.assert_allclose(
        np.asarray(l_pre[3:]), np.asarray(l_resumed), rtol=1e-6
    )


@run_in_subprocess()
def test_zero3_fsdp_matches_zero1(request, tmp_path, devices):
    """ZeRO stage 3 (FSDP param sharding over the data axis — beyond the
    reference's stage 1): identical training math (GSPMD all-gathers per
    use, reduce-scatters grads), params ACTUALLY sharded (per-device shard
    strictly smaller than the logical array), and loss-exact resume
    through the layout-independent checkpoint."""
    cfg1 = make_config(tmp_path / "z1", dp=2, zero=True, train_iterations=5,
                       save_interval=100)
    cfg3 = make_config(tmp_path / "z3", dp=2, zero=True, train_iterations=5,
                       save_interval=3)
    d = cfg3.model_dump(mode="json")
    d["optimizer"]["zero_stage"] = 3
    cfg3 = type(cfg3).from_dict(d)

    l1 = run_steps(build_trainer(cfg1), 5)
    t3 = build_trainer(cfg3)
    sharded = 0
    for key, p, _ in t3.module.named_parameters(t3.params):
        shard = p.addressable_shards[0].data
        if shard.shape != p.shape:
            sharded += 1
    assert sharded >= 4, "stage 3 left the params unsharded"
    l3 = run_steps(t3, 5)
    np.testing.assert_allclose(np.asarray(l1), np.asarray(l3), rtol=1e-5)

    # resume the stage-3 run from its own (unsharded-on-disk) checkpoint
    resume_cfg = make_config(tmp_path / "z3", dp=2, zero=True,
                             train_iterations=5, save_interval=100,
                             load_dir=tmp_path / "z3" / "ckpt")
    d = resume_cfg.model_dump(mode="json")
    d["optimizer"]["zero_stage"] = 3
    resume_cfg = type(resume_cfg).from_dict(d)
    resumed = build_trainer(resume_cfg)
    assert resumed.context.iterations == 3
    np.testing.assert_array_equal(
        np.asarray(l3[3:]), np.asarray(run_steps(resumed, 2))
    )


def test_zero_stage2_rejected():
    import pytest as _pytest

    from scaling_tpu.optimizer import OptimizerConfig

    with _pytest.raises(Exception, match="implicit"):
        OptimizerConfig.from_dict({"zero": True, "zero_stage": 2})
    # a stage request without zero enabled must not silently no-op
    with _pytest.raises(Exception, match="requires zero"):
        OptimizerConfig.from_dict({"zero": False, "zero_stage": 3})
