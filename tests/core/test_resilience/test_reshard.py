"""Elastic resharding (ISSUE 12): mesh-shape-independent checkpoints
restore loss-exact on a different mesh.

- MESH.json rides every commit (inside the staging dir, digested by the
  manifest — the atomic-commit contract covers it);
- the reshard parity matrix: save at dp2 x pp2, restore at dp1 x pp2 /
  dp4 x pp1 / vpp2 -> pp1 — restored param AND optimizer trees are
  bit-equal to the saver's state (pure serialization plus re-slicing,
  no math), and the ``ckpt.reshard`` fault point fires exactly when the
  mesh actually changed;
- a ``run_with_resume`` continuation at the new shape replays the
  saved-shape trajectory (measured drift on this container: the first
  steps after the boundary are BIT-identical, later steps reassociate
  fp32 reductions at the last ulp — same bound family as the pp-parity
  tests in tests/transformer/test_training_pipeline.py);
- ``restore.assemble`` failures: transient -> retried by the bounded-
  retry load layer (resume from the NEWEST step), persistent -> the
  candidate is demoted and restore falls back to the newest VALID
  checkpoint instead of aborting;
- legacy checkpoints without MESH.json restore at the same shape
  (backward compat pinned), while an unparseable MESH.json is corrupt,
  never silently legacy.

Every full-trainer test is subprocess-isolated with the compile cache
off (tests/core/subproc.py): the restore path re-jits the same step a
warm persistent cache mis-executes on this container (the known PR 3
zone), and an abort must cost one test, not the suite. Pure-policy
units run in-process.
"""

import json
from pathlib import Path

import numpy as np
import pytest

from scaling_tpu.resilience import (
    CheckpointCorruptionError,
    FaultPlan,
    ReshardError,
    build_mesh_meta,
    get_fault_plan,
    mesh_matches,
    read_mesh_meta,
    rescale_consumed_samples,
    reshard_plan,
    set_fault_plan,
    signature_label,
    verify_checkpoint,
    write_mesh_meta,
)
from tests.core.subproc import run_in_subprocess


# ------------------------------------------------------------ pure units
def test_topology_signature_and_labels():
    meta = build_mesh_meta(
        {"world_size": 4, "pipe_parallel_size": 2, "data_parallel_size": 2,
         "num_hosts": 2},
        {"k": {"shape": [4, 4], "dtype": "float32",
               "partition_spec": [None, "model"]}},
    )
    assert mesh_matches(meta, {"world_size": 4, "pipe_parallel_size": 2,
                               "data_parallel_size": 2, "num_hosts": 2})
    # a host-count change alone is a mesh transition (per-host shard
    # dirs had a peer set)
    assert not mesh_matches(meta, {"world_size": 4, "pipe_parallel_size": 2,
                                   "data_parallel_size": 2, "num_hosts": 1})
    assert signature_label(meta["topology"]) == (
        "world4·pp2·dp2·cp1·mp1·hosts2"
    )


def test_reshard_plan_decides_and_preflights():
    meta = build_mesh_meta(
        {"world_size": 2, "data_parallel_size": 2},
        {"k": {"shape": [8, 4], "dtype": "float32", "partition_spec": []}},
    )
    # legacy (no MESH.json) and matching signatures: no reshard
    assert reshard_plan(None, {"world_size": 1}) is None
    assert reshard_plan(meta, {"world_size": 2, "data_parallel_size": 2}) is None
    plan = reshard_plan(meta, {"world_size": 1}, {"k": {"shape": [8, 4]}})
    assert plan.needed and plan.event_fields()["saved_world"] == 2
    # a GLOBAL-shape disagreement is a different model, never a reshard
    with pytest.raises(ReshardError, match="different model"):
        reshard_plan(meta, {"world_size": 1}, {"k": {"shape": [8, 8]}})


def test_rescale_consumed_samples_contract():
    # the count is mesh-independent; only the sampler grid constrains it
    assert rescale_consumed_samples(
        48, micro_batch_size=2, data_parallel_size=4) == 48
    assert rescale_consumed_samples(
        48, micro_batch_size=2, data_parallel_size=1) == 48
    with pytest.raises(ReshardError, match="not divisible"):
        rescale_consumed_samples(48, micro_batch_size=5, data_parallel_size=2)
    # the EVAL cursor advances by the old mbs*dp (not gbs-aligned):
    # floor mode realigns instead of killing a viable downsize
    assert rescale_consumed_samples(
        8, micro_batch_size=1, data_parallel_size=6,
        what="consumed_eval_samples", on_misaligned="floor") == 6


def test_unparseable_mesh_json_is_corrupt_not_legacy(tmp_path):
    assert read_mesh_meta(tmp_path) is None  # absent == legacy
    (tmp_path / "MESH.json").write_text("{not json")
    with pytest.raises(CheckpointCorruptionError):
        read_mesh_meta(tmp_path)
    write_mesh_meta(tmp_path, {"schema_version": 99})
    with pytest.raises(CheckpointCorruptionError, match="newer"):
        read_mesh_meta(tmp_path)


# --------------------------------------------------- full-trainer helpers
@pytest.fixture(scope="module")
def data_prefix(tmp_path_factory):
    from scaling_tpu.data.memory_map import MemoryMapDatasetBuilder

    prefix = tmp_path_factory.mktemp("reshard_data") / "data"
    rng = np.random.default_rng(29)
    with MemoryMapDatasetBuilder(prefix, dtype=np.uint16) as builder:
        for _ in range(64):
            doc = rng.integers(1, 96, size=rng.integers(8, 64))
            builder.add(np.append(doc, 0).astype(np.uint16))
    return prefix


@pytest.fixture(scope="module")
def dp2pp2_save(tmp_path_factory, data_prefix):
    """The matrix's source checkpoint: 3 steps at dp2 x pp2 (world 4)."""
    from tests.transformer.test_training import (
        build_capturing_trainer,
        train_capture,
    )
    from tests.transformer.test_training_pipeline import make_pp_config

    tmp = tmp_path_factory.mktemp("dp2pp2")
    cfg = make_pp_config(tmp, data_prefix, pp=2, dp=2, gas=2,
                         train_iterations=3, save_interval=3)
    t = build_capturing_trainer(cfg)
    train_capture(t, 3)
    return cfg, t


def _flat_view(trainer):
    import jax

    from scaling_tpu.nn.param import ParamMeta

    view = trainer.module.ckpt_view(trainer.params)
    metas = trainer.module.ckpt_metas()
    m_leaves = jax.tree.leaves(
        metas, is_leaf=lambda x: isinstance(x, ParamMeta)
    )
    return {m.key: np.asarray(p)
            for m, p in zip(m_leaves, jax.tree.leaves(view))}


def _flat_opt_view(trainer):
    import jax

    out = {}
    for field in ("master", "exp_avg", "exp_avg_sq"):
        tree = trainer.module.ckpt_view(getattr(trainer.opt_state, field))
        for i, leaf in enumerate(jax.tree.leaves(tree)):
            if getattr(leaf, "size", 0):
                out[f"{field}.{i}"] = np.asarray(leaf)
    return out


def _assert_restores_bit_equal(saver, cfg_load):
    from tests.transformer.test_training import build_capturing_trainer

    before = get_fault_plan().hits("ckpt.reshard")
    t2 = build_capturing_trainer(cfg_load, load=True)
    assert t2.context.iterations == 3
    # the mesh actually changed, so the reshard path must have engaged
    assert get_fault_plan().hits("ckpt.reshard") == before + 1
    saved_p, loaded_p = _flat_view(saver), _flat_view(t2)
    assert set(saved_p) == set(loaded_p)
    for k in saved_p:
        np.testing.assert_array_equal(saved_p[k], loaded_p[k], err_msg=k)
    saved_o, loaded_o = _flat_opt_view(saver), _flat_opt_view(t2)
    assert set(saved_o) == set(loaded_o) and saved_o
    for k in saved_o:
        np.testing.assert_array_equal(saved_o[k], loaded_o[k], err_msg=k)
    return t2


# ------------------------------------------------- reshard parity matrix
@run_in_subprocess()
def test_reshard_dp2pp2_to_dp1pp2_bit_equal(request, tmp_path, data_prefix,
                                            dp2pp2_save):
    """The fast matrix representative, plus the commit contract:
    MESH.json is a manifest-listed, digested artifact of the atomic
    commit — and a dp2 x pp2 checkpoint restores bit-equal at dp1 x pp2
    and keeps training."""
    from tests.transformer.test_training_pipeline import make_pp_config

    cfg, saver = dp2pp2_save
    step_dir = Path(cfg.trainer.save_dir) / "global_step3"
    meta = read_mesh_meta(step_dir)
    sig = meta["topology"]
    assert (sig["world_size"], sig["pipe_parallel_size"],
            sig["data_parallel_size"]) == (4, 2, 2)
    assert meta["params"] and all(
        rec["shape"] for rec in meta["params"].values()
    )
    manifest = json.loads((step_dir / "MANIFEST.json").read_text())
    assert "MESH.json" in manifest["files"]
    assert verify_checkpoint(step_dir) == []

    cfg_load = make_pp_config(
        tmp_path, data_prefix, pp=2, dp=1, gas=4, train_iterations=6,
        save_interval=100, load_dir=Path(cfg.trainer.save_dir),
    )
    t2 = _assert_restores_bit_equal(saver, cfg_load)
    out = t2.train_step()  # and training continues at the new shape
    assert np.isfinite(float(out.loss))


@pytest.mark.slow
@run_in_subprocess(timeout=420)
def test_reshard_dp2pp2_to_dp4pp1_bit_equal(request, tmp_path, data_prefix,
                                            dp2pp2_save):
    from tests.transformer.test_training_pipeline import make_pp_config

    cfg, saver = dp2pp2_save
    cfg_load = make_pp_config(
        tmp_path, data_prefix, pp=1, dp=4, gas=1, train_iterations=6,
        save_interval=100, load_dir=Path(cfg.trainer.save_dir),
    )
    _assert_restores_bit_equal(saver, cfg_load)


@pytest.mark.slow
@run_in_subprocess(timeout=420)
def test_reshard_orbax_dp2pp2_cross_shape_bit_equal(request, tmp_path,
                                                    data_prefix):
    """The orbax backend's arm of the parity matrix: a dp2 x pp2 orbax
    checkpoint restores bit-equal at dp1 x pp2 AND dp4 x pp1. The
    reshard decision (MESH.json, preflight, ``ckpt.reshard`` fault
    point) is shared with the npz path — only the leaf I/O differs
    (orbax re-shards natively from tensorstore) — so the same
    ``_assert_restores_bit_equal`` bar applies."""
    pytest.importorskip("orbax.checkpoint")
    from tests.transformer.test_training import (
        build_capturing_trainer,
        train_capture,
    )
    from tests.transformer.test_training_pipeline import make_pp_config

    def orbax_pp_config(path, **kw):
        cfg = make_pp_config(path, data_prefix, **kw)
        d = cfg.model_dump(mode="json")
        d["trainer"]["checkpoint_backend"] = "orbax"
        return type(cfg).from_dict(d)

    cfg = orbax_pp_config(tmp_path / "save", pp=2, dp=2, gas=2,
                          train_iterations=3, save_interval=3)
    saver = build_capturing_trainer(cfg)
    train_capture(saver, 3)
    step_dir = Path(cfg.trainer.save_dir) / "global_step3"
    assert (step_dir / "orbax" / "model").is_dir()
    assert read_mesh_meta(step_dir) is not None

    for label, pp, dp, gas in (("dp1pp2", 2, 1, 4), ("dp4pp1", 1, 4, 1)):
        cfg_load = orbax_pp_config(
            tmp_path / f"load_{label}", pp=pp, dp=dp, gas=gas,
            train_iterations=6, save_interval=100,
            load_dir=Path(cfg.trainer.save_dir),
        )
        t2 = _assert_restores_bit_equal(saver, cfg_load)
        out = t2.train_step()
        assert np.isfinite(float(out.loss))


@pytest.mark.slow
@run_in_subprocess(timeout=420)
def test_reshard_vpp2_to_pp1_bit_equal(request, tmp_path, data_prefix):
    """The 3-dim (pp, v, lpv) interleaved stacking reshards too: the
    round-robin chunk layout must invert exactly for params AND all
    three optimizer trees, or layer j's moments land on layer k."""
    from tests.transformer.test_training import (
        build_capturing_trainer,
        train_capture,
    )
    from tests.transformer.test_training_pipeline import make_pp_config

    cfg = make_pp_config(tmp_path / "save", data_prefix, pp=2, vpp=2,
                         gas=4, train_iterations=3, save_interval=3,
                         num_layers=4)
    t = build_capturing_trainer(cfg)
    train_capture(t, 3)
    cfg_load = make_pp_config(
        tmp_path / "load", data_prefix, pp=1, gas=4, train_iterations=6,
        save_interval=100, num_layers=4,
        load_dir=Path(cfg.trainer.save_dir),
    )
    _assert_restores_bit_equal(t, cfg_load)


@run_in_subprocess()
def test_run_with_resume_continues_loss_exact_at_new_shape(
    request, tmp_path, data_prefix
):
    """dp2 -> dp1 continuation through the real ``run_with_resume``
    wrapper: the dp2 run's steps 4-6 vs the dp1 continuation resumed
    from the step-3 checkpoint, same global batch (gas doubles so the
    stream consumes identical contiguous sample blocks per step).

    Bound: step 4 is BIT-identical (restored state is bit-equal and the
    first step's math reassociates nothing observable); later steps
    drift at the last ulp only (measured 1e-7 relative on this exact
    setup) — rtol 1e-6 leaves headroom while a real reshard bug (wrong
    leaf re-sliced, samples skipped/repeated) lands orders of magnitude
    off."""
    from scaling_tpu.resilience import run_with_resume
    from tests.transformer.test_training import (
        build_capturing_trainer,
        make_config,
        train_capture,
    )

    cfg_a = make_config(tmp_path / "a", data_prefix, dp=2, gas=2,
                        train_iterations=6, save_interval=3)
    ta = build_capturing_trainer(cfg_a)
    losses_a = train_capture(ta, 6)

    ckpt = Path(cfg_a.trainer.save_dir)
    (ckpt / "latest").write_text("global_step3")  # replay from step 3

    captured = []

    def record(trainer, output, metrics):
        captured.append((trainer.context.iterations, output.loss))
        return metrics

    def factory():
        cfg_b = make_config(
            tmp_path / "b", data_prefix, dp=1, gas=4, train_iterations=6,
            save_interval=100, load_dir=ckpt,
        )
        return build_capturing_trainer(cfg_b, load=True)

    trainer = run_with_resume(factory, restart_budget=1,
                              log_metrics_fn=record)
    assert trainer.context.iterations == 6
    assert [s for s, _ in captured] == [4, 5, 6]
    cont = np.asarray([l for _, l in captured], np.float32)
    gold = np.asarray(losses_a[3:], np.float32)
    np.testing.assert_array_equal(gold[0], cont[0])  # first step: bit-exact
    np.testing.assert_allclose(cont, gold, rtol=1e-6, atol=0)


# ------------------------------------- fault points + backward compat
@run_in_subprocess()
def test_restore_faults_and_legacy_compat(request, tmp_path, data_prefix):
    """One cheap single-device run leaving two committed checkpoints
    (steps 3 and 6) drives all four restore-robustness contracts:

    1. a TRANSIENT ``restore.assemble`` failure is retried by the
       bounded-retry load layer — resume still lands on step 6;
    2. a PERSISTENT one (one per attempt, io_retry_attempts=3) demotes
       the newest candidate — restore falls back to the valid step 3;
    3. ``iter_global_leaves`` reconstructs every recorded global shape
       with no module and no mesh, through the same fault point;
    4. stripping MESH.json (as a pre-elastic writer's checkpoint) keeps
       restoring at the same shape with the reshard path disengaged.
    """
    import shutil

    from tests.transformer.test_training import (
        build_capturing_trainer,
        make_config,
        train_capture,
    )

    cfg = make_config(tmp_path / "src", data_prefix, train_iterations=6,
                      save_interval=3)
    t = build_capturing_trainer(cfg)
    train_capture(t, 6)
    src = Path(cfg.trainer.save_dir)

    # 1. transient: retried, newest step restored
    set_fault_plan(FaultPlan("restore.assemble=fail@1"))
    cfg1 = make_config(tmp_path / "r1", data_prefix, train_iterations=9,
                       save_interval=100, load_dir=src)
    t1 = build_capturing_trainer(cfg1, load=True)
    assert t1.context.iterations == 6
    assert get_fault_plan().hits("restore.assemble") > 1

    # 2. persistent: newest demoted, fallback to the newest VALID step
    set_fault_plan(FaultPlan("restore.assemble=fail@1x3"))
    cfg2 = make_config(tmp_path / "r2", data_prefix, train_iterations=9,
                       save_interval=100, load_dir=src)
    t2 = build_capturing_trainer(cfg2, load=True)
    assert t2.context.iterations == 3

    # 3. the mesh-free streaming reader covers the recorded tree
    from scaling_tpu.resilience import iter_global_leaves

    step_dir = src / "global_step6"
    meta = read_mesh_meta(step_dir)
    set_fault_plan(FaultPlan("restore.assemble=fail@1"))  # retried inside
    seen = {}
    for fname, entry, arr in iter_global_leaves(step_dir):
        seen[f"{fname}:{entry}"] = arr.shape
    assert len(seen) >= len(meta["params"])
    shapes = set(map(tuple, seen.values()))
    for key, rec in meta["params"].items():
        assert tuple(rec["shape"]) in shapes, key
    set_fault_plan(FaultPlan(""))

    # 4. legacy: no MESH.json -> same-shape restore, reshard disengaged
    legacy = tmp_path / "legacy"
    shutil.copytree(src, legacy)
    for sd in legacy.glob("global_step*"):
        (sd / "MESH.json").unlink()
        mf = sd / "MANIFEST.json"
        manifest = json.loads(mf.read_text())
        del manifest["files"]["MESH.json"]
        mf.write_text(json.dumps(manifest, indent=1, sort_keys=True))
        assert verify_checkpoint(sd) == []
    before = get_fault_plan().hits("ckpt.reshard")
    cfg4 = make_config(tmp_path / "r4", data_prefix, train_iterations=9,
                       save_interval=100, load_dir=legacy)
    t4 = build_capturing_trainer(cfg4, load=True)
    assert t4.context.iterations == 6
    assert get_fault_plan().hits("ckpt.reshard") == before
    out = t4.train_step()
    assert np.isfinite(float(out.loss))
