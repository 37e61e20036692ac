"""Pipeline-parallel transformer training: pp>1 loss parity with pp=1 and
checkpoint interchange across pipe layouts (reference:
tests/core/test_training/test_training.py grid with pp=2,
partitioned_module.py layout-independent checkpoints). What a pipelined step
costs (FLOPs, memory, the bubble the analyzer reports):
``test_training_pipeline_cost.py``."""

from pathlib import Path

import numpy as np
import pytest

from scaling_tpu.data.memory_map import MemoryMapDatasetBuilder

from .test_training import build_capturing_trainer, make_config, train_capture


@pytest.fixture(scope="module")
def data_prefix(tmp_path_factory):
    prefix = tmp_path_factory.mktemp("dataset") / "data"
    rng = np.random.default_rng(23)
    with MemoryMapDatasetBuilder(prefix, dtype=np.uint16) as builder:
        for _ in range(64):
            doc = rng.integers(1, 96, size=rng.integers(8, 64))
            builder.add(np.append(doc, 0).astype(np.uint16))
    return prefix


def make_pp_config(tmp_path, data_prefix, pp=2, mp=1, dp=1, gas=4, vpp=1,
                   token_slices=1, **kwargs):
    config = make_config(tmp_path, data_prefix, mp=mp, dp=dp, gas=gas, **kwargs)
    d = config.model_dump(mode="json")
    d["topology"]["pipe_parallel_size"] = pp
    d["topology"]["world_size"] = pp * mp * dp
    d["topology"]["pipe_virtual_size"] = vpp
    d["topology"]["pipe_token_slices"] = token_slices
    type_ = type(config)
    return type_.from_dict(d)


def test_pp2_loss_close_to_pp1(tmp_path, data_prefix):
    """From identical weights (checkpoint interchange) and the same data
    order, pp=1 and pp=2 must compute the same training math —
    float-association differences only. Init RNG streams differ between the
    per-layer and stage-stacked assemblies, hence the common checkpoint.

    Bound derivation (measured, this exact setup): the per-step losses are
    BIT-IDENTICAL for the first 3 steps and drift to ~1e-7 relative by
    step 5 — per-microbatch math is the same instruction stream, only the
    stage stacking reassociates a handful of reductions, and fp32 ulp
    noise compounds through 5 optimizer steps. rtol 1e-5 leaves two
    orders of magnitude of headroom over that measured drift while any
    real schedule bug (wrong micro-batch routed, wrong layer order, a
    garbage fill tick leaking into outputs) lands at >=1e-2 on step 1."""
    cfg0 = make_config(tmp_path / "seed", data_prefix, gas=4, train_iterations=1,
                       save_interval=100)
    t0 = build_capturing_trainer(cfg0)
    t0.save_checkpoint()  # iteration 0: pristine init

    losses = {}
    for pp in (1, 2):
        cfg = make_pp_config(tmp_path / f"pp{pp}", data_prefix, pp=pp, gas=4,
                             train_iterations=5, save_interval=100,
                             load_dir=Path(cfg0.trainer.save_dir))
        t = build_capturing_trainer(cfg, load=True)
        losses[pp] = train_capture(t, 5)

    np.testing.assert_allclose(
        np.asarray(losses[1], np.float32), np.asarray(losses[2], np.float32),
        rtol=1e-5, atol=1e-6,
    )


@pytest.mark.slow
def test_pp2_resume_loss_exact(tmp_path, data_prefix):
    """pp=2 train 10 save at 6, resume at pp=2: steps 7-10 match exactly."""
    cfg = make_pp_config(tmp_path, data_prefix, pp=2, gas=4)
    t = build_capturing_trainer(cfg)
    losses_full = train_capture(t, 10)

    cfg_resumed = make_pp_config(
        tmp_path / "resume", data_prefix, pp=2, gas=4,
        load_dir=Path(cfg.trainer.save_dir),
    )
    t_resumed = build_capturing_trainer(cfg_resumed, load=True)
    assert t_resumed.context.iterations == 6
    losses_resumed = train_capture(t_resumed, 4)
    np.testing.assert_array_equal(
        np.asarray(losses_full[6:], np.float32),
        np.asarray(losses_resumed, np.float32),
    )


@pytest.mark.parametrize(
    "save_pp,load_pp",
    [(2, 1), pytest.param(1, 2, marks=pytest.mark.slow),
     pytest.param(2, 4, marks=pytest.mark.slow)],
)
def test_checkpoint_interchanges_across_pipe_layouts(
    tmp_path, data_prefix, save_pp, load_pp
):
    """A checkpoint written at one pipe_parallel_size loads at another:
    stage-stacked body params un-stack into per-layer files
    (reference: layout-independent resume, partitioned_module.py:259-371)."""
    num_layers = 4  # divisible by every pp above
    cfg = make_pp_config(tmp_path, data_prefix, pp=save_pp, gas=2,
                         train_iterations=3, save_interval=3, num_layers=num_layers)
    t = build_capturing_trainer(cfg)
    train_capture(t, 3)

    cfg_load = make_pp_config(
        tmp_path / "reload", data_prefix, pp=load_pp, gas=2,
        train_iterations=6, save_interval=100, num_layers=num_layers,
        load_dir=Path(cfg.trainer.save_dir),
    )
    t2 = build_capturing_trainer(cfg_load, load=True)
    assert t2.context.iterations == 3

    # the loaded params must match the saved ones layer by layer
    view_saved = t.module.ckpt_view(t.params)
    view_loaded = t2.module.ckpt_view(t2.params)
    flat_saved = {m.key: p for (m, p) in zip(
        _meta_leaves(t.module.ckpt_metas()), _leaves(view_saved))}
    flat_loaded = {m.key: p for (m, p) in zip(
        _meta_leaves(t2.module.ckpt_metas()), _leaves(view_loaded))}
    assert set(flat_saved) == set(flat_loaded)
    for k in flat_saved:
        np.testing.assert_array_equal(
            np.asarray(flat_saved[k]), np.asarray(flat_loaded[k]), err_msg=k
        )

    # and training continues without error
    out = t2.train_step()
    assert np.isfinite(float(out.loss))


def _leaves(tree):
    import jax

    return jax.tree.leaves(tree)


def _meta_leaves(metas):
    import jax

    from scaling_tpu.nn.param import ParamMeta

    return jax.tree.leaves(metas, is_leaf=lambda x: isinstance(x, ParamMeta))


@pytest.mark.parametrize("vpp,num_layers", [(2, 4), pytest.param(4, 8, marks=pytest.mark.slow)])
def test_interleaved_loss_close_to_pp1(tmp_path, data_prefix, vpp, num_layers):
    """Interleaved virtual stages vs the pp=1 golden under the same
    checkpoint-transfer + rng/dropout decorrelation contract as the
    fill-drain parity test above: same instruction stream per layer, only
    the chunk circulation reassociates a handful of reductions, so rtol
    1e-5 holds while any schedule bug (wrong chunk at a round, a wrap
    mis-phase, garbage injected over a live slot) lands at >=1e-2 on
    step 1."""
    cfg0 = make_config(tmp_path / "seed", data_prefix, gas=4,
                       train_iterations=1, save_interval=100,
                       num_layers=num_layers)
    t0 = build_capturing_trainer(cfg0)
    t0.save_checkpoint()

    losses = {}
    for arm, kw in (("pp1", {}), ("vpp", {"pp": 2, "vpp": vpp})):
        cfg = make_pp_config(tmp_path / arm, data_prefix, gas=4,
                             train_iterations=5, save_interval=100,
                             num_layers=num_layers,
                             load_dir=Path(cfg0.trainer.save_dir),
                             **({"pp": 1} if arm == "pp1" else kw))
        t = build_capturing_trainer(cfg, load=True)
        losses[arm] = train_capture(t, 5)

    np.testing.assert_allclose(
        np.asarray(losses["pp1"], np.float32),
        np.asarray(losses["vpp"], np.float32),
        rtol=1e-5, atol=1e-6,
    )


def test_token_slice_loss_close_to_pp1(tmp_path, data_prefix):
    """TeraPipe token slicing vs the pp=1 golden, on REAL packed-document
    data: each stage's attention runs against the per-stage KV cache with
    the cached slots' segment ids, so a slice must see exactly the causal
    prefix of its own documents — a cache offset bug, a missing segment
    mask (cross-document attention), or rotary positions drifting per
    slice all break the 1e-5 parity immediately."""
    cfg0 = make_config(tmp_path / "seed", data_prefix, gas=4,
                       train_iterations=1, save_interval=100)
    t0 = build_capturing_trainer(cfg0)
    t0.save_checkpoint()

    losses = {}
    for arm, kw in (("pp1", {"pp": 1}), ("slice", {"pp": 2, "token_slices": 2})):
        cfg = make_pp_config(tmp_path / arm, data_prefix, gas=4,
                             train_iterations=5, save_interval=100,
                             load_dir=Path(cfg0.trainer.save_dir), **kw)
        t = build_capturing_trainer(cfg, load=True)
        losses[arm] = train_capture(t, 5)

    np.testing.assert_allclose(
        np.asarray(losses["pp1"], np.float32),
        np.asarray(losses["slice"], np.float32),
        rtol=1e-5, atol=1e-6,
    )


def test_interleaved_checkpoint_interchanges_with_other_layouts(
    tmp_path, data_prefix
):
    """A checkpoint written under the interleaved (pp, v, lpv) stacking
    unstacks into the same per-layer files as any other layout: the
    round-robin chunk order must be inverted exactly, or layer j's
    weights land in layer k's file."""
    cfg = make_pp_config(tmp_path, data_prefix, pp=2, vpp=2, gas=4,
                         train_iterations=3, save_interval=3, num_layers=4)
    t = build_capturing_trainer(cfg)
    train_capture(t, 3)

    cfg_load = make_pp_config(
        tmp_path / "reload", data_prefix, pp=1, gas=4,
        train_iterations=6, save_interval=100, num_layers=4,
        load_dir=Path(cfg.trainer.save_dir),
    )
    t2 = build_capturing_trainer(cfg_load, load=True)
    assert t2.context.iterations == 3
    view_saved = t.module.ckpt_view(t.params)
    view_loaded = t2.module.ckpt_view(t2.params)
    for (ka, a), (kb, b) in zip(
        sorted(view_saved.items()), sorted(view_loaded.items())
    ):
        assert ka == kb
        for la, lb in zip(_leaves(a), _leaves(b)):
            np.testing.assert_array_equal(np.asarray(la), np.asarray(lb),
                                          err_msg=ka)
    out = t2.train_step()
    assert np.isfinite(float(out.loss))


def test_pp2_remat_with_padding_loss_parity(tmp_path, data_prefix, monkeypatch):
    """The PADDED chunked-remat path end to end: gas=13 gives T=14 ticks,
    which factors as 3x5 with one discarded padding tick — a garbage tick
    leaking into outputs or gradients would break the 1e-5 loss parity
    with pp=1 immediately (the FLOPs test runs remat-off and cannot see
    this path)."""
    from scaling_tpu.parallel.pipeline import _remat_chunking

    # tiny test shapes fit the carry budget easily; force the chunked path
    monkeypatch.setenv("SCALING_TPU_PIPE_CARRY_BUDGET_MB", "0")

    gas = 13
    chunk, n_chunks = _remat_chunking(gas + 1)
    assert chunk * n_chunks > gas + 1, "want a padded shape for this test"

    cfg0 = make_config(tmp_path / "seed", data_prefix, gas=gas,
                       train_iterations=1, save_interval=100)
    t0 = build_capturing_trainer(cfg0)
    t0.save_checkpoint()

    losses = {}
    for pp, remat in ((1, False), (2, True)):
        cfg = make_pp_config(tmp_path / f"pp{pp}", data_prefix, pp=pp, gas=gas,
                             train_iterations=2, save_interval=100,
                             load_dir=Path(cfg0.trainer.save_dir))
        if remat:
            d = cfg.model_dump(mode="json")
            d["topology"]["activation_checkpointing_type"] = "every_layer"
            cfg = type(cfg).from_dict(d)
        t = build_capturing_trainer(cfg, load=True)
        losses[pp] = train_capture(t, 2)
    np.testing.assert_allclose(
        np.asarray(losses[1], np.float32), np.asarray(losses[2], np.float32),
        rtol=1e-5, atol=1e-6,
    )
