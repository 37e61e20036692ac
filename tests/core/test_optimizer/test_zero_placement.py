"""Where ZeRO keeps the compute copy between steps (ISSUE 67): in the
masters' placement. ``Optimizer.place_params`` puts a tree there,
``Optimizer.step`` returns it there and reads each gradient as the shard its
master consumes, ``Optimizer.gather_params`` is the step's one gather on
entry. Two-leaf problem on the virtual CPU mesh, no model."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from scaling_tpu.optimizer import (
    LossScalerConfig, Optimizer, OptimizerConfig, OptimizerParamGroup,
)
from scaling_tpu.topology import Topology, TopologyConfig

from .test_adamw import const_lr, metas


def build(dp=2, zero=True, stage=1, frozen_bias=False, clip=1.0, scaler=False):
    topology = Topology(TopologyConfig(
        model_parallel_size=1, pipe_parallel_size=1, data_parallel_size=dp,
        micro_batch_size=1, gradient_accumulation_steps=1))
    m = metas()
    keys = {m["weight"].key} | (set() if frozen_bias else {m["bias"].key})
    groups = [OptimizerParamGroup(keys=keys, learning_rate_scheduler=const_lr(0.1))]
    config = OptimizerConfig(
        zero=zero, zero_stage=stage, gradient_clipping=clip,
        loss_scaler=LossScalerConfig(enable=scaler, initial_scale=4.0))
    return Optimizer(config, groups, m, topology=topology), topology


def by_spec(topology):
    """The two leaves placed as ``shard_params`` places them: by their own
    spec (none), whole on every device."""
    rng = np.random.default_rng(0)
    whole = NamedSharding(topology.mesh, P())
    return {
        "weight": jax.device_put(
            jnp.asarray(rng.normal(size=(16, 4)), jnp.float32), whole),
        "bias": jax.device_put(jnp.asarray(rng.normal(size=(4,)), jnp.float32), whole),
    }


def shard_shapes(tree):
    return {k: v.sharding.shard_shape(v.shape) for k, v in tree.items()}


def test_place_params_moves_to_the_masters_placement_once(devices):
    optimizer, topology = build()
    params = by_spec(topology)
    placed = optimizer.place_params(params)
    # (16, 4) at dp=2: the last dimension that 2 divides carries the data axis
    assert shard_shapes(placed) == {"weight": (16, 2), "bias": (2,)}
    state = optimizer.init_state(params)
    assert shard_shapes(state.master) == shard_shapes(placed)
    np.testing.assert_array_equal(np.asarray(placed["weight"]),
                                  np.asarray(params["weight"]))
    assert not params["weight"].is_deleted()
    # already there: the very same arrays come back
    again = optimizer.place_params(placed, donate=True)
    assert again["weight"] is placed["weight"] and again["bias"] is placed["bias"]
    assert not placed["weight"].is_deleted()


def test_place_params_donate_deletes_what_it_moved(devices):
    optimizer, topology = build()
    params = by_spec(topology)
    want = np.asarray(params["weight"])
    placed = optimizer.place_params(params, donate=True)
    assert params["weight"].is_deleted() and params["bias"].is_deleted()
    np.testing.assert_array_equal(np.asarray(placed["weight"]), want)


def test_place_params_relabels_shapes_and_leaves_frozen_leaves(devices):
    optimizer, topology = build(frozen_bias=True)
    params = by_spec(topology)
    shapes = jax.tree.map(
        lambda p: jax.ShapeDtypeStruct(p.shape, p.dtype, sharding=p.sharding), params)
    placed = optimizer.place_params(shapes)
    assert isinstance(placed["weight"], jax.ShapeDtypeStruct)
    assert placed["weight"].sharding.shard_shape((16, 4)) == (16, 2)
    assert placed["bias"] is shapes["bias"]  # frozen: no master, stays put
    assert optimizer.place_params(params)["bias"] is params["bias"]


@pytest.mark.parametrize("dp,zero,stage,moved", [
    (2, True, 1, 2), (1, True, 1, 0), (2, False, 1, 0), (2, True, 3, 0)],
    ids=["zero1-dp2", "zero1-dp1", "off-dp2", "zero3-dp2"])
def test_gather_params_counts_and_places(devices, dp, zero, stage, moved):
    """The entry gather exists under stage 1 over a data axis only: it
    returns every optimized leaf by its own spec and says how many it moved.
    Elsewhere the tree passes through (stage 3 gathers at each use)."""
    optimizer, topology = build(dp=dp, zero=zero, stage=stage)
    params = optimizer.place_params(by_spec(topology))
    out = {}

    def enter(p):
        gathered, out["n"] = optimizer.gather_params(p)
        return gathered

    gathered = jax.jit(enter)(params)
    assert out["n"] == moved
    if moved:
        assert shard_shapes(gathered) == {"weight": (16, 4), "bias": (4,)}
    np.testing.assert_array_equal(np.asarray(gathered["weight"]),
                                  np.asarray(params["weight"]))


@pytest.mark.parametrize("stage", [1, 3])
def test_step_returns_the_masters_placement_and_equals_zero_off(devices, stage):
    """Three steps on whole gradients: ZeRO's new parameters come back as
    the shards their masters live on, and they, the masters and the global
    norm (a sum over shards and a scalar all-reduce) equal the unsharded
    optimizer's."""
    results = {}
    for zero in (False, True):
        optimizer, topology = build(zero=zero, stage=stage if zero else 1)
        params = optimizer.place_params(by_spec(topology))
        state = optimizer.init_state(params)
        step = jax.jit(optimizer.step)
        rng = np.random.default_rng(1)
        for _ in range(3):
            grads = {"weight": jnp.asarray(rng.normal(size=(16, 4)), jnp.float32),
                     "bias": jnp.asarray(rng.normal(size=(4,)), jnp.float32)}
            params, state, out = step(params, grads, state)
        results[zero] = (params, state, out)
        if zero:
            assert shard_shapes(params) == {"weight": (16, 2), "bias": (2,)}
            assert shard_shapes(state.master) == shard_shapes(params)
    (p_off, s_off, o_off), (p_on, s_on, o_on) = results[False], results[True]
    np.testing.assert_allclose(float(o_on.global_grad_norm),
                               float(o_off.global_grad_norm), rtol=1e-6)
    assert float(o_off.global_grad_norm) > 1.0  # the clipping was live
    for name in ("weight", "bias"):
        np.testing.assert_allclose(np.asarray(p_on[name]), np.asarray(p_off[name]),
                                   rtol=1e-6, atol=1e-7)
        np.testing.assert_allclose(np.asarray(s_on.master[name]),
                                   np.asarray(s_off.master[name]), rtol=1e-6, atol=1e-7)


def test_overflow_in_one_shard_skips_the_step_on_every_rank(devices):
    """A non-finite value that only ONE data rank's shard of a gradient
    holds still skips the whole step: the overflow flag is reduced over the
    shards before anything is updated."""
    optimizer, topology = build(scaler=True)
    params = optimizer.place_params(by_spec(topology))
    state = optimizer.init_state(params)
    before = np.asarray(params["weight"])
    bad = np.ones((16, 4), np.float32)
    bad[3, 3] = np.inf  # column 3: the second data rank's shard alone
    grads = {"weight": jnp.asarray(bad), "bias": jnp.ones((4,), jnp.float32)}
    new_params, new_state, out = jax.jit(optimizer.step)(params, grads, state)
    assert bool(out.overflow)
    np.testing.assert_array_equal(np.asarray(new_params["weight"]), before)
    assert int(new_state.step) == 0
