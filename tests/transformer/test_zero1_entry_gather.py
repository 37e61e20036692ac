"""ZeRO-1's data-axis traffic off the step's tail (ISSUE 67): the bf16 compute
copy crosses the step boundary in the masters' placement, the step gathers
each optimized leaf ONCE on entry, outside ``value_and_grad``, and constrains
each gradient to the masters' placement before the overflow check and the
norm read it. A toy TP=2 x DP=2 + SP transformer on the virtual CPU mesh,
driven as ``benchmark/train_kind.py`` drives the step."""

import collections
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import model as bench_model
from benchmark.device import CompileCounter
from scaling_tpu.analysis.hlo_audit import (
    _COLLECTIVE_RE, MeshAxes, _parse_replica_groups,
)
from scaling_tpu.models.transformer.model import (
    init_model, init_optimizer, loss_function,
)
from scaling_tpu.nn import ParamMeta
from scaling_tpu.obs import get_registry
from scaling_tpu.parallel.sharding import lookup_on_data_shard
from scaling_tpu.topology import Topology
from scaling_tpu.topology.topology import DATA_AXIS

from .test_training_vocab_parallel import make_batch, make_config

GAUGES = ("train_zero_entry_gathers", "train_zero_scattered_grads",
          "train_zero_shard_lookups")


def built(zero=True, mp=2, dp=2, precision="bfloat16", **kw):
    """Module, optimizer, weights placed as ``benchmark/model.py`` places
    them (by ``meta.partition_spec``, no data axis), fresh optimizer state,
    the built step and what the gauges read after the build."""
    config = make_config(mp=mp, dp=dp, sp=mp > 1, zero=zero,
                         precision=precision, **kw)
    topology = Topology(config.topology)
    module = init_model(config, topology)
    optimizer = init_optimizer(config, module, topology)
    params = bench_model.init_weights(module, 3_000_000_019)
    opt_state = bench_model.init_optimizer_state(optimizer, params)
    step = module.build_train_step(optimizer, loss_function)
    return module, optimizer, params, opt_state, step


def data_sharded_leaves(optimizer, params):
    """The leaves whose master carries the data axis: what ZeRO-1 moves."""
    out = []
    for p, m in zip(jax.tree.leaves(params), optimizer._meta_leaves):
        spec = optimizer._master_sharding(m, p.shape).spec
        if any(DATA_AXIS in (e if isinstance(e, tuple) else (e,))
               for e in spec if e is not None):
            out.append((m, p))
    return out


def looked_up_on_shard(optimizer, moved):
    """Of ``data_sharded_leaves``, those never gathered (ISSUE 72): the
    untied embedding table, whose rows are looked up where it lies."""
    return [(m, p) for m, p in moved if lookup_on_data_shard(
        m, p.shape, optimizer.topology.mesh, optimizer.gathers_on_entry())]


def in_masters_placement(optimizer, params):
    return all(
        p.sharding.is_equivalent_to(optimizer._master_sharding(m, p.shape), p.ndim)
        for p, m in zip(jax.tree.leaves(params), optimizer._meta_leaves))


def test_three_calls_from_the_harness_placement_lower_one_program(devices):
    """(a) The benchmark hands the first call weights without the data axis
    and every later call the step's own outputs. ONE step program serves
    both: the first call places its ``params`` (a jitted identity of local
    slices, the only other program) before the jitted step, later calls
    lower nothing, and what the step returns is where the masters live."""
    module, optimizer, params, opt_state, step = built()
    assert not in_masters_placement(optimizer, params)
    batch = module.shard_batch(make_batch(), stacked=True)
    counter = CompileCounter()
    losses, lowered = [], []
    for i in range(3):
        before = counter.count
        params, opt_state, loss, _, _ = step(
            params, opt_state, batch, jax.random.PRNGKey(i))
        assert in_masters_placement(optimizer, params), i
        losses.append(float(loss))
        lowered.append(counter.count - before)
    assert lowered == [2, 0, 0], lowered  # placement + step, then nothing
    assert np.isfinite(losses).all() and losses[2] < losses[0]


def collectives_over(text, mesh, op, axis):
    """``(result shapes, line)`` of each ``op`` of the compiled text whose
    replica groups are ``axis``'s."""
    axes = MeshAxes(mesh.axis_names, mesh.devices.shape)
    out = []
    for line in text.splitlines():
        m = _COLLECTIVE_RE.search(line)
        if not m or m.group(2) != op or "-done(" in line:
            continue
        groups = _parse_replica_groups(line)
        if groups and axes.axis_of_groups(groups) == axis:
            out.append((m.group(1), line.strip()[:200]))
    return out


def test_one_gather_a_leaf_and_outputs_stay_sharded(devices):
    """(b) The compiled TP=2 x DP=2 ZeRO-1 step gathers each data-sharded
    leaf over ``data`` once (the backward reads the gathered copy: a second
    gather a leaf is ZeRO-3's traffic) but the embedding table, which it
    never gathers (its rows are looked up on the shard and exchanged: one
    all-to-all over ``data`` forward, one backward), gathers nothing over
    ``data`` besides, and returns every compute copy as the shard it was cast
    from: no gather is left at the step's tail. (On the CPU a reduce-scatter is compiled as
    all-reduce + slice, so what the gradients cross chips as is held where
    the chip's compiler runs: tests/core/test_chip_compile.py.)"""
    module, optimizer, params, opt_state, step = built()
    batch = module.shard_batch(make_batch(), stacked=True)
    compiled = step.lower(params, opt_state, batch, jax.random.PRNGKey(0)).compile()
    mesh = module.topology.mesh
    moved = data_sharded_leaves(optimizer, params)
    assert len(moved) > 10
    (table_meta, table), = looked_up_on_shard(optimizer, moved)
    assert table_meta.parameter_name == "embedding.weight"
    moved = [(m, p) for m, p in moved if m is not table_meta]
    gathers = collectives_over(compiled.as_text(), mesh, "all-gather", DATA_AXIS)
    # each gathered to the shape its own spec leaves on a chip, as often as
    # leaves have that shape (the CPU compiler computes bf16 as f32: dims only)
    want = collections.Counter(
        jax.sharding.NamedSharding(
            mesh, jax.sharding.PartitionSpec(*m.partition_spec)
        ).shard_shape(p.shape) for m, p in moved)
    got = collections.Counter(
        tuple(int(d) for d in re.search(r"\[([0-9,]*)\]", g[0]).group(1).split(","))
        for g in gathers if re.match(r"(bf16|f32)\[", g[0]))
    assert {shape: got[shape] for shape in want} == dict(want), (got, want)
    assert got[(table.shape[0] // 2, table.shape[1])] == 0, got  # the table
    exchanges = collectives_over(compiled.as_text(), mesh, "all-to-all", DATA_AXIS)
    assert len(exchanges) == 2, exchanges
    out_params = compiled.output_shardings[0]
    for sh, (p, m) in zip(jax.tree.leaves(out_params),
                          zip(jax.tree.leaves(params), optimizer._meta_leaves)):
        assert sh.is_equivalent_to(optimizer._master_sharding(m, p.shape), p.ndim)


@pytest.mark.parametrize("dp", [2, 1], ids=["dp2", "dp1"])
def test_gauges_count_the_leaves_moved(devices, dp):
    """(d) The first two gauges read the leaves whose master carries the data
    axis (24 of the toy's 28: four have no dimension that 2 divides) less the
    one looked up on its shard, which the third counts, once a step over a
    data axis has been traced, and 0 where the axis is 1 (one chip's program
    is the one it was: no constraint, no placement, no manual lookup)."""
    registry = get_registry()
    for name in GAUGES:
        registry.gauge(name).set(-1)
    module, optimizer, params, opt_state, step = built(dp=dp)
    assert [registry.gauge(n).value for n in GAUGES] == [0, 0, 0]  # built, not traced
    batch = module.shard_batch(make_batch(), stacked=True)
    step.lower(params, opt_state, batch, jax.random.PRNGKey(0))
    leaves = data_sharded_leaves(optimizer, params)
    moved, looked_up = len(leaves), len(looked_up_on_shard(optimizer, leaves))
    assert (moved, looked_up) == ((24, 1) if dp == 2 else (0, 0))
    assert [registry.gauge(n).value for n in GAUGES] == [
        moved - looked_up, moved - looked_up, looked_up]
    if dp == 1:
        assert in_masters_placement(optimizer, params)  # the spec itself


def test_zero_off_builds_the_jitted_function_itself(devices):
    """Without ZeRO nothing is placed or constrained: ``build_train_step``
    returns ``jax.jit``'s own function and both gauges stay 0."""
    module, optimizer, params, opt_state, step = built(zero=False)
    assert hasattr(step, "trace") and hasattr(step, "eval_shape")
    batch = module.shard_batch(make_batch(), stacked=True)
    step.lower(params, opt_state, batch, jax.random.PRNGKey(0))
    assert [get_registry().gauge(n).value for n in GAUGES] == [0, 0, 0]


def test_five_steps_equal_zero_off(devices):
    """(c) Five float32 steps: losses and weights of the ZeRO-1 step equal
    the unsharded optimizer's (``test_zero_matches_nonzero_losses``'s
    demand, rtol 1e-5 on the losses; the clipping norm is summed in another
    order, ~1e-7 relative)."""
    runs = []
    for zero in (False, True):
        module, optimizer, params, opt_state, step = built(
            zero=zero, precision="float32")
        batch = module.shard_batch(make_batch(), stacked=True)
        losses = []
        for i in range(5):
            params, opt_state, loss, _, out = step(
                params, opt_state, batch, jax.random.PRNGKey(i))
            losses.append(float(loss))
        runs.append((np.asarray(losses), float(out.global_grad_norm),
                     [np.asarray(p) for p in jax.tree.leaves(params)]))
    (l_off, norm_off, w_off), (l_on, norm_on, w_on) = runs
    np.testing.assert_allclose(l_on, l_off, rtol=1e-5)
    np.testing.assert_allclose(norm_on, norm_off, rtol=1e-5)
    for a, b in zip(w_on, w_off):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-6)


def test_the_step_lowers_from_abstract_state(devices):
    """(e) ``step.lower`` over shapes (weights labelled by their own specs,
    ``Optimizer.abstract_state``'s masters) still compiles, to the program
    the placed call runs: its ``params`` come in the masters' placement."""
    module, optimizer, params, opt_state, step = built()
    mesh = module.topology.mesh
    shapes = jax.tree.map(
        lambda s, m: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=jax.sharding.NamedSharding(
                mesh, jax.sharding.PartitionSpec(*m.partition_spec))),
        jax.eval_shape(module.init_params, jax.random.PRNGKey(0)),
        module.param_metas(), is_leaf=lambda x: isinstance(x, ParamMeta))
    batch = module.shard_batch(make_batch(), stacked=True)
    compiled = step.lower(
        shapes, optimizer.abstract_state(shapes), batch,
        jax.ShapeDtypeStruct((2,), jnp.uint32)).compile()
    for sh, (s, m) in zip(jax.tree.leaves(compiled.input_shardings[0][0]),
                          zip(jax.tree.leaves(shapes), optimizer._meta_leaves)):
        assert sh.is_equivalent_to(optimizer._master_sharding(m, s.shape), s.ndim)
