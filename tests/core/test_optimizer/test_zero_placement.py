"""Where ZeRO keeps the compute copy between steps (ISSUE 67): in the
masters' placement. ``Optimizer.place_params`` puts a tree there,
``Optimizer.step`` returns it there and reads each gradient as the shard its
master consumes, ``Optimizer.gather_params`` is the step's one gather on
entry. Two-leaf problem on the virtual CPU mesh, no model. Below it
(ISSUE 72): the leaf that is never gathered because its layer looks rows up
on the shard, in a model of an embedding table and a head."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from scaling_tpu.nn import (
    BaseLayer, ForwardContext, LayerSpec, ParamMeta, TiedLayerSpec,
    VocabParallelEmbedding, replicated_meta,
)
from scaling_tpu.obs import get_registry
from scaling_tpu.optimizer import (
    LossScalerConfig, Optimizer, OptimizerConfig, OptimizerParamGroup,
)
from scaling_tpu.parallel import ParallelModule
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
        gathered, out["n"], looked_up = optimizer.gather_params(p)
        assert looked_up == 0  # neither leaf is a table read by row
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


# ---- the leaf looked up on its shard (ISSUE 72) --------------------------
VOCAB, SEQ = 48, 8
FINETUNABLE = [1, 5, 40]


class Embed(BaseLayer):
    """``token_ids`` -> rows of a vocabulary-parallel table."""

    def __init__(self, hidden, tied=False):
        self.embedding = VocabParallelEmbedding(
            VOCAB, hidden, finetunable_token_ids=FINETUNABLE,
            row_lookup=not tied)

    def init(self, key):
        return self.embedding.init(key)

    def param_metas(self):
        return self.embedding.param_metas()

    def __call__(self, params, batch, ctx):
        return self.embedding(params, batch["token_ids"], ctx)


class Head(BaseLayer):
    """Rows -> logits: by a matrix of its own, or (tied) by the table."""

    def __init__(self, hidden, tied=False):
        self.hidden, self.tied = hidden, tied

    def init(self, key):
        shape = (VOCAB, self.hidden) if self.tied else (self.hidden, VOCAB)
        return {"weight": 0.1 * jax.random.normal(key, shape, jnp.float32)}

    def param_metas(self):
        if self.tied:
            return Embed(self.hidden, tied=True).param_metas()
        return {"weight": replicated_meta(2, parameter_name="weight")}

    def __call__(self, params, x, ctx):
        return x @ (params["weight"].T if self.tied else params["weight"])


def token_loss(logits, batch):
    picked = jnp.take_along_axis(
        jax.nn.log_softmax(logits), batch["targets"][..., None], axis=-1)
    return -picked.mean(), {}


def lookup_model(hidden=16, mp=2, dp=2, sp=False, zero=True, tied=False):
    """Embed -> Head on an ``mp`` x ``dp`` mesh under ZeRO-1, its weights
    placed by their own specs, fresh optimizer state, the built step, a
    batch, and the gauges."""
    topology = Topology(TopologyConfig(
        model_parallel_size=mp, pipe_parallel_size=1, data_parallel_size=dp,
        micro_batch_size=2, gradient_accumulation_steps=1,
        sequence_parallel=sp))
    spec = (lambda cls: TiedLayerSpec(cls, hidden, tied=True, key="table")
            ) if tied else (lambda cls: LayerSpec(cls, hidden))
    module = ParallelModule([spec(Embed), spec(Head)], topology)
    metas = module.param_metas()
    keys = {m.key for m in jax.tree.leaves(
        metas, is_leaf=lambda x: isinstance(x, ParamMeta))}
    optimizer = Optimizer(
        OptimizerConfig(zero=zero, gradient_clipping=1.0),
        [OptimizerParamGroup(keys=keys, learning_rate_scheduler=const_lr(0.05))],
        metas, topology=topology)
    params = module.shard_params(module.init_params(jax.random.PRNGKey(0)))
    rng = np.random.default_rng(2)
    batch = module.shard_batch({
        name: jnp.asarray(rng.integers(0, VOCAB, (1, 2 * dp, SEQ)), jnp.int32)
        for name in ("token_ids", "targets")})
    step = module.build_train_step(optimizer, token_loss, donate=False)
    registry = get_registry()
    gauges = lambda: {  # noqa: E731
        name[len("train_zero_"):]: int(registry.gauge(name).value)
        for name in ("train_zero_entry_gathers", "train_zero_scattered_grads",
                     "train_zero_shard_lookups")}
    return module, optimizer, params, batch, step, gauges


def three_steps(monkeypatch, gathered_path, **kw):
    """Losses, the table's state after three steps, and the gauges; with
    ``gathered_path`` the ONE predicate says no, which is the parent's step:
    every leaf gathered on entry, GSPMD's lookup."""
    if gathered_path:
        for where in ("scaling_tpu.parallel.sharding", "scaling_tpu.nn.linear"):
            monkeypatch.setattr(f"{where}.lookup_on_data_shard",
                                lambda *a, **k: False)
    module, optimizer, params, batch, step, gauges = lookup_model(**kw)
    state = optimizer.init_state(params)
    losses = []
    for i in range(3):
        params, state, loss, _, _ = step(params, state, batch, jax.random.PRNGKey(i))
        losses.append(float(loss))
    table = [np.asarray(tree["layer_0"]["weight"])
             for tree in (state.master, state.exp_avg, state.exp_avg_sq)]
    assert state.master["layer_0"]["weight"].sharding.shard_shape(
        (VOCAB, kw.get("hidden", 16)))[0] == VOCAB // kw.get("mp", 2)
    return np.asarray(losses), table, gauges()


@pytest.mark.parametrize("sp", [False, True], ids=["sp-off", "sp-on"])
def test_shard_lookup_trains_as_the_gathered_path(devices, monkeypatch, sp):
    """TP=2 x DP=2 + ZeRO-1, float32, three steps: the step that leaves the
    table on the masters' shard (one leaf fewer gathered, one gradient fewer
    scattered, one lookup) gives the losses and the table's master and two
    moments of the step that gathers it."""
    losses, table, gauges = three_steps(monkeypatch, False, sp=sp)
    assert gauges == {"entry_gathers": 1, "scattered_grads": 1, "shard_lookups": 1}
    with monkeypatch.context() as patch:
        want_losses, want_table, parent = three_steps(patch, True, sp=sp)
    assert parent == {"entry_gathers": 2, "scattered_grads": 2, "shard_lookups": 0}
    assert losses[2] < losses[0]
    np.testing.assert_allclose(losses, want_losses, rtol=1e-6)
    for got, want in zip(table, want_table):
        np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)
        assert np.abs(want).max() > 0


@pytest.mark.parametrize("case", [
    dict(hidden=15), dict(tied=True), dict(dp=1, mp=4), dict(zero=False)],
    ids=["columns-the-data-axis-does-not-divide", "tied", "dp1", "zero-off"])
def test_todays_lookup_wherever_the_table_is_not_on_the_shard(devices, case):
    """A table whose columns the data axis does not divide (its master stays
    ``(model, None)``: nothing to gather either), a table that is the head's
    matrix too, one data rank, ZeRO off: no leaf is looked up on its shard
    and the step holds no manual region."""
    module, optimizer, params, batch, step, gauges = lookup_model(**case)
    lowered = step.lower(
        params, optimizer.init_state(params), batch, jax.random.PRNGKey(0))
    assert gauges()["shard_lookups"] == 0
    gathers = {"hidden": 1, "tied": 1, "dp": 0, "zero": 0}[next(iter(case))]
    assert gauges()["entry_gathers"] == gathers  # the head's; the tied table
    assert "all_to_all" not in lowered.as_text()


def test_a_tied_table_cannot_declare_row_lookup(devices):
    """The one way the optimizer and the layer could disagree: a table tied
    to a matrix whose layer was not told. ``param_metas`` refuses it."""
    topology = Topology(TopologyConfig(
        model_parallel_size=2, pipe_parallel_size=1, data_parallel_size=2,
        micro_batch_size=2, gradient_accumulation_steps=1))
    module = ParallelModule(
        [TiedLayerSpec(Embed, 16, key="table"),
         TiedLayerSpec(Head, 16, tied=True, key="table")], topology)
    with pytest.raises(ValueError, match="row_lookup"):
        module.param_metas()


@pytest.mark.parametrize("sp", [False, True], ids=["sp-off", "sp-on"])
def test_rows_looked_up_on_the_shard_are_the_tables_rows(devices, sp):
    """The region alone, TP=2 x DP=2: the forward is bitwise the gathered
    lookup's, from a table on the masters' shard and from one that arrives
    gathered (``in_specs`` slice it); the gradient comes back on the shard,
    equal to the gathered path's, and ``finetunable_grad_mask`` zeroes the
    rows it zeroed."""
    module, optimizer, params, batch, _, _ = lookup_model(sp=sp)
    mesh, layer = module.topology.mesh, module.layers[0].embedding
    ids = batch["token_ids"][0]
    weigh = jnp.asarray(
        np.random.default_rng(3).normal(size=ids.shape + (16,)), jnp.float32)

    def run(on_entry):
        def rows(p):
            ctx = ForwardContext(mesh=mesh, model_parallel_size=2,
                                 sequence_parallel=sp,
                                 zero_gathers_on_entry=on_entry)
            return layer(p, ids, ctx)
        y = jax.jit(rows)(params["layer_0"])
        grad = jax.jit(jax.grad(lambda p: (rows(p) * weigh).sum()))(
            params["layer_0"])["weight"]
        return y, grad

    (want, want_grad), (got, grad) = run(False), run(True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert got.sharding.is_equivalent_to(want.sharding, 3)
    on_shard = optimizer.place_params(params)["layer_0"]
    assert on_shard["weight"].sharding.shard_shape((VOCAB, 16)) == (24, 8)
    ctx = ForwardContext(mesh=mesh, model_parallel_size=2, sequence_parallel=sp,
                         zero_gathers_on_entry=True)
    np.testing.assert_array_equal(
        np.asarray(jax.jit(lambda p: layer(p, ids, ctx))(on_shard)),
        np.asarray(want))
    assert grad.sharding.shard_shape((VOCAB, 16)) == (24, 8)
    np.testing.assert_allclose(np.asarray(grad), np.asarray(want_grad),
                               rtol=1e-6, atol=1e-6)
    mask = np.asarray(layer.finetunable_grad_mask())
    masked = np.asarray(grad) * mask
    frozen = sorted(set(range(VOCAB)) - set(FINETUNABLE))
    assert not masked[frozen].any()
    np.testing.assert_array_equal(masked[FINETUNABLE],
                                  np.asarray(want_grad)[FINETUNABLE] * 1.0)
