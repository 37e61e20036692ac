"""Tensor-parallel parity: mesh-sharded layers == single-device layers.

Mirrors the reference's ``test_parallel_linear.py`` (MP outputs merged and
compared against a plain linear) — here the comparison is a jit over a real
(pipe=1, data=2, model=4) mesh vs the unsharded computation.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from scaling_tpu.nn import (
    ColumnParallelLinear,
    ForwardContext,
    ParallelSelfAttention,
    ParallelSwiGLUMLP,
    RelativePositionEmbeddingType,
    RotaryConfig,
    RowParallelLinear,
    VocabParallelEmbedding,
)
from scaling_tpu.parallel.sharding import sp_boundary_is_manual
from scaling_tpu.topology import Topology, TopologyConfig


@pytest.fixture(scope="module")
def topo():
    cfg = TopologyConfig(
        model_parallel_size=4,
        pipe_parallel_size=1,
        data_parallel_size=2,
        micro_batch_size=2,
        gradient_accumulation_steps=1,
    )
    return Topology(cfg)


def place(topo, params, metas):
    return jax.tree.map(
        lambda p, m: jax.device_put(p, NamedSharding(topo.mesh, m.spec())),
        params,
        metas,
        is_leaf=lambda x: hasattr(x, "partition_spec"),
    )


def run_pair(topo, layer, params, metas, x, sequence_parallel=False):
    """Return (single-device result, mesh-sharded result)."""
    ctx_plain = ForwardContext()
    y_plain = layer(params, x, ctx_plain)

    sharded_params = place(topo, params, metas)
    x_sharded = jax.device_put(
        x, NamedSharding(topo.mesh, P("data", *([None] * (x.ndim - 1))))
    )

    def fwd(p, xx):
        ctx = ForwardContext(
            mesh=topo.mesh,
            model_parallel_size=topo.model_parallel_size,
            sequence_parallel=sequence_parallel,
        )
        return layer(p, xx, ctx)

    y_mesh = jax.jit(fwd)(sharded_params, x_sharded)
    return np.asarray(y_plain), np.asarray(y_mesh)


def test_column_parallel_parity(topo):
    layer = ColumnParallelLinear(32, 64, parallel_output=True)
    params = layer.init(jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 8, 32))
    plain, mesh = run_pair(topo, layer, params, layer.param_metas(), x)
    np.testing.assert_allclose(plain, mesh, atol=1e-5)


def test_row_parallel_parity(topo):
    layer = RowParallelLinear(64, 32)
    params = layer.init(jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 8, 64))
    plain, mesh = run_pair(topo, layer, params, layer.param_metas(), x)
    np.testing.assert_allclose(plain, mesh, atol=1e-5)


def test_column_into_row_fused_region(topo):
    """col(parallel_output) -> row(parallel_input): stays sharded between."""
    col = ColumnParallelLinear(32, 64, parallel_output=True)
    row = RowParallelLinear(64, 32, parallel_input=True)
    cp, rp = col.init(jax.random.PRNGKey(0)), row.init(jax.random.PRNGKey(1))
    x = jax.random.normal(jax.random.PRNGKey(2), (4, 8, 32))

    ctx_plain = ForwardContext()
    y_plain = row(rp, col(cp, x, ctx_plain), ctx_plain)

    scp = place(topo, cp, col.param_metas())
    srp = place(topo, rp, row.param_metas())
    xs = jax.device_put(x, NamedSharding(topo.mesh, P("data", None, None)))

    def fwd(cpp, rpp, xx):
        ctx = ForwardContext(mesh=topo.mesh, model_parallel_size=4)
        return row(rpp, col(cpp, xx, ctx), ctx)

    y_mesh = jax.jit(fwd)(scp, srp, xs)
    np.testing.assert_allclose(np.asarray(y_plain), np.asarray(y_mesh), atol=1e-5)


def test_vocab_parallel_embedding_parity(topo):
    layer = VocabParallelEmbedding(128, 32)
    params = layer.init(jax.random.PRNGKey(0))
    ids = jax.random.randint(jax.random.PRNGKey(1), (4, 8), 0, 128)
    ctx_plain = ForwardContext()
    y_plain = layer(params, ids, ctx_plain)

    sp = place(topo, params, layer.param_metas())
    ids_s = jax.device_put(ids, NamedSharding(topo.mesh, P("data", None)))

    def fwd(p, i):
        return layer(p, i, ForwardContext(mesh=topo.mesh, model_parallel_size=4))

    y_mesh = jax.jit(fwd)(sp, ids_s)
    np.testing.assert_allclose(np.asarray(y_plain), np.asarray(y_mesh), atol=1e-6)


def test_swiglu_mlp_parity(topo):
    layer = ParallelSwiGLUMLP(32, intermediate_feature_factor=2.0)
    params = layer.init(jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 8, 32))
    plain, mesh = run_pair(topo, layer, params, layer.param_metas(), x)
    np.testing.assert_allclose(plain, mesh, atol=1e-5)


def test_attention_parity(topo):
    layer = ParallelSelfAttention(
        hidden_size=32,
        num_attention_heads=4,
        rotary_config=RotaryConfig(dimensions=8, max_seq_length=64),
        relative_position_embedding_type=RelativePositionEmbeddingType.ROTARY,
    )
    params = layer.init(jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 8, 32))
    plain, mesh = run_pair(topo, layer, params, layer.param_metas(), x)
    np.testing.assert_allclose(plain, mesh, atol=1e-5)


def test_sequence_parallel_parity(topo):
    """SP on vs off must produce identical results (reference's SP test)."""
    layer = ParallelSwiGLUMLP(32, intermediate_feature_factor=2.0)
    params = layer.init(jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 8, 32))
    plain, mesh_sp = run_pair(topo, layer, params, layer.param_metas(), x, sequence_parallel=True)
    np.testing.assert_allclose(plain, mesh_sp, atol=1e-5)


def test_params_actually_sharded(topo):
    layer = ColumnParallelLinear(32, 64)
    params = place(topo, layer.init(jax.random.PRNGKey(0)), layer.param_metas())
    w = params["weight"]
    # weight (32, 64) sharded over model axis (4) on dim 1 -> shard (32, 16)
    shard_shape = w.sharding.shard_shape(w.shape)
    assert shard_shape == (32, 16)


def test_gradients_match_single_device(topo):
    """TP backward (XLA-inserted collectives) == single-device grads."""
    layer = ParallelSwiGLUMLP(32, intermediate_feature_factor=2.0)
    params = layer.init(jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 8, 32))

    def loss_plain(p):
        return jnp.sum(layer(p, x, ForwardContext()) ** 2)

    g_plain = jax.grad(loss_plain)(params)

    sp = place(topo, params, layer.param_metas())
    xs = jax.device_put(x, NamedSharding(topo.mesh, P("data", None, None)))

    def loss_mesh(p, xx):
        ctx = ForwardContext(mesh=topo.mesh, model_parallel_size=4)
        return jnp.sum(layer(p, xx, ctx) ** 2)

    g_mesh = jax.jit(jax.grad(loss_mesh))(sp, xs)
    for a, b in zip(jax.tree.leaves(g_plain), jax.tree.leaves(g_mesh)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-3)


# -- sequence parallelism's explicit region boundaries (ISSUE 58) ----------
def sp_region(col, row, cp, rp, x, ctx):
    """enter -> gelu -> leave: a TP region between two SP layouts, and the
    counter of regions entered by hand."""
    y = row(rp, jax.nn.gelu(col(cp, x, ctx)), ctx)
    return y, ctx.sp_manual_boundaries


@pytest.mark.parametrize("bias", [False, True], ids=["nobias", "bias"])
@pytest.mark.parametrize("local_batch", [1, 2])
def test_sp_enter_leave_parity(topo, local_batch, bias):
    """Forward and gradients of a region crossed by ``sp_enter`` /
    ``sp_leave`` (one all-gather of the rows into the column-parallel matmul,
    the row-parallel matmul into one reduce-scatter) equal the single-device
    matmuls: at local batch 1, where rows-first is a reshape, and at 2, where
    it is a transpose."""
    col = ColumnParallelLinear(32, 64, bias=bias, parallel_output=True)
    row = RowParallelLinear(64, 32, bias=bias, parallel_output=True)
    cp, rp = col.init(jax.random.PRNGKey(0)), row.init(jax.random.PRNGKey(1))
    if bias:  # a zero bias would hide a bias added on the wrong side
        cp["bias"] = jax.random.normal(jax.random.PRNGKey(3), cp["bias"].shape)
        rp["bias"] = jax.random.normal(jax.random.PRNGKey(4), rp["bias"].shape)
    batch = local_batch * topo.data_parallel_size
    x = jax.random.normal(jax.random.PRNGKey(2), (batch, 8, 32))
    run = functools.partial(sp_region, col, row)

    def loss_plain(cp, rp, x):
        return jnp.sum(run(cp, rp, x, ForwardContext())[0] ** 2)

    def loss_mesh(cp, rp, x):
        ctx = ForwardContext(mesh=topo.mesh, model_parallel_size=4,
                             sequence_parallel=True)
        y, entered = run(cp, rp, x, ctx)
        assert entered == 1
        return jnp.sum(y ** 2), y

    y_plain, _ = run(cp, rp, x, ForwardContext())
    g_plain = jax.grad(loss_plain, argnums=(0, 1, 2))(cp, rp, x)
    scp = place(topo, cp, col.param_metas())
    srp = place(topo, rp, row.param_metas())
    xs = jax.device_put(x, NamedSharding(topo.mesh, P("data", "model", None)))
    step = jax.jit(jax.grad(loss_mesh, argnums=(0, 1, 2), has_aux=True))
    g_mesh, y_mesh = step(scp, srp, xs)
    assert y_mesh.sharding.spec[:2] == ("data", "model")  # left in the SP layout
    text = step.lower(scp, srp, xs).as_text()
    # one gather in, one scatter out, and each one's transpose
    assert text.count("stablehlo.all_gather") == 2
    assert text.count("stablehlo.reduce_scatter") == 2
    np.testing.assert_allclose(np.asarray(y_plain), np.asarray(y_mesh), atol=1e-5)
    for a, b in zip(jax.tree.leaves(g_plain), jax.tree.leaves(g_mesh)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-3)


def test_sp_boundary_without_a_model_axis_is_the_plain_matmul():
    """On a mesh whose model axis is 1 wide (what the one-chip cells run, and
    DP-only training) the helpers are never entered: no manual region, no
    collective, the plain matmuls."""
    topo = Topology(TopologyConfig(
        model_parallel_size=1, pipe_parallel_size=1, data_parallel_size=2,
        micro_batch_size=2, gradient_accumulation_steps=1))
    col = ColumnParallelLinear(32, 64, parallel_output=True)
    row = RowParallelLinear(64, 32, parallel_output=True)
    cp, rp = col.init(jax.random.PRNGKey(0)), row.init(jax.random.PRNGKey(1))
    x = jax.random.normal(jax.random.PRNGKey(2), (4, 8, 32))
    assert not sp_boundary_is_manual(x.shape, topo.mesh)
    assert not sp_boundary_is_manual(x.shape, None)
    run = functools.partial(sp_region, col, row)

    def fwd(cp, rp, x):
        ctx = ForwardContext(mesh=topo.mesh, sequence_parallel=True)
        y, entered = run(cp, rp, x, ctx)
        assert entered == 0
        return y

    text = jax.jit(fwd).lower(cp, rp, x).as_text()
    assert "manual_computation" not in text and "all_gather" not in text
    assert "reduce_scatter" not in text
    y_plain, _ = run(cp, rp, x, ForwardContext())
    np.testing.assert_allclose(
        np.asarray(y_plain), np.asarray(jax.jit(fwd)(cp, rp, x)), atol=1e-6)
