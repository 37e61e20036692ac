"""MoE layer: routing math, capacity behavior, expert-parallel sharding
(beyond the reference — SURVEY §2.4 lists EP as absent there)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from scaling_tpu.nn.base_layer import ForwardContext
from scaling_tpu.nn.moe import ParallelMoEMLP

B, S, H = 2, 16, 32


def make_layer(**kw):
    defaults = dict(
        io_features=H, intermediate_feature_factor=2.0, num_experts=4,
        top_k=2, capacity_factor=8.0, glu=True,
    )
    defaults.update(kw)
    return ParallelMoEMLP(**defaults)


def dense_expert(layer, params, x, e):
    """Run expert e's FFN densely over all tokens."""
    w_in = params["w_in"][e].astype(x.dtype)
    w_out = params["w_out"][e].astype(x.dtype)
    up = x @ w_in
    if layer.glu:
        act = jax.nn.silu(x @ params["w_gate"][e].astype(x.dtype)) * up
    else:
        act = layer.activation_fn(up)
    return act @ w_out


def test_topk_matches_dense_mixture():
    """With ample capacity, the dispatched computation equals the explicit
    gated mixture of each token's top-k experts."""
    layer = make_layer()
    params = layer.init(jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (B, S, H), jnp.float32) * 0.5
    y, aux = layer(params, x, ForwardContext())

    logits = jnp.einsum("bsh,he->bse", x, params["router"]["weight"])
    probs = jax.nn.softmax(logits, -1)
    gate_vals, gate_idx = jax.lax.top_k(probs, layer.top_k)
    gate_vals = gate_vals / gate_vals.sum(-1, keepdims=True)
    expert_out = jnp.stack(
        [dense_expert(layer, params, x, e) for e in range(layer.num_experts)], axis=2
    )  # (b, s, E, h)
    picked = jnp.take_along_axis(expert_out, gate_idx[..., None], axis=2)
    ref = (picked * gate_vals[..., None]).sum(axis=2)
    np.testing.assert_allclose(np.asarray(y), np.asarray(ref), atol=1e-5, rtol=1e-5)
    assert float(aux) > 0.0


def test_capacity_drops_overflow_tokens():
    """capacity 1 with every token routed to one expert: only the first
    token per sequence is processed, the rest fall through as zeros."""
    layer = make_layer(num_experts=2, top_k=1, capacity_factor=2.0 / S)
    params = layer.init(jax.random.PRNGKey(0))
    # positive inputs + positive column weight: every token's expert-0
    # logit dominates (a linear router can't be 'biased' on zero-mean x)
    params["router"]["weight"] = jnp.zeros((H, 2)).at[:, 0].set(1.0)
    x = jnp.abs(jax.random.normal(jax.random.PRNGKey(2), (1, S, H))) + 0.1
    y, _ = layer(params, x, ForwardContext())
    # capacity = max(1, int(cf * k * S / E)) = 1 -> exactly one token kept
    nonzero_tokens = np.count_nonzero(np.abs(np.asarray(y[0])).sum(-1) > 1e-7)
    assert nonzero_tokens == 1, nonzero_tokens
    np.testing.assert_allclose(
        np.asarray(y[0, 0]),
        np.asarray(dense_expert(layer, params, x, 0)[0, 0]),
        atol=1e-5, rtol=1e-5,
    )


def test_aux_loss_prefers_balance():
    """The Switch aux loss is minimal (=1 at coef 1) under perfectly uniform
    routing and larger when the router collapses to one expert."""
    layer = make_layer(num_experts=4, top_k=1, aux_loss_coef=1.0)
    params = layer.init(jax.random.PRNGKey(0))
    x = jnp.abs(jax.random.normal(jax.random.PRNGKey(3), (B, S, H))) + 0.1

    params_uniform = dict(params, router={"weight": jnp.zeros((H, 4))})
    _, aux_uniform = layer(params_uniform, x, ForwardContext())
    collapsed = jnp.zeros((H, 4)).at[:, 0].set(10.0)
    _, aux_collapsed = layer(dict(params, router={"weight": collapsed}), x, ForwardContext())
    assert float(aux_collapsed) > float(aux_uniform) * 1.5
    assert abs(float(aux_uniform) - 1.0) < 0.2


def test_expert_parallel_sharding_specs():
    layer = make_layer()
    metas = layer.param_metas()
    assert metas["w_in"].partition_spec == ("data", None, "model")
    assert metas["w_out"].partition_spec == ("data", "model", None)
    assert metas["router"]["weight"].is_model_parallel_duplicate


def test_gradients_flow_to_router_and_experts():
    layer = make_layer()
    params = layer.init(jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(4), (B, S, H), jnp.float32)

    def loss(p):
        y, aux = layer(p, x, ForwardContext())
        return (y * y).mean() + aux

    grads = jax.grad(loss)(params)
    assert float(jnp.abs(grads["router"]["weight"]).sum()) > 0
    assert float(jnp.abs(grads["w_in"]).sum()) > 0
    assert float(jnp.abs(grads["w_out"]).sum()) > 0


# ---- serving: nothing dropped, real positions counted, gates as published

def crowded(layer, params):
    """Every token of a 16-position row wants expert 0 first."""
    params["router"]["weight"] = jnp.zeros((H, layer.num_experts)).at[:, 0].set(1.0)
    return jnp.abs(jax.random.normal(jax.random.PRNGKey(2), (1, S, H))) + 0.1


def test_serve_gives_every_expert_room_for_the_whole_row():
    """The capacity factor that drops all but one token in training drops
    nothing when serving: the same einsums, C = s."""
    layer = make_layer(num_experts=2, top_k=1, capacity_factor=2.0 / S)
    params = layer.init(jax.random.PRNGKey(0))
    x = crowded(layer, params)
    trained, aux = layer(params, x, ForwardContext())
    served, load = layer.serve(params, x)
    assert load is None and float(aux) > 0
    assert np.count_nonzero(np.abs(np.asarray(trained[0])).sum(-1) > 1e-7) == 1
    np.testing.assert_allclose(
        np.asarray(served[0]), np.asarray(dense_expert(layer, params, x, 0)[0]),
        atol=1e-5, rtol=1e-5)


def test_serve_counts_the_assignments_of_real_positions_only():
    layer = make_layer(num_experts=4, top_k=2)
    params = layer.init(jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (B, S, H), jnp.float32)
    real = jnp.arange(S)[None, :] < jnp.asarray([5, 0])[:, None]
    y, load = layer.serve(params, x, real)
    assert load.shape == (4,) and load.dtype == jnp.int32
    assert int(load.sum()) == 5 * 2 and int(load.max()) <= 5
    # the padded positions are computed like any other: the output is that
    # of the same row served with every position real
    np.testing.assert_allclose(
        np.asarray(y), np.asarray(layer.serve(params, x, jnp.ones((B, S), bool))[0]))
    _, full = layer.serve(params, x, jnp.ones((B, S), bool))
    assert int(full.sum()) == B * S * 2


@pytest.mark.parametrize("norm_topk_prob", [True, False])
def test_gates_are_renormalised_only_when_the_model_says_so(norm_topk_prob):
    layer = make_layer(num_experts=8, top_k=2, norm_topk_prob=norm_topk_prob)
    params = layer.init(jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (B, S, H), jnp.float32) * 0.5
    y, _ = layer(params, x, ForwardContext())
    probs = jax.nn.softmax(jnp.einsum("bsh,he->bse", x, params["router"]["weight"]), -1)
    gate_vals, gate_idx = jax.lax.top_k(probs, 2)
    if norm_topk_prob:
        gate_vals = gate_vals / gate_vals.sum(-1, keepdims=True)
    else:
        assert float(gate_vals.sum(-1).max()) < 0.6  # two of eight: far from one
    expert_out = jnp.stack(
        [dense_expert(layer, params, x, e) for e in range(8)], axis=2)
    picked = jnp.take_along_axis(expert_out, gate_idx[..., None], axis=2)
    ref = (picked * gate_vals[..., None]).sum(axis=2)
    np.testing.assert_allclose(np.asarray(y), np.asarray(ref), atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(
        np.asarray(layer.serve(params, x)[0]), np.asarray(ref), atol=1e-5, rtol=1e-5)
