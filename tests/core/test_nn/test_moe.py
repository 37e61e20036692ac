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


def one_hot_serve(layer, params, x):
    """``serve``'s reference: the one-hot form at room for the whole row."""
    _, gate_vals, gate_idx = layer._route(params, x)
    y = layer._experts(params, x, gate_vals, gate_idx, capacity=x.shape[1])
    return layer._add_shared(params, x, y)


GROUPED_CASES = {
    "glu-k4": dict(num_experts=8, top_k=4),
    "plain-k4": dict(num_experts=8, top_k=4, glu=False),
    "k1": dict(num_experts=4, top_k=1),
    "k8-of-8": dict(num_experts=8, top_k=8, norm_topk_prob=False),
    "held-share": dict(num_experts=8, top_k=4, experts_first=2, experts_held=3,
                       router="sigmoid_bias", glu=False, shared_expert_width=48),
    "held-tail": dict(num_experts=8, top_k=2, experts_first=4),
}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("case", GROUPED_CASES)
def test_grouped_serve_equals_the_one_hot_at_room_for_the_whole_row(case, dtype):
    """``serve`` runs the experts over the assignments sorted by expert
    (ISSUE 50); ``_experts`` at ``C = s`` is what it has to equal: gated or
    not, every expert held or a share (the absent experts' gates dropped,
    not renormalised), k from 1 to all."""
    layer = make_layer(dtype=dtype, **GROUPED_CASES[case])
    params = layer.init(jax.random.PRNGKey(0))
    x = (jax.random.normal(jax.random.PRNGKey(1), (B, S, H)) * 0.5).astype(dtype)
    assert layer.serve_rows(B * S) == ("grouped", B * S * layer.top_k)
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(
        np.asarray(layer.serve(params, x)[0], np.float32),
        np.asarray(one_hot_serve(layer, params, x), np.float32),
        atol=tol, rtol=tol)


@pytest.mark.parametrize("case", ["glu-k4", "held-share"])
def test_what_a_padded_position_holds_reaches_no_real_position(case):
    """With ``real``: the real positions' outputs, the load and the absent
    count are those of the one-hot form, and what lies at a padded position
    (non-finite here; it is computed like any other, among the sorted rows
    of the experts it names) reaches no real position's output."""
    layer = make_layer(**GROUPED_CASES[case])
    params = layer.init(jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (B, S, H), jnp.float32) * 0.5
    real = jnp.arange(S)[None, :] < jnp.asarray([5, 11])[:, None]
    ref = one_hot_serve(layer, params, x)
    _, ref_load = layer.serve(params, x, real)
    poisoned = jnp.where(real[..., None], x, jnp.nan)
    y, load = layer.serve(params, poisoned, real)
    mask = np.asarray(real)
    assert np.isfinite(np.asarray(y)[mask]).all()
    np.testing.assert_allclose(
        np.asarray(y)[mask], np.asarray(ref)[mask], atol=1e-5, rtol=1e-5)
    np.testing.assert_array_equal(np.asarray(load), np.asarray(ref_load))
    held = load if layer.holds_all else load[:-1]
    absent = 0 if layer.holds_all else int(load[-1])
    assert int(held.sum()) + absent == 16 * layer.top_k


# a layer whose bound bites (ISSUE 56): 2 of 32 experts held, k = 4; 128
# places bring 512 assignments and a pass works on 128 rows of them
BOUND_PLACES, BOUND_ROWS = (2, 64), 128
BOUNDED = dict(num_experts=32, top_k=4, experts_first=3, experts_held=2,
               router="sigmoid_bias", shared_expert_width=48)


def bounded_tick(dtype, routing, with_real):
    """A layer of ``BOUNDED``, its parameters, a tick of 128 places and which
    of them are real, and the held assignments the routing gives it. The
    sigmoid router's selection bias forces the choice: ``balanced`` leaves it
    to the scores; ``held`` puts both held experts among every position's 4;
    ``exactly-R`` / ``R-plus-1`` give the share 128 / 129 assignments;
    ``empty`` keeps every choice off the share."""
    layer = make_layer(dtype=dtype, **BOUNDED)
    assert layer.serve_bound(128) == BOUND_ROWS < 128 * layer.top_k
    params = layer.init(jax.random.PRNGKey(0))
    x = (jax.random.normal(jax.random.PRNGKey(1), BOUND_PLACES + (H,)) * 0.5
         ).astype(dtype)
    real = jnp.ones(BOUND_PLACES, bool)
    if with_real:  # 100 of 128 real, the padding in the middle of a row too
        real = (jnp.arange(128) % 32 < 25).reshape(BOUND_PLACES)
    held = jnp.zeros((32,)).at[3:5].set(1.0)
    if routing == "balanced":
        return layer, params, x, real, None
    if routing == "empty":
        params["router"]["bias"] = -10.0 * held
        return layer, params, x, real, 0
    if routing == "held":
        params["router"]["bias"] = 10.0 * held
        return layer, params, x, real, 2 * int(real.sum())
    # expert 3 among every position's 4 (128 rows), expert 4 at position 0
    # alone or nowhere. The bias is one vector for all positions, so that
    # position is told apart by its input: a feature the router's column 4
    # reads, far above the other scores where it is set and far below elsewhere
    assert not with_real, "the 100 real places of the padded tick hold fewer"
    first = (jnp.arange(128) < (routing == "R-plus-1")).reshape(BOUND_PLACES)
    x = x.at[..., 0].set(jnp.where(first, 8.0, -8.0).astype(dtype))
    params["router"]["weight"] = params["router"]["weight"].at[0].set(
        jnp.zeros((32,)).at[4].set(4.0))
    params["router"]["bias"] = jnp.zeros((32,)).at[3].set(10.0)
    return layer, params, x, real, BOUND_ROWS + (routing == "R-plus-1")


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("routing,with_real", [
    ("balanced", False), ("balanced", True), ("held", False), ("held", True),
    ("exactly-R", False), ("R-plus-1", False), ("empty", False), ("empty", True)],
    ids=lambda v: {False: "all-real", True: "padded"}.get(v, v))
def test_a_bounded_serve_equals_the_one_hot_under_any_routing(
        routing, with_real, dtype):
    """Where a small share of the experts is held, the grouped form's matmuls
    see ``serve_bound`` rows a pass, not ``places x k`` (ISSUE 56): one pass
    in a balanced tick, further passes over what a skewed tick holds beyond
    the bound, so the result is the one-hot form's at ``C = s`` whatever the
    routing, and the load's last entry counts the passes beyond the first."""
    layer, params, x, real, held_rows = bounded_tick(dtype, routing, with_real)
    assert layer.serve_rows(128) == ("grouped", BOUND_ROWS)
    y, load = jax.jit(layer.serve)(params, x, real)
    assert load.shape == (2 + 1 + 1,) and load.dtype == jnp.int32
    if held_rows is None:  # balanced: ~1/16 of the assignments, one pass
        held_rows = int(load[:2].sum())
        assert 0 < held_rows < BOUND_ROWS
    assert int(load[:2].sum()) == held_rows
    assert int(load[-1]) == max(-(-held_rows // BOUND_ROWS) - 1, 0)
    assert int(load[:3].sum()) == layer.top_k * int(real.sum())
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    mask = np.asarray(real)
    np.testing.assert_allclose(
        np.asarray(y, np.float32)[mask],
        np.asarray(one_hot_serve(layer, params, x), np.float32)[mask],
        atol=tol, rtol=tol)
    # without `real` every place counts, and no count comes back
    y_all, none = layer.serve(params, x)
    assert none is None
    np.testing.assert_allclose(
        np.asarray(y_all, np.float32),
        np.asarray(one_hot_serve(layer, params, x), np.float32),
        atol=tol, rtol=tol)


def test_a_padded_position_takes_no_row_of_the_bound():
    """With ``real``, what a padded position's router chose goes to the
    group no matmul visits: non-finite padding reaches no real position, and
    a tick whose REAL held assignments fit the bound runs one pass however
    many the padding would have added."""
    layer, params, x, real, held_rows = bounded_tick(jnp.float32, "held", True)
    real = (jnp.arange(128) % 32 < 16).reshape(BOUND_PLACES)  # 64 real: 128 rows
    poisoned = jnp.where(real[..., None], x, jnp.nan)
    y, load = layer.serve(params, poisoned, real)
    assert np.asarray(load).tolist() == [64, 64, 2 * 64, 0]
    mask = np.asarray(real)
    np.testing.assert_allclose(
        np.asarray(y)[mask], np.asarray(one_hot_serve(layer, params, x))[mask],
        atol=1e-5, rtol=1e-5)


def test_the_bound_leaves_a_layer_that_holds_a_quarter_or_more_as_it_was(
        monkeypatch):
    """``serve_bound`` is ``places x k`` from a quarter of the experts held
    (OLMoE and LFM2 hold all, Nemotron half): the rule then changes nothing
    of the lowered program, which is the one with the rule disabled; under a
    quarter it does."""
    x = jnp.zeros(BOUND_PLACES + (H,), jnp.float32)
    real = jnp.ones(BOUND_PLACES, bool)

    def lowered(layer):
        params = layer.init(jax.random.PRNGKey(0))
        return jax.jit(layer.serve).lower(params, x, real).as_text()

    quarter = make_layer(num_experts=32, top_k=4, experts_held=8)
    small = make_layer(**BOUNDED)
    assert quarter.serve_bound(128) == 512 and small.serve_bound(128) == 128
    assert [make_layer(num_experts=8, top_k=2, experts_held=e).serve_bound(96)
            for e in (1, 2, 8)] == [128, 192, 192]  # whole tiles, at most all
    with_rule = {"quarter": lowered(quarter), "small": lowered(small)}
    monkeypatch.setattr("scaling_tpu.nn.moe._SKEW_ROOM", 10 ** 6)
    assert small.serve_bound(128) == 512
    assert lowered(quarter) == with_rule["quarter"]
    assert lowered(small) != with_rule["small"]
    assert "while" in with_rule["small"] and "while" not in with_rule["quarter"]


@pytest.mark.parametrize("crowd", ["one-expert", "an-idle-expert"])
def test_grouped_serve_with_a_group_as_long_as_the_buffer_or_empty(crowd):
    """Every position on ONE expert (its group is the whole buffer, every
    other is empty), and an expert nobody chose between two that are."""
    layer = make_layer(num_experts=4, top_k=1 if crowd == "one-expert" else 2)
    params = layer.init(jax.random.PRNGKey(0))
    x = jnp.abs(jax.random.normal(jax.random.PRNGKey(2), (B, S, H))) + 0.1
    weight = jnp.zeros((H, 4)).at[:, 0].set(1.0)
    if crowd == "an-idle-expert":
        weight = weight.at[:, 2].set(0.5).at[:, 1].set(-1.0).at[:, 3].set(-0.5)
    params["router"]["weight"] = weight
    y, load = layer.serve(params, x, jnp.ones((B, S), bool))
    expect = [B * S, 0, 0, 0] if crowd == "one-expert" else [B * S, 0, B * S, 0]
    assert np.asarray(load).tolist() == expect
    np.testing.assert_allclose(
        np.asarray(y), np.asarray(one_hot_serve(layer, params, x)),
        atol=1e-5, rtol=1e-5)


def test_sharded_expert_leaves_keep_the_one_hot_form():
    """Where a mesh axis the leaves' partition names has more than one
    device, GSPMD partitions the einsums and cannot partition the kernel:
    ``serve`` keeps the one-hot form, and says so (``serve_rows``)."""
    from jax.sharding import Mesh
    from scaling_tpu.topology.topology import DATA_AXIS, MODEL_AXIS, PIPE_AXIS

    layer = make_layer(num_experts=4, top_k=2)
    devices = np.array(jax.devices()[:2])
    whole = Mesh(devices.reshape(2, 1, 1), (PIPE_AXIS, DATA_AXIS, MODEL_AXIS))
    split = Mesh(devices.reshape(1, 1, 2), (PIPE_AXIS, DATA_AXIS, MODEL_AXIS))
    assert layer.serve_rows(64, whole) == ("grouped", 128)
    assert layer.serve_rows(64, split) == ("dense", 4 * 64)
    params = layer.init(jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (B, S, H), jnp.float32) * 0.5
    np.testing.assert_allclose(
        np.asarray(layer.serve(params, x, mesh=split)[0]),
        np.asarray(layer.serve(params, x)[0]), atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("transposed", [False, True], ids=["row-major", "transposed"])
def test_the_grouped_kernel_under_the_interpreter_equals_ragged_dot(
        transposed, monkeypatch):
    """The chip's form (the Pallas kernel, here interpreted) against the CPU's
    (``ragged_dot``) on rows that belong to a group; a matrix whose width is
    no lane multiple is read through its transpose."""
    from scaling_tpu.obs import kernel_build_count
    from scaling_tpu.ops.grouped_matmul import grouped_matmul, grouped_tiles

    m, k, n, groups = 64, 256, 192 if transposed else 256, 4
    lhs = jax.random.normal(jax.random.PRNGKey(0), (m, k), jnp.float32)
    rhs = jax.random.normal(jax.random.PRNGKey(1), (groups, k, n), jnp.float32)
    sizes = jnp.asarray([9, 0, 30, 12], jnp.int32)
    before = kernel_build_count("grouped_matmul", True)
    got = grouped_matmul(lhs, rhs, sizes, interpret=True)
    assert kernel_build_count("grouped_matmul", True) == before + 1
    ref = grouped_matmul(lhs, rhs, sizes)
    np.testing.assert_allclose(
        np.asarray(got)[:51], np.asarray(ref)[:51], atol=1e-3, rtol=1e-4)
    # cut into calls of 32 rows each (a buffer longer than VMEM keeps): the
    # rows of a group that fall inside a call are its group there
    monkeypatch.setattr(
        "scaling_tpu.ops.grouped_matmul._LHS_VMEM_BYTES", 32 * k * 4)
    cut = grouped_matmul(lhs, rhs, sizes, interpret=True)
    np.testing.assert_allclose(
        np.asarray(cut)[:51], np.asarray(ref)[:51], atol=1e-3, rtol=1e-4)
    # the tiles are the shapes': a window holds twice the mean rows a group
    # (32 to 128), the matrix is taken whole where two buffers of it fit
    assert grouped_tiles(1024, 2048, 1536, 64) == (32, 1536)
    assert grouped_tiles(4096, 2048, 1024, 64) == (128, 1024)
    assert grouped_tiles(1024, 8192, 8192, 64)[1] % 128 == 0


# ---- the group-limited choice (DeepSeek-V3's noaux_tc: n_group, topk_group) --

def group_limited_by_hand(choice, n_group, topk_group, top_k):
    """The chosen experts of one token, in numpy: a group scores the sum of
    its two largest, the best groups stay (a tie to the lower group), the
    ``top_k`` largest inside them are chosen."""
    groups = choice.reshape(n_group, -1)
    score = np.sort(groups, axis=1)[:, -2:].sum(axis=1)
    kept = np.argsort(-score, kind="stable")[:topk_group]
    masked = np.full_like(groups, -np.inf)
    masked[kept] = groups[kept]
    return sorted(np.argsort(-masked.reshape(-1), kind="stable")[:top_k].tolist())


def grouped_layer(first=0, held=None, n_group=8, topk_group=4, **kw):
    return make_layer(num_experts=32, top_k=6, router="sigmoid_bias",
                      routed_scaling_factor=2.5, norm_topk_eps=1e-20,
                      shared_expert_width=16, experts_first=first,
                      experts_held=held, n_group=n_group, topk_group=topk_group,
                      **kw)


@pytest.fixture(scope="module")
def grouped_params():
    params = grouped_layer().init(jax.random.PRNGKey(0))
    params["router"]["weight"] = 30 * params["router"]["weight"]
    params["router"]["bias"] = 0.2 * jax.random.normal(jax.random.PRNGKey(1), (32,))
    return params


@pytest.mark.parametrize("n_group,topk_group", [(8, 4), (4, 2), (8, 2), (2, 1), (8, 8)])
def test_the_group_limited_choice_is_the_one_by_hand(grouped_params, n_group, topk_group):
    """Every token's chosen experts lie in its ``topk_group`` best groups and
    are the ones the equations give; the gates are the chosen scores' share
    (the bias moves the choice alone)."""
    layer = grouped_layer(n_group=n_group, topk_group=topk_group)
    x = jax.random.normal(jax.random.PRNGKey(2), (B, S, H))
    probs, gates, idx = layer._route(grouped_params, x)
    choice = np.asarray(probs) + np.asarray(grouped_params["router"]["bias"])
    for b in range(B):
        for s in range(S):
            want = group_limited_by_hand(choice[b, s], n_group, topk_group, 6)
            assert sorted(np.asarray(idx[b, s]).tolist()) == want
            assert len({e // (32 // n_group) for e in want}) <= topk_group
    picked = np.take_along_axis(np.asarray(probs), np.asarray(idx), axis=-1)
    np.testing.assert_allclose(
        gates, 2.5 * picked / picked.sum(-1, keepdims=True), rtol=1e-6)
    if (n_group, topk_group) != (8, 8):   # every group kept: no limit at all
        plain, *_ = (np.asarray(a) for a in grouped_layer(n_group=1, topk_group=1)._route(
            grouped_params, x)[2:])
        assert (np.sort(plain, -1) != np.sort(np.asarray(idx), -1)).any()


def test_one_group_lowers_the_program_it_lowered_before(grouped_params):
    """``n_group`` 1 (every routed configuration the benchmark had) takes the
    old path: no second ``top_k``, no mask, in the lowered text."""
    x = jnp.zeros((1, 8, H))
    text = lambda layer: jax.jit(
        lambda p, x: layer.serve(p, x)[0]).lower(grouped_params, x).as_text()
    plain, limited = text(grouped_layer(n_group=1, topk_group=1)), text(grouped_layer())
    assert plain.count("top_k") < limited.count("top_k")
    assert "top_k" in plain and plain == text(
        make_layer(num_experts=32, top_k=6, router="sigmoid_bias",
                   routed_scaling_factor=2.5, norm_topk_eps=1e-20,
                   shared_expert_width=16))


def test_all_thirty_two_shares_add_up_to_the_uncut_layer(grouped_params):
    """The guide's share test under the group limit: 32 experts in 8 groups
    of 4, a token keeping 4 groups and 6 experts; 32 ranks of ONE expert each
    (a quarter of a group a rank), every rank routing over all 32 outputs and
    all 8 groups. The ranks' ``serve`` outputs, the shared expert counted
    once, add up to the uncut layer's."""
    x = jax.random.normal(jax.random.PRNGKey(3), (1, 24, H))
    real = jnp.ones((1, 24), bool)
    whole, load = grouped_layer().serve(grouped_params, x, real)
    assert load.shape == (32,) and int(load.sum()) == 24 * 6
    p = grouped_params
    shared = (jax.nn.silu(x @ p["shared_gate"]) * (x @ p["shared_in"])) @ p["shared_out"]
    parts, held_total = [], 0
    for first in range(32):
        rank = dict(grouped_params)
        for leaf in ("w_in", "w_out", "w_gate"):
            rank[leaf] = grouped_params[leaf][first:first + 1]
        y, rank_load = grouped_layer(first, 1).serve(rank, x, real)
        # the one held expert's count, then the absent assignments
        assert int(rank_load[0]) == int(load[first])
        assert int(rank_load[0]) + int(rank_load[1]) == 24 * 6
        held_total += int(rank_load[0])
        parts.append(y - shared)    # every rank added the shared expert
    assert held_total == 24 * 6
    np.testing.assert_allclose(sum(parts) + shared, whole, atol=1e-4)
    sizes = [float(jnp.abs(part).max()) for part in parts]
    assert max(sizes) > 1e-3 and sum(size > 1e-3 for size in sizes) > 16


def test_a_group_limit_the_layer_cannot_build_is_refused():
    with pytest.raises(AssertionError, match="group-limited choice"):
        make_layer(num_experts=32, top_k=6, n_group=8, topk_group=4)   # softmax
    with pytest.raises(AssertionError, match="group-limited choice"):
        grouped_layer(n_group=5)
    with pytest.raises(AssertionError, match="group-limited choice"):
        grouped_layer(n_group=8, topk_group=1)   # 4 experts cannot hold 6 choices
