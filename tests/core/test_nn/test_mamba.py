"""The Mamba-2 mixer alone (``scaling_tpu/nn/mamba.py``): the one-chunk
closed form against the recurrence written step by step, a sequence walked in
chunks against one pass, what is no token, and the served path's regrouping."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from scaling_tpu.nn.attention import packed_token_map
from scaling_tpu.nn.base_layer import ForwardContext
from scaling_tpu.nn.mamba import Mamba2Mixer, RecurrentStateView, ssd_chunk

H, HEADS, P, N, G, K = 48, 4, 8, 16, 2, 4
# float32 against float32, another order of summation: a few roundings of
# values of magnitude ~1
ATOL = 2e-5


def step_by_step(x, dt, A, B, C, S0):
    """The recurrence as it is written: S_t = exp(dt_t A) S_{t-1} + dt_t x_t
    B_t^T, y_t = S_t C_t; one row, numpy float64."""
    x, dt, A, B, C, S = (np.asarray(a, np.float64) for a in (x, dt, A, B, C, S0))
    per = x.shape[1] // B.shape[1]
    ys = []
    for t in range(x.shape[0]):
        Bt, Ct = np.repeat(B[t], per, 0), np.repeat(C[t], per, 0)      # (heads, N)
        S = np.exp(dt[t] * A)[:, None, None] * S + (
            dt[t][:, None, None] * x[t][:, :, None] * Bt[:, None, :])
        ys.append(np.einsum("hpn,hn->hp", S, Ct))
    return np.stack(ys), S


def operands(key, rows, w):
    ks = jax.random.split(key, 6)
    x = jax.random.normal(ks[0], (rows, w, HEADS, P))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (rows, w, HEADS)))
    A = -jnp.exp(jax.random.uniform(ks[2], (HEADS,), minval=0.0, maxval=2.5))
    B = jax.random.normal(ks[3], (rows, w, G, N))
    C = jax.random.normal(ks[4], (rows, w, G, N))
    S0 = jax.random.normal(ks[5], (rows, HEADS, P, N))
    return x, dt, A, B, C, S0


@pytest.mark.parametrize("w", [1, 5, 32])
def test_one_chunk_form_equals_the_step_by_step_recurrence(w):
    x, dt, A, B, C, S0 = operands(jax.random.PRNGKey(w), 3, w)
    y, S = jax.jit(ssd_chunk)(x, dt, A, B, C, S0)
    for r in range(3):
        want_y, want_S = step_by_step(x[r], dt[r], A, B[r], C[r], S0[r])
        np.testing.assert_allclose(np.asarray(y[r]), want_y, atol=ATOL, rtol=1e-5)
        np.testing.assert_allclose(np.asarray(S[r]), want_S, atol=ATOL, rtol=1e-5)


def test_a_position_with_dt_zero_leaves_the_state_as_it_was():
    x, dt, A, B, C, S0 = operands(jax.random.PRNGKey(7), 2, 8)
    # row 0: only its first 3 positions are tokens; row 1: none is
    real = jnp.arange(8)[None, :] < jnp.asarray([3, 0])[:, None]
    _, S = ssd_chunk(x, jnp.where(real[..., None], dt, 0.0), A, B, C, S0)
    _, want = step_by_step(x[0, :3], dt[0, :3], A, B[0, :3], C[0, :3], S0[0])
    np.testing.assert_allclose(np.asarray(S[0]), want, atol=ATOL, rtol=1e-5)
    assert np.array_equal(np.asarray(S[1]), np.asarray(S0[1]))      # bit for bit


@pytest.fixture(scope="module")
def mixer():
    layer = Mamba2Mixer(H, HEADS, P, N, G, K)
    params = layer.init(jax.random.PRNGKey(0))
    # away from the init (D of ones, a conv bias of zeros, a norm of ones)
    leaves, treedef = jax.tree.flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(1), len(leaves))
    params = jax.tree.unflatten(treedef, [
        x + 0.2 * jax.random.normal(k, x.shape) for x, k in zip(leaves, keys)])
    return layer, params


def test_the_init_is_mamba2s(mixer):
    layer, _ = mixer
    params = layer.init(jax.random.PRNGKey(5))
    A = np.exp(np.asarray(params["A_log"]))
    dt = np.asarray(jax.nn.softplus(params["dt_bias"]))
    assert (1.0 <= A).all() and (A <= 16.0).all()
    assert (dt >= 0.001 - 1e-7).all() and (dt <= 0.1 + 1e-6).all()
    assert np.array_equal(np.asarray(params["D"]), np.ones(HEADS))
    assert params["in_proj"]["weight"].shape == (H, 2 * HEADS * P + 2 * G * N + HEADS)
    assert params["conv"]["weight"].shape == (HEADS * P + 2 * G * N, K)
    for name in ("A_log", "D", "dt_bias"):
        assert params[name].dtype == jnp.float32
    assert jax.tree.structure(params) == jax.tree.structure(layer.param_metas())


def test_a_sequence_longer_than_a_chunk_is_walked_chunk_by_chunk(mixer, monkeypatch):
    """75 positions = a chunk of 64 and a ragged one of 11: equal to chunks of
    8 (10 chunks, 5 positions of padding) and to one chunk of 75."""
    from scaling_tpu.nn import mamba

    layer, params = mixer
    u = jax.random.normal(jax.random.PRNGKey(2), (2, 75, H))
    outs = []
    for chunk in (64, 8, 75):
        monkeypatch.setattr(mamba, "CHUNK", chunk)
        out, (S, tail) = layer(params, u, ForwardContext(), return_state=True)
        outs.append((np.asarray(out), np.asarray(S), np.asarray(tail)))
    for out, S, tail in outs[1:]:
        np.testing.assert_allclose(out, outs[0][0], atol=ATOL, rtol=1e-5)
        np.testing.assert_allclose(S, outs[0][1], atol=ATOL, rtol=1e-5)
        assert np.array_equal(tail, outs[0][2])
    assert outs[0][2].shape == (2, HEADS * P + 2 * G * N, K - 1)


@pytest.mark.parametrize("token_major", [False, True], ids=["row-major", "token-major"])
def test_chunk_32_then_one_token_at_a_time_equals_one_pass(mixer, token_major):
    """A row served a chunk of 32, then 5 + 1 + 1 + 1 tokens against its
    line of the state pool, beside an empty slot and a row that starts later:
    each row's outputs are its sequence's in one uncached pass."""
    layer, params = mixer
    ctx = ForwardContext()
    u = jax.random.normal(jax.random.PRNGKey(3), (2, 40, H))
    want = np.asarray(layer(params, u, ctx))
    slots, w = 3, 32
    view = RecurrentStateView(
        ssm=jnp.full((slots, HEADS, P, N), 7.0),           # an old occupant's
        conv=jnp.full((slots, HEADS * P + 2 * G * N, K - 1), 7.0),
        context_len=None, new_len=None)
    got = {0: [], 2: []}
    # (tokens of row 0, tokens of row 2) a tick; slot 1 stays empty
    seen = [0, 0]
    for n0, n2 in ((32, 0), (5, 32), (1, 1), (1, 1), (1, 6)):
        new_len = jnp.asarray([n0, 0, n2], jnp.int32)
        ctx_len = jnp.asarray([seen[0], 0, seen[1]], jnp.int32)
        rows = [u[0, seen[0]:seen[0] + n0], u[1, :0], u[1, seen[1]:seen[1] + n2]]
        if token_major:
            packed = jnp.concatenate(rows)
            width = -(-packed.shape[0] // w) * w
            batch = jnp.pad(packed, ((0, width - packed.shape[0]), (0, 0)))
            batch = batch.reshape(width // w, w, H)
            tmap = packed_token_map(new_len, batch.shape[:2], w)
        else:
            batch = jnp.stack([jnp.pad(r, ((0, w - r.shape[0]), (0, 0))) for r in rows])
            tmap = None
        out, view = layer(params, batch, ctx, state=view._replace(
            context_len=ctx_len, new_len=new_len, token_map=tmap))
        flat, at = np.asarray(out).reshape(-1, H), 0
        for slot, n in ((0, n0), (1, 0), (2, n2)):
            if slot in got and n:
                rows_out = flat[at:at + n] if token_major else np.asarray(out)[slot, :n]
                got[slot].append(rows_out)
            at += n
        seen = [seen[0] + n0, seen[1] + n2]
    np.testing.assert_allclose(np.concatenate(got[0]), want[0], atol=ATOL, rtol=1e-5)
    np.testing.assert_allclose(np.concatenate(got[2]), want[1], atol=ATOL, rtol=1e-5)
    # the empty slot's lines were never written
    assert np.array_equal(np.asarray(view.ssm[1]), np.full((HEADS, P, N), 7.0))
    assert np.array_equal(np.asarray(view.conv[1]), np.full_like(view.conv[1], 7.0))


def test_relu2_is_the_square_of_relu():
    from scaling_tpu.nn import ActivationFunction, get_activation_function

    x = jnp.asarray([-2.0, -0.0, 0.5, 3.0])
    assert ActivationFunction("relu2") is ActivationFunction.RELU2
    np.testing.assert_array_equal(
        np.asarray(get_activation_function(ActivationFunction.RELU2)(x)),
        np.asarray([0.0, 0.0, 0.25, 9.0]))
