"""The gated short convolution alone (``scaling_tpu/nn/short_conv.py``): the
operator against its equations written position by position, a sequence in
chunks with a carried tail against one pass and against token by token, the
zero start at context 0 over a dirty line, what is no token, and the served
path's two layouts (row-major, token-major)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from scaling_tpu.nn.attention import packed_token_map
from scaling_tpu.nn.base_layer import ForwardContext
from scaling_tpu.nn.short_conv import ConvTailView, GatedShortConv

H, K = 48, 3
# float32 against float64, another order of summation
ATOL = 2e-5
CTX = ForwardContext()


@pytest.fixture(scope="module")
def conv():
    layer = GatedShortConv(H, K)
    params = layer.init(jax.random.PRNGKey(0))
    # a filter away from its init, so that every tap says something
    params["conv"]["weight"] = jax.random.normal(jax.random.PRNGKey(1), (H, K))
    return layer, params


def by_the_equations(params, x):
    """One sequence (s, H), numpy float64: [B | C | X] = x W_in, u = B * X,
    v_t = sum_j w[:, j] u_{t-K+1+j} with u = 0 before the sequence, y = (C *
    v) W_out."""
    w_in, w, w_out = (np.asarray(params[k]["weight"], np.float64)
                      for k in ("in_proj", "conv", "out_proj"))
    B, C, X = np.split(np.asarray(x, np.float64) @ w_in, 3, axis=-1)
    u = B * X
    v = np.zeros_like(u)
    for t in range(u.shape[0]):
        for j in range(K):
            if t - K + 1 + j >= 0:
                v[t] += w[:, j] * u[t - K + 1 + j]
    return (C * v) @ w_out, u


def view(tail, ctx_len, new_len, token_map=None):
    return ConvTailView(tail=tail, context_len=jnp.asarray(ctx_len, jnp.int32),
                        new_len=jnp.asarray(new_len, jnp.int32), token_map=token_map)


def test_the_whole_sequence_is_the_equations_and_its_tail_the_last_inputs(conv):
    layer, params = conv
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 11, H))
    y, tail = layer(params, x, CTX, return_state=True)
    for b in range(2):
        want, u = by_the_equations(params, x[b])
        np.testing.assert_allclose(np.asarray(y[b]), want, atol=ATOL)
        np.testing.assert_allclose(np.asarray(tail[b]), u[-(K - 1):], atol=ATOL)
    # a sequence shorter than the filter: the tail still holds K - 1 places,
    # zeros where the sequence has not begun
    _, tail = layer(params, x[:, :1], CTX, return_state=True)
    assert tail.shape == (2, K - 1, H) and float(jnp.abs(tail[:, 0]).max()) == 0.0
    assert layer(params, x, CTX).shape == x.shape      # no state asked: no tuple


@pytest.mark.parametrize("chunks", [(11,), (4, 4, 3), (1,) * 11, (5, 1, 1, 4)],
                         ids=["whole", "chunks", "token-by-token", "chunk-then-decode"])
def test_chunks_with_a_carried_tail_equal_the_sequence(conv, chunks):
    """Row-major ticks of width 5 (or the chunk's): each brings ``n`` real
    positions and padding; the line starts DIRTY and the row at context 0."""
    layer, params = conv
    x = jax.random.normal(jax.random.PRNGKey(3), (1, 11, H))
    want, u = by_the_equations(params, x[0])
    tail = jnp.full((1, K - 1, H), 7.0)            # what an earlier occupant left
    got, done = [], 0
    for n in chunks:
        width = max(n, 5)
        chunk = jnp.zeros((1, width, H)).at[:, :n].set(x[:, done:done + n])
        # the padding is anything but zeros
        chunk = chunk.at[:, n:].set(3.0)
        y, new = layer(params, chunk, CTX, state=view(tail, [done], [n]))
        got.append(y[0, :n])
        tail, done = new.tail, done + n
    np.testing.assert_allclose(np.asarray(jnp.concatenate(got)), want, atol=ATOL)
    np.testing.assert_allclose(np.asarray(tail[0]), u[-(K - 1):], atol=ATOL)


def test_a_row_that_brings_nothing_keeps_its_line(conv):
    layer, params = conv
    tail = jax.random.normal(jax.random.PRNGKey(4), (3, K - 1, H))
    x = jax.random.normal(jax.random.PRNGKey(5), (3, 4, H))
    # row 0 brings 2 tokens at context 9, row 1 nothing at context 5, row 2
    # nothing at context 0 (an empty slot: not fresh, nothing to start)
    _, new = layer(params, x, CTX, state=view(tail, [9, 5, 0], [2, 0, 0]))
    np.testing.assert_array_equal(np.asarray(new.tail[1:]), np.asarray(tail[1:]))
    _, u = by_the_equations(params, x[0, :2])
    np.testing.assert_allclose(np.asarray(new.tail[0]), u, atol=ATOL)
    # one real token shifts the line by one place
    _, one = layer(params, x, CTX, state=view(tail, [9, 5, 0], [1, 0, 0]))
    np.testing.assert_allclose(np.asarray(one.tail[0, 0]), np.asarray(tail[0, 1]))
    np.testing.assert_allclose(np.asarray(one.tail[0, 1]), u[0], atol=ATOL)


def test_token_major_ticks_equal_row_major_ones(conv):
    """The engine's layout: the rows' real tokens back to back in one batch
    of another shape, a chunk row, decode rows and empty slots mixed."""
    layer, params = conv
    rows, width = 5, 4
    new_len = jnp.asarray([3, 1, 0, 4, 1], jnp.int32)
    ctx_len = jnp.asarray([0, 6, 0, 8, 0], jnp.int32)
    tail = jax.random.normal(jax.random.PRNGKey(6), (rows, K - 1, H))
    x = jax.random.normal(jax.random.PRNGKey(7), (rows, width, H))
    want_y, want = layer(params, x, CTX, state=view(tail, ctx_len, new_len))
    shape = (3, 4)                                   # 12 places for 9 tokens
    tmap = packed_token_map(new_len, shape, width)
    packed = jnp.full((12, H), 5.0)
    place = 0
    for r in range(rows):
        n = int(new_len[r])
        packed = packed.at[place:place + n].set(x[r, :n])
        place += n
    got_y, got = layer(params, packed.reshape(*shape, H), CTX,
                       state=view(tail, ctx_len, new_len, tmap))
    np.testing.assert_allclose(np.asarray(got.tail), np.asarray(want.tail), atol=ATOL)
    place = 0
    for r in range(rows):
        n = int(new_len[r])
        np.testing.assert_allclose(
            np.asarray(got_y.reshape(12, H)[place:place + n]),
            np.asarray(want_y[r, :n]), atol=ATOL)
        place += n
    # rows 0 and 4 started at context 0: from zeros, whatever their line held
    fresh_y, _ = layer(params, x, CTX, state=view(jnp.zeros_like(tail), ctx_len, new_len))
    np.testing.assert_allclose(np.asarray(want_y[0, :3]), np.asarray(fresh_y[0, :3]), atol=ATOL)
    np.testing.assert_allclose(np.asarray(want_y[4, :1]), np.asarray(fresh_y[4, :1]), atol=ATOL)


def test_the_filter_runs_in_float32_and_the_line_keeps_its_dtype(conv):
    layer = GatedShortConv(H, K, jnp.bfloat16)
    params = layer.init(jax.random.PRNGKey(8))
    assert {k: v["weight"].dtype for k, v in params.items()} == {
        "in_proj": jnp.bfloat16, "conv": jnp.bfloat16, "out_proj": jnp.bfloat16}
    assert {k: v["weight"].shape for k, v in params.items()} == {
        "in_proj": (H, 3 * H), "conv": (H, K), "out_proj": (H, H)}
    x = jax.random.normal(jax.random.PRNGKey(9), (2, 4, H)).astype(jnp.bfloat16)
    tail = jnp.zeros((2, K - 1, H), jnp.bfloat16)
    y, new = layer(params, x, CTX, state=view(tail, [0, 0], [4, 2]))
    assert y.dtype == jnp.bfloat16 and new.tail.dtype == jnp.bfloat16
    assert set(layer.param_metas()) == set(params)
