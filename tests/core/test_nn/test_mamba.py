"""The Mamba-2 mixer alone (``scaling_tpu/nn/mamba.py``): the one-chunk
closed form against the recurrence written step by step, a sequence walked in
chunks against one pass, what is no token, and the served path's regrouping."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from scaling_tpu.nn.attention import packed_token_map
from scaling_tpu.nn.base_layer import ForwardContext
from scaling_tpu.nn.mamba import (
    Mamba2Mixer, RecurrentStateView, split_capacity, ssd_chunk)

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


def run_tick(layer, params, u_rows, lines, ctx_len, new_len, w, width=None):
    """One tick of the served path: row ``r`` brings ``u_rows[r]`` (new_len[r],
    H). ``width`` None: the row-major caller; else token-major at that many
    places. Returns ``([each row's outputs], ssm, conv)``."""
    ssm, conv = lines
    if width is None:
        batch = jnp.stack([jnp.pad(r, ((0, w - r.shape[0]), (0, 0))) for r in u_rows])
        tmap = None
    else:
        packed = jnp.concatenate(u_rows)
        batch = jnp.pad(packed, ((0, width - packed.shape[0]), (0, 0)))
        batch = batch.reshape(width // w, w, H)
        tmap = packed_token_map(jnp.asarray(new_len, jnp.int32), batch.shape[:2], w)
    out, view = jax.jit(lambda b, v: layer(params, b, ForwardContext(), state=v))(
        batch, RecurrentStateView(
            ssm, conv, jnp.asarray(ctx_len, jnp.int32),
            jnp.asarray(new_len, jnp.int32), tmap))
    out = np.asarray(out)
    if width is None:
        outs = [out[r, :n] for r, n in enumerate(new_len)]
    else:
        ends = np.cumsum(new_len)
        outs = [out.reshape(-1, H)[e - n:e] for e, n in zip(ends, new_len)]
    return outs, np.asarray(view.ssm), np.asarray(view.conv)


@pytest.mark.parametrize("token_major", [False, True], ids=["row-major", "token-major"])
def test_chunk_32_then_one_token_at_a_time_equals_one_pass(mixer, token_major):
    """A row served a chunk of 32, then 5 + 1 + 1 + 1 tokens against its
    line of the state pool, beside an empty slot and a row that starts later:
    each row's outputs are its sequence's in one uncached pass. Token-major,
    at the fewest whole rows of places that hold a tick's tokens, the ticks
    cross both forms: whole rows, a step beside a gathered chunk, steps alone."""
    layer, params = mixer
    u = jax.random.normal(jax.random.PRNGKey(3), (2, 40, H))
    want = np.asarray(layer(params, u, ForwardContext()))
    slots, w = 3, 32
    lines = (jnp.full((slots, HEADS, P, N), 7.0),           # an old occupant's
             jnp.full((slots, HEADS * P + 2 * G * N, K - 1), 7.0))
    got = {0: [], 2: []}
    # (tokens of row 0, tokens of row 2) a tick; slot 1 stays empty
    seen = [0, 0]
    for n0, n2 in ((32, 0), (5, 32), (1, 1), (1, 1), (1, 6)):
        new_len, ctx_len = [n0, 0, n2], [seen[0], 0, seen[1]]
        rows = [u[0, seen[0]:seen[0] + n0], u[1, :0], u[1, seen[1]:seen[1] + n2]]
        width = -(-(n0 + n2) // w) * w if token_major else None
        outs, *lines = run_tick(layer, params, rows, lines, ctx_len, new_len, w, width)
        for slot in got:
            got[slot].append(outs[slot])
        seen = [seen[0] + n0, seen[1] + n2]
    np.testing.assert_allclose(np.concatenate(got[0]), want[0], atol=ATOL, rtol=1e-5)
    np.testing.assert_allclose(np.concatenate(got[2]), want[1], atol=ATOL, rtol=1e-5)
    # the empty slot's lines were never written
    assert np.array_equal(lines[0][1], np.full((HEADS, P, N), 7.0))
    assert np.array_equal(lines[1][1], np.full_like(lines[1][1], 7.0))


def width_for(new_len, widths, w):
    """The engine's rule (serve/engine.py ``_run_mixed``): the smallest width
    that holds the tick's tokens AND its multi-token rows."""
    return next(T for T in widths if sum(new_len) <= T
                and sum(n > 1 for n in new_len) <= split_capacity(T, w))


# 8 slots, rows of up to 4 tokens: a small program of 16 places (R = 4
# multi-token rows) and the full one of 32. (new_len, ctx_len) a case
SPLIT_W, SPLIT_WIDTHS = 4, (16, 32)
SPLIT_TICKS = {
    "every row decodes": ([1] * 8, [5, 9, 3, 7, 1, 2, 8, 4]),
    "decode rows among chunks of 2..w": ([1, 3, 1, 2, 4, 1, 1, 1],
                                         [5, 4, 3, 8, 4, 7, 6, 2]),
    "empty rows": ([0, 1, 0, 0, 1, 0, 2, 0], [0, 3, 5, 0, 2, 9, 4, 0]),
    "context 0 brings ONE token": ([1, 1, 1, 1, 0, 2, 0, 1],
                                   [0, 3, 0, 6, 0, 5, 2, 0]),
    "chunks in reused slots": ([3, 1, 4, 1, 0, 1, 2, 1], [0, 2, 0, 5, 0, 0, 0, 9]),
    "exactly R multi-token rows": ([2, 2, 3, 2, 1, 1, 1, 1],
                                   [0, 4, 8, 0, 1, 0, 3, 2]),
    "R + 1 multi-token rows": ([2, 2, 2, 2, 2, 1, 1, 1], [0, 4, 8, 0, 1, 0, 3, 2]),
    "nothing but a chunk": ([0, 0, 0, 4, 0, 0, 0, 0], [0] * 8),
}


def assert_a_tick_equals_whole_rows(layer, params, seed, new_len, ctx_len, w,
                                    width, reference_width):
    """A token-major tick of ``width`` places against the whole-rows form
    (``reference_width`` None: the row-major caller; else token-major at that
    full width) from seeded lines in which every slot a row starts over in
    holds NaNs: each row's outputs, every state line and conv tail agree, and
    a row that brings nothing keeps its lines bit for bit."""
    slots = len(new_len)
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    starts_over = [r for r in range(slots) if ctx_len[r] == 0 and new_len[r] > 0]
    lines = tuple(
        jax.random.normal(k, shape).at[jnp.asarray(starts_over, int)].set(jnp.nan)
        for k, shape in ((ks[0], (slots, HEADS, P, layer.state_size)),
                         (ks[1], (slots, layer.conv_dim, K - 1))))
    u = jax.random.normal(ks[2], (slots, w, H))
    u_rows = [u[r, :n] for r, n in enumerate(new_len)]
    got = run_tick(layer, params, u_rows, lines, ctx_len, new_len, w, width)
    want = run_tick(layer, params, u_rows, lines, ctx_len, new_len, w,
                    reference_width)
    for r, n in enumerate(new_len):
        np.testing.assert_allclose(got[0][r], want[0][r], atol=ATOL, rtol=1e-5)
        assert not n or np.isfinite(got[0][r]).all()
        if not n:   # bit for bit what the slot held
            assert np.array_equal(got[1][r], np.asarray(lines[0][r]), equal_nan=True)
            assert np.array_equal(got[2][r], np.asarray(lines[1][r]), equal_nan=True)
    np.testing.assert_allclose(got[1], want[1], atol=ATOL, rtol=1e-5)
    np.testing.assert_allclose(got[2], want[2], atol=ATOL, rtol=1e-5)


@pytest.mark.parametrize("reference", ["row-major", "token-major at the full width"])
@pytest.mark.parametrize("case", list(SPLIT_TICKS))
def test_each_row_in_its_own_form_equals_the_whole_rows_form(mixer, case, reference):
    """Below the full width a row that brings one token takes the single
    step and the few that bring more are gathered into a chunk: outputs, state
    lines and conv tails are the whole-rows form's, whichever caller reaches
    that. A row at context 0 starts from zeros though its slot holds an old
    occupant's NaNs; an empty row's lines are not touched."""
    layer, params = mixer
    new_len, ctx_len = SPLIT_TICKS[case]
    small, full = SPLIT_WIDTHS
    width = width_for(new_len, SPLIT_WIDTHS, SPLIT_W)
    assert width == (full if case.startswith("R + 1") else small)
    assert_a_tick_equals_whole_rows(
        layer, params, len(case), new_len, ctx_len, SPLIT_W, width,
        None if reference == "row-major" else full)


# 6 slots, rows of up to 4 tokens, a small program of 8 places: R = 2 places
# for rows that bring a chunk. (new_len, ctx_len) a case
GATHER_W, GATHER_WIDTH = 4, 8
GATHER_TICKS = {
    "no chunk row": ([1, 1, 0, 1, 1, 1], [3, 5, 0, 2, 7, 1]),
    "exactly R chunk rows": ([3, 1, 0, 1, 2, 1], [4, 5, 0, 2, 7, 1]),
    # the place no row fills reads past the pool and is clamped to the LAST
    # slot, which here holds a real chunk row: nothing of it may be written
    "a chunk row in the last slot beside a filler place": (
        [1, 1, 1, 0, 1, 3], [3, 5, 2, 0, 7, 6]),
    "a fresh chunk row": ([1, 0, 4, 1, 1, 0], [3, 0, 0, 2, 7, 0]),
    "a row that brings nothing in the last slot": ([0, 1, 2, 0, 1, 0],
                                                   [4, 5, 1, 0, 7, 9]),
}


@pytest.mark.parametrize("state_size", [128, 256])
@pytest.mark.parametrize("case", list(GATHER_TICKS))
def test_chunk_rows_lines_fetched_row_by_row_equal_whole_rows(case, state_size):
    """``_split_rows`` fetches each chunk row's line by a read of its own
    (ISSUE 53: on the chip a general gather over a state wider than the 128
    lanes copies every slot's line first), at a state of one lane tile and of
    two: outputs, state lines and conv tails are ``_chunk_rows``' over whole
    rows, and a row that brings nothing keeps its lines bit for bit, also in
    the last slot, onto which the places no row fills are clamped."""
    layer = Mamba2Mixer(H, HEADS, P, state_size, G, K)
    new_len, ctx_len = GATHER_TICKS[case]
    assert sum(n > 1 for n in new_len) <= split_capacity(GATHER_WIDTH, GATHER_W)
    assert_a_tick_equals_whole_rows(
        layer, layer.init(jax.random.PRNGKey(0)), len(case), new_len, ctx_len,
        GATHER_W, GATHER_WIDTH, None)


def test_relu2_is_the_square_of_relu():
    from scaling_tpu.nn import ActivationFunction, get_activation_function

    x = jnp.asarray([-2.0, -0.0, 0.5, 3.0])
    assert ActivationFunction("relu2") is ActivationFunction.RELU2
    np.testing.assert_array_equal(
        np.asarray(get_activation_function(ActivationFunction.RELU2)(x)),
        np.asarray([0.0, 0.0, 0.25, 9.0]))


# ---- published multipliers on in_proj's input and output (Falcon-H1) --------
SEGMENTS = ("z", "x", "B", "C", "dt")


def multiplied_mixer(in_multiplier=1.0, multipliers=None):
    return Mamba2Mixer(H, HEADS, P, N, G, K, in_multiplier=in_multiplier,
                       multipliers=multipliers)


@pytest.mark.parametrize("ones", [None, (1.0,) * 5], ids=["absent", "all-ones"])
def test_multipliers_of_one_are_todays_mixer_bit_for_bit(mixer, ones):
    """Nothing is multiplied and nothing divided: the same init, the same
    outputs and the same lowered program as a mixer built without them."""
    layer, params = mixer
    same = multiplied_mixer(1.0, ones)
    assert same.multipliers is None
    fresh, was = same.init(jax.random.PRNGKey(5)), layer.init(jax.random.PRNGKey(5))
    assert all(np.array_equal(a, b) for a, b in zip(jax.tree.leaves(fresh),
                                                    jax.tree.leaves(was)))
    u = jax.random.normal(jax.random.PRNGKey(2), (2, 9, H))
    ctx = ForwardContext()
    assert np.array_equal(np.asarray(same(params, u, ctx)),
                          np.asarray(layer(params, u, ctx)))
    assert (jax.jit(lambda p, u: same(p, u, ctx)).lower(params, u).as_text()
            == jax.jit(lambda p, u: layer(p, u, ctx)).lower(params, u).as_text())


@pytest.mark.parametrize("segment", range(5), ids=SEGMENTS)
def test_a_segments_multiplier_scales_its_columns_of_in_proj_and_no_others(mixer, segment):
    """``proj = ((s_in u) W_in) * m``: a mixer with a multiplier on ONE
    segment equals the plain mixer whose ``W_in`` has that segment's columns
    scaled, and moves the output."""
    layer, params = mixer
    m = [1.0] * 5
    m[segment] = 0.3
    scaled = multiplied_mixer(1.0, m)
    inner, GN = HEADS * P, G * N
    edges = np.cumsum([0, inner, inner, GN, GN, HEADS])
    by = np.ones(edges[-1], np.float32)
    by[edges[segment]:edges[segment + 1]] = 0.3
    folded = dict(params, in_proj={"weight": params["in_proj"]["weight"] * by})
    u = jax.random.normal(jax.random.PRNGKey(3), (2, 40, H))
    ctx = ForwardContext()
    got, (S, tail) = scaled(params, u, ctx, return_state=True)
    want, (S_w, tail_w) = layer(folded, u, ctx, return_state=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=ATOL, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(S), np.asarray(S_w), atol=ATOL, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(tail), np.asarray(tail_w), atol=ATOL)
    plain = np.asarray(layer(params, u, ctx))
    assert np.abs(np.asarray(got) - plain).max() > 1e-3
    # z gates the output and C reads the state out: the state sees neither
    _, (S_plain, _) = layer(params, u, ctx, return_state=True)
    assert np.array_equal(np.asarray(S), np.asarray(S_plain)) == (
        SEGMENTS[segment] in ("z", "C"))


def test_the_input_multiplier_and_the_served_path_see_the_same_constants(mixer):
    """``s_in`` scales every column; a served tick (single steps and a
    gathered chunk) applies the same constants as the uncached pass."""
    layer, params = mixer
    m = (0.35, 0.25, 0.18, 0.5, 0.35)
    scaled = multiplied_mixer(0.25, m)
    u = jax.random.normal(jax.random.PRNGKey(4), (1, 12, H))
    ctx = ForwardContext()
    whole, (S_whole, tail_whole) = scaled(params, u, ctx, return_state=True)
    by = np.asarray(scaled._column_multipliers()) * 0.25
    folded = dict(params, in_proj={"weight": params["in_proj"]["weight"] * by})
    np.testing.assert_allclose(np.asarray(whole), np.asarray(layer(folded, u, ctx)),
                               atol=ATOL, rtol=1e-5)
    # served: 3 slots, slot 1 takes a chunk of 8 then four single steps
    lines = (jnp.zeros((3, HEADS, P, N)), jnp.zeros((3, HEADS * P + 2 * G * N, K - 1)))
    outs, ssm, conv = run_tick(scaled, params, [u[0, :0], u[0, :8], u[0, :0]], lines,
                               [0, 0, 0], [0, 8, 0], 8, width=16)
    got = [outs[1]]
    for t in range(8, 12):
        outs, ssm, conv = run_tick(scaled, params, [u[0, :0], u[0, t:t + 1], u[0, :0]],
                                   (ssm, conv), [0, t, 0], [0, 1, 0], 8, width=16)
        got.append(outs[1])
    np.testing.assert_allclose(np.concatenate(got), np.asarray(whole[0]),
                               atol=ATOL, rtol=1e-5)
    np.testing.assert_allclose(ssm[1], np.asarray(S_whole[0]), atol=ATOL, rtol=1e-5)


def test_the_seeded_in_proj_starts_at_its_xavier_scale_over_its_multipliers():
    """muP's own init: each column's deviation is the plain one divided by
    what multiplies the column, so ``proj`` starts where a plain mixer's does."""
    m = (0.35, 0.25, 0.18, 0.5, 0.35)
    scaled = multiplied_mixer(0.25, m)
    plain = Mamba2Mixer(H, HEADS, P, N, G, K)
    key = jax.random.PRNGKey(9)
    w, w_plain = (layer.init(key)["in_proj"]["weight"] for layer in (scaled, plain))
    by = np.asarray(scaled._column_multipliers()) * 0.25
    np.testing.assert_allclose(np.asarray(w) * by, np.asarray(w_plain), rtol=1e-6)
    others = set(scaled.init(key)) - {"in_proj"}
    assert all(np.array_equal(a, b) for name in others for a, b in zip(
        jax.tree.leaves(scaled.init(key)[name]), jax.tree.leaves(plain.init(key)[name])))
