"""The gated delta-rule mixer alone (``scaling_tpu/nn/gated_delta.py``): the
step against the recurrence written as a Python loop, the chunk form (a unit
lower-triangular system a head) against the step, what is no token, the
extremes of its two gates, and the served path's regrouping."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from scaling_tpu.nn.attention import packed_token_map
from scaling_tpu.nn.base_layer import ForwardContext
from scaling_tpu.nn.gated_delta import (
    DeltaStateView, GatedDeltaMixer, delta_chunk, delta_chunk_rows, delta_step,
    unit_lower_inverse)
from scaling_tpu.nn.mamba import split_capacity
from scaling_tpu.obs import kernel_build_count

H, NK, NV, DK, DV, K = 48, 2, 4, 16, 8, 4
CONV = 2 * NK * DK + NV * DV
# float32 against float64 (or float32 in another order of summation): a few
# roundings of values of magnitude ~1
ATOL = 3e-5


def loop(q, k, v, g, beta, S0):
    """The recurrence as it is written, one row, numpy float64: S~ = exp(g_t)
    S; u = beta_t (v_t - S~^T k_t); S = S~ + k_t u^T; o_t = S^T q_t."""
    q, k, v, g, beta, S = (np.asarray(a, np.float64) for a in (q, k, v, g, beta, S0))
    per = v.shape[1] // k.shape[1]
    outs = []
    for t in range(q.shape[0]):
        kt, qt = np.repeat(k[t], per, 0), np.repeat(q[t], per, 0)     # (nv, dk)
        S = np.exp(g[t])[:, None, None] * S
        u = beta[t][:, None] * (v[t] - np.einsum("hkv,hk->hv", S, kt))
        S = S + kt[:, :, None] * u[:, None, :]
        outs.append(np.einsum("hkv,hk->hv", S, qt))
    return np.stack(outs), S


def operands(key, rows, w, g_scale=1.0):
    ks = jax.random.split(key, 6)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)  # noqa: E731
    q = unit(jax.random.normal(ks[0], (rows, w, NK, DK))) * DK ** -0.5
    k = unit(jax.random.normal(ks[1], (rows, w, NK, DK)))
    v = jax.random.normal(ks[2], (rows, w, NV, DV))
    g = -g_scale * jax.nn.softplus(jax.random.normal(ks[3], (rows, w, NV)))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (rows, w, NV)))
    S0 = jax.random.normal(ks[5], (rows, NV, DK, DV))
    return q, k, v, g, beta, S0


def test_the_inverse_of_a_unit_lower_triangle():
    for C in (1, 2, 16, 32, 64):
        L = jnp.tril(jax.random.normal(jax.random.PRNGKey(C), (3, 2, C, C)), -1)
        T = jax.jit(unit_lower_inverse)(L)
        want = np.linalg.inv(np.eye(C) + np.asarray(L, np.float64))
        np.testing.assert_allclose(np.asarray(T), want, atol=1e-3 * np.abs(want).max())


@pytest.fixture(scope="module")
def mixer():
    layer = GatedDeltaMixer(H, NK, NV, DK, DV, K)
    params = layer.init(jax.random.PRNGKey(0))
    # away from the init (a norm of ones)
    leaves, treedef = jax.tree.flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(1), len(leaves))
    params = jax.tree.unflatten(treedef, [
        x + 0.2 * jax.random.normal(k, x.shape) for x, k in zip(leaves, keys)])
    return layer, params


def step_rows(q, k, v, g, beta, S0):
    """``_step_rows``'s recurrence on prepared operands: one token a row."""
    outs, S = [], S0
    per = NV // NK
    for t in range(q.shape[1]):
        Sg = S.reshape(-1, NK, per, DK, DV)
        kt, qt = k[:, t], q[:, t]
        decay = jnp.exp(g[:, t]).reshape(-1, NK, per, 1)
        kS = jnp.sum(Sg * kt[:, :, None, :, None], -2)
        qS = jnp.sum(Sg * qt[:, :, None, :, None], -2)
        u = beta[:, t].reshape(-1, NK, per, 1) * (
            v[:, t].reshape(-1, NK, per, DV) - decay * kS)
        S = (decay[..., None] * Sg + kt[:, :, None, :, None] * u[:, :, :, None, :]
             ).reshape(S0.shape)
        outs.append((decay * qS + jnp.sum(qt * kt, -1)[:, :, None, None] * u
                     ).reshape(-1, NV, DV))
    return jnp.stack(outs, 1), S


def test_the_step_equals_the_recurrence_as_a_python_loop():
    q, k, v, g, beta, S0 = operands(jax.random.PRNGKey(3), 2, 6)
    o, S = step_rows(q, k, v, g, beta, S0)
    for r in range(2):
        want_o, want_S = loop(q[r], k[r], v[r], g[r], beta[r], S0[r])
        np.testing.assert_allclose(np.asarray(o[r]), want_o, atol=ATOL, rtol=1e-5)
        np.testing.assert_allclose(np.asarray(S[r]), want_S, atol=ATOL, rtol=1e-5)


def step_reference(params, proj, ba, tail, S0, eps=1e-6):
    """One row from ``in_proj``'s output to ``out_proj``'s input as it is
    written, numpy float64: ``proj`` (W,), ``ba`` (2 nv,), ``tail`` (K - 1,
    C), ``S0`` (nv, dk, dv). Returns ``(y (nv dv,), state, tail)``."""
    f64 = lambda a: np.asarray(jnp.asarray(a, jnp.float32), np.float64)  # noqa: E731
    proj, ba, tail, S0 = f64(proj), f64(ba), f64(tail), f64(S0)
    silu = lambda a: a / (1.0 + np.exp(-a))                       # noqa: E731
    window = np.concatenate([tail, proj[None, :CONV]])
    c = silu(np.sum(window * f64(params["conv"]["weight"]).T, axis=0))
    q, k, v = (c[:NK * DK].reshape(NK, DK), c[NK * DK:2 * NK * DK].reshape(NK, DK),
               c[2 * NK * DK:].reshape(NV, DV))
    q = q / np.sqrt(np.sum(q * q, -1, keepdims=True) + 1e-6) * DK ** -0.5
    k = k / np.sqrt(np.sum(k * k, -1, keepdims=True) + 1e-6)
    beta = 1.0 / (1.0 + np.exp(-ba[:NV]))
    g = -np.exp(f64(params["A_log"])) * np.logaddexp(
        0.0, ba[NV:] + f64(params["dt_bias"]))
    o, S = loop(q[None], k[None], v[None], g[None], beta[None], S0)
    o = o[0] / np.sqrt(np.mean(o[0] * o[0], -1, keepdims=True) + eps)
    y = o * f64(params["norm"]["weight"]) * silu(proj[CONV:].reshape(NV, DV))
    return y.reshape(-1), S, window[1:]


# (first place, context, new tokens) a slot; the model's dtype
STEP_CASES = {
    "the smallest grid": ([(0, 4, 1)], jnp.float32),
    # a row's token lies in whichever block of 16 places its index falls
    "three slots over two blocks of places": (
        [(3, 9, 1), (17, 2, 1), (30, 5, 1)], jnp.float32),
    "a fresh row over a dirty slot": ([(0, 7, 1), (1, 0, 1)], jnp.float32),
    "rows of 0 and of 5 tokens beside a stepping row": (
        [(0, 3, 1), (1, 8, 5), (6, 4, 0), (6, 0, 0), (6, 2, 1)], jnp.float32),
    "bfloat16 in and out, float32 inside": (
        [(2, 6, 1), (3, 0, 1), (20, 11, 1)], jnp.bfloat16),
}


@pytest.mark.parametrize("case", list(STEP_CASES))
def test_the_step_kernel_interpreted_equals_the_python_loop(mixer, case):
    """``delta_step`` (a Pallas kernel; interpreted here, its own arithmetic)
    from ``proj`` / ``ba`` / the slot's conv tail to the gated output, the new
    state and the new tail: a slot a grid step, the row's token found among
    the tick's places through the prefetched index, the state read once and
    written over itself. A row that brings no single token keeps state AND
    tail bit for bit; a fresh row starts from zeros though its lines hold
    NaNs; with bfloat16 operands the state is still the float32 loop's."""
    from scaling_tpu.nn.gated_delta import conv_line

    layer, params = mixer
    rows, dtype = STEP_CASES[case]
    first, ctx_len, new_len = (jnp.asarray(col, jnp.int32) for col in zip(*rows))
    r, places = len(rows), 32
    params = jax.tree.map(
        lambda x: x if x.shape == (NV,) else x.astype(dtype), params)
    ks = jax.random.split(jax.random.PRNGKey(len(case)), 4)
    proj = jax.random.normal(ks[0], (places, layer.in_width)).astype(dtype)
    ba = jax.random.normal(ks[1], (places, 2 * NV)).astype(dtype)
    S0 = jax.random.normal(ks[2], (r, NV, DK, DV))
    tail = jax.random.normal(ks[3], (r, K - 1, CONV)).astype(dtype)
    fresh = np.asarray((ctx_len == 0) & (new_len == 1))
    dirty = jnp.asarray(fresh)[:, None, None]
    S0 = jnp.where(dirty[..., None], jnp.nan, S0)
    tail = jnp.where(dirty, jnp.nan, tail)
    before = kernel_build_count("delta_step", interpret=True)
    y, S, new_tail = jax.jit(lambda *a: delta_step(
        *a, params["conv"]["weight"], params["A_log"], params["dt_bias"],
        params["norm"]["weight"], layer.norm_eps, interpret=True))(
        proj, ba, first, ctx_len, new_len, S0, conv_line(tail))
    assert kernel_build_count("delta_step", interpret=True) == before + 1
    assert y.dtype == dtype and new_tail.dtype == dtype and S.dtype == jnp.float32
    assert new_tail.shape == (r, K - 1, 1, CONV)
    y, new_tail = np.asarray(y, np.float32), np.asarray(new_tail[:, :, 0], np.float32)
    # a bfloat16 result is the float32 one rounded once
    out_tol = dict(atol=ATOL, rtol=1e-5) if dtype == jnp.float32 else dict(
        atol=2e-2, rtol=2 ** -7)
    for row, (place, _, n) in enumerate(rows):
        if n != 1:   # bit for bit what the slot held
            assert np.array_equal(np.asarray(S[row]), np.asarray(S0[row]))
            assert np.array_equal(new_tail[row], np.asarray(tail[row], np.float32))
            continue
        zero = fresh[row]
        want_y, want_S, want_tail = step_reference(
            params, proj[place], ba[place],
            jnp.zeros_like(tail[row]) if zero else tail[row],
            jnp.zeros_like(S0[row]) if zero else S0[row], layer.norm_eps)
        np.testing.assert_allclose(y[row], want_y, **out_tol)
        np.testing.assert_allclose(np.asarray(S[row]), want_S, atol=ATOL, rtol=1e-5)
        assert np.array_equal(new_tail[row], want_tail.astype(np.float32))


@pytest.mark.parametrize("w", [1, 2, 31, 32, 33])
def test_the_chunk_form_equals_the_step(w):
    q, k, v, g, beta, S0 = operands(jax.random.PRNGKey(w), 3, w)
    o, S = jax.jit(delta_chunk)(q, k, v, g, beta, S0)
    for r in range(3):
        want_o, want_S = loop(q[r], k[r], v[r], g[r], beta[r], S0[r])
        np.testing.assert_allclose(np.asarray(o[r]), want_o, atol=ATOL, rtol=1e-5)
        np.testing.assert_allclose(np.asarray(S[r]), want_S, atol=ATOL, rtol=1e-5)


def test_a_chunk_with_padding_leaves_the_state_as_the_real_positions_do():
    q, k, v, g, beta, S0 = operands(jax.random.PRNGKey(7), 2, 8)
    # row 0: only its first 3 positions are tokens; row 1: none is
    real = (jnp.arange(8)[None, :] < jnp.asarray([3, 0])[:, None])[..., None]
    o, S = delta_chunk(q, k, v, jnp.where(real, g, 0.0),
                       jnp.where(real, beta, 0.0), S0)
    want_o, want = loop(q[0, :3], k[0, :3], v[0, :3], g[0, :3], beta[0, :3], S0[0])
    np.testing.assert_allclose(np.asarray(o[0, :3]), want_o, atol=ATOL, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(S[0]), want, atol=ATOL, rtol=1e-5)
    assert np.array_equal(np.asarray(S[1]), np.asarray(S0[1]))      # bit for bit


def test_a_fresh_row_starts_from_zeros_whatever_its_state_holds():
    q, k, v, g, beta, S0 = operands(jax.random.PRNGKey(8), 2, 5)
    fresh = jnp.asarray([True, False])
    o, S = delta_chunk(q, k, v, g, beta, S0.at[0].set(jnp.nan), fresh)
    want_o, want_S = loop(q[0], k[0], v[0], g[0], beta[0], np.zeros_like(S0[0]))
    np.testing.assert_allclose(np.asarray(o[0]), want_o, atol=ATOL, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(S[0]), want_S, atol=ATOL, rtol=1e-5)
    want_o, want_S = loop(q[1], k[1], v[1], g[1], beta[1], S0[1])
    np.testing.assert_allclose(np.asarray(S[1]), want_S, atol=ATOL, rtol=1e-5)


@pytest.mark.parametrize("case", ["g near -20", "beta near 0", "beta near 1"])
def test_the_gates_extremes_stay_finite_and_right(case):
    q, k, v, g, beta, S0 = operands(jax.random.PRNGKey(11), 2, 32)
    if case == "g near -20":
        g = jnp.full_like(g, -20.0)
    else:
        beta = jnp.full_like(beta, 1e-7 if case == "beta near 0" else 1.0 - 1e-7)
    o, S = jax.jit(delta_chunk)(q, k, v, g, beta, S0)
    assert np.isfinite(np.asarray(o)).all() and np.isfinite(np.asarray(S)).all()
    want_o, want_S = loop(q[0], k[0], v[0], g[0], beta[0], S0[0])
    np.testing.assert_allclose(np.asarray(o[0]), want_o, atol=ATOL, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(S[0]), want_S, atol=ATOL, rtol=1e-5)


def chunk_rows_plainly(q, k, v, g, beta, state, fresh, at, interpret=None):
    """What ``delta_chunk_rows`` replaced: the rows' lines gathered, advanced by
    ``delta_chunk`` and scattered back; a place past the pool is dropped."""
    held = jnp.minimum(at, state.shape[0] - 1)
    o, S = delta_chunk(q, k, v, g, beta, state[held], fresh)
    return o, state.at[at].set(S, mode="drop")


# (slots, places' width, the slot a place (== slots: no row fills it), tokens a
# place, the places that start from zeros)
CHUNK_KERNEL_CASES = {
    "rows of 2..32 tokens, the rest padding": (6, 32, [0, 2, 3, 5], [2, 17, 31, 32], []),
    "five places padded to the system's eight": (4, 5, [1, 2], [5, 3], []),
    "one position a place": (3, 1, [0, 2], [1, 1], []),
    "a fresh row over a line of NaNs": (4, 8, [1, 3], [8, 6], [0]),
    # under a clamp to slot ``slots - 1`` an empty place would write that
    # line's OLD copy over what the filled place advanced
    "empty places, the last slot itself a chunk row": (
        6, 8, [1, 5, 6, 6], [8, 8, 0, 0], []),
    "one row, then empty places only": (5, 4, [2, 5, 5], [3, 0, 0], [0]),
    "no place filled": (4, 4, [4, 4], [0, 0], []),
    "every place filled, the neighbours only step": (6, 4, [0, 2, 4], [4, 2, 3], [1]),
    "g near -20": (3, 32, [0, 2], [32, 32], []),
    "beta near 0": (3, 32, [1, 2], [32, 20], []),
    "beta near 1": (3, 32, [0, 1], [32, 32], []),
}


@pytest.mark.parametrize("case", list(CHUNK_KERNEL_CASES))
def test_the_chunk_kernel_interpreted_equals_the_chunk_form(case):
    """``delta_chunk_rows`` (a Pallas kernel; interpreted here, its own
    arithmetic) over a list of places against ``delta_chunk`` on the gathered
    lines: a place's read-out and its row's new line are the chunk form's, a
    line no place names is bit for bit what it was (the pool's other rows only
    step), a place no row fills moves nothing and writes nothing, whichever
    slots the filled places name, and a fresh row starts from zeros though
    its line holds NaNs."""
    slots, w, at, lens, fresh_places = CHUNK_KERNEL_CASES[case]
    R = len(at)
    q, k, v, g, beta, _ = operands(jax.random.PRNGKey(len(case)), R, w)
    if case == "g near -20":
        g = jnp.full_like(g, -20.0)
    elif case.startswith("beta near"):
        beta = jnp.full_like(beta, 1e-7 if case == "beta near 0" else 1.0 - 1e-7)
    real = (jnp.arange(w)[None, :] < jnp.asarray(lens)[:, None])[..., None]
    g, beta = jnp.where(real, g, 0.0), jnp.where(real, beta, 0.0)
    fresh = jnp.zeros((R,), bool).at[jnp.asarray(fresh_places, int)].set(True)
    state = jax.random.normal(jax.random.PRNGKey(R), (slots, NV, DK, DV))
    state = state.at[jnp.asarray([at[place] for place in fresh_places], int)].set(
        jnp.nan)
    before = kernel_build_count("delta_chunk_rows", interpret=True)
    o, S = jax.jit(lambda *a: delta_chunk_rows(*a, interpret=True))(
        q, k, v, g, beta, state, fresh, jnp.asarray(at, jnp.int32))
    assert kernel_build_count("delta_chunk_rows", interpret=True) == before + 1
    want_o, want_S = jax.jit(chunk_rows_plainly)(
        q, k, v, g, beta, state, fresh, jnp.asarray(at, jnp.int32))
    assert o.shape == (R, w, NV, DV) and S.shape == state.shape
    o, S, want_o, want_S = (np.asarray(a) for a in (o, S, want_o, want_S))
    for slot in range(slots):
        if slot not in at:   # bit for bit what the line held
            assert np.array_equal(S[slot], np.asarray(state[slot]), equal_nan=True)
            continue
        place, n = at.index(slot), lens[at.index(slot)]
        np.testing.assert_allclose(o[place], want_o[place], atol=ATOL, rtol=1e-5)
        np.testing.assert_allclose(S[slot], want_S[slot], atol=ATOL, rtol=1e-5)
        # and the recurrence's own over the row's real positions
        S0 = np.zeros((NV, DK, DV)) if place in fresh_places else state[slot]
        loop_o, loop_S = loop(q[place, :n], k[place, :n], v[place, :n],
                              g[place, :n], beta[place, :n], S0)
        np.testing.assert_allclose(o[place, :n], loop_o, atol=ATOL, rtol=1e-5)
        np.testing.assert_allclose(S[slot], loop_S, atol=ATOL, rtol=1e-5)


def test_the_init_and_the_leaves(mixer):
    layer, _ = mixer
    params = layer.init(jax.random.PRNGKey(5))
    A = np.exp(np.asarray(params["A_log"]))
    dt = np.asarray(jax.nn.softplus(params["dt_bias"]))
    assert (1.0 <= A).all() and (A <= 16.0).all()
    assert (dt >= 0.001 - 1e-7).all() and (dt <= 0.1 + 1e-6).all()
    assert params["in_proj"]["weight"].shape == (H, CONV + NV * DV)
    assert params["ba_proj"]["weight"].shape == (H, 2 * NV)
    assert params["conv"]["weight"].shape == (CONV, K)
    assert "bias" not in params["conv"]
    assert params["norm"]["weight"].shape == (DV,)
    for name in ("A_log", "dt_bias"):
        assert params[name].dtype == jnp.float32
    assert jax.tree.structure(params) == jax.tree.structure(layer.param_metas())


def test_a_sequence_longer_than_a_chunk_is_walked_chunk_by_chunk(mixer, monkeypatch):
    """75 positions = a chunk of 64 and a ragged one of 11: equal to chunks of
    16 (5 chunks, 5 positions of padding) and to one chunk of 75 (padded to
    128 inside)."""
    from scaling_tpu.nn import gated_delta

    layer, params = mixer
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 75, H))
    outs = []
    for chunk in (64, 16, 75):
        monkeypatch.setattr(gated_delta, "CHUNK", chunk)
        out, (S, tail) = jax.jit(lambda x: layer(
            params, x, ForwardContext(), return_state=True))(x)
        outs.append((np.asarray(out), np.asarray(S), np.asarray(tail)))
    for out, S, tail in outs[1:]:
        np.testing.assert_allclose(out, outs[0][0], atol=ATOL, rtol=1e-5)
        np.testing.assert_allclose(S, outs[0][1], atol=ATOL, rtol=1e-5)
        assert np.array_equal(tail, outs[0][2])
    assert outs[0][2].shape == (2, K - 1, 1, CONV)


def run_tick(layer, params, x_rows, lines, ctx_len, new_len, w, width=None):
    """One tick of the served path: row ``r`` brings ``x_rows[r]`` (new_len[r],
    H). ``width`` None: the row-major caller; else token-major at that many
    places. Returns ``([each row's outputs], state, conv)``."""
    state, conv = lines
    if width is None:
        batch = jnp.stack([jnp.pad(r, ((0, w - r.shape[0]), (0, 0))) for r in x_rows])
        tmap = None
    else:
        packed = jnp.concatenate(x_rows)
        batch = jnp.pad(packed, ((0, width - packed.shape[0]), (0, 0)))
        batch = batch.reshape(width // w, w, H)
        tmap = packed_token_map(jnp.asarray(new_len, jnp.int32), batch.shape[:2], w)
    out, view = jax.jit(lambda b, v: layer(params, b, ForwardContext(), state=v))(
        batch, DeltaStateView(
            state, conv, jnp.asarray(ctx_len, jnp.int32),
            jnp.asarray(new_len, jnp.int32), tmap))
    out = np.asarray(out)
    if width is None:
        outs = [out[r, :n] for r, n in enumerate(new_len)]
    else:
        ends = np.cumsum(new_len)
        outs = [out.reshape(-1, H)[e - n:e] for e, n in zip(ends, new_len)]
    return outs, np.asarray(view.state), np.asarray(view.conv)


@pytest.mark.parametrize("token_major", [False, True], ids=["row-major", "token-major"])
def test_state_carried_across_ticks_equals_one_pass(mixer, token_major):
    """A row served a chunk of 32, then 5 + 1 + 1 + 1 tokens against its line,
    beside an empty slot and a row that starts later in a REUSED slot (its
    lines hold an old occupant's values): each row's outputs are its
    sequence's in one uncached pass. Token-major, at the fewest whole rows of
    places that hold a tick's tokens, the ticks cross both forms: whole rows,
    a step beside a gathered chunk, steps alone."""
    layer, params = mixer
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 40, H))
    chunk_kernels = kernel_build_count("delta_chunk_rows", interpret=True)
    want = np.asarray(jax.jit(lambda x: layer(params, x, ForwardContext()))(x))
    # the uncached pass is plain ``jax.numpy``; every served tick below
    # advances its chunk rows through the kernel, their lines in place
    assert kernel_build_count("delta_chunk_rows", interpret=True) == chunk_kernels
    slots, w = 3, 32
    lines = (jnp.full((slots, NV, DK, DV), 7.0),           # an old occupant's
             jnp.full((slots, K - 1, 1, CONV), 7.0))
    got = {0: [], 2: []}
    seen = [0, 0]
    for n0, n2 in ((32, 0), (5, 32), (1, 1), (1, 1), (1, 6)):
        new_len, ctx_len = [n0, 0, n2], [seen[0], 0, seen[1]]
        rows = [x[0, seen[0]:seen[0] + n0], x[1, :0], x[1, seen[1]:seen[1] + n2]]
        width = -(-(n0 + n2) // w) * w if token_major else None
        outs, *lines = run_tick(layer, params, rows, lines, ctx_len, new_len, w, width)
        for slot in got:
            got[slot].append(outs[slot])
        seen = [seen[0] + n0, seen[1] + n2]
    np.testing.assert_allclose(np.concatenate(got[0]), want[0], atol=ATOL, rtol=1e-5)
    np.testing.assert_allclose(np.concatenate(got[2]), want[1], atol=ATOL, rtol=1e-5)
    assert kernel_build_count("delta_chunk_rows", interpret=True) == chunk_kernels + 5
    # the empty slot's lines were never written
    assert np.array_equal(lines[0][1], np.full((NV, DK, DV), 7.0))
    assert np.array_equal(lines[1][1], np.full_like(lines[1][1], 7.0))


def test_a_served_row_wider_than_a_chunk_advances_its_line_chunk_by_chunk(
        mixer, monkeypatch):
    """A row-major tick of rows wider than ``CHUNK`` (16 here: 40 and 23
    positions are three chunks, the second row's last one all padding) walks
    the chunks through the kernel, the pool's whole leaf the carry: each row's
    outputs and lines are the uncached pass's, from zeros though the slots
    held an old occupant's values."""
    from scaling_tpu.nn import gated_delta

    layer, params = mixer
    monkeypatch.setattr(gated_delta, "CHUNK", 16)
    x = jax.random.normal(jax.random.PRNGKey(9), (2, 40, H))
    lens = [40, 23]
    lines = (jnp.full((2, NV, DK, DV), 7.0), jnp.full((2, K - 1, 1, CONV), 7.0))
    chunk_kernels = kernel_build_count("delta_chunk_rows", interpret=True)
    outs, S, tail = run_tick(layer, params, [x[r, :n] for r, n in enumerate(lens)],
                             lines, [0, 0], lens, 40)
    assert kernel_build_count("delta_chunk_rows", interpret=True) == chunk_kernels + 1
    for r, n in enumerate(lens):
        want, (want_S, want_tail) = jax.jit(lambda x: layer(
            params, x, ForwardContext(), return_state=True))(x[r:r + 1, :n])
        np.testing.assert_allclose(outs[r], np.asarray(want[0]), atol=ATOL, rtol=1e-5)
        np.testing.assert_allclose(S[r], np.asarray(want_S[0]), atol=ATOL, rtol=1e-5)
        assert np.array_equal(tail[r], np.asarray(want_tail[0]))


def width_for(new_len, widths, w):
    """The engine's rule (serve/engine.py): the smallest width that holds the
    tick's tokens AND its multi-token rows."""
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
    "a chunk row in the last slot beside a filler place": (
        [1, 1, 1, 0, 1, 1, 1, 3], [3, 5, 2, 0, 7, 6, 1, 6]),
}


@pytest.mark.parametrize("reference", [
    "row-major", "token-major at the full width",
    "row-major, the lines gathered and advanced as plain jax.numpy"])
@pytest.mark.parametrize("case", list(SPLIT_TICKS))
def test_each_row_in_its_own_form_equals_the_whole_rows_form(
        mixer, case, reference, monkeypatch):
    """Below the full width a row that brings one token takes the single step
    and the few that bring more advance through the chunk rows' kernel, each
    line where it lies: outputs, state lines and conv tails are the
    whole-rows form's, whichever caller reaches that, and whether that form
    runs the kernel over every row or ``delta_chunk`` as plain ``jax.numpy``
    over gathered lines. A row at context 0 starts from zeros though its slot
    holds an old occupant's NaNs; an empty row's lines are not touched."""
    from scaling_tpu.nn import gated_delta

    layer, params = mixer
    new_len, ctx_len = SPLIT_TICKS[case]
    small, full = SPLIT_WIDTHS
    width = width_for(new_len, SPLIT_WIDTHS, SPLIT_W)
    assert width == (full if case.startswith("R + 1") else small)
    slots, w = len(new_len), SPLIT_W
    ks = jax.random.split(jax.random.PRNGKey(len(case)), 3)
    starts_over = [r for r in range(slots) if ctx_len[r] == 0 and new_len[r] > 0]
    lines = tuple(
        jax.random.normal(k, shape).at[jnp.asarray(starts_over, int)].set(jnp.nan)
        for k, shape in ((ks[0], (slots, NV, DK, DV)), (ks[1], (slots, K - 1, 1, CONV))))
    x = jax.random.normal(ks[2], (slots, w, H))
    x_rows = [x[r, :n] for r, n in enumerate(new_len)]
    chunk_kernels = kernel_build_count("delta_chunk_rows", interpret=True)
    got = run_tick(layer, params, x_rows, lines, ctx_len, new_len, w, width)
    assert kernel_build_count("delta_chunk_rows", interpret=True) == chunk_kernels + 1
    if reference.endswith("plain jax.numpy"):
        monkeypatch.setattr(gated_delta, "delta_chunk_rows", chunk_rows_plainly)
    want = run_tick(layer, params, x_rows, lines, ctx_len, new_len, w,
                    None if reference.startswith("row-major") else full)
    for r, n in enumerate(new_len):
        np.testing.assert_allclose(got[0][r], want[0][r], atol=ATOL, rtol=1e-5)
        assert not n or np.isfinite(got[0][r]).all()
        if not n:   # bit for bit what the slot held
            assert np.array_equal(got[1][r], np.asarray(lines[0][r]), equal_nan=True)
            assert np.array_equal(got[2][r], np.asarray(lines[1][r]), equal_nan=True)
    np.testing.assert_allclose(got[1], want[1], atol=ATOL, rtol=1e-5)
    np.testing.assert_allclose(got[2], want[2], atol=ATOL, rtol=1e-5)


def test_a_bfloat16_state_is_not_the_float32_one():
    """What the serving pool must not do: 40 steps with the state rounded to
    bfloat16 after each leave the outputs off by 30 x ATOL and more."""
    q, k, v, g, beta, S0 = operands(jax.random.PRNGKey(13), 1, 40, g_scale=0.05)
    want, _ = loop(q[0], k[0], v[0], g[0], beta[0], S0[0])
    S, outs = S0, []
    for t in range(40):
        o, S = step_rows(*(a[:, t:t + 1] for a in (q, k, v, g, beta)), S)
        S = S.astype(jnp.bfloat16).astype(jnp.float32)
        outs.append(np.asarray(o[0, 0]))
    assert np.abs(np.stack(outs) - want).max() > 30 * ATOL
