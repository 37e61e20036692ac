"""``nn/hyper_connection.py``: a sub-layer's mapping (``pre``, ``post``) and
the readout against the benchmark's plain reference, in float32; the Sinkhorn
kernel (interpreted here) against plain ``(n, n)`` matrices; how far from
doubly stochastic 20 steps leave ``H_res``; that the comparison SEES each part
of the mapping (one Sinkhorn step for 20, a transposed ``H_res``, a dropped
clamp, ``H_post`` without its factor 2, each fails it at the seeded weights);
and the bf16 stream's projection, float32 in earnest at one bf16 pass."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import cells
from scaling_tpu.nn import hyper_connection as hc
from scaling_tpu.nn.hyper_connection import HyperConnection, HyperReadout

N, C, TOKENS = 4, 32, 40
ITERS, EPS, CLAMP, NORM_EPS = 20, 1e-6, (-30.0, 30.0), 1e-6
SPEC = {"hc_streams": N, "hc_sinkhorn_iters": ITERS, "hc_eps": EPS,
        "hc_clamp": CLAMP, "eps": NORM_EPS}
# float32 on both sides: the order of the sums differs (the module's token
# axis is minor, the reference's matrices are (tokens, n, n))
ATOL = 1e-5


@pytest.fixture(scope="module")
def reference():
    return cells.load_module(cells.ROOT, "reference", "hc_latent_moe_decoder",
                             cells.REFERENCE_CONTRACT)


def mapping_of(**changed):
    return HyperConnection(**{
        "hidden_size": C, "streams": N, "sinkhorn_iters": ITERS, "eps": EPS,
        "clamp": CLAMP, "norm_eps": NORM_EPS, **changed})


@pytest.fixture(scope="module")
def seeded():
    """The seeded init: biases of ``H_res`` uniform over +-60, so the clamp
    acts and 20 Sinkhorn steps are far from their limit."""
    params = mapping_of().init(jax.random.PRNGKey(0))
    assert float(jnp.abs(params["bias"][2 * N:]).max()) > 30.0
    return params


@pytest.fixture(scope="module")
def streams():
    kx, ky = jax.random.split(jax.random.PRNGKey(1))
    x = jax.random.normal(kx, (2, TOKENS // 2, N * C), jnp.float32)
    # streams that differ, as after a few sub-layers
    x = x * jnp.repeat(jnp.array([1.0, 0.5, 2.0, 1.5]), C)
    y = jax.random.normal(ky, (2, TOKENS // 2, C), jnp.float32)
    return x, y


def by_the_reference(reference, params, x, y):
    """``(u, X')`` of the reference's equations on ``(tokens, n, C)``."""
    with jax.default_matmul_precision("highest"):
        X = x.reshape(-1, N, C)
        h_pre, h_post, h_res = reference.mapping(X, params, SPEC)
        u = jnp.einsum("sj,sjc->sc", h_pre, X)
        out = reference.hyper_connected(X, params, SPEC, lambda _: y.reshape(-1, C))
    return u, out.reshape(-1, N * C), (h_pre, h_post, h_res)


def test_pre_and_post_are_the_references_equations(reference, seeded, streams):
    x, y = streams
    u, mix = mapping_of().pre(seeded, x)
    out = mapping_of().post(x, y, mix)
    assert u.shape == (2, TOKENS // 2, C) and out.shape == x.shape
    assert mix.shape == (TOKENS, N * N + N)
    want_u, want_out, (_, h_post, h_res) = by_the_reference(reference, seeded, x, y)
    np.testing.assert_allclose(u.reshape(-1, C), want_u, atol=ATOL)
    np.testing.assert_allclose(out.reshape(-1, N * C), want_out, atol=ATOL)
    np.testing.assert_allclose(mix[:, :N * N].reshape(-1, N, N), h_res, atol=ATOL)
    np.testing.assert_allclose(mix[:, N * N:], h_post, atol=ATOL)
    # the mapping depends on the token: H_res is no constant of the weights
    assert float(jnp.abs(h_res - h_res[0]).max()) > 0.05


def test_the_readout_is_the_references(reference, streams):
    x, _ = streams
    readout = HyperReadout(C, N, EPS, NORM_EPS)
    params = readout.init(jax.random.PRNGKey(2))
    with jax.default_matmul_precision("highest"):
        want = reference.readout(x.reshape(-1, N, C), params, SPEC)
    got = readout(params, x)
    assert got.shape == (2, TOKENS // 2, C)
    np.testing.assert_allclose(got.reshape(-1, C), want, atol=ATOL)


def test_the_kernel_is_twenty_steps_on_plain_matrices(reference):
    """``sinkhorn_tokens`` pads the tokens to whole registers and gives back
    what the reference's ``(tokens, n, n)`` loop gives, token for token."""
    s = jnp.clip(12.0 * jax.random.normal(jax.random.PRNGKey(3), (N * N, 1500)),
                 *CLAMP)
    got = hc.sinkhorn_tokens(s, n=N, iters=ITERS, eps=EPS, interpret=True)
    want = reference.doubly_stochastic(s.T.reshape(-1, N, N), ITERS, EPS)
    np.testing.assert_allclose(got.T.reshape(-1, N, N), want, atol=ATOL)


def test_twenty_steps_leave_moderate_logits_doubly_stochastic(seeded, streams):
    """Logits that spread by a unit or two (a trained mapping's: the paper
    starts a_res at 0.01) are doubly stochastic to 2e-3 after 20 steps. The
    SEEDED biases spread by +-60: the limit then has zeros, the sums approach
    1 like 1 / steps, and a row is still a few percent off, which is what lets
    the comparison tell 20 steps from fewer (the next test)."""
    x, _ = streams
    moderate = dict(seeded, alpha=seeded["alpha"].at[2].set(0.25),
                    bias=seeded["bias"].at[2 * N:].multiply(1.0 / 60.0))
    _, mix = mapping_of().pre(moderate, x)
    h_res = mix[:, :N * N].reshape(-1, N, N)
    assert float(jnp.abs(h_res.sum(axis=-1) - 1).max()) < 2e-3
    assert float(jnp.abs(h_res.sum(axis=-2) - 1).max()) < 2e-3
    assert float(h_res.min()) >= 0
    _, mix = mapping_of().pre(seeded, x)
    wide = mix[:, :N * N].reshape(-1, N, N)
    assert float(jnp.abs(wide.sum(axis=-2) - 1).max()) < 1e-5   # the last step
    assert 5e-3 < float(jnp.abs(wide.sum(axis=-1) - 1).max()) < 0.2


def transposed(self, x, y, mix):
    mix = jnp.concatenate([
        mix[:, :N * N].reshape(-1, N, N).swapaxes(1, 2).reshape(-1, N * N),
        mix[:, N * N:]], axis=1)
    return HyperConnection.post(self, x, y, mix)


def without_the_factor(self, x, y, mix):
    return HyperConnection.post(self, x, y, mix.at[:, N * N:].multiply(0.5))


@pytest.mark.parametrize("mutation,changed,post", [
    ("one Sinkhorn step for 20", {"sinkhorn_iters": 1}, None),
    ("a transposed H_res", {}, transposed),
    ("a dropped clamp", {"clamp": (-1e9, 1e9)}, None),
    ("H_post without its factor 2", {}, without_the_factor),
])
def test_each_part_of_the_mapping_is_seen_by_the_comparison(
        reference, seeded, streams, mutation, changed, post):
    x, y = streams
    mapping = mapping_of(**changed)
    _, mix = mapping.pre(seeded, x)
    out = (post or HyperConnection.post)(mapping, x, y, mix)
    _, want, _ = by_the_reference(reference, seeded, x, y)
    worst = float(jnp.abs(out.reshape(-1, N * C) - want).max())
    assert worst > 1000 * ATOL, (mutation, worst)


def test_a_bf16_streams_projection_is_float32_in_earnest():
    """``x phi`` of a bf16 stream: three bf16 terms of ``phi`` in ONE matmul
    give what float32 at the highest precision gives (a bf16 ``phi`` would be
    off by 2 ** -9 of every term)."""
    kx, kp = jax.random.split(jax.random.PRNGKey(4))
    x = jax.random.normal(kx, (64, N * C), jnp.float32).astype(jnp.bfloat16)
    phi = jax.random.normal(kp, (N * C, N * N + 2 * N), jnp.float32)
    want = jnp.einsum("td,dk->kt", x.astype(jnp.float32), phi,
                      precision=jax.lax.Precision.HIGHEST)
    got = hc.project(x, phi)
    assert got.dtype == jnp.float32 and got.shape == (N * N + 2 * N, 64)
    np.testing.assert_allclose(got, want, atol=2e-5)
    rounded = jnp.einsum("td,dk->kt", x.astype(jnp.float32),
                         phi.astype(jnp.bfloat16).astype(jnp.float32),
                         precision=jax.lax.Precision.HIGHEST)
    assert float(jnp.abs(rounded - want).max()) > 100 * 2e-5


def test_the_leaves_are_float32_and_counted():
    """A mapping is 344,091 parameters at 4 streams of 3,584, the readout
    57,349 (abstract shapes: nothing is made)."""
    mapping = jax.eval_shape(
        HyperConnection(3584, 4, 20, 1e-6, CLAMP, 1e-6).init, jax.random.PRNGKey(0))
    readout = jax.eval_shape(HyperReadout(3584, 4, 1e-6, 1e-6).init,
                             jax.random.PRNGKey(0))
    assert {x.dtype for x in jax.tree.leaves((mapping, readout))} == {jnp.dtype("float32")}
    assert sum(x.size for x in jax.tree.leaves(mapping)) == 14_336 * 24 + 3 + 24 == 344_091
    assert sum(x.size for x in jax.tree.leaves(readout)) == 14_336 * 4 + 1 + 4 == 57_349
    metas = HyperConnection(3584, 4, 20, 1e-6, CLAMP, 1e-6).param_metas()
    assert set(metas) == set(mapping) and metas["phi"].parameter_name == "phi"
