"""Memory-lean cross entropy (ops/cross_entropy.py): identical fp32 math
and gradients to the autodiff log_softmax path, with a COMPILED-memory
win — the fp32 (b, s, vocab) residual must actually be gone, asserted on
XLA's buffer assignment, not claimed."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from scaling_tpu.ops.cross_entropy import cross_entropy_from_logits


def ref_loss(logits, targets):
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    return -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_loss_matches_log_softmax_reference(dtype):
    rng = np.random.default_rng(0)
    logits = jnp.asarray(rng.normal(size=(2, 16, 97)) * 3, dtype)
    targets = jnp.asarray(rng.integers(0, 97, size=(2, 16)), jnp.int32)
    np.testing.assert_allclose(
        np.asarray(cross_entropy_from_logits(logits, targets)),
        np.asarray(ref_loss(logits, targets)),
        rtol=1e-6, atol=1e-6,
    )


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_gradients_match_reference(dtype):
    rng = np.random.default_rng(1)
    logits = jnp.asarray(rng.normal(size=(2, 8, 64)), dtype)
    targets = jnp.asarray(rng.integers(0, 64, size=(2, 8)), jnp.int32)
    w = jnp.asarray(rng.uniform(0.1, 1.0, size=(2, 8)), jnp.float32)

    g_new = jax.grad(
        lambda lg: (cross_entropy_from_logits(lg, targets) * w).sum()
    )(logits)
    g_ref = jax.grad(lambda lg: (ref_loss(lg, targets) * w).sum())(logits)
    assert g_new.dtype == dtype  # cotangent stays in the primal dtype
    np.testing.assert_allclose(
        np.asarray(g_new, np.float32), np.asarray(g_ref, np.float32),
        rtol=1e-5, atol=1e-6,
    )


def test_backward_drops_the_fp32_residual():
    """head-matmul + loss, fwd+bwd, compiled: the custom VJP must use LESS
    temp memory than autodiff of log_softmax — by at least the fp32
    (b, s, vocab) residual it exists to eliminate."""
    b, s, d, v = 4, 256, 128, 8192
    h = jax.ShapeDtypeStruct((b, s, d), jnp.bfloat16)
    w_head = jax.ShapeDtypeStruct((d, v), jnp.bfloat16)
    t = jax.ShapeDtypeStruct((b, s), jnp.int32)

    def temp_bytes(loss_fn):
        def f(h, w_head, t):
            return loss_fn(h @ w_head, t).mean()

        compiled = jax.jit(jax.grad(f, argnums=(0, 1))).lower(h, w_head, t).compile()
        return compiled.memory_analysis().temp_size_in_bytes

    saved = temp_bytes(ref_loss) - temp_bytes(cross_entropy_from_logits)
    residual = b * s * v * 4  # the fp32 log-probabilities
    assert saved >= residual, (saved, residual)


# -- the vocabulary sharded over the model axis (ISSUE 54) -----------------
VOCAB = 1000  # two shards of 500; no other dimension below is 1000 or 500


def sharded_case(devices, dtype):
    """Logits ``(data, None, model)`` on a 2 x 2 mesh with the targets a
    shard's ends can get wrong: the first and last column of each half."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from scaling_tpu.topology.topology import DATA_AXIS, MODEL_AXIS

    mesh = Mesh(np.asarray(devices[:4]).reshape(2, 2), (DATA_AXIS, MODEL_AXIS))
    rng = np.random.default_rng(2)
    logits = jnp.asarray(rng.normal(size=(4, 6, VOCAB)) * 3, dtype)
    targets = rng.integers(0, VOCAB, size=(4, 6))
    targets[:, :4] = [0, VOCAB // 2 - 1, VOCAB // 2, VOCAB - 1]
    targets = jnp.asarray(targets, jnp.int32)
    weights = rng.uniform(0.1, 1.0, size=(4, 6))
    weights[1] = 0.0  # a row masked whole
    weights = jnp.asarray(weights, jnp.float32)
    placed = (
        jax.device_put(logits, NamedSharding(mesh, P(DATA_AXIS, None, MODEL_AXIS))),
        jax.device_put(targets, NamedSharding(mesh, P(DATA_AXIS, None))),
        jax.device_put(weights, NamedSharding(mesh, P(DATA_AXIS, None))),
    )
    return (logits, targets, weights), placed


def weighted(loss_fn):
    def f(logits, targets, weights):
        loss = (loss_fn(logits, targets) * weights).sum() / weights.sum()
        correct = (logits.argmax(-1) == targets).astype(jnp.float32)
        return loss, (correct * weights).sum() / weights.sum()

    return jax.jit(jax.value_and_grad(f, has_aux=True))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_sharded_vocabulary_gives_the_one_device_loss_and_gradient(devices, dtype):
    """Loss, accuracy and cotangent over vocabulary shards equal the whole
    rows' (and the gather-and-log_softmax reference's): targets on both ends
    of each shard, one row of zero weights."""
    whole, placed = sharded_case(devices, dtype)
    (loss_s, acc_s), grad_s = weighted(cross_entropy_from_logits)(*placed)
    (loss_1, acc_1), grad_1 = weighted(cross_entropy_from_logits)(*whole)
    (loss_r, _), grad_r = weighted(ref_loss)(*whole)
    assert grad_s.dtype == dtype and grad_s.sharding == placed[0].sharding
    np.testing.assert_allclose(float(loss_s), float(loss_1), rtol=1e-6)
    np.testing.assert_allclose(float(loss_s), float(loss_r), rtol=1e-6)
    assert float(acc_s) == float(acc_1)
    for other in (grad_1, grad_r):
        np.testing.assert_allclose(
            np.asarray(grad_s, np.float32), np.asarray(other, np.float32),
            rtol=1e-5, atol=1e-6)
    assert not np.asarray(grad_s, np.float32)[1].any()  # the masked row


def test_sharded_vocabulary_is_never_gathered(devices):
    """The compiled loss + backward over ``(data, None, model)`` logits holds
    no array as wide as the vocabulary: what crosses the model axis is one
    number a position."""
    import re

    _, placed = sharded_case(devices, jnp.bfloat16)
    text = weighted(cross_entropy_from_logits).lower(*placed).compile().as_text()
    assert re.search(rf"\[[0-9,]*\b{VOCAB // 2}\]", text)  # the shard is there
    wide = [line for line in text.splitlines()
            if re.search(rf"\[[0-9,]*\b{VOCAB}\b[0-9,]*\]", line)]
    assert not wide, wide[:3]


@pytest.mark.parametrize("target", [-1, VOCAB, VOCAB + 7])
def test_a_target_outside_the_vocabulary_selects_no_column(target):
    """No column compares equal: the loss is the row's logsumexp and the
    cotangent the plain softmax (``one_hot`` gave the same zeros)."""
    rng = np.random.default_rng(3)
    logits = jnp.asarray(rng.normal(size=(1, 2, VOCAB)), jnp.float32)
    targets = jnp.full((1, 2), target, jnp.int32)
    loss, grad = jax.value_and_grad(
        lambda lg: cross_entropy_from_logits(lg, targets).sum())(logits)
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    np.testing.assert_allclose(float(loss), float(lse.sum()), rtol=1e-6)
    np.testing.assert_allclose(
        np.asarray(grad), np.asarray(jax.nn.softmax(logits, axis=-1)),
        rtol=1e-5, atol=1e-7)
