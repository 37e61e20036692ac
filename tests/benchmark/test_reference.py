"""The plain reference agrees with the program's model on seeded weights,
for both branches of the family: RMSNorm / SwiGLU / no bias (Mistral) and
LayerNorm / GELU / biases (Pharia)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import model
from benchmark.reference import dense_decoder as ref
from benchmark.views import dense_decoder as view

BASE = dict(vocab_size=96, hidden_size=64, num_attention_heads=4,
            attention_num_kv_heads=2, attention_qkv_in_one=False,
            relative_position_embedding_type="rotary",
            rotary_embedding_base=1000000, sequence_length=32, causal=True,
            weight_tying=False, layernorm={"layernorm_epsilon": 1e-5})
BRANCHES = {
    "rms-swiglu-nobias": dict(mlp_type="swiglu", mlp_factor=3.5, norm_type="rms",
                              attention_bias=False, mlp_bias=False),
    "layernorm-gelu-bias": dict(mlp_type="default", mlp_factor=4.0,
                                norm_type="layernorm", attention_bias=True,
                                mlp_bias=True, activation_function="gelu"),
}
TOPOLOGY = dict(model_parallel_size=1, pipe_parallel_size=1, data_parallel_size=1,
                micro_batch_size=1, gradient_accumulation_steps=1)
# float32 on both sides, same mathematics in another order of summation:
# logits of magnitude ~1 agree to a few float32 roundings per layer. 2e-4
# would already fail a bf16 computation (2**-9 = 2e-3 per rounding).
LOGIT_ATOL = 2e-4
LOSS_ATOL = 2e-4
# bf16 weights and activations against the float32 reference on the same
# (bf16) weights: the program rounds every activation to 8 significant bits
# (2**-9 relative) over two layers, and its logits, up to ~6 in magnitude
# here, come out in bf16 steps of 2**-5 = 0.031. Held to four such steps on
# a logit and to 0.02 on the mean loss; a dropped term moves both by more
# than ten times that (checked below with the rotary base).
BF16_LOGIT_ATOL = 0.125
BF16_LOSS_ATOL = 2e-2


def build(branch, precision):
    from scaling_tpu.models.transformer.model import init_model, loss_function

    arch = {**BASE, **BRANCHES[branch], "num_layers": 2, "precision": precision}
    cfg = model.transformer_config(
        {"transformer_architecture": arch, "topology": TOPOLOGY}, {})
    module = init_model(cfg, None)
    params = module.init_params(jax.random.PRNGKey(3))
    # biases start at zero and norm weights at one: perturb every leaf so
    # that each of them is really exercised
    leaves, treedef = jax.tree.flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(4), len(leaves))
    params = jax.tree.unflatten(treedef, [
        (x.astype(jnp.float32) + 0.1 * jax.random.normal(k, x.shape)).astype(x.dtype)
        for x, k in zip(leaves, keys)])
    return arch, module, params, loss_function


@pytest.mark.parametrize("precision,logit_atol,loss_atol", [
    ("float32", LOGIT_ATOL, LOSS_ATOL), ("bfloat16", BF16_LOGIT_ATOL, BF16_LOSS_ATOL)])
@pytest.mark.parametrize("branch", sorted(BRANCHES))
def test_reference_agrees_with_the_model(branch, precision, logit_atol, loss_atol):
    arch, module, params, loss_function = build(branch, precision)
    rng = np.random.default_rng(0)
    s = arch["sequence_length"]
    tokens = rng.integers(1, arch["vocab_size"], size=(1, s)).astype(np.int32)
    targets = np.roll(tokens, -1, axis=-1)
    weights = np.zeros((1, s), np.float32)
    weights[0, :20] = 1.0  # the train cell's check keeps leading positions only
    batch = {"token_ids": jnp.asarray(tokens), "target_token_ids": jnp.asarray(targets),
             "position_ids": jnp.arange(s, dtype=jnp.int32)[None],
             "segment_ids": jnp.zeros((1, s), jnp.int32),
             "loss_weights": jnp.asarray(weights)}
    with jax.default_matmul_precision("highest"):
        out = module.build_forward()(params, batch)
        loss, _ = loss_function(out, batch)
    got = np.asarray(out["activations"][0], np.float32)

    logits = ref.forward(view.reference_weights(params, arch), jnp.asarray(tokens[0]),
                         view.reference_spec(arch))
    want = np.asarray(logits)
    assert want.dtype == np.float32
    np.testing.assert_allclose(got, want, atol=logit_atol, rtol=0)
    want_loss = float(ref.token_loss(logits[:20], jnp.asarray(targets[0, :20])).mean())
    assert abs(float(loss) - want_loss) < loss_atol
    # the tolerance has teeth: dropping the rotary positions moves the logits
    spec = {**view.reference_spec(arch), "rope_base": 10.0}
    moved = np.asarray(ref.forward(view.reference_weights(params, arch),
                                   jnp.asarray(tokens[0]), spec))
    assert np.abs(moved - want).max() > 5 * logit_atol


def test_head_positions_and_padding():
    """Logits of chosen positions only, and padding after them changes
    nothing (attention is causal): what the serve check relies on."""
    arch, _, params, _ = build("rms-swiglu-nobias", "float32")
    weights, spec = view.reference_weights(params, arch), view.reference_spec(arch)
    tokens = jnp.arange(1, 25, dtype=jnp.int32)
    full = np.asarray(ref.forward(weights, tokens, spec))
    padded = jnp.concatenate([tokens[:16], jnp.zeros((16,), jnp.int32)])
    part = np.asarray(ref.forward(weights, padded, spec,
                                  head_positions=jnp.asarray([3, 15])))
    np.testing.assert_allclose(part, full[[3, 15]], atol=1e-5, rtol=0)
