"""The head's logits stay sharded over the vocabulary under tensor
parallelism, through the loss and its backward (ISSUE 54): what a TP x DP
layout computes equals the one-device step's, whatever the sequence
parallelism, ZeRO, gradient accumulation or pipeline around it, and the step
says at build time which layout it was built with."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from scaling_tpu.models.transformer import TransformerConfig
from scaling_tpu.models.transformer.model import (
    init_model,
    init_optimizer,
    loss_function,
)
from scaling_tpu.obs import get_registry
from scaling_tpu.topology import Topology

VOCAB, SEQ, ROWS = 96, 16, 4  # two shards of 48 columns


def make_config(mp=1, dp=1, pp=1, gas=1, sp=False, zero=False,
                precision="float32", **arch):
    return TransformerConfig.from_dict({
        "topology": {
            "model_parallel_size": mp, "pipe_parallel_size": pp,
            "data_parallel_size": dp, "micro_batch_size": ROWS // dp,
            "gradient_accumulation_steps": gas, "sequence_parallel": sp,
        },
        "transformer_architecture": {
            "vocab_size": VOCAB, "hidden_size": 32, "num_layers": 2,
            "num_attention_heads": 4, "sequence_length": SEQ,
            "precision": precision, "weight_tying": False, **arch,
        },
        "optimizer": {"gradient_clipping": 1.0, "zero": zero,
                      "loss_scaler": {"enable": False}},
        "learning_rate_scheduler": {
            "learning_rate": 0.01, "learning_rate_warmup_steps": 2,
            "learning_rate_decay_iters": 50,
        },
        "trainer": {"train_iterations": 1, "seed": 0},
        "data": {}, "logger": {"log_dir": None},
    })


def make_batch(gas=1, seed=0):
    """``(gas, ROWS, SEQ)`` micro batches whose targets sit on both ends of
    each vocabulary shard, with one row of every micro batch masked whole."""
    rng = np.random.default_rng(seed)
    ids = rng.integers(1, VOCAB, size=(gas, ROWS, SEQ + 1))
    ids[:, :, 1:5] = [0, VOCAB // 2 - 1, VOCAB // 2, VOCAB - 1]
    weights = rng.uniform(0.2, 1.0, size=(gas, ROWS, SEQ))
    weights[:, 2] = 0.0
    return {
        "token_ids": jnp.asarray(ids[..., :-1], jnp.int32),
        "target_token_ids": jnp.asarray(ids[..., 1:], jnp.int32),
        "position_ids": jnp.broadcast_to(
            jnp.arange(SEQ, dtype=jnp.int32), (gas, ROWS, SEQ)),
        "segment_ids": jnp.zeros((gas, ROWS, SEQ), jnp.int32),
        "loss_weights": jnp.asarray(weights, jnp.float32),
    }


def built(config):
    topology = Topology(config.topology)
    module = init_model(config, topology)
    params = module.shard_params(module.init_params(jax.random.PRNGKey(0)))
    return topology, module, params


def loss_and_gradients(config, batch):
    """Loss, metrics and the gradient of every parameter for one micro
    batch, as host arrays."""
    _, module, params = built(config)
    micro = module.shard_batch(jax.tree.map(lambda x: x[0], batch), stacked=False)

    def f(p, mb):
        ctx = module._make_ctx(deterministic=True, dropout_key=None)
        return loss_function(module.forward(p, mb, ctx), mb)

    (loss, metrics), grads = jax.jit(jax.value_and_grad(f, has_aux=True))(
        params, micro)
    leaves = {jax.tree_util.keystr(k): np.asarray(v, np.float32)
              for k, v in jax.tree_util.tree_leaves_with_path(grads)}
    return float(loss), float(metrics["accuracy"]), leaves


def train_losses(config, batch, steps=3):
    """Losses and accuracies of ``steps`` steps of the built train step on
    one batch, and what the step's gauge read when it was built."""
    topology, module, params = built(config)
    optimizer = init_optimizer(config, module, topology)
    opt_state = optimizer.init_state(params)
    gauge = get_registry().gauge("train_loss_vocab_shards")
    gauge.set(-1)
    step = module.build_train_step(optimizer, loss_function)
    shards = gauge.value
    placed = module.shard_batch(batch, stacked=True)
    out = []
    for i in range(steps):
        params, opt_state, loss, metrics, _ = step(
            params, opt_state, placed, jax.random.PRNGKey(i))
        out.append((float(loss), float(metrics["accuracy"])))
    return np.asarray(out, np.float32), shards


# bf16: a TP rank rounds its partial sums where one device rounds the whole
TOLERANCE = {"float32": dict(rtol=2e-4, atol=2e-5),
             "bfloat16": dict(rtol=5e-2, atol=2e-2)}


@pytest.mark.parametrize("sp", [False, True], ids=["tp", "tp+sp"])
@pytest.mark.parametrize("precision", ["float32", "bfloat16"])
def test_loss_accuracy_and_every_gradient_equal_one_device(devices, precision, sp):
    batch = make_batch()
    loss_1, acc_1, grads_1 = loss_and_gradients(
        make_config(precision=precision), batch)
    loss_s, acc_s, grads_s = loss_and_gradients(
        make_config(mp=2, dp=2, sp=sp, precision=precision), batch)
    tol = TOLERANCE[precision]
    np.testing.assert_allclose(loss_s, loss_1, **tol)
    np.testing.assert_allclose(acc_s, acc_1, atol=1e-6 if precision == "float32" else 0.05)
    assert grads_s.keys() == grads_1.keys() and len(grads_1) > 10
    for name, g_1 in grads_1.items():
        scale = max(float(np.abs(g_1).max()), 1e-6)
        np.testing.assert_allclose(
            grads_s[name] / scale, g_1 / scale, err_msg=name,
            rtol=tol["rtol"], atol=tol["atol"])
    head = next(k for k in grads_1 if "linear" in k and grads_1[k].shape == (32, VOCAB))
    assert np.abs(grads_1[head]).max() > 0


@pytest.mark.parametrize("gas", [1, 2], ids=["gas1", "gas2"])
@pytest.mark.parametrize("zero", [False, True], ids=["plain", "zero1"])
def test_train_steps_equal_one_device_and_say_their_layout(devices, zero, gas):
    """TP=2 x DP=2 + SP, with and without ZeRO-1 and gradient accumulation:
    three steps' losses and accuracies are the one-device step's (so every
    gradient reached its parameter whole), and ``train_loss_vocab_shards``
    reads the model axis where the head is sharded and 1 on one device."""
    batch = make_batch(gas=gas)
    one, shards_one = train_losses(make_config(gas=gas, zero=zero), batch)
    par, shards_par = train_losses(
        make_config(mp=2, dp=2, sp=True, gas=gas, zero=zero), batch)
    assert (shards_one, shards_par) == (1, 2)
    np.testing.assert_allclose(par, one, rtol=2e-4, atol=2e-4)
    assert one[-1, 0] < one[0, 0]  # it trains


@pytest.mark.parametrize("mp", [1, 2], ids=["pp2", "pp2xtp2"])
def test_spatial_pipeline_step_with_a_sharded_head_equals_the_gathered(
        devices, monkeypatch, mp):
    """Under stages the head's vocabulary lies over ``(pipe, model)`` and so
    do the logits: ``run_post`` takes head and loss under ``scan`` and
    ``checkpoint`` per micro batch on ``vocab / (pp * mp)`` columns a device.
    Against the same stage-stacked weights with the logits gathered, as the
    head left them until PR 54."""
    from scaling_tpu.models.transformer.layers import lm_head
    from scaling_tpu.parallel.sharding import shard_activation_replicated_h

    batch = make_batch(gas=2)
    config = make_config(mp=mp, pp=2, gas=2)
    sharded, shards = train_losses(config, batch)
    assert shards == 2 * mp
    monkeypatch.setattr(lm_head, "shard_logits", shard_activation_replicated_h)
    gathered, _ = train_losses(config, batch)
    np.testing.assert_allclose(sharded, gathered, rtol=2e-4, atol=2e-4)
    assert gathered[-1, 0] < gathered[0, 0]


def test_a_tied_head_gathers_its_rows_and_says_so(devices):
    """``TransformerLMHeadTied`` still replicates its logits over the model
    axis (no cell trains one under TP): the gauge reads 1 there."""
    batch = make_batch()
    one, _ = train_losses(make_config(weight_tying=True), batch, steps=2)
    par, shards = train_losses(
        make_config(mp=2, dp=2, weight_tying=True), batch, steps=2)
    assert shards == 1
    np.testing.assert_allclose(par, one, rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("use_cache", [True, False], ids=["cached", "uncached"])
def test_greedy_decoding_at_mp2_gives_the_tokens_of_mp1(devices, use_cache):
    """Sampling reads whole rows of logits the head left in two shards."""
    from scaling_tpu.models.transformer.inference import (
        TransformerInferenceModule,
    )

    tokens, logits = [], []
    for mp in (1, 2):
        config = make_config(mp=mp)
        _, module, params = built(config)
        inference = TransformerInferenceModule(config, module, params)
        prompt = [5, 9, 2, 47, 48, 95]
        tokens.append(inference.generate(
            prompt, max_tokens=6, use_cache=use_cache).completion_ids)
        logits.append(np.asarray(inference.logits(prompt), np.float32))
    assert tokens[0] == tokens[1] and len(tokens[0]) == 6
    np.testing.assert_allclose(logits[1], logits[0], rtol=2e-4, atol=2e-4)


def test_the_one_device_step_compiles_to_no_collective(devices):
    """At ``mp`` 1 the mesh's model axis has one device: the head's layout
    names it and the compiled step is the unsharded one (no collective)."""
    config = make_config()
    topology, module, params = built(config)
    optimizer = init_optimizer(config, module, topology)
    step = module.build_train_step(optimizer, loss_function)
    text = step.lower(
        params, optimizer.init_state(params),
        module.shard_batch(make_batch(), stacked=True), jax.random.PRNGKey(0),
    ).compile().as_text()
    for collective in ("all-gather", "all-reduce", "reduce-scatter", "all-to-all"):
        assert collective not in text, collective
