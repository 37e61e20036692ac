"""The program's looped decoder (Ouro's equations: a trunk run ``loop_steps``
times over the same weights, sandwich norms, an exit gate) against the plain
reference ``benchmark/reference/looped_decoder.py`` on seeded random weights,
at a small size on the CPU: the full forward pass, prefill in chunks and
decoding through the paged cache (one line per (step, layer)), the exit
distribution, the parameter count, and three ALTERED programs that each must
fail the comparison."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import cells, model
from benchmark.reference import dense_decoder, looped_decoder as ref
from benchmark.views import dense_decoder as dense_view, looped_decoder as view

ARCH = dict(
    vocab_size=96, hidden_size=64, num_layers=2, num_attention_heads=4,
    attention_num_kv_heads=4, attention_qkv_in_one=False, attention_bias=False,
    mlp_type="swiglu", mlp_factor=2.75, mlp_bias=False, norm_type="rms",
    layernorm={"layernorm_epsilon": 1e-6},
    relative_position_embedding_type="rotary", rotary_embedding_base=1000000,
    sequence_length=128, precision="float32", causal=True, weight_tying=False,
    loop_steps=4, sandwich_norm=True, loop_exit_gate=True)
TOPOLOGY = dict(model_parallel_size=1, pipe_parallel_size=1, data_parallel_size=1,
                micro_batch_size=1, gradient_accumulation_steps=1)
# float32 on both sides, the same mathematics in another order of summation
# (a rolled loop and a paged cache against neither): logits of magnitude ~1
# agree to a few float32 roundings per layer application. 2e-4 would already
# fail a bf16 computation (2**-9 = 2e-3 a rounding) and each altered program
# below, which miss it by two orders of magnitude.
LOGIT_ATOL = 2e-4
ALTERED_BY = 1e-2


def build(topology=TOPOLOGY, **changes):
    from scaling_tpu.models.transformer.inference import TransformerInferenceModule
    from scaling_tpu.models.transformer.model import init_model
    from scaling_tpu.topology import Topology

    arch = {**ARCH, **changes}
    config = model.transformer_config(
        {"transformer_architecture": arch, "topology": topology}, {})
    topo = Topology(config.topology) if topology["model_parallel_size"] > 1 else None
    module = init_model(config, topo)
    params = module.init_params(jax.random.PRNGKey(3))
    # norm weights start at one and the gate's bias at zero (a norm of ones
    # cannot tell where it sits): perturb every leaf
    leaves, treedef = jax.tree.flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(4), len(leaves))
    params = jax.tree.unflatten(treedef, [
        (x.astype(jnp.float32) + 0.1 * jax.random.normal(k, x.shape)).astype(x.dtype)
        for x, k in zip(leaves, keys)])
    if topo is not None:
        params = module.shard_params(params)
    return arch, TransformerInferenceModule(config, module, params)


def reference_logits(arch, params, tokens):
    return np.asarray(ref.forward(view.reference_weights(params, arch),
                                  jnp.asarray(tokens), view.reference_spec(arch)))


def tokens_of(length, seed=0):
    return np.random.default_rng(seed).integers(1, ARCH["vocab_size"], length).astype(np.int32)


def served_logits(inf, tokens, prompt_len, chunk, kernel="pallas"):
    """Prefill ``tokens[:prompt_len]`` in chunks of ``chunk`` (the last one
    ragged, padded to the program's width) and decode the rest one token at
    a time, all through the paged cache, as the engine's programs do: the
    logits of every real position."""
    from scaling_tpu.serve.kvcache import (
        build_layer_views, init_pools, state_from_views)

    block_size, max_blocks = 16, 8
    pools = init_pools(inf, max_blocks + 1, block_size)
    assert pools.kv_lines == inf.architecture.loop_steps * inf.architecture.num_layers
    state = (pools.pool_k, pools.pool_v, pools.scale_k, pools.scale_v)
    table = jnp.arange(1, max_blocks + 1, dtype=jnp.int32)[None]

    @jax.jit
    def step(state, row, ctx, new_len):
        pos = ctx[:, None] + jnp.arange(row.shape[1], dtype=jnp.int32)[None]
        views = build_layer_views(state, table, ctx, new_len)
        logits, new_views = inf._run_layers(
            inf.params, inf._make_batch(row, pos), views, None, paged_kernel=kernel)
        return logits, state_from_views(new_views)

    logits, done = [], 0
    while done < len(tokens):
        width = chunk if done < prompt_len else 1
        n = min(width, prompt_len - done) if done < prompt_len else 1
        row = np.zeros((1, width), np.int32)
        row[0, :n] = tokens[done:done + n]
        out, state = step(state, jnp.asarray(row), jnp.asarray([done], jnp.int32),
                          jnp.asarray([n], jnp.int32))
        logits.append(np.asarray(out[0, :n]))
        done += n
    return np.concatenate(logits)


# ---- (1) the full forward, the loop rolled

@pytest.mark.parametrize("steps", [1, 2, 4])
def test_full_forward_agrees_with_the_reference(steps):
    arch, inf = build(loop_steps=steps, loop_exit_gate=steps > 1)
    tokens = tokens_of(40)
    got = np.asarray(inf.logits(tokens)[0])
    np.testing.assert_allclose(got, reference_logits(arch, inf.params, tokens),
                               atol=LOGIT_ATOL, rtol=0)


def test_one_step_without_norms_or_gate_is_the_dense_decoder():
    """The looped reference at ``steps`` 1, no sandwich norms, no gate is
    ``dense_decoder``'s RMSNorm + SwiGLU block, on the same weights."""
    arch, inf = build(loop_steps=1, sandwich_norm=False, loop_exit_gate=False)
    tokens = tokens_of(40)
    want = np.asarray(dense_decoder.forward(
        dense_view.reference_weights(inf.params, arch), jnp.asarray(tokens),
        dense_view.reference_spec(arch)))
    np.testing.assert_allclose(reference_logits(arch, inf.params, tokens), want,
                               atol=1e-5, rtol=0)


# ---- (2) each altered program fails the comparison that the program keeps

def logits_with_the_final_norm_once(inf, tokens):
    """The program with its final norm left out of the loop and applied
    once, after the last step (where a plain decoder has it)."""
    from scaling_tpu.models.transformer.layers.lm_head import LayerNormWrapper

    *_, norm_i, _, _ = inf._loop_plan()
    real = inf.module.layers[norm_i]

    class Skipped(LayerNormWrapper):
        def __init__(self):
            self.record_embeddings = False

        def __call__(self, params, x, ctx):
            return x

    inf.module.layers[norm_i] = Skipped()
    try:
        ctx = inf._make_ctx()
        pos = jnp.arange(len(tokens))[None]
        return np.asarray(inf._run_looped(
            inf.params, inf._make_batch(jnp.asarray(tokens)[None], pos), ctx,
            pick=lambda h: real(inf.module._layer_params(inf.params, norm_i),
                                {"activations": h}, ctx)["activations"])[0][0])
    finally:
        inf.module.layers[norm_i] = real


def logits_without_the_output_norms(inf, tokens):
    """The program with the norms on the sub-layers' outputs dropped."""
    from scaling_tpu.models.transformer.layers.layer import TransformerLayer

    layers = [l for l in inf.module.layers if isinstance(l, TransformerLayer)]
    held = [l.output_norms for l in layers]
    for l in layers:
        l.output_norms = {}
    try:
        inf._logits_fn = None
        return np.asarray(inf.logits(tokens)[0])
    finally:
        for l, norms in zip(layers, held):
            l.output_norms = norms
        inf._logits_fn = None


def served_with_one_line_a_layer(inf, tokens, monkeypatch):
    """Prefill + paged decode with every step of a layer writing and reading
    the SAME cache line (step 0's blocks)."""
    from scaling_tpu.nn.attention import PagedKVCacheView

    monkeypatch.setattr(PagedKVCacheView, "at_step", lambda self, step, num_blocks: self)
    return served_logits(inf, tokens, 24, 32)


@pytest.mark.parametrize("altered", ["final_norm_once", "no_output_norms",
                                     "one_line_a_layer"])
def test_an_altered_program_fails_the_comparison(altered, monkeypatch):
    arch, inf = build()
    tokens = tokens_of(40)
    want = reference_logits(arch, inf.params, tokens)
    if altered == "final_norm_once":
        got = logits_with_the_final_norm_once(inf, tokens)
    elif altered == "no_output_norms":
        got = logits_without_the_output_norms(inf, tokens)
    else:
        got = served_with_one_line_a_layer(inf, tokens, monkeypatch)
        # the prompt's single chunk is right at step 0 only by luck of order;
        # from the first decoded token on every step reads the last step's K/V
    assert np.abs(got - want).max() > ALTERED_BY
    # and the program as it is keeps it (the same path, unaltered)
    monkeypatch.undo()
    sound = (served_logits(inf, tokens, 24, 32) if altered == "one_line_a_layer"
             else np.asarray(inf.logits(tokens)[0]))
    np.testing.assert_allclose(sound, want, atol=LOGIT_ATOL, rtol=0)


# ---- (3) prefill in chunks + decoding through the paged cache

@pytest.mark.parametrize("chunk", [32, 8])
def test_chunked_prefill_and_paged_decode_agree_with_the_reference(chunk):
    """A prompt of 45 tokens streams in chunks (the last ragged), 11 tokens
    decode one at a time; every position's logits are the reference's full
    forward pass, whatever the chunk: each (step, layer) keeps its own line."""
    arch, inf = build()
    tokens = tokens_of(56, seed=1)
    got = served_logits(inf, tokens, 45, chunk)
    np.testing.assert_allclose(got, reference_logits(arch, inf.params, tokens),
                               atol=LOGIT_ATOL, rtol=0)


def test_the_gather_formulation_reads_the_same_lines():
    arch, inf = build()
    tokens = tokens_of(40, seed=2)
    np.testing.assert_allclose(served_logits(inf, tokens, 30, 16, kernel="xla"),
                               served_logits(inf, tokens, 30, 16), atol=1e-5, rtol=0)


# ---- (4) the exit distribution

def test_exit_distribution_is_the_references_and_sums_to_one():
    arch, inf = build()
    tokens = tokens_of(40)
    got = np.asarray(inf.exit_probabilities(tokens))[:, 0]
    _, want = ref.forward_with_exit(view.reference_weights(inf.params, arch),
                                    jnp.asarray(tokens), view.reference_spec(arch))
    assert got.shape == (4, 40) and got.dtype == np.float32
    np.testing.assert_allclose(got, np.asarray(want), atol=1e-5, rtol=0)
    np.testing.assert_allclose(got.sum(0), 1.0, atol=1e-6)
    assert (got > 0).all() and got.std() > 1e-3  # a gate that says something


# ---- (5) the published count, the counts of operations, mp 2

def test_the_tree_counts_the_published_parameters_at_depth_48():
    from scaling_tpu.models.transformer.model import init_model

    config = cells.load_json(cells.ROOT / "configs" / "ouro-2.6b-serve.json")
    assert config["published"]["num_hidden_layers"] == 48
    shapes = model.param_shapes(init_model(
        model.transformer_config(config, {}, num_layers=48), None))
    assert model.count_params(shapes) == 2_667_974_657
    layer = model.count_params(shapes["layer_1"])
    assert layer == 51_388_416 and set(shapes) == {f"layer_{i}" for i in range(52)}
    # a trained token works the trunk four times and the head once
    arch = {**config["transformer_architecture"], "num_layers": 48}
    held = 2_667_974_657 - 49_152 * 2048
    assert view.train_flops_per_token(arch, shapes, 640) == (
        6.0 * (held + 3 * 48 * layer) + 6.0 * (4 * 48) * 16 * 128 * 640)


def test_a_configuration_that_states_other_equations_is_refused():
    with pytest.raises(SystemExit, match="attention_bias"):
        view.reference_spec({**ARCH, "attention_bias": True})
    with pytest.raises(SystemExit, match="norm_type"):
        view.reference_spec({k: v for k, v in ARCH.items() if k != "norm_type"})
    assert view.reference_spec(ARCH)["steps"] == 4


def test_model_parallel_two_serves_what_one_device_serves():
    """mp 2 needs nothing new: pools sharded over their KV heads, the loop's
    carry with them; the logits through the paged cache are mp 1's."""
    arch, one = build()
    _, two = build(topology={**TOPOLOGY, "model_parallel_size": 2})
    tokens = tokens_of(40, seed=5)
    np.testing.assert_allclose(served_logits(two, tokens, 30, 16),
                               served_logits(one, tokens, 30, 16), atol=1e-4, rtol=0)
