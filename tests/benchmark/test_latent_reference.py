"""``reference/latent_moe_decoder.py`` and its view against the program on
seeded weights at a toy width: the program (expanded where there is no cache)
is the reference; YaRN's numbers at Kimi-K2's sizes; each constant perturbed in
the reference alone moves the logits; four ranks' shares of a 16-expert
sigmoid-routed layer, the shared expert counted once, add up to the uncut
reference's layer; the blocks in which the reference evaluates the softmax
change nothing; the published parameter counts by ``jax.eval_shape``."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import cells, model
from benchmark.control import lower_precision
from scaling_tpu.models.transformer import TransformerConfig
from scaling_tpu.models.transformer.inference import TransformerInferenceModule
from scaling_tpu.models.transformer.model import init_model

CONFIG = "kimi-k2-instruct-serve"
VOCAB = 128
TOPOLOGY = {"model_parallel_size": 1, "pipe_parallel_size": 1,
            "data_parallel_size": 1, "micro_batch_size": 1,
            "gradient_accumulation_steps": 1}
PUBLISHED = cells.load_json(cells.ROOT / "configs" / f"{CONFIG}.json")
# the published equations and constants at a toy width: hidden 256, 4 heads,
# latents of 96 / 64, heads of 32 + 16 / 32, 16 experts, 4 a token, 4 held,
# YaRN at factor 8 over an original context of 32 (its ramp inside the table)
ARCH = {**PUBLISHED["transformer_architecture"],
        "vocab_size": VOCAB, "hidden_size": 256, "num_layers": 6,
        "layer_pattern": ["latent", "mlp", "latent", "moe", "latent", "moe"],
        "num_attention_heads": 4, "q_lora_rank": 96, "kv_lora_rank": 64,
        "qk_nope_head_dim": 32, "qk_rope_head_dim": 16, "v_head_dim": 32,
        "rope_scaling": {**PUBLISHED["transformer_architecture"]["rope_scaling"],
                         "factor": 8, "original_max_position_embeddings": 32},
        "mlp_factor": 2.5, "moe_num_experts": 16, "moe_top_k": 4,
        "moe_expert_width": 64, "moe_shared_expert_width": 64,
        "moe_experts_first": 0, "moe_experts_held": 4,
        "sequence_length": 128, "precision": "float32"}


@pytest.fixture(scope="module")
def files():
    return (cells.load_module(cells.ROOT, "reference", "latent_moe_decoder",
                              cells.REFERENCE_CONTRACT),
            cells.load_module(cells.ROOT, "views", "latent_moe_decoder",
                              cells.VIEW_CONTRACT))


def build(arch, key=11):
    config = TransformerConfig.from_dict({
        "topology": TOPOLOGY, "transformer_architecture": arch,
        "data": {}, "logger": {"log_dir": None}})
    module = init_model(config, None)
    params = module.init_params(jax.random.PRNGKey(key))
    # away from the init: norms off one, a selection bias that changes choices
    leaves, treedef = jax.tree.flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(key + 1), len(leaves))
    params = jax.tree.unflatten(treedef, [
        x + (0.2 * jax.random.normal(k, x.shape) if x.ndim == 1 else 0.0)
        for x, k in zip(leaves, keys)])
    return TransformerInferenceModule(config, module, params)


@pytest.fixture(scope="module")
def kimi():
    return build(ARCH)


@pytest.fixture(scope="module")
def tokens():
    return jnp.asarray(np.random.default_rng(3).integers(1, VOCAB, size=80))


@pytest.fixture(scope="module")
def sound(files, kimi, tokens):
    ref, view = files
    weights = view.reference_weights(kimi.params, ARCH)
    spec = view.reference_spec(ARCH)
    return weights, spec, np.asarray(ref.forward(weights, tokens, spec))


def test_the_program_is_the_reference(kimi, tokens, sound):
    """The program's uncached pass (the expanded form, its own rotary tables
    and norms) against the reference, float32 on both sides: what is left is
    the order of float32 sums (3e-5 is 10 x the largest difference seen)."""
    _, spec, want = sound
    assert spec["yarn"][:2] == (8.0, 32.0) and spec["experts_first"] == 0
    got = np.asarray(kimi.logits(tokens[None])[0])
    np.testing.assert_allclose(got, want, atol=3e-5)
    assert 0.2 < want.std() < 2.0   # the init's work: fresh logits of a size


def test_yarn_at_the_published_numbers(files):
    """Kimi-K2's rope_scaling: the ramp runs from index 19 to 20, frequencies
    0-19 are the base's and 20-31 the base's over 32; the softmax scale is
    192^-0.5 x (0.1 ln 32 + 1)^2 = 0.130861. The program's tables and the
    reference's formula agree."""
    from scaling_tpu.nn import rotary

    ref, view = files
    yarn = view.yarn(PUBLISHED["transformer_architecture"])
    assert yarn == (32.0, 4096.0, 1.0, 1.0, 1.0, 1.0)
    assert ref.yarn_range(64, 50000.0, yarn) == (19, 20)
    base = 50000.0 ** (-np.arange(0, 64, 2) / 64)
    got = np.asarray(ref.inv_freq(64, 50000.0, yarn))
    np.testing.assert_allclose(got[:20], base[:20], rtol=1e-6)
    np.testing.assert_allclose(got[20:], base[20:] / 32, rtol=1e-6)
    assert ref.softmax_scale(128, 64, yarn) == pytest.approx(0.130861, abs=5e-7)
    assert ref.yarn_m(32.0, 1.0) == pytest.approx(1.34657, abs=5e-6)
    scaling = rotary.RopeScalingConfig(**PUBLISHED["transformer_architecture"]["rope_scaling"])
    assert rotary.yarn_correction_range(scaling, 64, 50000.0) == (19, 20)
    np.testing.assert_allclose(rotary.yarn_inv_freq(scaling, 64, 50000.0), got, rtol=1e-6)
    assert 192 ** -0.5 * rotary.yarn_softmax_scale(scaling) == pytest.approx(
        0.130861, abs=5e-7)
    # without rope_scaling: the base frequencies and the plain scale
    np.testing.assert_allclose(np.asarray(ref.inv_freq(64, 50000.0, None)), base, rtol=1e-6)
    assert rotary.yarn_softmax_scale(None) == 1.0


@pytest.mark.parametrize("name,off", [
    ("scale", 2.827 * 1.25), ("gate_eps", 0.5), ("top_k", 3), ("eps", 1e-2),
    ("rope_base", 10000.0), ("yarn", None), ("yarn", (8.0, 32.0, 1.0, 1.0, 1.0, 0.0)),
    ("yarn", (4.0, 32.0, 1.0, 1.0, 1.0, 1.0)), ("experts_first", 4), ("shared", False),
    ("num_dense", 0)])
def test_each_constant_perturbed_in_the_reference_alone_moves_the_logits(
        files, tokens, sound, name, off):
    """None is dropped "because the result stays inside the tolerance"."""
    ref, _ = files
    weights, spec, want = sound
    if name == "num_dense":   # layer 0 taken for a routed one has no router
        with pytest.raises(KeyError):
            ref.forward(weights, tokens, {**spec, name: off})
        return
    got = np.asarray(ref.forward(weights, tokens, {**spec, name: off}))
    assert np.abs(got - want).max() > 1e-3, name


def test_four_ranks_shares_add_up_to_the_uncut_layer(files):
    """The shares test: a 16-expert sigmoid-routed layer held whole against
    the same layer as four ranks of 4 experts each (the router keeps its 16
    outputs and its 4 a token; absent experts' gates are dropped, not
    renormalised): the ranks' routed parts plus the shared expert ONCE are the
    whole layer. In the reference, and in the program's ``serve``."""
    from scaling_tpu.nn.moe import ParallelMoEMLP

    ref, _ = files
    key = jax.random.PRNGKey(0)
    H, F, E, K = 64, 32, 16, 4
    make = lambda first, held: ParallelMoEMLP(
        io_features=H, intermediate_feature_factor=1.0, num_experts=E, top_k=K,
        norm_topk_prob=True, norm_topk_eps=1e-20, glu=True, intermediate=F,
        router="sigmoid_bias", routed_scaling_factor=2.827,
        shared_expert_width=F, experts_first=first, experts_held=held)
    whole = make(0, E)
    params = whole.init(key)
    params["router"]["bias"] = 0.1 * jax.random.normal(jax.random.PRNGKey(1), (E,))
    x = jax.random.normal(jax.random.PRNGKey(2), (1, 24, H))

    def rank_params(first, held):
        p = dict(params)
        for leaf in ("w_in", "w_out", "w_gate"):
            p[leaf] = params[leaf][first:first + held]
        return p

    def as_reference(p):
        return {"router": p["router"]["weight"], "router_bias": p["router"]["bias"],
                "shared_gate": p["shared_gate"], "shared_up": p["shared_in"],
                "shared_down": p["shared_out"]}, {
                "w_gate": p["w_gate"], "w_up": p["w_in"], "w_down": p["w_out"]}

    spec = {"top_k": K, "scale": 2.827, "gate_eps": 1e-20, "experts_first": 0,
            "shared": True}
    with jax.default_matmul_precision("highest"):
        p, experts = as_reference(params)
        want = ref.routed_ffn(x[0], p, experts, spec)
        shared = ref.swiglu(x[0], p["shared_gate"], p["shared_up"], p["shared_down"])
        parts = []
        for first in range(0, E, 4):
            p, experts = as_reference(rank_params(first, 4))
            parts.append(ref.routed_ffn(
                x[0], p, experts, {**spec, "experts_first": first, "shared": False}))
        np.testing.assert_allclose(sum(parts) + shared, want, atol=2e-6)
        # the program: each rank's serve() holds the shared expert, so the
        # four outputs count it four times
        got_whole, _ = whole.serve(params, x)
        np.testing.assert_allclose(got_whole[0], want, atol=2e-5)
        ranks = [make(first, 4).serve(rank_params(first, 4), x)[0][0]
                 for first in range(0, E, 4)]
        np.testing.assert_allclose(sum(ranks) - 3 * shared, want, atol=5e-5)
    assert float(jnp.abs(parts[0]).max()) > 1e-3   # a share is not nothing


def test_the_blocks_of_the_softmax_change_nothing(files, tokens, sound, monkeypatch):
    """The one departure in how the equations are evaluated: the causal
    softmax a block of queries at a time; a block of 16 (several, the last
    one ragged) gives the same logits as one block, and so do the gathered
    head positions."""
    ref, _ = files
    weights, spec, want = sound
    monkeypatch.setattr(ref, "QUERY_BLOCK", 16)
    ref.attention_block.clear_cache()
    split = np.asarray(ref.forward(weights, tokens[:70], spec))
    ref.attention_block.clear_cache()
    np.testing.assert_allclose(split, want[:70], atol=3e-6)
    positions = jnp.asarray([79, 3, 20])
    picked = np.asarray(ref.forward(weights, tokens, spec, head_positions=positions))
    np.testing.assert_allclose(picked, want[np.asarray(positions)], atol=2e-6)


def test_the_fp8_control_moves_the_logits_and_keeps_the_vectors(files, tokens, sound):
    ref, _ = files
    weights, spec, want = sound
    lowered = lower_precision(weights, "fp8")
    assert lowered["layers"][0]["kv_b"].dtype == jnp.float8_e4m3fn
    assert lowered["layers"][1]["w_up"].dtype == jnp.float8_e4m3fn
    assert lowered["layers"][1]["router_bias"].dtype == jnp.float32
    got = np.asarray(ref.forward(lowered, tokens, spec))
    assert np.abs(got - want).max() > 0.02


def test_the_reference_takes_nothing_of_the_program(files):
    ref, _ = files
    source = open(ref.__file__).read().split('"""', 2)[2]
    assert "scaling_tpu" not in source
    assert "W_UK" not in source and "absorb" not in source   # the expanded form only


@pytest.mark.parametrize("blocks,count", [(61, 1_026_408_232_448), (6, 4_173_177_728)])
def test_the_tree_counts_the_published_parameters(blocks, count):
    """``jax.eval_shape`` of the program's own tree: the cut as it is run, and
    the whole model (61 blocks, 384 experts held, the whole vocabulary)."""
    config = PUBLISHED
    if blocks == 61:
        arch = {**PUBLISHED["transformer_architecture"], "num_layers": 122,
                "layer_pattern": ["latent", "mlp"] + ["latent", "moe"] * 60,
                "moe_experts_held": 384, "vocab_size": 163_840}
        config = {**PUBLISHED, "transformer_architecture": arch}
        assert count == PUBLISHED["published"]["parameter_count"]
    shapes = model.param_shapes(init_model(model.transformer_config(config, {}), None))
    assert model.count_params(shapes) == count
    if blocks == 61:
        return

    def size(tree):
        return sum(math.prod(x.shape) for x in jax.tree.leaves(tree))

    assert size(shapes["layer_1"]["mixer"]) == 101_124_096
    assert size(shapes["layer_2"]["mixer"]) == 3 * 7168 * 18_432
    routed = shapes["layer_4"]["mixer"]
    assert routed["w_in"].shape == (12, 7168, 2048)
    assert routed["router"]["weight"].shape == (7168, 384)
    assert size(routed) == 7168 * 384 + 384 + 13 * 44_040_192
    assert size(shapes["layer_1"]) + size(shapes["layer_2"]) == 497_500_160
    assert size(shapes["layer_3"]) + size(shapes["layer_4"]) == 147_931_520 + 12 * 44_040_192
    assert size(shapes["layer_0"]) == size(shapes["layer_14"]) == 20_480 * 7168
    assert size(shapes["layer_13"]) == 7168
