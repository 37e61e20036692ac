"""``reference/parallel_hybrid_decoder.py`` and its view against the program
on seeded weights with Falcon-H1's NON-trivial multipliers: the program is the
reference; each multiplier perturbed in the reference alone moves the logits;
two single-mixer layers in a row are another model; the blocks in which the
reference evaluates the MLP and the head change nothing; the published
parameter counts by ``jax.eval_shape``."""

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

CONFIG = "falcon-h1-34b-serve"
VOCAB, LAYERS = 128, 3
TOPOLOGY = {"model_parallel_size": 1, "pipe_parallel_size": 1,
            "data_parallel_size": 1, "micro_batch_size": 1,
            "gradient_accumulation_steps": 1}
PUBLISHED = cells.load_json(cells.ROOT / "configs" / f"{CONFIG}.json")
# the published constants, at a toy width: 10 q heads over 2 KV heads (group
# 5), 4 Mamba-2 heads in 2 groups, an MLP of 16 blocks' worth of columns
ARCH = {**PUBLISHED["transformer_architecture"],
        "vocab_size": VOCAB, "hidden_size": 40, "num_layers": LAYERS,
        "num_attention_heads": 10, "attention_num_kv_heads": 2,
        "attention_head_dim": 8, "mlp_factor": 3.2,
        "mamba_num_heads": 4, "mamba_head_dim": 8, "ssm_state_size": 16,
        "n_groups": 2, "sequence_length": 128, "precision": "float32"}
SCALARS = ("e", "l", "a_in", "a_out", "k_m", "s_in", "s_out", "g_m", "d_m")


@pytest.fixture(scope="module")
def files():
    return (cells.load_module(cells.ROOT, "reference", "parallel_hybrid_decoder",
                              cells.REFERENCE_CONTRACT),
            cells.load_module(cells.ROOT, "views", "parallel_hybrid_decoder",
                              cells.VIEW_CONTRACT))


@pytest.fixture(scope="module")
def falcon():
    config = TransformerConfig.from_dict({
        "topology": TOPOLOGY, "transformer_architecture": ARCH,
        "data": {}, "logger": {"log_dir": None}})
    module = init_model(config, None)
    params = module.init_params(jax.random.PRNGKey(11))
    # away from the init: norms off one, a conv bias, D off ones
    leaves, treedef = jax.tree.flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(12), len(leaves))
    params = jax.tree.unflatten(treedef, [
        x + (0.2 * jax.random.normal(k, x.shape) if x.ndim == 1 else 0.0)
        for x, k in zip(leaves, keys)])
    return TransformerInferenceModule(config, module, params)


@pytest.fixture(scope="module")
def tokens():
    return jnp.asarray(np.random.default_rng(3).integers(1, VOCAB, size=48))


@pytest.fixture(scope="module")
def sound(files, falcon, tokens):
    ref, view = files
    weights = view.reference_weights(falcon.params, ARCH)
    spec = view.reference_spec(ARCH)
    return weights, spec, np.asarray(ref.forward(weights, tokens, spec))


def test_the_program_is_the_reference_with_the_published_multipliers(falcon, tokens, sound):
    _, spec, want = sound
    mult = PUBLISHED["published"]
    assert (spec["e"], spec["l"], spec["k_m"]) == (
        mult["embedding_multiplier"], mult["lm_head_multiplier"], mult["key_multiplier"])
    assert (spec["a_in"], spec["a_out"], spec["s_in"], spec["s_out"]) == (
        mult["attention_in_multiplier"], mult["attention_out_multiplier"],
        mult["ssm_in_multiplier"], mult["ssm_out_multiplier"])
    assert spec["ssm_m"] == tuple(mult["ssm_multipliers"])
    assert [spec["g_m"], spec["d_m"]] == mult["mlp_multipliers"]
    assert spec["rope_base"] == mult["rope_theta"] == 1e11
    got = np.asarray(falcon.logits(tokens[None])[0])
    np.testing.assert_allclose(got, want, atol=3e-5)
    # the init's work: fresh logits of a size (muP's multipliers over plain
    # Xavier weights would leave them under 1e-3)
    assert 0.2 < want.std() < 2.0


@pytest.mark.parametrize("name", SCALARS + tuple(f"ssm_m[{i}]" for i in range(5)))
def test_each_multiplier_perturbed_in_the_reference_alone_moves_the_logits(
        files, tokens, sound, name):
    """None is dropped "because the result stays inside the tolerance": with
    one constant off by a quarter the reference no longer is the program."""
    ref, _ = files
    weights, spec, want = sound
    if name.startswith("ssm_m"):
        i = int(name[6])
        m = list(spec["ssm_m"])
        m[i] *= 1.25
        off = {**spec, "ssm_m": tuple(m)}
    else:
        off = {**spec, name: spec[name] * 1.25}
    got = np.asarray(ref.forward(weights, tokens, off))
    assert np.abs(got - want).max() > 1e-3, name


def test_two_single_mixer_layers_in_a_row_are_another_model(falcon, tokens):
    """``x + Attn(N'(x + SSM(N(x))))`` is not ``x + SSM(N(x)) + Attn(N(x))``:
    the pattern stack ``mamba, attention, mlp`` a block, given the parallel
    blocks' own leaves (both norms the block's one norm, every multiplier at
    1 on both sides), computes other logits than the parallel stack."""
    arch = {**ARCH, "multipliers": {}}
    parallel = TransformerConfig.from_dict({
        "topology": TOPOLOGY, "transformer_architecture": arch,
        "data": {}, "logger": {"log_dir": None}})
    chained = TransformerConfig.from_dict({
        "topology": TOPOLOGY, "data": {}, "logger": {"log_dir": None},
        "transformer_architecture": {
            **arch, "parallel_ssm": False, "num_layers": 3 * LAYERS,
            "layer_pattern": ["mamba", "attention", "mlp"] * LAYERS}})
    par_module, seq_module = init_model(parallel, None), init_model(chained, None)
    par = par_module.init_params(jax.random.PRNGKey(5))
    seq = seq_module.init_params(jax.random.PRNGKey(5))
    for i in range(LAYERS):
        block = par[f"layer_{i + 1}"]
        seq[f"layer_{3 * i + 1}"] = {"norm": block["input_layernorm"], "mixer": block["ssm"]}
        seq[f"layer_{3 * i + 2}"] = {"norm": block["input_layernorm"],
                                     "mixer": block["attention"]}
        seq[f"layer_{3 * i + 3}"] = {"norm": block["post_attention_layernorm"],
                                     "mixer": block["mlp"]}
    seq["layer_0"] = par["layer_0"]
    for k in (1, 2):   # final norm and head
        seq[f"layer_{3 * LAYERS + k}"] = par[f"layer_{LAYERS + k}"]
    assert jax.tree.structure(seq) == jax.tree.structure(
        seq_module.init_params(jax.random.PRNGKey(5)))
    side = TransformerInferenceModule(parallel, par_module, par).logits(tokens[None])
    row = TransformerInferenceModule(chained, seq_module, seq).logits(tokens[None])
    assert float(jnp.abs(side - row).max()) > 1e-3


def test_the_blocks_of_the_mlp_and_of_the_head_change_nothing(files, tokens, sound,
                                                             monkeypatch):
    """The two departures in how the equations are evaluated: the MLP in 8
    blocks of its columns, the head in 8 blocks of vocabulary columns; one
    block each gives the same logits, and so do the gathered head positions."""
    ref, _ = files
    weights, spec, want = sound
    assert weights["layers"][0]["gate"].shape[1] % ref.MLP_BLOCKS == 0
    assert weights["head"].shape[1] % ref.HEAD_BLOCKS == 0
    monkeypatch.setattr(ref, "MLP_BLOCKS", 1)
    monkeypatch.setattr(ref, "HEAD_BLOCKS", 1)
    ref.layer_forward.clear_cache()
    whole = np.asarray(ref.forward(weights, tokens, spec))
    ref.layer_forward.clear_cache()
    np.testing.assert_allclose(whole, want, atol=2e-6)
    positions = jnp.asarray([47, 3, 20])
    picked = np.asarray(ref.forward(weights, tokens, spec, head_positions=positions))
    np.testing.assert_allclose(picked, whole[np.asarray(positions)], atol=2e-6)


def test_the_fp8_control_moves_the_logits_and_keeps_the_vectors(files, tokens, sound):
    ref, _ = files
    weights, spec, want = sound
    lowered = lower_precision(weights, "fp8")
    assert lowered["layers"][0]["in_proj"].dtype == jnp.float8_e4m3fn
    assert lowered["layers"][0]["A_log"].dtype == jnp.float32
    got = np.asarray(ref.forward(lowered, tokens, spec))
    assert np.abs(got - want).max() > 0.02


def test_the_reference_takes_nothing_of_the_program(files):
    ref, _ = files
    source = open(ref.__file__).read().split('"""', 2)[2]
    assert "scaling_tpu" not in source
    assert "lax.scan(step" in source           # the recurrence, position by position


@pytest.mark.parametrize("depth,count", [(72, 33_642_516_224), (5, 4_824_474_080)])
def test_the_tree_counts_the_published_parameters(depth, count):
    """``jax.eval_shape`` of the program's own tree, by ``num_layers`` alone:
    no allocation."""
    cfg = model.transformer_config(PUBLISHED, {}, num_layers=depth)
    shapes = model.param_shapes(init_model(cfg, None))
    assert model.count_params(shapes) == count
    if depth == 72:
        assert count == PUBLISHED["published"]["parameter_count"]
        return

    def size(tree):
        return sum(math.prod(x.shape) for x in jax.tree.leaves(tree))

    block = shapes["layer_1"]
    assert size(block["attention"]) == 31_457_280
    assert size(block["ssm"]) == 68_351_072
    assert size(block["ssm"]["in_proj"]) == 5120 * 9248 == 47_349_760
    assert size(block["ssm"]["out_proj"]) == 20_971_520
    assert size(block["mlp"]) == 330_301_440
    assert size(block) == 430_120_032
    assert size(shapes["layer_0"]) == size(shapes["layer_7"]) == 261_120 * 5120
    assert size(shapes["layer_6"]) == 5120
